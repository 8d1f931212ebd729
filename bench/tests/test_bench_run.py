"""A whole run of the harness at a CPU size: the served tokens agree with
the reference, and the comparison refuses the control (tables in bfloat16)
and a timed path broken underneath.  The chip check itself: without a TPU
the command exits non-zero and prints no result."""

import json
import os
import subprocess
import sys
from types import SimpleNamespace

import numpy as np
import pytest

from bench import model, run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DATA = os.path.join(os.path.dirname(__file__), "data")
MIX = {"loop": "closed", "slots": 4, "requests": 400,
       "prompt": {"median": 6, "sigma": 0.5, "min": 2, "max": 12},
       "output": {"median": 40, "sigma": 0.5, "min": 8, "max": 100}}


def tiny_run(seed, seconds=2.0, table_dtype=None):
    conf = model.load_config(os.path.join(DATA, "mamba2-tiny.json"))
    man = dict(run.manifest(), workloads=[
        {"name": "tiny.batch", "config": "mamba2-tiny", "traffic": "batch",
         "chips": 1, "why": "a CPU size"}])
    return run.run_cell("tiny.batch", seed, seconds, False,
                        table_dtype=table_dtype, man=man, conf=conf, mix=MIX)


def share(out):
    return out["checks"]["mismatch_share"]["value"]


def test_served_tokens_agree_with_the_reference():
    out = tiny_run(2**32 + 11)
    assert out["correct"] is True and share(out) == 0.0
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"setup_s", "itl_p95_ms"}


def test_control_bf16_tables_is_refused():
    out = tiny_run(12, table_dtype="bfloat16")
    assert out["correct"] is False
    assert share(out) > run.MAX_MISMATCH_SHARE


def test_a_step_that_returns_its_state_unchanged_is_refused(monkeypatch):
    from repro.core.serving import PCILTMambaDecode

    step = PCILTMambaDecode.step

    def stuck(self, params, cache, tokens, *a, **k):
        logits, _, *rest = step(self, params, cache, tokens, *a, **k)
        return (logits, cache, *rest)

    monkeypatch.setattr(PCILTMambaDecode, "step", stuck)
    out = tiny_run(13)
    assert out["correct"] is False


def test_a_token_altered_where_it_is_produced_is_refused(monkeypatch):
    from repro.launch.serve import Engine

    step = Engine._step
    calls = []

    def altered(self):
        out = step(self)
        calls.append(1)
        if len(calls) % 7 == 0:  # every 7th step commits wrong tokens
            out = (np.asarray(out) + 1) % self.cfg.vocab
        return out

    monkeypatch.setattr(Engine, "_step", altered)
    out = tiny_run(14)
    assert out["correct"] is False


@pytest.mark.parametrize("half", [0, 1])
def test_tokens_of_half_the_slots_altered_is_refused(monkeypatch, half):
    from repro.launch.serve import Engine

    step = Engine._step

    def altered(self):
        out = np.array(step(self))
        n = self.slots // 2
        bad = slice(half * n, (half + 1) * n)
        out[bad] = (out[bad] + 1) % self.cfg.vocab
        return out

    monkeypatch.setattr(Engine, "_step", altered)
    out = tiny_run(15 + half)
    assert out["correct"] is False
    assert share(out) > run.MAX_MISMATCH_SHARE


def test_sample_takes_the_longest_then_one_request_of_each_slot():
    slots = 16
    reqs = [SimpleNamespace(rid=i, out=[1] * (1000 if i == 5 else 20 + i))
            for i in range(64)]
    slot_of = {i: i % slots for i in range(64)}
    picked = run.sample(reqs, slot_of, 2**31 + 9)
    assert len(picked) == run.SAMPLE_MAX
    assert picked[0].rid == 5
    assert len({slot_of[r.rid] for r in picked}) == slots
    again = run.sample(reqs, slot_of, 2**31 + 9)
    assert [r.rid for r in again] == [r.rid for r in picked]
    assert run.sample([SimpleNamespace(rid=0, out=[])], {0: 0}, 1) == []


def test_without_a_tpu_the_command_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    res = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"),
         "--workload", "m130.batch", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=120)
    assert res.returncode != 0
    for line in res.stdout.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)
