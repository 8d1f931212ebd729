"""The serving program's own spans, device scopes and counters (CPU).

A smoke ``Engine(pcilt=True)`` serves a few requests under the JAX profiler:
its ``serve.*`` host spans must nest as ``docs/serving.md`` lays them out,
count what the engine counts, and carry their args; its telemetry and the
health monitor's counters must add up; and its compiled decode step must
name its parts in the ops' ``op_name`` metadata."""

import dataclasses as dc
import glob
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_smoke_config
from repro.configs.base import PCILTConfig
from repro.launch.serve import Engine, Request

#: the span each program span may sit in (None: the top of the stack)
PARENTS = {
    "serve.tick": {None},
    "serve.admit": {"serve.tick"},
    "serve.refill": {"serve.admit"},
    "serve.step": {"serve.refill", "serve.tick"},
    "serve.step.dispatch": {"serve.step"},
    "serve.step.gate": {"serve.step"},
    "serve.step.sample": {"serve.step"},
    "serve.commit": {"serve.refill", "serve.tick"},
    "serve.monitor": {"serve.tick"},
    "serve.monitor.saturation": {"serve.monitor"},
    "serve.monitor.crc_layer": {"serve.monitor"},
    "serve.monitor.oracle": {"serve.monitor"},
    "serve.monitor.crc_head": {"serve.monitor"},
    "serve.integrity.checksum": {"serve.monitor.crc_layer",
                                 "serve.monitor.crc_head"},
    "serve.deadlines": {"serve.tick"},
    "serve.checkpoint": {None, "serve.tick"},  # the first is before a tick
    "serve.recover": {"serve.tick"},
}


def _cfg():
    cfg = get_smoke_config("mamba2-130m")
    return dc.replace(cfg, pcilt=PCILTConfig(act_bits=4, group=2),
                      dtype=jnp.float32)


def _requests(cfg, n=3, max_new=5, seed=1):
    rng = np.random.default_rng(seed)
    return [Request(i, rng.integers(2, cfg.vocab, size=rng.integers(3, 7)),
                    max_new) for i in range(n)]


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One served run under the profiler: the engine, its stats, the step
    count before the run, and the run's ``serve.*`` spans as
    ``(name, start, end, args)`` in start order (longest first on a tie)."""
    from jax.profiler import ProfileData

    eng = Engine(_cfg(), max_len=64, slots=2, pcilt=True)
    reqs = _requests(eng.cfg)
    steps0 = eng.steps
    out = str(tmp_path_factory.mktemp("trace"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(out, profiler_options=opts)
    try:
        stats = eng.run(reqs)
    finally:
        jax.profiler.stop_trace()
    assert all(r.outcome == "served" for r in reqs)
    (path,) = glob.glob(os.path.join(out, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    spans = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
              dict(ev.stats))
             for plane in ProfileData.from_file(path).planes
             for line in plane.lines for ev in line.events
             if ev.name.startswith("serve.")]
    spans.sort(key=lambda s: (s[1], -s[2]))
    return eng, stats, steps0, spans


def parents(spans):
    """Each span's innermost enclosing span (``None`` at the top)."""
    out, stack = [], []
    for name, a, b, _ in spans:
        while stack and stack[-1][2] <= a:
            stack.pop()
        out.append((name, stack[-1][0] if stack else None))
        stack.append((name, a, b))
    return out


def named(spans, name):
    return [s for s in spans if s[0] == name]


def test_the_spans_nest_as_documented(traced):
    _, _, _, spans = traced
    seen = set()
    for name, parent in parents(spans):
        assert name in PARENTS, name
        assert parent in PARENTS[name], (name, parent)
        seen.add(name)
    # a clean run takes every path but a recovery
    assert seen == set(PARENTS) - {"serve.recover"}


def test_step_spans_count_the_engine_steps(traced):
    eng, stats, steps0, spans = traced
    steps = named(spans, "serve.step")
    assert len(steps) == eng.steps - steps0
    assert [s[3]["step"] for s in steps] == list(range(steps0, eng.steps))
    phases = [s[3]["phase"] for s in steps]
    assert phases.count("prefill") == stats["prefill_ticks"]
    assert phases.count("decode") == stats["decode_ticks"]
    for sub in ("serve.step.dispatch", "serve.step.gate",
                "serve.step.sample"):
        assert len(named(spans, sub)) == len(steps)


def test_span_args(traced):
    eng, stats, _, spans = traced
    pc = eng.pdecode.pcilt
    layer_bytes = pc["tables"][0].nbytes + sum(
        t[0].nbytes for t in pc["proj"]["tables"].values())
    crc = named(spans, "serve.monitor.crc_layer")
    assert len(crc) == stats["decode_ticks"]
    assert [s[3]["layer"] for s in crc] == [
        t % eng.cfg.n_layers for t in range(stats["decode_ticks"])]
    assert {s[3]["bytes"] for s in crc} == {layer_bytes}
    head = pc["head"]
    assert {s[3]["bytes"] for s in named(spans, "serve.monitor.crc_head")} \
        == {head["pool"].nbytes + head["seg_idx"].nbytes}
    assert [s[3]["tick"] for s in named(spans, "serve.monitor")] == \
        list(range(stats["decode_ticks"]))
    # a request's first token comes from its own refill, not a commit
    reqs = eng._requests
    assert sum(s[3]["tokens"] for s in named(spans, "serve.commit")) == \
        sum(len(r.out) - 1 for r in reqs)
    refills = named(spans, "serve.refill")
    assert {s[3]["rid"]: s[3]["prompt_len"] for s in refills} == \
        {r.rid: len(r.prompt) for r in reqs}
    assert {s[3]["slot"] for s in refills} <= set(range(eng.slots))
    assert sum(s[3]["admitted"] for s in named(spans, "serve.admit")) == \
        len(refills) == len(reqs)


def test_a_monitored_tick_copies_no_table_to_the_host(traced):
    """The monitor's checks run on the device: no ``integrity.host_copy``
    span opens, and each layer or head check is one device checksum call
    over the bytes its span names."""
    _, _, _, spans = traced
    assert named(spans, "serve.integrity.host_copy") == []
    checks = sorted(named(spans, "serve.monitor.crc_layer") +
                    named(spans, "serve.monitor.crc_head"),
                    key=lambda s: s[1])
    sums = named(spans, "serve.integrity.checksum")
    assert len(sums) == len(checks)
    for (_, a, b, args), (_, c, d, sargs) in zip(checks, sums):
        assert a <= c and d <= b
        assert sargs["bytes"] == args["bytes"]


def test_telemetry_splits_each_tick(traced):
    _, stats, _, _ = traced
    tel = stats["telemetry"]
    assert len(tel) == stats["decode_ticks"]
    assert sum(e["refill_steps"] for e in tel) == stats["prefill_ticks"]
    for e in tel:
        assert 0.0 <= e["step_s"] <= e["tick_s"]
        assert 0.0 <= e["monitor_s"] <= e["tick_s"]
        assert e["step_s"] + e["monitor_s"] <= e["tick_s"]


def test_monitor_counters_match_the_ticks(traced):
    eng, stats, _, _ = traced
    mon, ticks = eng.monitor, stats["decode_ticks"]
    L = eng.cfg.n_layers
    assert mon.layer_checks == mon.checks == ticks  # every check clean
    assert mon.head_checks == len(range(0, ticks, L))
    assert mon.oracle_probes == ticks // mon.oracle_every
    pd = eng.pdecode
    assert mon.crc_bytes == (ticks * pd.layer_check_bytes() +
                             mon.head_checks * pd.head_check_bytes())
    assert {k: stats[k] for k in mon.counters()} == mon.counters()


def test_the_compiled_step_names_its_parts(traced):
    eng, _, _, _ = traced
    pd = eng.pdecode
    lmask, hmask = eng.monitor.ok_masks()
    text = pd.executor(eng.slots, stats=True).lower(
        eng.params, eng.cache, jnp.asarray(eng.tokens), lmask, hmask,
        pd.bundle_arrays()).compile().as_text()
    assert text.startswith("HloModule jit_pcilt_decode_step")
    block = "blocks/while/body/closed_call/"
    for scope in ("embed", "head", block + "in_proj", block + "conv",
                  block + "ssd", block + "out_proj"):
        assert f'op_name="jit(pcilt_decode_step)/{scope}/' in text, scope
