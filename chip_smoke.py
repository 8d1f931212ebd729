#!/usr/bin/env python3
"""Chip smoke: serve mamba2-130m at its published widths through the PCILT
``Engine`` on a TPU, and check what comes out.

Run from the root of a checkout::

    python3 chip_smoke.py             # one chip
    python3 chip_smoke.py --chips 4   # segment-sharded tables on 4 chips

One chip: the ``Engine`` that ``python -m repro.launch.serve --arch
mamba2-130m --full --pcilt`` builds (24 layers, d768, vocab 50280, INT2 g2
f32 tables, shared-pool logits head, drift sentinel on, random weights from
a fixed seed) serves 8 seeded requests of 32 new tokens on 4 slots; every
request must be served undegraded with no restart, rollback or health
event.  Then K decode steps run twice through the same compiled step — all
tables healthy, and all layers plus the head demoted to the exact dense
fake-quant oracle — and the greedy tokens must agree.  The compiled step
must hold the three served kernels (stacked GEMV, depthwise conv, shared
head) as ``tpu_custom_call``s, in the counter-carrying form the Engine runs.

``--chips 4``: the same decode with its projection tables segment-sharded
over a 4-device ``model`` axis, against the one-device decode; greedy
tokens must agree.  Nothing else runs.

Earlier lines report set-up and compile seconds, the median steady engine
tick, peak device bytes and table bytes: bring-up observations, not
benchmark numbers.  The last line is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``.  Without a TPU,
or when any check fails, the script exits non-zero and prints no such line.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
ARCH = "mamba2-130m"
SLOTS = 4
REQUESTS = 8
MAX_NEW = 32
K_STEPS = 8
#: the served kernels, by their Pallas names, in the variant the Engine
#: runs (the sentinel is on: the stacked GEMV runs both forms)
KERNELS = ("pcilt_stacked_gemv_sat", "pcilt_dwconv1d_sat", "pcilt_shared_gemv")


class SmokeFailure(Exception):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def log(msg: str) -> None:
    print(msg, flush=True)


def make_engine(cfg, mesh=None):
    from repro.launch.serve import Engine

    return Engine(cfg, max_len=256, slots=SLOTS, pcilt=True, mesh=mesh)


def start_state(eng, seed: int = 0):
    """A fresh zero decode cache and seeded first tokens for ``SLOTS``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.nn.module import materialize

    cache = materialize(eng.model.cache_specs(SLOTS, eng.max_len),
                        jax.random.PRNGKey(1))
    cache = dict(cache, pos=jnp.asarray(0, jnp.int32))
    rng = np.random.default_rng(seed)
    toks = rng.integers(2, eng.cfg.vocab, size=(SLOTS, 1)).astype(np.int32)
    return cache, jnp.asarray(toks)


def greedy_decode(eng, layer_ok, head_ok, steps: int = K_STEPS):
    """``steps`` monitored decode steps through the Engine's own compiled
    step, feeding back each step's greedy tokens; returns ``(tokens [steps,
    SLOTS], logits [steps, SLOTS, vocab])``."""
    import jax.numpy as jnp
    import numpy as np

    cache, toks = start_state(eng)
    vocab = eng.cfg.vocab
    out_t, out_l = [], []
    for _ in range(steps):
        logits, cache, _ = eng.pdecode.step(eng.params, cache, toks,
                                            layer_ok, head_ok,
                                            with_stats=True)
        logits = np.asarray(logits[:, :vocab], np.float64)
        nxt = logits.argmax(-1).astype(np.int32)
        out_t.append(nxt)
        out_l.append(logits)
        toks = jnp.asarray(nxt[:, None])
    return np.stack(out_t), np.stack(out_l)


def serve_phase(eng):
    """Serve the seeded request stream; returns the Engine's stats."""
    from repro.launch.serve import _make_requests

    reqs = _make_requests(eng.cfg, REQUESTS, MAX_NEW, None, seed=0)
    stats = eng.run(reqs)
    outcomes = [r.outcome for r in reqs]
    log(f"served: {sum(o == 'served' for o in outcomes)}/{REQUESTS} "
        f"outcomes={outcomes}")
    log(f"degraded={stats['degraded']} failed={stats['failed']} "
        f"rejected={stats['rejected']} restarts={stats['restarts']} "
        f"rollbacks={stats['rollbacks']} "
        f"health_events={len(stats['health_events'])} "
        f"decode_ticks={stats['decode_ticks']} "
        f"prefill_ticks={stats['prefill_ticks']}")
    check(all(o == "served" for o in outcomes),
          f"not every request was served undegraded: {outcomes}")
    for k in ("degraded", "failed", "rejected", "restarts", "rollbacks"):
        check(stats[k] == 0, f"{k}={stats[k]}, want 0")
    check(not stats["health_events"],
          f"health events: {stats['health_events']}")
    check(all(len(r.out) == MAX_NEW for r in reqs),
          "a request ended with the wrong number of tokens")
    return stats


def oracle_phase(eng):
    """Tables vs the all-demoted dense fake-quant oracle: same greedy
    tokens.  Returns the max |logit difference|."""
    import jax.numpy as jnp
    import numpy as np

    L = eng.cfg.n_layers
    t_tab, l_tab = greedy_decode(eng, jnp.ones((L,), bool),
                                 jnp.asarray(True))
    t_orc, l_orc = greedy_decode(eng, jnp.zeros((L,), bool),
                                 jnp.asarray(False))
    dmax = float(np.max(np.abs(l_tab - l_orc)))
    log(f"oracle: {K_STEPS} steps x {SLOTS} slots, greedy tokens "
        f"{'identical' if np.array_equal(t_tab, t_orc) else 'DIFFER'}, "
        f"max |dlogit| = {dmax!r}")
    check(np.isfinite(l_tab).all() and np.isfinite(l_orc).all(),
          "non-finite logits")
    check(np.array_equal(t_tab, t_orc),
          f"table and oracle greedy tokens differ:\n{t_tab}\n{t_orc}")
    return dmax


def kernel_phase(eng):
    """The compiled monitored step holds each served kernel as a
    ``tpu_custom_call`` (a kernel run in interpret mode, or replaced by a
    reference, leaves none)."""
    import re

    cache, toks = start_state(eng)
    lmask, hmask = eng.monitor.ok_masks()
    text = eng.pdecode.executor(SLOTS, stats=True).lower(
        eng.params, cache, toks, lmask, hmask,
        eng.pdecode.bundle_arrays()).compile().as_text()
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    found = {k: sum(1 for ln in calls if re.search(rf"%{k}[.\s=]", ln))
             for k in KERNELS}
    log(f"kernels: {len(calls)} tpu_custom_call(s); {found}")
    for k, n in found.items():
        check(n > 0, f"no tpu_custom_call for {k}")


def one_chip():
    import jax
    import numpy as np

    from repro.launch.serve import serve_config

    cfg = serve_config(ARCH, full=True, pcilt=True)
    log(f"config: {cfg.name} L={cfg.n_layers} d={cfg.d_model} "
        f"vocab={cfg.vocab} pcilt=INT{cfg.pcilt.act_bits} g{cfg.pcilt.group}"
        f" slots={SLOTS}")
    t0 = time.perf_counter()
    eng = make_engine(cfg)
    jax.block_until_ready(eng.pdecode.bundle_arrays())
    setup_s = time.perf_counter() - t0
    head = eng.pdecode.pcilt["head"]
    head_bytes = int(head["pool"].nbytes + head["seg_idx"].nbytes)
    log(f"setup_s={setup_s!r} table_bytes={eng.pdecode.table_bytes()} "
        f"head_pool_bytes={head_bytes}")

    # compile (plus one run) of the monitored step the Engine serves with
    cache, toks = start_state(eng)
    lmask, hmask = eng.monitor.ok_masks()
    t0 = time.perf_counter()
    jax.block_until_ready(eng.pdecode.step(eng.params, cache, toks, lmask,
                                           hmask, with_stats=True))
    log(f"compile_s={time.perf_counter() - t0!r} (first monitored step, "
        f"compile + one run)")

    stats = serve_phase(eng)
    ticks = [e["tick_s"] for e in stats["telemetry"]][2:]
    log(f"steady_tick_ms_median={1e3 * float(np.median(ticks))!r} "
        f"over {len(ticks)} decode ticks (wall clock, monitor included)")
    oracle_phase(eng)
    kernel_phase(eng)
    mem = jax.devices()[0].memory_stats() or {}
    peak = mem.get("peak_bytes_in_use")
    log(f"peak_bytes_in_use={peak} bytes_limit={mem.get('bytes_limit')}")
    check(peak is not None and peak < 16 * 10**9,
          f"peak device bytes {peak} not under 16 GB")


def device_bytes(arrays):
    """Bytes each device holds of ``arrays``."""
    per = {}
    for a in arrays:
        for sh in a.addressable_shards:
            per[sh.device.id] = per.get(sh.device.id, 0) + sh.data.nbytes
    return per


def four_chips():
    import jax
    import numpy as np

    from repro.launch.mesh import make_decode_mesh
    from repro.launch.serve import serve_config

    check(len(jax.devices()) >= 4,
          f"--chips 4 needs 4 devices, found {len(jax.devices())}")
    cfg = serve_config(ARCH, full=True, pcilt=True)
    L = cfg.n_layers
    ok, hok = np.ones((L,), bool), np.asarray(True)

    t0 = time.perf_counter()
    ref = make_engine(cfg)
    t_ref, l_ref = greedy_decode(ref, ok, hok)
    log(f"one-device decode: {time.perf_counter() - t0!r}s "
        f"bytes per device {device_bytes(ref.pdecode.bundle_arrays())}")
    del ref

    t0 = time.perf_counter()
    eng = make_engine(cfg, mesh=make_decode_mesh(4))
    t_sh, l_sh = greedy_decode(eng, ok, hok)
    log(f"sharded decode (model=4): {time.perf_counter() - t0!r}s "
        f"bytes per device {device_bytes(eng.pdecode.bundle_arrays())}")
    dmax = float(np.max(np.abs(l_ref - l_sh)))
    same = np.array_equal(t_ref, t_sh)
    log(f"sharded vs one-device: greedy tokens "
        f"{'identical' if same else 'DIFFER'}, max |dlogit| = {dmax!r}")
    check(same, f"sharded tokens differ:\n{t_ref}\n{t_sh}")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = p.parse_args(argv)

    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"chip_smoke: no program next to this script ({src}/repro "
              f"missing)", file=sys.stderr)
        return 2
    sys.path.insert(0, src)
    import jax

    try:
        devs = jax.devices()
    except RuntimeError as e:
        print(f"chip_smoke: JAX found no devices: {e}", file=sys.stderr)
        return 2
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX's first device is "
              f"{devs[0].platform!r}); refusing to run", file=sys.stderr)
        return 2

    from repro.launch.compile_cache import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")
    log(f"device: {devs[0].device_kind} x{len(devs)}")
    try:
        four_chips() if args.chips == 4 else one_chip()
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
