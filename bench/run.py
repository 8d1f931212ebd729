#!/usr/bin/env python3
"""Run one benchmark cell once on the chip this process finds.

    python3 bench/run.py --workload m130.batch --seed 7 --seconds 51 --trace 0

The cell, its configuration file and its traffic mix are looked up by name
in ``BENCHMARK.json``; each metric is read by ``bench/metrics/<name>.py``
(or the file named by the part of the name before its first ``.``).

A run: makes the weights from the seed, calibrates the activation scales
with the plain reference (``bench/reference.py``), builds the program's
tables from them (``MambaLM.build_pcilt``, the configuration's own table
format), and serves the mix's requests through the program's ``Engine``
with the tables, the health monitor and the drift sentinel on, on the wall
clock.  Set-up ends, and the window of ``--seconds`` opens, when a closed
loop's first fill is done (its first decode step) or an open loop's first
arrival is due.  The window ends without draining.  Then the served tokens
of a sample of requests are compared with the reference (``correct``).

With ``--trace 1`` the window runs under the profiler and the line carries
the cell's per-layer metrics, the device's busy and window seconds, and a
breakdown of device operations and idle time; otherwise its end-to-end
metrics.  The last line of standard output is one JSON object; the last
lines of standard error give each compared number beside its limit.
Without a TPU, or with fewer chips than the cell asks for, the run exits 2
and prints no result.

``--control bf16_tables`` serves from tables held in bfloat16 (the
program's own lower-precision path): the control that the comparison has to
refuse.  The benchmark's runs never pass it.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from types import SimpleNamespace  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import model, traffic, window  # noqa: E402

TRACE_DIR = os.path.join(ROOT, ".bench_trace")
#: the comparison's sample: at most this many requests, every served token
#: of each; the longest request is always in it, then one request of each
#: slot before a second of any
SAMPLE_MAX = 16
#: the reference's padded shapes: a multiple of this many positions
POS_BUCKET = 256
#: share of the compared served tokens whose logit may lie below the
#: reference's best: sound runs read 0.0030 at most, tables held in
#: bfloat16 0.0125 or more (PERF.md gives the readings)
MAX_MISMATCH_SHARE = 0.007


class WindowClosed(BaseException):
    """Raised from the engine's own calls once the window has ended; a
    ``BaseException`` so the engine's fault handling lets it through."""


def log(msg: str) -> None:
    print(msg, flush=True)


# -- manifest ----------------------------------------------------------------

def manifest(root: str = ROOT) -> Dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def cell(man: Dict, workload: str) -> Dict:
    for c in man["workloads"]:
        if c["name"] == workload:
            return c
    raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")


def config_file(man: Dict, name: str) -> str:
    for c in man["configs"]:
        if c["name"] == name:
            return os.path.join(ROOT, c["file"])
    raise SystemExit(f"no config {name!r} in BENCHMARK.json")


def metrics_for(man: Dict, workload: str, trace: bool) -> List[Dict]:
    """The cell's end-to-end metrics (``trace`` False) or per-layer ones."""
    group = man["per_layer"] if trace else man["end_to_end"]
    return [m for m in group if workload in m.get("workloads", [workload])]


def reader(name: str):
    """``bench/metrics/<name>.py``, else the file of the name's first part."""
    for stem in (name, name.split(".")[0]):
        path = os.path.join(HERE, "metrics", stem + ".py")
        if os.path.exists(path):
            spec = importlib.util.spec_from_file_location(
                "bench_metric_" + stem.replace(".", "_"), path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod
    raise SystemExit(f"no reader for metric {name!r} in bench/metrics")


def devices_or_exit(chips: int):
    import jax

    try:
        devs = jax.devices()
    except RuntimeError as e:
        print(f"bench: JAX found no devices: {e}", file=sys.stderr)
        raise SystemExit(2)
    if devs[0].platform != "tpu":
        print(f"bench: no TPU (JAX's first device is {devs[0].platform!r})",
              file=sys.stderr)
        raise SystemExit(2)
    if len(devs) < chips:
        print(f"bench: the cell asks for {chips} chips, JAX finds "
              f"{len(devs)}", file=sys.stderr)
        raise SystemExit(2)
    return devs


# -- the clock and the instrumented engine -----------------------------------

class Clock:
    """Wall clock whose ``sleep`` stops at the window's end."""

    def __init__(self):
        self.end: Optional[float] = None

    def time(self) -> float:
        return time.time()

    def sleep(self, seconds: float) -> None:
        if self.end is not None:
            seconds = min(seconds, self.end - time.time())
        if seconds > 0:
            time.sleep(seconds)
        if self.end is not None and time.time() >= self.end:
            raise WindowClosed


def timed_engine_class():
    """An ``Engine`` whose calls into each layer are timed from outside:
    steps (prefill or decode), prefill of a slot, the health monitor's tick
    and token commits.  It records when each output token was committed and
    raises :class:`WindowClosed` at the first step after the window."""
    import jax

    from repro.launch.serve import Engine

    class TimedEngine(Engine):
        def setup_timing(self, seconds: float, loop: str, trace_dir=None):
            self.seconds, self.loop = seconds, loop
            self.trace_dir = trace_dir
            self.serving = False
            self.window = None
            self.spans: List[tuple] = []
            self.token_times: Dict[int, List[float]] = {}
            self.slot_of: Dict[int, int] = {}
            self._prefilling = False
            mon = self.monitor
            on_tick = mon.on_tick
            mon.on_tick = lambda *a, **k: self._span("monitor", on_tick,
                                                     *a, **k)

        def open_window(self):
            if self.trace_dir is not None:
                shutil.rmtree(self.trace_dir, ignore_errors=True)
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                jax.profiler.start_trace(self.trace_dir,
                                         profiler_options=opts)
                with jax.profiler.TraceAnnotation("bench.window_open"):
                    pass
            t0 = self.clock.time()
            self.window = (t0, t0 + self.seconds)
            self.clock.end = self.window[1]
            return t0

        def _span(self, name, fn, *a, **k):
            t0 = self.clock.time()
            try:
                if self.trace_dir is not None:
                    with jax.profiler.TraceAnnotation("bench." + name):
                        return fn(*a, **k)
                return fn(*a, **k)
            finally:
                self.spans.append((name, t0, self.clock.time()))

        def _step(self):
            if self.window is None and self.serving and \
                    self.loop == "closed" and not self._prefilling:
                self.open_window()
            if self.window is not None and \
                    self.clock.time() >= self.window[1]:
                raise WindowClosed
            name = "prefill_step" if self._prefilling else "decode_step"
            return self._span(name, super()._step)

        def _prefill_into_slot(self, slot, req):
            self.slot_of[req.rid] = slot
            self._prefilling = True
            try:
                self._span("prefill", super()._prefill_into_slot, slot, req)
            finally:
                self._prefilling = False
            self._record(req, 0)

        def _commit_tokens(self, nxt, skip=None):
            before = [(r, len(r.out)) for s, r in enumerate(self.active)
                      if r is not None and s != skip]
            self._span("commit", super()._commit_tokens, nxt, skip)
            for r, n in before:
                self._record(r, n)

        def _record(self, req, n_before: int):
            ts = self.token_times.setdefault(req.rid, [])
            del ts[n_before:]  # a rollback rewinds what it replays
            ts.extend([self.clock.time()] * (len(req.out) - n_before))

    return TimedEngine


# -- one run -----------------------------------------------------------------

def build(conf: Dict, mix: Dict, seed: int, table_dtype: str):
    """Weights, scales, the program's tables and its engine."""
    import jax
    import jax.numpy as jnp

    from bench import reference
    from repro.models import build_model

    cfg = model.program_config(conf)
    params = model.make_params(conf, seed)
    d = reference.dims(conf)
    scales = jax.jit(lambda p, t: reference.calibrate(p, t, d))(
        params, jnp.asarray(model.calib_tokens(conf, seed)))
    q = conf["pcilt"]
    if q["head"] != "shared":
        raise SystemExit(f"unsupported head {q['head']!r}")
    bundle = build_model(cfg).build_pcilt(
        params, scales["conv"], proj_scales={"in": scales["in"],
                                             "out": scales["out"]},
        proj_path="fused", table_dtype=jnp.dtype(table_dtype),
        head_scale=scales["head"], head_weight_bits=q["head_weight_bits"])
    eng = timed_engine_class()(cfg, slots=mix["slots"], pcilt=True,
                               pcilt_bundle=bundle, clock=Clock())
    # the engine draws its own weights from a fixed key; serve the seed's
    eng.params = params
    eng.monitor.params = params
    return eng, params, scales


def warm_up(eng) -> None:
    """Compile, before the window, every program the window runs: the
    monitored step at this slot count, the engine's per-step host-side
    device ops, slot resets, and the monitor's dense-oracle probe of each
    projection."""
    import jax

    from repro.nn.ssm import PROJ_NAMES

    eng._step()
    for s in range(eng.slots):
        eng._reset_slot(s)
    for name in PROJ_NAMES:
        eng.monitor._oracle_check(0, name)
    jax.block_until_ready(eng.cache)


def compile_counter():
    """Backend compiles and persistent-cache loads, with their times."""
    import jax

    seen: List[tuple] = []

    def listen(event, duration, **_):
        if event in ("/jax/core/compile/backend_compile_duration",
                     "/jax/compilation_cache/cache_retrieval_time_sec"):
            seen.append((event, time.time()))

    jax.monitoring.register_event_duration_secs_listener(listen)
    return seen


def sample(reqs, slot_of: Dict[int, int], seed: int) -> list:
    """At most ``SAMPLE_MAX`` requests to compare: the one with most served
    tokens, then the others in an order drawn from the seed, taking first
    one request from each slot (``slot_of``: the slot each request last
    held) that the sample does not cover yet, so a fault confined to some
    slots meets the comparison."""
    served = [r for r in reqs if r.out]
    if not served:
        return []
    longest = max(served, key=lambda r: (len(r.out), -r.rid))
    rest = [r for r in served if r is not longest]
    order = [rest[i] for i in
             model.rng(seed, model.STREAM_SAMPLE).permutation(len(rest))]
    out, slots = [longest], {slot_of.get(longest.rid)}
    for r in order:
        if slot_of.get(r.rid) not in slots:
            out.append(r)
            slots.add(slot_of.get(r.rid))
    taken = {r.rid for r in out}
    out += [r for r in order if r.rid not in taken]
    return out[:SAMPLE_MAX]


def compare(conf: Dict, params, scales, picked: list) -> Dict:
    """Teacher-forced reference over each picked request's prompt and
    served tokens: the share of served tokens whose logit lies below the
    reference's best, and the widest such gap, in logits and in steps of
    the logit grid."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench import reference

    d = reference.dims(conf)
    T = max(len(r.prompt) + len(r.out) - 1 for r in picked)
    T = -(-T // POS_BUCKET) * POS_BUCKET
    inputs = np.zeros((SAMPLE_MAX, T), np.int32)
    targets = np.full((SAMPLE_MAX, T), -1, np.int32)
    for i, r in enumerate(picked):
        seq = list(r.prompt) + list(r.out[:-1])
        inputs[i, :len(seq)] = seq
        p = len(r.prompt) - 1
        targets[i, p:p + len(r.out)] = r.out
    fn = jax.jit(lambda p, s, x, y: reference.gaps(p, s, x, y, d))
    gap, step = fn(params, scales, jnp.asarray(inputs), jnp.asarray(targets))
    gap, step = np.asarray(gap, np.float64), float(step)
    mask = targets >= 0
    steps = np.rint(gap[mask] / step)
    return {"max_gap_steps": float(steps.max()),
            "max_gap_logits": float(gap[mask].max()),
            "mismatch_share": float((steps > 0).mean()),
            "compared_tokens": int(mask.sum()),
            "compared_requests": len(picked)}


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             table_dtype: Optional[str] = None, man: Optional[Dict] = None,
             conf: Optional[Dict] = None, mix: Optional[Dict] = None) -> Dict:
    """One run of a cell; returns the result line's object."""
    import jax

    from repro.launch.serve import Request

    man = man or manifest()
    c = cell(man, workload)
    conf = conf or model.load_config(config_file(man, c["config"]))
    mix = mix or traffic.load(c["traffic"])
    table_dtype = table_dtype or conf["pcilt"]["table_dtype"]
    compiles = compile_counter()

    eng, params, scales = build(conf, mix, seed, table_dtype)
    eng.setup_timing(seconds, mix["loop"], TRACE_DIR if trace else None)
    warm_up(eng)
    gen = traffic.generate(mix, conf["vocab_size"], seconds,
                           model.rng(seed, model.STREAM_TRAFFIC))
    reqs = [Request(g.rid, g.prompt, g.max_new) for g in gen]
    arrivals: Dict[int, Optional[float]] = {g.rid: None for g in gen}
    eng.serving = True
    try:
        if mix["loop"] == "closed":
            eng.run(reqs)
        else:
            t0 = eng.open_window()
            arrivals = {g.rid: t0 + g.arrival_s for g in gen}
            eng.run_traffic(reqs, [arrivals[g.rid] for g in gen])
        raise RuntimeError("every request of the mix finished before the "
                           "window closed: the mix needs more requests")
    except WindowClosed:
        pass
    w0, w1 = eng.window
    setup_s = w0 - (time.time() - (time.perf_counter() - T_START))
    trace_red = None
    if trace:
        jax.profiler.stop_trace()
        from bench import trace as tr

        path = tr.latest_xplane(TRACE_DIR)
        trace_red = tr.reduce(path, seconds) if path else None
    dev = jax.devices()[0]
    mem = dev.memory_stats() or {}
    in_window = sum(w0 <= t <= w1 for _, t in compiles)

    itemsize = next(iter(
        eng.pdecode.pcilt["proj"]["tables"].values())).dtype.itemsize
    pool_rows = int(eng.pdecode.pcilt["head"]["pool"].shape[0])
    ctx = SimpleNamespace(
        conf=conf, mix=mix, cell=c, seconds=seconds, w0=w0, w1=w1,
        token_times=eng.token_times, arrivals=arrivals, spans=eng.spans,
        setup_s=setup_s, trace=trace_red, device_kind=dev.device_kind,
        rows=mix["slots"], chips=c["chips"], table_itemsize=itemsize)
    outcomes = [r.outcome for r in reqs]
    attempted = sum(o != "queued" for o in outcomes)
    failed = sum(o in ("degraded", "failed", "rejected") or
                 (o == "active" and r.degraded)
                 for o, r in zip(outcomes, reqs))
    health = list(eng.monitor.events)
    log(f"window: {w1 - w0:.3f}s; decode ticks {eng.tick}, steps "
        f"{eng.steps}; restarts {eng.restarts}, rollbacks {eng.rollbacks}, "
        f"health events {len(health)} {health[:3]}")
    done = sum(o in ("served", "degraded") for o in outcomes)
    waiting = sum(o == "queued" and (arrivals[r.rid] or w0) <= w1
                  for o, r in zip(outcomes, reqs))
    log(f"requests: {done} finished, {outcomes.count('active')} in flight, "
        f"{waiting} waiting for a slot; tokens in window "
        f"{window.tokens(eng.token_times, w0, w1)}; prefill steps "
        f"{len(window.spans(eng.spans, 'prefill_step', w0, w1))}, decode "
        f"steps {len(window.spans(eng.spans, 'decode_step', w0, w1))}")
    log(f"compiles or cache loads inside the window: {in_window} "
        f"(whole run: {len(compiles)})")
    from bench import roofline

    rows = mix["slots"]
    staged = sum(roofline.gemv_staged_bytes(conf, n, itemsize)
                 for n in roofline.projections(conf)) * conf["n_layer"]
    least = sum(roofline.gemv_call(conf, n, rows, itemsize)[0]
                for n in roofline.projections(conf)) * conf["n_layer"]
    log(f"bytes per step: GEMV tables staged {staged:.0f}, GEMV least "
        f"{least:.0f}; head staged {roofline.head_staged_bytes(conf, pool_rows, 4):.0f}"
        f", head least {roofline.head_call(conf, rows, 4)[0]:.0f}")

    metrics = {}
    for m in metrics_for(man, workload, trace):
        v = reader(m["name"]).read(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": c["chips"],
              "memory_peak_bytes": int(mem.get("peak_bytes_in_use", 0))}
    out = {"attempted": attempted, "failed": failed, "metrics": metrics,
           "device": device}
    if trace_red is not None:
        from bench import trace as tr

        device.update(busy_s=trace_red["busy_s"],
                      window_s=trace_red["window_s"])
        out["breakdown"] = {
            "device_ops": tr.top(trace_red["ops"], key=lambda v: v[1]),
            "idle_gaps": tr.top(trace_red["idle"])}

    # the comparison runs once the program's state is gone
    picked = sample(reqs, eng.slot_of, seed)
    n_slots = len({eng.slot_of.get(r.rid) for r in picked})
    del eng
    gc.collect()
    checks = compare(conf, params, scales, picked) if picked else None
    if checks:
        checks["compared_slots"] = n_slots
    limits = {"mismatch_share": MAX_MISMATCH_SHARE}
    compared = {k: {"value": checks[k] if checks else None, "limit": v}
                for k, v in limits.items()}
    correct = checks is not None and all(
        x["value"] <= x["limit"] for x in compared.values())
    for k, v in (checks or {}).items():
        if k not in limits:
            log(f"compare: {k} = {v!r}")
    return {"correct": bool(correct), **out, "checks": compared}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--control", choices=("bf16_tables",), default=None,
                   help=argparse.SUPPRESS)
    args = p.parse_args(argv)

    man = manifest()
    c = cell(man, args.workload)
    import jax

    devices_or_exit(int(c["chips"]))
    from repro.launch.compile_cache import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    out = run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                   table_dtype="bfloat16" if args.control else None, man=man)
    for k, v in out["checks"].items():
        print(f"check {k}: {v['value']!r} (limit {v['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
