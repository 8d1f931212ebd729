"""Reduction of a profiler trace to the numbers the benchmark reports.

The harness marks the opening of the measured window in the trace itself
with a host annotation (``OPEN``), which puts the window on the trace's
clock, and wraps each call into the program's layers in a
``bench.<layer>`` annotation, so device time and host spans share that
clock.  :func:`reduce` reads an ``.xplane.pb`` with nothing but JAX and
returns, for the window:

* ``busy_s``: the union of the intervals in which an operation ran on a
  device, averaged over the devices; ``window_s``: the window's length;
* ``ops``: device self time and count by operation name, the numeric
  suffix XLA adds dropped (``fusion.12`` -> ``fusion``), summed over
  devices; an op's self time leaves out the ops nested in it;
* ``idle``: the device's idle time, by the innermost harness span the host
  was in at the middle of each gap (``"(none)"`` outside every span).

A device plane is one named ``/device:...`` that has an ``XLA Ops`` line;
its operations are that line's events.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

OPEN = "bench.window_open"
SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"
_SUFFIX = re.compile(r"(\.(\d+|clone))+$")


def op_name(name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` (a TPU trace names an op by
    its HLO text) or ``fusion.12`` -> ``fusion``."""
    if name.startswith("%"):
        name = name[1:].split(" ", 1)[0]
    return _SUFFIX.sub("", name)


def self_times(ops: List[Tuple[int, int, str]]) -> List[Tuple[str, int]]:
    """``(name, self ns)`` of each op: its duration less the time of the ops
    nested in it on the same line (a ``while`` holds its body's ops)."""
    out: List[list] = []
    stack: List[list] = []
    for a, b, name in sorted(ops, key=lambda e: (e[0], -e[1])):
        while stack and (stack[-1][1] <= a or stack[-1][1] < b):
            stack.pop()  # ended before this op, or does not hold all of it
        rec = [a, b, name, b - a]
        if stack:
            stack[-1][3] -= b - a
        stack.append(rec)
        out.append(rec)
    return [(r[2], r[3]) for r in out]


def latest_xplane(trace_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def _clip(iv, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in iv if b > lo and a < hi]


def _innermost(spans: List[Tuple[int, int, str]], starts: List[int],
               t: int) -> str:
    """The span covering ``t`` that started last (spans nest), from
    ``spans`` sorted by start."""
    i = bisect.bisect_right(starts, t) - 1
    while i >= 0:
        a, b, name = spans[i]
        if b > t:
            return name[len(SPAN_PREFIX):]
        i -= 1
    return "(none)"


def reduce_planes(planes, window_s: float) -> Dict:
    """The reduction of the ``window_s`` seconds that follow the ``OPEN``
    mark, over already-read planes: ``planes`` yields objects
    with ``name`` and ``lines``, each line ``name`` and ``events`` with
    ``name``, ``start_ns`` and ``duration_ns`` (``jax.profiler.ProfileData``
    or a test's stand-in)."""
    marks: Dict[str, int] = {}
    spans: List[Tuple[int, int, str]] = []
    devices: List[List[Tuple[int, int, str]]] = []
    for plane in planes:
        lines = list(plane.lines)
        is_dev = plane.name.startswith("/device:") and \
            any(line.name == OPS_LINE for line in lines)
        ops: List[Tuple[int, int, str]] = []
        for line in lines:
            if is_dev and line.name != OPS_LINE:
                continue
            for ev in line.events:
                a = int(ev.start_ns)
                b = a + int(ev.duration_ns)
                if is_dev:
                    ops.append((a, b, ev.name))
                elif ev.name == OPEN:
                    marks.setdefault(ev.name, a)
                elif ev.name.startswith(SPAN_PREFIX):
                    spans.append((a, b, ev.name))
        if is_dev:
            devices.append(ops)
    if OPEN not in marks or not devices:
        raise ValueError("trace holds no window mark or no device plane")
    lo = marks[OPEN]
    hi = lo + int(window_s * 1e9)
    busy = []
    by_op: Dict[str, List[float]] = {}
    for ops in devices:
        inside = [(max(a, lo), min(b, hi), n) for a, b, n in ops
                  if b > lo and a < hi]
        iv = union([(a, b) for a, b, _ in inside])
        busy.append(sum(b - a for a, b in iv))
        for name, ns in self_times(inside):
            rec = by_op.setdefault(op_name(name), [0, 0.0])
            rec[0] += 1
            rec[1] += ns / 1e9
    idle: Dict[str, float] = {}
    spans.sort()
    starts = [a for a, _, _ in spans]
    first = union(_clip([(a, b) for a, b, _ in devices[0]], lo, hi))
    edges = [lo] + [t for iv in first for t in iv] + [hi]
    for a, b in zip(edges[0::2], edges[1::2]):
        if b > a:
            name = _innermost(spans, starts, (a + b) // 2)
            idle[name] = idle.get(name, 0.0) + (b - a) / 1e9
    return {"window_s": (hi - lo) / 1e9,
            "busy_s": sum(busy) / len(busy) / 1e9,
            "devices": len(devices),
            "ops": {k: (int(c), float(s)) for k, (c, s) in by_op.items()},
            "idle": idle}


def reduce(path: str, window_s: float) -> Dict:
    from jax.profiler import ProfileData

    return reduce_planes(ProfileData.from_file(path).planes, window_s)


def top(d: Dict, n: int = 10, key=lambda v: v) -> List[list]:
    """The ``n`` largest entries of ``{name: value}`` as ``[name, value]``."""
    return [[k, key(v)] for k, v in
            sorted(d.items(), key=lambda kv: -key(kv[1]))[:n]]
