"""The program's own spans and device scopes in a traced run's profile.

The program marks its host work with spans named ``serve.<part>``
(``repro.runtime.tracing.span``; their args arrive as event stats) and the
parts of its decode step with ``jax.named_scope`` (``embed``, ``blocks``,
``head``), which reach each device operation's ``op_name``.  This module
reads the ``.xplane.pb`` that a ``--trace 1`` run leaves in
``.bench_trace`` (found with ``bench/trace.py``'s ``latest_xplane``) and
returns, for the window that ``bench/trace.py`` takes (the
``bench.window_open`` mark and the run's seconds):

* ``spans``: the ``serve.*`` spans that started in the window, as
  ``(name, start_ns, end_ns, args)``;
* ``host``: the window's host time by innermost program span (a span's self
  time); outside every program span the innermost ``bench.*`` span, else
  ``"(none)"``;
* ``idle``: the device's idle time split the same way, exactly: each idle
  interval is cut at span edges, so each piece goes to the span the host was
  in for all of it; ``idle_under``: idle time while a program span of each
  name was open anywhere on the stack (the span and its children);
* ``scopes``: device self time by top-level scope, the first part of an
  op's ``op_name`` after the ``jit(...)`` of its module (``"(none)"`` for an
  op outside every scope); ``modules``: device self time by XLA module;
  ``module_scopes``: both at once; ``scope_ops``: by scope and op name
  (with the ``op_name`` of an op in no scope).

A TPU trace keeps an op's ``op_name`` in the ``tf_op`` stat of the op's
event metadata, which ``jax.profiler.ProfileData`` does not expose;
:func:`op_names` reads it from the file's bytes (``xplane.proto``'s
``XSpace``), keyed by the op's HLO text and the ``program_id`` of its
module.  Device self time is as in ``bench/trace.py``: an op's duration
less the ops nested in it, clipped to the window, summed over devices;
idle time is that of the first device.

    python3 -m bench.program_trace .bench_trace 51

prints the whole breakdown.
"""

from __future__ import annotations

import bisect
import functools
import json
import os
import re
import sys
from typing import Dict, Iterator, List, Optional, Tuple

from bench import trace

HERE = os.path.dirname(os.path.abspath(__file__))
#: where ``bench/run.py`` has the profiler write a traced run
TRACE_DIR = os.path.join(os.path.dirname(HERE), ".bench_trace")
PROGRAM = "serve."
MODULES_LINE = "XLA Modules"
NONE = "(none)"
_PROGRAM_ID = re.compile(r"^(.*)\((\d+)\)$")

# -- op_name from the event metadata -----------------------------------------


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    x = shift = 0
    while True:
        b = buf[i]
        i += 1
        x |= (b & 0x7F) << shift
        if b < 0x80:
            return x, i
        shift += 7


def _fields(buf: bytes, lo: int = 0,
            hi: Optional[int] = None) -> Iterator[Tuple[int, object]]:
    """``(field number, value)`` of each field of the message in
    ``buf[lo:hi]``; a length-delimited value is its ``(start, end)``."""
    i = lo
    hi = len(buf) if hi is None else hi
    while i < hi:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 1:
            v, i = buf[i:i + 8], i + 8
        elif wire == 2:
            n, i = _varint(buf, i)
            v, i = (i, i + n), i + n
        elif wire == 5:
            v, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"protobuf wire type {wire} in an xplane")
        yield key >> 3, v


def _text(buf: bytes, span) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def op_names(raw: bytes) -> Dict[str, Dict[Tuple[Optional[int], str], str]]:
    """``{plane name: {(program_id, event name): op_name}}`` from the
    serialized ``XSpace``: each event metadata's ``tf_op`` stat (a string or
    a reference to an interned one), its trailing ``:`` dropped."""
    out: Dict[str, Dict[Tuple[Optional[int], str], str]] = {}
    for f, plane in _fields(raw):
        if f != 1:  # XSpace.planes
            continue
        name, metas, stat_names = "", [], {}
        for g, v in _fields(raw, *plane):
            if g == 2:  # XPlane.name
                name = _text(raw, v)
            elif g == 4:  # XPlane.event_metadata (map entry)
                metas.extend(v2 for k, v2 in _fields(raw, *v) if k == 2)
            elif g == 5:  # XPlane.stat_metadata (map entry)
                for k, v2 in _fields(raw, *v):
                    if k == 2:
                        sid, sname = 0, ""
                        for h, w in _fields(raw, *v2):
                            if h == 1:
                                sid = w
                            elif h == 2:
                                sname = _text(raw, w)
                        stat_names[sid] = sname
        want = {s: n for s, n in stat_names.items()
                if n in ("tf_op", "program_id")}
        if not want:
            continue
        table = out.setdefault(name, {})
        for m in metas:
            ev_name, stats = "", {}
            for h, w in _fields(raw, *m):
                if h == 2:  # XEventMetadata.name
                    ev_name = _text(raw, w)
                elif h == 5:  # XEventMetadata.stats
                    sid, val = None, None
                    for k, x in _fields(raw, *w):
                        if k == 1:
                            sid = x
                        elif k in (3, 4):  # uint64, int64
                            val = x
                        elif k == 5:  # str
                            val = _text(raw, x)
                        elif k == 7:  # ref to an interned string
                            val = stat_names.get(x)
                    if sid in want:
                        stats[want[sid]] = val
            if isinstance(stats.get("tf_op"), str):
                pid = stats.get("program_id")
                table[(pid if isinstance(pid, int) else None, ev_name)] = \
                    stats["tf_op"].rstrip(":")
    return out


def scope(op_name: Optional[str]) -> str:
    """The top-level scope in an ``op_name``:
    ``jit(pcilt_decode_step)/blocks/while/body/in_proj/dot`` -> ``blocks``;
    an op with no scope above it -> ``"(none)"``."""
    parts = [p for p in (op_name or "").split("/") if p]
    while parts and parts[0].startswith("jit("):
        parts.pop(0)
    return parts[0] if len(parts) > 1 else NONE


# -- the reduction -------------------------------------------------------------


def _segments(spans: List[Tuple[int, int, str]], lo: int,
              hi: int) -> List[Tuple[int, int, Tuple[str, ...]]]:
    """``[lo, hi]`` cut at every span edge into ``(a, b, open)`` pieces,
    ``open`` the names of the spans covering the piece, outermost first
    (spans of one thread nest; of overlapping ones, the later started is
    the inner)."""
    edges = []
    for k, (a, b, _) in enumerate(spans):
        a, b = max(a, lo), min(b, hi)
        if b > a:
            edges.append((a, 1, -b, k))  # at one instant: ends first, then
            edges.append((b, 0, 0, k))   # the longer of two starts
    edges.sort()
    out, stack, t = [], [], lo
    for at, starts, _, k in edges:
        if at > t:
            out.append((t, at, tuple(spans[j][2] for j in stack)))
            t = at
        if starts:
            stack.append(k)
        else:
            stack.remove(k)
    if t < hi:
        out.append((t, hi, ()))
    return out


def _label(names: Tuple[str, ...]) -> str:
    for n in reversed(names):
        if n.startswith(PROGRAM):
            return n
    return names[-1] if names else NONE


def reduce_planes(planes, names: Dict, window_s: float) -> Dict:
    """The reduction over already-read ``planes`` (``ProfileData`` planes
    or a test's stand-in) and the ``op_names`` table of the same trace."""
    mark: Optional[int] = None
    spans: List[Tuple[int, int, str]] = []
    args: List[Dict] = []
    devices = []
    for plane in planes:
        lines = list(plane.lines)
        if plane.name.startswith("/device:") and \
                any(line.name == trace.OPS_LINE for line in lines):
            ops, mods = [], []
            for line in lines:
                if line.name in (trace.OPS_LINE, MODULES_LINE):
                    rows = ops if line.name == trace.OPS_LINE else mods
                    rows.extend((int(ev.start_ns),
                                 int(ev.start_ns) + int(ev.duration_ns),
                                 ev.name) for ev in line.events)
            devices.append((plane.name, ops, sorted(mods)))
            continue
        for line in lines:
            for ev in line.events:
                if ev.name == trace.OPEN:
                    if mark is None:
                        mark = int(ev.start_ns)
                elif ev.name.startswith((PROGRAM, trace.SPAN_PREFIX)):
                    a = int(ev.start_ns)
                    spans.append((a, a + int(ev.duration_ns), ev.name))
                    args.append(dict(ev.stats)
                                if ev.name.startswith(PROGRAM) else {})
    if mark is None or not devices:
        raise ValueError("trace holds no window mark or no device plane")
    lo, hi = mark, mark + int(window_s * 1e9)

    segs = _segments(spans, lo, hi)
    host: Dict[str, float] = {}
    for a, b, open_ in segs:
        lab = _label(open_)
        host[lab] = host.get(lab, 0.0) + (b - a) / 1e9
    busy = trace.union([(max(a, lo), min(b, hi)) for a, b, _ in
                        devices[0][1] if b > lo and a < hi])
    edges = [lo] + [t for iv in busy for t in iv] + [hi]
    idle_iv = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
    idle: Dict[str, float] = {}
    under: Dict[str, float] = {}
    j = 0
    for a, b, open_ in segs:  # both lists sorted and disjoint
        while j < len(idle_iv) and idle_iv[j][1] <= a:
            j += 1
        k = j
        while k < len(idle_iv) and idle_iv[k][0] < b:
            ns = min(b, idle_iv[k][1]) - max(a, idle_iv[k][0])
            lab = _label(open_)
            idle[lab] = idle.get(lab, 0.0) + ns / 1e9
            for n in set(open_):
                if n.startswith(PROGRAM):
                    under[n] = under.get(n, 0.0) + ns / 1e9
            k += 1

    module_scopes: Dict[str, Dict[str, float]] = {}
    scope_ops: Dict[str, Dict[str, float]] = {}
    for plane_name, ops, mods in devices:
        table = names.get(plane_name, {})
        by_name: Dict[str, Optional[str]] = {}
        for (_, n), o in table.items():
            by_name[n] = o if by_name.get(n, o) == o else None
        starts = [m[0] for m in mods]
        inside = [(max(a, lo), min(b, hi), k) for k, (a, b, _) in
                  enumerate(ops) if b > lo and a < hi]
        for k, ns in trace.self_times(inside):
            a, _, op = ops[k]
            i = bisect.bisect_right(starts, a) - 1
            module, pid = NONE, None
            if i >= 0 and mods[i][1] > a:
                m = _PROGRAM_ID.match(mods[i][2])
                module = m.group(1) if m else mods[i][2]
                pid = int(m.group(2)) if m else None
            name = table.get((pid, op), by_name.get(op))
            per = module_scopes.setdefault(module, {})
            sc = scope(name)
            per[sc] = per.get(sc, 0.0) + ns / 1e9
            # an op outside every scope keeps its op_name in the key (an
            # eager op's is its module's path), so that share stays legible
            key = trace.op_name(op) if sc != NONE or not name else \
                f"{trace.op_name(op)} ({name})"
            by_op = scope_ops.setdefault(sc, {})
            by_op[key] = by_op.get(key, 0.0) + ns / 1e9
    scopes: Dict[str, float] = {}
    for per in module_scopes.values():
        for sc, s in per.items():
            scopes[sc] = scopes.get(sc, 0.0) + s
    window = [(n, a, b, x) for (a, b, n), x in zip(spans, args)
              if n.startswith(PROGRAM) and lo <= a <= hi]
    return {"window_s": (hi - lo) / 1e9, "spans": window, "host": host,
            "idle": idle, "idle_under": under, "scopes": scopes,
            "modules": {m: sum(p.values()) for m, p in module_scopes.items()},
            "module_scopes": module_scopes, "scope_ops": scope_ops}


@functools.lru_cache(maxsize=2)
def _reduce(path: str, mtime: float, window_s: float) -> Dict:
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        raw = f.read()
    return reduce_planes(ProfileData.from_serialized_xspace(raw).planes,
                         op_names(raw), window_s)


def reduce(path: str, window_s: float) -> Dict:
    """The reduction of the trace at ``path``, read once per process."""
    return _reduce(path, os.path.getmtime(path), float(window_s))


def for_ctx(ctx) -> Optional[Dict]:
    """The reduction of a run's trace; ``None`` for an untraced run or a
    trace without a window."""
    if ctx.trace is None:
        return None
    path = trace.latest_xplane(TRACE_DIR)
    if path is None:
        return None
    try:
        return reduce(path, ctx.seconds)
    except ValueError:
        return None


# -- what the metrics read -----------------------------------------------------


def count(red: Dict, part: str) -> int:
    """Spans ``serve.<part>`` started in the window (``step``: prefill and
    decode steps; ``monitor``: decode ticks)."""
    return sum(s[0] == PROGRAM + part for s in red["spans"])


def span_seconds(red: Dict, *parts: str) -> float:
    """Total host seconds of the ``serve.<part>`` spans started in the
    window."""
    names = {PROGRAM + p for p in parts}
    return sum(b - a for n, a, b, _ in red["spans"] if n in names) / 1e9


def scope_ms_per_step(red: Optional[Dict], name: str) -> Optional[float]:
    """Device self ms under the top-level scope ``name`` per ``serve.step``
    span started in the window; ``None`` where either is missing."""
    steps = count(red, "step") if red else 0
    if not steps or name not in red["scopes"]:
        return None
    return 1e3 * red["scopes"][name] / steps


def summary(red: Dict) -> Dict:
    """Count and total host ms of the window's spans of each name."""
    by: Dict[str, List[float]] = {}
    for n, a, b, _ in red["spans"]:
        rec = by.setdefault(n, [0, 0.0])
        rec[0] += 1
        rec[1] += (b - a) / 1e6
    return {n: {"count": c, "ms": ms} for n, (c, ms) in sorted(by.items())}


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 2:
        print(__doc__.strip().splitlines()[0], file=sys.stderr)
        print("usage: python3 -m bench.program_trace <trace dir> <seconds>",
              file=sys.stderr)
        return 2
    path = trace.latest_xplane(argv[0])
    if path is None:
        print(f"no .xplane.pb under {argv[0]}", file=sys.stderr)
        return 1
    red = reduce(path, float(argv[1]))
    out = {k: v for k, v in red.items() if k != "spans"}
    out["span_totals"] = summary(red)
    out["steps"] = count(red, "step")
    print(json.dumps(out, indent=1, sort_keys=True))
    return 0


if __name__ == "__main__":
    sys.exit(main())
