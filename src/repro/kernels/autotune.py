"""Persistent tile autotuner for the PCILT Pallas kernels.

Mirrors the PyTorch-Inductor template lookup-table design: the best kernel
tiling for a given problem shape is discovered *once* by timing a small set of
candidate configurations, then persisted to a JSON lookup table keyed by
``(kernel, B, G, V, O, dtype, backend)``.  Every later dispatch on the same
shape key is a pure dict hit — zero timing runs, zero extra compiles.

Cache format (JSON, one object per shape key)::

    {
      "fused_gemv|B=8,G=512,V=16,O=1024,bits=2,g=2,dtype=float32|backend=cpu": {
        "tiles": {"Bb": 8, "Gb": 512, "Ob": 128, "row_tile": 8},
        "us": 812.4,          # winning candidate's measured microseconds
                              # (null only in hand-written or legacy files)
        "candidates": 4       # how many tilings were timed at record time
      },
      "shared_gemv|B=8,G=512,O=1024,V=16,X=16,bits=2,g=2,...": {...},
      ...
    }

Shape-key dimensions are kernel-specific; the shared-pool kernels
(``shared_gemv`` / ``shared_conv2d``) add ``X``, the pool cardinality (number
of deduped segment tables), because the staged-pool VMEM footprint — and so
the winning tiling — scales with ``X`` rather than ``G``.  The layer-stacked
decode GEMV (``pcilt_fused.pcilt_fused_gemv_stacked_pallas``) records under
``fused_gemv_stacked`` keys shaped
``fused_gemv_stacked|B=...,G=...,L=...,O=...,V=...,bits=...,g=...,dtype=...|backend=...``:
``L`` is the stacked layer count (a ``[L, G, V, O]`` operand with a
different ``L`` is a different HBM-resident problem even though the staged
per-layer ``[1, Gb, V, Ob]`` tile is L-independent), and ``G`` is — as for
every mesh-dispatched kernel — the **local** shard's segment count
(``G/D`` under a model-axis mesh), so stacked tunings recorded at different
device counts occupy different keys; the ``tiles`` entry reuses the plain
``TileConfig`` fields (``Bb``/``Gb``/``Ob``; ``row_tile`` unused, recorded
as 8), and a failed stacked tune records ``us: null`` exactly like every
other kernel.  The fused
depthwise-conv1d kernel records under ``fused_dwconv1d`` keys shaped
``fused_dwconv1d|B=...,C=...,T=...,V=...,bits=...,k=...,dtype=...|backend=...``
(``T`` is the *output* length, ``k`` the tap count); its ``tiles`` entry
reuses the ``TileConfig`` fields as ``Bb`` = time tile ``Tb`` and ``Ob`` =
channel tile ``Cb`` (``Gb``/``row_tile`` unused, recorded as 1/8).  Conv2d
keys tuned under a mesh use the **local** shard's ``G`` (see below); the
``seg_offset`` operand of the fused/shared conv kernels does not enter the
key — it only shifts which patch columns the in-VMEM im2col slices, never
the tiling-relevant shapes.  ``us`` is strict JSON: ``null``, never a bare
``NaN`` token (which ``jq`` and strict parsers reject); ``TileCache`` both
writes and tolerates it.

**Sharded keying policy.**  Mesh execution (``core.lut_layers`` ``mesh=``)
dispatches the kernels from inside ``shard_map``, so the shapes reaching
``shape_key`` are the per-device *local* shard shapes — ``G/D`` segments,
local pool cardinality — and ``PCILTLinear.tune`` likewise tunes on the
local shard.  Two caches tuned at different device counts therefore record
under different keys (``G=512`` at 1 device vs ``G=256`` at 2 vs ``G=128``
at 4 ...) and can never collide; conversely, two deployments whose local
problems are identical deliberately share one entry — the tiling depends
only on the problem the kernel actually sees.  A failed sharded tune records
``us: null`` exactly like an unsharded one.

The cache file lives at ``$REPRO_PCILT_TUNE_CACHE`` (tests point this at a
tmpdir) or ``pcilt_tiles.json`` at the root of the checkout, and is written
atomically (tmp + rename) so concurrent processes can share it.  On save, a
process merges the freshest on-disk state with **only the keys it recorded
itself** — last writer wins per key, and a writer can never clobber another
process's newer entry for a key it merely loaded at startup.

Policy:

* **lookup** is always on: every ``ops.py`` dispatch consults the cache and
  uses the recorded tiles on a hit, falling back to the VMEM-budget heuristic
  (``default_tiles``) on a miss.
* **tuning** (the timing runs on a miss) only happens eagerly — never under a
  ``jit`` trace, where there are no concrete arrays to time — and only when
  requested: pass ``autotune=True`` to the ``ops`` wrappers, or set
  ``REPRO_PCILT_AUTOTUNE=1`` to make it the ambient default.

``TIMING_RUNS`` counts individual timed candidate executions; tests assert it
stays zero on a warm cache (the "second process does no work" contract).
"""

from __future__ import annotations

import dataclasses
import json
import logging
import math
import os
import time
from typing import Callable, Dict, List, Optional, Sequence

log = logging.getLogger("repro.autotune")

__all__ = [
    "TileConfig",
    "TileCache",
    "get_cache",
    "reset_cache",
    "shape_key",
    "lookup",
    "tune",
    "gemv_candidates",
    "stacked_gemv_candidates",
    "paired_gemv_candidates",
    "paired_stacked_gemv_candidates",
    "conv2d_candidates",
    "shared_gemv_candidates",
    "shared_conv2d_candidates",
    "dwconv1d_candidates",
    "autotune_enabled",
    "TIMING_RUNS",
    "SCRATCH_BUDGET",
]

#: incremented once per timed candidate execution (reps included).  Tests use
#: this to assert that a warm cache performs *zero* timing runs.
TIMING_RUNS = 0

#: the tile table kept in the checkout (``src/repro/kernels`` -> repo root)
_DEFAULT_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), "pcilt_tiles.json")


#: quarantined cache files kept per path — repeated corruption (flaky disk,
#: crashing writer) must not grow unbounded ``.corrupt`` litter
QUARANTINE_KEEP = 3


def _quarantine_path(path: str) -> str:
    """Timestamp-suffixed quarantine name: ``<path>.corrupt-<ns>``.  Distinct
    per incident, so a second corruption never overwrites the post-mortem
    bytes of the first (the fixed ``.corrupt`` suffix did exactly that)."""
    return f"{path}.corrupt-{time.time_ns()}"


def _prune_quarantine(path: str, keep: int = QUARANTINE_KEEP) -> None:
    """Drop all but the ``keep`` newest quarantined copies of ``path``.
    Sorted by the name's timestamp suffix, not mtime — quarantine renames
    preserve the corrupt file's original mtime, which says when it was
    *written*, not when it was caught."""
    base = os.path.basename(path) + ".corrupt-"
    d = os.path.dirname(path) or "."
    try:
        names = [n for n in os.listdir(d) if n.startswith(base)
                 and n[len(base):].isdigit()]
    except OSError:
        return
    for stale in sorted(names, key=lambda n: int(n[len(base):]))[:-keep]:
        try:
            os.remove(os.path.join(d, stale))
        except OSError:
            pass


def _read_json(path: str, quarantine: bool = True) -> Dict[str, dict]:
    """Read a cache file, tolerating absence silently but never *silently*
    resetting on corruption: an unreadable/unparseable file is loudly
    warned about and (when ``quarantine``) renamed to a timestamped
    ``<path>.corrupt-<ns>`` so the bytes survive for post-mortem while
    tuning restarts empty.  Only the newest :data:`QUARANTINE_KEEP`
    quarantined copies are retained."""
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        return {}
    except (OSError, ValueError) as e:
        qpath = _quarantine_path(path)
        log.warning(
            "autotune cache %s is unreadable (%s: %s); starting empty — "
            "corrupt file preserved at %s",
            path, type(e).__name__, e, qpath)
        if quarantine:
            try:
                os.replace(path, qpath)
            except OSError:
                pass  # read-only fs etc.: keep serving, just without quarantine
            _prune_quarantine(path)
        return {}


@dataclasses.dataclass(frozen=True)
class TileConfig:
    """One kernel tiling: batch/group/output block plus the conv row strip."""

    Bb: int
    Gb: int
    Ob: int
    row_tile: int = 8

    def to_json(self) -> Dict[str, int]:
        return dataclasses.asdict(self)

    @staticmethod
    def from_json(d: Dict[str, int]) -> "TileConfig":
        cfg = TileConfig(
            Bb=int(d["Bb"]), Gb=int(d["Gb"]), Ob=int(d["Ob"]),
            row_tile=int(d.get("row_tile", 8)),
        )
        if min(cfg.Bb, cfg.Gb, cfg.Ob, cfg.row_tile) < 1:
            raise ValueError(f"non-positive tile in cache entry: {d}")
        return cfg


def autotune_enabled(flag: Optional[bool] = None) -> bool:
    """Resolve an ``autotune=`` argument against the ambient env default."""
    if flag is not None:
        return flag
    return os.environ.get("REPRO_PCILT_AUTOTUNE", "0") not in ("", "0", "false")


def shape_key(kernel: str, *, dtype, backend: str, **dims: int) -> str:
    """Stable string key for one problem shape, e.g.
    ``fused_gemv|B=8,G=512,V=16,O=1024,dtype=float32|backend=cpu``."""
    parts = ",".join(f"{k}={v}" for k, v in sorted(dims.items()))
    return f"{kernel}|{parts},dtype={dtype}|backend={backend}"


class TileCache:
    """The persistent shape-key -> TileConfig lookup table."""

    def __init__(self, path: Optional[str] = None):
        self.path = path or os.environ.get("REPRO_PCILT_TUNE_CACHE") or _DEFAULT_CACHE
        self._entries: Dict[str, dict] = {}
        #: keys recorded by *this process* — the only keys a save may overwrite
        #: on disk (the "last writer wins per key only" contract).
        self._dirty: set = set()
        self._load()

    def _load(self) -> None:
        self._entries = _read_json(self.path)

    def _save(self) -> None:
        d = os.path.dirname(self.path)
        if d:
            os.makedirs(d, exist_ok=True)
        # Start from the freshest on-disk state and overlay only the keys this
        # process actually recorded.  Overlaying the whole in-memory dict would
        # clobber entries a concurrent tuner wrote after our startup load with
        # our stale copies of them.  A file that went corrupt since load is
        # quarantined (warned + renamed *.corrupt) and the merge starts empty.
        on_disk = _read_json(self.path)
        merged = dict(on_disk)
        merged.update({k: self._entries[k] for k in self._dirty
                       if k in self._entries})
        for e in merged.values():
            # Legacy cache files may carry bare-NaN timings (json.load accepts
            # them); sanitize on the way out or allow_nan=False below would
            # crash every later record() — dispatch must never crash on a
            # malformed cache.
            if isinstance(e, dict) and isinstance(e.get("us"), float) \
                    and not math.isfinite(e["us"]):
                e["us"] = None
        self._entries = merged
        tmp = f"{self.path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            # allow_nan=False: a bare NaN token is not valid JSON and breaks
            # strict parsers / jq on the shared cache file.
            json.dump(self._entries, f, indent=1, sort_keys=True,
                      allow_nan=False)
        os.replace(tmp, self.path)

    def lookup(self, key: str) -> Optional[TileConfig]:
        e = self._entries.get(key)
        if not e:
            return None
        try:
            return TileConfig.from_json(e["tiles"])
        except (KeyError, TypeError, ValueError):
            # A malformed hand-edited / cross-version entry must degrade to
            # the heuristic, never crash dispatch.
            return None

    def record(self, key: str, tiles: TileConfig, us: Optional[float],
               candidates: int) -> None:
        if us is not None and not math.isfinite(us):
            us = None  # "untimed fallback" is null in the JSON, never NaN/Inf
        self._entries[key] = {
            "tiles": tiles.to_json(), "us": us, "candidates": candidates,
        }
        self._dirty.add(key)
        self._save()


_CACHE: Optional[TileCache] = None


def get_cache() -> TileCache:
    global _CACHE
    if _CACHE is None:
        _CACHE = TileCache()
    return _CACHE


def reset_cache(path: Optional[str] = None) -> TileCache:
    """Drop the in-memory cache and reload from disk (tests: simulates a fresh
    process sharing the same persisted lookup table)."""
    global _CACHE
    _CACHE = TileCache(path)
    return _CACHE


def lookup(key: str) -> Optional[TileConfig]:
    return get_cache().lookup(key)


def _time_one(fn: Callable[[], None], reps: int, warmup: int) -> float:
    global TIMING_RUNS
    for _ in range(warmup):
        fn()
        TIMING_RUNS += 1
    t0 = time.perf_counter()
    for _ in range(reps):
        fn()
        TIMING_RUNS += 1
    return (time.perf_counter() - t0) / reps * 1e6  # us


def tune(
    key: str,
    candidates: Sequence[TileConfig],
    bench: Callable[[TileConfig], Callable[[], None]],
    reps: int = 2,
    warmup: int = 1,
) -> TileConfig:
    """Miss -> time every candidate, record the winner; hit -> return it.

    ``bench(cfg)`` returns a nullary closure that runs the kernel once (and
    blocks) at tiling ``cfg``.  A candidate that fails to run (e.g. a tiling
    the backend rejects) is skipped; when *no* candidate runs the tune
    raises with the last error — recording an untimed fallback would hide a
    kernel the device cannot compile at all.
    """
    cache = get_cache()
    hit = cache.lookup(key)
    if hit is not None:
        return hit
    best: Optional[TileConfig] = None
    best_us = float("inf")
    tried = 0
    last_err: Optional[Exception] = None
    for cfg in candidates:
        try:
            fn = bench(cfg)
            us = _time_one(fn, reps=reps, warmup=warmup)
        except Exception as e:  # noqa: BLE001 — a rejected tiling
            last_err = e
            continue
        tried += 1
        if us < best_us:
            best, best_us = cfg, us
    if best is None:
        raise RuntimeError(
            f"autotune {key}: none of {len(candidates)} candidate tilings "
            f"ran (last error: {last_err!r})") from last_err
    cache.record(key, best, best_us, tried)
    return best


# ----------------------------------------------------------------------------
# Candidate generators.  Small sets on purpose: each candidate costs a kernel
# compile at tune time, and the heuristic default is always candidate 0 so a
# degenerate tune (every candidate fails) still dispatches correctly.
# ----------------------------------------------------------------------------


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _fit_gb(G: int, V: int, Ob: int, itemsize: int,
            vmem_budget: int = 8 * 2**20) -> int:
    """Largest group-tile whose staged ``[Gb, V, Ob]`` table fits the budget
    and divides G (bf16 tables halve itemsize, doubling the groups staged)."""
    cap = max(1, vmem_budget // max(V * Ob * itemsize, 1))
    Gb = max(1, min(G, cap))
    while G % Gb:
        Gb -= 1
    return Gb


#: Per-grid-step scratch budget for the in-kernel one-hot (and, for the
#: shared kernels, the pool-space counts).  Deliberately looser than the
#: staged-table budget: scratch is transient VPU/VMEM working set, but a
#: tiling whose one-hot alone oversubscribes the chip can never compile —
#: generating it only to have ``tune`` compile-reject it is pure waste.
SCRATCH_BUDGET = 12 * 2**20


def _fit_scratch_gb(G: int, R: int, V: int, onehot_itemsize: int = 4,
                    fixed_bytes: int = 0,
                    budget: float = SCRATCH_BUDGET) -> int:
    """Largest group-tile whose per-grid-step scratch fits ``budget``.

    The analytic mirror of :func:`_fit_gb` for the *activation-side* scratch
    the kernels materialize each grid step: the ``[R, Gb, V]`` one-hot
    (``R`` = rows per step — ``Bb`` for GEMV, ``Hb*Wo`` for conv) in
    ``onehot_itemsize`` bytes, plus ``fixed_bytes`` of Gb-independent scratch
    (the shared kernels' ``[R, V, X]`` counts and staged ``[V, X, Ob]``
    pool).  Replaces try-compile pruning: candidates above the bound used to
    be generated anyway and relied on TPU compile-rejection inside ``tune``
    — every rejection a wasted compile.  Returns the largest ``Gb | G``
    admitted (>= 1, so degenerate budgets still yield a dispatchable tile).
    """
    avail = budget - fixed_bytes
    per_gb = max(R * V * onehot_itemsize, 1)
    if avail < per_gb:
        cap = 1
    elif math.isinf(avail):  # tests pass float('inf') to reproduce the
        cap = G              # old unbounded try-compile sweep
    else:
        cap = max(1, int(avail // per_gb))
    Gb = max(1, min(G, cap))
    while G % Gb:
        Gb -= 1
    return Gb


def gemv_candidates(B: int, G: int, V: int, O: int, itemsize: int = 4,
                    scratch_budget: float = SCRATCH_BUDGET
                    ) -> List[TileConfig]:
    """Tilings for the (fused) GEMV: vary Ob (lane blocks) and Gb (staging).

    Candidate 0 is always the VMEM-budget heuristic (the no-tune fallback).
    Later candidates trade staging footprint for fewer grid steps, up to
    "stage everything" — every ``Gb`` is pre-clamped by the analytic scratch
    bound (:func:`_fit_scratch_gb`: the fused kernel's ``[Bb, Gb*V]``
    one-hot), so no candidate relies on TPU compile-rejection to be pruned.
    On CPU (interpret mode, where per-grid-step overhead dominates) the
    largest admitted staging usually wins.
    """
    Bb = min(128, _round_up(max(B, 1), 8))
    O_full = _round_up(O, 128) if O >= 128 else O
    g_cap = _fit_scratch_gb(G, Bb, V, itemsize, budget=scratch_budget)
    out: List[TileConfig] = []
    seen = set()

    def add(gb: int, ob: int) -> None:
        gb = max(1, min(gb, g_cap))
        while G % gb:
            gb -= 1
        if (gb, ob) not in seen:
            seen.add((gb, ob))
            out.append(TileConfig(Bb=Bb, Gb=gb, Ob=ob))

    add(_fit_gb(G, V, min(128, O_full), itemsize), min(128, O_full))  # heuristic
    add(G, O_full)  # stage everything (scratch-clamped): fewest grid steps
    for Ob in (128, 256, 512, O_full):
        if Ob > O_full:
            continue
        Gb = _fit_gb(G, V, Ob, itemsize)
        add(Gb, Ob)
        add(max(1, Gb // 4), Ob)
    return out[:6]


def _row_tiles(R: int) -> List[int]:
    """R-aware row tiles for the stacked decode sweeps.

    ``R`` is the decode batch — the number of serving slots stepping
    together.  The first tile is the classic padded sublane tile (one grid
    row covers the whole batch); the rest are power-of-two sub-tiles that
    divide it, splitting the batch across grid rows.  Sub-tiles re-stage the
    layer's table tile once per row step but shrink the per-step one-hot
    scratch ``R``-fold — at R=32-64 with wide stagings that trade starts to
    matter, which is exactly what the sweep measures instead of guessing.
    """
    Bb = min(128, _round_up(max(R, 1), 8))
    out = [Bb]
    t = 8
    while t < Bb:
        if Bb % t == 0:
            out.append(t)
        t *= 2
    return out


def stacked_gemv_candidates(B: int, L: int, G: int, V: int, O: int,
                            itemsize: int = 4,
                            scratch_budget: float = SCRATCH_BUDGET
                            ) -> List[TileConfig]:
    """Tilings for the layer-stacked fused GEMV (``fused_gemv_stacked`` keys).

    The kernel stages the *per-layer slice*: its table tile is the scalar-
    prefetch-selected ``[1, Gb, V, Ob]`` block of the ``[L, G, V, O]``
    operand — byte-identical to the unstacked kernel's ``[Gb, V, Ob]`` tile
    at the same ``(Gb, Ob)``, and the in-kernel ``[Bb, Gb*V]`` one-hot
    scratch is unchanged, so both the staged-table budget (:func:`_fit_gb`)
    and the analytic scratch bound (:func:`_fit_scratch_gb`) carry over to
    the per-layer slice verbatim and the dense sweep is reused as the
    **prefix** (candidate 0 stays the no-tune heuristic fallback).  ``L``
    affects the shape key (a different stack is a different HBM-resident
    problem), never the candidate tiling space.

    The decode batch ``R`` (== ``B`` at dispatch: the serving slot count) is
    a tuned axis: after the dense sweep, :func:`_row_tiles` sub-tile
    variants split the batch across grid rows at the two lead stagings —
    each ``Gb`` re-clamped by the scratch bound at the *smaller* row count,
    which can admit stagings the full-batch tile could not.
    """
    del L  # enters the shape key, not the tiling space (per-layer staging)
    base = gemv_candidates(B, G, V, O, itemsize, scratch_budget=scratch_budget)
    out = list(base)
    seen = {(c.Bb, c.Gb, c.Ob) for c in base}
    for bb in _row_tiles(B)[1:]:
        for lead in base[:2]:  # heuristic + stage-everything stagings
            gb = min(lead.Gb,
                     _fit_scratch_gb(G, bb, V, itemsize,
                                     budget=scratch_budget))
            while G % gb:
                gb -= 1
            if (bb, gb, lead.Ob) not in seen:
                seen.add((bb, gb, lead.Ob))
                out.append(TileConfig(Bb=bb, Gb=gb, Ob=lead.Ob))
    return out[:8]


def _fit_paired_gb(G: int, R: int, Ob: int,
                   budget: float = SCRATCH_BUDGET) -> int:
    """Largest segment-tile whose per-grid-step *gather* scratch fits
    ``budget``: the f32 ``[Gb, R, Ob]`` fetched rows plus the ``[R, Gb]``
    pair-index plane.  The paired kernels fetch table rows with
    ``take_along_axis`` — they never build a one-hot — so unlike
    :func:`_fit_scratch_gb` there is **no V factor**: scratch scales with
    the output tile, not the table cardinality, which is exactly why the
    V→V² trade is free on the activation side.  Returns the largest
    ``Gb | G`` admitted (>= 1)."""
    per_gb = max(R * Ob * 4 + R * 4, 1)
    if math.isinf(budget):
        cap = G
    else:
        cap = max(1, int(budget // per_gb))
    Gb = max(1, min(G, cap))
    while G % Gb:
        Gb -= 1
    return Gb


def paired_gemv_candidates(B: int, G: int, V: int, O: int, itemsize: int = 4,
                           scratch_budget: float = SCRATCH_BUDGET
                           ) -> List[TileConfig]:
    """Tilings for the paired-table GEMV (``fused_gemv_paired`` keys).

    ``G`` and ``V`` are **paired-space**: ``G`` counts segment *pairs*
    (``ceil(G_dense / 2)``) and ``V`` is the squared cardinality
    (``V_dense**2``), matching the ``[G, V, O]`` operand the kernel stages.
    Candidate 0 is the staging heuristic (:func:`_fit_gb` keeps the
    ``[Gb, V, Ob]`` table tile under the 8 MiB budget — the no-tune
    fallback must never oversubscribe VMEM), later candidates trade staging
    for fewer grid steps up to the single-step ``(Gb=G, Ob=O)``
    configuration that usually wins on CPU interpret, and every ``Gb`` is
    clamped by the gather scratch bound (:func:`_fit_paired_gb` — no V
    factor, see there).  An exact-``B`` row tile rides along: batch-1
    decode pads to the sublane multiple otherwise, and on interpret the
    un-padded gather is measurably cheaper.
    """
    Bb = min(128, _round_up(max(B, 1), 8))
    B_exact = max(1, min(B, 128))
    O_full = _round_up(O, 128) if O >= 128 else O
    out: List[TileConfig] = []
    seen = set()

    def add(bb: int, gb: int, ob: int) -> None:
        gb = max(1, min(gb, _fit_paired_gb(G, bb, ob, budget=scratch_budget)))
        while G % gb:
            gb -= 1
        if (bb, gb, ob) not in seen:
            seen.add((bb, gb, ob))
            out.append(TileConfig(Bb=bb, Gb=gb, Ob=ob))

    add(Bb, _fit_gb(G, V, min(128, O_full), itemsize), min(128, O_full))
    add(Bb, G, O_full)        # single grid step (scratch-clamped)
    add(B_exact, G, O_full)   # un-padded rows, single step
    for Ob in (128, O_full):
        if Ob > O_full:
            continue
        add(Bb, _fit_gb(G, V, Ob, itemsize), Ob)
    return out[:6]


def paired_stacked_gemv_candidates(B: int, L: int, G: int, V: int, O: int,
                                   itemsize: int = 4,
                                   scratch_budget: float = SCRATCH_BUDGET
                                   ) -> List[TileConfig]:
    """Tilings for the seg-major layer-stacked paired GEMV
    (``fused_gemv_paired_stacked`` keys; ``[G, L, V, O]`` operand).

    Unlike the dense stacked kernel — which scalar-prefetch-selects a
    per-layer ``[1, Gb, V, Ob]`` slice — the seg-major kernel stages the
    **whole layer axis** for its segment tile (``[Gb, L, V, Ob]``: the
    layer index is folded into the flattened value axis so the row-gather's
    segment iota stays constant), so the staged-table budget acquires an
    ``L`` factor: the heuristic runs :func:`_fit_gb` at effective
    cardinality ``L*V``.  The gather scratch bound is L-independent
    (the fetched ``[Gb, Bb, Ob]`` rows and ``[Bb, Gb]`` indices don't
    grow with the stack), so :func:`_fit_paired_gb` carries over verbatim.

    Like the dense stacked sweep, the decode batch ``R`` (== ``B``: the
    serving slot count) is a tuned axis: :func:`_row_tiles` sub-tile
    variants ride along after the classic candidates, shrinking the
    per-step gather scratch ``R``-fold at the cost of re-staging the
    seg-major ``[Gb, L, V, Ob]`` block per row step.
    """
    Bb = min(128, _round_up(max(B, 1), 8))
    B_exact = max(1, min(B, 128))
    O_full = _round_up(O, 128) if O >= 128 else O
    out: List[TileConfig] = []
    seen = set()

    def add(bb: int, gb: int, ob: int) -> None:
        gb = max(1, min(gb, _fit_paired_gb(G, bb, ob, budget=scratch_budget)))
        while G % gb:
            gb -= 1
        if (bb, gb, ob) not in seen:
            seen.add((bb, gb, ob))
            out.append(TileConfig(Bb=bb, Gb=gb, Ob=ob))

    add(Bb, _fit_gb(G, L * V, min(128, O_full), itemsize), min(128, O_full))
    add(Bb, G, O_full)        # single grid step (scratch-clamped)
    add(B_exact, G, O_full)   # un-padded rows, single step
    for Ob in (128, O_full):
        if Ob > O_full:
            continue
        add(Bb, _fit_gb(G, L * V, Ob, itemsize), Ob)
    for bb in _row_tiles(B)[1:]:  # R sub-tiles: split the batch across rows
        add(bb, G, O_full)
        add(bb, _fit_gb(G, L * V, min(128, O_full), itemsize),
            min(128, O_full))
    return out[:8]


def conv2d_candidates(Ho: int, G: int, V: int, O: int, itemsize: int = 4,
                      Wo: int = 128,
                      scratch_budget: float = SCRATCH_BUDGET
                      ) -> List[TileConfig]:
    """Tilings for the (fused) conv2d: vary the row strip, table staging, and
    output blocking.  Same ordering contract as ``gemv_candidates``: the
    heuristic first, then progressively larger stagings — each ``Gb``
    pre-clamped by the analytic scratch bound at that candidate's row count
    ``R = row_tile * Wo`` (``Wo`` defaults conservatively to 128 for callers
    that don't know the output width)."""
    out: List[TileConfig] = []
    seen = set()
    O_full = _round_up(O, 128) if O >= 128 else O
    Ob0 = min(128, O_full)
    Gb = _fit_gb(G, V, Ob0, itemsize)

    def add(hb: int, gb: int, ob: int) -> None:
        hb = max(1, min(hb, Ho))
        while Ho % hb:
            hb -= 1
        gb = max(1, min(gb, _fit_scratch_gb(G, hb * max(Wo, 1), V, itemsize,
                                            budget=scratch_budget)))
        while G % gb:
            gb -= 1
        if (hb, gb, ob) not in seen:
            seen.add((hb, gb, ob))
            out.append(TileConfig(Bb=1, Gb=gb, Ob=ob, row_tile=hb))

    add(8, Gb, Ob0)  # heuristic
    add(Ho, G, O_full)  # stage everything: one grid step per batch element
    for rt in (8, 4, 2, Ho):
        add(rt, Gb, Ob0)
        add(rt, max(1, Gb // 4), Ob0)
    return out[:6]


def _div_down(x: int, cap: int) -> int:
    """Largest divisor of ``x`` that is ``<= cap`` (and ``>= 1``)."""
    d = max(1, min(x, cap))
    while x % d:
        d -= 1
    return d


def _shared_fixed_bytes(R: int, V: int, X: int, Ob: int, itemsize: int) -> int:
    """Gb-independent per-step scratch of the shared kernels: the f32
    ``[R, V, X]`` counts plus the staged (pre-transposed) ``[V, X, Ob]``
    pool tile."""
    return R * V * X * 4 + V * X * Ob * itemsize


def shared_gemv_candidates(B: int, G: int, V: int, O: int, X: int,
                           itemsize: int = 4,
                           scratch_budget: float = SCRATCH_BUDGET
                           ) -> List[TileConfig]:
    """Tilings for the shared-pool GEMV (``kernels/pcilt_shared.py``).

    The staged table operand is the deduped ``[X, V, Ob]`` pool — its VMEM
    footprint is *independent of Gb*, so unlike the dense kernels ``Gb`` only
    trades one-hot scratch / MXU contraction size against grid steps.  The
    dense sweep stays valid (its budget is just conservative), plus "stage
    as many groups as the scratch admits": the analytic bound
    (:func:`_fit_scratch_gb` over the f32 ``[Bb, Gb, V]`` one-hot with the
    ``[Bb, V, X]`` counts + pool tile as fixed bytes) replaces the old
    unconditional ``Gb=G`` candidates that relied on TPU compile-rejection —
    strictly fewer candidates whenever the bound bites, zero wasted tune
    compiles.  On CPU interpret (grid-step overhead dominates) the largest
    admitted staging usually wins, and small recorded problems admit
    ``Gb=G`` unchanged.
    """
    Bb = min(128, _round_up(max(B, 1), 8))

    def clamp(c: TileConfig) -> Optional[TileConfig]:
        # Re-clamp an inherited dense-sweep candidate against the *shared*
        # kernel's per-step scratch: its one-hot is f32 and the counts +
        # staged pool add Gb-independent fixed bytes the dense bound
        # doesn't know about.  A candidate whose fixed footprint alone
        # (counts + staged pool at this Ob) exceeds the budget is dropped —
        # no Gb can save it, and it's exactly the tiling the old sweep
        # wasted a compile-rejection on.
        fixed = _shared_fixed_bytes(c.Bb, V, X, c.Ob, itemsize)
        if fixed + c.Bb * V * 4 > scratch_budget:  # even Gb=1 won't fit
            return None
        gb = min(c.Gb, _fit_scratch_gb(G, c.Bb, V, 4, fixed,
                                       budget=scratch_budget))
        while G % gb:
            gb -= 1
        return dataclasses.replace(c, Gb=gb)

    out: List[TileConfig] = []
    for c in map(clamp, gemv_candidates(B, G, V, O, itemsize,
                                        scratch_budget=scratch_budget)):
        if c is not None and c not in out:
            out.append(c)
    O_full = _round_up(O, 128) if O >= 128 else O
    for ob in (min(128, O_full), O_full):
        cand = clamp(TileConfig(Bb=Bb, Gb=G, Ob=ob))
        if cand is not None and cand not in out:
            out.append(cand)
    if not out:  # degenerate budget: still emit one dispatchable tile
        out.append(TileConfig(Bb=Bb, Gb=1, Ob=min(128, O_full)))
    return out[:7]


def shared_conv2d_candidates(Ho: int, G: int, V: int, O: int, X: int,
                             itemsize: int = 4, Wo: int = 128,
                             scratch_budget: float = SCRATCH_BUDGET
                             ) -> List[TileConfig]:
    """Shared-pool conv2d tilings: the dense sweep plus the largest
    scratch-admitted "stage every group per row strip" configuration (see
    :func:`shared_gemv_candidates`; ``R = row_tile * Wo`` rows per step)."""
    def clamp(c: TileConfig) -> Optional[TileConfig]:
        R = c.row_tile * max(Wo, 1)
        fixed = _shared_fixed_bytes(R, V, X, c.Ob, itemsize)
        if fixed + R * V * 4 > scratch_budget:  # even Gb=1 won't fit
            return None
        gb = min(c.Gb, _fit_scratch_gb(G, R, V, 4, fixed,
                                       budget=scratch_budget))
        while G % gb:
            gb -= 1
        return dataclasses.replace(c, Gb=gb)

    out: List[TileConfig] = []
    for c in map(clamp, conv2d_candidates(Ho, G, V, O, itemsize, Wo=Wo,
                                          scratch_budget=scratch_budget)):
        if c is not None and c not in out:
            out.append(c)
    O_full = _round_up(O, 128) if O >= 128 else O
    Ob0 = min(128, O_full)
    for rt in (_div_down(Ho, 8), Ho):
        cand = clamp(TileConfig(Bb=1, Gb=G, Ob=Ob0, row_tile=rt))
        if cand is not None and cand not in out:
            out.append(cand)
    if not out:  # degenerate budget: still emit one dispatchable tile
        out.append(TileConfig(Bb=1, Gb=1, Ob=Ob0, row_tile=1))
    return out[:7]


def dwconv1d_candidates(T: int, C: int, V: int, k: int, itemsize: int = 4,
                        scratch_budget: float = SCRATCH_BUDGET, B: int = 1
                        ) -> List[TileConfig]:
    """``(Tb, Cb)`` tilings for the fused depthwise conv1d
    (``kernels/pcilt_dwconv1d.py``), encoded as ``TileConfig(Bb=Tb, Ob=Cb)``.

    A grid step covers ``Tb`` time steps of all ``B`` batch rows
    (``R = Tb*B`` rows).  Its scratch is the in-VMEM transposed ``[V, Cb]``
    f32 table plus the staged ``[Cb, V]`` table tile (``Tb``-independent
    fixed bytes) and three f32/int32 ``[R, Cb]`` working planes (codes,
    packed offsets, the select accumulator) — so the analytic bound caps
    the time tile at 3 effective lanes per row (``T`` is the output
    length)."""
    Cb = _div_down(C, 128)
    out: List[TileConfig] = []
    seen = set()

    def add(tb: int, cb: int) -> None:
        fixed = cb * V * 4 + cb * V * itemsize
        cap = _fit_scratch_gb(T, max(B, 1) * cb, 3, 4, fixed,
                              budget=scratch_budget)
        tb = _div_down(T, max(1, min(tb, cap)))
        if (tb, cb) not in seen:
            seen.add((tb, cb))
            out.append(TileConfig(Bb=tb, Gb=1, Ob=cb))

    add(128, Cb)   # heuristic: sublane-friendly time tile
    add(T, Cb)     # stage the whole signal (scratch-clamped)
    add(8, Cb)
    if C > 128:
        add(128, _div_down(C, 256))
    return out[:5]
