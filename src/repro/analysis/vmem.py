"""Static VMEM / grid verifier for the PCILT kernel zoo.

Proves — by abstract tracing only, never executing a kernel — that the
analytic scratch bound the candidate generators apply
(``kernels.autotune._fit_scratch_gb`` / ``SCRATCH_BUDGET``) is sound, and
that every BlockSpec ``index_map`` tiles its operand correctly over the full
grid.  For each kernel family the verifier:

1. enumerates the family's *actual* candidate generator over a recorded
   shape sweep (the same generator ``ops.py`` dispatches through — nothing
   is re-modeled on the analysis side);
2. recomputes each emitted candidate's modeled per-grid-step scratch (the
   one-hot the kernel materializes, plus the family's Gb-independent fixed
   bytes) and proves it respects the budget (**VMEM001** — this is exactly
   the clamp ``_fit_scratch_gb`` promises, so a generator change that stops
   applying it fires here);
3. traces the real jitted ``*_pallas`` wrapper with ``jax.make_jaxpr`` on
   ``ShapeDtypeStruct`` inputs — a trace, not a run — and from the recorded
   ``pallas_call`` equation:

   * evaluates every BlockSpec ``index_map`` jaxpr over the **full grid**
     (vectorized — the maps are elementwise in the grid indices) and checks
     each emitted block index stays in-bounds (**VMEM002**) and that
     grid-dependent axes tile their operand without gaps (**VMEM003**);
     scalar-prefetch-driven axes (the stacked decode kernel's layer axis)
     are exempt from coverage but bounds-checked for *every* prefetch value
     after ``discharge_state`` rewrites the ref-typed map into a pure one;
   * searches the kernel jaxpr (sub-jaxprs included) for an intermediate
     whose shape matches the modeled one-hot — the witness that the
     analytic model still describes the kernel body (**VMEM004**: model
     drift);

4. checks the *untuned fallback* (candidate 0 — what a cache miss
   dispatches) fits staged blocks + modeled scratch in the full per-core
   VMEM (**VMEM005**), and flags tuned candidates that exceed it and so
   rely on TPU compile-rejection inside ``tune`` (**VMEM006**, warning —
   by design ``tune`` skips rejected candidates, but they cost a compile).

``verify_all(sweep=..., scratch_budget=...)`` is the entry point;
``scratch_budget`` overrides the generators' budget so tests can prove the
verifier *rejects* once the budget shrinks below the smallest admissible
tile (soundness: the pass is not vacuously green).
"""

from __future__ import annotations

import dataclasses
import itertools
import math
import os
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from repro.analysis import Finding

__all__ = ["RULES", "verify_all", "FAMILIES", "TOTAL_VMEM_BUDGET"]

RULES: Dict[str, str] = {
    "VMEM001": "emitted candidate's modeled per-grid-step scratch exceeds "
               "SCRATCH_BUDGET (the generator's analytic clamp was not "
               "applied)",
    "VMEM002": "BlockSpec index_map emits an out-of-bounds block index "
               "somewhere in the grid",
    "VMEM003": "grid walk leaves gaps: a grid-dependent block axis does not "
               "cover its operand",
    "VMEM004": "scratch model drift: traced kernel body lacks the modeled "
               "one-hot intermediate",
    "VMEM005": "untuned fallback candidate does not fit staged blocks + "
               "scratch in per-core VMEM",
    "VMEM006": "tuned candidate exceeds per-core VMEM and relies on "
               "compile-rejection at tune time",
}

_MiB = 2 ** 20
#: full per-core VMEM the fallback (cache-miss) candidate must fit into —
#: staged operand blocks plus modeled scratch.  Tuned candidates may exceed
#: it (``tune`` skips compile-rejected tilings), the fallback must not: a
#: cache miss dispatches it unconditionally.
TOTAL_VMEM_BUDGET = 16 * _MiB

#: full-grid index-map enumeration cap; sweeps are sized to stay below it
#: (above it the verifier samples bounds and skips the coverage proof).
_MAX_GRID_POINTS = 4096


# ----------------------------------------------------------------------------
# Family specs: tie each candidate generator to its kernel's scratch model,
# its jitted wrapper (for tracing), and a recorded shape sweep.
# ----------------------------------------------------------------------------


@dataclasses.dataclass
class Family:
    name: str                 # autotune kernel name (shape-key prefix)
    path: str                 # kernel source file findings anchor to
    sweep: Dict[str, List[dict]]          # {"quick": [...], "full": [...]}
    candidates: Callable      # (shape, budget) -> List[TileConfig]
    scratch_bytes: Callable   # (shape, cfg) -> int (the generator's model)
    witness: Callable         # (shape, eff) -> acceptable one-hot shapes
    trace: Callable           # (shape, cfg) -> (jaxpr, eff_cfg)


def _kpath(fname: str) -> str:
    return os.path.join("src", "repro", "kernels", fname)


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _padded_O(O: int, Ob: int) -> int:
    return _round_up(O, Ob) if O >= 128 else O


def _build_families() -> List[Family]:
    import jax
    import jax.numpy as jnp

    from repro.kernels import autotune as atn
    from repro.kernels import ops
    from repro.kernels.pcilt_conv2d import pcilt_conv2d_pallas
    from repro.kernels.pcilt_dwconv1d import pcilt_fused_dwconv1d_pallas
    from repro.kernels.pcilt_fused import (
        pack_chunk, pcilt_fused_conv2d_pallas, pcilt_fused_gemv_pallas,
        pcilt_fused_gemv_paired_pallas,
        pcilt_fused_gemv_paired_stacked_pallas, pcilt_fused_gemv_plan_pallas,
        pcilt_fused_gemv_stacked_pallas)
    from repro.kernels.pcilt_gemv import pcilt_gemv_pallas
    from repro.kernels.pcilt_shared import (pcilt_shared_conv2d_pallas,
                                            pcilt_shared_gemv_pallas)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype)

    def tdt(s):
        return jnp.bfloat16 if s.get("itemsize", 4) == 2 else jnp.float32

    def mk(fn, *args, **static_kw):
        return jax.make_jaxpr(lambda *a: fn(*a, **static_kw))(*args)

    # -- gemv (host-packed + fused + stacked share the generator) ----------

    GEMV_SWEEP = {
        "quick": [dict(B=8, G=16, V=16, O=256, group=2, bits=2, itemsize=4),
                  dict(B=8, G=16, V=16, O=256, group=2, bits=2, itemsize=2)],
        "full": [dict(B=8, G=16, V=16, O=256, group=2, bits=2, itemsize=4),
                 dict(B=8, G=16, V=16, O=256, group=2, bits=2, itemsize=2),
                 dict(B=64, G=64, V=16, O=512, group=2, bits=2, itemsize=4),
                 dict(B=1, G=128, V=16, O=1024, group=4, bits=4, itemsize=2)],
    }

    def gemv_cands(s, budget):
        return atn.gemv_candidates(s["B"], s["G"], s["V"], s["O"],
                                   s["itemsize"], scratch_budget=budget)

    def gemv_scratch(s, c):
        # the fused [Bb, Gb*V] one-hot in table dtype — the exact quantity
        # _fit_scratch_gb(G, Bb, V, itemsize) bounds.
        return c.Bb * c.Gb * s["V"] * s["itemsize"]

    def host_gemv_trace(s, c):
        tiles = ops._fit_tiles((c.Bb, c.Gb, c.Ob), s["B"], s["G"], s["O"])
        Bp = _round_up(s["B"], tiles[0])
        Op = _padded_O(s["O"], tiles[2])
        j = mk(pcilt_gemv_pallas,
               sds((Bp, s["G"]), jnp.int32),
               sds((s["G"], s["V"], Op), tdt(s)),
               interpret=True, tiles=tiles)
        return j, tiles

    def host_gemv_witness(s, eff):
        # host kernel one-hots one group per fori step: [Bb_eff, V] in table
        # dtype (the generator's [Bb, Gb, V] model is deliberately
        # conservative for this kernel).
        return [(eff[0], s["V"])]

    def fused_gemv_trace(s, c):
        tiles = ops._fit_tiles((c.Bb, c.Gb, c.Ob), s["B"], s["G"], s["O"])
        Bp = _round_up(s["B"], tiles[0])
        Op = _padded_O(s["O"], tiles[2])
        j = mk(pcilt_fused_gemv_pallas,
               sds((Bp, s["G"] * s["group"]), jnp.float32),
               sds((1, 1), jnp.float32),
               sds((s["G"], s["V"], Op), tdt(s)),
               bits=s["bits"], zero_point=(1 << s["bits"]) // 2,
               group=s["group"], tiles=tiles, interpret=True)
        return j, tiles

    def fused_gemv_witness(s, eff):
        # the [Bb, Gs*V] one-hot of one pack chunk (Gs | Gb segments)
        return [(eff[0], pack_chunk(eff[1], s["group"], s["V"]) * s["V"])]

    STACKED_SWEEP = {
        "quick": [dict(B=8, L=3, G=16, V=16, O=256, group=2, bits=2,
                       itemsize=4)],
        "full": [dict(B=8, L=3, G=16, V=16, O=256, group=2, bits=2,
                      itemsize=4),
                 dict(B=1, L=4, G=64, V=16, O=512, group=2, bits=2,
                      itemsize=2),
                 # batch-R serving regime: the R-aware row-tile sweep emits
                 # Bb sub-tiles (8/16/32) here — verify each one fits
                 dict(B=64, L=3, G=32, V=16, O=256, group=2, bits=2,
                      itemsize=4)],
    }

    def stacked_cands(s, budget):
        return atn.stacked_gemv_candidates(s["B"], s["L"], s["G"], s["V"],
                                           s["O"], s["itemsize"],
                                           scratch_budget=budget)

    def stacked_trace(s, c, counters=False):
        tiles = ops._fit_tiles((c.Bb, c.Gb, c.Ob), s["B"], s["G"], s["O"])
        Bp = _round_up(s["B"], tiles[0])
        Op = _padded_O(s["O"], tiles[2])
        j = mk(pcilt_fused_gemv_stacked_pallas,
               sds((1,), jnp.int32),
               sds((Bp, s["G"] * s["group"]), jnp.float32),
               sds((1, 1), jnp.float32),
               sds((s["L"], s["G"], s["V"], Op), tdt(s)),
               bits=s["bits"], zero_point=(1 << s["bits"]) // 2,
               group=s["group"], tiles=tiles, counters=counters,
               interpret=True)
        return j, tiles

    # -- paired (TL1-style) gemv + seg-major stack -------------------------
    # G/V are paired-space (segment pairs at V**2 entries).  The paired
    # kernels gather table rows with take_along_axis — no one-hot — so the
    # scratch model is the f32 [Gb, Bb, Ob] fetched rows plus the [Bb, Gb]
    # pair-index plane (autotune._fit_paired_gb), with no V factor.

    PAIRED_SWEEP = {
        "quick": [dict(B=8, G=8, V=256, O=256, group=2, bits=2, itemsize=4)],
        "full": [dict(B=8, G=8, V=256, O=256, group=2, bits=2, itemsize=4),
                 dict(B=1, G=16, V=16, O=128, group=1, bits=2, itemsize=2)],
    }

    def paired_cands(s, budget):
        return atn.paired_gemv_candidates(s["B"], s["G"], s["V"], s["O"],
                                          s["itemsize"],
                                          scratch_budget=budget)

    def paired_scratch(s, c):
        # f32 fetched rows [Gb, Bb, Ob] + int32 pair indices [Bb, Gb] —
        # exactly what _fit_paired_gb(G, Bb, Ob) bounds (no V factor).
        return c.Gb * (c.Bb * c.Ob * 4 + c.Bb * 4)

    def paired_witness(s, eff):
        # the take_along_axis row-fetch intermediate [Gb, Bb, Ob]
        return [(eff[1], eff[0], eff[2])]

    def paired_trace(s, c, counters=False):
        tiles = ops._fit_tiles((c.Bb, c.Gb, c.Ob), s["B"], s["G"], s["O"])
        Bp = _round_up(s["B"], tiles[0])
        Op = _padded_O(s["O"], tiles[2])
        j = mk(pcilt_fused_gemv_paired_pallas,
               sds((Bp, s["G"] * 2 * s["group"]), jnp.float32),
               sds((1, 1), jnp.float32),
               sds((s["G"], s["V"], Op), tdt(s)),
               bits=s["bits"], zero_point=(1 << s["bits"]) // 2,
               group=s["group"], tiles=tiles, counters=counters,
               interpret=True)
        return j, tiles

    PAIRED_STACKED_SWEEP = {
        "quick": [dict(B=8, L=2, G=8, V=256, O=128, group=2, bits=2,
                       itemsize=4)],
        "full": [dict(B=8, L=2, G=8, V=256, O=128, group=2, bits=2,
                      itemsize=4),
                 dict(B=1, L=4, G=16, V=16, O=128, group=1, bits=2,
                      itemsize=2),
                 # batch-R serving regime (row-tile sub-tiles of Bb=64)
                 dict(B=64, L=2, G=8, V=256, O=128, group=2, bits=2,
                      itemsize=4)],
    }

    def paired_stacked_cands(s, budget):
        return atn.paired_stacked_gemv_candidates(
            s["B"], s["L"], s["G"], s["V"], s["O"], s["itemsize"],
            scratch_budget=budget)

    def paired_stacked_trace(s, c, counters=False):
        tiles = ops._fit_tiles((c.Bb, c.Gb, c.Ob), s["B"], s["G"], s["O"])
        Bp = _round_up(s["B"], tiles[0])
        Op = _padded_O(s["O"], tiles[2])
        j = mk(pcilt_fused_gemv_paired_stacked_pallas,
               sds((1,), jnp.int32),
               sds((Bp, s["G"] * 2 * s["group"]), jnp.float32),
               sds((1, 1), jnp.float32),
               sds((s["G"], s["L"], s["V"], Op), tdt(s)),
               bits=s["bits"], zero_point=(1 << s["bits"]) // 2,
               group=s["group"], tiles=tiles, counters=counters,
               interpret=True)
        return j, tiles

    # -- plan-gather gemv (generalized SegmentPlans on the fused path) -----
    # Same one-hot contraction (and so the same generator + scratch model +
    # witness) as fused_gemv; only the in-VMEM plan gather differs.

    def plan_trace(s, c):
        tiles = ops._fit_tiles((c.Bb, c.Gb, c.Ob), s["B"], s["G"], s["O"])
        Bp = _round_up(s["B"], tiles[0])
        Op = _padded_O(s["O"], tiles[2])
        j = mk(pcilt_fused_gemv_plan_pallas,
               sds((Bp, s["G"] * s["group"]), jnp.float32),
               sds((1, 1), jnp.float32),
               sds((s["G"], s["group"]), jnp.int32),
               sds((s["G"], s["V"], Op), tdt(s)),
               bits=s["bits"], zero_point=(1 << s["bits"]) // 2,
               group=s["group"], tiles=tiles, interpret=True)
        return j, tiles

    # -- conv2d (host-packed + fused share the generator) ------------------

    CONV_SWEEP = {
        "quick": [dict(B=1, Ho=8, Wo=8, C=8, kh=3, kw=3, stride=1, G=36,
                       group=2, V=16, O=128, bits=2, itemsize=4)],
        "full": [dict(B=1, Ho=8, Wo=8, C=8, kh=3, kw=3, stride=1, G=36,
                      group=2, V=16, O=128, bits=2, itemsize=4),
                 dict(B=2, Ho=16, Wo=16, C=16, kh=5, kw=5, stride=1, G=200,
                      group=2, V=16, O=256, bits=2, itemsize=2)],
    }

    def host_conv_cands(s, budget):
        # the host dispatch site calls the generator with the default
        # conservative Wo=128 (it does not thread the real output width).
        return atn.conv2d_candidates(s["Ho"], s["G"], s["V"], s["O"],
                                     s["itemsize"], scratch_budget=budget)

    def host_conv_scratch(s, c):
        return c.row_tile * 128 * c.Gb * s["V"] * s["itemsize"]

    def host_conv_trace(s, c):
        tiles = ops._fit_conv_tiles((c.row_tile, c.Gb, c.Ob),
                                    s["Ho"], s["G"], s["O"])
        Wop = _round_up(s["Wo"], 8) if s["Wo"] >= 8 else s["Wo"]
        Op = _padded_O(s["O"], tiles[2])
        j = mk(pcilt_conv2d_pallas,
               sds((s["B"], s["Ho"], Wop, s["G"]), jnp.int32),
               sds((s["G"], s["V"], Op), tdt(s)),
               interpret=True, tiles=tiles)
        return j, tiles

    def host_conv_witness(s, eff):
        Wop = _round_up(s["Wo"], 8) if s["Wo"] >= 8 else s["Wo"]
        return [(eff[0] * Wop, s["V"])]

    def fused_conv_cands(s, budget):
        return atn.conv2d_candidates(s["Ho"], s["G"], s["V"], s["O"],
                                     s["itemsize"], Wo=s["Wo"],
                                     scratch_budget=budget)

    def fused_conv_scratch(s, c):
        return c.row_tile * s["Wo"] * c.Gb * s["V"] * s["itemsize"]

    def fused_conv_trace(s, c):
        tiles = ops._fit_conv_tiles((c.row_tile, c.Gb, c.Ob),
                                    s["Ho"], s["G"], s["O"])
        Hp = (s["Ho"] - 1) * s["stride"] + s["kh"]
        Wp = (s["Wo"] - 1) * s["stride"] + s["kw"]
        Op = _padded_O(s["O"], tiles[2])
        j = mk(pcilt_fused_conv2d_pallas,
               sds((s["B"], Hp, Wp, s["C"]), jnp.float32),
               sds((1, 1), jnp.float32),
               sds((1, 1), jnp.int32),
               sds((s["G"], s["V"], Op), tdt(s)),
               bits=s["bits"], zero_point=(1 << s["bits"]) // 2,
               group=s["group"], kh=s["kh"], kw=s["kw"], stride=s["stride"],
               n_total=s["G"] * s["group"], tiles=tiles, interpret=True)
        return j, tiles

    def fused_conv_witness(s, eff):
        return [(eff[0] * s["Wo"],
                 pack_chunk(eff[1], s["group"], s["V"]) * s["V"])]

    # -- shared pool (extension 3) ----------------------------------------

    SHARED_GEMV_SWEEP = {
        "quick": [dict(B=8, G=16, X=4, V=16, O=256, group=2, bits=2,
                       itemsize=4)],
        "full": [dict(B=8, G=16, X=4, V=16, O=256, group=2, bits=2,
                      itemsize=4),
                 dict(B=16, G=128, X=8, V=16, O=512, group=2, bits=2,
                      itemsize=2)],
    }

    def shared_gemv_cands(s, budget):
        return atn.shared_gemv_candidates(s["B"], s["G"], s["V"], s["O"],
                                          s["X"], s["itemsize"],
                                          scratch_budget=budget)

    def shared_gemv_scratch(s, c):
        # f32 [Bb, Gb, V] one-hot + Gb-independent counts/pool fixed bytes.
        fixed = atn._shared_fixed_bytes(c.Bb, s["V"], s["X"], c.Ob,
                                        s["itemsize"])
        return c.Bb * c.Gb * s["V"] * 4 + fixed

    def shared_gemv_trace(s, c):
        tiles = ops._fit_tiles((c.Bb, c.Gb, c.Ob), s["B"], s["G"], s["O"])
        Bp = _round_up(s["B"], tiles[0])
        j = mk(pcilt_shared_gemv_pallas,
               sds((Bp, s["G"] * s["group"]), jnp.float32),
               sds((1, 1), jnp.float32),
               sds((1, s["G"]), jnp.int32),
               sds((s["X"], s["V"], s["O"]), tdt(s)),
               bits=s["bits"], zero_point=(1 << s["bits"]) // 2,
               group=s["group"], tiles=tiles, interpret=True)
        return j, tiles

    def shared_gemv_witness(s, eff):
        # one offset value's [Bb, Gb] slice of the modeled [Bb, Gb, V]
        # one-hot (the kernel walks the V values in turn)
        return [(eff[0], eff[1])]

    SHARED_CONV_SWEEP = {
        "quick": [dict(B=1, Ho=8, Wo=8, C=8, kh=3, kw=3, stride=1, G=36,
                       X=4, group=2, V=16, O=128, bits=2, itemsize=4)],
        "full": [dict(B=1, Ho=8, Wo=8, C=8, kh=3, kw=3, stride=1, G=36,
                      X=4, group=2, V=16, O=128, bits=2, itemsize=4),
                 dict(B=2, Ho=16, Wo=16, C=16, kh=5, kw=5, stride=1, G=200,
                      X=8, group=2, V=16, O=256, bits=2, itemsize=2)],
    }

    def shared_conv_cands(s, budget):
        return atn.shared_conv2d_candidates(s["Ho"], s["G"], s["V"], s["O"],
                                            s["X"], s["itemsize"],
                                            Wo=s["Wo"], scratch_budget=budget)

    def shared_conv_scratch(s, c):
        R = c.row_tile * s["Wo"]
        fixed = atn._shared_fixed_bytes(R, s["V"], s["X"], c.Ob,
                                        s["itemsize"])
        return R * c.Gb * s["V"] * 4 + fixed

    def shared_conv_trace(s, c):
        tiles = ops._fit_conv_tiles((c.row_tile, c.Gb, c.Ob),
                                    s["Ho"], s["G"], s["O"])
        Hp = (s["Ho"] - 1) * s["stride"] + s["kh"]
        Wp = (s["Wo"] - 1) * s["stride"] + s["kw"]
        j = mk(pcilt_shared_conv2d_pallas,
               sds((s["B"], Hp, Wp, s["C"]), jnp.float32),
               sds((1, 1), jnp.float32),
               sds((1, 1), jnp.int32),
               sds((1, s["G"]), jnp.int32),
               sds((s["X"], s["V"], s["O"]), tdt(s)),
               bits=s["bits"], zero_point=(1 << s["bits"]) // 2,
               group=s["group"], kh=s["kh"], kw=s["kw"], stride=s["stride"],
               n_total=s["G"] * s["group"], tiles=tiles, interpret=True)
        return j, tiles

    def shared_conv_witness(s, eff):
        return [(eff[0] * s["Wo"], eff[1])]

    # -- fused depthwise conv1d --------------------------------------------

    DW_SWEEP = {
        "quick": [dict(B=2, To=16, C=128, k=4, bits=2, itemsize=4)],
        "full": [dict(B=2, To=16, C=128, k=4, bits=2, itemsize=4),
                 dict(B=1, To=64, C=256, k=4, bits=2, itemsize=2)],
    }

    def dw_V(s):
        return 1 << (s["bits"] * s["k"])

    def dw_cands(s, budget):
        return atn.dwconv1d_candidates(s["To"], s["C"], dw_V(s), s["k"],
                                       s["itemsize"], scratch_budget=budget,
                                       B=s["B"])

    def dw_eff(s, c):
        return (atn._div_down(s["To"], max(1, c.Bb)),
                atn._div_down(s["C"], max(1, c.Ob)))

    def dw_scratch(s, c):
        V = dw_V(s)
        Tb, Cb = dw_eff(s, c)
        fixed = Cb * V * 4 + Cb * V * s["itemsize"]
        return Tb * s["B"] * Cb * 3 * 4 + fixed

    def dw_trace(s, c, counters=False):
        Tb, Cb = dw_eff(s, c)
        Tp = s["To"] + s["k"] - 1
        j = mk(pcilt_fused_dwconv1d_pallas,
               sds((s["B"], Tp, s["C"]), jnp.float32),
               sds((1, 1), jnp.float32),
               sds((s["C"], dw_V(s)), tdt(s)),
               bits=s["bits"], zero_point=(1 << s["bits"]) // 2,
               k=s["k"], tiles=(Tb, Cb), counters=counters, interpret=True)
        return j, (Tb, Cb)

    def dw_witness(s, eff):
        # the in-VMEM transposed [V, Cb] table the select fetch walks
        return [(dw_V(s), eff[1])]

    return [
        Family("gemv_host", _kpath("pcilt_gemv.py"), GEMV_SWEEP,
               gemv_cands, gemv_scratch, host_gemv_witness, host_gemv_trace),
        Family("fused_gemv", _kpath("pcilt_fused.py"), GEMV_SWEEP,
               gemv_cands, gemv_scratch, fused_gemv_witness,
               fused_gemv_trace),
        Family("fused_gemv_stacked", _kpath("pcilt_fused.py"), STACKED_SWEEP,
               stacked_cands, gemv_scratch, fused_gemv_witness,
               stacked_trace),
        Family("fused_gemv_paired", _kpath("pcilt_fused.py"), PAIRED_SWEEP,
               paired_cands, paired_scratch, paired_witness, paired_trace),
        Family("fused_gemv_paired_stacked", _kpath("pcilt_fused.py"),
               PAIRED_STACKED_SWEEP, paired_stacked_cands, paired_scratch,
               paired_witness, paired_stacked_trace),
        Family("fused_gemv_plan", _kpath("pcilt_fused.py"), GEMV_SWEEP,
               gemv_cands, gemv_scratch, fused_gemv_witness, plan_trace),
        Family("conv2d_host", _kpath("pcilt_conv2d.py"), CONV_SWEEP,
               host_conv_cands, host_conv_scratch, host_conv_witness,
               host_conv_trace),
        Family("fused_conv2d", _kpath("pcilt_fused.py"), CONV_SWEEP,
               fused_conv_cands, fused_conv_scratch, fused_conv_witness,
               fused_conv_trace),
        Family("shared_gemv", _kpath("pcilt_shared.py"), SHARED_GEMV_SWEEP,
               shared_gemv_cands, shared_gemv_scratch, shared_gemv_witness,
               shared_gemv_trace),
        Family("shared_conv2d", _kpath("pcilt_shared.py"), SHARED_CONV_SWEEP,
               shared_conv_cands, shared_conv_scratch, shared_conv_witness,
               shared_conv_trace),
        Family("fused_dwconv1d", _kpath("pcilt_dwconv1d.py"), DW_SWEEP,
               dw_cands, dw_scratch, dw_witness, dw_trace),
        # monitored (_sat) variants: same candidate generators, scratch
        # models, and one-hot witnesses as their base families — the trace
        # compiles with counters=True, so the verifier proves the counter
        # reduction adds no modeled scratch and the [1,1] counter outputs'
        # constant index maps stay in-bounds over the full grid
        Family("fused_gemv_stacked_sat", _kpath("pcilt_fused.py"),
               STACKED_SWEEP, stacked_cands, gemv_scratch,
               fused_gemv_witness,
               lambda s, c: stacked_trace(s, c, counters=True)),
        Family("fused_gemv_paired_sat", _kpath("pcilt_fused.py"),
               PAIRED_SWEEP, paired_cands, paired_scratch, paired_witness,
               lambda s, c: paired_trace(s, c, counters=True)),
        Family("fused_gemv_paired_stacked_sat", _kpath("pcilt_fused.py"),
               PAIRED_STACKED_SWEEP, paired_stacked_cands, paired_scratch,
               paired_witness,
               lambda s, c: paired_stacked_trace(s, c, counters=True)),
        Family("fused_dwconv1d_sat", _kpath("pcilt_dwconv1d.py"), DW_SWEEP,
               dw_cands, dw_scratch, dw_witness,
               lambda s, c: dw_trace(s, c, counters=True)),
    ]


_FAMILIES: Optional[List[Family]] = None


def FAMILIES() -> List[Family]:
    global _FAMILIES
    if _FAMILIES is None:
        _FAMILIES = _build_families()
    return _FAMILIES


# ----------------------------------------------------------------------------
# Jaxpr plumbing: find the pallas_call, walk sub-jaxprs, eval index maps
# ----------------------------------------------------------------------------


def _subjaxprs(params: dict):
    from jax.extend import core as jcore

    def as_jaxprs(v):
        if isinstance(v, jcore.ClosedJaxpr):
            yield v.jaxpr
        elif isinstance(v, jcore.Jaxpr):
            yield v
        elif isinstance(v, (list, tuple)):
            for x in v:
                yield from as_jaxprs(x)

    for v in params.values():
        yield from as_jaxprs(v)


def _find_pallas_eqn(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            return eqn
        for sub in _subjaxprs(eqn.params):
            hit = _find_pallas_eqn(sub)
            if hit is not None:
                return hit
    return None


def _all_avals(jaxpr, out: Optional[list] = None) -> list:
    if out is None:
        out = []
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            aval = getattr(v, "aval", None)
            if aval is not None and hasattr(aval, "shape"):
                out.append(aval)
        for sub in _subjaxprs(eqn.params):
            _all_avals(sub, out)
    return out


def _block_shape(bm) -> Tuple[int, ...]:
    # block dims are ``pl.Blocked`` (carrying ``block_size``) or squeezed
    # (size-1) markers
    return tuple(b if isinstance(b, int) else int(getattr(b, "block_size", 1))
                 for b in bm.block_shape)


def _eval_index_map(bm, grid_vecs, prefetch_val):
    """Evaluate one BlockSpec index-map jaxpr over *vectors* of grid indices
    (the maps are elementwise in the grid coordinates, so one eval covers
    the whole grid).  ``prefetch_val`` is the scalar-prefetch operand value
    (or None); ref-typed maps are rewritten pure via ``discharge_state``
    first.  Returns one int array per block axis, broadcast to grid size."""
    import jax
    import numpy as np

    ij = bm.index_map_jaxpr
    n = len(grid_vecs[0]) if len(grid_vecs) else 1
    if prefetch_val is None:
        outs = jax.core.eval_jaxpr(ij.jaxpr, ij.consts, *grid_vecs)
    else:
        from jax._src.state.discharge import discharge_state
        dj, dconsts = discharge_state(ij.jaxpr, ij.consts)
        outs = jax.core.eval_jaxpr(dj, dconsts, *grid_vecs, prefetch_val)
        outs = outs[:len(outs) - 1]  # drop the discharged final ref value
    return [np.broadcast_to(np.asarray(o, np.int64).reshape(-1)
                            if np.ndim(o) else np.asarray(o, np.int64), (n,))
            for o in outs]


def _check_blocks(fam: Family, sym: str, eqn, L: Optional[int]
                  ) -> List[Finding]:
    """VMEM002/VMEM003 for one traced config: bounds + coverage of every
    BlockSpec over the full grid."""
    import numpy as np

    findings: List[Finding] = []
    gm = eqn.params["grid_mapping"]
    grid = tuple(int(g) for g in gm.grid)
    total = int(np.prod(grid)) if grid else 1
    if total > _MAX_GRID_POINTS:  # sweeps are sized to avoid this
        mesh = [np.linspace(0, g - 1, num=min(g, 64), dtype=np.int64)
                for g in grid]
        coverage_ok = False
    else:
        mesh = [np.arange(g, dtype=np.int64) for g in grid]
        coverage_ok = True
    pts = np.meshgrid(*mesh, indexing="ij") if mesh else []
    grid_vecs = [p.reshape(-1) for p in pts]

    n_out = len(eqn.outvars)
    n_index = int(getattr(gm, "num_index_operands", 0))
    prefetch_vals = [None]
    if n_index:
        prefetch_vals = [np.array([l], np.int32) for l in range(L or 1)]

    for bi, bm in enumerate(gm.block_mappings):
        is_output = bi >= len(gm.block_mappings) - n_out
        bs = _block_shape(bm)
        dims = tuple(int(d) for d in bm.array_aval.shape)
        nblocks = [max(1, -(-d // b)) for d, b in zip(dims, bs)]
        per_l = []
        for pv in prefetch_vals:
            idx = _eval_index_map(bm, grid_vecs, pv)
            per_l.append(idx)
            for a, (ia, nb) in enumerate(zip(idx, nblocks)):
                bad = (ia < 0) | (ia >= nb)
                if bad.any():
                    w = int(np.argmax(bad))
                    pt = tuple(int(v[w]) for v in grid_vecs)
                    findings.append(Finding(
                        "VMEM002", "error", fam.path, 0,
                        f"operand {bi} axis {a}: index_map emits block "
                        f"{int(ia[w])} outside [0, {nb}) at grid point "
                        f"{pt}" + (f" (prefetch={int(pv[0])})" if pv
                                   is not None else "")
                        + f"; array dims {dims}, block {bs}",
                        symbol=sym))
                    break
        if not coverage_ok or not per_l:
            continue
        idx0 = per_l[0]
        # axes whose index changes with the prefetch value (the stacked
        # kernel's layer axis) are staged per-prefetch, not per-grid —
        # exempt from grid coverage (bounds were checked for every value).
        prefetch_axes = set()
        for other in per_l[1:]:
            for a in range(len(idx0)):
                if not np.array_equal(idx0[a], other[a]):
                    prefetch_axes.add(a)
        for a, nb in enumerate(nblocks):
            if a in prefetch_axes:
                continue
            seen = set(np.unique(idx0[a]).tolist())
            varies = len(seen) > 1
            if (is_output or varies) and seen != set(range(nb)):
                missing = sorted(set(range(nb)) - seen)[:8]
                findings.append(Finding(
                    "VMEM003", "error", fam.path, 0,
                    f"operand {bi} axis {a}: grid walk covers blocks "
                    f"{sorted(seen)[:8]} of [0, {nb}) — operand is tiled "
                    f"with gaps (missing {missing})"
                    + ("" if is_output else " on a grid-dependent axis"),
                    symbol=sym))
        if is_output and len(nblocks) <= 4 and coverage_ok:
            want = set(itertools.product(*(range(nb) for nb in nblocks)))
            got = set(zip(*(tuple(x.tolist()) for x in idx0))) if idx0 \
                else set()
            if got != want:
                findings.append(Finding(
                    "VMEM003", "error", fam.path, 0,
                    f"output operand {bi}: grid writes {len(got)} of "
                    f"{len(want)} blocks — some output blocks are never "
                    f"visited",
                    symbol=sym))
    return findings


def _staged_bytes(eqn) -> int:
    gm = eqn.params["grid_mapping"]
    total = 0
    for bm in gm.block_mappings:
        bs = _block_shape(bm)
        n = 1
        for b in bs:
            n *= b
        total += n * bm.array_aval.dtype.itemsize
    return total


def _has_witness(eqn, shapes: Sequence[Tuple[int, ...]]) -> bool:
    from jax.extend import core as jcore

    kj = eqn.params["jaxpr"]
    if isinstance(kj, jcore.ClosedJaxpr):
        kj = kj.jaxpr
    want = {tuple(s) for s in shapes}
    for aval in _all_avals(kj):
        if tuple(aval.shape) in want:
            return True
    return False


# ----------------------------------------------------------------------------
# Driver
# ----------------------------------------------------------------------------


def verify_all(sweep: str = "quick",
               scratch_budget: Optional[float] = None,
               families: Optional[Sequence[str]] = None) -> List[Finding]:
    """Run the static verifier over every kernel family's candidate
    generator and traced wrapper.  ``scratch_budget=None`` uses the shipped
    ``SCRATCH_BUDGET``; tests shrink it to prove non-vacuity."""
    from repro.kernels import autotune as atn

    if sweep not in ("quick", "full"):
        raise ValueError(f"sweep must be 'quick' or 'full', got {sweep!r}")
    budget = atn.SCRATCH_BUDGET if scratch_budget is None else scratch_budget
    findings: List[Finding] = []
    for fam in FAMILIES():
        if families is not None and fam.name not in families:
            continue
        for s in fam.sweep[sweep]:
            shape_tag = ",".join(f"{k}={v}" for k, v in sorted(s.items()))
            cands = fam.candidates(s, budget)
            if not cands:
                findings.append(Finding(
                    "VMEM001", "error", fam.path, 0,
                    f"candidate generator emitted no candidates for shape "
                    f"{shape_tag}", symbol=fam.name))
                continue
            traced = set()
            for ci, cfg in enumerate(cands):
                sym = f"{fam.name}[{shape_tag}]#{ci}"
                scratch = fam.scratch_bytes(s, cfg)
                if scratch > budget:
                    findings.append(Finding(
                        "VMEM001", "error", fam.path, 0,
                        f"candidate {cfg} modeled scratch "
                        f"{scratch} B > SCRATCH_BUDGET {int(budget)} B"
                        f"; the analytic clamp (_fit_scratch_gb) was not "
                        f"applied for shape {shape_tag}",
                        symbol=sym))
                jaxpr, eff = fam.trace(s, cfg)
                eqn = _find_pallas_eqn(jaxpr.jaxpr)
                if eqn is None:
                    findings.append(Finding(
                        "VMEM004", "error", fam.path, 0,
                        "no pallas_call equation found in traced wrapper",
                        symbol=sym))
                    continue
                key = (tuple(eff), tuple(int(g) for g in
                                         eqn.params["grid_mapping"].grid))
                if key not in traced:
                    traced.add(key)
                    findings.extend(_check_blocks(fam, sym, eqn, s.get("L")))
                    if not _has_witness(eqn, fam.witness(s, eff)):
                        findings.append(Finding(
                            "VMEM004", "error", fam.path, 0,
                            f"traced kernel body has no intermediate of the "
                            f"modeled one-hot shape "
                            f"{list(fam.witness(s, eff))}"
                            f"; the scratch model no longer describes the "
                            f"kernel (shape {shape_tag}, config {cfg})",
                            symbol=sym))
                total = _staged_bytes(eqn) + scratch
                if ci == 0 and total > TOTAL_VMEM_BUDGET:
                    findings.append(Finding(
                        "VMEM005", "error", fam.path, 0,
                        f"untuned fallback candidate {cfg} stages "
                        f"{_staged_bytes(eqn)} B + {scratch} B scratch "
                        f"> {TOTAL_VMEM_BUDGET} B per-core VMEM"
                        f"; a cache miss cannot dispatch (shape "
                        f"{shape_tag})",
                        symbol=sym))
                elif ci > 0 and total > TOTAL_VMEM_BUDGET:
                    findings.append(Finding(
                        "VMEM006", "warning", fam.path, 0,
                        f"candidate {cfg} stages {_staged_bytes(eqn)} B + "
                        f"{scratch} B scratch > {TOTAL_VMEM_BUDGET} B"
                        f"; it relies on compile-rejection at tune time "
                        f"(shape {shape_tag})",
                        symbol=sym))
    return findings
