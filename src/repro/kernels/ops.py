"""Jit'd dispatch wrappers for the PCILT Pallas kernels.

Handles platform selection (compiled Pallas on TPU, ``interpret=True``
elsewhere so the exact kernel body is validated on CPU), padding to tile
multiples, unpadding — and **tile dispatch through the persistent autotune
lookup table** (``autotune.py``, Inductor-style):

* every wrapper builds a shape key ``(kernel, B, G, V, O, dtype, backend)``
  and consults the JSON-backed cache; a hit dispatches the recorded tiles at
  zero cost (a dict probe, no timing, no extra compile);
* a miss falls back to the VMEM-budget heuristic — unless tuning is requested
  (``autotune=True`` per call, or ``REPRO_PCILT_AUTOTUNE=1`` ambient) *and*
  the inputs are concrete (never under a ``jit`` trace), in which case the
  candidate tilings are timed once and the winner recorded for every later
  process.

Three pipelines are exposed per op:

* **host-packed** (``pcilt_gemv`` / ``pcilt_conv2d`` / ``pcilt_dwconv1d``):
  caller quantizes + packs offsets on the host; kernels fetch-and-add.
* **fused** (``pcilt_fused_gemv`` / ``pcilt_fused_conv2d`` /
  ``pcilt_fused_dwconv1d``): raw float activations in; quantize → pack →
  fetch → adder-tree run entirely in VMEM (``pcilt_fused.py``,
  ``pcilt_dwconv1d.py``), so the int32 offset tensor never touches HBM.
* **shared-pool fused** (``pcilt_shared_gemv`` / ``pcilt_shared_conv2d``):
  the extension-3 weight-deduped configuration — a ``[X, V, O]`` pool of
  unique segment tables plus ``[G]`` int pointers — executed at fused speed;
  the pointer indirection is resolved inside the kernel
  (``pcilt_shared.py``) and the dense ``[G, V, O]`` tables are never
  materialized in HBM.  Shape keys carry the pool cardinality ``X``.

Mesh execution (``core.lut_layers`` ``mesh=``) calls these same wrappers
from inside ``shard_map``: the table operand arrives as one device's
``[G/D, V, O]`` shard (``PartitionSpec("model", None, None)`` — only the
segment axis shards) or its local ext.-3 pool (``ShardedSharedPool``:
``[Xmax, V, O]`` with ``Xmax = max_d X_d`` the largest *local* pool
cardinality, so staged bytes follow local X, not global G or X), and the
wrapper's output is that shard's partial adder-tree sum — the ``psum`` over
the model axis lives one level up, in ``lut_layers``, never in a kernel.
The conv wrappers additionally take ``seg_offset`` / ``n_total`` so a
shard's kernel can im2col the full replicated image **in VMEM** and slice
exactly its own patch columns — no host-side im2col even under a mesh.
Consequently the autotune shape keys are built from the **local** shapes
(``G/D``, local ``X``): tunings recorded at different device counts occupy
different keys, and two deployments whose local problems coincide share one
entry on purpose.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro import compat
# Single sources of truth for padding — the host-packed reference paths and
# the fused kernel wrappers must pad identically: the XLA-conformant
# stride-aware "SAME" split for conv2d, and the CAUSAL/SAME/VALID time pads
# for the depthwise conv1d.
from repro.core.lut_layers import conv_same_pads as _conv_same_pads
from repro.core.lut_layers import _dwconv_pads

from . import autotune as atn
from .pcilt_gemv import pcilt_gemv_pallas, default_tiles
from .pcilt_conv2d import pcilt_conv2d_pallas
from .pcilt_dwconv1d import pcilt_dwconv1d_pallas, pcilt_fused_dwconv1d_pallas
from .pcilt_fused import (pcilt_fused_gemv_pallas,
                          pcilt_fused_gemv_stacked_pallas,
                          pcilt_fused_gemv_paired_pallas,
                          pcilt_fused_gemv_paired_stacked_pallas,
                          pcilt_fused_gemv_plan_pallas,
                          pcilt_fused_conv2d_pallas)
from .pcilt_shared import (pcilt_shared_gemv_pallas,
                           pcilt_shared_conv2d_pallas)

__all__ = [
    "pcilt_gemv",
    "pcilt_conv2d",
    "pcilt_dwconv1d",
    "pcilt_fused_gemv",
    "pcilt_fused_gemv_stacked",
    "pcilt_fused_gemv_paired",
    "pcilt_fused_gemv_paired_stacked",
    "pcilt_fused_gemv_plan",
    "pcilt_fused_conv2d",
    "pcilt_fused_dwconv1d",
    "pcilt_shared_gemv",
    "pcilt_shared_conv2d",
    "on_tpu",
]


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _is_concrete(*xs) -> bool:
    return not any(compat.is_tracer(x) for x in xs)


_round_up = atn._round_up


def _pad_axis(x: jax.Array, axis: int, mult: int):
    pad = (-x.shape[axis]) % mult
    if not pad:
        return x, 0
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), pad


def _scale_2d(scale, dtype) -> jax.Array:
    """Per-tensor scale as the ``[1, 1]`` operand the fused kernels stage."""
    s = jnp.asarray(scale, dtype)
    if s.size != 1:
        raise ValueError(
            f"fused kernels take a per-tensor (scalar) scale, got shape {s.shape}"
        )
    return s.reshape(1, 1)


def _fit_tiles(tiles, B: int, G: int, O: int) -> tuple:
    """Clamp a (Bb, Gb, Ob) tiling to the problem and force ``Gb | G``."""
    Bb, Gb, Ob = tiles
    Bb, Gb, Ob = min(Bb, _round_up(B, 8)), min(Gb, G), min(Ob, O)
    while G % Gb:
        Gb -= 1
    return Bb, Gb, Ob


def _fit_conv_tiles(tiles, Ho: int, G: int, O: int) -> tuple:
    """Clamp a (Hb, Gb, Ob) conv tiling: ``Hb | Ho`` and ``Gb | G``."""
    Hb, Gb, Ob = tiles
    Hb, Gb, Ob = min(Hb, Ho), min(Gb, G), min(Ob, O)
    while Ho % Hb:
        Hb -= 1
    while G % Gb:
        Gb -= 1
    return Hb, Gb, Ob


# ----------------------------------------------------------------------------
# Host-packed pipeline
# ----------------------------------------------------------------------------


def pcilt_gemv(
    offsets: jax.Array,
    tables: jax.Array,
    tiles=None,
    autotune: Optional[bool] = None,
) -> jax.Array:
    """offsets [B, G] int32, tables [G, V, O] -> [B, O]."""
    B, O = offsets.shape[0], tables.shape[-1]
    G, V = tables.shape[0], tables.shape[1]
    key = atn.shape_key("gemv_host", dtype=tables.dtype,
                        backend=jax.default_backend(), B=B, G=G, V=V, O=O)
    if tiles is None:
        tiles = atn.lookup(key)
        if tiles is not None:
            tiles = (tiles.Bb, tiles.Gb, tiles.Ob)
        elif atn.autotune_enabled(autotune) and _is_concrete(offsets, tables):
            cfg = atn.tune(
                key,
                atn.gemv_candidates(B, G, V, O, tables.dtype.itemsize),
                lambda c: _host_gemv_bench(offsets, tables, c),
            )
            tiles = (cfg.Bb, cfg.Gb, cfg.Ob)
    if tiles is not None:
        tiles = _fit_tiles(tiles, B, G, O)
    offsets, _ = _pad_axis(offsets, 0, tiles[0] if tiles else 8)
    tables, _ = _pad_axis(
        tables, 2, (tiles[2] if tiles else 128) if O >= 128 else 1)
    out = pcilt_gemv_pallas(offsets, tables, interpret=not on_tpu(), tiles=tiles)
    return out[:B, :O]


def _host_gemv_bench(offsets, tables, cfg):
    B, O = offsets.shape[0], tables.shape[-1]
    tiles = _fit_tiles((cfg.Bb, cfg.Gb, cfg.Ob), B, tables.shape[0], O)
    off_p, _ = _pad_axis(offsets, 0, tiles[0])
    tab_p, _ = _pad_axis(tables, 2, tiles[2] if O >= 128 else 1)
    return lambda: pcilt_gemv_pallas(
        off_p, tab_p, interpret=not on_tpu(), tiles=tiles
    ).block_until_ready()


def pcilt_conv2d(
    offsets: jax.Array,
    tables: jax.Array,
    tiles=None,
    autotune: Optional[bool] = None,
) -> jax.Array:
    """offsets [B, Ho, Wo, G] int32, tables [G, V, O] -> [B, Ho, Wo, O].

    Pads Wo to a sublane multiple and O to a lane multiple (mirroring the
    gemv wrapper), then unpads — non-128-multiple channel counts and ragged
    widths are the caller's problem no longer.
    """
    B, Ho, Wo, G = offsets.shape
    V, O = tables.shape[1], tables.shape[-1]
    key = atn.shape_key("conv2d_host", dtype=tables.dtype,
                        backend=jax.default_backend(),
                        B=B, Ho=Ho, Wo=Wo, G=G, V=V, O=O)
    cfg = None
    if tiles is None:
        cfg = atn.lookup(key)
        if cfg is None and atn.autotune_enabled(autotune) and _is_concrete(
                offsets, tables):
            cfg = atn.tune(
                key,
                atn.conv2d_candidates(Ho, G, V, O, tables.dtype.itemsize),
                lambda c: _host_conv2d_bench(offsets, tables, c),
            )
        if cfg is not None:
            tiles = (cfg.row_tile, cfg.Gb, cfg.Ob)
    if tiles is not None:
        # Same clamp the fused path applies: a hand-edited or cross-version
        # cache entry with Gb ∤ G (or oversized Hb/Ob) must never reach the
        # kernel unclamped.
        tiles = _fit_conv_tiles(tiles, Ho, G, O)
    # Padded-Wo offsets index table row 0; the fetched garbage is sliced off.
    offsets, _ = _pad_axis(offsets, 2, 8 if Wo >= 8 else 1)
    tables, _ = _pad_axis(
        tables, 2, (tiles[2] if tiles else 128) if O >= 128 else 1)
    out = pcilt_conv2d_pallas(offsets, tables, interpret=not on_tpu(),
                              tiles=tiles)
    return out[:, :, :Wo, :O]


def _host_conv2d_bench(offsets, tables, cfg):
    Wo, O = offsets.shape[2], tables.shape[-1]
    off_p, _ = _pad_axis(offsets, 2, 8 if Wo >= 8 else 1)
    tab_p, _ = _pad_axis(tables, 2, cfg.Ob if O >= 128 else 1)
    tiles = (cfg.row_tile, cfg.Gb, min(cfg.Ob, tab_p.shape[-1]))
    return lambda: pcilt_conv2d_pallas(
        off_p, tab_p, interpret=not on_tpu(), tiles=tiles
    ).block_until_ready()


def pcilt_dwconv1d(offsets: jax.Array, tables: jax.Array) -> jax.Array:
    """offsets [B, T, C] int32, tables [C, V] -> [B, T, C]."""
    C = offsets.shape[-1]
    offsets, padc = _pad_axis(offsets, 2, 128 if C >= 128 else 1)
    tables, _ = _pad_axis(tables, 0, 128 if C >= 128 else 1)
    out = pcilt_dwconv1d_pallas(offsets, tables, interpret=not on_tpu())
    return out[..., :C]


def pcilt_fused_dwconv1d(
    x: jax.Array,
    tables: jax.Array,
    spec,
    scale,
    k: int,
    padding: str = "CAUSAL",
    tiles=None,
    autotune: Optional[bool] = None,
    with_stats: bool = False,
):
    """x [B, T, C] float, tables [C, V] (``V = 2**(bits*k)``) -> [B, To, C].

    The fused depthwise pipeline: the only host-side work is the time
    zero-pad of the raw signal; quantize, causal tap-stack, little-endian
    pack, and the one-fetch-per-output table lookup all run in VMEM
    (``pcilt_fused_dwconv1d_pallas``), so the ``[B, T, C]`` int32 offset
    tensor of the host-packed path never exists in HBM.  ``padding``:
    ``"CAUSAL"`` (``To = T``, taps ``t-k+1..t`` — the Mamba/SSM decode
    frontend), ``"SAME"`` (centered), or ``"VALID"`` (``To = T - k + 1`` —
    e.g. a pre-assembled ``[B, k, C]`` decode window yielding one output).

    ``with_stats=True`` runs the counter-carrying kernel variant and
    returns ``(out, count, ratio)`` saturation stats (the count covers the
    raw ``[B, T, C]`` signal exactly — time/channel pads quantize in
    range).  Counted and uncounted timings never share an autotune entry:
    stats dispatch records under the ``fused_dwconv1d_sat`` key family.
    """
    B, T, C = x.shape
    C2, V = tables.shape
    if C != C2:
        raise ValueError(
            f"x channel dim {C} != tables channel dim {C2} "
            f"(x {x.shape}, tables {tables.shape})")
    x = jnp.pad(x, ((0, 0), _dwconv_pads(k, padding), (0, 0)))
    To = x.shape[1] - k + 1
    kname = "fused_dwconv1d_sat" if with_stats else "fused_dwconv1d"
    key = atn.shape_key(kname, dtype=tables.dtype,
                        backend=jax.default_backend(),
                        B=B, T=To, C=C, V=V, k=k, bits=spec.bits)
    s2 = _scale_2d(scale, x.dtype)
    kw = dict(bits=spec.bits, zero_point=spec.zero_point, k=k,
              counters=with_stats, interpret=not on_tpu())
    xp, _ = _pad_axis(x, 2, 128 if C >= 128 else 1)
    tp, _ = _pad_axis(tables, 0, 128 if C >= 128 else 1)
    Cp = xp.shape[-1]
    if tiles is None:
        cfg = atn.lookup(key)
        if cfg is None and atn.autotune_enabled(autotune) and _is_concrete(
                xp, s2, tp):
            cfg = atn.tune(
                key,
                atn.dwconv1d_candidates(To, Cp, V, k, tables.dtype.itemsize,
                                        B=B),
                lambda c: _fused_dwconv1d_bench(xp, s2, tp, c, kw, To),
            )
        if cfg is None:
            cfg = atn.dwconv1d_candidates(To, Cp, V, k,
                                          tables.dtype.itemsize, B=B)[0]
        tiles = (cfg.Bb, cfg.Ob)
    tiles = (atn._div_down(To, max(1, tiles[0])),
             atn._div_down(Cp, max(1, tiles[1])))
    if with_stats:
        out, cnt, ratio = pcilt_fused_dwconv1d_pallas(xp, s2, tp,
                                                      tiles=tiles, **kw)
        return out[..., :C], cnt, ratio
    out = pcilt_fused_dwconv1d_pallas(xp, s2, tp, tiles=tiles, **kw)
    return out[..., :C]


def _fused_dwconv1d_bench(xp, s2, tp, cfg, kw, To):
    tiles = (atn._div_down(To, max(1, cfg.Bb)),
             atn._div_down(xp.shape[-1], max(1, cfg.Ob)))
    return lambda: jax.block_until_ready(pcilt_fused_dwconv1d_pallas(
        xp, s2, tp, tiles=tiles, **kw
    ))


# ----------------------------------------------------------------------------
# Fused pipeline: raw floats in, quantize/pack/fetch in VMEM
# ----------------------------------------------------------------------------


def pcilt_fused_gemv(
    x: jax.Array,
    tables: jax.Array,
    spec,
    scale,
    group: int,
    tiles=None,
    autotune: Optional[bool] = None,
) -> jax.Array:
    """x [B, n] float, tables [G, V, O] (``n == G * group``) -> [B, O].

    Fuses ``quantize(x, spec, scale)`` + ``pack_offsets`` + fetch into one
    Pallas call; ``spec`` is a ``core.QuantSpec`` (only ``bits`` and
    ``zero_point`` cross into the kernel, both static).
    """
    B, n = x.shape
    G, V, O = tables.shape
    if n != G * group:
        raise ValueError(
            f"x trailing dim {n} != G*group = {G}*{group} (the fused kernel "
            f"packs contiguous segments; generalized SegmentPlans are "
            f"rejected upstream at the core.lut_layers dispatch boundary)")
    key = atn.shape_key("fused_gemv", dtype=tables.dtype,
                        backend=jax.default_backend(),
                        B=B, G=G, V=V, O=O, g=group, bits=spec.bits)
    s2 = _scale_2d(scale, x.dtype)
    kw = dict(bits=spec.bits, zero_point=spec.zero_point, group=group,
              interpret=not on_tpu())
    if tiles is None:
        cfg = atn.lookup(key)
        if cfg is None and atn.autotune_enabled(autotune) and _is_concrete(
                x, s2, tables):
            cfg = atn.tune(
                key,
                atn.gemv_candidates(B, G, V, O, tables.dtype.itemsize),
                lambda c: _fused_gemv_bench(x, s2, tables, c, kw),
            )
        if cfg is not None:
            tiles = (cfg.Bb, cfg.Gb, cfg.Ob)
        else:
            tiles = default_tiles(B, G, V, O, itemsize=tables.dtype.itemsize)
    tiles = _fit_tiles(tiles, B, G, O)
    xp, _ = _pad_axis(x, 0, tiles[0])  # zero rows quantize harmlessly
    tp, _ = _pad_axis(tables, 2, tiles[2] if O >= 128 else 1)
    out = pcilt_fused_gemv_pallas(xp, s2, tp, tiles=tiles, **kw)
    return out[:B, :O]


def _fused_gemv_bench(x, s2, tables, cfg, kw):
    B, G, O = x.shape[0], tables.shape[0], tables.shape[-1]
    tiles = _fit_tiles((cfg.Bb, cfg.Gb, cfg.Ob), B, G, O)
    xp, _ = _pad_axis(x, 0, tiles[0])
    tp, _ = _pad_axis(tables, 2, tiles[2] if O >= 128 else 1)
    return lambda: pcilt_fused_gemv_pallas(
        xp, s2, tp, tiles=tiles, **kw
    ).block_until_ready()




def pcilt_fused_gemv_stacked(
    x: jax.Array,
    tables: jax.Array,
    layer,
    spec,
    scale,
    group: int,
    tiles=None,
    autotune: Optional[bool] = None,
    with_stats: bool = False,
):
    """x [B, n] float, tables [L, G, V, O] (``n == G * group``), layer a
    (possibly traced) int scalar -> [B, O].

    The layer-scanned decode dispatch: one ``[L, G, V, O]`` stack holds the
    tables of every layer of a network, the ``lax.scan`` over layers carries
    only the integer layer index, and the kernel's scalar-prefetched index
    map stages that layer's tiles straight out of the resident stack — no
    per-step ``dynamic_slice`` copy of a whole ``[G, V, O]`` table through
    HBM.  ``scale`` is this layer's per-tensor activation scale (callers
    slice it from their ``[L]`` calibration vector; a traced scalar is
    fine).  Tiles dispatch through ``fused_gemv_stacked`` shape keys, which
    carry ``L``, the decode-batch row count ``R`` (== ``B`` here: the
    serving slot count whose row-tile sweep the recorded winner came from —
    keyed explicitly so a future row-packing dispatch can tune at
    ``R != B`` without a key-grammar change), and — under a mesh, where
    this wrapper sees one device's ``[L, G/D, V, O]`` shard — the *local*
    ``G``.

    ``with_stats=True`` runs the counter-carrying kernel variant and
    returns ``(out, count, ratio)`` — the int32 saturation count and the
    f32 ``max(|x|)/scale`` overshoot of this call's quantization.  Stats
    dispatch records under the ``fused_gemv_stacked_sat`` key family (same
    dims), so counted and uncounted timings never share a cache entry.
    """
    B, n = x.shape
    L, G, V, O = tables.shape
    if n != G * group:
        raise ValueError(
            f"x trailing dim {n} != G*group = {G}*{group} (the stacked fused "
            f"kernel packs contiguous segments; generalized SegmentPlans are "
            f"rejected upstream at the core.lut_layers dispatch boundary)")
    kname = "fused_gemv_stacked_sat" if with_stats else "fused_gemv_stacked"
    key = atn.shape_key(kname, dtype=tables.dtype,
                        backend=jax.default_backend(),
                        B=B, R=B, L=L, G=G, V=V, O=O, g=group,
                        bits=spec.bits)
    s2 = _scale_2d(scale, x.dtype)
    l1 = jnp.asarray(layer, jnp.int32).reshape(1)
    kw = dict(bits=spec.bits, zero_point=spec.zero_point, group=group,
              counters=with_stats, interpret=not on_tpu())
    if tiles is None:
        cfg = atn.lookup(key)
        if cfg is None and atn.autotune_enabled(autotune) and _is_concrete(
                x, s2, l1, tables):
            cfg = atn.tune(
                key,
                atn.stacked_gemv_candidates(B, L, G, V, O,
                                            tables.dtype.itemsize),
                lambda c: _fused_gemv_stacked_bench(l1, x, s2, tables, c, kw),
            )
        if cfg is not None:
            tiles = (cfg.Bb, cfg.Gb, cfg.Ob)
        else:
            tiles = default_tiles(B, G, V, O, itemsize=tables.dtype.itemsize)
    tiles = _fit_tiles(tiles, B, G, O)
    xp, _ = _pad_axis(x, 0, tiles[0])  # zero rows quantize harmlessly
    tp, _ = _pad_axis(tables, 3, tiles[2] if O >= 128 else 1)
    if with_stats:
        out, cnt, ratio = pcilt_fused_gemv_stacked_pallas(l1, xp, s2, tp,
                                                          tiles=tiles, **kw)
        return out[:B, :O], cnt, ratio
    out = pcilt_fused_gemv_stacked_pallas(l1, xp, s2, tp, tiles=tiles, **kw)
    return out[:B, :O]


def _fused_gemv_stacked_bench(l1, x, s2, tables, cfg, kw):
    B, G, O = x.shape[0], tables.shape[1], tables.shape[-1]
    tiles = _fit_tiles((cfg.Bb, cfg.Gb, cfg.Ob), B, G, O)
    xp, _ = _pad_axis(x, 0, tiles[0])
    tp, _ = _pad_axis(tables, 3, tiles[2] if O >= 128 else 1)
    return lambda: jax.block_until_ready(pcilt_fused_gemv_stacked_pallas(
        l1, xp, s2, tp, tiles=tiles, **kw
    ))


def pcilt_fused_gemv_paired(
    x: jax.Array,
    tables: jax.Array,
    spec,
    scale,
    group: int,
    tiles=None,
    autotune: Optional[bool] = None,
    with_stats: bool = False,
):
    """x [B, n] float, paired tables [G2, V2, O] (``n == G2 * 2 * group``,
    ``V2 = (2**(bits*group))**2``) -> [B, O].

    The TL1-style multi-scalar dispatch: each fetch covers two adjacent
    ``group``-wide segments (``core.pcilt.build_paired_tables``), halving
    the fetch count and adder-tree depth.  Keys record under
    ``fused_gemv_paired`` with **paired-space** ``G``/``V`` — the shapes
    the kernel actually stages.

    ``with_stats=True`` returns ``(out, count, ratio)`` saturation stats
    (see :func:`pcilt_fused_gemv_stacked`); keys record under
    ``fused_gemv_paired_sat``.
    """
    B, n = x.shape
    G2, V2, O = tables.shape
    if n != G2 * 2 * group:
        raise ValueError(
            f"x trailing dim {n} != G2*2*group = {G2}*2*{group} (pad x over "
            f"the phantom segment when the unpaired G was odd — "
            f"core.lut_layers does this for you)")
    kname = "fused_gemv_paired_sat" if with_stats else "fused_gemv_paired"
    key = atn.shape_key(kname, dtype=tables.dtype,
                        backend=jax.default_backend(),
                        B=B, G=G2, V=V2, O=O, g=group, bits=spec.bits)
    s2 = _scale_2d(scale, x.dtype)
    kw = dict(bits=spec.bits, zero_point=spec.zero_point, group=group,
              counters=with_stats, interpret=not on_tpu())
    if tiles is None:
        cfg = atn.lookup(key)
        if cfg is None and atn.autotune_enabled(autotune) and _is_concrete(
                x, s2, tables):
            cfg = atn.tune(
                key,
                atn.paired_gemv_candidates(B, G2, V2, O,
                                           tables.dtype.itemsize),
                lambda c: _fused_gemv_paired_bench(x, s2, tables, c, kw),
            )
        if cfg is None:
            # Candidate 0 keeps the staged [Gb, V2, Ob] tile under the VMEM
            # budget — the untuned fallback must never oversubscribe.
            cfg = atn.paired_gemv_candidates(B, G2, V2, O,
                                             tables.dtype.itemsize)[0]
        tiles = (cfg.Bb, cfg.Gb, cfg.Ob)
    tiles = _fit_tiles(tiles, B, G2, O)
    xp, _ = _pad_axis(x, 0, tiles[0])  # zero rows quantize harmlessly
    tp, _ = _pad_axis(tables, 2, tiles[2] if O >= 128 else 1)
    if with_stats:
        out, cnt, ratio = pcilt_fused_gemv_paired_pallas(xp, s2, tp,
                                                         tiles=tiles, **kw)
        return out[:B, :O], cnt, ratio
    out = pcilt_fused_gemv_paired_pallas(xp, s2, tp, tiles=tiles, **kw)
    return out[:B, :O]


def _fused_gemv_paired_bench(x, s2, tables, cfg, kw):
    B, G2, O = x.shape[0], tables.shape[0], tables.shape[-1]
    tiles = _fit_tiles((cfg.Bb, cfg.Gb, cfg.Ob), B, G2, O)
    xp, _ = _pad_axis(x, 0, tiles[0])
    tp, _ = _pad_axis(tables, 2, tiles[2] if O >= 128 else 1)
    return lambda: jax.block_until_ready(pcilt_fused_gemv_paired_pallas(
        xp, s2, tp, tiles=tiles, **kw
    ))


def pcilt_fused_gemv_paired_stacked(
    x: jax.Array,
    tables: jax.Array,
    layer,
    spec,
    scale,
    group: int,
    tiles=None,
    autotune: Optional[bool] = None,
    with_stats: bool = False,
):
    """x [B, n] float, **segment-major** paired tables [G2, L, V2, O]
    (``n == G2 * 2 * group``), layer a (possibly traced) int scalar
    -> [B, O].

    The paired decode dispatch: the whole network's paired tables live in
    one segment-major stack (``core.pcilt.build_paired_stacked_tables``)
    and the scan's layer index rides the fetch's value coordinate (the
    kernel folds L into the gathered row), so staging is layer-independent
    and the traced layer costs nothing.  Keys record under
    ``fused_gemv_paired_stacked`` with paired-space ``G``/``V`` plus ``L``
    and the decode-batch row count ``R`` (== ``B``: the serving slot count
    the row-tile sweep anchors on, keyed explicitly like the dense stacked
    family); under a mesh the wrapper sees one device's ``[G2/D, L, V2, O]``
    shard and keys carry the local ``G``.

    ``with_stats=True`` returns ``(out, count, ratio)`` saturation stats
    (see :func:`pcilt_fused_gemv_stacked`); keys record under
    ``fused_gemv_paired_stacked_sat``.
    """
    B, n = x.shape
    G2, L, V2, O = tables.shape
    if n != G2 * 2 * group:
        raise ValueError(
            f"x trailing dim {n} != G2*2*group = {G2}*2*{group} (pad x over "
            f"the phantom segment when the unpaired G was odd — "
            f"core.lut_layers does this for you)")
    kname = ("fused_gemv_paired_stacked_sat" if with_stats
             else "fused_gemv_paired_stacked")
    key = atn.shape_key(kname, dtype=tables.dtype,
                        backend=jax.default_backend(),
                        B=B, R=B, L=L, G=G2, V=V2, O=O, g=group,
                        bits=spec.bits)
    s2 = _scale_2d(scale, x.dtype)
    l1 = jnp.asarray(layer, jnp.int32).reshape(1)
    kw = dict(bits=spec.bits, zero_point=spec.zero_point, group=group,
              counters=with_stats, interpret=not on_tpu())
    if tiles is None:
        cfg = atn.lookup(key)
        if cfg is None and atn.autotune_enabled(autotune) and _is_concrete(
                x, s2, l1, tables):
            cfg = atn.tune(
                key,
                atn.paired_stacked_gemv_candidates(B, L, G2, V2, O,
                                                   tables.dtype.itemsize),
                lambda c: _fused_gemv_paired_stacked_bench(
                    l1, x, s2, tables, c, kw),
            )
        if cfg is None:
            # Candidate 0's [Gb, L, V2, Ob] staging is budget-clamped with
            # the L factor (the seg-major kernel stages every layer of its
            # segment tile) — the untuned fallback stays VMEM-safe.
            cfg = atn.paired_stacked_gemv_candidates(
                B, L, G2, V2, O, tables.dtype.itemsize)[0]
        tiles = (cfg.Bb, cfg.Gb, cfg.Ob)
    tiles = _fit_tiles(tiles, B, G2, O)
    xp, _ = _pad_axis(x, 0, tiles[0])  # zero rows quantize harmlessly
    tp, _ = _pad_axis(tables, 3, tiles[2] if O >= 128 else 1)
    if with_stats:
        out, cnt, ratio = pcilt_fused_gemv_paired_stacked_pallas(
            l1, xp, s2, tp, tiles=tiles, **kw)
        return out[:B, :O], cnt, ratio
    out = pcilt_fused_gemv_paired_stacked_pallas(l1, xp, s2, tp, tiles=tiles,
                                                 **kw)
    return out[:B, :O]


def _fused_gemv_paired_stacked_bench(l1, x, s2, tables, cfg, kw):
    B, G2, O = x.shape[0], tables.shape[0], tables.shape[-1]
    tiles = _fit_tiles((cfg.Bb, cfg.Gb, cfg.Ob), B, G2, O)
    xp, _ = _pad_axis(x, 0, tiles[0])
    tp, _ = _pad_axis(tables, 3, tiles[2] if O >= 128 else 1)
    return lambda: jax.block_until_ready(
        pcilt_fused_gemv_paired_stacked_pallas(
            l1, xp, s2, tp, tiles=tiles, **kw
        ))


def pcilt_fused_gemv_plan(
    x: jax.Array,
    tables: jax.Array,
    plan_idx: jax.Array,
    spec,
    scale,
    group: int,
    tiles=None,
    autotune: Optional[bool] = None,
) -> jax.Array:
    """x [B, n] float, tables [G, V, O], plan_idx [G, group] int32
    (``-1`` = unused slot) -> [B, O].

    The generalized-``SegmentPlan`` fused dispatch: segments may skip or
    reuse arbitrary positions of ``x``, resolved by an in-VMEM gather of
    the plan index before the standard quantize→pack→fetch — plan-built
    tables no longer fall back to the host gather path.  Keys record under
    ``fused_gemv_plan``; the tiling space is the dense GEMV's (the plan
    gather adds only a ``[Gb*group]`` index block per step).
    """
    B, n = x.shape
    G, V, O = tables.shape
    if plan_idx.shape != (G, group):
        raise ValueError(
            f"plan_idx shape {tuple(plan_idx.shape)} != (G, group) = "
            f"({G}, {group}) (tables {tables.shape})")
    key = atn.shape_key("fused_gemv_plan", dtype=tables.dtype,
                        backend=jax.default_backend(),
                        B=B, G=G, V=V, O=O, g=group, bits=spec.bits)
    s2 = _scale_2d(scale, x.dtype)
    p2 = plan_idx.astype(jnp.int32)
    kw = dict(bits=spec.bits, zero_point=spec.zero_point, group=group,
              interpret=not on_tpu())
    if tiles is None:
        cfg = atn.lookup(key)
        if cfg is None and atn.autotune_enabled(autotune) and _is_concrete(
                x, s2, p2, tables):
            cfg = atn.tune(
                key,
                atn.gemv_candidates(B, G, V, O, tables.dtype.itemsize),
                lambda c: _fused_gemv_plan_bench(x, s2, p2, tables, c, kw),
            )
        if cfg is not None:
            tiles = (cfg.Bb, cfg.Gb, cfg.Ob)
        else:
            tiles = default_tiles(B, G, V, O, itemsize=tables.dtype.itemsize)
    tiles = _fit_tiles(tiles, B, G, O)
    xp, _ = _pad_axis(x, 0, tiles[0])  # zero rows quantize harmlessly
    tp, _ = _pad_axis(tables, 2, tiles[2] if O >= 128 else 1)
    out = pcilt_fused_gemv_plan_pallas(xp, s2, p2, tp, tiles=tiles, **kw)
    return out[:B, :O]


def _fused_gemv_plan_bench(x, s2, p2, tables, cfg, kw):
    B, G, O = x.shape[0], tables.shape[0], tables.shape[-1]
    tiles = _fit_tiles((cfg.Bb, cfg.Gb, cfg.Ob), B, G, O)
    xp, _ = _pad_axis(x, 0, tiles[0])
    tp, _ = _pad_axis(tables, 2, tiles[2] if O >= 128 else 1)
    return lambda: pcilt_fused_gemv_plan_pallas(
        xp, s2, p2, tp, tiles=tiles, **kw
    ).block_until_ready()


def _seg_2d(seg_offset) -> jax.Array:
    """Segment offset as the ``[1, 1]`` int32 operand the conv kernels stage
    (0 when unsharded; the shard's first global segment under ``shard_map``)."""
    if seg_offset is None:
        return jnp.zeros((1, 1), jnp.int32)
    return jnp.asarray(seg_offset, jnp.int32).reshape(1, 1)


def pcilt_fused_conv2d(
    x: jax.Array,
    tables: jax.Array,
    spec,
    scale,
    group: int,
    kh: int,
    kw: int,
    stride: int = 1,
    padding: str = "SAME",
    tiles=None,
    autotune: Optional[bool] = None,
    seg_offset=None,
    n_total: Optional[int] = None,
) -> jax.Array:
    """x [B, H, W, C] float NHWC, tables [G, V, O] -> [B, Ho, Wo, O].

    The only host-side work is the spatial zero-pad of the raw activations;
    im2col happens on quantized codes inside VMEM (``pcilt_fused.py``), so
    neither the ``[B, Ho, Wo, kh*kw*C]`` float patch tensor nor the
    ``[B, Ho, Wo, G]`` int32 offset tensor is ever materialized in HBM.
    Tables must cover ``n_total = G * group >= kh*kw*C`` (alignment slots
    built from zero weights, as ``core.lut_layers.pcilt_conv2d`` does).

    Under ``shard_map`` (``core.lut_layers`` ``mesh=`` conv route) ``tables``
    is one device's ``[G/D, V, O]`` shard: pass ``seg_offset`` (the shard's
    first segment in global segment space — typically
    ``axis_index * G_local``) and ``n_total`` (the *global* padded reduction
    length) so the in-VMEM im2col slices this shard's patch columns.  The
    autotune shape key carries the local ``G`` as usual.
    """
    if padding == "SAME":
        x = jnp.pad(x, _conv_same_pads(x.shape[1], x.shape[2], kh, kw, stride))
    B, Hp, Wp, C = x.shape
    G, V, O = tables.shape
    Ho = (Hp - kh) // stride + 1
    Wo = (Wp - kw) // stride + 1
    key = atn.shape_key("fused_conv2d", dtype=tables.dtype,
                        backend=jax.default_backend(),
                        B=B, Ho=Ho, W=Wp, C=C, k=kh * kw, s=stride,
                        G=G, V=V, O=O, g=group, bits=spec.bits)
    s2 = _scale_2d(scale, x.dtype)
    seg2 = _seg_2d(seg_offset)
    kw_args = dict(bits=spec.bits, zero_point=spec.zero_point, group=group,
                   kh=kh, kw=kw, stride=stride,
                   n_total=int(n_total) if n_total else G * group,
                   interpret=not on_tpu())
    if tiles is None:
        cfg = atn.lookup(key)
        if cfg is None and atn.autotune_enabled(autotune) and _is_concrete(
                x, s2, seg2, tables):
            cfg = atn.tune(
                key,
                atn.conv2d_candidates(Ho, G, V, O, tables.dtype.itemsize,
                                      Wo=Wo),
                lambda c: _fused_conv2d_bench(x, s2, seg2, tables, c,
                                              kw_args, Ho),
            )
        if cfg is None:
            cfg = atn.conv2d_candidates(Ho, G, V, O, tables.dtype.itemsize,
                                        Wo=Wo)[0]
        tiles = (cfg.row_tile, cfg.Gb, cfg.Ob)
    Hb, Gb, Ob = _fit_conv_tiles(tiles, Ho, G, O)
    tp, _ = _pad_axis(tables, 2, Ob if O >= 128 else 1)
    out = pcilt_fused_conv2d_pallas(x, s2, seg2, tp, tiles=(Hb, Gb, Ob),
                                    **kw_args)
    return out[..., :O]


def _fused_conv2d_bench(x, s2, seg2, tables, cfg, kw_args, Ho):
    G, O = tables.shape[0], tables.shape[-1]
    Hb, Gb, Ob = _fit_conv_tiles((cfg.row_tile, cfg.Gb, cfg.Ob), Ho, G, O)
    tp, _ = _pad_axis(tables, 2, Ob if O >= 128 else 1)
    return lambda: pcilt_fused_conv2d_pallas(
        x, s2, seg2, tp, tiles=(Hb, Gb, Ob), **kw_args
    ).block_until_ready()


# ----------------------------------------------------------------------------
# Shared-pool fused pipeline (extension 3): pool + pointers in, indirection
# resolved in VMEM — the dense [G, V, O] tables never exist in HBM.
# ----------------------------------------------------------------------------


def pcilt_shared_gemv(
    x: jax.Array,
    pool: jax.Array,
    seg_idx: jax.Array,
    spec,
    scale,
    group: int,
    tiles=None,
    autotune: Optional[bool] = None,
) -> jax.Array:
    """x [B, n] float, pool [X, V, O], seg_idx [G] int32 (``n == G * group``)
    -> [B, O].

    The fused quantize→pack→fetch pipeline over the extension-3 shared pool;
    the per-shape tiling is dispatched through the autotune lookup table
    under a ``shared_gemv`` key that includes the pool cardinality ``X``.
    """
    B, n = x.shape
    X, V, O = pool.shape
    G = int(seg_idx.shape[-1])
    if n != G * group:
        raise ValueError(
            f"x trailing dim {n} != G*group = {G}*{group} (the shared-pool "
            f"kernel packs contiguous segments; generalized SegmentPlans are "
            f"rejected upstream at the core.lut_layers dispatch boundary)")
    key = atn.shape_key("shared_gemv", dtype=pool.dtype,
                        backend=jax.default_backend(),
                        B=B, G=G, V=V, O=O, X=X, g=group, bits=spec.bits)
    s2 = _scale_2d(scale, x.dtype)
    idx2 = seg_idx.astype(jnp.int32).reshape(1, G)
    kw = dict(bits=spec.bits, zero_point=spec.zero_point, group=group,
              interpret=not on_tpu())
    if tiles is None:
        cfg = atn.lookup(key)
        if cfg is None and atn.autotune_enabled(autotune) and _is_concrete(
                x, s2, idx2, pool):
            cfg = atn.tune(
                key,
                atn.shared_gemv_candidates(B, G, V, O, X,
                                           pool.dtype.itemsize),
                lambda c: _shared_gemv_bench(x, s2, idx2, pool, c, kw),
            )
        if cfg is None:
            # The staged pool is Gb-independent, but the in-kernel one-hot
            # scratch still scales with Gb — the untuned fallback must use
            # the VMEM-bounded heuristic (candidate 0), like every other
            # pipeline; "stage everything" is only reached via tuning, where
            # a compile rejection is skipped rather than fatal.
            cfg = atn.shared_gemv_candidates(B, G, V, O, X,
                                             pool.dtype.itemsize)[0]
        tiles = (cfg.Bb, cfg.Gb, cfg.Ob)
    tiles = _fit_tiles(tiles, B, G, O)
    xp, _ = _pad_axis(x, 0, tiles[0])  # zero rows quantize harmlessly
    pp, _ = _pad_axis(pool, 2, tiles[2] if O >= 128 else 1)
    out = pcilt_shared_gemv_pallas(xp, s2, idx2, pp, tiles=tiles, **kw)
    return out[:B, :O]


def _shared_gemv_bench(x, s2, idx2, pool, cfg, kw):
    B, O = x.shape[0], pool.shape[-1]
    G = idx2.shape[-1]
    tiles = _fit_tiles((cfg.Bb, cfg.Gb, cfg.Ob), B, G, O)
    xp, _ = _pad_axis(x, 0, tiles[0])
    pp, _ = _pad_axis(pool, 2, tiles[2] if O >= 128 else 1)
    return lambda: pcilt_shared_gemv_pallas(
        xp, s2, idx2, pp, tiles=tiles, **kw
    ).block_until_ready()


def pcilt_shared_conv2d(
    x: jax.Array,
    pool: jax.Array,
    seg_idx: jax.Array,
    spec,
    scale,
    group: int,
    kh: int,
    kw: int,
    stride: int = 1,
    padding: str = "SAME",
    tiles=None,
    autotune: Optional[bool] = None,
    seg_offset=None,
    n_total: Optional[int] = None,
) -> jax.Array:
    """x [B, H, W, C] float NHWC, pool [X, V, O], seg_idx [G] int32
    -> [B, Ho, Wo, O].

    The shared-pool sibling of :func:`pcilt_fused_conv2d`: same host-side
    spatial pad and in-VMEM im2col, with the dense table operand replaced by
    (pointers, pool).  ``n_total = G * group >= kh*kw*C`` (alignment slots
    must have been built from zero weights).  ``seg_offset`` / ``n_total``
    carry the shard's first global segment and the global padded reduction
    length under ``shard_map`` — the pool and pointers stay local.
    """
    if padding == "SAME":
        x = jnp.pad(x, _conv_same_pads(x.shape[1], x.shape[2], kh, kw, stride))
    B, Hp, Wp, C = x.shape
    X, V, O = pool.shape
    G = int(seg_idx.shape[-1])
    Ho = (Hp - kh) // stride + 1
    Wo = (Wp - kw) // stride + 1
    key = atn.shape_key("shared_conv2d", dtype=pool.dtype,
                        backend=jax.default_backend(),
                        B=B, Ho=Ho, W=Wp, C=C, k=kh * kw, s=stride,
                        G=G, V=V, O=O, X=X, g=group, bits=spec.bits)
    s2 = _scale_2d(scale, x.dtype)
    seg2 = _seg_2d(seg_offset)
    idx2 = seg_idx.astype(jnp.int32).reshape(1, G)
    kw_args = dict(bits=spec.bits, zero_point=spec.zero_point, group=group,
                   kh=kh, kw=kw, stride=stride,
                   n_total=int(n_total) if n_total else G * group,
                   interpret=not on_tpu())
    if tiles is None:
        cfg = atn.lookup(key)
        if cfg is None and atn.autotune_enabled(autotune) and _is_concrete(
                x, s2, seg2, idx2, pool):
            cfg = atn.tune(
                key,
                atn.shared_conv2d_candidates(Ho, G, V, O, X,
                                             pool.dtype.itemsize, Wo=Wo),
                lambda c: _shared_conv2d_bench(x, s2, seg2, idx2, pool, c,
                                               kw_args, Ho),
            )
        if cfg is None:
            cfg = atn.shared_conv2d_candidates(Ho, G, V, O, X,
                                               pool.dtype.itemsize, Wo=Wo)[0]
        tiles = (cfg.row_tile, cfg.Gb, cfg.Ob)
    Hb, Gb, Ob = _fit_conv_tiles(tiles, Ho, G, O)
    pp, _ = _pad_axis(pool, 2, Ob if O >= 128 else 1)
    out = pcilt_shared_conv2d_pallas(x, s2, seg2, idx2, pp,
                                     tiles=(Hb, Gb, Ob), **kw_args)
    return out[..., :O]


def _shared_conv2d_bench(x, s2, seg2, idx2, pool, cfg, kw_args, Ho):
    G, O = idx2.shape[-1], pool.shape[-1]
    Hb, Gb, Ob = _fit_conv_tiles((cfg.row_tile, cfg.Gb, cfg.Ob), Ho, G, O)
    pp, _ = _pad_axis(pool, 2, Ob if O >= 128 else 1)
    return lambda: pcilt_shared_conv2d_pallas(
        x, s2, seg2, idx2, pp, tiles=(Hb, Gb, Ob), **kw_args
    ).block_until_ready()
