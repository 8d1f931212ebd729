"""Fused Pallas kernels for shared-pool PCILTs (paper extension 3).

Extension 3 keeps "only one PCILT for given algorithm base value(s) and
replace[s] the others with pointers to it".  At segment granularity that is a
deduped pool ``pool[X, V, O]`` of unique segment tables plus an integer
pointer vector ``seg_idx[G]`` mapping each of the ``G`` segments onto its pool
row (``core.pcilt.SharedGroupedTables``).  The dense-fused kernels
(``pcilt_fused.py``) cannot consume that representation — they would force a
``materialize()`` back to the full ``[G, V, O]`` tables in HBM, forfeiting the
entire ext.-3 memory win before the first fetch.

The kernels here stage **the pool and the pointers, never the dense tables**:

* the ``[X, V, Ob]`` pool tile and the ``[Gb]`` pointer block live in VMEM
  (``X << G`` is the whole point — the staged bytes scale with the weights'
  *actual* segment cardinality, so even "stage every group" tilings fit);
* the pointer indirection is resolved *inside* the kernel by accumulating the
  activation one-hot into **pool space**: every segment pointing at pool row
  ``x`` with offset ``v`` fetches the *same* table cell, so the fetch-and-add
  over this grid step's ``Gb`` segments collapses to a multiplicity count
  followed by one small contraction per offset value::

      oh_v[r, g]       = (off[r, g] == v)          # [R, Gb] per v
      sel[x, g]        = (seg_idx[g] == x)         # [X, Gb] — tiny
      counts_v[r, x]   = sum_g oh_v[r, g] * sel[x, g]
      out[r, o]       += sum_v sum_x counts_v[r, x] * pool[x, v, o]

  The kernel reads the pool **where it lies**: the TPU stores a pool whose
  row count ``X`` fills whole 128-lane tiles (both served heads) with its
  rows minor, ``[V, O, X]`` in memory, so the wrapper hands the kernel that
  view (``transpose(pool, (1, 2, 0))``, a bitcast there) and each offset
  value's ``[Ob, X]`` slab is a leading-axis slice of the ``[V, Ob, X]``
  tile.  Nothing rewrites or pads the pool per call; the last output tile
  may run past ``O`` (its extra columns are dropped).  The fetch
  contraction scales with the pool cardinality ``X``, not the segment
  count ``G``, mirroring exactly how ext. 3 makes the table *memory* scale
  with ``X``.  No data-dependent addressing reaches the memory system
  (compares + 2-D matmuls, TPU-friendly);
* the activation side is the dense-fused pipeline's — quantize in VMEM and
  pack each segment's codes with one exact integer contraction (helpers
  imported from ``pcilt_fused``) — and counts are small integers built in
  f32 (exact up to 2**24 ≫ any Gb), so ``path="shared"`` matches the gather
  reference to f32 summation-order tolerance.

Tiling comes from the caller (``ops.py``) via the persistent autotune lookup
table under the ``shared_gemv`` / ``shared_conv2d`` shape keys, which include
the pool cardinality ``X`` (``autotune.shared_*_candidates``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .autotune import _round_up
from .pcilt_fused import _precision, _quantize_f32, _strip_codes, pack_matrix

__all__ = ["pcilt_shared_gemv_pallas", "pcilt_shared_conv2d_pallas"]


def _pool_counts_dot(codes, pack_ref, idx_ref, pool_ref, *, V: int, X: int):
    """The pooled fetch: f32 ``codes [R, Gb*group]``, pointer row
    ``idx_ref [1, Gb]``, pool tile ``pool_ref [V, Ob, X]`` -> f32
    ``[R, Ob]``.

    Every segment pointing at pool row ``x`` with offset ``v`` fetches the
    *same* cell, so the adder tree over this grid step's ``Gb`` segments is
    ``sum_v counts_v @ pool_ref[v]^T``: count how many segments land on
    each ``(v, x)`` cell (an ``[R, Gb] x [X, Gb]^T`` contraction of 0/1
    values, exact at any precision), then one ``[R, X] x [Ob, X]^T``
    contraction per offset value.  Counts are small integers in f32 (exact
    up to 2**24 ≫ any Gb); f32 pools contract at full precision.
    """
    off = jnp.dot(codes, pack_ref[...],
                  preferred_element_type=jnp.float32)  # [R, Gb] offsets
    Gb = off.shape[1]
    sel = (jax.lax.broadcasted_iota(jnp.int32, (X, Gb), 0) == idx_ref[...]
           ).astype(jnp.float32)  # [X, Gb]
    acc = None
    for v in range(V):
        oh = (off == float(v)).astype(jnp.float32)  # [R, Gb]
        counts = jax.lax.dot_general(
            oh, sel, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)  # [R, X]
        pool_v = pool_ref[v]  # [Ob, X]
        part = jax.lax.dot_general(
            counts.astype(pool_v.dtype), pool_v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=_precision(pool_v.dtype))
        acc = part if acc is None else acc + part
    return acc


# ----------------------------------------------------------------------------
# Shared-pool fused GEMV
# ----------------------------------------------------------------------------


def _gemv_kernel(x_ref, scale_ref, pack_ref, idx_ref, pool_ref, out_ref, *,
                 bits: int, zero_point: int, V: int, X: int):
    @pl.when(pl.program_id(2) == 0)
    def _zero():
        out_ref[...] = jnp.zeros_like(out_ref)

    _, codes = _quantize_f32(x_ref[...], scale_ref[...], bits=bits,
                             zero_point=zero_point)  # [Bb, Gb*group]
    out_ref[...] += _pool_counts_dot(codes, pack_ref, idx_ref, pool_ref,
                                     V=V, X=X)


@functools.partial(
    jax.jit,
    static_argnames=("bits", "zero_point", "group", "tiles", "interpret"),
)
def pcilt_shared_gemv_pallas(
    x: jax.Array,
    scale: jax.Array,
    seg_idx: jax.Array,
    pool: jax.Array,
    *,
    bits: int,
    zero_point: int,
    group: int,
    tiles,
    interpret: bool = False,
) -> jax.Array:
    """x ``[B, n]`` float, scale ``[1, 1]``, seg_idx ``[1, G]`` int32,
    pool ``[X, V, O]`` -> ``[B, O]``.

    ``n == G * group``; B is padded to a tile multiple by ``ops.py``;
    ``tiles`` is a ``(Bb, Gb, Ob)`` tuple with ``Gb | G``.  Every pool row
    is staged per output tile, as the ``[V, Ob, X]`` tile of the pool's
    stored ``[V, O, X]`` order; only the ``[Gb]`` pointer block walks the G
    axis.  The last output tile may be ragged.
    """
    B, n = x.shape
    G = seg_idx.shape[-1]
    X, V, O = pool.shape
    if n != G * group:
        raise ValueError(
            f"x trailing dim {n} != G*group = {G}*{group} "
            f"(x {x.shape}, seg_idx {seg_idx.shape}, pool {pool.shape})")
    pool_t = jnp.transpose(pool, (1, 2, 0))  # [V, O, X]: as stored
    Bb, Gb, Ob = tiles
    pack = pack_matrix(Gb, group, bits, 1)  # codes -> [Gb] offsets
    grid = (pl.cdiv(B, Bb), pl.cdiv(O, Ob), G // Gb)
    return pl.pallas_call(
        functools.partial(_gemv_kernel, bits=bits, zero_point=zero_point,
                          V=V, X=X),
        grid=grid,
        in_specs=[
            pl.BlockSpec((Bb, Gb * group), lambda i, j, k: (i, k)),
            pl.BlockSpec((1, 1), lambda i, j, k: (0, 0)),
            pl.BlockSpec(pack.shape, lambda i, j, k: (0, 0)),
            pl.BlockSpec((1, Gb), lambda i, j, k: (0, k)),
            pl.BlockSpec((V, Ob, X), lambda i, j, k: (0, j, 0)),
        ],
        out_specs=pl.BlockSpec((Bb, Ob), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((B, O), jnp.float32),
        interpret=interpret,
        name="pcilt_shared_gemv",
        **_vmem_limit(Bb, Gb, Ob, V, X, group, pool.dtype.itemsize),
    )(x, scale, pack, seg_idx, pool_t).astype(pool.dtype)


#: VMEM a Pallas call may use on the chip unless it asks for more
SCOPED_VMEM = 16 * 2**20


def _vmem_limit(Bb, Gb, Ob, V, X, group, itemsize):
    """The compiler parameter raising the call's VMEM limit when its
    double-buffered ``[V, Ob, X]`` pool tile (``X`` on lanes, padded to
    whole 128-lane tiles) and per-step scratch need more than the default
    scoped VMEM (a pool of many rows); none otherwise."""
    tile = V * _round_up(Ob, 8) * _round_up(X, 128) * itemsize
    need = (2 * (tile + Bb * Gb * group * 4 + Bb * Ob * 4)
            + Bb * V * X * 4 + X * Gb * 4 + Gb * group * Gb * 4)
    if need <= SCOPED_VMEM * 3 // 4:
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        vmem_limit_bytes=min(need + SCOPED_VMEM, 100 * 2**20))}


# ----------------------------------------------------------------------------
# Shared-pool fused conv2d
# ----------------------------------------------------------------------------


def _conv_kernel(x_ref, scale_ref, seg_ref, pack_ref, idx_ref, pool_ref,
                 out_ref, *, bits: int, zero_point: int, group: int,
                 kh: int, kw: int, stride: int,
                 Gb: int, V: int, X: int, Hb: int, n_pad: int):
    @pl.when(pl.program_id(3) == 0)
    def _zero():
        out_ref[...] = jnp.zeros_like(out_ref)

    codes = _strip_codes(x_ref, scale_ref, seg_ref,
                         bits=bits, zero_point=zero_point,
                         group=group, kh=kh, kw=kw, stride=stride,
                         Gb=Gb, Hb=Hb, n_pad=n_pad)  # [Hb*Wo, Gb*group]
    acc = _pool_counts_dot(codes, pack_ref, idx_ref, pool_ref, V=V, X=X)
    out_ref[...] += acc.reshape(out_ref.shape)  # [Hb*Wo, Ob] f32


@functools.partial(
    jax.jit,
    static_argnames=("bits", "zero_point", "group", "kh", "kw", "stride",
                     "n_total", "tiles", "interpret"),
)
def pcilt_shared_conv2d_pallas(
    x: jax.Array,
    scale: jax.Array,
    seg_offset: jax.Array,
    seg_idx: jax.Array,
    pool: jax.Array,
    *,
    bits: int,
    zero_point: int,
    group: int,
    kh: int,
    kw: int,
    stride: int = 1,
    n_total: int = 0,
    tiles=None,
    interpret: bool = False,
) -> jax.Array:
    """x ``[B, Hp, Wp, C]`` float (already spatially padded), scale ``[1, 1]``,
    seg_offset ``[1, 1]`` int32, seg_idx ``[1, G]`` int32, pool ``[X, V, O]``
    -> ``[B, Ho, Wo, O]``.

    Same contract as ``pcilt_fused_conv2d_pallas`` with the dense ``[G, V, O]``
    table operand replaced by (pointers, pool); ``tiles`` is ``(Hb, Gb, Ob)``
    with ``Gb | G`` and ``Hb | Ho``.  ``seg_offset`` / ``n_total`` carry the
    shard's first global segment and the global padded reduction length under
    ``shard_map`` (0 / ``G * group`` when unsharded): pointers stay *local*
    to the staged pool, only the activation-side im2col slice is global.
    """
    B, Hp, Wp, C = x.shape
    G = seg_idx.shape[-1]
    X, V, O = pool.shape
    n = kh * kw * C
    n_tot = n_total or G * group
    if n_tot < max(n, G * group):
        raise ValueError(
            f"n_total {n_tot} must cover the patch length kh*kw*C = {n} "
            f"and the table span G*group = {G}*{group} "
            f"(x {x.shape}, seg_idx {seg_idx.shape}, pool {pool.shape})")
    pool_t = jnp.transpose(pool, (1, 2, 0))  # [V, O, X]: as stored
    Ho = (Hp - kh) // stride + 1
    Wo = (Wp - kw) // stride + 1
    Hb, Gb, Ob = tiles
    pack = pack_matrix(Gb, group, bits, 1)  # codes -> [Gb] offsets
    grid = (B, Ho // Hb, pl.cdiv(O, Ob), G // Gb)
    return pl.pallas_call(
        functools.partial(_conv_kernel, bits=bits, zero_point=zero_point,
                          group=group, kh=kh, kw=kw, stride=stride,
                          Gb=Gb, V=V, X=X, Hb=Hb, n_pad=n_tot - n),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, Hp, Wp, C), lambda b, r, j, k: (b, 0, 0, 0)),
            pl.BlockSpec((1, 1), lambda b, r, j, k: (0, 0)),
            pl.BlockSpec((1, 1), lambda b, r, j, k: (0, 0)),
            pl.BlockSpec(pack.shape, lambda b, r, j, k: (0, 0)),
            pl.BlockSpec((1, Gb), lambda b, r, j, k: (0, k)),
            pl.BlockSpec((V, Ob, X), lambda b, r, j, k: (0, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, Hb, Wo, Ob), lambda b, r, j, k: (b, r, 0, j)),
        out_shape=jax.ShapeDtypeStruct((B, Ho, Wo, O), jnp.float32),
        interpret=interpret,
    )(x, scale, seg_offset, pack, seg_idx, pool_t).astype(pool.dtype)
