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
      out[r, :]       += sum_v counts_v @ pool_t[v]

  where ``pool_t`` is the pool staged **pre-transposed** to ``[V, X, Ob]``
  (done once per call by the wrapper) so each offset value's ``[X, Ob]``
  slab is a leading-axis slice.  The fetch contraction therefore scales
  with the pool cardinality ``X``, not the segment count ``G``, mirroring
  exactly how ext. 3 makes the table *memory* scale with ``X``.  No
  data-dependent addressing reaches the memory system (compares + 2-D
  matmuls, TPU-friendly);
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

from .pcilt_fused import _precision, _quantize_f32, _strip_codes, pack_matrix

__all__ = ["pcilt_shared_gemv_pallas", "pcilt_shared_conv2d_pallas"]


def _pool_counts_dot(codes, pack_ref, idx_ref, pool_ref, *, V: int, X: int):
    """The pooled fetch: f32 ``codes [R, Gb*group]``, pointer row
    ``idx_ref [1, Gb]``, pre-transposed pool tile ``pool_ref [V, X, Ob]``
    -> f32 ``[R, Ob]``.

    Every segment pointing at pool row ``x`` with offset ``v`` fetches the
    *same* cell, so the adder tree over this grid step's ``Gb`` segments is
    ``sum_v counts_v @ pool_t[v]``: count how many segments land on each
    ``(v, x)`` cell (an ``[R, Gb] x [X, Gb]^T`` contraction of 0/1 values,
    exact at any precision), then one ``[R, X] x [X, Ob]`` contraction per
    offset value.  Counts are small integers in f32 (exact up to 2**24 ≫
    any Gb); f32 pools contract at full precision.
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
        pool_v = pool_ref[v]
        part = jnp.dot(counts.astype(pool_v.dtype), pool_v,
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

    ``n == G * group``; B, O are padded to tile multiples by ``ops.py``;
    ``tiles`` is a ``(Bb, Gb, Ob)`` tuple with ``Gb | G``.  The whole pool is
    staged per output tile (pre-transposed to ``[V, X, Ob]`` so each offset
    value's slab is a leading-axis slice); only the ``[Gb]`` pointer block
    walks the G axis.
    """
    B, n = x.shape
    G = seg_idx.shape[-1]
    X, V, O = pool.shape
    if n != G * group:
        raise ValueError(
            f"x trailing dim {n} != G*group = {G}*{group} "
            f"(x {x.shape}, seg_idx {seg_idx.shape}, pool {pool.shape})")
    pool_t = jnp.transpose(pool, (1, 0, 2))  # [V, X, O], once per call
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
            pl.BlockSpec((V, X, Ob), lambda i, j, k: (0, 0, j)),
        ],
        out_specs=pl.BlockSpec((Bb, Ob), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((B, O), jnp.float32),
        interpret=interpret,
        name="pcilt_shared_gemv",
    )(x, scale, pack, seg_idx, pool_t).astype(pool.dtype)


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
    pool_t = jnp.transpose(pool, (1, 0, 2))  # [V, X, O], once per call
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
            pl.BlockSpec((V, X, Ob), lambda b, r, j, k: (0, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, Hb, Wo, Ob), lambda b, r, j, k: (b, r, 0, j)),
        out_shape=jax.ShapeDtypeStruct((B, Ho, Wo, O), jnp.float32),
        interpret=interpret,
    )(x, scale, seg_offset, pack, seg_idx, pool_t).astype(pool.dtype)
