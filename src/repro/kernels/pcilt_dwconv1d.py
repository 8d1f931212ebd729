"""Pallas TPU kernels: PCILT depthwise conv1d — one fetch per output element.

For a k-tap causal depthwise conv with activation cardinality K, the k input
codes of a channel pack into one offset and the whole tap-dot is a single
table cell:  ``out[b, t, c] = tables[c, offsets[b, t, c]]``.

This is the purest PCILT case on TPU (Mamba2 / Zamba2 conv frontends, k=4):
there is no reduction left.  Channels ride the 128-lane axis; time rides
sublanes.  Two kernels implement it:

* **host-packed** (``pcilt_dwconv1d_pallas``): the caller quantizes, stacks
  the causal tap window, and shift-or packs offsets on the host; the kernel
  is a blocked masked-sum "gather" (a ``fori_loop`` over the ``V`` table
  entries) with the per-channel tables staged in VMEM.
* **fused** (``pcilt_fused_dwconv1d_pallas``): raw float activations in —
  quantize, little-endian shift-or pack of the ``k`` causal taps, and the
  fetch all run in VMEM, so the ``[B, T, C]`` int32 offset tensor never
  touches HBM.  The wrapper lays the padded signal out **taps-major**,
  ``[k, To*B, C]`` (tap ``j`` of output row ``t*B + b`` is padded input
  ``t + j`` of batch ``b``; a decode window is just its transpose), so
  every tap is a leading-axis slice with rows on sublanes and channels on
  lanes.  The fetch is a select chain over the ``V`` table entries against
  the in-VMEM transposed ``[V, Cb]`` table: exactly one entry matches each
  output, so the result is the table cell bit-exactly (bf16 tables
  included).

The ``(Tb, Cb)`` tiling (``Tb`` time steps of every batch row per grid step)
is dispatched through the persistent autotune table under ``fused_dwconv1d``
keys (``ops.py`` / ``autotune.dwconv1d_candidates``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pcilt_fused import _call_with_stats, _quantize_f32, _update_stats

__all__ = ["pcilt_dwconv1d_pallas", "pcilt_fused_dwconv1d_pallas"]


def _kernel(off_ref, tab_ref, out_ref, *, V: int):
    _, Tb, Cb = off_ref.shape
    # For every offset value v: mask where off == v, add T[c, v].
    # Expressed as a V-step accumulation entirely on the VPU; V is small for
    # the depthwise case (K**k with K<=4, k=4 ⇒ V<=256).  Accumulate f32 and
    # cast once at the end — bf16 tables must not round through bf16 on every
    # loop step (same contract as the gemv/conv kernels).
    def body(v, acc):
        hit = (off_ref[0] == v).astype(jnp.float32)  # [Tb, Cb]
        return acc + hit * tab_ref[:, v][None, :].astype(jnp.float32)

    acc = jax.lax.fori_loop(0, V, body, jnp.zeros((Tb, Cb), jnp.float32))
    out_ref[0] = acc.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("time_tile", "interpret"))
def pcilt_dwconv1d_pallas(
    offsets: jax.Array,
    tables: jax.Array,
    time_tile: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """offsets ``[B, T, C]`` int32, tables ``[C, V]`` -> out ``[B, T, C]``."""
    B, T, C = offsets.shape
    C2, V = tables.shape
    if C != C2:
        raise ValueError(
            f"offsets channel dim {C} != tables channel dim {C2} "
            f"(offsets {offsets.shape}, tables {tables.shape})")
    Tb = min(time_tile, T)
    while T % Tb:
        Tb -= 1
    Cb = min(C, 128)
    while C % Cb:
        Cb -= 1
    grid = (B, T // Tb, C // Cb)
    return pl.pallas_call(
        functools.partial(_kernel, V=V),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, Tb, Cb), lambda b, i, j: (b, i, j)),
            pl.BlockSpec((Cb, V), lambda b, i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((1, Tb, Cb), lambda b, i, j: (b, i, j)),
        out_shape=jax.ShapeDtypeStruct((B, T, C), tables.dtype),
        interpret=interpret,
    )(offsets, tables)


# ----------------------------------------------------------------------------
# Fused pipeline: quantize + causal tap pack + fetch in VMEM
# ----------------------------------------------------------------------------

#: table entries per select slab: the fetch walks the transposed table in
#: ``[min(V, _SLAB), Cb]`` row slabs (static selects within a slab)
_SLAB = 256


def _fused_kernel(x_ref, scale_ref, tab_ref, out_ref, *rest, bits: int,
                  zero_point: int, k: int, V: int, B: int):
    """One ``(Tb*B, Cb)`` output tile from the taps-major ``[k, Tb*B, Cb]``
    block.

    With counters, two ``STAT_BLOCK`` outputs precede the transposed-table
    scratch in ``rest``.  Tap ``j`` of output ``t`` reads padded input
    ``t + j``, so adjacent outputs share ``k - 1`` inputs: the count keeps
    tap ``k - 1`` everywhere and the other taps only on the first time step
    (rows ``< B`` of time tile 0) — every padded input row counted exactly
    once, and the zero pads quantize in range.  ``max`` is idempotent; the
    ratio folds every tap.
    """
    *stat_refs, tabT_ref = rest
    i, j = pl.program_id(0), pl.program_id(1)
    scale = scale_ref[...]
    off = None
    for t in range(k):
        xt = x_ref[t]  # [Rb, Cb]
        q, codes = _quantize_f32(xt, scale, bits=bits, zero_point=zero_point)
        if stat_refs:
            rows = jax.lax.broadcasted_iota(jnp.int32, xt.shape, 0)
            keep = None if t == k - 1 else (i == 0) & (rows < B)
            _update_stats(*stat_refs, q, xt, scale, bits=bits,
                          first=(i == 0) & (j == 0) & (t == 0), keep=keep)
        c = codes.astype(jnp.int32) << (t * bits)
        off = c if off is None else off + c
    tabT_ref[...] = jnp.transpose(tab_ref[...].astype(jnp.float32))
    slab = min(V, _SLAB)

    def fetch(h, acc):
        base = pl.multiple_of(h * slab, slab)
        rows = tabT_ref[pl.ds(base, slab), :]  # [slab, Cb]
        for v in range(slab):
            acc = jnp.where(off == base + v, rows[v:v + 1, :], acc)
        return acc

    acc = jax.lax.fori_loop(0, V // slab, fetch,
                            jnp.zeros(off.shape, jnp.float32))
    out_ref[...] = acc.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bits", "zero_point", "k",
                                             "tiles", "counters", "interpret"))
def pcilt_fused_dwconv1d_pallas(
    x: jax.Array,
    scale: jax.Array,
    tables: jax.Array,
    *,
    bits: int,
    zero_point: int,
    k: int,
    tiles,
    counters: bool = False,
    interpret: bool = False,
):
    """x ``[B, Tp, C]`` float (already time-padded: ``Tp = To + k - 1``),
    scale ``[1, 1]``, tables ``[C, V]`` (``V = 2**(bits*k)``) -> ``[B, To, C]``.

    Each grid step quantizes its ``[k, Tb*B, Cb]`` taps-major block, packs
    the ``k`` causal taps, and fetches — offsets never exist outside VMEM.
    ``tiles`` is a ``(Tb, Cb)`` tuple with ``Tb | To`` and ``Cb | C``.

    ``counters=True`` (a static opt-in: the default trace is unchanged)
    returns ``(out, count, ratio)`` — the int32 number of signal elements
    the quantizer clipped and the f32 ``max(|x|)/scale`` overshoot, reduced
    in VMEM by the same kernel.
    """
    B, Tp, C = x.shape
    C2, V = tables.shape
    if C != C2:
        raise ValueError(
            f"x channel dim {C} != tables channel dim {C2} "
            f"(x {x.shape}, tables {tables.shape})")
    To = Tp - k + 1
    Tb, Cb = tiles
    taps = jnp.stack([x[:, j:j + To] for j in range(k)])  # [k, B, To, C]
    taps = jnp.transpose(taps, (0, 2, 1, 3)).reshape(k, To * B, C)
    out = _call_with_stats(
        functools.partial(_fused_kernel, bits=bits, zero_point=zero_point,
                          k=k, V=V, B=B),
        counters, interpret, tables.dtype,
        grid=(To // Tb, C // Cb),
        in_specs=[
            pl.BlockSpec((k, Tb * B, Cb), lambda i, j: (0, i, j)),
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
            pl.BlockSpec((Cb, V), lambda i, j: (j, 0)),
        ],
        out_spec=pl.BlockSpec((Tb * B, Cb), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((To * B, C), jnp.float32),
        scratch_shapes=[pltpu.VMEM((V, Cb), jnp.float32)],
        name="pcilt_dwconv1d",
    )(taps, scale, tables)
    rest = ()
    if counters:
        out, *rest = out
    out = jnp.transpose(out.reshape(To, B, C), (1, 0, 2))
    return (out, *rest) if counters else out
