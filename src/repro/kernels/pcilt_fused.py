"""Fused Pallas TPU kernels: quantize -> offset-pack -> table-fetch in VMEM.

The host-packed pipeline (``pcilt_gemv.py`` / ``pcilt_conv2d.py``) quantizes,
im2col-packs, and bit-packs offsets *on the host*, materializing a
``[..., G]`` int32 offset tensor in HBM that the kernel then re-reads — for a
conv that tensor is ``[B, Ho, Wo, kh*kw*Cin/group]`` and routinely larger than
the activations themselves.  The kernels here fuse the whole paper pipeline
(Fig. 6: quantize, shift/mask pack, fetch, adder tree) into one ``pallas_call``
over the *raw float activations*, so the offsets live only in VMEM/registers:

* **quantize** — ``clip(round(x / scale) + zero_point, 0, K-1)``, bit-exact
  with ``core.quantization.quantize`` (same round-half-even, same clip);
* **pack** — little-endian shift-or of ``group`` codes per segment, bit-exact
  with ``core.offsets.pack_offsets``, computed as one exact integer
  contraction against a constant pack matrix (``pack_matrix``) that also
  repeats each offset across its segment's ``V`` one-hot lanes;
* **fetch + adder tree** — one *flattened* one-hot contraction per staged
  table tile: instead of a ``fori_loop`` of ``Gb`` small ``[Bb,V] x [V,Ob]``
  dots, the one-hot is laid out as ``[Bb, Gb*V]`` (segment-major) against
  the ``[Gb*V, Ob]`` tile of the free ``[G*V, O]`` table view, so the MXU
  runs large contractions per grid step.  The adder tree over group tiles
  is grid accumulation on the revisited output block.

Every step is a 2-D matmul, compare or select: the chip's compiler refuses
lane-splitting reshapes and scalar stores to VMEM, so the kernels have
neither (saturation counters are lane-dense vector tiles).

Tables may be stored **bf16** (pass ``tables.astype(jnp.bfloat16)``): the
one-hot is built in the table dtype, the contraction *and* the cross-tile
accumulation run in f32 (f32 ``preferred_element_type`` into an f32 output
block, cast to the table dtype once at the end), and the staged-tile VMEM
cost halves — doubling the groups per stage under the same ~8 MB budget
(``autotune._fit_gb`` is itemsize-aware).

Tiling is supplied by the caller (``ops.py``), which consults the persistent
autotune lookup table (``autotune.py``) — cache hit ⇒ zero-cost dispatch,
miss ⇒ the VMEM-budget heuristic, optionally tune-once-and-record.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["pcilt_fused_gemv_pallas", "pcilt_fused_gemv_stacked_pallas",
           "pcilt_fused_gemv_paired_pallas",
           "pcilt_fused_gemv_paired_stacked_pallas",
           "pcilt_fused_gemv_plan_pallas",
           "pcilt_fused_conv2d_pallas"]


def _quantize_f32(x, scale, *, bits: int, zero_point: int):
    """In-kernel mirror of ``core.quantization.quantize``: ``(q, codes)``,
    the pre-clip code ``round(x/scale) + zero_point`` and the clipped code,
    both f32 (small integers, exact — same round-half-even, same clip) so
    the codes can feed the MXU pack."""
    q = jnp.round(x / scale) + zero_point
    return q, jnp.clip(q, 0, (1 << bits) - 1)


#: Counter outputs are one lane-dense ``(8, 128)`` tile each: every element
#: holds the same running value (the caller reads ``[0, 0]``).  Vector
#: stores only — Mosaic cannot store a scalar to VMEM.
STAT_BLOCK = (8, 128)


def _stat_outputs():
    """``(out_specs, out_shapes)`` of the (count, ratio) counter outputs,
    resident across the whole grid (constant index map)."""
    const = (lambda *_: (0, 0))
    return ((pl.BlockSpec(STAT_BLOCK, const), pl.BlockSpec(STAT_BLOCK, const)),
            (jax.ShapeDtypeStruct(STAT_BLOCK, jnp.int32),
             jax.ShapeDtypeStruct(STAT_BLOCK, jnp.float32)))


def _update_stats(cnt_ref, ratio_ref, q, x, scale, *, bits: int, first,
                  count=True, keep=None):
    """Fold one block's saturation stats into the counter outputs.

    ``q`` is the pre-clip code: an element saturates when it leaves
    ``[0, K)`` (elements landing on the clip edge are in range, so the count
    is exact).  ``first`` (traced bool) zeroes the counters; ``count``
    (traced bool) gates the count — a block revisited once per output tile
    must be counted once — and ``keep`` optionally masks elements out of
    it.  The ratio is ``max(|x|)/scale``; ``max`` is idempotent, so it folds
    every step."""
    @pl.when(first)
    def _zero():
        cnt_ref[...] = jnp.zeros_like(cnt_ref)
        ratio_ref[...] = jnp.zeros_like(ratio_ref)

    sat = ((q < 0) | (q > (1 << bits) - 1)).astype(jnp.int32)
    if keep is not None:
        sat = jnp.where(keep, sat, 0)
    c = jnp.sum(jnp.sum(sat, axis=1, keepdims=True), axis=0, keepdims=True)

    @pl.when(count)
    def _count():
        cnt_ref[...] += jnp.broadcast_to(c, cnt_ref.shape)

    r = jnp.max(jnp.max(jnp.abs(x), axis=1, keepdims=True), axis=0,
                keepdims=True) / scale
    ratio_ref[...] = jnp.maximum(ratio_ref[...],
                                 jnp.broadcast_to(r, ratio_ref.shape))


def _pack_flat(codes, *, bits: int, group: int, Gseg: int):
    """``[R, Gseg*group]`` codes -> ``[R, Gseg]`` little-endian offsets."""
    R = codes.shape[0]
    c = codes.reshape(R, Gseg, group)
    shifts = jax.lax.broadcasted_iota(jnp.int32, (1, 1, group), 2) * bits
    return jnp.sum(jnp.left_shift(c, shifts), axis=-1)  # [R, Gseg]


def pack_chunk(Gb: int, group: int, V: int, budget: int = 1 << 20) -> int:
    """Segments per pack chunk of the one-hot fetch (:func:`_onehot_fetch`).

    The pack matrix of a chunk of ``Gs`` segments is ``[Gs*group, Gs*V]``
    f32, resident in VMEM.  A chunk's code columns must be whole 128-lane
    tiles (``Gs*group % 128 == 0``) or the whole tile, so the chip slices
    them without relayout: the largest such divisor of ``Gb`` whose matrix
    fits ``budget`` wins, else the smallest such divisor."""
    aligned = [d for d in range(Gb, 0, -1)
               if Gb % d == 0 and (d == Gb or (d * group) % 128 == 0)]
    fits = [d for d in aligned if d * group * d * V * 4 <= budget]
    return fits[0] if fits else aligned[-1]


def pack_matrix(Gs: int, group: int, bits: int, V: int) -> jax.Array:
    """``[Gs*group, Gs*V]`` f32: column ``g*V + v`` of ``codes @ M`` is the
    packed little-endian offset of segment ``g`` (row ``g*group + j``
    carries ``2**(bits*j)`` into segment ``g``'s ``V`` columns) — the
    offset, repeated across the segment's one-hot lanes."""
    r = jnp.arange(Gs * group)[:, None]
    c = jnp.arange(Gs * V)[None, :]
    w = jnp.left_shift(1, bits * (r % group)).astype(jnp.float32)
    return jnp.where(c // V == r // group, w, 0.0)


def _onehot_fetch(codes, pack_ref, tab_ref, lead=(), *, V: int):
    """The fused fetch: clipped f32 ``codes [R, Gb*group]`` against the
    staged ``[Gb*V, Ob]`` table tile (``tab_ref[lead]``) -> f32 ``[R, Ob]``.

    Per chunk of ``Gs`` segments: one exact integer contraction packs the
    codes into each segment's offset, repeated across its ``V`` one-hot
    lanes (``codes @ pack_matrix`` — codes and powers of two are exact in
    any MXU precision), the compare against ``lane % V`` builds the
    ``[R, Gs*V]`` one-hot, and one MXU contraction fetches and sums.  Two
    2-D matmuls and a compare: no lane-splitting reshape, which the chip's
    compiler refuses.  f32 tables contract at full precision, so a fetch
    returns the table cell exactly; the adder tree over group tiles is grid
    accumulation on the revisited output block."""
    R = codes.shape[0]
    GsV = pack_ref.shape[1]
    Gsg = pack_ref.shape[0]
    n_chunks = codes.shape[1] // Gsg
    lanes = (jax.lax.broadcasted_iota(jnp.int32, (R, GsV), 1) & (V - 1)
             ).astype(jnp.float32)
    pack = pack_ref[...]
    acc = None
    for s in range(n_chunks):
        off = jnp.dot(codes[:, s * Gsg:(s + 1) * Gsg], pack,
                      preferred_element_type=jnp.float32)
        tab = tab_ref[(*lead, slice(s * GsV, (s + 1) * GsV), slice(None))]
        oh = (off == lanes).astype(tab.dtype)
        part = jnp.dot(oh, tab, preferred_element_type=jnp.float32,
                       precision=_precision(tab.dtype))
        acc = part if acc is None else acc + part
    return acc


def _precision(dtype):
    """Full precision for f32 table contractions (TPU's default f32 matmul
    rounds operands to bf16); bf16 tables are exact at default."""
    return jax.lax.Precision.HIGHEST if dtype == jnp.float32 else None


def _take_rows(off, tab):
    """The row-gather fetch: ``off [R, Gb]``, ``tab [Gb, Vt, Ob]`` -> f32
    ``[R, Ob]``.

    The paired-table fetch is literal fetch-and-add — the paper's
    hardware-regime execution model — rather than the dense path's one-hot
    contraction: at ``Vt = V**2`` lanes the one-hot matrix is ``V``-times
    wider than the dense kernel's and the MXU contraction cost explodes
    exactly where the table got cheaper.  ``take_along_axis`` with a
    *constant* segment index (the leading ``Gb`` axis is iota — never
    traced data) lowers to the backend's batched row-gather fast path; the
    adder tree is the f32 sum over the segment axis.  No dot, so bf16
    tables promote to f32 only at the accumulate.
    """
    fetched = jnp.take_along_axis(
        tab, off.T[:, :, None].astype(jnp.int32), axis=1)  # [Gb, R, Ob]
    return jnp.sum(fetched.astype(jnp.float32), axis=0)


# ----------------------------------------------------------------------------
# Fused GEMV
# ----------------------------------------------------------------------------


def _gemv_kernel(x_ref, scale_ref, pack_ref, tab_ref, out_ref, *,
                 bits: int, zero_point: int, V: int):
    @pl.when(pl.program_id(2) == 0)
    def _zero():
        out_ref[...] = jnp.zeros_like(out_ref)

    _, codes = _quantize_f32(x_ref[...], scale_ref[...], bits=bits,
                             zero_point=zero_point)  # [Bb, Gb*group]
    # The output block is f32 regardless of table dtype, so the adder tree
    # over G tiles never rounds through bf16 (caller casts once at the end).
    out_ref[...] += _onehot_fetch(codes, pack_ref, tab_ref, V=V)


def _pack_operand(Gb: int, group: int, bits: int, V: int):
    """The pack matrix operand for a ``Gb``-segment table tile."""
    return pack_matrix(pack_chunk(Gb, group, V), group, bits, V)


@functools.partial(
    jax.jit,
    static_argnames=("bits", "zero_point", "group", "tiles", "interpret"),
)
def pcilt_fused_gemv_pallas(
    x: jax.Array,
    scale: jax.Array,
    tables: jax.Array,
    *,
    bits: int,
    zero_point: int,
    group: int,
    tiles,
    interpret: bool = False,
) -> jax.Array:
    """x ``[B, n]`` float, scale ``[1, 1]``, tables ``[G, V, O]`` -> ``[B, O]``.

    ``n == G * group``; B, O are padded to tile multiples by ``ops.py``;
    ``tiles`` is a ``(Bb, Gb, Ob)`` tuple with ``Gb | G``.
    """
    B, n = x.shape
    G, V, O = tables.shape
    if n != G * group:
        raise ValueError(
            f"x trailing dim {n} != G*group = {G}*{group} "
            f"(x {x.shape}, tables {tables.shape})")
    Bb, Gb, Ob = tiles
    pack = _pack_operand(Gb, group, bits, V)
    grid = (pl.cdiv(B, Bb), pl.cdiv(O, Ob), G // Gb)
    return pl.pallas_call(
        functools.partial(_gemv_kernel, bits=bits, zero_point=zero_point,
                          V=V),
        grid=grid,
        in_specs=[
            pl.BlockSpec((Bb, Gb * group), lambda i, j, k: (i, k)),
            pl.BlockSpec((1, 1), lambda i, j, k: (0, 0)),
            pl.BlockSpec(pack.shape, lambda i, j, k: (0, 0)),
            pl.BlockSpec((Gb * V, Ob), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((Bb, Ob), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((B, O), jnp.float32),
        interpret=interpret,
    )(x, scale, pack, tables.reshape(G * V, O)).astype(tables.dtype)


# ----------------------------------------------------------------------------
# Paired (TL1-style multi-scalar) fused GEMV: two segments per fetch.
# ----------------------------------------------------------------------------


def _gemv_paired_kernel(x_ref, scale_ref, tab_ref, out_ref, *stat_refs,
                        bits: int, zero_point: int, group: int, Gb: int):
    """Paired fetch; with ``stat_refs`` (count, ratio) the call also
    reduces the saturation counters (see :func:`_gemv_stacked_kernel`)."""
    i, j, k = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(k == 0)
    def _zero():
        out_ref[...] = jnp.zeros_like(out_ref)

    x, scale = x_ref[...], scale_ref[...]
    q, codes = _quantize_f32(x, scale, bits=bits, zero_point=zero_point)
    if stat_refs:
        _update_stats(*stat_refs, q, x, scale, bits=bits,
                      first=(i == 0) & (j == 0) & (k == 0), count=j == 0)
    # Packing 2*group codes little-endian IS the paired index
    # off_even + off_odd * V (V = 2**(bits*group)) — the same arithmetic
    # `build_paired_tables` indexes its [G/2, V**2, O] entries by, so the
    # in-kernel pack emits the paired offset directly.
    off = _pack_flat(codes.astype(jnp.int32), bits=bits, group=2 * group,
                     Gseg=Gb)  # [Bb, Gb]
    out_ref[...] += _take_rows(off, tab_ref[...])


@functools.partial(
    jax.jit,
    static_argnames=("bits", "zero_point", "group", "tiles", "counters",
                     "interpret"),
)
def pcilt_fused_gemv_paired_pallas(
    x: jax.Array,
    scale: jax.Array,
    tables: jax.Array,
    *,
    bits: int,
    zero_point: int,
    group: int,
    tiles,
    counters: bool = False,
    interpret: bool = False,
):
    """x ``[B, n]`` float, scale ``[1, 1]``, paired tables ``[G2, V2, O]``
    (``V2 = (2**(bits*group))**2``) -> ``[B, O]``.

    The TL1-style multi-scalar variant of :func:`pcilt_fused_gemv_pallas`:
    each staged table row covers *two* adjacent ``group``-wide segments, so
    ``n == G2 * 2 * group`` (the caller zero-pads ``x`` over the phantom
    segment when the unpaired ``G`` was odd — its table column is exactly
    zero).  Half the fetches, half the adder-tree depth; the fetch itself is
    a batched row-gather (see :func:`_take_rows`), not a one-hot
    contraction.  ``tiles`` is ``(Bb, Gb, Ob)`` with ``Gb | G2``.

    ``counters=True`` (static opt-in) returns ``(out, count, ratio)``
    saturation stats — see :func:`pcilt_fused_gemv_stacked_pallas`.  The
    phantom-segment zero pad quantizes in range, so the count covers exactly
    the real activations.
    """
    B, n = x.shape
    G2, V2, O = tables.shape
    if n != G2 * 2 * group:
        raise ValueError(
            f"x trailing dim {n} != G2*2*group = {G2}*2*{group} "
            f"(x {x.shape}, paired tables {tables.shape})")
    if V2 != 1 << (2 * bits * group):
        raise ValueError(
            f"paired tables value axis {V2} != (2**(bits*group))**2 = "
            f"{1 << (2 * bits * group)} (tables {tables.shape}, bits={bits}, "
            f"group={group})")
    Bb, Gb, Ob = tiles
    return _call_with_stats(
        functools.partial(_gemv_paired_kernel, bits=bits,
                          zero_point=zero_point, group=group, Gb=Gb),
        counters, interpret, tables.dtype,
        grid=(pl.cdiv(B, Bb), pl.cdiv(O, Ob), G2 // Gb),
        in_specs=[
            pl.BlockSpec((Bb, Gb * 2 * group), lambda i, j, k: (i, k)),
            pl.BlockSpec((1, 1), lambda i, j, k: (0, 0)),
            pl.BlockSpec((Gb, V2, Ob), lambda i, j, k: (k, 0, j)),
        ],
        out_spec=pl.BlockSpec((Bb, Ob), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((B, O), jnp.float32),
    )(x, scale, tables)


def _call_with_stats(kernel, counters: bool, interpret: bool, out_dtype, *,
                     grid, in_specs, out_spec, out_shape,
                     num_scalar_prefetch: int = 0, scratch_shapes=(),
                     name=None):
    """``pallas_call`` of a kernel whose trailing (count, ratio) outputs
    exist only under ``counters``.  The returned callable yields ``out``
    cast to ``out_dtype`` — or ``(out, count, ratio)`` with the counters
    read back as scalars."""
    out_specs, out_shapes = [out_spec], [out_shape]
    if counters:
        specs, shapes = _stat_outputs()
        out_specs += specs
        out_shapes += shapes
    call = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=num_scalar_prefetch, grid=grid,
            in_specs=in_specs, out_specs=out_specs,
            scratch_shapes=list(scratch_shapes)),
        out_shape=out_shapes,
        interpret=interpret,
        name=None if name is None else name + ("_sat" if counters else ""),
    )

    def run(*args):
        res = call(*args)
        out = res[0].astype(out_dtype)
        if counters:
            return out, res[1][0, 0], res[2][0, 0]
        return out

    return run


# ----------------------------------------------------------------------------
# Layer-stacked fused GEMV (LM decode: one kernel per projection per layer,
# tables for every layer resident in one [L, G, V, O] array)
# ----------------------------------------------------------------------------


def _gemv_stacked_kernel(layer_ref, x_ref, scale_ref, pack_ref, tab_ref,
                         out_ref, *stat_refs, bits: int, zero_point: int,
                         V: int):
    """One ``(Bb, Ob)`` output tile of the layer-stacked fetch.

    ``tab_ref``'s block is the current layer's ``[1, Gb*V, Ob]`` slice — the
    scalar-prefetched layer index already selected it in the index map, so
    the body is the plain fused fetch.

    With ``stat_refs`` (the counters variant), two ``STAT_BLOCK`` outputs
    ride the call, block-resident across the whole grid: the int32
    saturation count and the f32 running ``max(|x|)/scale`` ratio.  The x
    block at ``(i, k)`` is revisited once per output tile ``j``, so the
    count accumulates only on ``j == 0`` — every activation element counted
    exactly once.  Zero-padded rows (the batch pad) quantize to the
    in-range zero_point and contribute nothing.
    """
    i, j, k = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(k == 0)
    def _zero():
        out_ref[...] = jnp.zeros_like(out_ref)

    x, scale = x_ref[...], scale_ref[...]
    q, codes = _quantize_f32(x, scale, bits=bits, zero_point=zero_point)
    if stat_refs:
        _update_stats(*stat_refs, q, x, scale, bits=bits,
                      first=(i == 0) & (j == 0) & (k == 0), count=j == 0)
    out_ref[...] += _onehot_fetch(codes, pack_ref, tab_ref, (0,), V=V)


@functools.partial(
    jax.jit,
    static_argnames=("bits", "zero_point", "group", "tiles", "counters",
                     "interpret"),
)
def pcilt_fused_gemv_stacked_pallas(
    layer: jax.Array,
    x: jax.Array,
    scale: jax.Array,
    tables: jax.Array,
    *,
    bits: int,
    zero_point: int,
    group: int,
    tiles,
    counters: bool = False,
    interpret: bool = False,
):
    """layer ``[1]`` int32, x ``[B, n]`` float, scale ``[1, 1]``,
    tables ``[L, G, V, O]`` -> ``[B, O]``.

    The layer-scanned decode variant of :func:`pcilt_fused_gemv_pallas`:
    the per-layer tables of a whole network stack live in one ``[L, G, V, O]``
    array that never moves, and the (traced) ``layer`` operand is
    **scalar-prefetched** so the BlockSpec index map stages exactly that
    layer's ``[1, Gb*V, Ob]`` tiles (of the free ``[L, G*V, O]`` view) — per
    grid step the staged bytes equal the unstacked kernel's, and the
    ``lax.scan`` over layers never pays the HBM copy a per-iteration
    ``dynamic_slice`` of the stacked tables would materialize.
    ``n == G * group``; ``tiles`` is ``(Bb, Gb, Ob)`` with ``Gb | G``.

    With ``counters=True`` (a static opt-in: the default trace carries no
    counter outputs) the call returns ``(out, count, ratio)`` — the int32
    number of activations the quantizer clipped and the f32
    ``max(|x|)/scale`` overshoot, reduced in VMEM by the same kernel.
    """
    B, n = x.shape
    L, G, V, O = tables.shape
    if n != G * group:
        raise ValueError(
            f"x trailing dim {n} != G*group = {G}*{group} "
            f"(x {x.shape}, stacked tables {tables.shape})")
    Bb, Gb, Ob = tiles
    pack = _pack_operand(Gb, group, bits, V)
    return _call_with_stats(
        functools.partial(_gemv_stacked_kernel, bits=bits,
                          zero_point=zero_point, V=V),
        counters, interpret, tables.dtype,
        grid=(pl.cdiv(B, Bb), pl.cdiv(O, Ob), G // Gb),
        in_specs=[
            pl.BlockSpec((Bb, Gb * group), lambda i, j, k, l: (i, k)),
            pl.BlockSpec((1, 1), lambda i, j, k, l: (0, 0)),
            pl.BlockSpec(pack.shape, lambda i, j, k, l: (0, 0)),
            pl.BlockSpec((1, Gb * V, Ob), lambda i, j, k, l: (l[0], k, j)),
        ],
        out_spec=pl.BlockSpec((Bb, Ob), lambda i, j, k, l: (i, j)),
        out_shape=jax.ShapeDtypeStruct((B, O), jnp.float32),
        num_scalar_prefetch=1,
        name="pcilt_stacked_gemv",
    )(layer, x, scale, pack, tables.reshape(L, G * V, O))


# ----------------------------------------------------------------------------
# Layer-stacked paired GEMV (the paired decode path): segment-major tables,
# layer folded into the fetch's value axis.
# ----------------------------------------------------------------------------


def _gemv_paired_stacked_kernel(layer_ref, x_ref, scale_ref, tab_ref,
                                out_ref, *stat_refs, bits: int,
                                zero_point: int, group: int, Gb: int,
                                V2: int):
    i, j, k = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(k == 0)
    def _zero():
        out_ref[...] = jnp.zeros_like(out_ref)

    x, scale = x_ref[...], scale_ref[...]
    q, codes = _quantize_f32(x, scale, bits=bits, zero_point=zero_point)
    if stat_refs:
        _update_stats(*stat_refs, q, x, scale, bits=bits,
                      first=(i == 0) & (j == 0) & (k == 0), count=j == 0)
    off = _pack_flat(codes.astype(jnp.int32), bits=bits, group=2 * group,
                     Gseg=Gb)  # [Bb, Gb]
    # The staged block is [Gb, L, V2, Ob] with a *constant* layer index in
    # the BlockSpec map; folding L into the value axis keeps the segment
    # index of the gather a constant iota (the batched-row-gather fast path)
    # and moves the traced layer into the gathered *row* — the layout that
    # makes the traced layer free instead of forcing a general gather.
    Gb_, L, _, Ob = tab_ref.shape
    tab = tab_ref[...].reshape(Gb_, L * V2, Ob)
    out_ref[...] += _take_rows(off + layer_ref[0] * V2, tab)


@functools.partial(
    jax.jit,
    static_argnames=("bits", "zero_point", "group", "tiles", "counters",
                     "interpret"),
)
def pcilt_fused_gemv_paired_stacked_pallas(
    layer: jax.Array,
    x: jax.Array,
    scale: jax.Array,
    tables: jax.Array,
    *,
    bits: int,
    zero_point: int,
    group: int,
    tiles,
    counters: bool = False,
    interpret: bool = False,
):
    """layer ``[1]`` int32, x ``[B, n]`` float, scale ``[1, 1]``,
    **segment-major** paired tables ``[G2, L, V2, O]`` -> ``[B, O]``.

    The layer-scanned decode variant of
    :func:`pcilt_fused_gemv_paired_pallas`.  The stack is segment-major
    (``build_paired_stacked_tables``) so each grid step stages a
    ``[Gb, L, V2, Ob]`` block whose index map is constant in the
    scalar-prefetched layer; the kernel reshapes it to ``[Gb, L*V2, Ob]``
    (adjacent contiguous axes — free) and fetches row ``l*V2 + off``.  The
    traced layer index thus rides the gather's *value* coordinate while the
    segment coordinate stays a constant iota — XLA's batched row-gather fast
    path, where a traced segment index would fall off onto the slow general
    gather.  ``n == G2 * 2 * group``; ``tiles`` is ``(Bb, Gb, Ob)`` with
    ``Gb | G2``.

    ``counters=True`` (static opt-in) returns ``(out, count, ratio)``
    saturation stats — see :func:`pcilt_fused_gemv_stacked_pallas`.
    """
    B, n = x.shape
    G2, L, V2, O = tables.shape
    if n != G2 * 2 * group:
        raise ValueError(
            f"x trailing dim {n} != G2*2*group = {G2}*2*{group} "
            f"(x {x.shape}, stacked paired tables {tables.shape})")
    if V2 != 1 << (2 * bits * group):
        raise ValueError(
            f"paired tables value axis {V2} != (2**(bits*group))**2 = "
            f"{1 << (2 * bits * group)} (tables {tables.shape}, bits={bits}, "
            f"group={group})")
    Bb, Gb, Ob = tiles
    return _call_with_stats(
        functools.partial(_gemv_paired_stacked_kernel, bits=bits,
                          zero_point=zero_point, group=group, Gb=Gb, V2=V2),
        counters, interpret, tables.dtype,
        grid=(pl.cdiv(B, Bb), pl.cdiv(O, Ob), G2 // Gb),
        in_specs=[
            pl.BlockSpec((Bb, Gb * 2 * group), lambda i, j, k, l: (i, k)),
            pl.BlockSpec((1, 1), lambda i, j, k, l: (0, 0)),
            pl.BlockSpec((Gb, L, V2, Ob), lambda i, j, k, l: (k, 0, 0, j)),
        ],
        out_spec=pl.BlockSpec((Bb, Ob), lambda i, j, k, l: (i, j)),
        out_shape=jax.ShapeDtypeStruct((B, O), jnp.float32),
        num_scalar_prefetch=1,
    )(layer, x, scale, tables)


# ----------------------------------------------------------------------------
# Plan-gather fused GEMV: generalized (non-contiguous) SegmentPlans run
# fused via an in-VMEM gather of the plan index.
# ----------------------------------------------------------------------------


def _gemv_plan_kernel(x_ref, scale_ref, plan_ref, pack_ref, tab_ref, out_ref,
                      *, bits: int, zero_point: int, group: int,
                      Gb: int, V: int):
    @pl.when(pl.program_id(2) == 0)
    def _zero():
        out_ref[...] = jnp.zeros_like(out_ref)

    pidx = plan_ref[...].reshape(Gb * group)  # int32, -1 = unused slot
    # In-VMEM gather of the plan's source positions; unused (-1) slots clamp
    # to 0 and are zeroed — their table rows were built from zero weights
    # (SegmentPlan.gather_weights), so any code fetches exactly 0, but
    # forcing x=0 keeps the packed offset deterministic.
    xg = jnp.take(x_ref[...], jnp.maximum(pidx, 0), axis=1)  # [Bb, Gb*group]
    xg = jnp.where((pidx < 0)[None, :], jnp.zeros_like(xg), xg)
    _, codes = _quantize_f32(xg, scale_ref[...], bits=bits,
                             zero_point=zero_point)
    out_ref[...] += _onehot_fetch(codes, pack_ref, tab_ref, V=V)


@functools.partial(
    jax.jit,
    static_argnames=("bits", "zero_point", "group", "tiles", "interpret"),
)
def pcilt_fused_gemv_plan_pallas(
    x: jax.Array,
    scale: jax.Array,
    plan_idx: jax.Array,
    tables: jax.Array,
    *,
    bits: int,
    zero_point: int,
    group: int,
    tiles,
    interpret: bool = False,
) -> jax.Array:
    """x ``[B, n]`` float, scale ``[1, 1]``, plan_idx ``[G, group]`` int32
    (``-1`` = unused slot), tables ``[G, V, O]`` -> ``[B, O]``.

    The generalized-:class:`~repro.core.offsets.SegmentPlan` variant of
    :func:`pcilt_fused_gemv_pallas`: segments may skip or reuse arbitrary
    source positions, so the *whole* activation row is staged (the x
    BlockSpec is constant in the segment grid axis) and each grid step
    gathers its ``[Gb, group]`` plan block's positions in VMEM before the
    standard quantize→pack→fetch.  ``tiles`` is ``(Bb, Gb, Ob)`` with
    ``Gb | G``; ``B`` and ``O`` are padded by ``ops.py`` as usual.
    """
    B, n = x.shape
    G, V, O = tables.shape
    if plan_idx.shape != (G, group):
        raise ValueError(
            f"plan_idx shape {plan_idx.shape} != (G, group) = "
            f"({G}, {group}) (tables {tables.shape})")
    Bb, Gb, Ob = tiles
    pack = _pack_operand(Gb, group, bits, V)
    grid = (pl.cdiv(B, Bb), pl.cdiv(O, Ob), G // Gb)
    return pl.pallas_call(
        functools.partial(_gemv_plan_kernel, bits=bits,
                          zero_point=zero_point, group=group, Gb=Gb, V=V),
        grid=grid,
        in_specs=[
            pl.BlockSpec((Bb, n), lambda i, j, k: (i, 0)),
            pl.BlockSpec((1, 1), lambda i, j, k: (0, 0)),
            pl.BlockSpec((Gb, group), lambda i, j, k: (k, 0)),
            pl.BlockSpec(pack.shape, lambda i, j, k: (0, 0)),
            pl.BlockSpec((Gb * V, Ob), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((Bb, Ob), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((B, O), jnp.float32),
        interpret=interpret,
    )(x, scale, plan_idx, pack, tables.reshape(G * V, O)).astype(tables.dtype)


# ----------------------------------------------------------------------------
# Fused conv2d
# ----------------------------------------------------------------------------


def _strip_codes(x_ref, scale_ref, seg_ref, *, bits: int, zero_point: int,
                 group: int, kh: int, kw: int, stride: int,
                 Gb: int, Hb: int, n_pad: int):
    """Quantize this grid step's row strip, im2col it in VMEM, and slice the
    current group range -> f32 codes ``[Hb*Wo, Gb*group]``.

    ``seg_ref`` holds the segment offset of this device's table shard in the
    *global* segment space (``[1, 1]`` int32, 0 when unsharded): under
    ``shard_map`` every device stages the full (replicated) activation image,
    rebuilds the full patch in VMEM, and slices out the column range its local
    ``[G/D, V, O]`` table shard covers — the in-VMEM im2col never leaves the
    device even when the tables are tensor-parallel.

    Shared between the dense-fused conv kernel and the shared-pool conv
    kernel (``pcilt_shared.py``) — the activation side of the pipeline is
    identical; only the table operand differs.
    """
    _, Hp, Wp, C = x_ref.shape
    Wo = (Wp - kw) // stride + 1
    strip_h = (Hb - 1) * stride + kh
    row0 = pl.program_id(1) * (Hb * stride)
    strip = x_ref[0, pl.ds(row0, strip_h), :, :]  # [strip_h, Wp, C] from VMEM
    _, codes = _quantize_f32(strip, scale_ref[...].reshape(1, 1, 1),
                             bits=bits, zero_point=zero_point)

    # In-VMEM im2col over the strip: static kh*kw slice loop (matches the
    # [kh, kw, C] patch flattening of core.lut_layers.im2col).  The full
    # patch is rebuilt per (output, group) grid step and sliced — VPU work
    # that is redundant when Gb < G or Ob < O, but small next to the MXU
    # contraction; building only the k-th segment's columns is a follow-on.
    cols = []
    for i in range(kh):
        for j in range(kw):
            cols.append(codes[i:i + (Hb - 1) * stride + 1:stride,
                              j:j + (Wo - 1) * stride + 1:stride, :])
    patch = jnp.concatenate(cols, axis=-1).reshape(Hb * Wo, kh * kw * C)
    if n_pad:
        # Group-alignment slots: the table rows for these slots were built
        # from zero weights, so any code value contributes exactly zero.
        patch = jnp.pad(patch, ((0, 0), (0, n_pad)))

    # This grid step's group range in global segment space:
    # [seg0 + k*Gb, seg0 + (k+1)*Gb) — seg0 is the shard's segment offset.
    col0 = (seg_ref[0, 0] + pl.program_id(3) * Gb) * group
    return jax.lax.dynamic_slice(patch, (0, col0), (Hb * Wo, Gb * group))


def _conv_kernel(x_ref, scale_ref, seg_ref, pack_ref, tab_ref, out_ref, *,
                 bits: int, zero_point: int, group: int,
                 kh: int, kw: int, stride: int,
                 Gb: int, V: int, Hb: int, n_pad: int):
    @pl.when(pl.program_id(3) == 0)
    def _zero():
        out_ref[...] = jnp.zeros_like(out_ref)

    codes = _strip_codes(x_ref, scale_ref, seg_ref,
                         bits=bits, zero_point=zero_point,
                         group=group, kh=kh, kw=kw, stride=stride,
                         Gb=Gb, Hb=Hb, n_pad=n_pad)
    acc = _onehot_fetch(codes, pack_ref, tab_ref, V=V)  # [Hb*Wo, Ob] f32
    out_ref[...] += acc.reshape(out_ref.shape)


@functools.partial(
    jax.jit,
    static_argnames=("bits", "zero_point", "group", "kh", "kw", "stride",
                     "n_total", "tiles", "interpret"),
)
def pcilt_fused_conv2d_pallas(
    x: jax.Array,
    scale: jax.Array,
    seg_offset: jax.Array,
    tables: jax.Array,
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
    """x ``[B, Hp, Wp, C]`` float (already spatially padded for the conv),
    scale ``[1, 1]``, seg_offset ``[1, 1]`` int32, tables ``[G, V, O]``
    -> ``[B, Ho, Wo, O]``.

    The whole (small) image is staged in VMEM once per batch element and
    revisited across row/output/group tiles; each grid step quantizes a row
    strip, extracts patches, packs offsets, and fetches — the int32 offsets
    never exist outside VMEM.  ``tiles`` is ``(Hb, Gb, Ob)`` with ``Gb | G``
    and ``Hb | Ho``.

    ``n_total`` is the *global* padded reduction length (``>= kh*kw*C``;
    defaults to ``G * group``, the unsharded case).  Under ``shard_map`` the
    tables operand is one device's ``[G/D, V, O]`` shard and ``seg_offset``
    carries the shard's first segment in global segment space, so the
    in-VMEM im2col slices exactly the patch columns the local shard covers
    (``n_total`` stays the global length; ``G * group`` is only the local
    slice width).
    """
    B, Hp, Wp, C = x.shape
    G, V, O = tables.shape
    n = kh * kw * C
    n_tot = n_total or G * group
    if n_tot < max(n, G * group):
        raise ValueError(
            f"n_total {n_tot} must cover the patch length kh*kw*C = {n} "
            f"and the table span G*group = {G}*{group} "
            f"(x {x.shape}, tables {tables.shape})")
    Ho = (Hp - kh) // stride + 1
    Wo = (Wp - kw) // stride + 1
    Hb, Gb, Ob = tiles
    pack = _pack_operand(Gb, group, bits, V)
    grid = (B, Ho // Hb, pl.cdiv(O, Ob), G // Gb)
    return pl.pallas_call(
        functools.partial(_conv_kernel, bits=bits, zero_point=zero_point,
                          group=group, kh=kh, kw=kw, stride=stride,
                          Gb=Gb, V=V, Hb=Hb, n_pad=n_tot - n),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, Hp, Wp, C), lambda b, r, j, k: (b, 0, 0, 0)),
            pl.BlockSpec((1, 1), lambda b, r, j, k: (0, 0)),
            pl.BlockSpec((1, 1), lambda b, r, j, k: (0, 0)),
            pl.BlockSpec(pack.shape, lambda b, r, j, k: (0, 0)),
            pl.BlockSpec((Gb * V, Ob), lambda b, r, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((1, Hb, Wo, Ob), lambda b, r, j, k: (b, r, 0, j)),
        out_shape=jax.ShapeDtypeStruct((B, Ho, Wo, O), jnp.float32),
        interpret=interpret,
    )(x, scale, seg_offset, pack, tables.reshape(G * V, O)).astype(
        tables.dtype)
