"""PCILT inference layers.

Each layer executes the paper's fetch-instead-of-multiply semantics through
one of three interchangeable paths that produce bit-identical arithmetic:

* ``path="gather"`` — the literal algorithm: offsets address table rows
  (paper Fig. 2/6).  Reference semantics; also the right shape for CPU.
* ``path="onehot"`` — ``T[off] == onehot(off) @ T``: re-expresses every fetch
  as an MXU matmul.  This is the TPU-idiomatic lookup (DESIGN.md §2) and the
  path the distributed dry-run lowers, since it partitions like any einsum.
* ``path="kernel"`` — the Pallas TPU kernel (``repro.kernels``): tables tiled
  into VMEM via BlockSpec, offsets packed on the host and re-read by the
  kernel.
* ``path="fused"`` — the fused Pallas pipeline (``repro.kernels.pcilt_fused``):
  quantize → offset-pack → fetch → adder-tree entirely in VMEM from the raw
  float activations, so the int32 offset tensor never touches HBM.  Fastest
  deployment path; requires a per-tensor scale and the default contiguous
  segment plan.
* ``path="shared"`` — the shared-pool fused pipeline
  (``repro.kernels.pcilt_shared``) for extension-3 segment-deduped tables:
  ``tables`` is a ``SharedGroupedTables`` (pool + pointers) and the pointer
  indirection is resolved inside the kernel, so weight-deduped layers run at
  fused speed without ever materializing the dense ``[G, V, O]`` tables.
  A ``SharedGroupedTables`` also executes on ``path="gather"`` (its
  pointer-gather reference semantics) for parity checking.

Both kernel paths dispatch tile shapes through the persistent autotune lookup
table (``repro.kernels.autotune``) — recorded winners are used on a cache
hit, the VMEM heuristic otherwise.

The convolution layers reduce to the linear case by im2col — a PCILT is
indexed by (segment, offset) regardless of whether the segment came from a
flattened conv receptive field or a projection row.  (``path="fused"`` does
the im2col on quantized codes inside the kernel instead.)

Mesh execution (tensor-parallel decode)
---------------------------------------

Every path also runs sharded: pass ``mesh=`` (and optionally
``mesh_axis=``, default ``"model"``) and the segment axis ``G`` is split
across the mesh axis under ``shard_map`` — each device holds a ``[G/D, V, O]``
table shard (or a local ext.-3 pool, see ``pcilt.ShardedSharedPool``) plus
the matching slice of the activation's reduction dim, fetches and sums its
local segments with the *same* single-device kernels it would use unsharded,
and a single ``psum`` over the mesh axis combines the partial adder-tree
sums (the paper's segment sum is associative).  The fused/shared **conv**
paths stay VMEM-resident under the mesh too: the image is replicated, each
shard's conv kernel rebuilds the patch in VMEM and slices exactly the
columns its table shard covers (the kernels' ``seg_offset`` parameter) —
there is no host-im2col detour at any device count.  When the mesh axis
does not divide ``G`` the call falls back to replicated single-device
execution — the same divisibility fallback ``repro.nn.module.ShardingRules``
applies to parameters.  Because the kernels see *local* shapes, the autotune
lookup table is keyed on the local shard shape automatically.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .quantization import QuantSpec, quantize, quantize_with_stats
from .offsets import SegmentPlan, pack_offsets
from .pcilt import (SharedTables, SharedGroupedTables, ShardedSharedPool,
                    build_grouped_tables, shard_shared_grouped_tables)

__all__ = [
    "lut_lookup",
    "pcilt_linear",
    "pcilt_conv2d",
    "pcilt_depthwise_conv1d",
    "build_dwconv_tables",
    "im2col",
    "conv_same_pads",
    "mesh_shard_count",
    "replicated_on",
]


@functools.lru_cache(maxsize=4096)
def conv_same_pads(h: int, w: int, kh: int, kw: int, stride: int = 1):
    """XLA-conformant "SAME" pads for NHWC (single source of truth — the
    fused/shared kernel wrappers in ``repro.kernels.ops`` import this).

    Matches ``lax.conv_general_dilated``: output extent ``ceil(size/stride)``
    and ``pad_total = (out-1)*stride + k - size`` split low-first as
    ``pad_total // 2`` — which differs from the naive stride-agnostic
    ``(k-1)//2`` whenever ``stride > 1`` and the size isn't congruent
    (e.g. stride 2 on an even extent: the naive split pads one extra low and
    every window samples shifted positions).

    Memoized (pure int arithmetic, hashable args): eager serving calls this
    on every conv step, and ``serving.PCILTConv2d`` additionally caches the
    whole padded-shape plan per input shape.
    """
    def axis(size: int, k: int):
        out = -(-size // stride)
        total = max((out - 1) * stride + k - size, 0)
        return (total // 2, total - total // 2)

    return ((0, 0), axis(h, kh), axis(w, kw), (0, 0))


def lut_lookup(tables: jax.Array, offsets: jax.Array, path: str = "gather") -> jax.Array:
    """Fetch-and-sum: ``sum_s T[s, off[..., s], :]``.

    tables: ``[G, V, O]`` grouped PCILTs.  offsets: integer ``[..., G]``.
    Returns ``[..., O]``.
    """
    G, V, O = tables.shape
    if path == "gather":
        # Literal table addressing.  [..., G, O] partials, then the adder tree.
        partial = jnp.take_along_axis(
            tables[(None,) * (offsets.ndim - 1)],
            offsets[..., None, None].astype(jnp.int32),
            axis=-2,
        )[..., 0, :]
        return jnp.sum(partial, axis=-2)
    if path == "onehot":
        oh = jax.nn.one_hot(offsets, V, dtype=tables.dtype)  # [..., G, V]
        return jnp.einsum("...gv,gvo->...o", oh, tables)
    if path == "kernel":
        from repro.kernels import ops  # local import: kernels are optional

        flat = offsets.reshape(-1, G)
        out = ops.pcilt_gemv(flat.astype(jnp.int32), tables)
        return out.reshape(*offsets.shape[:-1], O)
    raise ValueError(f"unknown path {path!r}")


def mesh_shard_count(mesh, mesh_axis: str, n_segments: int) -> int:
    """How many G-shards a mesh yields; 1 means replicate (fallback).

    Falls back to replication when there is no mesh, the axis is absent, or
    the axis size does not divide the segment count — the same divisibility
    fallback ``repro.nn.module.ShardingRules`` applies to parameter dims.
    """
    if mesh is None or mesh_axis not in mesh.axis_names:
        return 1
    d = int(mesh.shape[mesh_axis])
    if d <= 1 or n_segments % d:
        return 1
    return d


def replicated_on(mesh, fn):
    """``fn`` run replicated on every device of ``mesh``, under
    ``shard_map``: in a program partitioned over several devices, each
    Pallas kernel must sit inside a ``shard_map`` (the compiler cannot
    partition a Mosaic call).  Without a multi-device mesh, ``fn`` itself.
    """
    if mesh is None or mesh.size == 1:
        return fn
    return jax.shard_map(fn, mesh=mesh, in_specs=P(), out_specs=P(),
                         check_vma=False)


def _check_contiguous_segments(path: str, plan, n: int, n_segments: int,
                               group: int) -> None:
    """Typed boundary validation for the in-kernel-packing paths.

    ``path="shared"`` packs contiguous segments inside the kernel, so a
    generalized ``SegmentPlan`` (non-adjacent / skipped / reused positions)
    cannot execute there — reject it here, at the dispatch boundary, instead
    of letting a bare shape error surface from deep inside the kernel
    wrapper.  ``path="fused"`` *does* run generalized plans (the plan-gather
    kernel resolves the index in VMEM), so this helper only sees
    ``plan=None`` on the fused route — the residual check catches tables
    *built* with a generalized plan but dispatched without passing it
    (their segment count no longer satisfies ``G * group == n``).
    """
    if plan is not None:
        raise ValueError(
            f"path={path!r} packs contiguous segments in-kernel and cannot "
            f"follow a generalized SegmentPlan; drop plan= (contiguous "
            f"default), use path='fused' (which gathers the plan index in "
            f"VMEM), or use the host-packed paths ('gather'/'onehot'/"
            f"'kernel'), which honor plan.pack()")
    if n != n_segments * group:
        raise ValueError(
            f"path={path!r} requires contiguous segments covering the "
            f"reduction dim: got x trailing dim {n} but G*group = "
            f"{n_segments}*{group} = {n_segments * group}. Tables built from "
            f"a generalized SegmentPlan (skipped/reused positions) need that "
            f"plan passed as plan= (path='fused' runs it via the in-VMEM "
            f"plan gather; 'gather'/'onehot'/'kernel' via plan.pack())")


def _pad_paired_phantom(x: jax.Array, n_pairs: int, group: int) -> jax.Array:
    """Zero-pad ``x`` over the phantom segment of an odd-``G`` pairing.

    Paired tables cover ``n_pairs`` two-segment fetches; when the unpaired
    segment count was odd the builder padded a phantom segment whose table
    column is exactly zero (``build_paired_tables``), so the matching
    activation slots are zero here — any code they quantize to fetches 0.
    """
    want = n_pairs * 2 * group
    n = x.shape[-1]
    if n == want:
        return x
    if n == want - group:
        widths = [(0, 0)] * (x.ndim - 1) + [(0, group)]
        return jnp.pad(x, widths)
    raise ValueError(
        f"x trailing dim {n} matches neither G2*2*group = {want} nor the "
        f"odd-G phantom layout {want - group} for paired tables with "
        f"G2={n_pairs}, group={group}")


def _shard_pool_for(tables: SharedGroupedTables,
                    n_shards: int) -> ShardedSharedPool:
    from repro import compat

    if compat.is_tracer(tables.seg_idx):
        raise ValueError(
            "sharding a SharedGroupedTables pool is an offline build step "
            "(np.unique on concrete pointers) and cannot run under jit; "
            "pre-shard with pcilt.shard_shared_grouped_tables(...) — or "
            "convert_kernel(..., shared=True, mesh=...) — and pass the "
            "ShardedSharedPool instead")
    return shard_shared_grouped_tables(tables, n_shards)


def _pcilt_linear_sharded(x, tables, spec, scale, group, path, mesh,
                          mesh_axis, paired: bool = False) -> jax.Array:
    """Run one fetch-and-sum layer under ``shard_map`` over local G-shards.

    Each device executes the unsharded layer on its table shard and the
    matching slice of the reduction dim, then contributes its partial sum to
    the ``psum`` over ``mesh_axis`` — the one collective of the whole layer.
    ``check_vma=False``: Pallas calls carry no replication rule.
    """
    lead = x.shape[:-1]
    flat = x.reshape(-1, x.shape[-1])

    if isinstance(tables, ShardedSharedPool):
        def shard_fn(xl, pool_l, idx_l):
            local = SharedGroupedTables(pool=pool_l[0], seg_idx=idx_l[0],
                                        group=group)
            part = pcilt_linear(xl, local, spec, scale, group, path=path)
            return jax.lax.psum(part, mesh_axis)

        out = jax.shard_map(
            shard_fn, mesh=mesh,
            in_specs=(P(None, mesh_axis), P(mesh_axis), P(mesh_axis)),
            out_specs=P(), check_vma=False,
        )(flat, tables.pools, tables.seg_idx)
    else:
        def shard_fn(xl, tab_l):
            part = pcilt_linear(xl, tab_l, spec, scale, group, path=path,
                                paired=paired)
            return jax.lax.psum(part, mesh_axis)

        out = jax.shard_map(
            shard_fn, mesh=mesh,
            in_specs=(P(None, mesh_axis), P(mesh_axis, None, None)),
            out_specs=P(), check_vma=False,
        )(flat, tables)
    return out.reshape(*lead, out.shape[-1])


def _pcilt_linear_stacked_sharded(x, tables, layer, spec, scale, group,
                                  mesh, mesh_axis) -> jax.Array:
    """Layer-stacked fused GEMV under ``shard_map``: the ``[L, G, V, O]``
    stack shards on its *segment* axis (the same ``"table_seg"`` rule dense
    tables use, one position to the right), each device runs the stacked
    kernel over its resident ``[L, G/D, V, O]`` shard at the scan-carried
    layer index, and one ``psum`` per step combines the partial adder-tree
    sums — the stacked kernel's scalar-prefetch table staging survives the
    mesh unchanged because every shard's stack stays put in its own HBM.
    """
    from repro.kernels import ops  # local import: kernels are optional

    lead = x.shape[:-1]
    flat = x.reshape(-1, x.shape[-1])
    l1 = jnp.asarray(layer, jnp.int32).reshape(1)

    def shard_fn(xl, tab_l, lyr):
        part = ops.pcilt_fused_gemv_stacked(xl, tab_l, lyr[0], spec, scale,
                                            group)
        return jax.lax.psum(part, mesh_axis)

    out = jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(None, mesh_axis), P(None, mesh_axis, None, None), P()),
        out_specs=P(), check_vma=False,
    )(flat, tables, l1)
    return out.reshape(*lead, out.shape[-1])


def _pcilt_linear_paired_stacked_sharded(x, tables, layer, spec, scale,
                                         group, mesh, mesh_axis) -> jax.Array:
    """Seg-major paired stack ``[G2, L, V2, O]`` under ``shard_map``: shards
    split the *pair* axis (axis 0 — the ``"table_seg"`` position for
    ``ndim=4, seg_axis=0`` in ``pcilt_table_sharding``), each device runs
    the paired stacked kernel over its resident ``[G2/D, L, V2, O]`` shard,
    and one ``psum`` per step combines the partial adder-tree sums."""
    from repro.kernels import ops  # local import: kernels are optional

    lead = x.shape[:-1]
    flat = x.reshape(-1, x.shape[-1])
    l1 = jnp.asarray(layer, jnp.int32).reshape(1)

    def shard_fn(xl, tab_l, lyr):
        part = ops.pcilt_fused_gemv_paired_stacked(xl, tab_l, lyr[0], spec,
                                                   scale, group)
        return jax.lax.psum(part, mesh_axis)

    out = jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(None, mesh_axis), P(mesh_axis, None, None, None), P()),
        out_specs=P(), check_vma=False,
    )(flat, tables, l1)
    return out.reshape(*lead, out.shape[-1])


def _pcilt_linear_paired(x, tables, spec, scale, group, path, mesh,
                         mesh_axis, stacked, return_stats=False):
    """The paired (TL1-style multi-scalar) routes of :func:`pcilt_linear`.

    ``tables`` is a paired ``[G2, V2, out]`` array
    (``build_paired_tables``) or, with ``stacked=``, the **segment-major**
    ``[G2, L, V2, out]`` stack (``build_paired_stacked_tables``).  ``x`` is
    zero-padded over the odd-``G`` phantom segment here, once, before any
    shard split — so mesh divisibility is judged on the padded layout.
    The host-packed reference paths fall out for free: a paired table *is*
    a grouped table at width ``2*group``, so ``gather``/``onehot``/
    ``kernel`` recurse into the dense layer with the doubled group.
    """
    pair = 2 * group
    if stacked is not None:
        if tables.ndim != 4:
            raise ValueError(
                f"paired stacked= expects seg-major [G2, L, V2, O] tables "
                f"(build_paired_stacked_tables), got shape {tables.shape}")
        G2, L, V2, O = tables.shape
        x = _pad_paired_phantom(x, G2, group)
        if path == "fused":
            if mesh_shard_count(mesh, mesh_axis, G2) > 1:
                out = _pcilt_linear_paired_stacked_sharded(
                    x, tables, stacked, spec, scale, group, mesh, mesh_axis)
                if return_stats:
                    _, count, ratio = quantize_with_stats(x, spec, scale)
                    return out, count, ratio
                return out
            from repro.kernels import ops  # local import: kernels optional

            flat = x.reshape(-1, x.shape[-1])
            if return_stats:
                out, count, ratio = ops.pcilt_fused_gemv_paired_stacked(
                    flat, tables, stacked, spec, scale, group,
                    with_stats=True)
                return out.reshape(*x.shape[:-1], O), count, ratio
            out = ops.pcilt_fused_gemv_paired_stacked(
                flat, tables, stacked, spec, scale, group)
            return out.reshape(*x.shape[:-1], O)
        # Reference / host-packed baseline: slice the layer out of the
        # seg-major stack (axis 1) and run it as a dense grouped table at
        # the doubled group width.
        tab_l = jax.lax.dynamic_index_in_dim(
            tables, jnp.asarray(stacked, jnp.int32), 1, keepdims=False)
        return pcilt_linear(x, tab_l, spec, scale, pair, path=path,
                            return_stats=return_stats)
    if tables.ndim != 3:
        raise ValueError(
            f"paired tables are [G2, V2, O] (build_paired_tables), got "
            f"shape {tables.shape}")
    G2, V2, O = tables.shape
    x = _pad_paired_phantom(x, G2, group)
    if path == "fused":
        if mesh_shard_count(mesh, mesh_axis, G2) > 1:
            out = _pcilt_linear_sharded(x, tables, spec, scale, group,
                                        path, mesh, mesh_axis, paired=True)
            if return_stats:
                _, count, ratio = quantize_with_stats(x, spec, scale)
                return out, count, ratio
            return out
        from repro.kernels import ops  # local import: kernels are optional

        flat = x.reshape(-1, x.shape[-1])
        if return_stats:
            out, count, ratio = ops.pcilt_fused_gemv_paired(
                flat, tables, spec, scale, group, with_stats=True)
            return out.reshape(*x.shape[:-1], O), count, ratio
        out = ops.pcilt_fused_gemv_paired(flat, tables, spec, scale, group)
        return out.reshape(*x.shape[:-1], O)
    # gather/onehot/kernel reference (and their sharded forms): a paired
    # table is exactly a grouped table of width 2*group.
    return pcilt_linear(x, tables, spec, scale, pair, path=path, mesh=mesh,
                        mesh_axis=mesh_axis, return_stats=return_stats)


def pcilt_linear(
    x: jax.Array,
    tables,
    spec: QuantSpec,
    scale,
    group: int,
    plan: Optional[SegmentPlan] = None,
    path: str = "gather",
    mesh=None,
    mesh_axis: str = "model",
    stacked=None,
    paired: bool = False,
    return_stats: bool = False,
):
    """Quantize -> pack offsets -> fetch -> sum.   ``x: [..., n] -> [..., out]``.

    ``tables`` is the dense grouped ``[G, V, out]`` array, a
    ``SharedGroupedTables`` pool (required for ``path="shared"``; also
    accepted on ``path="gather"`` for the pointer-gather reference), or a
    pre-sharded ``ShardedSharedPool`` (mesh execution only).

    With ``stacked=`` (a possibly-traced integer layer index), ``tables``
    is a layer-stacked dense ``[L, G, V, out]`` array holding every layer's
    tables of a scanned network, and the call executes layer ``stacked``:
    ``path="fused"`` runs the scalar-prefetch stacked kernel
    (``repro.kernels.pcilt_fused_gemv_stacked``) so the resident stack is
    tiled directly — the ``lax.scan`` carrying the index never copies a
    ``[G, V, out]`` slice through HBM — while the host-packed reference
    paths (``gather``/``onehot``/``kernel``) slice the layer explicitly
    (paying exactly that copy; they exist for parity and as the baseline
    the stacked kernel is benchmarked against).

    With ``paired=True``, ``tables`` is the TL1-style multi-scalar layout:
    ``[G2, V2, out]`` from ``build_paired_tables`` (or, stacked, the
    segment-major ``[G2, L, V2, out]`` from ``build_paired_stacked_tables``)
    where each fetch covers **two** adjacent ``group``-wide segments.  ``x``
    keeps the *unpaired* layout — the layer zero-pads the odd-``G`` phantom
    segment itself — and ``group`` stays the unpaired width.
    ``path="fused"`` runs the row-gather paired kernels (halved fetch count
    and adder-tree depth); the host-packed paths execute the paired table
    as a dense grouped table of width ``2*group``.  Mesh execution shards
    the pair axis (``pcilt_table_sharding(..., ndim=4, seg_axis=0)`` for
    the seg-major stack).

    A generalized ``SegmentPlan`` with ``path="fused"`` executes via the
    plan-gather kernel (``pcilt_fused_gemv_plan``): the plan index is
    gathered in VMEM before the standard quantize→pack→fetch, so plan-built
    tables no longer fall back to the host-packed paths.

    A scalar-level :class:`~repro.core.pcilt.SharedTables` (per-unique-value
    pool) is accepted on ``path="shared"``/``"gather"``: it is re-expressed
    as its 1-wide segment pool (``SharedTables.as_grouped_pool``) and runs
    the fused shared kernel / pointer-gather — ``materialize()`` is never
    called.

    With ``mesh=``, the segment axis is sharded over ``mesh_axis`` and the
    partial sums are ``psum``-combined (see the module docstring); without a
    mesh — or when the axis does not divide ``G`` — execution is the
    single-device reference.  A generalized ``SegmentPlan`` cannot shard
    (its positions are arbitrary): combining ``plan=`` with a mesh that
    would shard raises rather than silently replicating.

    With ``return_stats=True`` the call returns ``(out, count, ratio)``:
    the saturation statistics of the quantizer feeding the fetch —
    ``count`` (int32) elements whose pre-clip code left ``[0, K)`` and
    ``ratio`` (f32) ``max(|x|)/scale`` — exactly
    :func:`~repro.core.quantization.quantize_with_stats`'s definition.
    ``out`` is bit-identical to the ``return_stats=False`` result.  On the
    unsharded fused stacked/paired routes the counters are reduced inside
    the fetch kernel's grid (no second pass over ``x``); every other route
    derives the same stats host-side.  Zero-padding (group alignment,
    paired phantom segments) never perturbs the stats: padded slots
    quantize to ``zero_point``, which is in range.
    """
    if isinstance(tables, SharedTables):
        if paired:
            raise ValueError(
                "paired tables are dense [G2, V2, O] arrays; scalar-level "
                "SharedTables pools have no paired layout")
        # Scalar-level ext.-3: each weight position is a 1-wide segment over
        # the deduped pointer-row pool; group becomes the pool's (1).
        tables = tables.as_grouped_pool()
        group = tables.group
    if paired:
        if plan is not None:
            raise ValueError(
                "paired tables pack adjacent contiguous segment pairs; "
                "generalized SegmentPlans cannot pair — drop plan= or use "
                "the unpaired paths")
        if isinstance(tables, (SharedGroupedTables, ShardedSharedPool)):
            raise ValueError(
                "paired=True consumes dense paired [G2, V2, O] tables "
                "(build_paired_tables); shared pools have no paired layout")
        if path == "shared":
            raise ValueError(
                "path='shared' has no paired variant; paired tables run "
                "path='fused' (row-gather kernels) or the host-packed "
                "reference paths")
        return _pcilt_linear_paired(x, tables, spec, scale, group, path,
                                    mesh, mesh_axis, stacked,
                                    return_stats=return_stats)
    if stacked is not None:
        if isinstance(tables, (SharedGroupedTables, ShardedSharedPool)):
            raise ValueError(
                "stacked= executes layer-stacked dense [L, G, V, O] tables; "
                "shared pools have no stacked path — materialize() per layer "
                "or use the unstacked shared layer")
        if tables.ndim != 4:
            raise ValueError(
                f"stacked= expects [L, G, V, O] tables, got shape "
                f"{tables.shape}")
        if plan is not None:
            raise ValueError(
                "stacked= packs contiguous segments (the tables of every "
                "layer share one segment grid); generalized SegmentPlans "
                "cannot ride the layer stack — drop plan= or slice the "
                "layer's tables and use the unstacked paths")
        L, G, V, O = tables.shape
        if path == "fused":
            _check_contiguous_segments(path, None, x.shape[-1], G, group)
            if mesh_shard_count(mesh, mesh_axis, G) > 1:
                out = _pcilt_linear_stacked_sharded(
                    x, tables, stacked, spec, scale, group, mesh, mesh_axis)
                if return_stats:
                    _, count, ratio = quantize_with_stats(x, spec, scale)
                    return out, count, ratio
                return out
            from repro.kernels import ops  # local import: kernels optional

            flat = x.reshape(-1, x.shape[-1])
            if return_stats:
                out, count, ratio = ops.pcilt_fused_gemv_stacked(
                    flat, tables, stacked, spec, scale, group,
                    with_stats=True)
                return out.reshape(*x.shape[:-1], O), count, ratio
            out = ops.pcilt_fused_gemv_stacked(flat, tables, stacked, spec,
                                               scale, group)
            return out.reshape(*x.shape[:-1], O)
        # Reference / host-packed baseline: slice the layer (the HBM copy
        # the stacked fused kernel exists to avoid) and fall through.
        tables = jax.lax.dynamic_index_in_dim(
            tables, jnp.asarray(stacked, jnp.int32), 0, keepdims=False)
    if return_stats:
        # Counter-less routes (host-packed references, shared pools, plans,
        # unstacked fused, sharded fallbacks): identical stats, computed
        # host-side from the same pre-clip codes (XLA drops the duplicate
        # quantize against the fetch's own).
        _, count, ratio = quantize_with_stats(x, spec, scale)
        out = pcilt_linear(x, tables, spec, scale, group, plan=plan,
                           path=path, mesh=mesh, mesh_axis=mesh_axis)
        return out, count, ratio
    shared = tables if isinstance(tables, SharedGroupedTables) else None
    if isinstance(tables, ShardedSharedPool):
        if path not in ("shared", "gather"):
            raise ValueError(
                f"a ShardedSharedPool executes path='shared' or 'gather', "
                f"not {path!r}")
        if plan is not None:
            raise ValueError(
                "a ShardedSharedPool was built over contiguous segment "
                "blocks; generalized SegmentPlans cannot execute on sharded "
                "pools — use the unsharded SharedGroupedTables with a "
                "host-packed path instead")
        if x.shape[-1] != tables.n_segments * group:
            raise ValueError(
                f"x trailing dim {x.shape[-1]} != G*group = "
                f"{tables.n_segments}*{group} = {tables.n_segments * group} "
                f"for this ShardedSharedPool")
        if mesh is None or mesh_axis not in mesh.axis_names:
            raise ValueError(
                "a ShardedSharedPool is a mesh operand; pass mesh= (and the "
                "mesh_axis its pools were sharded for), or execute the "
                "unsharded SharedGroupedTables instead")
        if int(mesh.shape[mesh_axis]) != tables.n_shards:
            raise ValueError(
                f"ShardedSharedPool was built for {tables.n_shards} shards "
                f"but mesh axis {mesh_axis!r} has size "
                f"{int(mesh.shape[mesh_axis])}; rebuild with "
                f"shard_shared_grouped_tables(st, {int(mesh.shape[mesh_axis])})")
        return _pcilt_linear_sharded(x, tables, spec, scale, group, path,
                                     mesh, mesh_axis)

    n_segments = shared.n_segments if shared is not None else (
        tables.shape[0] if path in ("fused", "shared") else None)
    if path == "shared" and shared is None:
        raise ValueError(
            "path='shared' executes a SharedGroupedTables pool; build one "
            "with build_shared_grouped_tables (got dense tables)")
    if path == "fused" and shared is not None:
        raise ValueError(
            "path='fused' consumes dense [G, V, O] tables; use "
            "path='shared' for a SharedGroupedTables pool (or "
            "materialize() it explicitly)")
    if path == "fused" and plan is not None:
        # Generalized plans run fused via the in-VMEM plan gather — only
        # validate that the plan and tables agree on the segment grid.
        if plan.n_segments != n_segments or plan.group != group:
            raise ValueError(
                f"plan grid [{plan.n_segments}, {plan.group}] does not match "
                f"tables' [{n_segments}, {group}] — tables must be built "
                f"from plan.gather_weights(...)")
    elif path in ("fused", "shared"):
        _check_contiguous_segments(path, plan, x.shape[-1], n_segments, group)

    D = mesh_shard_count(mesh, mesh_axis,
                         shared.n_segments if shared is not None
                         else tables.shape[0])
    if D > 1 and plan is not None:
        # Refuse rather than silently replicate: a generalized plan maps
        # positions arbitrarily, so it cannot shard along contiguous
        # G-blocks — and a silent fallback would keep full per-device table
        # residency exactly where the caller asked for sharding.
        raise ValueError(
            "mesh execution shards contiguous segment blocks; a generalized "
            "SegmentPlan cannot be sharded — pass mesh=None to execute the "
            "plan replicated")
    if D > 1:
        if shared is not None:
            if path not in ("shared", "gather"):
                raise ValueError(
                    f"SharedGroupedTables executes path='shared' or "
                    f"'gather', not {path!r}")
            tables = _shard_pool_for(shared, D)
        return _pcilt_linear_sharded(x, tables, spec, scale, group, path,
                                     mesh, mesh_axis)

    if path == "shared":
        from repro.kernels import ops  # local import: kernels are optional

        flat = x.reshape(-1, x.shape[-1])
        out = ops.pcilt_shared_gemv(flat, shared.pool, shared.seg_idx, spec,
                                    scale, shared.group)
        return out.reshape(*x.shape[:-1], shared.pool.shape[-1])
    if path == "fused":
        from repro.kernels import ops  # local import: kernels are optional

        G, _, O = tables.shape
        flat = x.reshape(-1, x.shape[-1])
        if plan is not None:
            out = ops.pcilt_fused_gemv_plan(
                flat, tables, jnp.asarray(plan.index, jnp.int32), spec,
                scale, group)
        else:
            out = ops.pcilt_fused_gemv(flat, tables, spec, scale, group)
        return out.reshape(*x.shape[:-1], O)
    codes = quantize(x, spec, scale)
    if plan is None:
        offsets = pack_offsets(codes, spec.bits, group)
    else:
        offsets = plan.pack(codes, spec.bits)
    if shared is not None:
        if path != "gather":
            raise ValueError(
                f"SharedGroupedTables executes path='shared' or 'gather', "
                f"not {path!r}")
        return shared.lookup(offsets)
    return lut_lookup(tables, offsets, path=path)


def im2col(
    x: jax.Array, kh: int, kw: int, stride: int = 1, padding: str = "SAME"
) -> jax.Array:
    """NHWC ``[B,H,W,C] -> [B,Ho,Wo,kh*kw*C]`` patch extraction.

    "SAME" padding follows the XLA/``lax.conv_general_dilated`` convention:
    output extent ``ceil(size/stride)`` with ``pad_total`` split low-first as
    ``pad_total // 2`` — stride-aware, unlike the naive ``(k-1)//2``, which
    samples shifted windows at stride > 1 on non-congruent sizes.
    """
    pads = ((0, 0),) * 4
    if padding == "SAME":
        pads = conv_same_pads(x.shape[1], x.shape[2], kh, kw, stride)
    xp = jnp.pad(x, pads)
    B, H, W, C = xp.shape
    Ho = (H - kh) // stride + 1
    Wo = (W - kw) // stride + 1
    # Extract with a static double loop over the (small) kernel extent; XLA
    # fuses these slices.  Patch layout [kh, kw, C] flattened, matching the
    # filter flattening in pcilt_conv2d.
    cols = []
    for i in range(kh):
        for j in range(kw):
            cols.append(
                jax.lax.slice(
                    xp,
                    (0, i, j, 0),
                    (B, i + (Ho - 1) * stride + 1, j + (Wo - 1) * stride + 1, C),
                    (1, stride, stride, 1),
                )
            )
    return jnp.concatenate(cols, axis=-1).reshape(B, Ho, Wo, kh * kw * C)


def _pcilt_conv2d_sharded_kernel(x, tables, spec, scale, group, kh, kw,
                                 stride, padding, path, mesh, mesh_axis):
    """Fused/shared conv under a mesh: **in-VMEM im2col per shard**.

    Every device stages the full (replicated) activation image, its
    ``[G/D, V, O]`` table shard (or local ext.-3 pool), and the shard's
    global segment offset; the conv kernel rebuilds the patch in VMEM and
    slices exactly the columns its shard covers (``seg_offset`` /
    ``n_total`` on the kernel wrappers), so neither the float patch tensor
    nor any offset tensor is ever materialized in HBM — the host-im2col +
    sharded-GEMV detour this route replaces paid for both.  One ``psum``
    over ``mesh_axis`` combines the partial adder-tree sums, exactly like
    the sharded linear path.
    """
    from repro.kernels import ops  # local import: kernels are optional

    if isinstance(tables, ShardedSharedPool):
        n_seg, D = tables.n_segments, tables.n_shards
        if mesh is None or mesh_axis not in mesh.axis_names:
            raise ValueError(
                "a ShardedSharedPool is a mesh operand; pass mesh= (and the "
                "mesh_axis its pools were sharded for), or execute the "
                "unsharded SharedGroupedTables instead")
        if int(mesh.shape[mesh_axis]) != D:
            raise ValueError(
                f"ShardedSharedPool was built for {D} shards but mesh axis "
                f"{mesh_axis!r} has size {int(mesh.shape[mesh_axis])}; "
                f"rebuild with shard_shared_grouped_tables(st, "
                f"{int(mesh.shape[mesh_axis])})")
    else:
        n_seg = tables.shape[0]
        D = int(mesh.shape[mesh_axis])
    n_total = n_seg * group
    Gl = n_seg // D

    if path == "fused":
        def shard_fn(xl, tab_l):
            seg0 = jax.lax.axis_index(mesh_axis) * Gl
            part = ops.pcilt_fused_conv2d(
                xl, tab_l, spec, scale, group, kh, kw, stride=stride,
                padding=padding, seg_offset=seg0, n_total=n_total)
            return jax.lax.psum(part, mesh_axis)

        return jax.shard_map(
            shard_fn, mesh=mesh,
            in_specs=(P(), P(mesh_axis, None, None)),
            out_specs=P(), check_vma=False,
        )(x, tables)

    def shard_fn(xl, pool_l, idx_l):
        seg0 = jax.lax.axis_index(mesh_axis) * Gl
        part = ops.pcilt_shared_conv2d(
            xl, pool_l[0], idx_l[0], spec, scale, group, kh, kw,
            stride=stride, padding=padding, seg_offset=seg0, n_total=n_total)
        return jax.lax.psum(part, mesh_axis)

    return jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(), P(mesh_axis), P(mesh_axis)),
        out_specs=P(), check_vma=False,
    )(x, tables.pools, tables.seg_idx)


def pcilt_conv2d(
    x: jax.Array,
    filters: jax.Array,
    spec: QuantSpec,
    scale,
    group: int,
    stride: int = 1,
    padding: str = "SAME",
    tables=None,
    path: str = "gather",
    mesh=None,
    mesh_axis: str = "model",
) -> jax.Array:
    """PCILT convolution, NHWC ``[B,H,W,Cin] -> [B,Ho,Wo,Cout]``.

    filters: ``[kh, kw, Cin, Cout]``.  Tables may be passed pre-built (the
    normal deployment: built once, reused for the network lifetime); when
    omitted they are built on the fly (tests / calibration) — as a
    segment-deduped ``SharedGroupedTables`` pool for ``path="shared"``,
    dense grouped tables otherwise.

    With ``mesh=`` the segment axis (the flattened ``kh*kw*Cin`` receptive
    field) is sharded over ``mesh_axis``.  The fused/shared paths keep
    their **in-VMEM im2col even under the mesh**: each device's conv kernel
    rebuilds the patch in VMEM and indexes its local table slice directly
    via the kernels' ``seg_offset`` parameter (one ``psum`` of partial
    sums).  Only the host-packed paths (``gather``/``onehot``/``kernel``),
    which consume explicit offset tensors, extract patches host-side
    (``im2col``) and route through the sharded linear layer.
    """
    kh, kw, cin, cout = filters.shape
    n = kh * kw * cin
    pad_n = (-n) % group
    wflat = filters.reshape(n, cout)
    if pad_n:
        wflat = jnp.concatenate([wflat, jnp.zeros((pad_n, cout), wflat.dtype)], 0)
    if tables is None:
        if path == "shared":
            from .pcilt import build_shared_grouped_tables

            tables = build_shared_grouped_tables(wflat, spec, scale, group)
        else:
            tables = build_grouped_tables(wflat, spec, scale, group)
    if isinstance(tables, (ShardedSharedPool, SharedGroupedTables)):
        n_seg = tables.n_segments
    else:
        n_seg = tables.shape[0]
    sharded = (isinstance(tables, ShardedSharedPool)
               or mesh_shard_count(mesh, mesh_axis, n_seg) > 1)
    if path == "shared" and not isinstance(
            tables, (SharedGroupedTables, ShardedSharedPool)):
        raise ValueError(
            "path='shared' executes a SharedGroupedTables pool; "
            "build one with build_shared_grouped_tables (got dense "
            "tables)")
    if path == "fused" and isinstance(
            tables, (SharedGroupedTables, ShardedSharedPool)):
        raise ValueError(
            "path='fused' consumes dense [G, V, O] tables; use "
            "path='shared' for a SharedGroupedTables pool (or "
            "materialize() it explicitly)")
    if path in ("fused", "shared"):
        _check_contiguous_segments(path, None, n + pad_n, n_seg, group)
        if sharded:
            if isinstance(tables, SharedGroupedTables):
                tables = _shard_pool_for(
                    tables, mesh_shard_count(mesh, mesh_axis, n_seg))
            return _pcilt_conv2d_sharded_kernel(
                x, tables, spec, scale, group, kh, kw, stride, padding,
                path, mesh, mesh_axis)
        # Single-device / fallback: the same conv-native kernels, unsharded.
        from repro.kernels import ops  # local import: kernels are optional

        if path == "shared":
            return ops.pcilt_shared_conv2d(
                x, tables.pool, tables.seg_idx, spec, scale, tables.group,
                kh, kw, stride=stride, padding=padding
            )
        return ops.pcilt_fused_conv2d(
            x, tables, spec, scale, group, kh, kw, stride=stride,
            padding=padding
        )
    patches = im2col(x, kh, kw, stride, padding)
    if pad_n:
        zeros = jnp.zeros((*patches.shape[:-1], pad_n), patches.dtype)
        patches = jnp.concatenate([patches, zeros], axis=-1)
    return pcilt_linear(patches, tables, spec, scale, group, path=path,
                        mesh=mesh, mesh_axis=mesh_axis)


def _dwconv_pads(k: int, padding: str):
    try:
        return {"CAUSAL": (k - 1, 0),
                "SAME": ((k - 1) // 2, k - 1 - (k - 1) // 2),
                "VALID": (0, 0)}[padding]
    except KeyError:
        raise ValueError(
            f"padding must be CAUSAL|SAME|VALID, got {padding!r}") from None


def build_dwconv_tables(filters: jax.Array, spec: QuantSpec, scale) -> jax.Array:
    """Per-channel depthwise-conv1d PCILTs: ``[k, C]`` filters -> ``[C, V]``.

    Segment slot ``j`` corresponds to tap ``j`` (slot ``j`` of the packed
    offset holds the code at time ``t-k+1+j`` ⇒ weight ``filters[j]``).
    Offline, once per network lifetime — serving callers
    (``serving.PCILTDwConv1d``) cache the result instead of rebuilding the
    ``V``-entry enumeration on every step.
    """
    from .offsets import offset_grid
    from .quantization import code_values

    k, _ = filters.shape
    vals = code_values(spec, scale)[offset_grid(spec.bits, k)]  # [V, k]
    return jnp.einsum("vk,kc->cv", vals, filters.astype(vals.dtype),
                      precision=jax.lax.Precision.HIGHEST)


def pcilt_depthwise_conv1d(
    x: jax.Array,
    filters: jax.Array,
    spec: QuantSpec,
    scale,
    tables: Optional[jax.Array] = None,
    path: str = "gather",
    padding: str = "CAUSAL",
    return_stats: bool = False,
):
    """Depthwise conv1d where *one fetch produces one output element*.

    x: ``[B, T, C]``; filters: ``[k, C]`` (k taps per channel).  The k taps of
    a channel form exactly one PCILT segment, so the packed offset of the k
    input codes addresses a ``[C, K**k]`` table directly — the cleanest TPU
    incarnation of the paper's claim that small filters over large data are
    the technique's sweet spot (Mamba/Zamba frontends: k=4).

    ``padding``: ``"CAUSAL"`` (default — taps ``t-k+1..t``, the decode
    frontend), ``"SAME"`` (centered), or ``"VALID"`` (``T - k + 1``
    outputs).  ``path="fused"`` executes quantize + tap-stack + pack + fetch
    in one Pallas call (``repro.kernels.pcilt_fused_dwconv1d``) so the
    ``[B, T, C]`` offset tensor never exists in HBM; the host-packed paths
    (``gather``/``onehot``/``kernel``) build it explicitly.

    ``return_stats=True`` additionally returns the saturation ``(count,
    ratio)`` of the quantized signal (the :func:`quantize_with_stats`
    definition over the full ``[B, T, C]`` input — causal/SAME pad zeros
    quantize in range, so the count is the same for every padding mode).
    The fused path reduces the counters inside the kernel grid; the
    host-packed paths reuse the codes they quantize anyway.
    """
    k, C = filters.shape
    B, T, _ = x.shape
    if tables is None:
        tables = build_dwconv_tables(filters, spec, scale)
    if path == "fused":
        from repro.kernels import ops  # local import: kernels are optional

        if return_stats:
            return ops.pcilt_fused_dwconv1d(x, tables, spec, scale, k,
                                            padding=padding, with_stats=True)
        return ops.pcilt_fused_dwconv1d(x, tables, spec, scale, k,
                                        padding=padding)
    if return_stats:
        codes, count, ratio = quantize_with_stats(x, spec, scale)  # [B,T,C]
    else:
        codes = quantize(x, spec, scale)  # [B, T, C]
    lo, hi = _dwconv_pads(k, padding)
    padded = jnp.pad(codes, ((0, 0), (lo, hi), (0, 0)))
    To = padded.shape[1] - k + 1
    # Tap window: stack codes feeding output t  ->  [B, To, C, k]
    taps = jnp.stack([padded[:, i : i + To] for i in range(k)], axis=-1)
    shifts = jnp.arange(k, dtype=jnp.int32) * spec.bits
    offsets = jnp.sum(
        jnp.left_shift(taps.astype(jnp.int32), shifts[None, None, None]), axis=-1
    )  # [B, To, C]
    if path == "gather":
        out = jnp.take_along_axis(
            jnp.broadcast_to(tables, (B, To) + tables.shape),
            offsets[..., None],
            axis=-1,
        )[..., 0]
    elif path == "onehot":
        V = tables.shape[-1]
        oh = jax.nn.one_hot(offsets, V, dtype=tables.dtype)  # [B,To,C,V]
        out = jnp.einsum("btcv,cv->btc", oh, tables)
    elif path == "kernel":
        from repro.kernels import ops

        out = ops.pcilt_dwconv1d(offsets, tables)
    else:
        raise ValueError(f"unknown path {path!r}")
    if return_stats:
        return out, count, ratio
    return out
