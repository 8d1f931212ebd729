"""Pre-Calculated Inference Lookup Table (PCILT) construction.

"Prior to the learning start, the multiplications of the filter values by all
possible activation values are calculated and placed in pre-calculated lookup
tables" (paper, §Basic Version).  This module builds those tables, in all the
paper's flavors:

* **scalar tables** — one table per weight, ``T[k, a] = f(w_k, val(a))``
  (basic algorithm, Fig. 1);
* **grouped tables** — one table per weight *segment*, entries hold the
  pre-summed partial dot product of the whole segment against one packed
  offset (extension 1, Fig. 5);
* **shared tables** — tables dedupe to the weight's *actual* cardinality;
  layers keep integer pointers into a shared pool (extension 3), with an
  optional second indirection level onto unique table *values*;
* **shared grouped tables** — extension 3 applied at *segment* granularity:
  the grouped ``[G, V, out]`` tables dedupe to a ``pool[X, V, out]`` of the
  ``X`` unique segment tables plus a ``seg_idx[G]`` int32 pointer vector
  (``SharedGroupedTables``).  Two segments share a pool row iff their
  ``[group, out]`` weight blocks are identical — the regime weight
  clustering / palettization / low weight cardinality produces, where
  ``X << G`` and table memory shrinks by ``G/X``.  This is the
  representation the shared-pool fused kernel
  (``repro.kernels.pcilt_shared``) consumes directly from VMEM;
* **custom convolutional functions** — ``f`` need not be multiplication
  (extension 2); any ``f(w, a_val)`` builds at the same cost and executes at
  zero extra inference cost.

Memory accounting lives here too (``table_bytes`` and friends) — the paper's
own feasibility argument is a memory argument, and ``benchmarks/paper_claims``
reproduces its 1.65 GB / ~100 MB / ~75 MB / ~25 MB / ~18 MB examples from
these formulas.

Sharded-table layout (tensor-parallel decode)
---------------------------------------------

Grouped tables for real LM projections reach GBs (``benchmarks/run.py``
``lm.*`` rows) — past single-device HBM.  The mesh execution path shards the
**segment axis** ``G`` across the ``"model"`` mesh axis:

* dense ``[G, V, O]`` tables live under ``PartitionSpec("model", None, None)``
  (logical axis ``"table_seg"`` in ``repro.nn.module.DEFAULT_RULES``), so each
  of the ``D`` devices holds the ``[G/D, V, O]`` tables of its contiguous
  segment block — per-device table bytes shrink linearly with the model axis;
* the paper's adder tree ``sum_s T[s, off_s]`` is associative, so each device
  fetches and sums only its local segments and one ``psum`` over ``"model"``
  combines the partial sums (the single cross-device collective, placed in
  ``repro.core.lut_layers``);
* shared (ext.-3) pools are sharded by **partitioning the pointer vector**:
  shard ``d`` keeps only the pool rows its ``seg_idx[d*G/D:(d+1)*G/D]`` slice
  references, remapped to local indices (:class:`ShardedSharedPool`,
  :func:`shard_shared_grouped_tables`) — per-device pool memory scales with
  the *local* cardinality ``X_d <= X``, preserving the extension-3 property
  under tensor parallelism.

If the mesh axis does not divide ``G``, execution falls back to replication
(single-device semantics), mirroring the divisibility fallback of
``repro.nn.module.ShardingRules``.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, List, Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from .quantization import QuantSpec, code_values
from .offsets import SegmentPlan, offset_grid
from repro.runtime.tracing import span

__all__ = [
    "mul_fn",
    "log_mul_fn",
    "build_scalar_tables",
    "build_grouped_tables",
    "build_paired_tables",
    "build_paired_stacked_tables",
    "SharedTables",
    "build_shared_tables",
    "SharedGroupedTables",
    "build_shared_grouped_tables",
    "ShardedSharedPool",
    "shard_shared_grouped_tables",
    "table_bytes",
    "grouped_table_bytes",
    "shared_table_bytes",
    "shared_pool_bytes",
    "build_cost_multiplies",
    "table_checksum",
    "table_checksums",
    "slice_checksums",
    "stacked_checksums",
]

# ----------------------------------------------------------------------------
# Convolutional functions (extension 2).  A convolutional function maps a
# (weight, activation-value) pair to the number that enters the adder tree.
# The classic choice is multiplication; anything else rides for free because
# only the table build evaluates it.
# ----------------------------------------------------------------------------


def mul_fn(w, a):
    """The classic convolution: plain product."""
    return w * a


def log_mul_fn(w, a, gamma: float = 1.0):
    """A paper-suggested custom function: log-compressed product.

    Rescales the inferred value range non-uniformly (paper: "re-scale and
    modify the range of the inferred values and their distribution").
    """
    p = w * a
    return jnp.sign(p) * jnp.log1p(gamma * jnp.abs(p)) / gamma


# ----------------------------------------------------------------------------
# Table builders
# ----------------------------------------------------------------------------


def build_scalar_tables(
    w: jax.Array,
    spec: QuantSpec,
    scale,
    fn: Callable = mul_fn,
    dtype=jnp.float32,
) -> jax.Array:
    """Basic PCILT: per-weight tables.

    w: ``[n, out]`` reduction-major weights (a conv filter is flattened to
      ``n = kh*kw*cin`` per output channel).
    Returns ``T[n, K, out]`` with ``T[k, a, o] = fn(w[k, o], val(a))``.
    """
    vals = code_values(spec, scale, dtype)  # [K]
    return fn(w[:, None, :].astype(dtype), vals[None, :, None])


def build_grouped_tables(
    w: jax.Array,
    spec: QuantSpec,
    scale,
    group: int,
    plan: Optional[SegmentPlan] = None,
    fn: Callable = mul_fn,
    dtype=jnp.float32,
    build_chunk: int = 4096,
) -> jax.Array:
    """Extension-1 PCILT: per-segment pre-summed tables (Fig. 5).

    w: ``[n, out]``; segments follow ``plan`` (default: ``group`` contiguous
    positions per segment).  Returns ``T[G, V, out]`` with ``V = K**group``::

        T[s, v, o] = sum_j fn(w_seg[s, j, o], val(code_j(v)))

    so that a single fetch ``T[s, offset, o]`` yields the entire segment's
    contribution.  Built once per network lifetime; the build enumerates all
    ``V`` offsets (chunked so huge ``V`` stays within memory).
    """
    n, out = w.shape
    if plan is None:
        plan = SegmentPlan.contiguous(n, group)
    w_seg = plan.gather_weights(w).astype(dtype)  # [G, g, out]
    grid = offset_grid(spec.bits, plan.group)  # [V, g] codes
    vals = code_values(spec, scale, dtype)[grid]  # [V, g] values
    V = vals.shape[0]

    if fn is mul_fn:
        # full precision: a TPU's default f32 contraction rounds operands
        # to bf16, and a table must hold the exact pre-summed products
        return jnp.einsum("vj,gjo->gvo", vals, w_seg,
                          precision=jax.lax.Precision.HIGHEST)

    def chunk_tables(vchunk):  # [C, g] -> [G, C, out]
        contrib = fn(w_seg[:, None, :, :], vchunk[None, :, :, None])
        return jnp.sum(contrib, axis=2)

    chunks = [
        chunk_tables(vals[i : i + build_chunk]) for i in range(0, V, build_chunk)
    ]
    return jnp.concatenate(chunks, axis=1)


def build_paired_tables(
    w: jax.Array,
    spec: QuantSpec,
    scale,
    group: int,
    fn: Callable = mul_fn,
    dtype=jnp.float32,
    build_chunk: int = 4096,
) -> jax.Array:
    """TL1-style paired (multi-scalar) tables: ``[ceil(G/2), V**2, out]``.

    Pairs adjacent ``group``-wide segments into one double-wide segment so a
    single fetch covers *two* segments' worth of weights: the table trades
    ``V`` entries for ``V**2`` while halving the segment count ``G`` — half
    the fetches, half the adder-tree depth on the hot decode path.

    The paired index is **little-endian in the pair**, matching the fused
    kernels' ``_pack_flat`` shift-or over ``2*group`` codes::

        paired_off = off_even + off_odd * V        (V = K**group)

    so ``T2[s, off_even + off_odd*V] == T[2s, off_even] + T[2s+1, off_odd]``
    exactly (each paired entry is a single pre-summed dot over the combined
    ``2*group`` weights — same summation the unpaired pair of fetches adds at
    runtime).  When ``G`` is odd, ``w`` is zero-padded by one phantom segment
    whose table column is exactly zero under ``mul_fn`` (``0 * val == 0``),
    so parity with the unpaired tables holds bit-exactly.  ``dtype`` may be
    bf16 — the build is one einsum in ``dtype``, same as the unpaired build.
    """
    n, out = w.shape
    pair = 2 * group
    pad = (-n) % pair
    if pad:
        w = jnp.concatenate([w, jnp.zeros((pad, out), w.dtype)], axis=0)
    return build_grouped_tables(w, spec, scale, pair, fn=fn, dtype=dtype,
                                build_chunk=build_chunk)


def build_paired_stacked_tables(
    ws: jax.Array,
    spec: QuantSpec,
    scales,
    group: int,
    fn: Callable = mul_fn,
    dtype=jnp.float32,
) -> jax.Array:
    """Layer-stacked paired tables in **segment-major** layout
    ``[G2, L, V**2, out]`` (``G2 = ceil(G/2)``).

    ``ws`` is ``[L, n, out]`` (one projection per layer), ``scales`` is
    ``[L]``.  Segment-major rather than the dense stack's layer-major
    ``[L, G, V, O]`` so the stacked paired kernel can fold the layer into the
    table's *value* axis: the BlockSpec stages a ``[Gb, L, V**2, Ob]`` block
    whose segment index is constant in the prefetched layer, and the kernel
    indexes row ``l*V**2 + off`` of the reshaped ``[Gb, L*V**2, Ob]`` block —
    a constant-iota row-gather XLA lowers to its batched fast path, instead
    of the traced-layer general gather that made the dense layout slow.
    """
    build = functools.partial(build_paired_tables, spec=spec, group=group,
                              fn=fn, dtype=dtype)
    t = jax.vmap(lambda w, s: build(w, scale=s))(ws, scales)  # [L, G2, V2, O]
    return jnp.transpose(t, (1, 0, 2, 3))


# ----------------------------------------------------------------------------
# Shared tables (extension 3)
# ----------------------------------------------------------------------------


@dataclasses.dataclass
class SharedTables:
    """Weight-deduped PCILT pool.

    ``pool[x, a] = fn(unique_w[x], val(a))`` and every layer weight is replaced
    by a pointer ``w_idx`` into the pool — "keep only one PCILT for given
    algorithm base value(s) and replace the others with pointers to it".

    With ``value_pool`` set, a second indirection maps table cells onto unique
    *values* (the paper's variant for low per-value diversity): ``pool`` then
    holds integer indices into ``value_pool``.
    """

    pool: jax.Array  # [X, K] table values, or int indices if value_pool
    w_idx: jax.Array  # [n, out] uint16 pointers into pool rows
    unique_w: jax.Array  # [X]
    value_pool: Optional[jax.Array] = None  # [U] unique table values
    #: lazily-built 1-wide segment pool (offline np.unique — built once)
    _grouped: Optional["SharedGroupedTables"] = dataclasses.field(
        default=None, repr=False, compare=False)

    def as_grouped_pool(self) -> "SharedGroupedTables":
        """The scalar pool re-expressed as a 1-wide segment pool
        (:class:`SharedGroupedTables` with ``group=1``).

        Each of the ``n`` weight positions is a 1-wide segment whose table is
        the ``[K, out]`` slice its pointer row selects; positions with
        bit-identical pointer rows share one pool row, so the pool holds only
        the ``X' <= n`` *distinct rows of the pointer matrix* — never the
        dense ``[n, K, out]`` tables ``materialize()`` expands in HBM.  This
        is how the scalar-level extension-3 representation reaches the fused
        shared kernel (``path="shared"``) and the pointer-gather lookup.
        Must run outside jit (``np.unique`` on concrete pointers — part of
        the offline table build; the result is cached on the instance).
        """
        if self._grouped is None:
            pool = self.pool
            if self.value_pool is not None:
                pool = self.value_pool[pool]
            idx = np.asarray(self.w_idx)
            rows, inv = np.unique(idx, axis=0, return_inverse=True)  # [X',out]
            seg_pool = jnp.transpose(
                jnp.take(jnp.asarray(pool), jnp.asarray(rows), axis=0),
                (0, 2, 1))  # [X', out, K] -> [X', K, out]
            self._grouped = SharedGroupedTables(
                pool=seg_pool,
                seg_idx=jnp.asarray(inv.reshape(-1), jnp.int32),
                group=1,
            )
        return self._grouped

    def lookup(self, codes: jax.Array) -> jax.Array:
        """codes ``[..., n]`` -> summed dot result ``[..., out]``.

        Routed through the 1-wide segment pool's pointer-gather
        (:meth:`as_grouped_pool`): two advanced indexes on the deduped pool
        and one adder-tree sum — the dense ``[n, K, out]`` tables are never
        materialized in HBM.  Table-bytes accounting is unchanged (the pool
        is the same ``[X', K]``-cell storage, only re-blocked per segment).
        """
        return self.as_grouped_pool().lookup(codes.astype(jnp.int32))

    def materialize(self) -> jax.Array:
        """Expand pointers back into dense per-weight tables ``[n, K, out]``.

        Exists for parity tests and memory-accounting comparisons only — the
        execution paths (:meth:`lookup`, ``path="shared"``) go through
        :meth:`as_grouped_pool` and never call this.
        """
        pool = self.pool
        if self.value_pool is not None:
            pool = self.value_pool[pool]
        return jnp.transpose(pool[self.w_idx], (0, 2, 1))  # [n, out, K]->[n,K,out]

    @property
    def actual_cardinality(self) -> int:
        return int(self.unique_w.shape[0])


def build_shared_tables(
    w: jax.Array,
    spec: QuantSpec,
    scale,
    fn: Callable = mul_fn,
    dedup_values: bool = False,
    dtype=jnp.float32,
) -> SharedTables:
    """Build the shared pool for weights whose *actual* cardinality is small.

    Must run outside jit (uses ``np.unique`` on concrete weights — table
    construction is an offline, once-per-lifetime step in the paper).
    """
    w_np = np.asarray(w)
    uniq, inv = np.unique(w_np, return_inverse=True)
    vals = code_values(spec, scale, dtype)  # [K]
    pool = fn(jnp.asarray(uniq, dtype)[:, None], vals[None, :])  # [X, K]
    value_pool = None
    if dedup_values:
        pv, pinv = np.unique(np.asarray(pool), return_inverse=True)
        value_pool = jnp.asarray(pv, dtype)
        pool = jnp.asarray(pinv.reshape(pool.shape), jnp.int32)
    return SharedTables(
        pool=pool,
        w_idx=jnp.asarray(inv.reshape(w_np.shape), jnp.int32),
        unique_w=jnp.asarray(uniq, dtype),
        value_pool=value_pool,
    )


# ----------------------------------------------------------------------------
# Shared grouped tables (extension 3 at segment granularity)
# ----------------------------------------------------------------------------


@dataclasses.dataclass
class SharedGroupedTables:
    """Segment-deduped grouped PCILT pool (extension 3 over extension 1).

    ``pool[x, v, o]`` holds the ``X`` *unique* segment tables; ``seg_idx[g]``
    points segment ``g`` at its pool row, so the dense grouped tables are
    recoverable as ``pool[seg_idx]`` — "keep only one PCILT for given
    algorithm base value(s) and replace the others with pointers to it",
    applied to whole ``[group, out]`` weight segments instead of scalar
    weights.  Table memory scales with the weights' actual segment
    cardinality ``X``, not the nominal segment count ``G``.
    """

    pool: jax.Array  # [X, V, out] unique segment tables
    seg_idx: jax.Array  # [G] int32 pointers into pool rows
    group: int  # codes packed per offset (V == K**group)

    @property
    def n_segments(self) -> int:
        return int(self.seg_idx.shape[0])

    @property
    def pool_cardinality(self) -> int:
        return int(self.pool.shape[0])

    def materialize(self) -> jax.Array:
        """Expand pointers back into dense grouped tables ``[G, V, out]``.

        Exists for parity testing and for callers that insist on the dense
        fused path — the shared-pool kernel never calls it.
        """
        return jnp.take(self.pool, self.seg_idx, axis=0)

    def lookup(self, offsets: jax.Array) -> jax.Array:
        """Gather path: offsets ``[..., G]`` -> ``[..., out]`` without ever
        materializing the dense tables (double advanced-index on the pool)."""
        partial = self.pool[self.seg_idx, offsets.astype(jnp.int32)]
        return jnp.sum(partial, axis=-2)

    def pool_bytes(self, value_bytes: Optional[int] = None) -> int:
        """Ext.-3 memory: unique segment tables plus the pointer vector."""
        X, V, out = self.pool.shape
        vb = value_bytes if value_bytes is not None else self.pool.dtype.itemsize
        # The pool is exactly ext.-3 accounting with one packed-offset "act
        # bits" entry of log2(V), each table cell holding an out-vector.
        return (shared_table_bytes(X, [(V - 1).bit_length()], out * vb)
                + self.n_segments * self.seg_idx.dtype.itemsize)

    def dense_bytes(self, value_bytes: Optional[int] = None) -> int:
        """What the equivalent dense ``[G, V, out]`` tables would occupy."""
        _, V, out = self.pool.shape
        vb = value_bytes if value_bytes is not None else self.pool.dtype.itemsize
        return self.n_segments * V * out * vb

    @property
    def dedup_ratio(self) -> float:
        """Dense-to-pool table-memory ratio (≈ ``G / X`` for large tables)."""
        return self.dense_bytes() / max(self.pool_bytes(), 1)


def build_shared_grouped_tables(
    w: jax.Array,
    spec: QuantSpec,
    scale,
    group: int,
    plan: Optional[SegmentPlan] = None,
    fn: Callable = mul_fn,
    dtype=jnp.float32,
    build_chunk: int = 4096,
) -> SharedGroupedTables:
    """Segment-level extension-3 dedup over the grouped-table build.

    w: ``[n, out]`` reduction-major weights.  Segments follow ``plan``
    (default contiguous); segments whose ``[group, out]`` weight blocks are
    bit-identical share one pool row.  Only the ``X`` unique segment tables
    are ever built — the build cost, like the memory, scales with the actual
    segment cardinality.  Must run outside jit (``np.unique`` on concrete
    weights; table construction is the paper's offline once-per-lifetime
    step).
    """
    n, out = w.shape
    if plan is None:
        plan = SegmentPlan.contiguous(n, group)
    w_seg = np.asarray(plan.gather_weights(jnp.asarray(w)))  # [G, g, out]
    G = w_seg.shape[0]
    uniq, inv = np.unique(w_seg.reshape(G, -1), axis=0, return_inverse=True)
    X = uniq.shape[0]
    uw = jnp.asarray(uniq.reshape(X, plan.group, out), dtype)
    grid = offset_grid(spec.bits, plan.group)  # [V, g] codes
    vals = code_values(spec, scale, dtype)[grid]  # [V, g] values
    V = vals.shape[0]

    if fn is mul_fn:
        pool = jnp.einsum("vj,xjo->xvo", vals, uw,
                          precision=jax.lax.Precision.HIGHEST)
    else:
        def chunk_tables(vchunk):  # [C, g] -> [X, C, out]
            contrib = fn(uw[:, None, :, :], vchunk[None, :, :, None])
            return jnp.sum(contrib, axis=2)

        pool = jnp.concatenate(
            [chunk_tables(vals[i:i + build_chunk])
             for i in range(0, V, build_chunk)], axis=1)
    return SharedGroupedTables(
        pool=pool,
        seg_idx=jnp.asarray(inv.reshape(-1), jnp.int32),
        group=plan.group,
    )


# ----------------------------------------------------------------------------
# Mesh-sharded shared pools (extension 3 under tensor parallelism)
# ----------------------------------------------------------------------------


@dataclasses.dataclass
class ShardedSharedPool:
    """Per-shard shared pools for mesh execution of an ext.-3 layer.

    The global ``SharedGroupedTables`` is partitioned along the segment axis
    into ``D`` contiguous blocks of ``Gl = G / D`` segments.  Shard ``d``
    keeps only the pool rows its pointer slice references — its *local*
    cardinality ``X_d <= X`` — remapped to local indices, and every local
    pool is zero-padded to ``Xmax = max_d X_d`` so the stacked operands have
    uniform shapes for ``shard_map`` (padded rows are never referenced by any
    local pointer).

    Layout (leading axis = shard = ``"model"`` mesh axis):

    * ``pools   [D, Xmax, V, O]`` — ``PartitionSpec("model", None, None, None)``
    * ``seg_idx [D, Gl]`` int32   — ``PartitionSpec("model", None)``

    so under ``shard_map`` each device sees one ``[Xmax, V, O]`` local pool
    plus its ``[Gl]`` local pointers, executes the shared-pool kernel over
    them, and contributes its partial adder-tree sum to the ``psum`` over the
    model axis.  Per-device table memory is ``Xmax*V*O*itemsize + Gl*4`` —
    local-``X`` pool math, not global ``G`` or global ``X``.
    """

    pools: jax.Array  # [D, Xmax, V, O] stacked local pools (rows zero-padded)
    seg_idx: jax.Array  # [D, Gl] int32 local pointers into the local pool
    group: int  # codes packed per offset (V == K**group)
    shard_cards: Tuple[int, ...] = ()  # true per-shard cardinality X_d (pre-pad)

    @property
    def n_shards(self) -> int:
        return int(self.pools.shape[0])

    @property
    def n_segments(self) -> int:
        return int(self.seg_idx.shape[0] * self.seg_idx.shape[1])

    @property
    def max_cardinality(self) -> int:
        """Padded local pool rows ``Xmax`` — what every device stages."""
        return int(self.pools.shape[1])

    def local_pool_bytes(self, value_bytes: Optional[int] = None) -> int:
        """Per-device table memory: the padded local pool + local pointers."""
        _, Xmax, V, out = self.pools.shape
        vb = value_bytes if value_bytes is not None else self.pools.dtype.itemsize
        return (shared_table_bytes(Xmax, [(V - 1).bit_length()], out * vb)
                + self.seg_idx.shape[1] * self.seg_idx.dtype.itemsize)

    def materialize(self) -> jax.Array:
        """Dense ``[G, V, O]`` tables recovered shard by shard (parity tests)."""
        parts = [jnp.take(self.pools[d], self.seg_idx[d], axis=0)
                 for d in range(self.n_shards)]
        return jnp.concatenate(parts, axis=0)


def shard_shared_grouped_tables(
    st: SharedGroupedTables, n_shards: int
) -> ShardedSharedPool:
    """Offline shard build: partition ``seg_idx`` and dedupe pools per shard.

    Must run outside jit (``np.unique`` on concrete pointers — like every
    table build, sharding is part of the paper's once-per-lifetime offline
    step).  ``n_shards`` must divide ``G``; the mesh execution path applies
    its divisibility fallback *before* calling this.
    """
    G = st.n_segments
    if n_shards < 1 or G % n_shards:
        raise ValueError(
            f"n_shards={n_shards} must divide the segment count G={G} "
            f"(the caller applies the replication fallback otherwise)")
    Gl = G // n_shards
    si = np.asarray(st.seg_idx)
    pool = np.asarray(st.pool)
    locals_: list = []
    for d in range(n_shards):
        rows, inv = np.unique(si[d * Gl:(d + 1) * Gl], return_inverse=True)
        locals_.append((rows, inv.astype(np.int32)))
    x_max = max(len(rows) for rows, _ in locals_)
    pools = np.zeros((n_shards, x_max) + pool.shape[1:], pool.dtype)
    idx = np.zeros((n_shards, Gl), np.int32)
    for d, (rows, inv) in enumerate(locals_):
        pools[d, : len(rows)] = pool[rows]
        idx[d] = inv
    return ShardedSharedPool(
        pools=jnp.asarray(pools),
        seg_idx=jnp.asarray(idx),
        group=st.group,
        shard_cards=tuple(len(rows) for rows, _ in locals_),
    )


# ----------------------------------------------------------------------------
# Memory & build-cost accounting (drives benchmarks/paper_claims.py)
# ----------------------------------------------------------------------------


def table_bytes(n_weights: int, act_bits: int, value_bytes: int) -> int:
    """Basic-PCILT memory: one ``2**act_bits``-entry table per weight."""
    return n_weights * (1 << act_bits) * value_bytes


def grouped_table_bytes(
    n_weights: int, act_bits: int, group: int, value_bytes: int
) -> int:
    """Extension-1 memory: ``K**group`` entries per segment of ``group`` weights."""
    segments = -(-n_weights // group)
    return segments * (1 << (act_bits * group)) * value_bytes


def shared_table_bytes(
    actual_cardinality: int, act_bits_list: Sequence[int], value_bytes: int,
    nested: bool = False,
) -> int:
    """Extension-3 memory: unique tables only.

    ``nested=True`` models the paper's note that the table for a lower
    cardinality is a prefix of the higher-cardinality one, so only the largest
    table per base value is kept.
    """
    if nested:
        return actual_cardinality * (1 << max(act_bits_list)) * value_bytes
    return actual_cardinality * sum(1 << b for b in act_bits_list) * value_bytes


def shared_pool_bytes(pool_cardinality: int, act_bits: int, group: int,
                      out: int, value_bytes: int,
                      n_segments: int = 0, ptr_bytes: int = 4) -> int:
    """Segment-level extension-3 memory: ``X`` unique ``[K**group, out]``
    segment tables (plus the ``[G]`` pointer vector when ``n_segments`` is
    given) — the pool the shared fused kernel stages.  Delegates to
    :func:`shared_table_bytes` with the packed-offset width as the single
    "act bits" entry."""
    return (shared_table_bytes(pool_cardinality, [act_bits * group],
                               out * value_bytes)
            + n_segments * ptr_bytes)


def build_cost_multiplies(n_weights: int, act_bits: int) -> int:
    """Multiplications to build basic tables (paper: 5x5 INT8 -> 6,400)."""
    return n_weights * (1 << act_bits)


# ----------------------------------------------------------------------------
# Table integrity (serving resilience).  Tables are immutable deployment
# artifacts — any in-memory difference from the conversion-time bytes is
# corruption.  The checksum is a jitted reduction over the device array
# itself, so it reads the bytes the kernels read, and it never copies a
# table to the host.  Its record holds two 32-bit lanes over the array's
# bytes viewed as little-endian uint32 words w_i (16- and 8-bit entries
# packed to a word, the tail zero-padded):
#
# * lane 1, sum(w_i) mod 2**32: any change inside one word, and any error
#   burst of <= 32 bits in the byte stream, is always caught — such a burst
#   changes word i by a nonzero multiple of 2**a and word i+1 by less than
#   2**a, so the two can never cancel.  A single flipped table entry
#   (float32/bfloat16 value, int32 seg_idx pointer) can never be missed —
#   the zero-false-negative property the chaos suite unit-tests;
# * lane 2, sum(fmix32(w_i ^ h(i))) mod 2**32, with ``fmix32`` MurmurHash3's
#   bijective finalizer and h a position hash: swapped entries and changes
#   to several words are missed with probability about 2**-32.
#
# Both lanes are wrapping sums, so the record depends neither on XLA's
# reduction order nor on how the array is sharded: a sharded table reduces
# in place and records what the unsharded one does.
# ----------------------------------------------------------------------------

_WEYL = 0x9E3779B9  # position hash h(i) = i * 2**32 / golden ratio


def _fmix32(h: jax.Array) -> jax.Array:
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(0xC2B2AE35)
    return h ^ (h >> 16)


def _words(x: jax.Array) -> jax.Array:
    """``x``'s bytes (1-, 2- or 4-byte entries) as little-endian
    ``uint32`` words: narrower entries pack along the last axis (the array
    is flattened and zero-padded first when that axis does not divide into
    words)."""
    size = x.dtype.itemsize
    if size == 4:
        return jax.lax.bitcast_convert_type(x, jnp.uint32)
    per = 4 // size
    u = jax.lax.bitcast_convert_type(x, jnp.dtype(f"uint{8 * size}"))
    if u.ndim == 0 or u.shape[-1] % per:
        u = u.reshape(-1)
        u = jnp.pad(u, (0, -u.size % per))
    return jax.lax.bitcast_convert_type(
        u.reshape(*u.shape[:-1], -1, per), jnp.uint32)


def _lanes(x: jax.Array) -> jax.Array:
    """The checksum's two lanes over all of ``x``: ``uint32[2]``."""
    w = _words(x)
    i = jnp.zeros(w.shape, jnp.uint32)
    stride = 1
    for d in reversed(range(w.ndim)):  # row-major word index
        i = i + jax.lax.broadcasted_iota(jnp.uint32, w.shape, d) * \
            jnp.uint32(stride % (1 << 32))
        stride *= w.shape[d]
    return jnp.stack([
        jnp.sum(w, dtype=jnp.uint32),
        jnp.sum(_fmix32(w ^ (i * jnp.uint32(_WEYL))), dtype=jnp.uint32)])


@jax.jit
def _table_lanes(*arrays) -> jax.Array:
    with jax.named_scope("checksum"):
        return jnp.stack([_lanes(a) for a in arrays])


@functools.partial(jax.jit, static_argnames="axes")
def _slice_lanes(index, *stacks, axes) -> jax.Array:
    # the slice is read in place: the dynamic slice fuses into the
    # reductions, so no copy of it is made
    with jax.named_scope("checksum"):
        return jnp.stack([
            _lanes(jax.lax.dynamic_index_in_dim(t, index, a, keepdims=False))
            for t, a in zip(stacks, axes)])


def _records(lanes) -> List[int]:
    """``[n, 2]`` lanes -> one 64-bit record (a Python int) per row: lane
    1 in the low 32 bits, lane 2 in the high."""
    return [int(a) | int(b) << 32
            for a, b in np.asarray(jax.device_get(lanes), np.uint64)]


def table_checksums(*arrays) -> List[int]:
    """The checksum record of each whole array, in one device call."""
    with span("integrity.checksum", bytes=sum(int(a.nbytes) for a in arrays)):
        return _records(_table_lanes(*arrays))


def table_checksum(arr) -> int:
    """The checksum record of a whole table array (sharded arrays reduce
    in place)."""
    return table_checksums(arr)[0]


def slice_checksums(index: int, stacks: Sequence, axes: Sequence[int]
                    ) -> List[int]:
    """The record of slice ``index`` of each stack along its axis, in one
    device call (``index`` is traced: one compile serves every layer)."""
    axes = tuple(int(a) for a in axes)
    with span("integrity.checksum",
              bytes=sum(int(t.nbytes) // t.shape[a]
                        for t, a in zip(stacks, axes))):
        return _records(_slice_lanes(int(index), *stacks, axes=axes))


def stacked_checksums(arr, axis: int = 0) -> List[int]:
    """Per-layer records for a stacked table — one per slice along
    ``axis``, so verification localizes a breach to the layer that must be
    demoted.  Dense stacks are layer-major (``[L, G, V, O]``, ``axis=0``);
    paired stacks are segment-major (``[G2, L, V**2, O]``, ``axis=1``).
    Each slice goes through the function of :func:`slice_checksums`, so a
    record equals the layer check ``PCILTMambaDecode.verify_layer`` makes.
    """
    with span("integrity.checksum", bytes=int(arr.nbytes)):
        return _records(jnp.concatenate([
            _slice_lanes(i, arr, axes=(int(axis),))
            for i in range(arr.shape[axis])]))
