"""PCILT serving-mode conversion for LM decode paths.

Implements the paper's deployment story for the framework's language models:
an *offline* table build ("done only once in the lifetime of a CNN") that
converts selected projection kernels into grouped PCILTs, plus the decode
helpers that execute them via the fetch paths.  Used by
``examples/serve_pcilt.py`` and the integration tests; the per-architecture
table-memory accounting (the paper's own feasibility analysis applied to the
10 assigned archs) is in ``benchmarks/paper_claims.py``.

Scoping (DESIGN.md §6): tables address the *decode GEMV* regime — batch-
starved, memory-bound — and the conv frontends.  Weight-side cardinality is
reduced by weight quantization first (paper: tables exist per distinct weight
value; shared-PCILT keeps memory feasible).
"""

from __future__ import annotations

import logging
from typing import Any, Dict, List, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from .quantization import (QuantSpec, calibrate, fake_quant, quantize,
                           dequantize, scale_from_amax)
from .pcilt import (SharedGroupedTables, ShardedSharedPool,
                    build_grouped_tables, build_shared_grouped_tables,
                    shard_shared_grouped_tables, slice_checksums,
                    stacked_checksums, table_checksum, table_checksums)
from .lut_layers import (build_dwconv_tables, mesh_shard_count, pcilt_conv2d,
                         pcilt_depthwise_conv1d, pcilt_linear)
from repro.runtime.tracing import span

log = logging.getLogger("repro.serving")

__all__ = ["PCILTLinear", "PCILTConv2d", "PCILTDwConv1d", "convert_kernel",
           "convert_conv_kernel", "convert_dwconv", "convert_mamba_decode",
           "PCILTMambaDecode", "HealthMonitor", "pcilt_integrity",
           "pcilt_apply", "mlp_table_bytes"]


def pcilt_integrity(pcilt: Dict) -> Dict:
    """Conversion-time checksum record of every table array in a Mamba
    PCILT bundle (``core.pcilt.table_checksum``, computed on the device
    over the arrays' own bytes) — per layer for the stacked arrays, so
    verification localizes a breach to the layer the health monitor must
    demote.  A change inside one 32-bit word and any error burst of <= 32
    bits are always caught, so a single flipped table entry (f32/bf16
    value, int32 pointer) can never slip through; any other change is
    missed with probability about 2**-32."""
    integ: Dict[str, Any] = {"conv": stacked_checksums(pcilt["tables"])}
    proj = pcilt.get("proj")
    if proj is not None:
        # paired bundles stack seg-major ([G/2, L, V^2, O]) so the fused
        # kernel's table blocks are contiguous; the layer axis is axis 1
        axis = 1 if proj.get("paired") else 0
        integ["proj"] = {name: stacked_checksums(t, axis=axis)
                        for name, t in proj["tables"].items()}
    head = pcilt.get("head")
    if head is not None:
        pool, seg_idx = table_checksums(head["pool"], head["seg_idx"])
        integ["head"] = {"pool": pool, "seg_idx": seg_idx}
    return integ


def _place_sharded_pool(sp: ShardedSharedPool, mesh,
                        mesh_axis: str) -> ShardedSharedPool:
    """Park each local pool + pointer block on its device (the whole point
    is that no device ever holds the global pool)."""
    from repro.nn.module import pcilt_table_sharding

    return ShardedSharedPool(
        pools=jax.device_put(
            sp.pools, pcilt_table_sharding(mesh, sp.n_shards, ndim=4,
                                           mesh_axis=mesh_axis)),
        seg_idx=jax.device_put(
            sp.seg_idx, pcilt_table_sharding(mesh, sp.n_shards, ndim=2,
                                             mesh_axis=mesh_axis)),
        group=sp.group, shard_cards=sp.shard_cards)


class PCILTLinear:
    """A converted projection: grouped tables + activation quantizer.

    ``path="fused"`` executes the whole quantize→pack→fetch pipeline in one
    Pallas call (``repro.kernels.pcilt_fused``); ``path="shared"`` does the
    same over an extension-3 segment-deduped pool (``repro.kernels.
    pcilt_shared``) — the configuration that keeps table memory feasible for
    real LM projections.  All kernel paths dispatch tile shapes through the
    persistent autotune lookup table.  Call :meth:`tune` once per decode
    shape at serving warmup to populate it — every later dispatch (this
    process or the next) is a pure cache hit.

    Exactly one table representation needs to exist: dense ``tables``
    (``[G, V, O]``) and/or a ``shared`` pool.  A shared-only instance (the
    memory-feasible deployment) executes ``path="gather"`` and
    ``path="shared"``; dense-only instances execute everything else.

    With ``mesh=``, the layer is tensor-parallel: dense tables are placed
    under ``PartitionSpec(mesh_axis, None, None)`` (each device holds the
    ``[G/D, V, O]`` shard), a shared pool is pre-sharded into a
    ``ShardedSharedPool`` (per-device memory scales with the *local* pool
    cardinality), every ``__call__`` runs the fetch under ``shard_map`` with
    one ``psum`` of the partial adder-tree sums, and :meth:`tune` keys the
    autotune cache on the **local shard shape** — the shape the kernels
    actually see per device.  When ``mesh_axis`` does not divide ``G`` the
    layer falls back to replicated execution (divisibility fallback).
    """

    def __init__(self, tables: Optional[jax.Array], spec: QuantSpec,
                 scale: jax.Array, group: int,
                 shared: Optional[SharedGroupedTables] = None,
                 mesh=None, mesh_axis: str = "model"):
        if tables is None and shared is None:
            raise ValueError("PCILTLinear needs dense tables, a shared pool, "
                             "or both")
        self.tables = tables
        self.spec = spec
        self.scale = scale
        self.group = group
        self.shared = shared
        self.mesh = mesh
        self.mesh_axis = mesh_axis
        # conversion-time integrity record (pre-placement bytes; device_put
        # moves, never rewrites) — verified on demand by verify_integrity
        self.integrity: Dict[str, int] = {}
        if tables is not None:
            self.integrity["tables"] = table_checksum(tables)
        if shared is not None:
            self.integrity["pool"], self.integrity["seg_idx"] = \
                table_checksums(shared.pool, shared.seg_idx)
        self.shard_pools: Optional[ShardedSharedPool] = None
        if mesh is not None and self.shard_count > 1:
            if shared is not None:
                self.shard_pools = shard_shared_grouped_tables(
                    shared, self.shard_count)
                self._place_shard_pools()
            if tables is not None:
                # Park each [G/D, V, O] shard on its device now — the whole
                # point is that no device ever holds the global tables.
                from repro.nn.module import pcilt_table_sharding

                self.tables = jax.device_put(
                    tables, pcilt_table_sharding(mesh, tables.shape[0],
                                                 mesh_axis=mesh_axis))

    def _place_shard_pools(self) -> None:
        self.shard_pools = _place_sharded_pool(self.shard_pools, self.mesh,
                                               self.mesh_axis)

    @property
    def n_segments(self) -> int:
        if self.tables is not None:
            return self.tables.shape[0]
        return self.shared.n_segments

    @property
    def shard_count(self) -> int:
        """Effective G-shards on the layer's mesh (1 = replicated fallback)."""
        return mesh_shard_count(self.mesh, self.mesh_axis, self.n_segments)

    def table_bytes(self) -> int:
        """Bytes of the representation this layer would deploy (the shared
        pool when present — the paper's ext.-3 memory argument)."""
        if self.shared is not None:
            return self.shared.pool_bytes()
        return self.tables.size * self.tables.dtype.itemsize

    def per_device_table_bytes(self) -> int:
        """Table bytes each device holds under the layer's mesh.

        Dense tables shard exactly linearly (``G/D`` segments per device);
        shared layers stage the padded local pool.  Replicated layers (no
        mesh / fallback) hold everything everywhere.
        """
        if self.shard_pools is not None:
            return self.shard_pools.local_pool_bytes()
        return -(-self.table_bytes() // self.shard_count)

    def verify_integrity(self) -> Dict[str, bool]:
        """Recompute each held table's checksum against the conversion-time
        record; ``False`` marks a corrupted representation."""
        cur = {}
        if self.tables is not None:
            cur["tables"] = table_checksum(self.tables)
        if self.shared is not None:
            cur["pool"], cur["seg_idx"] = table_checksums(
                self.shared.pool, self.shared.seg_idx)
        return {k: cur[k] == v for k, v in self.integrity.items()}

    def _pad_x(self, x: jax.Array) -> jax.Array:
        n = self.n_segments * self.group
        pad = n - x.shape[-1]
        if pad:
            x = jnp.concatenate([x, jnp.zeros((*x.shape[:-1], pad), x.dtype)], -1)
        return x

    def _tables_for(self, path: str):
        if path == "shared" or (self.tables is None and path == "gather"):
            if self.shared is None:
                raise ValueError(
                    "no shared pool on this layer; convert with shared=True")
            return self.shard_pools if self.shard_pools is not None else self.shared
        if self.tables is None:
            raise ValueError(
                f"shared-only PCILTLinear executes path='shared' or 'gather', "
                f"not {path!r}")
        return self.tables

    def __call__(self, x: jax.Array, path: str = "gather") -> jax.Array:
        return pcilt_linear(self._pad_x(x), self._tables_for(path), self.spec,
                            self.scale, self.group, path=path,
                            mesh=self.mesh, mesh_axis=self.mesh_axis)

    def tune(self, x: jax.Array) -> jax.Array:
        """Eagerly autotune the fused kernel for this decode shape and record
        the winner in the persistent lookup table; returns the output.
        Shared-only layers tune the shared-pool kernel.

        Under a mesh, tuning runs on the **local shard shape** — one shard's
        ``[G/D, V, O]`` tables (or local pool) against the matching slice of
        the reduction dim — because that is the problem each device's kernel
        dispatches, and the shape key the sharded ``shard_map`` execution
        looks up at trace time.  Caches tuned at different device counts
        therefore occupy different keys and never collide.
        """
        from repro.kernels import ops  # local import: kernels are optional

        x = self._pad_x(x)
        flat = x.reshape(-1, x.shape[-1])
        D = self.shard_count
        if D > 1:
            Gl = self.n_segments // D
            xl = flat[:, : Gl * self.group]
            if self.tables is None:
                sp = self.shard_pools
                ops.pcilt_shared_gemv(xl, sp.pools[0], sp.seg_idx[0],
                                      self.spec, self.scale, self.group,
                                      autotune=True)
                return self(x, path="shared")
            ops.pcilt_fused_gemv(xl, self.tables[:Gl], self.spec, self.scale,
                                 self.group, autotune=True)
            return self(x, path="fused")
        if self.tables is None:
            out = ops.pcilt_shared_gemv(
                flat, self.shared.pool, self.shared.seg_idx, self.spec,
                self.scale, self.group, autotune=True)
        else:
            out = ops.pcilt_fused_gemv(flat, self.tables, self.spec,
                                       self.scale, self.group, autotune=True)
        return out.reshape(*x.shape[:-1], out.shape[-1])


def convert_kernel(kernel: jax.Array, act_spec: QuantSpec, act_scale,
                   group: int, weight_bits: Optional[int] = None,
                   shared: bool = False, mesh=None,
                   mesh_axis: str = "model") -> PCILTLinear:
    """Offline build for one [d_in, d_out] kernel.

    weight_bits: optionally quantize weights first (lowers table value
    diversity, the precondition for shared-PCILT dedup, ext. 3).
    shared: build the extension-3 segment-deduped pool *instead of* the dense
    tables — the layer then executes ``path="shared"`` (fused kernel) and
    ``path="gather"`` (pointer-gather reference), and its table memory scales
    with the weights' actual segment cardinality.  Usually combined with
    ``weight_bits`` (or otherwise weight-clustered kernels): dedup only bites
    when whole ``[group, d_out]`` segments repeat.
    mesh: build a tensor-parallel layer — tables are sharded on the segment
    axis over ``mesh_axis`` at conversion time (shared pools become per-shard
    local pools) and every call executes under ``shard_map`` with a psum of
    the partial sums.  Conversion is the offline step, so the sharding is
    too."""
    k = kernel.astype(jnp.float32)
    if kernel.ndim > 2:
        k = k.reshape(kernel.shape[0], -1)
    if weight_bits:
        wspec = QuantSpec(bits=weight_bits, symmetric=True)
        wscale = calibrate(k, wspec)
        k = dequantize(quantize(k, wspec, wscale), wspec, wscale)
    n, out = k.shape
    pad = (-n) % group
    if pad:
        k = jnp.concatenate([k, jnp.zeros((pad, out), k.dtype)], 0)
    if shared:
        pool = build_shared_grouped_tables(k, act_spec, act_scale, group)
        return PCILTLinear(None, act_spec, act_scale, group, shared=pool,
                           mesh=mesh, mesh_axis=mesh_axis)
    tables = build_grouped_tables(k, act_spec, act_scale, group)
    return PCILTLinear(tables, act_spec, act_scale, group, mesh=mesh,
                       mesh_axis=mesh_axis)


class PCILTConv2d:
    """A converted convolution: pre-built grouped tables + a per-path jitted
    executor cache.

    Eager (non-jit) serving used to pay the whole host-side pre-processing on
    *every* call — ``conv_same_pads`` arithmetic, the ``[kh*kw*Cin, Cout]``
    filter flatten/pad, and (worst) a full table rebuild when no tables were
    passed.  Conversion hoists all of it to the offline build (the paper's
    once-per-lifetime step), and ``__call__`` dispatches through one jitted
    closure per path — so repeated decode steps re-enter compiled code
    instead of re-tracing the quantize/pack/fetch pipeline each time.

    With ``mesh=``, calls execute the tensor-parallel conv route: the
    fused/shared kernels keep their in-VMEM im2col per shard via the
    kernels' ``seg_offset`` parameter (``core.lut_layers``), dense table
    shards are placed at conversion like :class:`PCILTLinear`.
    """

    def __init__(self, filters: jax.Array, spec: QuantSpec, scale, group: int,
                 stride: int = 1, padding: str = "SAME",
                 tables=None, shared: Optional[SharedGroupedTables] = None,
                 mesh=None, mesh_axis: str = "model"):
        if tables is None and shared is None:
            raise ValueError("PCILTConv2d needs dense tables, a shared pool, "
                             "or both")
        self.filters = filters
        self.spec = spec
        self.scale = scale
        self.group = group
        self.stride = stride
        self.padding = padding
        self.tables = tables
        self.shared = shared
        self.mesh = mesh
        self.mesh_axis = mesh_axis
        self.shard_pools: Optional[ShardedSharedPool] = None
        if mesh is not None and self.shard_count > 1:
            # Shard and place at conversion (the offline step), exactly like
            # PCILTLinear: no device ever holds the global tables/pool, and
            # the np.unique pool-shard build never re-runs inside a trace.
            if shared is not None:
                self.shard_pools = _place_sharded_pool(
                    shard_shared_grouped_tables(shared, self.shard_count),
                    mesh, mesh_axis)
            if tables is not None:
                from repro.nn.module import pcilt_table_sharding

                self.tables = jax.device_put(
                    tables, pcilt_table_sharding(mesh, tables.shape[0],
                                                 mesh_axis=mesh_axis))
        self._exec: Dict[str, object] = {}

    @property
    def n_segments(self) -> int:
        if self.tables is not None:
            return self.tables.shape[0]
        return self.shared.n_segments

    @property
    def shard_count(self) -> int:
        """Effective G-shards on the layer's mesh (1 = replicated fallback)."""
        return mesh_shard_count(self.mesh, self.mesh_axis, self.n_segments)

    def _tables_for(self, path: str):
        if path == "shared" or (self.tables is None and path == "gather"):
            if self.shared is None:
                raise ValueError(
                    "no shared pool on this layer; convert with shared=True")
            return self.shard_pools if self.shard_pools is not None else self.shared
        if self.tables is None:
            raise ValueError(
                f"shared-only PCILTConv2d executes path='shared' or "
                f"'gather', not {path!r}")
        return self.tables

    def table_bytes(self) -> int:
        if self.shared is not None:
            return self.shared.pool_bytes()
        return self.tables.size * self.tables.dtype.itemsize

    def per_device_table_bytes(self) -> int:
        """Table bytes each device holds under the layer's mesh (the padded
        local pool for shared layers; linear ``G/D`` scaling for dense)."""
        if self.shard_pools is not None:
            return self.shard_pools.local_pool_bytes()
        return -(-self.table_bytes() // self.shard_count)

    def __call__(self, x: jax.Array, path: str = "fused") -> jax.Array:
        fn = self._exec.get(path)
        if fn is None:
            tables = self._tables_for(path)

            def run(xc):
                return pcilt_conv2d(
                    xc, self.filters, self.spec, self.scale, self.group,
                    stride=self.stride, padding=self.padding, tables=tables,
                    path=path, mesh=self.mesh, mesh_axis=self.mesh_axis)

            fn = self._exec[path] = jax.jit(run)
        return fn(x)

    def tune(self, x: jax.Array) -> jax.Array:
        """Eagerly autotune the conv kernel for this input shape and record
        the winner; shared-only layers tune the shared-pool kernel.  The
        jitted dispatch then hits the recorded entry at trace time.

        Under a mesh, tuning runs on the **local shard shape** — one shard's
        ``[G/D, V, O]`` tables (or local pool) with a concrete
        ``seg_offset`` — because that is the problem each device's kernel
        dispatches and the shape key the sharded ``shard_map`` trace looks
        up (same contract as :meth:`PCILTLinear.tune`)."""
        from repro.kernels import ops  # local import: kernels are optional

        kh, kw, _, _ = self.filters.shape
        conv_kw = dict(stride=self.stride, padding=self.padding,
                       autotune=True)
        D = self.shard_count
        if D > 1:
            G = self.n_segments
            n_total = G * self.group
            if self.tables is None:
                sp = self.shard_pools
                ops.pcilt_shared_conv2d(
                    x, sp.pools[0], sp.seg_idx[0], self.spec, self.scale,
                    self.group, kh, kw, seg_offset=0, n_total=n_total,
                    **conv_kw)
                return self(x, path="shared")
            ops.pcilt_fused_conv2d(
                x, self.tables[: G // D], self.spec, self.scale, self.group,
                kh, kw, seg_offset=0, n_total=n_total, **conv_kw)
            return self(x, path="fused")
        if self.tables is None:
            ops.pcilt_shared_conv2d(
                x, self.shared.pool, self.shared.seg_idx, self.spec,
                self.scale, self.group, kh, kw, **conv_kw)
            return self(x, path="shared")
        ops.pcilt_fused_conv2d(
            x, self.tables, self.spec, self.scale, self.group, kh, kw,
            **conv_kw)
        return self(x, path="fused")


def convert_conv_kernel(filters: jax.Array, act_spec: QuantSpec, act_scale,
                        group: int, stride: int = 1, padding: str = "SAME",
                        weight_bits: Optional[int] = None,
                        shared: bool = False, mesh=None,
                        mesh_axis: str = "model") -> PCILTConv2d:
    """Offline build for one ``[kh, kw, Cin, Cout]`` conv filter — the conv
    sibling of :func:`convert_kernel`.  Flattens/pads the receptive field to
    the segment grid once, builds dense grouped tables (or the ext.-3
    segment-deduped pool with ``shared=True``), and returns the serving
    layer with every per-call host cost hoisted out."""
    kh, kw, cin, cout = filters.shape
    f = filters.astype(jnp.float32)
    if weight_bits:
        wspec = QuantSpec(bits=weight_bits, symmetric=True)
        wscale = calibrate(f, wspec)
        f = dequantize(quantize(f, wspec, wscale), wspec, wscale)
    n = kh * kw * cin
    wflat = f.reshape(n, cout)
    pad = (-n) % group
    if pad:
        wflat = jnp.concatenate([wflat, jnp.zeros((pad, cout), wflat.dtype)], 0)
    if shared:
        pool = build_shared_grouped_tables(wflat, act_spec, act_scale, group)
        return PCILTConv2d(f, act_spec, act_scale, group, stride=stride,
                           padding=padding, shared=pool, mesh=mesh,
                           mesh_axis=mesh_axis)
    tables = build_grouped_tables(wflat, act_spec, act_scale, group)
    return PCILTConv2d(f, act_spec, act_scale, group, stride=stride,
                       padding=padding, tables=tables, mesh=mesh,
                       mesh_axis=mesh_axis)


class PCILTDwConv1d:
    """A converted depthwise-conv1d frontend (Mamba/Zamba conv, k=4): the
    ``[C, V]`` per-channel tables are built once at conversion and every call
    executes one fetch per output element.

    ``path="fused"`` runs quantize + causal tap-stack + pack + fetch in one
    Pallas call (``repro.kernels.pcilt_fused_dwconv1d``) — the decode
    frontend's offsets never exist in HBM; the host-packed paths remain for
    reference/parity.  :meth:`tune` records the ``(Tb, Cb)`` tiling under
    the ``fused_dwconv1d`` autotune key for this signal shape.
    """

    def __init__(self, filters: jax.Array, spec: QuantSpec, scale,
                 tables: Optional[jax.Array] = None):
        self.filters = filters
        self.spec = spec
        self.scale = scale
        self.k = int(filters.shape[0])
        self.tables = tables if tables is not None else build_dwconv_tables(
            filters, spec, scale)
        self._exec: Dict[tuple, object] = {}

    def table_bytes(self) -> int:
        return self.tables.size * self.tables.dtype.itemsize

    def __call__(self, x: jax.Array, path: str = "fused",
                 padding: str = "CAUSAL") -> jax.Array:
        fn = self._exec.get((path, padding))
        if fn is None:
            def run(xc):
                return pcilt_depthwise_conv1d(
                    xc, self.filters, self.spec, self.scale,
                    tables=self.tables, path=path, padding=padding)

            fn = self._exec[(path, padding)] = jax.jit(run)
        return fn(x)

    def tune(self, x: jax.Array, padding: str = "CAUSAL") -> jax.Array:
        from repro.kernels import ops  # local import: kernels are optional

        out = ops.pcilt_fused_dwconv1d(x, self.tables, self.spec, self.scale,
                                       self.k, padding=padding, autotune=True)
        return out


def convert_dwconv(filters: jax.Array, act_spec: QuantSpec,
                   act_scale) -> PCILTDwConv1d:
    """Offline build for one ``[k, C]`` depthwise-conv1d filter: per-channel
    ``[C, 2**(bits*k)]`` tables, built once (the per-call rebuild the eager
    path used to pay is exactly what this hoists)."""
    return PCILTDwConv1d(filters, act_spec, act_scale)


class PCILTMambaDecode:
    """A fully-converted Mamba decode path: the calibrated PCILT bundle
    (conv ``[L, C, V]`` tables + layer-stacked ``[L, G, V, O]`` projection
    tables) plus the **hoisted jitted step executor** — eager serving loops
    call one compiled function per token instead of re-tracing
    ``decode_step`` (and re-closing over the table stack) every step.

    Built by :func:`convert_mamba_decode`; ``step``/``__call__`` mirror
    ``MambaLM.decode_step(params, cache, tokens)``.  :meth:`tune` eagerly
    autotunes the stacked projection kernels for a decode batch shape and
    records the winners under ``fused_gemv_stacked`` keys (local-shard
    shapes under a mesh), so the jitted dispatch hits the lookup table at
    trace time.

    Integrity: the bundle carries a conversion-time checksum record per
    table (per layer for the stacked arrays; ``core.pcilt``'s device
    checksum: one-word changes and bursts of <= 32 bits always caught,
    other changes missed with probability about 2**-32, the device bytes
    themselves read); it is verified at load
    (``verify=True``) and on demand (:meth:`verify_layer` /
    :meth:`verify_head` / :meth:`verify_integrity` — what the serving
    :class:`HealthMonitor` amortizes one layer per tick).  The step executor
    takes per-layer/head health masks as runtime *arguments* (defaulting to
    all-healthy), so demoting a layer to its dense oracle never retraces.
    """

    def __init__(self, model, pcilt: Dict, ctx=None, verify: bool = True):
        from repro.nn.layers import Ctx

        self.model = model
        self.pcilt = pcilt
        self.ctx = ctx if ctx is not None else Ctx()
        if "integrity" not in pcilt:
            pcilt["integrity"] = pcilt_integrity(pcilt)
        if verify:
            bad = self.verify_integrity()
            if bad:
                raise RuntimeError(
                    f"PCILT bundle failed integrity verification at load "
                    f"(corrupted tables): {bad}")
        self._hoist()

    def _hoist(self) -> None:
        # One jitted executor **per (decode batch, stats) pair**: the batch
        # dimension R is a first-class tuned axis of the stacked kernels
        # (``fused_gemv_stacked`` keys carry R), so an engine serving R=8
        # slots and a sibling serving R=32 dispatch distinct compiled steps
        # instead of sharing one retraced-on-shape-change function.  The
        # stats flag is a static trace property (counter outputs change the
        # step's result pytree), so monitored and unmonitored steps likewise
        # hold separate compiled executors.
        #
        # The bundle's arrays are executor *arguments*, never closure
        # constants: a closed-over array is embedded in the compiled
        # program (GBs of tables at published widths — past what a
        # serialized module can hold, and a second device copy).  The
        # bundle's structure and its non-array leaves (quantizer specs,
        # groups, mesh) are the static part, captured here.
        self._execs: Dict[Tuple[int, bool], object] = {}
        leaves, self._treedef = jax.tree.flatten(self._traced_bundle())
        self._is_array = tuple(isinstance(l, (jax.Array, np.ndarray))
                               for l in leaves)
        self._statics = [l for l, a in zip(leaves, self._is_array) if not a]

    def _traced_bundle(self) -> Dict:
        # the integrity record is host-side bookkeeping, not step input
        return {k: v for k, v in self.pcilt.items() if k != "integrity"}

    def bundle_arrays(self) -> List[jax.Array]:
        """The bundle's current array leaves, in executor-argument order —
        read at every step, so swapped tables are what the step uses."""
        leaves = jax.tree.leaves(self._traced_bundle())
        return [l for l, a in zip(leaves, self._is_array) if a]

    def _rebuild(self, arrays) -> Dict:
        arrs, statics = iter(arrays), iter(self._statics)
        return jax.tree.unflatten(
            self._treedef,
            [next(arrs) if a else next(statics) for a in self._is_array])

    def executor(self, rows: int, stats: bool = False):
        """The hoisted jitted step for a decode batch of ``rows`` slots
        (built on first use, then cached — serving loops at a fixed slot
        count pay tracing exactly once): ``f(params, cache, tokens,
        layer_ok, head_ok, arrays)`` with ``arrays`` from
        :meth:`bundle_arrays`.  ``stats=True`` builds the drift-monitored
        variant: the step additionally returns the per-layer saturation
        counters (``decode_step(with_stats=True)``)."""
        key = (rows, stats)
        f = self._execs.get(key)
        if f is None:
            # a named function, so the compiled module has a stable name
            # (``jit_pcilt_decode_step``) in device traces
            def pcilt_decode_step(p, c, t, ok, hok, arrays):
                return self.model.decode_step(
                    p, c, t, self.ctx, pcilt=self._rebuild(arrays),
                    layer_ok=ok, head_ok=hok, with_stats=stats)

            f = jax.jit(pcilt_decode_step)
            self._execs[key] = f
        return f

    def rehoist(self, verify: bool = False) -> None:
        """Rebuild the jitted executors after the bundle was modified.
        Swapped table *arrays* need no rehoist — they are step arguments —
        but a change to the bundle's structure or static entries does; this
        drops every cached executor, each rebuilt lazily on its next step.

        By default this does NOT re-verify integrity: detecting bad bytes at
        serving time is the health monitor's job, and the chaos suite
        exercises exactly that path.  ``verify=True`` opts in — the
        recalibration hot-swap path uses it so a rebuild whose re-recorded
        checksums don't match the freshly-swapped bytes fails loudly at the
        swap, not silently at some later amortized check."""
        if verify:
            bad = self.verify_integrity()
            if bad:
                raise RuntimeError(
                    f"PCILT bundle failed integrity verification at rehoist "
                    f"(corrupted tables): {bad}")
        self._hoist()

    def step(self, params, cache, tokens, layer_ok=None, head_ok=None,
             with_stats: bool = False):
        """One converted decode step: ``(logits, new_cache)`` — or, with
        ``with_stats=True``, ``(logits, new_cache, sat)`` where ``sat`` is
        the per-layer saturation-counter pytree of
        ``MambaLM.decode_step(with_stats=True)``.

        ``layer_ok`` (``[L]`` bool) / ``head_ok`` (bool) demote unhealthy
        layers' fetches (and the PCILT logits head) to their exact dense
        fake-quant oracles; both default to all-healthy."""
        if layer_ok is None:
            layer_ok = jnp.ones((self.model.cfg.n_layers,), bool)
        if head_ok is None:
            head_ok = jnp.asarray(True)
        fn = self.executor(int(tokens.shape[0]), stats=with_stats)
        return fn(params, cache, tokens, jnp.asarray(layer_ok, bool),
                  jnp.asarray(head_ok, bool), self.bundle_arrays())

    __call__ = step

    # -- integrity verification ----------------------------------------------

    def verify_layer(self, layer: int) -> List[Tuple]:
        """Checksum one layer's conv + projection table slices against the
        conversion-time record, in one device call that reads the slices
        in place; returns the breached ``(name, layer)`` sites (empty =
        clean)."""
        integ = self.pcilt["integrity"]
        sites = [("conv", self.pcilt["tables"], 0, integ["conv"])]
        proj = self.pcilt.get("proj")
        if proj is not None:
            # paired stacks are seg-major: the layer axis is axis 1
            axis = 1 if proj.get("paired") else 0
            sites += [(name, t, axis, integ["proj"][name])
                      for name, t in proj["tables"].items()]
        got = slice_checksums(layer, [t for _, t, _, _ in sites],
                              [a for _, _, a, _ in sites])
        return [(name, int(layer))
                for (name, _, _, rec), g in zip(sites, got)
                if g != rec[layer]]

    def layer_check_bytes(self) -> int:
        """Bytes :meth:`verify_layer` hashes: one layer's slice of the conv
        table and of each projection table."""
        L = self.pcilt["tables"].shape[0]
        stacks = [self.pcilt["tables"]]
        proj = self.pcilt.get("proj")
        if proj is not None:
            stacks += list(proj["tables"].values())
        return sum(int(t.nbytes) // L for t in stacks)

    def head_check_bytes(self) -> int:
        """Bytes :meth:`verify_head` hashes (0 = no head)."""
        head = self.pcilt.get("head")
        if head is None:
            return 0
        return int(head["pool"].nbytes) + int(head["seg_idx"].nbytes)

    def verify_head(self) -> List[Tuple]:
        """Checksum the shared-pool logits head (pool values + ``seg_idx``
        pointers); returns breached sites (empty = clean / no head)."""
        head = self.pcilt.get("head")
        if head is None:
            return []
        integ = self.pcilt["integrity"]["head"]
        names = ("pool", "seg_idx")
        got = table_checksums(*(head[k] for k in names))
        return [("head." + k,) for k, g in zip(names, got) if g != integ[k]]

    def verify_integrity(self) -> List[Tuple]:
        """Full verification: every layer of every stacked table plus the
        head; returns all breached sites (what the monitor amortizes)."""
        L = self.pcilt["tables"].shape[0]
        bad: List[Tuple] = []
        for l in range(L):
            bad.extend(self.verify_layer(l))
        bad.extend(self.verify_head())
        return bad

    def table_bytes(self) -> int:
        """Total bytes of every table the converted decode deploys."""
        t = self.pcilt["tables"]
        total = t.size * t.dtype.itemsize
        proj = self.pcilt.get("proj")
        if proj is not None:
            total += sum(a.size * a.dtype.itemsize
                         for a in proj["tables"].values())
        return total

    def tune(self, batch=1) -> None:
        """Eagerly autotune each projection's stacked kernel at this decode
        batch size (layer 0 is representative: the per-layer staged slice is
        what the kernel tiles, and the shape key is layer-independent), plus
        the conv frontend's fused dwconv key on the assembled ``[B, k, C]``
        decode window.  Paired bundles tune the paired stacked kernel on the
        seg-major ``[G/2, L, V^2, O]`` stack.  Under a mesh, tuning runs on
        the local shard — the problem each device's kernel dispatches.

        ``batch`` may be an int or an iterable of ints — the stacked keys
        carry the decode batch ``R``, so an engine that serves several slot
        counts (8-64) tunes each R's row-tile sweep once up front:
        ``decode.tune(batch=(8, 32, 64))``.

        Each kernel is tuned in both the uncounted and the counter-carrying
        (``with_stats=True``) variant: monitored serving is the engine
        default, and the ``*_sat`` key families never share entries with
        the base ones, so skipping them would leave the sentinel's hot
        path on heuristic tiles."""
        from repro.core.lut_layers import mesh_shard_count
        from repro.kernels import ops  # local import: kernels are optional

        batches = (batch,) if isinstance(batch, int) else tuple(batch)
        conv_t = self.pcilt["tables"]  # [L, C, V]
        k = self.model.cfg.ssm.conv_kernel
        for b in batches:
            win = jnp.zeros((b, k, conv_t.shape[1]), jnp.float32)
            for stats in (False, True):
                ops.pcilt_fused_dwconv1d(win, conv_t[0], self.pcilt["spec"],
                                         self.pcilt["scale"], k,
                                         padding="VALID", autotune=True,
                                         with_stats=stats)
        proj = self.pcilt.get("proj")
        if proj is None or proj.get("path") != "fused":
            return
        group = proj["group"]
        paired = bool(proj.get("paired"))
        for name, t in proj["tables"].items():
            G = t.shape[0] if paired else t.shape[1]
            D = mesh_shard_count(proj.get("mesh"),
                                 proj.get("mesh_axis", "model"), G)
            Gl = G // D
            for b in batches:
                for stats in (False, True):
                    if paired:
                        x = jnp.zeros((b, Gl * 2 * group), jnp.float32)
                        ops.pcilt_fused_gemv_paired_stacked(
                            x, t[:Gl], 0, proj["spec"],
                            proj["scales"][name][0], group, autotune=True,
                            with_stats=stats)
                    else:
                        x = jnp.zeros((b, Gl * group), jnp.float32)
                        ops.pcilt_fused_gemv_stacked(
                            x, t[:, :Gl], 0, proj["spec"],
                            proj["scales"][name][0], group, autotune=True,
                            with_stats=stats)


class HealthMonitor:
    """Amortized health checking + graceful degradation for a converted
    Mamba decode path.

    The paper's exactness guarantee — a PCILT fetch is *bit-exact* against
    the dense matmul on the quantized activation grid — makes health
    checking uniquely cheap: any deviation at all is corruption, not noise.
    The monitor holds per-layer (and head) boolean health masks and, once
    per tick, spot-checks **one** still-healthy layer (round-robin), so the
    steady-state overhead is one layer's checksum per tick regardless of
    depth (and the head's every ``n_layers``-th tick):

    * **checksum check** — :meth:`PCILTMambaDecode.verify_layer` checksums
      the layer's conv + projection table slices on the device, where the
      kernels read them, against the conversion-time record: one-word
      changes and bursts of <= 32 bits always caught (zero false negatives
      on single-entry flips), other changes missed with probability about
      2**-32;
    * **dense-oracle spot-check** (every ``oracle_every``-th clean check) —
      a fixed probe activation through the layer's table fetch vs the
      fake-quant dense matmul, catching corruption of anything the
      checksum record does not cover;
    * **output check** — :meth:`check_outputs` flags NaN/Inf in the decode
      logits (activation poisoning / numerical blowup), which the engine
      answers with checkpoint rollback rather than demotion.

    On breach the failing layer alone is demoted (its mask bit cleared), so
    subsequent steps run that layer's projections + conv on the exact dense
    oracle while every healthy layer keeps fetching — serving continues,
    degraded and logged, never wrong.  ``last_verified`` records the newest
    tick each layer passed at, bounding how far a rollback must rewind.

    Calibration-drift sentinel: the checksum/oracle checks above cover
    *table* corruption, but PCILT is only correct while runtime
    activations stay inside the absmax range captured at calibration —
    ``quantize`` silently clips anything outside, yielding wrong-but-finite
    outputs no checksum can see.  :meth:`observe_saturation` closes that
    hole from the in-kernel saturation counters of the monitored decode step
    (``step(with_stats=True)``): per (layer, quantizer-grid) saturation
    *rates* feed an EWMA, classified against two thresholds —
    ``sat_hard`` (instant ``"saturated"``: this step's outputs are already
    suspect) and ``sat_drift`` on the EWMA (``"drifting"``: sustained mild
    clipping).  Either breach demotes the drifting layer through the same
    typed ``layer_ok`` path as a checksum breach (event ``kind="drift"``)
    and queues it on :attr:`drift_pending`; the serving loop then calls
    :meth:`recalibrate_layer` between ticks — tables are cheap to rebuild
    (the paper's point), so the layer's grid is re-scaled to the observed
    peak ``|x|/scale`` ratio (× ``headroom``), its stacked tables are
    hot-swapped with checksums re-recorded, and the layer repromotes.
    ``max_recalibrations`` bounds thrash: a layer that keeps drifting past
    its budget stays demoted on the exact dense oracle (sticky).  The first
    recalibration sets :attr:`tainted` — outputs now come from a different
    (better-calibrated) grid than conversion time, so token streams are no
    longer comparable to a pre-drift reference.
    """

    #: the distinct quantizer grids a monitored decode step reports, in
    #: the order ``mamba_decode`` emits them
    SAT_GRIDS = ("in", "conv", "out")

    def __init__(self, decode: PCILTMambaDecode, params, *,
                 oracle_every: int = 4, oracle_batch: int = 1,
                 oracle_tol: float = 5e-3, seed: int = 0,
                 sat_hard: float = 0.25, sat_drift: float = 0.02,
                 sat_alpha: float = 0.2, headroom: float = 1.05,
                 max_recalibrations: int = 2):
        cfg = decode.model.cfg
        self.decode = decode
        self.params = params
        self.oracle_every = oracle_every
        self.oracle_tol = oracle_tol
        self.n_layers = int(cfg.n_layers)
        self.layer_ok = np.ones(self.n_layers, bool)
        self.head_ok = True
        #: newest tick each layer passed verification at (-1 = never)
        self.last_verified = np.full(self.n_layers, -1, np.int64)
        self.head_last_verified = -1
        #: clean layer checks (what ``oracle_every`` counts)
        self.checks = 0
        #: ``on_tick``'s work: layer and head checksum checks, dense-oracle
        #: probes, and the table bytes those checks read on the device
        self.layer_checks = 0
        self.head_checks = 0
        self.oracle_probes = 0
        self.crc_bytes = 0
        self.events: List[Dict] = []
        rng = np.random.default_rng(seed)
        d_inner = cfg.ssm.expand * cfg.d_model
        conv_ch = d_inner + 2 * cfg.ssm.n_groups * cfg.ssm.d_state
        self._probe = (0.3 * rng.normal(
            size=(oracle_batch, cfg.d_model))).astype(np.float32)
        # wo consumes the post-norm gated inner stream, not the block input
        # — the rotating oracle probe needs both widths.
        self._probe_out = (0.3 * rng.normal(
            size=(oracle_batch, d_inner))).astype(np.float32)
        self._oracle_rr = 0
        # -- drift sentinel state ------------------------------------------
        self.sat_hard = float(sat_hard)
        self.sat_drift = float(sat_drift)
        self.sat_alpha = float(sat_alpha)
        self.headroom = float(headroom)
        self.max_recalibrations = int(max_recalibrations)
        #: saturable elements per decode row per grid — the denominator
        #: turning the kernels' raw counts into rates
        self._sat_elems = {"in": int(cfg.d_model),
                           "conv": int(cfg.ssm.conv_kernel * conv_ch),
                           "out": int(d_inner)}
        self.sat_last = {g: np.zeros(self.n_layers) for g in self.SAT_GRIDS}
        self.sat_ewma = {g: np.zeros(self.n_layers) for g in self.SAT_GRIDS}
        #: running peak |x|/scale per (grid, layer) since last recalibration
        #: — the observed absmax the rebuild re-scales to
        self.sat_peak = {g: np.zeros(self.n_layers) for g in self.SAT_GRIDS}
        #: (layer, grid) pairs demoted for drift, awaiting recalibration
        self.drift_pending: List[Tuple[int, str]] = []
        self.recalibrations = np.zeros(self.n_layers, np.int64)
        #: True once any recalibration swapped tables: outputs thereafter
        #: come from a different quantization grid than conversion time
        self.tainted = False

    # -- masks / state -------------------------------------------------------

    def ok_masks(self) -> Tuple[jax.Array, jax.Array]:
        """The ``(layer_ok, head_ok)`` arguments for the next decode step."""
        return jnp.asarray(self.layer_ok), jnp.asarray(self.head_ok)

    @property
    def degraded(self) -> bool:
        return (not bool(self.layer_ok.all())) or not self.head_ok

    def demote(self, kind: str, layer: Optional[int], tick: int,
               reason: str) -> Dict:
        """Clear one health bit; the next step's cond takes the dense-oracle
        branch for that layer (or the head) — no retrace, no restart."""
        if kind == "head":
            self.head_ok = False
        else:
            self.layer_ok[int(layer)] = False
        ev = {"kind": kind, "layer": None if layer is None else int(layer),
              "tick": int(tick), "reason": reason}
        self.events.append(ev)
        log.warning("health breach at tick %d: %s layer=%s (%s) — demoted "
                    "to dense oracle", tick, kind, layer, reason)
        return ev

    # -- checks --------------------------------------------------------------

    def check_outputs(self, logits) -> bool:
        """NaN/Inf gate on the step's logits (True = healthy)."""
        return bool(jnp.all(jnp.isfinite(logits)))

    def _oracle_check(self, layer: int, name: str = "wx") -> bool:
        """Probe one layer's ``name`` table fetch against the fake-quant
        dense matmul — exact on the grid, so any mismatch beyond float-sum
        reassociation noise is corruption.  ``on_tick`` rotates ``name``
        across every converted projection (``nn.ssm.PROJ_NAMES``) so a
        corrupt ``wo`` or ``wdt`` is probed directly, not only via the
        checksum."""
        proj = self.decode.pcilt.get("proj")
        if proj is None or name not in proj["tables"]:
            return True
        t = proj["tables"][name]  # [L, G, V, O] (paired: [G/2, L, V^2, O])
        spec, group = proj["spec"], proj["group"]
        paired = bool(proj.get("paired"))
        scale = proj["scales"][name][layer]
        x = self._probe_out if name == "wo" else self._probe
        n = t.shape[0] * 2 * group if paired else t.shape[1] * group
        pad = n - x.shape[-1]
        xx = np.concatenate(
            [x, np.zeros((x.shape[0], pad), x.dtype)], -1) if pad else x
        got = pcilt_linear(jnp.asarray(xx), t, spec, scale, group,
                           path="gather", stacked=int(layer), paired=paired)
        k = self.params["blocks"]["mixer"][name]["kernel"][layer]
        want = jnp.dot(fake_quant(jnp.asarray(x), spec, scale),
                       k.astype(jnp.float32),
                       precision=jax.lax.Precision.HIGHEST)
        return bool(np.allclose(np.asarray(got), np.asarray(want),
                                rtol=self.oracle_tol, atol=self.oracle_tol))

    def _next_probe_name(self) -> str:
        """Round-robin over the converted projections for the dense-oracle
        spot-check (falls back to ``wx`` when no projections converted)."""
        from repro.nn.ssm import PROJ_NAMES

        proj = self.decode.pcilt.get("proj")
        names = tuple(n for n in PROJ_NAMES
                      if proj is not None and n in proj["tables"]) or ("wx",)
        name = names[self._oracle_rr % len(names)]
        self._oracle_rr += 1
        return name

    def on_tick(self, tick: int, sat=None, rows: int = 1) -> List[Dict]:
        """Amortized health pass for one decode tick; returns the breach
        events raised (empty = all checked slices clean).

        ``sat`` (optional) is the saturation-counter pytree of a monitored
        step (``PCILTMambaDecode.step(with_stats=True)``'s third result) and
        ``rows`` its decode batch; when given, the drift sentinel runs
        (:meth:`observe_saturation`) *before* the amortized checksum pass,
        so an instant ``"saturated"`` classification demotes on the very
        tick whose outputs it indicts."""
        tick = int(tick)
        with span("monitor", tick=tick):
            breaches: List[Dict] = []
            if sat is not None:
                with span("monitor.saturation"):
                    breaches.extend(self.observe_saturation(tick, sat, rows))
            candidates = [l for l in range(self.n_layers) if self.layer_ok[l]]
            if candidates:
                l = candidates[tick % len(candidates)]
                nbytes = self.decode.layer_check_bytes()
                with span("monitor.crc_layer", layer=l, bytes=nbytes):
                    bad = self.decode.verify_layer(l)
                self.layer_checks += 1
                self.crc_bytes += nbytes
                if bad:
                    breaches.append(self.demote(
                        "layer", l, tick, f"checksum breach: {bad}"))
                else:
                    self.checks += 1
                    if self.oracle_every and \
                            self.checks % self.oracle_every == 0:
                        name = self._next_probe_name()
                        with span("monitor.oracle", layer=l, proj=name):
                            ok = self._oracle_check(l, name)
                        self.oracle_probes += 1
                        if not ok:
                            breaches.append(self.demote(
                                "layer", l, tick,
                                f"dense-oracle divergence ({name})"))
                if self.layer_ok[l]:
                    self.last_verified[l] = tick
            if self.head_ok and self.decode.pcilt.get("head") is not None and \
                    tick % max(self.n_layers, 1) == 0:
                nbytes = self.decode.head_check_bytes()
                with span("monitor.crc_head", bytes=nbytes):
                    bad = self.decode.verify_head()
                self.head_checks += 1
                self.crc_bytes += nbytes
                if bad:
                    breaches.append(self.demote(
                        "head", None, tick, f"checksum breach: {bad}"))
                else:
                    self.head_last_verified = tick
            return breaches

    def counters(self) -> Dict[str, int]:
        """Totals of the health passes run so far (``Engine`` stats)."""
        return {"layer_checks": self.layer_checks,
                "head_checks": self.head_checks,
                "oracle_probes": self.oracle_probes,
                "crc_bytes": self.crc_bytes}

    # -- calibration-drift sentinel ------------------------------------------

    def saturation_state(self, grid: str, layer: int) -> str:
        """Classify one (grid, layer) quantizer: ``"healthy"`` /
        ``"drifting"`` (EWMA past ``sat_drift``) / ``"saturated"`` (last
        observed rate past ``sat_hard``)."""
        if self.sat_last[grid][layer] >= self.sat_hard:
            return "saturated"
        if self.sat_ewma[grid][layer] >= self.sat_drift:
            return "drifting"
        return "healthy"

    def observe_saturation(self, tick: int, sat, rows: int) -> List[Dict]:
        """Feed one monitored step's saturation counters into the sentinel.

        ``sat`` is ``{"in"|"conv"|"out": {"count" [L], "ratio" [L]}}`` from
        ``decode_step(with_stats=True)``; counts normalize to per-element
        rates by ``rows ×`` the grid's element count.  A layer whose rate
        breaches ``sat_hard`` (instant) or whose EWMA breaches ``sat_drift``
        (sustained) is demoted — typed event ``kind="drift"`` carrying the
        grid, classification, and observed peak ``|x|/scale`` — and queued
        on :attr:`drift_pending` for :meth:`recalibrate_layer`.  Demoted
        layers keep contributing (the oracle branch computes the same stats
        host-side), so the recalibration re-scale always sees the freshest
        peak ratio."""
        tick = int(tick)
        breaches: List[Dict] = []
        # one batched device->host pull for the whole stats pytree (six
        # per-array np.asarray syncs add measurable per-tick latency).
        sat = jax.device_get(sat)
        for grid, st in sat.items():
            counts = np.asarray(st["count"], np.int64)
            ratios = np.asarray(st["ratio"], np.float64)
            rates = counts / float(max(int(rows), 1) * self._sat_elems[grid])
            a = self.sat_alpha
            self.sat_last[grid] = rates
            self.sat_ewma[grid] = (1.0 - a) * self.sat_ewma[grid] + a * rates
            self.sat_peak[grid] = np.maximum(self.sat_peak[grid], ratios)
            for l in range(self.n_layers):
                if not self.layer_ok[l]:
                    continue
                state = self.saturation_state(grid, l)
                if state == "healthy":
                    continue
                if state == "saturated":
                    reason = (f"saturation {grid} rate={rates[l]:.4f} >= "
                              f"sat_hard={self.sat_hard}")
                else:
                    reason = (f"saturation {grid} "
                              f"ewma={self.sat_ewma[grid][l]:.4f} >= "
                              f"sat_drift={self.sat_drift}")
                ev = self.demote("drift", l, tick, reason)
                ev.update(grid=grid, state=state, rate=float(rates[l]),
                          ewma=float(self.sat_ewma[grid][l]),
                          ratio=float(self.sat_peak[grid][l]))
                self.drift_pending.append((l, grid))
                breaches.append(ev)
        return breaches

    def recalibrate_layer(self, layer: int, grid: str, tick: int) -> Dict:
        """Online table rebuild for one drift-demoted layer, then repromote.

        The observed peak ``|x|/scale`` ratio pins the post-drift absmax
        (``ratio × old_scale``); ``headroom`` pads it so an activation just
        past the old edge doesn't immediately re-saturate.  The drifted
        grid's projections (``"in"``: the five block-input projections;
        ``"out"``: ``wo``) are rebuilt at the new scale with the *same*
        arithmetic as conversion, hot-swapped into the stacked arrays,
        their per-layer checksums re-recorded, and the executors re-hoisted
        with ``verify=True`` — so ``last_verified`` keeps meaning "checked
        against a record that matches the deployed bytes".  The ``"conv"``
        grid shares one global scale across all layers and stays demoted
        instead (sticky — rebuilding every layer's conv tables mid-serve is
        a full reconversion, not a hot-swap).  A layer past its
        ``max_recalibrations`` budget also stays demoted: the exact dense
        oracle is degraded-but-correct, and thrash means the workload, not
        the tables, moved."""
        l, tick = int(layer), int(tick)

        def _sticky(reason: str) -> Dict:
            ev = {"kind": "drift_sticky", "layer": l, "tick": tick,
                  "grid": grid, "reason": reason}
            self.events.append(ev)
            log.warning("drift at layer %d stays demoted: %s", l, reason)
            return ev

        proj = self.decode.pcilt.get("proj")
        if grid == "conv":
            return _sticky("conv grid shares one global scale across layers "
                           "— per-layer hot-swap impossible; demoted to the "
                           "dense oracle")
        if proj is None:
            return _sticky("no converted projections to rebuild")
        if self.recalibrations[l] >= self.max_recalibrations:
            return _sticky(
                f"recalibration budget exhausted "
                f"({int(self.recalibrations[l])}/{self.max_recalibrations})")
        spec, group = proj["spec"], proj["group"]
        paired = bool(proj.get("paired"))
        integ = self.decode.pcilt["integrity"]["proj"]
        names = ("wo",) if grid == "out" else tuple(
            n for n in proj["tables"] if n != "wo")
        new_amax = float(self.sat_peak[grid][l]) * self.headroom
        new_scales: Dict[str, float] = {}
        for name in names:
            old_scale = float(np.asarray(proj["scales"][name][l]))
            new_scale = scale_from_amax(
                jnp.asarray(new_amax * old_scale, jnp.float32), spec)
            wf = jnp.asarray(
                self.params["blocks"]["mixer"][name]["kernel"][l],
                jnp.float32)
            t = proj["tables"][name]
            if paired:
                # seg-major [G2, L, V2, O]: rebuild the one layer through
                # the same per-layer-vmapped builder as conversion
                from .pcilt import build_paired_stacked_tables

                t_new = build_paired_stacked_tables(
                    wf[None], spec, jnp.reshape(new_scale, (1,)),
                    group)[:, 0]
                t = t.at[:, l].set(t_new.astype(t.dtype))
                proj["tables"][name] = t
                integ[name][l] = slice_checksums(l, [t], [1])[0]
            else:
                pad_n = (-wf.shape[0]) % group
                if pad_n:  # group-alignment slots, exactly as conversion
                    wf = jnp.concatenate(
                        [wf, jnp.zeros((pad_n, wf.shape[1]), wf.dtype)], 0)
                t_new = build_grouped_tables(wf, spec, new_scale, group)
                t = t.at[l].set(t_new.astype(t.dtype))
                proj["tables"][name] = t
                integ[name][l] = slice_checksums(l, [t], [0])[0]
            proj["scales"][name] = proj["scales"][name].at[l].set(
                jnp.asarray(new_scale, jnp.float32))
            new_scales[name] = float(np.asarray(new_scale))
        # executors close over the swapped arrays — rebuild them, verifying
        # the re-recorded checksums against the deployed bytes (satellite:
        # rehoist(verify=True))
        self.decode.rehoist(verify=True)
        self.recalibrations[l] += 1
        self.tainted = True
        self.layer_ok[l] = True
        self.last_verified[l] = tick
        self.sat_ewma[grid][l] = 0.0
        self.sat_last[grid][l] = 0.0
        self.sat_peak[grid][l] = 0.0
        ev = {"kind": "recalibrate", "layer": l, "tick": tick, "grid": grid,
              "amax_ratio": new_amax, "scales": new_scales,
              "attempt": int(self.recalibrations[l])}
        self.events.append(ev)
        log.warning("recalibrated layer %d grid %r at tick %d: new scales "
                    "%s — repromoted", l, grid, tick, new_scales)
        return ev

    def recalibrate_pending(self, tick: int) -> List[Dict]:
        """Drain :attr:`drift_pending` (the between-ticks hook the serving
        loop calls): one :meth:`recalibrate_layer` per queued (layer, grid),
        deduplicated."""
        events: List[Dict] = []
        seen = set()
        pending, self.drift_pending = self.drift_pending, []
        for l, grid in pending:
            if (l, grid) in seen:
                continue
            seen.add((l, grid))
            events.append(self.recalibrate_layer(l, grid, tick))
        return events

    def saturation_summary(self) -> Dict:
        """Compact per-tick telemetry block: worst rate/EWMA per grid, total
        recalibrations, pending drift responses, taint flag."""
        return {
            "rate": {g: float(self.sat_last[g].max(initial=0.0))
                     for g in self.SAT_GRIDS},
            "ewma": {g: float(self.sat_ewma[g].max(initial=0.0))
                     for g in self.SAT_GRIDS},
            "peak_ratio": {g: float(self.sat_peak[g].max(initial=0.0))
                           for g in self.SAT_GRIDS},
            "recalibrations": int(self.recalibrations.sum()),
            "pending": len(self.drift_pending),
            "tainted": bool(self.tainted),
        }


def convert_mamba_decode(model, params, calib_tokens, ctx=None, *,
                         proj_path: str = "fused", projections=None,
                         mesh=None, mesh_axis: str = "model",
                         table_dtype=jnp.float32, paired: bool = False,
                         head: Optional[str] = None) -> PCILTMambaDecode:
    """Offline full-PCILT conversion of a ``MambaLM`` decode step.

    The once-per-lifetime build for the paper's end-to-end decode story:

    1. **calibrate** — one prefill pass over ``calib_tokens`` ``[B, S]``
       (``MambaLM.calibrate_pcilt``) captures per-layer absmax of every
       activation the converted step quantizes, turned into per-projection
       per-layer scales on the symmetric ``cfg.pcilt.act_bits`` grid;
    2. **build** — per-layer conv ``[C, V]`` tables stacked to ``[L, C, V]``
       and, when ``cfg.pcilt.apply_to_gemv``, one layer-stacked
       ``[L, G, V, O]`` grouped-table array per projection
       (``MambaLM.build_pcilt``), segment-sharded over ``mesh_axis`` when a
       mesh is given;
    3. **hoist** — the jitted decode executor is built once and reused
       every step (:class:`PCILTMambaDecode`).

    ``projections`` restricts the converted set (default: all six —
    ``nn.ssm.PROJ_NAMES``); ``proj_path`` selects the execution route
    (``"fused"`` is the deployment path; ``"kernel"`` is the host-packed
    baseline the benchmark measures against; ``"dense_fq"`` the parity
    oracle).  ``table_dtype=jnp.bfloat16`` halves table memory (the stacked
    kernel contracts and accumulates f32 either way).  ``paired=True``
    builds TL1-style paired multi-scalar tables instead — adjacent segment
    pairs merge into ``[G/2, L, V^2, O]`` seg-major stacks, halving fetches
    per output at ``V^2`` table width (``docs/paired_tables.md``); parity
    with the unpaired build is exact.  ``head="shared"``
    additionally converts the logits head to a shared-pool (ext.-3) PCILT
    calibrated on the ``ln_f`` output absmax.  The returned executor carries
    the bundle's conversion-time integrity record, verified at load.
    """
    from repro.nn.layers import Ctx

    cfg = model.cfg
    if cfg.pcilt is None:
        raise ValueError(
            "convert_mamba_decode requires model.cfg.pcilt (a configs.base."
            "PCILTConfig supplying act_bits/group for the table build); got "
            "None — set cfg = dataclasses.replace(cfg, "
            "pcilt=PCILTConfig(...)) before converting")
    ctx = ctx if ctx is not None else Ctx()
    spec = QuantSpec(bits=cfg.pcilt.act_bits, symmetric=True)
    amax = jax.jit(lambda p, b: model.calibrate_pcilt(p, b, ctx))(
        params, {"tokens": calib_tokens})

    def to_scale(a):
        return scale_from_amax(jnp.asarray(a, jnp.float32), spec)

    proj_scales = None
    if cfg.pcilt.apply_to_gemv:
        proj_scales = {"in": to_scale(amax["in"]), "out": to_scale(amax["out"])}
    if head is not None and head != "shared":
        raise ValueError(f"head= accepts None or 'shared', got {head!r}")
    pcilt = model.build_pcilt(
        params, to_scale(amax["conv_in"]), proj_scales=proj_scales,
        proj_path=proj_path, projections=projections, mesh=mesh,
        mesh_axis=mesh_axis, table_dtype=table_dtype, paired=paired,
        head_scale=to_scale(amax["head_in"]) if head == "shared" else None)
    return PCILTMambaDecode(model, pcilt, ctx)


def pcilt_apply(lin: PCILTLinear, x: jax.Array, path: str = "gather"):
    return lin(x, path=path)


def mlp_table_bytes(d_model: int, d_ff: int, act_bits: int, group: int,
                    value_bytes: int = 2) -> int:
    """Per-layer table memory for a gated MLP (3 kernels) — the feasibility
    number the paper's memory argument turns on.  Each kernel [n, out]
    becomes [n/group, 2**(bits*group), out] tables."""
    V = 1 << (act_bits * group)
    gate_up = 2 * (d_model // group) * V * d_ff * value_bytes
    down = (d_ff // group) * V * d_model * value_bytes
    return gate_up + down
