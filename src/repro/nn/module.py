"""Declarative parameters with logical-axis sharding.

Models in this framework declare their parameters as a pytree of
:class:`ParamSpec` — shape, dtype, initializer, and **logical axis names**
(``"embed"``, ``"heads"``, ``"mlp"``, ``"vocab"``, ``"expert"``, ...).  A
:class:`ShardingRules` table maps logical axes onto mesh axes (the MaxText /
t5x pattern).  From one spec tree we derive, without ever materializing
weights:

* ``shardings(specs, mesh, rules)``   — NamedShardings for pjit,
* ``shape_structs(specs, mesh, rules)`` — ShapeDtypeStructs for the dry-run
  (this is how a 400B-parameter model lowers on a CPU host: nothing is
  allocated),
* ``materialize(specs, key)``         — real weights for runnable examples.

Divisibility fallback: if a logical axis maps to a mesh axis whose size does
not divide the dimension (e.g. 2 KV heads over a 16-way model axis), that
dimension silently falls back to replication.  This keeps one rule table
valid across all 10 assigned architectures; the dry-run report surfaces the
fallbacks so they are a conscious cost, not a hidden one.
"""

from __future__ import annotations

import dataclasses
import math
import zlib
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = [
    "ParamSpec",
    "ShardingRules",
    "DEFAULT_RULES",
    "logical_to_partition_spec",
    "shardings",
    "shape_structs",
    "materialize",
    "flatten_with_path",
    "count_params",
    "spec_bytes",
    "PCILT_TABLE_AXES",
    "pcilt_table_pspec",
    "pcilt_table_sharding",
]


@dataclasses.dataclass(frozen=True)
class ParamSpec:
    """One parameter: shape + logical axes + init recipe."""

    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]
    dtype: Any = jnp.float32
    init: str = "fan_in"  # fan_in | normal | zeros | ones | embed
    scale: float = 1.0

    def __post_init__(self):
        if len(self.shape) != len(self.axes):
            raise ValueError(f"shape {self.shape} vs axes {self.axes}")


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    """logical axis -> mesh axis (or tuple of mesh axes)."""

    rules: Dict[str, Any]
    mesh_axis_sizes: Dict[str, int]

    @staticmethod
    def for_mesh(mesh: Mesh, rules: Optional[Dict[str, Any]] = None) -> "ShardingRules":
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        return ShardingRules(rules=dict(rules or DEFAULT_RULES), mesh_axis_sizes=sizes)

    def mesh_axes_for(self, logical: Optional[str], dim: int):
        """Resolve one logical axis, applying the divisibility fallback."""
        if logical is None:
            return None
        target = self.rules.get(logical)
        if target is None:
            return None
        axes = target if isinstance(target, tuple) else (target,)
        # keep only mesh axes that exist; check divisibility of the product
        axes = tuple(a for a in axes if a in self.mesh_axis_sizes)
        if not axes:
            return None
        total = math.prod(self.mesh_axis_sizes[a] for a in axes)
        if dim % total != 0:
            return None
        return axes if len(axes) > 1 else axes[0]


#: batch over (pod,)data; TP dims over model; FSDP over data on the embed dim.
DEFAULT_RULES: Dict[str, Any] = {
    "batch": ("pod", "data"),
    "seq_sp": "model",      # sequence parallelism (activations only)
    "cache_seq": ("pod", "data"),  # KV-cache time axis (engages when batch=1)
    "embed": ("data", "pod"),  # FSDP axes on weights' d_model dim (params
                               # shard over the pod axis too on 512 chips)
    "embed_tp": "model",    # rows of row-parallel matmuls (flattened heads*dim / mlp)
    "vocab": "model",
    "heads": "model",
    "kv_heads": "model",
    "mlp": "model",
    "expert": "model",
    "ssm_heads": "model",
    "layers": None,
    "stage": "stage",
    # PCILT [G, V, O] grouped tables (and ShardedSharedPool shard stacks):
    # the segment axis G shards over the tensor-parallel axis; each device's
    # fetch-and-sum partial is psum'd (core.lut_layers mesh execution).
    "table_seg": "model",
}


#: Logical axes of a grouped PCILT table ``[G, V, O]``: only the segment
#: axis shards — the offset axis V is addressed by every device's local
#: fetches and the out axis rides the adder tree / psum.
PCILT_TABLE_AXES: Tuple[Optional[str], ...] = ("table_seg", None, None)


def pcilt_table_pspec(G: int, ndim: int = 3,
                      rules: Optional[ShardingRules] = None,
                      mesh_axis: Optional[str] = None,
                      seg_axis: int = 0) -> P:
    """PartitionSpec for a PCILT table operand whose segment axis is
    position ``seg_axis``.

    The segment axis (``G`` for dense ``[G, V, O]`` tables, the shard stack
    for ``ShardedSharedPool.pools``/``.seg_idx``; ``seg_axis=1`` for the
    layer-stacked ``[L, G, V, O]`` decode tables, whose leading layer axis
    rides the decode scan and must replicate) shards over the
    ``"table_seg"`` rule with the usual divisibility fallback; every other
    axis replicates.  ``mesh_axis`` overrides the rule table (still applying
    the fallback) for callers that shard over a non-default axis.
    """
    if mesh_axis is not None and rules is not None:
        rules = ShardingRules(rules={"table_seg": mesh_axis},
                              mesh_axis_sizes=rules.mesh_axis_sizes)
    resolved = rules.mesh_axes_for("table_seg", G) if rules is not None else None
    parts = [None] * ndim
    parts[seg_axis] = resolved
    return P(*parts)


def pcilt_table_sharding(mesh: Mesh, G: int, ndim: int = 3,
                         rules: Optional[ShardingRules] = None,
                         mesh_axis: Optional[str] = None,
                         seg_axis: int = 0) -> NamedSharding:
    """NamedSharding placing a PCILT table operand on ``mesh`` (G sharded)."""
    rules = rules or ShardingRules.for_mesh(mesh)
    return NamedSharding(mesh, pcilt_table_pspec(G, ndim, rules, mesh_axis,
                                                 seg_axis))


def logical_to_partition_spec(
    spec_axes: Sequence[Optional[str]], shape: Sequence[int], rules: ShardingRules
) -> P:
    parts = []
    used = set()
    for ax, dim in zip(spec_axes, shape):
        resolved = rules.mesh_axes_for(ax, dim)
        # one mesh axis may shard only one dim; later dims fall back
        flat = (
            tuple(resolved)
            if isinstance(resolved, tuple)
            else (resolved,) if resolved else ()
        )
        if any(a in used for a in flat):
            resolved = None
        used.update(flat)
        parts.append(resolved)
    return P(*parts)


def _tree_map_specs(fn: Callable[[ParamSpec], Any], specs):
    return jax.tree.map(fn, specs, is_leaf=lambda x: isinstance(x, ParamSpec))


def shardings(specs, mesh: Mesh, rules: Optional[ShardingRules] = None):
    rules = rules or ShardingRules.for_mesh(mesh)
    return _tree_map_specs(
        lambda s: NamedSharding(
            mesh, logical_to_partition_spec(s.axes, s.shape, rules)
        ),
        specs,
    )


def shape_structs(specs, mesh: Optional[Mesh] = None, rules=None):
    """ShapeDtypeStructs (with shardings when a mesh is given) — dry-run food."""
    if mesh is None:
        return _tree_map_specs(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype), specs)
    shards = shardings(specs, mesh, rules)
    return jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        specs,
        shards,
        is_leaf=lambda x: isinstance(x, ParamSpec),
    )


def _init_one(spec: ParamSpec, key: jax.Array) -> jax.Array:
    if spec.init == "zeros":
        return jnp.zeros(spec.shape, spec.dtype)
    if spec.init == "ones":
        return jnp.ones(spec.shape, spec.dtype)
    if spec.init == "normal":
        return (jax.random.normal(key, spec.shape) * spec.scale).astype(spec.dtype)
    if spec.init == "embed":
        return (jax.random.normal(key, spec.shape) * spec.scale).astype(spec.dtype)
    if spec.init == "fan_in":
        fan_in = spec.shape[0] if len(spec.shape) == 1 else math.prod(spec.shape[:-1])
        std = spec.scale / math.sqrt(max(fan_in, 1))
        return (jax.random.normal(key, spec.shape) * std).astype(spec.dtype)
    raise ValueError(f"unknown init {spec.init}")


def flatten_with_path(tree, is_leaf=None):
    """``jax.tree.flatten_with_path``."""
    return jax.tree.flatten_with_path(tree, is_leaf=is_leaf)


def materialize(specs, key: jax.Array):
    """Concrete params; per-leaf keys derived from a CRC-32 of the leaf's
    path, so the same key gives the same values in every process (Python's
    ``hash`` of a string is salted per process)."""
    leaves, treedef = flatten_with_path(
        specs, is_leaf=lambda x: isinstance(x, ParamSpec)
    )
    out = []
    for path, spec in leaves:
        digest = zlib.crc32(jax.tree_util.keystr(path).encode())
        sub = jax.random.fold_in(key, digest % (2**31))
        out.append(_init_one(spec, sub))
    return jax.tree.unflatten(treedef, out)


def count_params(specs) -> int:
    leaves = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, ParamSpec))
    return sum(math.prod(s.shape) for s in leaves)


def spec_bytes(specs) -> int:
    leaves = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, ParamSpec))
    return sum(math.prod(s.shape) * jnp.dtype(s.dtype).itemsize for s in leaves)
