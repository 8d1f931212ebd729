"""Base layers: sharding context, dense/embedding/norms, rotary embeddings.

All apply functions are pure: ``f(params, x, ...) -> y`` over pytrees built
from :mod:`repro.nn.module` ParamSpecs.  A :class:`Ctx` carries the mesh and
logical->mesh rules so layers can place internal activation sharding
constraints (the Megatron-SP pattern: residual stream sequence-sharded over
the model axis; attention/MLP interiors sharded over heads/mlp).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .module import ParamSpec, ShardingRules, logical_to_partition_spec




__all__ = ["Ctx", "dense_spec", "dense", "embed_spec", "rmsnorm_spec", "rmsnorm",
           "layernorm_spec", "layernorm", "rope", "sinusoidal_positions"]


@dataclasses.dataclass(frozen=True)
class Ctx:
    """Execution context: mesh + rules (None = single-device smoke mode)."""

    mesh: Optional[Mesh] = None
    rules: Optional[ShardingRules] = None
    decode: bool = False
    explicit_rs: bool = False  # §Perf: shard_map row-parallel matmuls with
                               # explicit bf16 psum_scatter instead of
                               # letting the partitioner all-reduce

    def constrain(self, x: jax.Array, *logical_axes):
        """Sharding constraint via logical axes; no-op without a mesh.

        Divisibility fallback in the rules means e.g. a seq axis of length 1
        (decode) silently replicates instead of erroring.
        """
        if self.mesh is None:
            return x
        spec = logical_to_partition_spec(logical_axes, x.shape, self.rules)
        return jax.lax.with_sharding_constraint(x, NamedSharding(self.mesh, spec))

    @property
    def data_axes(self) -> Tuple[str, ...]:
        if self.mesh is None:
            return ()
        return tuple(a for a in ("pod", "data") if a in self.mesh.axis_names)


# ---------------------------------------------------------------------------
# Dense
# ---------------------------------------------------------------------------


def dense_spec(d_in: int, d_out, axes, bias: bool = False, dtype=jnp.float32,
               scale: float = 1.0, init: str = "fan_in"):
    """Kernel [d_in, *d_out] (+ optional bias).  ``axes`` covers all dims."""
    out_shape = (d_out,) if isinstance(d_out, int) else tuple(d_out)
    p = {"kernel": ParamSpec((d_in, *out_shape), tuple(axes), dtype, init, scale)}
    if bias:
        p["bias"] = ParamSpec(tuple(out_shape), tuple(axes[1:]), dtype, "zeros")
    return p


def dense(params, x: jax.Array, compute_dtype=jnp.bfloat16,
          precision=None) -> jax.Array:
    """x [..., d_in] @ kernel [d_in, *rest] -> [..., *rest]."""
    k = params["kernel"].astype(compute_dtype)
    kernel_2d = k.reshape(k.shape[0], -1)
    y = jnp.matmul(x.astype(compute_dtype), kernel_2d, precision=precision
                   ).reshape(*x.shape[:-1], *k.shape[1:])
    if "bias" in params:
        y = y + params["bias"].astype(compute_dtype)
    return y


def row_parallel(ctx: Ctx, x: jax.Array, w: jax.Array, eq: str,
                 w_gather_axes=("data", "pod")) -> Optional[jax.Array]:
    """Explicit Megatron-SP row-parallel contraction (§Perf 'rowrs').

    ``y = einsum(eq, x, w)`` where the contraction dims are model-sharded
    (x's heads/mlp axis, w's matching axis), finishing with a **bf16
    psum_scatter onto the sequence axis** — vs the partitioner's choice of a
    full (fp32-widened on this backend) all-reduce + slice.  Ring bytes:
    RS = N vs AR = 2N, and the wire dtype stays bf16.

    Returns None when inapplicable (no mesh / seq not divisible / flag off)
    so callers fall back to the einsum + sharding-constraint path.
    """
    if ctx.mesh is None or not ctx.explicit_rs:
        return None
    sizes = dict(zip(ctx.mesh.axis_names, ctx.mesh.devices.shape))
    tp = sizes.get("model", 1)
    S = x.shape[1]
    if tp == 1 or S % tp or S < tp:
        return None
    from jax.sharding import PartitionSpec as P

    dp = tuple(a for a in ("pod", "data") if a in sizes)
    gather_axes = tuple(a for a in w_gather_axes if a in sizes)
    # w: [contract..., d_out] with contract dim 0 model-sharded and d_out
    # FSDP-sharded on the last axis; x: [B, S, contract...] model-sharded
    # on dim 2
    x_spec = P(dp, None, "model", *([None] * (x.ndim - 3)))
    w_spec = P("model", *([None] * (w.ndim - 2)), gather_axes or None)

    def body(xl, wl):
        if gather_axes:
            wl = jax.lax.all_gather(wl.astype(xl.dtype), gather_axes,
                                    axis=wl.ndim - 1, tiled=True)
        y = jnp.einsum(eq, xl, wl.astype(xl.dtype))
        return jax.lax.psum_scatter(y, "model", scatter_dimension=1,
                                    tiled=True)

    return jax.shard_map(
        body, mesh=ctx.mesh, in_specs=(x_spec, w_spec),
        out_specs=P(dp, "model", None), check_vma=False,
    )(x, w)


# ---------------------------------------------------------------------------
# Embedding / norms
# ---------------------------------------------------------------------------


def embed_spec(vocab: int, d: int, dtype=jnp.float32):
    # 1/sqrt(d) init keeps tied logits ~unit variance at init (CE ≈ ln V)
    return {"embedding": ParamSpec((vocab, d), ("vocab", "embed"), dtype, "embed",
                                   scale=d ** -0.5)}


def rmsnorm_spec(d: int, dtype=jnp.float32):
    return {"scale": ParamSpec((d,), (None,), dtype, "ones")}


def rmsnorm(params, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * params["scale"].astype(jnp.float32)).astype(dt)


def layernorm_spec(d: int, dtype=jnp.float32):
    return {"scale": ParamSpec((d,), (None,), dtype, "ones"),
            "bias": ParamSpec((d,), (None,), dtype, "zeros")}


def layernorm(params, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mu), axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * params["scale"] + params["bias"]).astype(dt)


# ---------------------------------------------------------------------------
# Positions
# ---------------------------------------------------------------------------


def rope(x: jax.Array, positions: jax.Array, theta: float = 10000.0) -> jax.Array:
    """Rotary embedding.  x [..., S, H, D] (D even), positions [..., S]."""
    d = x.shape[-1]
    half = d // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freq  # [..., S, half]
    cos = jnp.cos(ang)[..., None, :]
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    ).astype(x.dtype)


def sinusoidal_positions(length: int, d: int, offset=0) -> jax.Array:
    pos = jnp.arange(length, dtype=jnp.float32) + offset
    half = d // 2
    freq = 10000.0 ** (-jnp.arange(half, dtype=jnp.float32) / max(half - 1, 1))
    ang = pos[:, None] * freq[None]
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)
