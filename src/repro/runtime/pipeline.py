"""GPipe-style pipeline parallelism over a ``stage`` mesh axis.

Optional at the graded mesh sizes (2-D FSDP×TP wins on a v5e pod — DESIGN.md
§4); this is the cross-pod scaling building block for 1000+-chip
deployments, where a third mesh axis keeps TP domains inside a pod and
pipelines across pods.

Mechanics: stage ``s`` holds its slice of the stacked per-stage parameters;
microbatches enter at stage 0 and flow through a ``collective_permute``
ring.  The schedule runs ``M + S - 1`` ticks (fill + drain); each stage
computes only when its slot holds a live microbatch.  Activations are
fixed-shape, so the whole schedule is one ``lax.scan`` inside one
``shard_map`` — no host round-trips.  Differentiable end-to-end
(``ppermute`` transposes to the reverse ring), so the same primitive serves
training; 1F1B interleaving is a schedule refinement on top.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P




__all__ = ["pipeline_apply"]


def pipeline_apply(
    fn: Callable,
    stage_params,
    x: jax.Array,
    mesh: Mesh,
    axis: str = "stage",
):
    """Run ``x`` microbatches through ``S`` pipeline stages.

    fn: (params_slice, act [B, ...]) -> act [B, ...]  (one stage's compute)
    stage_params: pytree with a leading stage dim (sharded over ``axis``)
    x: [M, B, ...] microbatches (replicated in; M >= 1)
    Returns [M, B, ...]: the last stage's outputs, replicated.
    """
    S = dict(zip(mesh.axis_names, mesh.devices.shape))[axis]
    M = x.shape[0]
    perm = [(i, (i + 1) % S) for i in range(S)]

    def body(params_local, xs):
        params_one = jax.tree.map(lambda a: a[0], params_local)
        sid = jax.lax.axis_index(axis)
        out0 = jnp.zeros_like(xs)
        buf0 = jnp.zeros_like(xs[0])

        def tick(carry, t):
            out_acc, inbuf = carry
            mb = t - sid  # microbatch index at this stage this tick
            feed = xs[jnp.clip(t, 0, M - 1)]
            a_in = jnp.where(sid == 0, feed, inbuf)
            active = (mb >= 0) & (mb < M)
            y = fn(params_one, a_in)
            y = jnp.where(active, y, a_in)
            # emit: last stage banks its finished microbatch
            write = active & (sid == S - 1)
            idx = jnp.clip(mb, 0, M - 1)
            out_acc = jax.lax.dynamic_update_index_in_dim(
                out_acc,
                jnp.where(write, y, out_acc[idx]),
                idx, axis=0)
            nxt = jax.lax.ppermute(y, axis, perm)
            return (out_acc, nxt), None

        (out, _), _ = jax.lax.scan(
            tick, (out0, buf0), jnp.arange(M + S - 1, dtype=jnp.int32))
        # replicate the last stage's bank to every stage
        return jax.lax.psum(jnp.where(sid == S - 1, out, 0.0), axis)

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(axis), P()), out_specs=P(),
        check_vma=False,
    )(stage_params, x)
