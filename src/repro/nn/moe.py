"""Expert-parallel Mixture-of-Experts with explicit shard_map collectives.

Design (DESIGN.md §4): experts shard over the ``model`` mesh axis; tokens
arrive **sequence-sharded** over the same axis (Megatron-SP residual stream),
so dispatch is two capacity-bounded ``all_to_all``s — the minimal-byte EP
schedule — rather than a replicated-compute psum.  At decode (seq len 1 the
sequence can't shard) the layer switches to the psum combine automatically.

Dispatch is scatter-based (positions from a cumsum over the one-hot routing
matrix), all shapes static.  Expert count pads up to the mesh (dead experts
masked at the router, ``-inf`` logits) — the config owns the padding so
parameter trees are mesh-independent.

Aux losses (load-balance + router z-loss) are returned for the trainer.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .layers import Ctx, dense
from .module import ParamSpec




__all__ = ["moe_spec", "moe_apply"]


def moe_spec(cfg, dtype=jnp.float32):
    m = cfg.moe
    E, d, f = m.padded_experts, cfg.d_model, m.d_ff_expert
    return {
        "router": {"kernel": ParamSpec((d, E), (None, None), dtype, "fan_in")},
        "w_gate": ParamSpec((E, d, f), ("expert", "embed", None), dtype, "fan_in"),
        "w_up": ParamSpec((E, d, f), ("expert", "embed", None), dtype, "fan_in"),
        "w_down": ParamSpec((E, f, d), ("expert", None, "embed"), dtype, "fan_in"),
    }


def _route(params, cfg, x_tokens, compute_dtype):
    """x [t, d] -> (probs [t, k], experts [t, k], aux losses)."""
    m = cfg.moe
    logits = dense(params["router"], x_tokens, jnp.float32)  # [t, E_pad]
    if m.padded_experts > m.n_experts:  # dead padding experts never win
        pad = jnp.full((m.padded_experts - m.n_experts,), -1e30, jnp.float32)
        logits = logits.at[..., m.n_experts:].set(pad)
    probs_full = jax.nn.softmax(logits, axis=-1)
    probs, experts = jax.lax.top_k(probs_full, m.top_k)  # [t, k]
    probs = probs / jnp.maximum(probs.sum(-1, keepdims=True), 1e-9)
    # load-balance (Switch) + z-loss
    t = x_tokens.shape[0]
    dispatch_frac = jnp.zeros((m.padded_experts,), jnp.float32).at[
        experts.reshape(-1)
    ].add(1.0) / (t * m.top_k)
    prob_frac = probs_full.mean(0)
    aux = {
        "load_balance": m.n_experts * jnp.sum(dispatch_frac * prob_frac),
        "router_z": jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2),
    }
    return probs.astype(compute_dtype), experts, aux


def _expert_ffn(recv, w_gate, w_up, w_down, compute_dtype):
    """recv [E_loc, c, d] through gated-SiLU expert FFNs."""
    g = jnp.einsum("ecd,edf->ecf", recv, w_gate.astype(compute_dtype))
    u = jnp.einsum("ecd,edf->ecf", recv, w_up.astype(compute_dtype))
    h = jax.nn.silu(g) * u
    return jnp.einsum("ecf,efd->ecd", h, w_down.astype(compute_dtype))


def _moe_body(params, cfg, x_local, model_axis: Optional[str],
              data_axes: Tuple[str, ...], use_a2a: bool):
    """shard_map body.  x_local [t, d] local tokens; expert weights local
    shards [E_loc, ...].  Returns (y_local [t, d], aux)."""
    m = cfg.moe
    cd = cfg.dtype
    E = m.padded_experts
    tp = 1
    if model_axis is not None:
        tp = jax.lax.axis_size(model_axis)
    E_loc = E // tp
    t, d = x_local.shape

    probs, experts, aux = _route(params, cfg, x_local, cd)
    k = m.top_k
    cap = max(1, int(math.ceil(t * k * m.capacity_factor / m.n_experts)))

    flat_e = experts.reshape(-1)                      # [t*k]
    flat_tok = jnp.repeat(jnp.arange(t, dtype=jnp.int32), k)
    flat_p = probs.reshape(-1)
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)          # [t*k, E]
    pos = jnp.cumsum(onehot, axis=0) - onehot                     # pre-count
    flat_pos = jnp.take_along_axis(pos, flat_e[:, None], axis=1)[:, 0]
    keep = flat_pos < cap                                         # dropped past capacity

    x_cast = x_local.astype(cd)
    send = jnp.zeros((E, cap, d), cd)
    # dropped (over-capacity) entries get an out-of-bounds expert index so the
    # scatter discards them instead of clobbering a real slot
    send = send.at[
        jnp.where(keep, flat_e, E),
        jnp.where(keep, flat_pos, 0),
    ].set(x_cast[flat_tok], mode="drop")

    if use_a2a and model_axis is not None:
        # [E, cap, d] -> split E across shards, gather sources on the cap axis
        recv = jax.lax.all_to_all(
            send, model_axis, split_axis=0, concat_axis=1, tiled=True
        )  # [E_loc, tp*cap, d]
    elif model_axis is not None:
        # psum mode: every shard routed the same (replicated) tokens; take
        # this shard's expert slice locally.
        shard = jax.lax.axis_index(model_axis)
        recv = jax.lax.dynamic_slice_in_dim(send, shard * E_loc, E_loc, axis=0)
    else:
        recv = send

    out = _expert_ffn(recv, params["w_gate"], params["w_up"], params["w_down"], cd)

    if use_a2a and model_axis is not None:
        ret = jax.lax.all_to_all(
            out, model_axis, split_axis=1, concat_axis=0, tiled=True
        )  # [E, cap, d]
    elif model_axis is not None:
        ret = jnp.zeros((E, cap, d), cd)
        shard = jax.lax.axis_index(model_axis)
        ret = jax.lax.dynamic_update_slice_in_dim(ret, out, shard * E_loc, axis=0)
    else:
        ret = out

    gathered = ret[
        jnp.where(keep, flat_e, 0), jnp.where(keep, flat_pos, 0)
    ]  # [t*k, d]
    contrib = jnp.where(keep[:, None], gathered * flat_p[:, None], 0.0)
    y = jnp.zeros((t, d), cd).at[flat_tok].add(contrib)

    if model_axis is not None and not use_a2a:
        y = jax.lax.psum(y, model_axis)
    # aux losses: average across shards so the trainer sees one scalar
    if model_axis is not None:
        axes = tuple(a for a in (*data_axes, model_axis) if a)
        aux = {n: jax.lax.pmean(v, axes) for n, v in aux.items()}
    return y, aux


def moe_apply(params, cfg, ctx: Ctx, x: jax.Array) -> Tuple[jax.Array, Dict]:
    """x [B, S, d] -> (y [B, S, d], aux).  Chooses the EP schedule:

    * mesh + S divisible by TP  -> sequence-sharded all_to_all dispatch,
    * mesh + tiny S (decode)    -> replicated-token psum combine,
    * no mesh (smoke tests)     -> single-shard local routing.
    """
    B, S, d = x.shape
    if ctx.mesh is None:
        y, aux = _moe_body(params, cfg, x.reshape(-1, d), None, (), False)
        return y.reshape(B, S, d), aux

    mesh = ctx.mesh
    tp = dict(zip(mesh.axis_names, mesh.devices.shape)).get("model", 1)
    dp_axes = ctx.data_axes
    use_a2a = S % tp == 0 and S >= tp
    x_spec = P(dp_axes, "model" if use_a2a else None, None)

    wspecs = {
        "router": {"kernel": P(None, None)},
        "w_gate": P("model", "data" if "data" in mesh.axis_names else None, None),
        "w_up": P("model", "data" if "data" in mesh.axis_names else None, None),
        "w_down": P("model", None, "data" if "data" in mesh.axis_names else None),
    }

    def body(p, xl):
        bl, sl, _ = xl.shape
        # FSDP: expert weights arrive data-sharded on d/f; cast to the
        # compute dtype *first* so the gather moves bf16, then gather.
        if "data" in mesh.axis_names:
            cast = lambda a: a.astype(cfg.dtype)
            p = dict(
                p,
                w_gate=jax.lax.all_gather(cast(p["w_gate"]), "data", axis=1,
                                          tiled=True),
                w_up=jax.lax.all_gather(cast(p["w_up"]), "data", axis=1,
                                        tiled=True),
                w_down=jax.lax.all_gather(cast(p["w_down"]), "data", axis=2,
                                          tiled=True),
            )
        y, aux = _moe_body(p, cfg, xl.reshape(-1, d), "model", dp_axes, use_a2a)
        return y.reshape(bl, sl, d), aux

    y, aux = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(wspecs, x_spec),
        out_specs=(x_spec, {"load_balance": P(), "router_z": P()}),
        check_vma=False,
    )(params, x)
    return y, aux
