"""Compressed cross-replica gradient reduction.

Two schemes, both expressed as explicit collectives inside ``shard_map`` so
the byte reduction is visible in the compiled HLO (and in the roofline
collective term):

* ``bf16``  — all-reduce in bf16: 2× fewer wire bytes than fp32.
* ``int8``  — two-phase compressed all-reduce: per-chunk int8 quantize →
  ``all_to_all`` (each replica owns one chunk) → local fp32 reduce → requant
  → ``all_gather``.  Wire bytes ≈ 2·N·1B vs 2·N·4B for a ring fp32
  all-reduce — a 4× cut.  Per-chunk fp32 scales travel alongside (negligible).

Error feedback: each scheme returns the *local* quantization residual
(``g_local − Q(g_local)``); the trainer folds it into the next step's local
gradient (EF-SGD), keeping the compressed reduction unbiased over time at
zero extra collective cost.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp


__all__ = ["compressed_pmean", "compress_grads_tree"]


def _int8_pmean(x: jax.Array, axis: str) -> Tuple[jax.Array, jax.Array]:
    """Mean over ``axis`` via int8 two-phase reduce.  Returns (mean, residual)."""
    n_shards = jax.lax.axis_size(axis)
    n = x.size
    pad = (-n) % n_shards
    flat = jnp.pad(x.reshape(-1), (0, pad)).reshape(n_shards, -1)
    scale = jnp.max(jnp.abs(flat), axis=1, keepdims=True) / 127.0 + 1e-12
    q = jnp.clip(jnp.round(flat / scale), -127, 127).astype(jnp.int8)
    residual = (flat - q.astype(jnp.float32) * scale).reshape(-1)[:n].reshape(x.shape)
    # phase 1: every replica receives the chunk it owns from all peers (int8)
    q_t = jax.lax.all_to_all(q, axis, split_axis=0, concat_axis=0, tiled=True)
    s_t = jax.lax.all_to_all(
        jnp.broadcast_to(scale, (n_shards, 1)), axis, split_axis=0,
        concat_axis=0, tiled=True)
    part = jnp.sum(q_t.astype(jnp.float32).reshape(n_shards, -1)
                   * s_t.reshape(n_shards, 1), axis=0) / n_shards
    # phase 2: requantize the reduced chunk, all-gather int8 + scales
    s2 = jnp.max(jnp.abs(part)) / 127.0 + 1e-12
    q2 = jnp.clip(jnp.round(part / s2), -127, 127).astype(jnp.int8)
    qg = jax.lax.all_gather(q2, axis, axis=0, tiled=False)   # [S, chunk] int8
    sg = jax.lax.all_gather(s2, axis, axis=0, tiled=False)   # [S]
    full = (qg.astype(jnp.float32) * sg[:, None]).reshape(-1)
    return full[:n].reshape(x.shape), residual


def compressed_pmean(x: jax.Array, axis: str, scheme: str = "int8"):
    """Returns (reduced, local_residual)."""
    x = x.astype(jnp.float32)
    if scheme == "int8":
        return _int8_pmean(x, axis)
    if scheme == "bf16":
        xq = x.astype(jnp.bfloat16)
        reduced = jax.lax.pmean(xq, axis).astype(jnp.float32)
        return reduced, x - xq.astype(jnp.float32)
    if scheme == "none":
        return jax.lax.pmean(x, axis), jnp.zeros_like(x)
    raise ValueError(f"unknown compression scheme {scheme!r}")


def compress_grads_tree(grads, axis: str, scheme: str = "int8"):
    """pmean every leaf with compression; returns (reduced, residuals)."""
    pairs = jax.tree.map(lambda g: compressed_pmean(g, axis, scheme), grads)
    reduced = jax.tree.map(lambda p: p[0], pairs,
                           is_leaf=lambda p: isinstance(p, tuple))
    residual = jax.tree.map(lambda p: p[1], pairs,
                            is_leaf=lambda p: isinstance(p, tuple))
    return reduced, residual
