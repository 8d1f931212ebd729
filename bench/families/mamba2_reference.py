"""Plain reference for the served decode: Mamba2 with quantized activations,
in straightforward ``jax.numpy`` at float32 and full matmul precision.

It states the semantics the table decode must reproduce, written from the
Mamba2 description (Dao & Gu, arXiv:2405.21060) and the paper's table
lookup, and imports nothing of the program under test:

* every projection input is quantized to the symmetric ``act_bits`` grid
  (codes ``round(x / scale)`` clipped to ``[-K/2, K/2 - 1]``) and then
  multiplied by the float32 kernel: a table fetch equals exactly this;
* the depthwise conv quantizes its raw ``k``-tap window with one scale for
  all layers, adds the bias, then applies SiLU;
* the SSD recurrence, the ``D`` skip, the SiLU gate and the gated RMSNorm run
  in float32;
* the tied logits head quantizes the final-norm output the same way and
  multiplies it by the embedding rounded to the ``head_weight_bits`` grid,
  then rounds each logit to its grid ``act scale x weight scale``.

Scales come from :func:`calibrate`: the absmax of each quantized stream over
a float (unquantized) pass on calibration tokens.  The benchmark computes
them here and hands the same numbers to the program's table build, so both
sides quantize on one grid.

Weights are the benchmark's own (``bench/families/mamba2.py``), in the
program's parameter layout: kernels are ``[d_in, d_out]`` and blocks are
stacked on a leading layer axis.
"""

from __future__ import annotations

from typing import Dict, NamedTuple

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


class Dims(NamedTuple):
    d_model: int
    n_layer: int
    vocab: int
    d_state: int
    headdim: int
    expand: int
    ngroups: int
    d_conv: int
    act_bits: int
    head_weight_bits: int
    eps: float

    @property
    def d_inner(self) -> int:
        return self.expand * self.d_model

    @property
    def nheads(self) -> int:
        return self.d_inner // self.headdim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.ngroups * self.d_state


def dims(conf: Dict) -> Dims:
    """The sizes the reference needs, read from a configuration file."""
    s, q = conf["ssm_cfg"], conf["pcilt"]
    return Dims(d_model=conf["d_model"], n_layer=conf["n_layer"],
                vocab=conf["vocab_size"], d_state=s["d_state"],
                headdim=s["headdim"], expand=s["expand"],
                ngroups=s["ngroups"], d_conv=s["d_conv"],
                act_bits=q["act_bits"], head_weight_bits=q["head_weight_bits"],
                eps=conf["norm_epsilon"])


def grid(bits: int):
    """``(lowest code, highest code, span)`` of the symmetric grid: codes
    run from ``-K/2`` to ``K/2 - 1`` and the scale maps an absmax onto the
    positive side's ``span`` steps."""
    k = 1 << bits
    lo, hi = -(k // 2), k - 1 - k // 2
    return lo, hi, max(hi, 1)


def scale_from_amax(amax, bits: int):
    return jnp.maximum(jnp.asarray(amax, jnp.float32), 1e-8) / grid(bits)[2]


def quant(x, scale, bits: int):
    lo, hi, _ = grid(bits)
    s = jnp.asarray(scale, x.dtype)
    return jnp.clip(jnp.round(x / s), lo, hi) * s


def rmsnorm(x, w, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def matmul(x, w):
    return jnp.matmul(x, w.astype(x.dtype), precision=HIGHEST)


def head_grid(params, d: Dims):
    """The embedding rounded to the head's weight grid, and that grid's
    scale."""
    k = params["embed"]["embedding"].astype(jnp.float32).T  # [d, Vp]
    w_scale = scale_from_amax(jnp.max(jnp.abs(k)), d.head_weight_bits)
    return quant(k, w_scale, d.head_weight_bits), w_scale


def zero_state(d: Dims, batch: int, dtype=jnp.float32):
    L = d.n_layer
    return {"conv": jnp.zeros((L, batch, d.d_conv - 1, d.conv_dim), dtype),
            "ssd": jnp.zeros((L, batch, d.nheads, d.d_state, d.headdim),
                             dtype)}


def block(p, h, conv_st, ssd_st, d: Dims, sc, dtype):
    """One Mamba2 block for one position: ``h [B, d_model]``.

    ``sc`` holds this layer's ``in``/``out`` scales and the shared ``conv``
    scale, or is ``None`` for the unquantized float pass; the float pass
    also returns the absmax of each stream that the served decode
    quantizes."""
    m = p["mixer"]
    q = (lambda x, s: x) if sc is None else \
        (lambda x, s: quant(x, s, d.act_bits))
    xn = rmsnorm(h, p["ln"]["scale"], d.eps)
    xq = q(xn, None if sc is None else sc["in"])
    z = matmul(xq, m["wz"]["kernel"])
    xi = matmul(xq, m["wx"]["kernel"])
    Bi = matmul(xq, m["wB"]["kernel"])
    Ci = matmul(xq, m["wC"]["kernel"])
    dt = matmul(xq, m["wdt"]["kernel"])
    xbc_raw = jnp.concatenate([xi, Bi, Ci], -1)
    window = jnp.concatenate([conv_st, xbc_raw[:, None]], 1)  # [B, k, C]
    wq = q(window, None if sc is None else sc["conv"])
    conv = jnp.einsum("bkc,kc->bc", wq, m["conv_w"].astype(dtype),
                      precision=HIGHEST) + m["conv_b"].astype(dtype)
    xBC = jax.nn.silu(conv)
    di, gn = d.d_inner, d.ngroups * d.d_state
    xi, Bi, Ci = xBC[:, :di], xBC[:, di:di + gn], xBC[:, di + gn:]
    B = h.shape[0]
    H, P, N = d.nheads, d.headdim, d.d_state
    rep = H // d.ngroups
    xh = xi.reshape(B, H, P)
    Bh = jnp.repeat(Bi.reshape(B, d.ngroups, N), rep, 1)
    Ch = jnp.repeat(Ci.reshape(B, d.ngroups, N), rep, 1)
    dt = jax.nn.softplus(dt + m["dt_bias"].astype(dtype))  # [B, H]
    A = -jnp.exp(m["A_log"].astype(dtype))
    ssd = ssd_st * jnp.exp(dt * A)[..., None, None] + \
        (Bh * dt[..., None])[..., :, None] * xh[..., None, :]
    y = jnp.einsum("bhn,bhnp->bhp", Ch, ssd, precision=HIGHEST)
    y = y + m["D"].astype(dtype)[None, :, None] * xh
    y = y.reshape(B, di) * jax.nn.silu(z)
    y = rmsnorm(y, m["norm"]["scale"], d.eps)
    out = matmul(q(y, None if sc is None else sc["out"]), m["wo"]["kernel"])
    amax = None
    if sc is None:
        amax = {"in": jnp.max(jnp.abs(xn)), "out": jnp.max(jnp.abs(y)),
                "conv_in": jnp.max(jnp.abs(xbc_raw))}
    return h + out, window[:, 1:], ssd, amax


def step(params, state, tok, d: Dims, scales=None, dtype=jnp.float32):
    """One position for a batch of sequences: ``tok [B]`` -> final-norm
    output ``[B, d_model]`` and the new state; the float pass (``scales``
    None) also returns the per-layer absmax of every quantized stream."""
    h = params["embed"]["embedding"].astype(dtype)[tok]

    def body(h, inp):
        p, cst, sst, s = inp
        h, cst, sst, amax = block(p, h, cst, sst, d, s, dtype)
        return h, (cst, sst, amax)

    per = None
    if scales is not None:
        per = {"in": scales["in"], "out": scales["out"],
               "conv": jnp.broadcast_to(scales["conv"], scales["in"].shape)}
    blocks = jax.tree.map(lambda a: a.astype(dtype), params["blocks"])
    h, (cst, sst, amax) = jax.lax.scan(
        body, h, (blocks, state["conv"], state["ssd"], per))
    hf = rmsnorm(h, params["ln_f"]["scale"], d.eps)
    return hf, {"conv": cst, "ssd": sst}, amax


def calibrate(params, tokens, d: Dims):
    """Scales for every quantized stream, from the absmax of a float pass
    over ``tokens [B, T]``: ``in``/``out`` per layer, one ``conv`` scale for
    all layers, and the head's activation scale."""
    B, T = tokens.shape

    def pos(state, tok):
        hf, state, amax = step(params, state, tok, d)
        amax = dict(amax, head_in=jnp.max(jnp.abs(hf)))
        return state, amax

    _, amax = jax.lax.scan(pos, zero_state(d, B), tokens.T)
    b = d.act_bits
    return {"in": scale_from_amax(amax["in"].max(0), b),
            "out": scale_from_amax(amax["out"].max(0), b),
            "conv": scale_from_amax(amax["conv_in"].max(), b),
            "head": scale_from_amax(amax["head_in"].max(), b)}


def logits(params, hf, scales, d: Dims, kq=None, w_scale=None):
    """Quantized tied head: ``[B, vocab]`` on its exact logit grid."""
    if kq is None:
        kq, w_scale = head_grid(params, d)
    hq = quant(hf.astype(jnp.float32), scales["head"], d.act_bits)
    y = matmul(hq, kq)
    step_ = scales["head"] * w_scale
    return (jnp.round(y / step_) * step_)[:, :d.vocab]


def gaps(params, scales, inputs, targets, d: Dims):
    """Teacher-forced comparison of served tokens.

    ``inputs [B, T]`` are the tokens fed at each position (prompt, then the
    served tokens but the last) and ``targets [B, T]`` the token served
    after each position (``-1`` where nothing is compared).  Returns
    ``gap [B, T]``: how far the reference's logit of the served token lies
    below its best logit there, and ``step``, the logit grid.
    """
    B, T = inputs.shape
    kq, w_scale = head_grid(params, d)

    def pos(st, x):
        tok, tgt = x
        hf, st, _ = step(params, st, tok, d, scales)
        lg = logits(params, hf, scales, d, kq, w_scale)
        got = jnp.take_along_axis(lg, jnp.maximum(tgt, 0)[:, None], 1)[:, 0]
        return st, jnp.where(tgt >= 0, lg.max(-1) - got, 0.0)

    _, gap = jax.lax.scan(pos, zero_state(d, B), (inputs.T, targets.T))
    return gap.T, scales["head"] * w_scale
