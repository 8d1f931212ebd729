"""Plain reference for the served decode of Zamba2 (Zyphra's Zamba2-7B,
arXiv:2411.15242, and the ``Zamba2`` classes of Hugging Face
``transformers``): Mamba2 layers with shared attention+MLP blocks, with
quantized activations, in straightforward ``jax.numpy`` at float32 and
full matmul precision.  It imports nothing of the program under test.

One position of layer ``i`` (``h`` the residual stream, ``e`` the
embedding of the token):

* at a hybrid layer (application ``j``, shared block ``j mod blocks``):
  ``t = RMSNorm(concat(h, e))``; ``q, k, v`` from ``t`` with RoPE on ``q``
  and ``k`` (rotate-half, ``rope_theta``); softmax attention over the
  positions so far at scale ``(head_dim / 2) ** -0.5``; ``a = o(...)``;
  ``n = RMSNorm(a)``; ``[g, u] = gate_up(n) + B_j(A_j(n))``;
  ``m = down(gelu(g) * u)`` with the exact (erf) gelu; ``s = linear_j(m)``;
  then ``h <- h + Mamba(RMSNorm(h + s))``;
* at every other layer ``h <- h + Mamba(RMSNorm(h))``;
* Mamba: ``z, x, B, C, dt`` projections, the causal depthwise conv with
  bias and SiLU over ``[x, B, C]``, ``dt = softplus(dt + dt_bias)``, the
  SSD recurrence with ``A = -exp(A_log)`` and the ``D`` skip, the gate
  ``y * silu(z)``, RMSNorm by group, the out projection;
* a final RMSNorm and the tied head.

Quantization, as the table decode serves it: every projection input is
rounded to the symmetric ``act_bits`` grid of its site's scale (codes
``round(x / scale)`` clipped to ``[-K/2, K/2 - 1]``) and multiplied by the
float32 kernel, which is what a table fetch computes; the conv quantizes
its raw window with one scale for all layers; the head quantizes the
final norm's output and multiplies it by the embedding rounded to the
``head_weight_bits`` grid, then rounds each logit to its grid.  A shared
block's sites take the widest scale over the applications of the block;
an application's own sites (adapter, ``linear``) take its own.

Departures, the program's alike: the configuration holds one chip's share
of a tensor-parallel layer (its attention heads, MLP columns, Mamba heads
with their group's B and C, vocabulary rows), so the outputs of ``o``,
``down`` and the Mamba out projection are this chip's partial sums (no
all-reduce), and the grouped norm's statistic covers only the channels
held of the group.  The in projection is held as five kernels
(``wz, wx, wB, wC, wdt``), a split of the published ``in_proj``.

Scales come from :func:`calibrate`: the absmax of each quantized stream
over a float (unquantized) pass on calibration tokens.  Weights are the
benchmark's (``bench/families/zamba2.py``), in the program's layout:
kernels ``[d_in, d_out]``, stacked on a leading layer, block or
application axis.
"""

from __future__ import annotations

from typing import Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
#: the shared blocks' sites: (weights group, key, stack, quantizer grid)
SHARED = (("q", "shared", "block", "attn_in"),
          ("k", "shared", "block", "attn_in"),
          ("v", "shared", "block", "attn_in"),
          ("o", "shared", "block", "o_in"),
          ("gate_up", "shared", "block", "mlp_in"),
          ("adapter_A", "apps", "app", "mlp_in"),
          ("adapter_B", "apps", "app", "ad_in"),
          ("down", "shared", "block", "down_in"),
          ("linear", "apps", "app", "lin_in"))
GRIDS = ("attn_in", "o_in", "mlp_in", "ad_in", "down_in", "lin_in")


class Dims(NamedTuple):
    d_model: int
    n_layer: int
    vocab: int
    d_state: int
    headdim: int
    nheads: int          # Mamba heads held
    ngroups: int         # Mamba groups held
    norm_group: int      # channels per gated-norm group held
    d_conv: int
    attn_heads: int      # attention heads held
    attn_head_dim: int
    ffn: int             # MLP columns held
    rank: int
    hybrid: Tuple[int, ...]
    blocks: int
    act_bits: int
    head_weight_bits: int
    eps: float
    theta: float

    @property
    def d_inner(self) -> int:
        return self.nheads * self.headdim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.ngroups * self.d_state


def dims(conf: Dict) -> Dims:
    """The sizes the reference needs, read from a configuration file."""
    d = conf["hidden_size"]
    heads = conf["mamba_expand"] * d // conf["mamba_headdim"]
    per_group = heads // conf["mamba_ngroups"]
    held = conf["n_mamba_heads"]
    first = conf["layers_held"][0]
    q = conf["pcilt"]
    return Dims(
        d_model=d, n_layer=conf["num_hidden_layers"],
        vocab=conf["vocab_size"], d_state=conf["mamba_d_state"],
        headdim=conf["mamba_headdim"], nheads=held,
        ngroups=max(held // per_group, 1),
        norm_group=min(per_group, held) * conf["mamba_headdim"],
        d_conv=conf["mamba_d_conv"], attn_heads=conf["num_attention_heads"],
        attn_head_dim=conf["attention_head_dim"],
        ffn=conf["ffn_columns_held"], rank=conf["adapter_rank"],
        hybrid=tuple(i - first for i in conf["hybrid_layer_ids"]
                     if i in conf["layers_held"]),
        blocks=conf["num_mem_blocks"], act_bits=q["act_bits"],
        head_weight_bits=q["head_weight_bits"], eps=conf["rms_norm_eps"],
        theta=float(conf["rope_theta"]))


def grid(bits: int):
    """``(lowest code, highest code, span)`` of the symmetric grid."""
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
    y = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
    return y * w


def matmul(x, w):
    return jnp.matmul(x, w, precision=HIGHEST)


def rope(x, pos, theta):
    """Rotate-half RoPE of ``x [B, H, D]`` at positions ``pos [B]``."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None, None] * freq
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def head_grid(params, d: Dims):
    k = params["embed"]["embedding"].T  # [d, V]
    w_scale = scale_from_amax(jnp.max(jnp.abs(k)), d.head_weight_bits)
    return quant(k, w_scale, d.head_weight_bits), w_scale


def site_scales(scales, d: Dims):
    """Each shared site's scale per application: its block's widest over
    the block's applications, or the application's own."""
    out = {}
    for name, _, stack, g in SHARED:
        s = scales[g]
        if stack == "block":
            wide = jnp.stack([jnp.max(s[b::d.blocks]) for b in range(d.blocks)])
            s = wide[jnp.arange(len(d.hybrid)) % d.blocks]
        out[name] = s
    return out


def zero_state(d: Dims, batch: int, positions: int):
    A, L = len(d.hybrid), d.n_layer
    kv = jnp.zeros((A, batch, positions, d.attn_heads, d.attn_head_dim))
    return {"conv": jnp.zeros((L, batch, d.d_conv - 1, d.conv_dim)),
            "ssd": jnp.zeros((L, batch, d.nheads, d.d_state, d.headdim)),
            "k": kv, "v": kv, "t": jnp.zeros((), jnp.int32)}


def mamba(m, x, conv_st, ssd_st, d: Dims, q, sc):
    """One Mamba2 mixer on the normed input ``x [B, d]``."""
    xq = q(x, sc["in"])
    z = matmul(xq, m["wz"]["kernel"])
    xi = matmul(xq, m["wx"]["kernel"])
    Bi = matmul(xq, m["wB"]["kernel"])
    Ci = matmul(xq, m["wC"]["kernel"])
    dt = matmul(xq, m["wdt"]["kernel"])
    raw = jnp.concatenate([xi, Bi, Ci], -1)
    window = jnp.concatenate([conv_st, raw[:, None]], 1)  # [B, k, C]
    conv = jnp.einsum("bkc,kc->bc", q(window, sc["conv"]), m["conv_w"],
                      precision=HIGHEST) + m["conv_b"]
    xBC = jax.nn.silu(conv)
    di, gn = d.d_inner, d.ngroups * d.d_state
    xi, Bi, Ci = xBC[:, :di], xBC[:, di:di + gn], xBC[:, di + gn:]
    B = x.shape[0]
    H, P, N = d.nheads, d.headdim, d.d_state
    rep = H // d.ngroups
    xh = xi.reshape(B, H, P)
    Bh = jnp.repeat(Bi.reshape(B, d.ngroups, N), rep, 1)
    Ch = jnp.repeat(Ci.reshape(B, d.ngroups, N), rep, 1)
    dt = jax.nn.softplus(dt + m["dt_bias"])
    A = -jnp.exp(m["A_log"])
    ssd = ssd_st * jnp.exp(dt * A)[..., None, None] + \
        (Bh * dt[..., None])[..., :, None] * xh[..., None, :]
    y = jnp.einsum("bhn,bhnp->bhp", Ch, ssd, precision=HIGHEST)
    y = (y + m["D"][None, :, None] * xh).reshape(B, di) * jax.nn.silu(z)
    yg = y.reshape(B, -1, d.norm_group)
    yg = yg * jax.lax.rsqrt(jnp.mean(yg * yg, -1, keepdims=True) + d.eps)
    y = yg.reshape(B, di) * m["norm"]["scale"]
    out = matmul(q(y, sc["out"]), m["wo"]["kernel"])
    amax = {"in": jnp.max(jnp.abs(x)), "out": jnp.max(jnp.abs(y)),
            "conv_in": jnp.max(jnp.abs(raw))}
    return out, window[:, 1:], ssd, amax


def shared(sp, ap, h, e, kj, vj, t, d: Dims, q, sc):
    """One application of a shared block at position ``t``: ``s`` and the
    application's caches with this position written."""
    B = h.shape[0]
    Hh, hd = d.attn_heads, d.attn_head_dim
    amax = {}

    def site(name, x, w, grid_):
        amax.setdefault(grid_, jnp.max(jnp.abs(x)))
        return matmul(q(x, sc[name]), w)

    tn = rmsnorm(jnp.concatenate([h, e], -1), sp["ln"]["scale"], d.eps)
    pos = jnp.full((B,), t)
    qh = rope(site("q", tn, sp["q"]["kernel"], "attn_in").reshape(B, Hh, hd),
              pos, d.theta)
    kh = rope(site("k", tn, sp["k"]["kernel"], "attn_in").reshape(B, Hh, hd),
              pos, d.theta)
    vh = site("v", tn, sp["v"]["kernel"], "attn_in").reshape(B, Hh, hd)
    kj = jax.lax.dynamic_update_index_in_dim(kj, kh, t, 1)
    vj = jax.lax.dynamic_update_index_in_dim(vj, vh, t, 1)
    att = jnp.einsum("bhd,bshd->bhs", qh, kj, precision=HIGHEST) \
        * (hd / 2) ** -0.5
    att = jnp.where(jnp.arange(kj.shape[1]) <= t, att, -jnp.inf)
    o = jnp.einsum("bhs,bshd->bhd", jax.nn.softmax(att, -1), vj,
                   precision=HIGHEST).reshape(B, Hh * hd)
    a = site("o", o, sp["o"]["kernel"], "o_in")
    n = rmsnorm(a, sp["ln_mlp"]["scale"], d.eps)
    gu = site("gate_up", n, sp["gate_up"]["kernel"], "mlp_in")
    lora = site("adapter_B", site("adapter_A", n, ap["adapter_A"]["kernel"],
                                  "mlp_in"),
                ap["adapter_B"]["kernel"], "ad_in")
    g, u = jnp.split(gu + lora, 2, -1)
    m = site("down", jax.nn.gelu(g, approximate=False) * u,
             sp["down"]["kernel"], "down_in")
    s = site("linear", m, ap["linear"]["kernel"], "lin_in")
    return s, kj, vj, amax


def step(params, state, tok, d: Dims, scales=None):
    """One position for a batch of sequences: ``tok [B]`` -> final-norm
    output ``[B, d_model]`` and the new state; the float pass (``scales``
    None) also returns the absmax of every quantized stream."""
    if scales is None:
        q, per = (lambda x, s: x), None
    else:
        q = lambda x, s: quant(x, s, d.act_bits)  # noqa: E731
        per = site_scales(scales, d)
    e = params["embed"]["embedding"][tok]
    h = e
    t = state["t"]
    conv, ssd, ks, vs = [], [], [], []
    amax = {"in": [], "out": [], "conv_in": []}
    shared_amax = []
    for i in range(d.n_layer):
        p = jax.tree.map(lambda a: a[i], params["blocks"])
        x = h
        if i in d.hybrid:
            j = d.hybrid.index(i)
            sp = jax.tree.map(lambda a: a[j % d.blocks], params["shared"])
            ap = jax.tree.map(lambda a: a[j], params["apps"])
            sc = {n: None if per is None else per[n][j] for n, *_ in SHARED}
            s, kj, vj, am = shared(sp, ap, h, e, state["k"][j],
                                   state["v"][j], t, d, q, sc)
            ks.append(kj), vs.append(vj), shared_amax.append(am)
            x = h + s
        sc = {"in": None, "out": None, "conv": None} if scales is None else {
            "in": scales["in"][i], "out": scales["out"][i],
            "conv": scales["conv"]}
        out, c, sd, am = mamba(p["mixer"],
                               rmsnorm(x, p["ln"]["scale"], d.eps),
                               state["conv"][i], state["ssd"][i], d, q, sc)
        h = h + out
        conv.append(c), ssd.append(sd)
        for k in amax:
            amax[k].append(am[k])
    hf = rmsnorm(h, params["ln_f"]["scale"], d.eps)
    new = {"conv": jnp.stack(conv), "ssd": jnp.stack(ssd), "k": jnp.stack(ks),
           "v": jnp.stack(vs), "t": t + 1}
    amax = {k: jnp.stack(v) for k, v in amax.items()}
    amax.update({g: jnp.stack([a[g] for a in shared_amax]) for g in GRIDS})
    return hf, new, amax


def calibrate(params, tokens, d: Dims):
    """Scales for every quantized stream, from the absmax of a float pass
    over ``tokens [B, T]``: ``in``/``out`` per layer, one ``conv`` scale,
    each shared grid per application, and the head's."""
    B, T = tokens.shape

    def pos(state, tok):
        hf, state, amax = step(params, state, tok, d)
        return state, dict(amax, head_in=jnp.max(jnp.abs(hf)))

    with jax.default_matmul_precision("highest"):
        _, amax = jax.lax.scan(pos, zero_state(d, B, T), tokens.T)
    b = d.act_bits
    out = {"in": scale_from_amax(amax["in"].max(0), b),
           "out": scale_from_amax(amax["out"].max(0), b),
           "conv": scale_from_amax(amax["conv_in"].max(), b),
           "head": scale_from_amax(amax["head_in"].max(), b)}
    out.update({g: scale_from_amax(amax[g].max(0), b) for g in GRIDS})
    return out


def logits(hf, scales, d: Dims, kq, w_scale):
    """Quantized tied head: ``[B, vocab]`` on its exact logit grid."""
    y = matmul(quant(hf, scales["head"], d.act_bits), kq)
    step_ = scales["head"] * w_scale
    return (jnp.round(y / step_) * step_)[:, :d.vocab]


def gaps(params, scales, inputs, targets, d: Dims):
    """Teacher-forced comparison of served tokens (``inputs``/``targets``
    ``[B, T]`` as in ``mamba2_reference.gaps``): ``gap [B, T]``, how far
    the reference's logit of the served token lies below its best, and
    ``step``, the logit grid."""
    B, T = inputs.shape
    kq, w_scale = head_grid(params, d)

    def pos(st, x):
        tok, tgt = x
        hf, st, _ = step(params, st, tok, d, scales)
        lg = logits(hf, scales, d, kq, w_scale)
        got = jnp.take_along_axis(lg, jnp.maximum(tgt, 0)[:, None], 1)[:, 0]
        return st, jnp.where(tgt >= 0, lg.max(-1) - got, 0.0)

    with jax.default_matmul_precision("highest"):
        _, gap = jax.lax.scan(pos, zero_state(d, B, T),
                              (inputs.T, targets.T))
    return gap.T, scales["head"] * w_scale
