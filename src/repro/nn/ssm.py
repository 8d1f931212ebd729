"""Mamba2 (state-space duality) blocks: chunked train/prefill + O(1) decode.

The SSD algorithm (Dao & Gu, 2024) splits the sequence into chunks: an
intra-chunk quadratic term (masked ``C Bᵀ`` attention-like matmuls — MXU
food), plus an inter-chunk state recurrence carried by ``lax.scan``.  Decode
keeps a constant-size ``(conv_state, ssd_state)`` instead of a KV cache —
which is exactly why the ``long_500k`` cell is runnable for the SSM/hybrid
architectures (DESIGN.md §7).

Sharding: projections are tensor-parallel on the inner channel dim ("mlp"
rule); the SSD interior shards over heads when the head count divides the TP
degree (zamba2: 112 heads ✓) and falls back to replicated SSD compute for
tiny models (mamba2-130m: 24 heads — noted in the roofline analysis).

PCILT integration (paper §6): the depthwise conv1d frontend is the paper's
small-filter/large-signal sweet spot; with ``cfg.pcilt`` set, serving builds
per-layer ``[C, V]`` tables once (``build_pcilt_conv`` /
``MambaLM.build_pcilt``) and both prefill and decode route the conv through
the **fused** PCILT pipeline (``core.lut_layers.pcilt_depthwise_conv1d``
``path="fused"``): quantize, causal tap-stack, offset-pack, and the
one-fetch-per-output lookup all run in VMEM — the decode step's offsets
never exist in HBM.  Tables are plain arrays, so they scan over the layer
axis exactly like parameters.

Full-PCILT decode (PR 5): the decode *projections* — ``wz``/``wx``/``wB``/
``wC``/``wdt`` on the block input and ``wo`` on the gated output — also
execute as table fetches.  The per-layer ``[G, V, O]`` grouped tables of
each projection stack into one layer-resident ``[L, G, V, O]`` array
(``MambaLM.build_pcilt(proj_scales=...)`` /
``core.serving.convert_mamba_decode``); the decode scan carries only the
integer layer index and that layer's calibrated activation scale, and
:func:`_proj` dispatches ``core.lut_layers.pcilt_linear(stacked=layer)`` —
the scalar-prefetch stacked kernel stages the layer's tiles straight out of
the resident stack, so a decode step's matmuls become fetches end to end.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .layers import Ctx, dense_spec, dense, rmsnorm_spec, rmsnorm
from .module import ParamSpec

__all__ = ["mamba_spec", "mamba_block", "mamba_decode", "ssm_cache_specs",
           "build_pcilt_conv", "PROJ_NAMES"]

#: The decode projections a full-PCILT conversion replaces with table
#: fetches: the five block-input projections plus the output projection.
PROJ_NAMES = ("wz", "wx", "wB", "wC", "wdt", "wo")


def build_pcilt_conv(params, cfg, scale):
    """Offline PCILT build for one layer's conv frontend: ``conv_w [k, C]``
    -> per-channel tables ``[C, 2**(act_bits*k)]`` (requires ``cfg.pcilt``).

    ``scale`` is the calibrated per-tensor activation scale of the conv
    input (the pre-activation ``xBC`` stream).  The returned dict is what
    ``mamba_block`` / ``mamba_decode`` accept as ``pcilt=``; stack the
    tables over layers to scan them (``models.mamba.MambaLM.build_pcilt``).
    """
    from repro.core import QuantSpec, build_dwconv_tables

    if cfg.pcilt is None:
        raise ValueError(
            "build_pcilt_conv requires cfg.pcilt (a configs.base.PCILTConfig "
            "supplying act_bits/group for the table build); got None — set "
            "cfg = dataclasses.replace(cfg, pcilt=PCILTConfig(...)) before "
            "converting, or run the conv dense with pcilt=None")
    # the conv input (xBC) is a pre-activation stream — signed, so the
    # grid must straddle zero (symmetric), unlike post-ReLU CNN codes
    spec = QuantSpec(bits=cfg.pcilt.act_bits, symmetric=True)
    tables = build_dwconv_tables(params["conv_w"], spec, scale)
    return {"tables": tables, "scale": scale, "spec": spec}


def _proj(params, name, x, cfg, proj, with_stats: bool = False):
    """One decode projection: PCILT stacked fetch, host-packed baseline, the
    fake-quant dense reference, or the plain dense matmul.

    ``proj`` is the per-layer slice of the full-PCILT bundle (see
    ``models.mamba.MambaLM.decode_step``): the stacked ``[L, G, V, O]``
    tables per projection (closure-resident, *not* scanned), this layer's
    index and calibrated per-tensor scales (both scan-carried), the shared
    ``QuantSpec``/``group``, and the dispatch ``path`` — ``"fused"`` (the
    scalar-prefetch stacked kernel), a host-packed reference path
    (``"kernel"``/``"gather"``/``"onehot"``: slices the layer's table, the
    copy the stacked kernel avoids — the benchmark baseline), or
    ``"dense_fq"`` (dense matmul on fake-quantized input: the parity oracle
    the table fetch must equal, since the fetch is exact on the quantized
    grid).

    Resilience: when the bundle carries a per-layer health bit
    (``proj["ok"]``, a traced bool from ``decode_step(layer_ok=...)``), the
    fetch runs under ``lax.cond`` against the dense fake-quant oracle — a
    layer whose tables failed their integrity/health check is demoted to the
    oracle branch without retracing (the bit is a runtime argument, not a
    closure constant), and the request keeps being served correctly.

    Drift sentinel: ``with_stats=True`` returns ``(out, count, ratio)`` —
    the saturation statistics of the quantizer feeding this projection
    (``core.quantization.quantize_with_stats`` semantics).  The fused
    stacked fetch reduces the counters inside the kernel grid; the *oracle*
    branch computes the identical stats host-side on the same input, so a
    demoted layer keeps reporting drift (the monitor can observe recovery /
    recalibrate while the layer serves from the oracle) and both
    ``lax.cond`` branches return matching pytrees.
    """
    if proj is None or name not in proj["tables"]:
        out = dense(params[name], x, cfg.dtype)
        if with_stats:  # dense projections never saturate a quantizer
            return out, jnp.zeros((), jnp.int32), jnp.zeros((), jnp.float32)
        return out
    from repro.core import fake_quant, pcilt_linear, quantize_with_stats

    scale = proj["scale"][name]
    path = proj.get("path", "fused")

    def _oracle(xx):
        # full precision, like the table build: the oracle is exact on the
        # quantized grid only if the contraction does not round to bf16
        xq = fake_quant(xx.astype(jnp.float32), proj["spec"], scale)
        out = dense(params[name], xq, jnp.float32,
                    precision=jax.lax.Precision.HIGHEST).astype(cfg.dtype)
        if with_stats:
            _, count, ratio = quantize_with_stats(xx, proj["spec"], scale)
            return out, count, ratio
        return out

    if path == "dense_fq":
        return _oracle(x)

    def _fetch(xx):
        tables = proj["tables"][name]
        paired = bool(proj.get("paired"))
        # Covered reduction width: dense stacks are [L, G, V, O] (G on axis
        # 1, width G*group); paired stacks are seg-major [G2, L, V2, O]
        # (pairs on axis 0, width G2*2*group — phantom slot included).
        want = (tables.shape[0] * 2 * proj["group"] if paired
                else tables.shape[1] * proj["group"])
        pad = want - xx.shape[-1]
        if pad:  # group-alignment slots: table rows built from zero weights
            xx = jnp.concatenate(
                [xx, jnp.zeros((*xx.shape[:-1], pad), xx.dtype)], axis=-1)
        out = pcilt_linear(xx, tables, proj["spec"], scale, proj["group"],
                           path=path, stacked=proj["layer"],
                           mesh=proj.get("mesh"),
                           mesh_axis=proj.get("mesh_axis", "model"),
                           paired=paired, return_stats=with_stats)
        if with_stats:
            out, count, ratio = out
            return out.astype(cfg.dtype), count, ratio
        return out.astype(cfg.dtype)

    ok = proj.get("ok")
    if ok is None:
        return _fetch(x)
    return jax.lax.cond(ok, _fetch, _oracle, x)


def _dims(cfg):
    s = cfg.ssm
    d_inner = s.expand * cfg.d_model
    n_heads = d_inner // s.head_dim
    conv_ch = d_inner + 2 * s.n_groups * s.d_state
    return d_inner, n_heads, conv_ch


def mamba_spec(cfg, dtype=jnp.float32):
    s = cfg.ssm
    d = cfg.d_model
    d_inner, H, conv_ch = _dims(cfg)
    GN = s.n_groups * s.d_state
    return {
        "wz": dense_spec(d, d_inner, ("embed", "mlp"), dtype=dtype),
        "wx": dense_spec(d, d_inner, ("embed", "mlp"), dtype=dtype),
        "wB": dense_spec(d, GN, ("embed", None), dtype=dtype),
        "wC": dense_spec(d, GN, ("embed", None), dtype=dtype),
        "wdt": dense_spec(d, H, ("embed", None), dtype=dtype),
        "conv_w": ParamSpec((s.conv_kernel, conv_ch), (None, "mlp"), dtype, "fan_in"),
        "conv_b": ParamSpec((conv_ch,), ("mlp",), dtype, "zeros"),
        "A_log": ParamSpec((H,), (None,), dtype, "zeros"),
        "dt_bias": ParamSpec((H,), (None,), dtype, "zeros"),
        "D": ParamSpec((H,), (None,), dtype, "ones"),
        "norm": rmsnorm_spec(d_inner, dtype),
        "wo": dense_spec(d_inner, d, ("mlp", "embed"), dtype=dtype),
    }


def _conv1d(params, cfg, x, conv_state=None, pcilt=None,
            with_stats: bool = False):
    """Causal depthwise conv over [B, T, C]; returns (y, new_state).

    With ``pcilt`` set (see :func:`build_pcilt_conv`) the tap-dot is a PCILT
    fetch through the fused Pallas pipeline: decode evaluates the assembled
    ``[B, k, C]`` window as a VALID conv (one fetch per channel), full
    sequences run the CAUSAL fused kernel over the whole signal.

    ``with_stats=True`` appends the quantizer's saturation ``(count,
    ratio)`` to the return tuple (``quantize_with_stats`` semantics over
    the conv input; the demoted oracle branch computes the identical stats
    host-side so a demoted layer keeps reporting drift).
    """
    k = cfg.ssm.conv_kernel
    w = params["conv_w"].astype(x.dtype)  # [k, C]
    zero_stats = (jnp.zeros((), jnp.int32), jnp.zeros((), jnp.float32))
    if conv_state is not None:  # decode: state [B, k-1, C]
        window = jnp.concatenate([conv_state.astype(x.dtype), x], axis=1)  # [B,k,C]
        if pcilt is not None:
            from repro.core import (fake_quant, pcilt_depthwise_conv1d,
                                    quantize_with_stats)
            from repro.core.lut_layers import replicated_on

            def _fetch(win):
                out = replicated_on(
                    pcilt.get("mesh"),
                    lambda w, f, s, t: pcilt_depthwise_conv1d(
                        w, f, pcilt["spec"], s, tables=t, path="fused",
                        padding="VALID", return_stats=with_stats),
                )(win, params["conv_w"], pcilt["scale"],
                  pcilt["tables"])  # [B, 1, C]
                if with_stats:
                    out, count, ratio = out
                    return out.astype(x.dtype), count, ratio
                return out.astype(x.dtype)

            def _oracle(win):
                wq = fake_quant(win.astype(jnp.float32), pcilt["spec"],
                                pcilt["scale"])
                out = jnp.einsum(
                    "bkc,kc->bc", wq, params["conv_w"].astype(jnp.float32),
                    precision=jax.lax.Precision.HIGHEST,
                )[:, None].astype(x.dtype)
                if with_stats:
                    _, count, ratio = quantize_with_stats(
                        win, pcilt["spec"], pcilt["scale"])
                    return out, count, ratio
                return out

            ok = pcilt.get("ok")
            win = window[:, -k:]
            y = _fetch(win) if ok is None else jax.lax.cond(
                ok, _fetch, _oracle, win)
            if with_stats:
                y, count, ratio = y
        else:
            y = jnp.einsum("bkc,kc->bc", window[:, -k:], w)[:, None]
            count, ratio = zero_stats
        new_state = window[:, -(k - 1):]
        y = y + params["conv_b"].astype(x.dtype)
        if with_stats:
            return y, new_state, count, ratio
        return y, new_state
    if pcilt is not None:
        from repro.core import pcilt_depthwise_conv1d

        y = pcilt_depthwise_conv1d(
            x, params["conv_w"], pcilt["spec"], pcilt["scale"],
            tables=pcilt["tables"], path="fused", padding="CAUSAL",
            return_stats=with_stats)
        if with_stats:
            y, count, ratio = y
            return (y.astype(x.dtype) + params["conv_b"].astype(x.dtype),
                    None, count, ratio)
        return y.astype(x.dtype) + params["conv_b"].astype(x.dtype), None
    pad = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    y = sum(pad[:, i : i + x.shape[1]] * w[i][None, None] for i in range(k))
    y = y + params["conv_b"].astype(x.dtype)
    if with_stats:
        return y, None, *zero_stats
    return y, None


def _ssd_chunked(xh, dt, A, Bm, Cm, chunk: int):
    """SSD over full sequences — mixed precision.

    xh [B,T,H,P]; dt [B,T,H] (post-softplus, fp32); A [H] (negative);
    Bm, Cm [B,T,H,N] (already repeated across the head group).
    Returns y [B,T,H,P] (bf16) and the final state [B,H,N,P] (fp32).

    Precision policy: the O(T)-sized operands (xh, B, C, xdt, decay-scaled
    variants) stay bf16 — they dominate residency in the backward pass —
    while the numerically-sensitive pieces (log-decay cumsums, inter-chunk
    state recurrence, matmul accumulation via preferred_element_type) run
    fp32.
    """
    f32 = jnp.float32
    cd = jnp.bfloat16
    Bsz, T, H, P = xh.shape
    N = Bm.shape[-1]
    Q = min(chunk, T)
    while T % Q:
        Q -= 1
    C_ = T // Q

    def r(t):  # [B,T,...] -> [B,C,Q,...]
        return t.reshape(Bsz, C_, Q, *t.shape[2:])

    xh, dt, Bm, Cm = r(xh.astype(cd)), r(dt.astype(f32)), r(Bm.astype(cd)), r(Cm.astype(cd))
    a = dt * A[None, None, None]                      # [B,C,Q,H] log-decay f32
    cum = jnp.cumsum(a, axis=2)                       # within-chunk cumsum
    # decay from j to i (i >= j): exp(cum_i - cum_j)
    li = cum[..., :, None, :]                         # [B,C,Q,1,H] at i
    lj = cum[..., None, :, :]                         # [B,C,1,Q,H] at j
    mask = jnp.tril(jnp.ones((Q, Q), bool))[None, None, :, :, None]
    L = jnp.where(mask, jnp.exp(li - lj), 0.0)        # [B,C,Q,Q,H] f32 (chunk-local)

    xdt = (xh * dt[..., None].astype(cd)).astype(cd)  # [B,C,Q,H,P] bf16
    scores = jnp.einsum("bcihn,bcjhn->bcijh", Cm, Bm,
                        preferred_element_type=f32) * L
    y_intra = jnp.einsum("bcijh,bcjhp->bcihp", scores.astype(cd), xdt,
                         preferred_element_type=f32)

    # chunk-final states: S_c = sum_j exp(cum_last - cum_j) * B_j ⊗ xdt_j
    decay_to_end = jnp.exp(cum[:, :, -1:, :] - cum)   # [B,C,Q,H] f32
    Bd = (Bm * decay_to_end[..., None].astype(cd)).astype(cd)
    S = jnp.einsum("bcjhn,bcjhp->bchnp", Bd, xdt, preferred_element_type=f32)
    chunk_decay = jnp.exp(jnp.sum(a, axis=2))         # [B,C,H] f32

    def step(h, inp):
        s_c, g_c = inp  # [B,H,N,P] f32, [B,H] f32
        h_new = h * g_c[..., None, None] + s_c
        return h_new, h.astype(cd)  # emit state *entering* the chunk

    S_t = jnp.moveaxis(S, 1, 0)                       # [C,B,H,N,P] f32
    g_t = jnp.moveaxis(chunk_decay, 1, 0)             # [C,B,H]
    h_final, h_enter = jax.lax.scan(
        step, jnp.zeros((Bsz, H, N, P), f32), (S_t, g_t)
    )
    h_enter = jnp.moveaxis(h_enter, 0, 1)             # [B,C,H,N,P] bf16

    Ce = (Cm * jnp.exp(cum)[..., None].astype(cd)).astype(cd)
    y_inter = jnp.einsum("bcihn,bchnp->bcihp", Ce, h_enter,
                         preferred_element_type=f32)
    y = (y_intra + y_inter).astype(cd).reshape(Bsz, T, H, P)
    return y, h_final


def _split_heads(cfg, ctx, x_in, B_in, C_in, dt_in):
    s = cfg.ssm
    d_inner, H, _ = _dims(cfg)
    Bsz, T = x_in.shape[:2]
    xh = x_in.reshape(Bsz, T, H, s.head_dim)
    xh = ctx.constrain(xh, "batch", None, "ssm_heads", None)
    rep = H // s.n_groups
    Bm = jnp.repeat(B_in.reshape(Bsz, T, s.n_groups, s.d_state), rep, axis=2)
    Cm = jnp.repeat(C_in.reshape(Bsz, T, s.n_groups, s.d_state), rep, axis=2)
    Bm = ctx.constrain(Bm, "batch", None, "ssm_heads", None)
    Cm = ctx.constrain(Cm, "batch", None, "ssm_heads", None)
    return xh, Bm, Cm


def _finish(params, cfg, ctx, y, xh, z, proj=None, return_inner=False,
            with_stats: bool = False):
    d_inner, H, _ = _dims(cfg)
    Bsz, T = y.shape[:2]
    y = y + params["D"].astype(y.dtype)[None, None, :, None] * xh
    y = y.reshape(Bsz, T, d_inner)
    y = y * jax.nn.silu(z.astype(y.dtype))
    y = rmsnorm(params["norm"], y, cfg.norm_eps)
    out = _proj(params, "wo", y, cfg, proj, with_stats=with_stats)
    if with_stats:
        out, count, ratio = out
    out = ctx.constrain(out, "batch", "seq_sp", None)
    if with_stats:
        return out, count, ratio
    if return_inner:  # the wo input — what projection calibration observes
        return out, y
    return out


def mamba_block(params, cfg, ctx: Ctx, x: jax.Array,
                return_state: bool = False, pcilt=None,
                return_calib: bool = False):
    """Full-sequence Mamba2 block (train / prefill).  x [B,T,d] -> [B,T,d].

    ``return_state=True`` additionally emits the decode-ready
    ``{"conv", "ssd"}`` state at the final position (prefill).  ``pcilt``
    (from :func:`build_pcilt_conv`) routes the conv frontend through the
    fused PCILT pipeline.  ``return_calib=True`` additionally emits the
    absmax of the internally-produced PCILT'd activations — the conv input
    (pre-activation ``xBC``) and the ``wo`` input (post-norm gated ``y``) —
    for projection/conv scale calibration
    (``models.mamba.MambaLM.calibrate_pcilt``)."""
    s = cfg.ssm
    d_inner, H, _ = _dims(cfg)
    z = dense(params["wz"], x, cfg.dtype)
    xi = dense(params["wx"], x, cfg.dtype)
    Bi = dense(params["wB"], x, cfg.dtype)
    Ci = dense(params["wC"], x, cfg.dtype)
    # dt projection in bf16 (fp32 here would materialize a full-width fp32
    # copy of x per layer); softplus/decay math upcasts the tiny [B,T,H]
    dt = dense(params["wdt"], x, cfg.dtype).astype(jnp.float32)
    xi = ctx.constrain(xi, "batch", None, "mlp")

    xBC = jnp.concatenate([xi, Bi, Ci], axis=-1)
    conv_tail = xBC[:, -(s.conv_kernel - 1):]  # pre-activation window
    conv_in_amax = jnp.max(jnp.abs(xBC)).astype(jnp.float32) \
        if return_calib else None
    xBC, _ = _conv1d(params, cfg, xBC, pcilt=pcilt)
    xBC = jax.nn.silu(xBC)
    xi, Bi, Ci = jnp.split(
        xBC, [d_inner, d_inner + s.n_groups * s.d_state], axis=-1
    )

    dt = jax.nn.softplus(dt + params["dt_bias"].astype(jnp.float32))
    A = -jnp.exp(params["A_log"].astype(jnp.float32))
    xh, Bm, Cm = _split_heads(cfg, ctx, xi, Bi, Ci, dt)
    y, h_final = _ssd_chunked(xh, dt, A, Bm, Cm, s.chunk)
    out = _finish(params, cfg, ctx, y.astype(cfg.dtype), xh, z,
                  return_inner=return_calib)
    results = []
    if return_calib:
        out, wo_in = out
        results.append({"conv_in": conv_in_amax,
                        "wo_in": jnp.max(jnp.abs(wo_in)).astype(jnp.float32)})
    if return_state:
        results.insert(0, {"conv": conv_tail.astype(jnp.float32),
                           "ssd": h_final.astype(jnp.float32)})
    if results:
        return (out, *results)
    return out


def mamba_decode(
    params, cfg, ctx: Ctx, x: jax.Array, state: Dict, pcilt=None,
    with_stats: bool = False
):
    """One-token step.  x [B,1,d]; state {conv [B,k-1,C], ssd [B,H,N,P]}.

    ``pcilt`` (from :func:`build_pcilt_conv`) replaces the conv frontend's
    tap-dot with one fused PCILT fetch per channel; a ``pcilt["proj"]``
    bundle (``MambaLM.build_pcilt(proj_scales=...)``) additionally routes
    every projection through the layer-stacked fused PCILT GEMV via
    :func:`_proj` — the decode step is then fetch-bound end to end.

    ``with_stats=True`` additionally returns the layer's saturation
    statistics ``{"in"|"conv"|"out": {"count", "ratio"}}`` — one entry per
    *distinct* quantizer the step runs: ``wz``/``wx``/``wB``/``wC``/``wdt``
    all quantize the same block input at the same ``"in"`` scale, so ``wx``
    stands in for the whole input grid; ``"conv"`` is the conv-frontend
    window; ``"out"`` is the post-norm gated ``wo`` input.  ``out`` and the
    new state are bit-identical to the ``with_stats=False`` step."""
    s = cfg.ssm
    d_inner, H, _ = _dims(cfg)
    proj = None if pcilt is None else pcilt.get("proj")
    stats = {}
    with jax.named_scope("in_proj"):
        z = _proj(params, "wz", x, cfg, proj)
        xi = _proj(params, "wx", x, cfg, proj, with_stats=with_stats)
        if with_stats:
            xi, count, ratio = xi
            stats["in"] = {"count": count, "ratio": ratio}
        Bi = _proj(params, "wB", x, cfg, proj)
        Ci = _proj(params, "wC", x, cfg, proj)
        dt = _proj(params, "wdt", x, cfg, proj).astype(jnp.float32)

    with jax.named_scope("conv"):
        xBC = jnp.concatenate([xi, Bi, Ci], axis=-1)
        conv = _conv1d(params, cfg, xBC, state["conv"], pcilt=pcilt,
                       with_stats=with_stats)
        if with_stats:
            xBC, conv_state, count, ratio = conv
            stats["conv"] = {"count": count, "ratio": ratio}
        else:
            xBC, conv_state = conv
        xBC = jax.nn.silu(xBC)
        xi, Bi, Ci = jnp.split(
            xBC, [d_inner, d_inner + s.n_groups * s.d_state], axis=-1
        )

    with jax.named_scope("ssd"):
        dt = jax.nn.softplus(dt + params["dt_bias"].astype(jnp.float32))[:, 0]  # [B,H]
        A = -jnp.exp(params["A_log"].astype(jnp.float32))
        xh, Bm, Cm = _split_heads(cfg, ctx, xi, Bi, Ci, dt)
        xh1, Bm1, Cm1 = xh[:, 0].astype(jnp.float32), Bm[:, 0].astype(jnp.float32), Cm[:, 0].astype(jnp.float32)

        dA = jnp.exp(dt * A[None])                        # [B,H]
        h = state["ssd"].astype(jnp.float32)
        h = h * dA[..., None, None] + jnp.einsum(
            "bhn,bhp->bhnp", Bm1 * dt[..., None], xh1
        )
        y = jnp.einsum("bhn,bhnp->bhp", Cm1, h)[:, None]  # [B,1,H,P]
    with jax.named_scope("out_proj"):
        out = _finish(params, cfg, ctx, y.astype(cfg.dtype), xh, z, proj=proj,
                      with_stats=with_stats)
    new_state = {"conv": conv_state.astype(state["conv"].dtype),
                 "ssd": h.astype(state["ssd"].dtype)}
    if with_stats:
        out, count, ratio = out
        stats["out"] = {"count": count, "ratio": ratio}
        return out, new_state, stats
    return out, new_state


def ssm_cache_specs(cfg, batch: int, n_layers: int, layer_axis: bool = True):
    s = cfg.ssm
    d_inner, H, conv_ch = _dims(cfg)
    conv = (batch, s.conv_kernel - 1, conv_ch)
    ssd = (batch, H, s.d_state, s.head_dim)
    conv_axes = ("batch", None, "mlp")
    ssd_axes = ("batch", "ssm_heads", None, None)
    if layer_axis:
        conv, ssd = (n_layers, *conv), (n_layers, *ssd)
        conv_axes, ssd_axes = ("layers", *conv_axes), ("layers", *ssd_axes)
    return {
        "conv": ParamSpec(conv, conv_axes, jnp.float32, "zeros"),
        "ssd": ParamSpec(ssd, ssd_axes, jnp.float32, "zeros"),
    }
