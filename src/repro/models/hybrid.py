"""Zamba2 hybrid: a Mamba2 backbone with *shared* transformer blocks.

Structure (Zamba2-7B, arXiv:2411.15242; ``modeling_zamba2``): a stack of
Mamba2 layers.  Each layer ``i`` in ``cfg.hybrid_layer_ids`` (application
``j``) first runs shared block ``j % n_shared_attn_blocks``:

* ``t = RMSNorm_2d(concat(h, e))``, ``e`` the original embedding;
* ``a = o(Attn(RoPE(q(t)), RoPE(k(t)), v(t)))``, scale ``(head_dim/2)^-0.5``,
  one KV cache per application;
* ``m = down(gelu(g) * u)``, ``[g, u] = gate_up(n) + B_j(A_j(n))`` with
  ``n = RMSNorm(a)``: the rank-``adapter_rank`` LoRA adapter of this
  application, exact (erf) gelu;
* ``s = linear_j(m)`` — the block has no residual of its own;

then its Mamba2 mixer takes ``h + s``: ``h <- h + Mamba(RMSNorm(h + s))``.
Every other layer is ``h <- h + Mamba(RMSNorm(h))``.  A final RMSNorm feeds
the tied head.  The mixers' gated norm is by group (``nn.ssm``).

A chip's share of a tensor-parallel deployment is the config's held sizes:
``n_heads`` attention heads, ``d_ff`` MLP columns (``gate_up``'s ``g`` and
``u`` halves and the adapter's ``B`` sliced alike), ``ssm.heads_held``
Mamba heads with their groups' B/C, ``vocab`` rows.  The outputs of ``o``,
``down`` and the mixers' out projection are then this chip's partial sums
(the all-reduce is not in the program); ``linear`` and the adapter's ``A``
are whole.  With nothing cut the same code is the whole model.

Serving (``decode_step``): the slot cache holds each layer's SSM state
beside one KV cache per application.  Each slot's KV cache is a ring of
``max_len`` positions: a request's tokens occupy ``kv_len`` positions from
``kv_start``, and a recycled slot starts its next request where the last
one ended, so the positions of a rolled-back request survive until the
ring wraps (``launch/serve.py`` keeps the cache by length in its
checkpoints).  With a table bundle (:meth:`build_pcilt`) every projection
site is a table fetch: the mixers through ``nn.ssm.mamba_decode``, the
shared blocks' ``q/k/v/o``, ``gate_up``, adapter, ``down`` and ``linear``
through the same stacked GEMV, one stack per block (or per application
for the adapter and ``linear``).  The attention core and the KV cache stay
float32.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.nn.module import ParamSpec
from repro.nn.layers import (Ctx, dense_spec, embed_spec, rmsnorm_spec,
                             rmsnorm, rope)
from repro.nn.ssm import (PROJ_NAMES, _dims, _proj, mamba_spec, mamba_block,
                          mamba_decode, ssm_cache_specs)
from .mamba import build_head_pcilt, head_logits
from .transformer import stack_specs, chunked_ce_loss

__all__ = ["HybridLM", "SHARED_SITES"]

HIGHEST = jax.lax.Precision.HIGHEST

#: the shared blocks' projection sites: ``(params group, key, stack,
#: grid)`` — the tables of ``"block"`` sites are one per shared block, of
#: ``"app"`` sites one per application; ``grid`` names the quantizer that
#: feeds the site (the drift sentinel's grid)
SHARED_SITES = {
    "shared.q": ("shared", "q", "block", "attn_in"),
    "shared.k": ("shared", "k", "block", "attn_in"),
    "shared.v": ("shared", "v", "block", "attn_in"),
    "shared.o": ("shared", "o", "block", "o_in"),
    "shared.gate_up": ("shared", "gate_up", "block", "mlp_in"),
    "shared.adapter_A": ("apps", "adapter_A", "app", "mlp_in"),
    "shared.adapter_B": ("apps", "adapter_B", "app", "ad_in"),
    "shared.down": ("shared", "down", "block", "down_in"),
    "shared.linear": ("apps", "linear", "app", "lin_in"),
}
#: the site of each shared grid whose fetch carries the sentinel's counters
SAT_SITES = {"attn_in": "shared.q", "o_in": "shared.o",
             "mlp_in": "shared.gate_up", "ad_in": "shared.adapter_B",
             "down_in": "shared.down", "lin_in": "shared.linear"}


def _amax(x):
    return jnp.max(jnp.abs(x)).astype(jnp.float32)


@dataclasses.dataclass
class HybridLM:
    cfg: Any

    # -- structure ---------------------------------------------------------

    @property
    def apps(self):
        """The hybrid layers, in order: application ``j`` runs at
        ``apps[j]`` with shared block ``j % n_shared_attn_blocks``."""
        return tuple(self.cfg.hybrid_layer_ids)

    def param_specs(self):
        cfg = self.cfg
        d, F, r, dt = cfg.d_model, cfg.d_ff, cfg.adapter_rank, cfg.param_dtype
        hd = cfg.n_heads * cfg.resolved_head_dim
        block = {"ln": rmsnorm_spec(d, dt), "mixer": mamba_spec(cfg, dt)}
        shared = {
            "ln": rmsnorm_spec(2 * d, dt),
            "q": dense_spec(2 * d, hd, ("embed", "heads"), dtype=dt),
            "k": dense_spec(2 * d, hd, ("embed", "heads"), dtype=dt),
            "v": dense_spec(2 * d, hd, ("embed", "heads"), dtype=dt),
            "o": dense_spec(hd, d, ("heads", "embed"), dtype=dt),
            "ln_mlp": rmsnorm_spec(d, dt),
            "gate_up": dense_spec(d, 2 * F, ("embed", "mlp"), dtype=dt),
            "down": dense_spec(F, d, ("mlp", "embed"), dtype=dt),
        }
        apps = {
            "adapter_A": dense_spec(d, r, ("embed", None), dtype=dt),
            "adapter_B": dense_spec(r, 2 * F, (None, "mlp"), dtype=dt),
            "linear": dense_spec(d, d, ("embed", None), dtype=dt),
        }
        return {
            "embed": embed_spec(cfg.padded_vocab, d, dt),
            "blocks": stack_specs(block, cfg.n_layers),
            "shared": stack_specs(shared, cfg.n_shared_attn_blocks),
            "apps": stack_specs(apps, len(self.apps)),
            "ln_f": rmsnorm_spec(d, dt),
        }

    def cache_specs(self, batch: int, max_len: int):
        """SSM state per layer; per application a KV ring ``[B, max_len,
        heads, head_dim]`` and the rows the last step wrote (what the
        engine's finite gate reads instead of the whole ring); per slot
        the ring's start and length."""
        cfg = self.cfg
        Hh, hd = cfg.n_heads, cfg.resolved_head_dim
        ring = ParamSpec((batch, max_len, Hh, hd),
                         ("batch", None, "heads", None), jnp.float32, "zeros")
        row = ParamSpec((batch, Hh, hd), ("batch", "heads", None),
                        jnp.float32, "zeros")
        idx = ParamSpec((batch,), ("batch",), jnp.int32, "zeros")
        return {
            "layers": ssm_cache_specs(cfg, batch, cfg.n_layers),
            "kv": [{"k": ring, "v": ring} for _ in self.apps],
            "kv_rows": [{"k": row, "v": row} for _ in self.apps],
            "kv_start": idx, "kv_len": idx,
            "pos": ParamSpec((), (), jnp.int32, "zeros"),
        }

    # -- helpers -------------------------------------------------------------

    def _embed(self, params, ctx, tokens):
        x = params["embed"]["embedding"].astype(self.cfg.dtype)[tokens]
        return ctx.constrain(x, "batch", "seq_sp", None)

    def _logits(self, params, x):
        return x @ params["embed"]["embedding"].astype(self.cfg.dtype).T

    def _policy(self):
        return {
            "none": None,
            "dots": jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
            "full": jax.checkpoint_policies.nothing_saveable,
        }[self.cfg.remat_policy]

    def _rope(self, x, positions):
        return rope(x, positions, self.cfg.rope_theta)

    def _sites(self, params, j, pcilt=None, ok=None, with_stats=False):
        """``(site, sp, stats, amax)`` for application ``j``: ``site(name,
        x)`` runs one projection site (its table fetch with a bundle, else
        the dense matmul), filling ``stats`` with the sentinel's counters
        per shared grid and ``amax`` with each grid's absmax (calibration);
        ``sp`` is the weights of the application's shared block."""
        cfg = self.cfg
        b = j % cfg.n_shared_attn_blocks
        group = {"shared": jax.tree.map(lambda a: a[b], params["shared"]),
                 "apps": jax.tree.map(lambda a: a[j], params["apps"])}
        proj = None if pcilt is None else pcilt.get("proj")
        stats, amax = {}, {}

        def site(name, x):
            grp, key, stack, grid = SHARED_SITES[name]
            p = None
            if proj is not None and name in proj["tables"]:
                i = b if stack == "block" else j
                p = {"tables": proj["tables"], "spec": proj["spec"],
                     "group": proj["group"], "path": proj["path"],
                     "mesh": proj["mesh"], "mesh_axis": proj["mesh_axis"],
                     "layer": i, "scale": {name: proj["scales"][name][i]},
                     "ok": ok}
            counted = with_stats and SAT_SITES[grid] == name
            amax.setdefault(grid, _amax(x))
            out = _proj({name: group[grp][key]}, name, x, cfg, p,
                        with_stats=counted)
            if counted:
                out, count, ratio = out
                stats[grid] = {"count": count, "ratio": ratio}
            return out

        return site, group["shared"], stats, amax

    def _attention(self, site, t, attend, positions):
        """The attention sublayer on ``t`` (the normed concat): the
        ``o`` projection of the heads held, and what ``attend`` keeps."""
        cfg = self.cfg
        B, T = t.shape[:2]
        Hh, hd = cfg.n_heads, cfg.resolved_head_dim
        q = self._rope(site("shared.q", t).reshape(B, T, Hh, hd), positions)
        k = self._rope(site("shared.k", t).reshape(B, T, Hh, hd), positions)
        v = site("shared.v", t).reshape(B, T, Hh, hd)
        o, kv = attend(q, k, v)
        return site("shared.o", o.reshape(B, T, Hh * hd).astype(cfg.dtype)), kv

    def _mlp(self, site, sp, a):
        """The MLP sublayer with this application's adapter: ``down`` of
        the columns held."""
        n = rmsnorm(sp["ln_mlp"], a, self.cfg.norm_eps)
        with jax.named_scope("mlp"):
            gu = site("shared.gate_up", n)
        with jax.named_scope("adapter"):
            gu = gu + site("shared.adapter_B", site("shared.adapter_A", n))
        with jax.named_scope("mlp"):
            g, u = jnp.split(gu, 2, axis=-1)
            return site("shared.down", jax.nn.gelu(g, approximate=False) * u)

    def _shared(self, params, j, h, x0, attend, positions, pcilt=None,
                ok=None, with_stats=False):
        """Application ``j`` of its shared block on ``h [B, T, d]``:
        returns ``(s, kv, stats, amax)`` — ``s = linear_j(m)``, what
        ``attend`` keeps, the sentinel's counters per shared grid, and each
        grid's absmax (calibration)."""
        site, sp, stats, amax = self._sites(params, j, pcilt, ok, with_stats)
        t = rmsnorm(sp["ln"], jnp.concatenate([h, x0], -1),
                    self.cfg.norm_eps)
        with jax.named_scope("attn"):
            a, kv = self._attention(site, t, attend, positions)
        m = self._mlp(site, sp, a)
        with jax.named_scope("linear"):
            s = site("shared.linear", m)
        return s, kv, stats, amax

    def _scale(self):
        return (self.cfg.resolved_head_dim / 2) ** -0.5

    def _attend_seq(self, q, k, v):
        """Causal attention over the whole sequence; ``q, k, v [B, T, H,
        hd]``."""
        T = q.shape[1]
        s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                       k.astype(jnp.float32), precision=HIGHEST) * self._scale()
        s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32),
                       precision=HIGHEST)
        return o, (k, v)

    def _attend_step(self, ring, widx, valid, q, k, v):
        """One position per slot: write ``k, v`` into the slot's ring at
        ``widx`` and attend over its ``valid`` positions."""
        B = q.shape[0]
        rows = jnp.arange(B)
        kr = ring["k"].at[rows, widx].set(k[:, 0].astype(ring["k"].dtype))
        vr = ring["v"].at[rows, widx].set(v[:, 0].astype(ring["v"].dtype))
        s = jnp.einsum("bhd,bthd->bht", q[:, 0].astype(jnp.float32), kr,
                       precision=HIGHEST) * self._scale()
        s = jnp.where(valid[:, None, :], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bht,bthd->bhd", p, vr, precision=HIGHEST)
        return o[:, None], ({"k": kr, "v": vr},
                            {"k": k[:, 0].astype(jnp.float32),
                             "v": v[:, 0].astype(jnp.float32)})

    # -- full sequences ------------------------------------------------------

    def _mamba_seq(self, params, ctx, h, lo, hi, extra=None, policy=None):
        """Layers ``[lo, hi)`` over the sequence: returns ``(h, states,
        amax)`` with each layer's decode-ready state and the absmax of
        every stream the table decode quantizes."""
        cfg = self.cfg
        seg = jax.tree.map(lambda a: jax.lax.slice_in_dim(a, lo, hi),
                           params["blocks"])

        def body(h, p):
            xn = rmsnorm(p["ln"], h if extra is None else h + extra,
                         cfg.norm_eps)
            y, st, calib = mamba_block(p["mixer"], cfg, ctx, xn,
                                       return_state=True, return_calib=True)
            return h + y, (st, {"in": _amax(xn), "out": calib["wo_in"],
                                "conv_in": calib["conv_in"]})

        if policy is not None:
            body = jax.checkpoint(body, policy=policy)
        h, (states, amax) = jax.lax.scan(body, h, seg)
        return h, states, amax

    def _sequence(self, params, tokens, ctx, policy=None):
        """The whole stack over ``tokens [B, S]``: ``(h, states, kv,
        amax)`` — the last hidden state before ``ln_f``, every layer's
        state, each application's ``(k, v)``, and the absmaxes."""
        cfg = self.cfg
        B, S = tokens.shape
        x0 = self._embed(params, ctx, tokens)
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None],
                                     (B, S))
        h, states, kvs, amax, shared_amax = x0, [], [], [], []
        lo = 0
        for j, hid in enumerate(self.apps):
            if hid > lo:
                h, st, am = self._mamba_seq(params, ctx, h, lo, hid,
                                            policy=policy)
                states.append(st), amax.append(am)
            def shared(h, x0, j=j):
                s, kv, _, am = self._shared(params, j, h, x0,
                                            self._attend_seq, positions)
                return s, kv, am

            if policy is not None:  # shared blocks sit outside the scans
                shared = jax.checkpoint(shared, policy=policy)
            s, kv, am_s = shared(h, x0)
            kvs.append(kv), shared_amax.append(am_s)
            h, st, am = self._mamba_seq(params, ctx, h, hid, hid + 1, s,
                                        policy=policy)
            states.append(st), amax.append(am)
            lo = hid + 1
        if cfg.n_layers > lo:
            h, st, am = self._mamba_seq(params, ctx, h, lo, cfg.n_layers,
                                        policy=policy)
            states.append(st), amax.append(am)

        def cat(*xs):
            return jnp.concatenate(xs, axis=0)

        amax = jax.tree.map(cat, *amax)
        if shared_amax:
            amax.update(jax.tree.map(lambda *xs: jnp.stack(xs),
                                     *shared_amax))
        return h, jax.tree.map(cat, *states), kvs, amax

    def loss(self, params, batch, ctx: Ctx):
        cfg = self.cfg
        tokens, labels = batch["tokens"], batch["labels"]
        h, _, _, _ = self._sequence(params, tokens, ctx, self._policy())
        x = rmsnorm(params["ln_f"], h, cfg.norm_eps)
        mask = batch.get("loss_mask", jnp.ones_like(labels, jnp.float32))
        ce, z = chunked_ce_loss(lambda xc: self._logits(params, xc), x, labels,
                                mask.astype(jnp.float32), cfg.loss_chunk)
        return ce + 1e-4 * z, {"ce": ce, "z": z}

    def forward(self, params, tokens, ctx: Ctx):
        """Logits ``[B, S, padded_vocab]`` of every position."""
        h, _, _, _ = self._sequence(params, tokens, ctx)
        return self._logits(params, rmsnorm(params["ln_f"], h,
                                            self.cfg.norm_eps))

    def prefill(self, params, batch, ctx: Ctx, max_len: Optional[int] = None):
        """Full-sequence pass emitting the decode cache: every layer's SSM
        state and each application's KV ring (``max_len`` positions,
        default the prompt's) holding the prompt from position 0."""
        cfg = self.cfg
        tokens = batch["tokens"]
        B, S = tokens.shape
        T = max_len or S
        h, states, kvs, _ = self._sequence(params, tokens, ctx)
        x = rmsnorm(params["ln_f"], h, cfg.norm_eps)
        logits = self._logits(params, x[:, -1])
        pad = ((0, 0), (0, T - S), (0, 0), (0, 0))
        kv = [{"k": jnp.pad(k.astype(jnp.float32), pad),
               "v": jnp.pad(v.astype(jnp.float32), pad)} for k, v in kvs]
        rows = [{"k": k[:, -1].astype(jnp.float32),
                 "v": v[:, -1].astype(jnp.float32)} for k, v in kvs]
        cache = {"layers": states, "kv": kv, "kv_rows": rows,
                 "kv_start": jnp.zeros((B,), jnp.int32),
                 "kv_len": jnp.full((B,), S, jnp.int32),
                 "pos": jnp.asarray(S, jnp.int32)}
        return logits, cache

    def calibrate_pcilt(self, params, batch, ctx: Ctx):
        """Calibration prefill: the absmax of every stream the table decode
        quantizes — ``in``/``out`` per layer, ``conv_in`` over all layers,
        each shared grid per application, and the head's input."""
        cfg = self.cfg
        h, _, _, amax = self._sequence(params, batch["tokens"], ctx)
        amax = dict(amax, conv_in=jnp.max(amax["conv_in"]))
        amax["head_in"] = _amax(rmsnorm(params["ln_f"], h, cfg.norm_eps))
        return amax

    # -- the table sites -----------------------------------------------------

    @property
    def n_units(self) -> int:
        """Units the health monitor checks and demotes: the layers, then
        the shared-block applications."""
        return self.cfg.n_layers + len(self.apps)

    def pcilt_sites(self):
        """``{site: (stack, grid)}`` of every projection the tables serve:
        the mixers' (one table per layer) and the shared blocks'."""
        sites = {n: ("layer", "out" if n == "wo" else "in")
                 for n in PROJ_NAMES}
        sites.update({n: (stack, grid)
                      for n, (_, _, stack, grid) in SHARED_SITES.items()})
        return sites

    def site_kernels(self, params):
        """``{site: [stack, n, O]}`` dense kernels of every site."""
        out = {n: params["blocks"]["mixer"][n]["kernel"] for n in PROJ_NAMES}
        out.update({n: params[grp][key]["kernel"]
                    for n, (grp, key, _, _) in SHARED_SITES.items()})
        return out

    def site_index(self, stack: str, unit: int):
        """Index into a ``stack`` of the tables a unit uses (None: none)."""
        L = self.cfg.n_layers
        if stack == "layer":
            return unit if unit < L else None
        if unit < L:
            return None
        j = unit - L
        return j % self.cfg.n_shared_attn_blocks if stack == "block" else j

    def unit_label(self, unit: int):
        L = self.cfg.n_layers
        return int(unit) if unit < L else f"shared.{unit - L}"

    def sat_grids(self):
        """``{grid: (first unit, units, saturable elements per row)}``."""
        cfg = self.cfg
        d_inner, _, conv_ch = _dims(cfg)
        L, A, d = cfg.n_layers, len(self.apps), cfg.d_model
        return {"in": (0, L, d), "conv": (0, L, cfg.ssm.conv_kernel * conv_ch),
                "out": (0, L, d_inner),
                "attn_in": (L, A, 2 * d),
                "o_in": (L, A, cfg.n_heads * cfg.resolved_head_dim),
                "mlp_in": (L, A, d), "ad_in": (L, A, cfg.adapter_rank),
                "down_in": (L, A, cfg.d_ff), "lin_in": (L, A, d)}

    def _site_scales(self, name, proj_scales):
        """A site's activation scale per stack entry: a shared block's
        tables serve all its applications, so they take the widest."""
        stack, grid = self.pcilt_sites()[name]
        s = jnp.asarray(proj_scales[grid], jnp.float32)
        if stack != "block":
            return s
        nb = self.cfg.n_shared_attn_blocks
        return jnp.stack([jnp.max(s[b::nb]) for b in range(nb)])

    def build_pcilt(self, params, scale, proj_scales=None, proj_path="fused",
                    projections=None, mesh=None, mesh_axis="model",
                    table_dtype=jnp.float32, head_scale=None,
                    head_weight_bits=4, paired=False):
        """Offline table build (``MambaLM.build_pcilt``'s contract): the
        mixers' conv tables ``[L, C, V]``; with ``proj_scales`` (per grid:
        ``in``/``out`` per layer, each shared grid per application) one
        stacked ``[S, G, V, O]`` table per site of :meth:`pcilt_sites`;
        with ``head_scale`` the shared-pool head; and the integrity
        record."""
        from repro.core import QuantSpec
        from repro.core.lut_layers import build_dwconv_tables
        from repro.core.serving import build_site_tables, pcilt_integrity

        cfg = self.cfg
        if cfg.pcilt is None:
            raise ValueError(
                "HybridLM.build_pcilt requires cfg.pcilt (a configs.base."
                "PCILTConfig supplying act_bits/group for the table build)")
        if paired:
            raise ValueError("paired tables serve the Mamba decode only")
        spec = QuantSpec(bits=cfg.pcilt.act_bits, symmetric=True)
        tables = jax.vmap(lambda w: build_dwconv_tables(w, spec, scale))(
            params["blocks"]["mixer"]["conv_w"])  # [L, C, V]
        out = {"tables": tables, "scale": scale, "spec": spec}
        if proj_scales is not None:
            names = [n for n in self.pcilt_sites()
                     if projections is None or n in projections]
            kernels = self.site_kernels(params)
            scales = {n: self._site_scales(n, proj_scales) for n in names}
            out["proj"] = {
                "tables": build_site_tables(
                    {n: kernels[n] for n in names}, scales, spec,
                    cfg.pcilt.group, table_dtype=table_dtype, mesh=mesh,
                    mesh_axis=mesh_axis),
                "scales": scales, "spec": spec, "group": cfg.pcilt.group,
                "path": proj_path, "mesh": mesh, "mesh_axis": mesh_axis,
                "paired": False}
        if head_scale is not None:
            out["head"] = build_head_pcilt(cfg, params, head_scale,
                                           head_weight_bits)
        out["integrity"] = pcilt_integrity(out)
        return out

    # -- decode --------------------------------------------------------------

    def _mamba_steps(self, params, ctx, layers, pcilt, ok, h, lo, hi,
                     extra=None, with_stats=False):
        """Layers ``[lo, hi)`` for one token, scanned over the layer index
        (tables and weights stay whole and are indexed in place)."""
        cfg = self.cfg
        proj = None if pcilt is None else pcilt.get("proj")
        mesh = None if proj is None else proj.get("mesh")

        def body(carry, l):
            h, layers = carry
            p = jax.tree.map(lambda a: a[l], params["blocks"])
            st = jax.tree.map(lambda a: a[l], layers)
            pc = None
            if pcilt is not None:
                pc = {"tables": pcilt["tables"][l], "scale": pcilt["scale"],
                      "spec": pcilt["spec"], "mesh": mesh}
                if ok is not None:
                    pc["ok"] = ok[l]
                if proj is not None:
                    pc["proj"] = {
                        "tables": proj["tables"], "spec": proj["spec"],
                        "group": proj["group"], "path": proj["path"],
                        "mesh": proj["mesh"], "mesh_axis": proj["mesh_axis"],
                        "layer": l,
                        "scale": {n: proj["scales"][n][l] for n in PROJ_NAMES
                                  if n in proj["scales"]},
                        "ok": None if ok is None else ok[l]}
            x_in = h if extra is None else h + extra
            res = mamba_decode(p["mixer"], cfg, ctx,
                               rmsnorm(p["ln"], x_in, cfg.norm_eps), st,
                               pcilt=pc, with_stats=with_stats)
            layers = jax.tree.map(
                lambda full, new: full.at[l].set(new.astype(full.dtype)),
                layers, res[1])
            return (h + res[0], layers), (res[2] if with_stats else None)

        (h, layers), sat = jax.lax.scan(body, (h, layers),
                                        jnp.arange(lo, hi, dtype=jnp.int32))
        return h, layers, sat

    def decode_step(self, params, cache, tokens, ctx: Ctx, pcilt=None,
                    layer_ok=None, head_ok=None, with_stats: bool = False):
        """One decode step over the slot cache (``MambaLM.decode_step``'s
        contract).  ``layer_ok`` (``[n_units]``) demotes a layer's mixer or
        an application's shared-block sites to their dense fake-quant
        oracles; ``with_stats`` adds the sentinel's counters per grid of
        :meth:`sat_grids`.  Device scopes: ``embed``, ``blocks`` (the
        mixers), ``shared`` (``attn``, ``mlp``, ``adapter``, ``linear``),
        ``head``."""
        cfg = self.cfg
        if pcilt is None and (layer_ok is not None or head_ok is not None):
            raise ValueError(
                "layer_ok/head_ok demote PCILT fetches to their dense "
                "oracles — they require a pcilt bundle (got pcilt=None)")
        if with_stats and pcilt is None:
            raise ValueError(
                "with_stats reports the PCILT quantizers' saturation — it "
                "requires a pcilt bundle (got pcilt=None)")
        L = cfg.n_layers
        ok = None if layer_ok is None else jnp.asarray(layer_ok, bool)
        with jax.named_scope("embed"):
            x0 = self._embed(params, ctx, tokens)
        start, ln = cache["kv_start"], cache["kv_len"]
        positions = ln[:, None]
        h, layers, sats, shared_sats = x0, cache["layers"], [], []
        kv, rows = [], []
        lo = 0
        for j, hid in enumerate(self.apps):
            if hid > lo:
                with jax.named_scope("blocks"):
                    h, layers, sat = self._mamba_steps(
                        params, ctx, layers, pcilt, ok, h, lo, hid,
                        with_stats=with_stats)
                sats.append(sat)
            ring = cache["kv"][j]
            T = ring["k"].shape[1]
            widx = (start + ln) % T
            valid = (jnp.arange(T)[None] - start[:, None]) % T <= ln[:, None]
            with jax.named_scope("shared"):
                s, (ring, row), st, _ = self._shared(
                    params, j, h, x0,
                    functools.partial(self._attend_step, ring, widx, valid),
                    positions, pcilt, None if ok is None else ok[L + j],
                    with_stats)
            kv.append(ring), rows.append(row), shared_sats.append(st)
            with jax.named_scope("blocks"):
                h, layers, sat = self._mamba_steps(
                    params, ctx, layers, pcilt, ok, h, hid, hid + 1, s,
                    with_stats=with_stats)
            sats.append(sat)
            lo = hid + 1
        if L > lo:
            with jax.named_scope("blocks"):
                h, layers, sat = self._mamba_steps(
                    params, ctx, layers, pcilt, ok, h, lo, L,
                    with_stats=with_stats)
            sats.append(sat)
        x = rmsnorm(params["ln_f"], h, cfg.norm_eps)
        head = None if pcilt is None else pcilt.get("head")
        with jax.named_scope("head"):
            if head is None:
                logits = self._logits(params, x)[:, -1]
            else:
                proj = pcilt.get("proj")
                logits = head_logits(cfg, head, x[:, -1], head_ok,
                                     None if proj is None else proj["mesh"])
        new_cache = dict(cache, layers=layers, kv=kv, kv_rows=rows,
                         kv_len=ln + 1, pos=cache["pos"] + 1)
        if not with_stats:
            return logits, new_cache
        sat = jax.tree.map(lambda *xs: jnp.concatenate(xs), *sats)
        if shared_sats:
            sat.update(jax.tree.map(lambda *xs: jnp.stack(xs), *shared_sats))
        return logits, new_cache, sat
