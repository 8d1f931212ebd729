"""Mamba2 language model (attention-free) — the [ssm] architecture.

Scanned Mamba2 blocks with pre-norm residuals.  Decode carries constant-size
(conv, ssd) states — no KV cache — so the ``long_500k`` cell costs the same
memory as ``decode`` at any context length.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.nn.module import ParamSpec
from repro.nn.layers import Ctx, dense, embed_spec, rmsnorm_spec, rmsnorm
from repro.nn.ssm import mamba_spec, mamba_block, mamba_decode, ssm_cache_specs
from .transformer import stack_specs, chunked_ce_loss

__all__ = ["MambaLM"]


@dataclasses.dataclass
class MambaLM:
    cfg: Any

    def param_specs(self):
        cfg = self.cfg
        block = {"ln": rmsnorm_spec(cfg.d_model, cfg.param_dtype),
                 "mixer": mamba_spec(cfg, cfg.param_dtype)}
        p = {
            "embed": embed_spec(cfg.padded_vocab, cfg.d_model, cfg.param_dtype),
            "blocks": stack_specs(block, cfg.n_layers),
            "ln_f": rmsnorm_spec(cfg.d_model, cfg.param_dtype),
        }
        if not cfg.tie_embeddings:
            p["lm_head"] = {
                "kernel": ParamSpec((cfg.d_model, cfg.padded_vocab), ("embed", "vocab"),
                                    cfg.param_dtype, "fan_in")
            }
        return p

    def cache_specs(self, batch: int, max_len: int):
        return {"layers": ssm_cache_specs(self.cfg, batch, self.cfg.n_layers),
                "pos": ParamSpec((), (), jnp.int32, "zeros")}

    def _logits(self, params, x):
        cfg = self.cfg
        if cfg.tie_embeddings:
            return x @ params["embed"]["embedding"].astype(cfg.dtype).T
        return dense(params["lm_head"], x, cfg.dtype)

    def _embed(self, params, ctx, tokens):
        cfg = self.cfg
        x = params["embed"]["embedding"].astype(cfg.dtype)[tokens]
        return ctx.constrain(x, "batch", "seq_sp", None)

    def _policy(self):
        return {
            "none": None,
            "dots": jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
            "full": jax.checkpoint_policies.nothing_saveable,
        }[self.cfg.remat_policy]

    def loss(self, params, batch, ctx: Ctx):
        cfg = self.cfg
        tokens, labels = batch["tokens"], batch["labels"]
        x = self._embed(params, ctx, tokens)
        policy = self._policy()

        def blk(x, p):
            return x + mamba_block(p["mixer"], cfg, ctx,
                                   rmsnorm(p["ln"], x, cfg.norm_eps))

        if policy is not None:
            blk = jax.checkpoint(blk, policy=policy)

        x, _ = jax.lax.scan(lambda h, p: (blk(h, p), ()), x, params["blocks"])
        x = rmsnorm(params["ln_f"], x, cfg.norm_eps)
        mask = batch.get("loss_mask", jnp.ones_like(labels, jnp.float32))
        ce, z = chunked_ce_loss(lambda xc: self._logits(params, xc), x, labels,
                                mask.astype(jnp.float32), cfg.loss_chunk)
        return ce + 1e-4 * z, {"ce": ce, "z": z}

    def prefill(self, params, batch, ctx: Ctx):
        """Full-sequence pass emitting final (conv, ssd) states per layer —
        the decode-ready cache (constant-size regardless of prompt length)."""
        cfg = self.cfg
        tokens = batch["tokens"]
        B, S = tokens.shape
        x = self._embed(params, ctx, tokens)

        def body(h, p):
            y, st = mamba_block(p["mixer"], cfg, ctx,
                                rmsnorm(p["ln"], h, cfg.norm_eps),
                                return_state=True)
            return h + y, st

        x, states = jax.lax.scan(body, x, params["blocks"])
        x = rmsnorm(params["ln_f"], x, cfg.norm_eps)
        logits = self._logits(params, x[:, -1:])[:, 0]
        return logits, {"layers": states, "pos": jnp.asarray(S, jnp.int32)}

    def build_pcilt(self, params, scale, proj_scales=None, proj_path="fused",
                    projections=None, mesh=None, mesh_axis="model",
                    table_dtype=jnp.float32, head_scale=None,
                    head_weight_bits=4, paired=False):
        """Offline PCILT build for the decode hot loop (requires
        ``cfg.pcilt``).

        Conv frontend: per-layer ``[C, V]`` tables stacked to ``[L, C, V]``
        so they ride the decode scan exactly like parameters; ``scale`` is
        the calibrated per-tensor activation scale of the conv input.

        Projections (full-PCILT decode): pass ``proj_scales`` — per-layer
        calibrated absmax-derived scales ``{"in": [L], "out": [L]}`` (see
        :meth:`calibrate_pcilt` / ``core.serving.convert_mamba_decode``) —
        and every projection in ``projections`` (default: all six,
        ``nn.ssm.PROJ_NAMES``) gains a layer-stacked ``[L, G, V, O]``
        grouped-table array.  The stack is **closure-resident** in
        :meth:`decode_step` (never sliced by the scan — the stacked kernel's
        scalar-prefetch staging reads it in place); with ``mesh=`` it is
        placed with the segment axis sharded over ``mesh_axis`` (the
        ``"table_seg"`` rule, ``seg_axis=1``) so each device holds
        ``[L, G/D, V, O]`` and every projection costs one psum per step.
        ``proj_path`` selects the execution route (``"fused"`` stacked
        kernel; ``"kernel"``/``"gather"``/``"onehot"`` host-packed
        references; ``"dense_fq"`` fake-quant dense oracle).

        With ``paired=True`` the projection stacks are built in the
        TL1-style multi-scalar layout instead: **segment-major**
        ``[G2, L, V2, O]`` paired tables
        (``core.pcilt.build_paired_stacked_tables`` — each fetch covers two
        adjacent segments, halving fetch count and adder-tree depth) and
        decode dispatches the paired row-gather kernels.  Under a mesh the
        *pair* axis shards (``seg_axis=0``).  The conv frontend and logits
        head are unchanged.

        Logits head: pass ``head_scale`` (calibrated absmax-derived scale of
        the ``ln_f`` output — ``calibrate_pcilt``'s ``head_in``) and the
        tied-embedding / ``lm_head`` kernel is fake-quantized to
        ``head_weight_bits`` and converted to a **shared-pool** (ext.-3)
        PCILT (``pool [X, V, O]`` + ``seg_idx [G]``), executed by
        :meth:`_head_logits` on the ``"shared"`` dispatch path.

        The returned bundle carries an ``"integrity"`` record — per-layer
        checksums of every table array, computed on the device
        (``core.serving.pcilt_integrity``): one-word changes and bursts of
        <= 32 bits always caught, other changes missed with probability
        about 2**-32 — verified at executor load and on demand by the
        serving health monitor, which reads the device bytes themselves.
        """
        from repro.core import QuantSpec
        from repro.core.lut_layers import build_dwconv_tables

        cfg = self.cfg
        if cfg.pcilt is None:
            raise ValueError(
                "MambaLM.build_pcilt requires cfg.pcilt (a configs.base."
                "PCILTConfig supplying act_bits/group for the table build); "
                "got None — set cfg = dataclasses.replace(cfg, "
                "pcilt=PCILTConfig(...)) before converting, or decode dense "
                "with pcilt=None")
        # the conv input (xBC) is a pre-activation stream — signed, so the
        # grid must straddle zero (symmetric), unlike post-ReLU CNN codes
        spec = QuantSpec(bits=cfg.pcilt.act_bits, symmetric=True)
        tables = jax.vmap(
            lambda w: build_dwconv_tables(w, spec, scale)
        )(params["blocks"]["mixer"]["conv_w"])  # [L, C, V]
        out = {"tables": tables, "scale": scale, "spec": spec}
        if proj_scales is not None:
            out["proj"] = self._build_proj_pcilt(
                params, spec, proj_scales, proj_path, projections, mesh,
                mesh_axis, table_dtype, paired)
        if head_scale is not None:
            out["head"] = self._build_head_pcilt(
                params, head_scale, head_weight_bits)
        from repro.core.serving import pcilt_integrity

        out["integrity"] = pcilt_integrity(out)
        return out

    def _build_head_pcilt(self, params, head_scale, head_weight_bits):
        """Shared-pool (ext.-3) PCILT over the weight-quantized logits head.

        Weight fake-quantization to ``head_weight_bits`` gives the kernel a
        low segment cardinality, so the ``[G, V, O]`` grouped tables dedupe
        into a ``pool [X, V, O]`` + ``seg_idx [G]`` pointer vector; the
        quantized kernel itself rides along as the exact dense oracle the
        demoted path evaluates (``fetch(x) == fake_quant(x) @ kernel_q`` on
        the activation grid — zero-padded alignment rows contribute 0).
        Every logit is an integer multiple of ``step = act scale x weight
        scale``; both paths snap to that grid (:meth:`_head_logits`).
        """
        from repro.core import (QuantSpec, build_shared_grouped_tables,
                                fake_quant, scale_from_amax)

        cfg = self.cfg
        group = cfg.pcilt.group
        if cfg.tie_embeddings:
            k = params["embed"]["embedding"].astype(jnp.float32).T  # [d, Vp]
        else:
            k = params["lm_head"]["kernel"].astype(jnp.float32)
        wspec = QuantSpec(bits=head_weight_bits, symmetric=True)
        w_scale = scale_from_amax(jnp.max(jnp.abs(k)), wspec)
        kq = fake_quant(k, wspec, w_scale)
        n = kq.shape[0]
        pad = (-n) % group
        kp = jnp.concatenate(
            [kq, jnp.zeros((pad, kq.shape[1]), kq.dtype)], 0) if pad else kq
        spec = QuantSpec(bits=cfg.pcilt.act_bits, symmetric=True)
        shared = build_shared_grouped_tables(
            kp, spec, head_scale, group)
        return {"pool": shared.pool, "seg_idx": shared.seg_idx,
                "group": group, "spec": spec,
                "scale": jnp.asarray(head_scale, jnp.float32),
                "step": jnp.asarray(head_scale * w_scale, jnp.float32),
                "kernel_q": kq, "n": n + pad}

    def _head_logits(self, head, x, ok=None, mesh=None):
        """Last-position logits through the shared-pool PCILT head.

        ``x [B, d]`` -> ``[B, padded_vocab]``.  ``ok`` (traced bool) demotes
        the fetch to the exact fake-quant dense oracle under ``lax.cond`` —
        the response to a corrupted pool entry or re-aimed ``seg_idx``
        pointer.  ``mesh`` (the projections' table mesh) runs the fetch
        kernel replicated on each of its devices.

        Quantized activations times quantized weights put every logit on the
        grid ``k * step``, and many logits tie exactly.  Both paths snap
        their f32 sums back to the grid, so the fetch and the oracle return
        the same bits and break ties (greedy argmax) the same way — their
        f32 summation orders differ by far less than half a step."""
        from repro.core import fake_quant, pcilt_linear
        from repro.core.lut_layers import replicated_on
        from repro.core.pcilt import SharedGroupedTables

        cfg = self.cfg

        def shared_fetch(xx, pool, seg_idx, scale):
            shared = SharedGroupedTables(pool=pool, seg_idx=seg_idx,
                                         group=head["group"])
            return pcilt_linear(xx.astype(jnp.float32), shared, head["spec"],
                                scale, head["group"], path="shared")

        def _fetch(xx):
            pad = head["n"] - xx.shape[-1]
            if pad:  # group-alignment slots (zero weights -> zero tables)
                xx = jnp.concatenate(
                    [xx, jnp.zeros((*xx.shape[:-1], pad), xx.dtype)], -1)
            return snap(replicated_on(mesh, shared_fetch)(
                xx, head["pool"], head["seg_idx"], head["scale"]))

        def _oracle(xx):
            xq = fake_quant(xx.astype(jnp.float32), head["spec"],
                            head["scale"])
            return snap(jnp.dot(xq, head["kernel_q"],
                                precision=jax.lax.Precision.HIGHEST))

        def snap(y):
            step = head["step"]
            return (jnp.round(y / step) * step).astype(cfg.dtype)

        if ok is None:
            return _fetch(x)
        return jax.lax.cond(jnp.asarray(ok, bool), _fetch, _oracle, x)

    def _build_proj_pcilt(self, params, spec, proj_scales, proj_path,
                          projections, mesh, mesh_axis, table_dtype,
                          paired=False):
        """Stacked grouped tables per decode projection: dense
        ``[L, G, V, O]`` or, with ``paired``, seg-major ``[G2, L, V2, O]``
        paired stacks (``build_paired_stacked_tables``)."""
        from repro.core import build_grouped_tables
        from repro.core.pcilt import build_paired_stacked_tables
        from repro.core.lut_layers import mesh_shard_count
        from repro.nn.ssm import PROJ_NAMES

        cfg = self.cfg
        group = cfg.pcilt.group
        tabs, scales = {}, {}
        for name in (projections or PROJ_NAMES):
            ks = params["blocks"]["mixer"][name]["kernel"]  # [L, n, O]
            s_l = jnp.asarray(
                proj_scales["out" if name == "wo" else "in"], jnp.float32)
            _, n, O = ks.shape
            pad_n = (-n) % group

            if paired:
                # build_paired_stacked_tables pads n to the pair width
                # itself (alignment + phantom slots from zero weights) and
                # returns the seg-major [G2, L, V2, O] layout; building in
                # f32 and casting once keeps bf16 tables rounding-safe.
                t = build_paired_stacked_tables(
                    ks.astype(jnp.float32), spec, s_l, group
                ).astype(table_dtype)
                seg_count, seg_axis = t.shape[0], 0
            else:
                def build(w, s):
                    wf = w.astype(jnp.float32)
                    if pad_n:  # group-alignment slots from zero weights
                        wf = jnp.concatenate(
                            [wf, jnp.zeros((pad_n, wf.shape[-1]), wf.dtype)],
                            0)
                    return build_grouped_tables(wf, spec, s, group)

                t = jax.vmap(build)(ks, s_l).astype(table_dtype)
                seg_count, seg_axis = t.shape[1], 1
            if mesh is not None and mesh_shard_count(
                    mesh, mesh_axis, seg_count) > 1:
                from repro.nn.module import pcilt_table_sharding

                t = jax.device_put(t, pcilt_table_sharding(
                    mesh, seg_count, ndim=4, mesh_axis=mesh_axis,
                    seg_axis=seg_axis))
            tabs[name] = t
            scales[name] = s_l
        return {"tables": tabs, "scales": scales, "spec": spec,
                "group": group, "path": proj_path, "mesh": mesh,
                "mesh_axis": mesh_axis, "paired": paired}

    def calibrate_pcilt(self, params, batch, ctx: Ctx):
        """Calibration prefill: one full-sequence pass over a calibration
        batch capturing the per-layer absmax of every activation the PCILT
        decode quantizes — the in-projection input (the post-``ln`` block
        input feeding ``wz``/``wx``/``wB``/``wC``/``wdt``), the ``wo``
        input (post-norm gated ``y``), and the conv input (pre-activation
        ``xBC``).  Returns ``{"in": [L], "out": [L], "conv_in": []}``
        absmax arrays; ``core.serving.convert_mamba_decode`` turns them
        into quantization scales."""
        cfg = self.cfg
        x = self._embed(params, ctx, batch["tokens"])

        def body(h, p):
            xn = rmsnorm(p["ln"], h, cfg.norm_eps)
            y, calib = mamba_block(p["mixer"], cfg, ctx, xn,
                                   return_calib=True)
            stats = {"in": jnp.max(jnp.abs(xn)).astype(jnp.float32),
                     "out": calib["wo_in"], "conv_in": calib["conv_in"]}
            return h + y, stats

        h, stats = jax.lax.scan(body, x, params["blocks"])
        head_in = jnp.max(
            jnp.abs(rmsnorm(params["ln_f"], h, cfg.norm_eps))
        ).astype(jnp.float32)
        return {"in": stats["in"], "out": stats["out"],
                "conv_in": jnp.max(stats["conv_in"]), "head_in": head_in}

    def decode_step(self, params, cache, tokens, ctx: Ctx, pcilt=None,
                    layer_ok=None, head_ok=None, with_stats: bool = False):
        """One decode step.  ``pcilt`` (from :meth:`build_pcilt`) routes every
        layer's conv frontend through the fused PCILT fetch; with a
        ``pcilt["proj"]`` bundle the projections execute as layer-stacked
        table fetches too — the stacked ``[L, G, V, O]`` tables stay
        closure-resident while only the integer layer index and that layer's
        calibration scales ride the scan.

        Resilience masks: ``layer_ok`` (``[L]`` bool) and ``head_ok`` (bool)
        demote individual layers' fetches (conv + projections) or the PCILT
        logits head to their exact dense fake-quant oracles under
        ``lax.cond``.  They are runtime *arguments* — flipping a bit never
        retraces — and an all-True mask executes the identical fetch
        computation, so healthy serving is bitwise-unchanged.

        Drift sentinel: ``with_stats=True`` returns a third value — the
        per-layer saturation statistics of every distinct quantizer,
        ``{"in"|"conv"|"out": {"count" [L] i32, "ratio" [L] f32}}``
        (see :func:`repro.nn.ssm.mamba_decode`), stacked by the layer scan.
        Logits and the cache are bit-identical either way; the counters ride
        the fetch kernels' own grids, so the monitored step adds no second
        pass over any activation."""
        cfg = self.cfg
        if pcilt is None and (layer_ok is not None or head_ok is not None):
            raise ValueError(
                "layer_ok/head_ok demote PCILT fetches to their dense "
                "oracles — they require a pcilt bundle (got pcilt=None)")
        if with_stats and pcilt is None:
            raise ValueError(
                "with_stats reports the PCILT quantizers' saturation — it "
                "requires a pcilt bundle (got pcilt=None)")
        pos = cache["pos"]
        with jax.named_scope("embed"):
            x = self._embed(params, ctx, tokens)
        proj = None if pcilt is None else pcilt.get("proj")
        # the projections' table mesh: every other kernel of the step runs
        # replicated on it (core.lut_layers.replicated_on)
        mesh = None if proj is None else proj.get("mesh")

        def body(h, inp):
            p, st = inp[0], inp[1]
            per = inp[3] if len(inp) > 3 else {}
            pc = None
            if pcilt is not None:
                pc = {"tables": inp[2], "scale": pcilt["scale"],
                      "spec": pcilt["spec"], "mesh": mesh}
                if "ok" in per:
                    pc["ok"] = per["ok"]
                if proj is not None:
                    pc["proj"] = {
                        "tables": proj["tables"],  # full stack, not scanned
                        "spec": proj["spec"], "group": proj["group"],
                        "path": proj["path"], "mesh": proj["mesh"],
                        "mesh_axis": proj["mesh_axis"],
                        "layer": per["layer"], "scale": per["scale"],
                        "paired": proj.get("paired", False),
                        "ok": per.get("ok")}
            res = mamba_decode(p["mixer"], cfg, ctx,
                               rmsnorm(p["ln"], h, cfg.norm_eps), st,
                               pcilt=pc, with_stats=with_stats)
            if with_stats:
                y, st2, sat = res
                return h + y, (st2, sat)
            y, st2 = res
            return h + y, st2

        xs = (params["blocks"], cache["layers"])
        if pcilt is not None:
            xs = xs + (pcilt["tables"],)
            per = {}
            if proj is not None:
                per["layer"] = jnp.arange(cfg.n_layers, dtype=jnp.int32)
                per["scale"] = proj["scales"]
            if layer_ok is not None:
                per["ok"] = jnp.asarray(layer_ok, bool)
            if per:
                xs = xs + (per,)
        # device scopes (op_name metadata, so a trace names each op's part
        # of the step): embed, blocks (in_proj, conv, ssd, out_proj inside
        # each layer), head
        with jax.named_scope("blocks"):
            x, ys = jax.lax.scan(body, x, xs)
        new_states, sat = ys if with_stats else (ys, None)
        x = rmsnorm(params["ln_f"], x, cfg.norm_eps)
        head = None if pcilt is None else pcilt.get("head")
        with jax.named_scope("head"):
            if head is None:
                logits = self._logits(params, x)[:, -1]
            else:
                logits = self._head_logits(head, x[:, -1], head_ok, mesh)
        new_cache = dict(cache, layers=new_states, pos=pos + 1)
        if with_stats:
            return logits, new_cache, sat
        return logits, new_cache
