"""The Zamba2 hybrid (``models/hybrid.py``) against its plain reference
(``bench/families/zamba2_reference.py``) at a CPU size, on seeded random
weights: the dense forward, prefill then decode through the slot cache,
the table decode under the reference's quantization (float32 tables pass
the family's limit, bfloat16 tables exceed it), and the tie between a
tensor-parallel share and the uncut model."""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import model as bench_model
from bench.families import zamba2 as fam
from bench.families import zamba2_reference as ref
from repro.models import build_model
from repro.nn.layers import Ctx
from repro.nn.module import materialize

TINY = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "bench", "tests", "data", "zamba2-tiny.json")
CTX = Ctx()
B, S = 2, 10


@pytest.fixture(scope="module")
def setup():
    """The tiny configuration (2 groups, 2 of 8 Mamba heads held, 2 shared
    blocks at 2 hybrid layers), its program model and weights at fan-in
    scale, so every layer moves the residual stream as at full width."""
    conf = bench_model.load_config(TINY)
    cfg = fam.program_config(conf)
    m = build_model(cfg)
    params = materialize(m.param_specs(), jax.random.PRNGKey(7))
    tokens = jax.random.randint(jax.random.PRNGKey(8), (B, S), 0,
                                conf["vocab_size"])
    return conf, cfg, m, params, tokens


def reference_logits(conf, params, tokens):
    """The reference's float pass, unquantized: ``[B, S, vocab]``."""
    d = ref.dims(conf)

    def pos(st, tok):
        hf, st, _ = ref.step(params, st, tok, d)
        return st, hf @ params["embed"]["embedding"].T

    with jax.default_matmul_precision("highest"):
        _, lg = jax.lax.scan(pos, ref.zero_state(d, tokens.shape[0],
                                                 tokens.shape[1]), tokens.T)
    return np.asarray(jnp.moveaxis(lg, 0, 1))


def test_dense_forward_matches_the_reference(setup):
    """The full-sequence pass (its SSD chunks in bfloat16, as the program
    computes them) agrees with the reference's float32 recurrence."""
    conf, cfg, m, params, tokens = setup
    got = np.asarray(jax.jit(lambda p, t: m.forward(p, t, CTX))(
        params, tokens))
    want = reference_logits(conf, params, tokens)
    scale = np.abs(want).max()
    np.testing.assert_allclose(got, want, atol=2e-2 * scale, rtol=0)


def test_prefill_then_decode_matches_the_reference(setup):
    """Prefill half the sequence, then decode the rest through the slot
    cache (SSM state and KV ring): every position's logits match the
    reference's full pass."""
    conf, cfg, m, params, tokens = setup
    P = S // 2
    lg, cache = m.prefill(params, {"tokens": tokens[:, :P]}, CTX, max_len=32)
    got = [lg]
    step = jax.jit(lambda c, t: m.decode_step(params, c, t, CTX))
    for i in range(P, S):
        lg, cache = step(cache, tokens[:, i:i + 1])
        got.append(lg)
    got = np.stack([np.asarray(g) for g in got], 1)
    want = reference_logits(conf, params, tokens)[:, P - 1:]
    assert int(cache["kv_len"][0]) == S
    np.testing.assert_allclose(got, want, atol=2e-2 * np.abs(want).max(),
                               rtol=0)


def served_mismatch(conf, m, params, table_dtype, steps=40):
    """Greedy table decode of seeded prompts, then the reference's
    teacher-forced gaps at the served tokens: the share below its best."""
    scales = fam.calibrate(conf, params, 5)
    bundle = m.build_pcilt(
        params, scales["conv"], proj_scales={g: scales[g] for g in fam.GRIDS},
        table_dtype=jnp.dtype(table_dtype), head_scale=scales["head"],
        head_weight_bits=conf["pcilt"]["head_weight_bits"])
    bundle.pop("integrity")
    R, P = 4, 6
    prompt = jax.random.randint(jax.random.PRNGKey(3), (R, P), 0,
                                conf["vocab_size"])
    cache = materialize(m.cache_specs(R, 64), jax.random.PRNGKey(0))
    step = jax.jit(lambda c, t: m.decode_step(params, c, t, CTX,
                                              pcilt=bundle))
    seq, served = [prompt[:, i] for i in range(P)], []
    for i in range(P + steps - 1):
        lg, cache = step(cache, seq[i][:, None])
        if i >= P - 1:
            served.append(jnp.argmax(lg[:, :conf["vocab_size"]], -1))
            if i + 1 == len(seq):
                seq.append(served[-1])
    inputs = jnp.stack(seq[:P + steps - 1], 1)
    targets = np.full(inputs.shape, -1)
    targets[:, P - 1:] = np.stack(served, 1)
    gap, grid = fam.gaps(conf, params, scales, inputs, jnp.asarray(targets))
    gap = np.asarray(gap)[targets >= 0]
    return float((np.rint(gap / float(grid)) > 0).mean())


@pytest.mark.parametrize("table_dtype", ["float32", "bfloat16"])
def test_table_decode_against_the_reference(setup, table_dtype):
    """Every projection site served from tables (the mixers', the shared
    block's q/k/v/o, gate_up, adapter, down, linear, and the head) matches
    the reference under the same quantization; tables held in bfloat16
    must exceed the family's limit."""
    conf, cfg, m, params, _ = setup
    share = served_mismatch(conf, m, params, table_dtype)
    limit = fam.LIMITS["mismatch_share"]
    if table_dtype == "float32":
        assert share <= limit
    else:
        assert share > limit


# -- the tensor-parallel share -------------------------------------------------


def share_cfg(cfg, shards, mamba_shards=1):
    ssm = dataclasses.replace(
        cfg.ssm, heads_held=cfg.ssm.expand * cfg.d_model
        // cfg.ssm.head_dim // mamba_shards)
    return dataclasses.replace(cfg, n_heads=cfg.n_heads // shards,
                               n_kv_heads=cfg.n_kv_heads // shards,
                               d_ff=cfg.d_ff // shards, ssm=ssm)


def cols(a, s, n, axis=-1):
    w = a.shape[axis] // n
    return jax.lax.slice_in_dim(a, s * w, (s + 1) * w, axis=axis)


def gate_cols(a, s, n):
    """Columns of share ``s`` of a ``[g | u]`` matrix: its slice of each
    half."""
    g, u = jnp.split(a, 2, axis=-1)
    return jnp.concatenate([cols(g, s, n), cols(u, s, n)], -1)


@pytest.fixture(scope="module")
def uncut():
    """The smoke structure uncut (4 attention heads, 8 Mamba heads in 2
    groups, 128 MLP columns), float32."""
    from repro.configs import get_smoke_config

    cfg = dataclasses.replace(get_smoke_config("zamba2-7b"),
                              dtype=jnp.float32)
    m = build_model(cfg)
    return cfg, m, materialize(m.param_specs(), jax.random.PRNGKey(11))


def test_attention_shares_add_up_to_the_uncut_sublayer(uncut):
    cfg, m, params = uncut
    n = 4  # one head a share
    h = jax.random.normal(jax.random.PRNGKey(1), (2, 6, 2 * cfg.d_model))
    pos = jnp.broadcast_to(jnp.arange(6)[None], (2, 6))

    def attn(model, p):
        site, sp, _, _ = model._sites(p, 0)
        return model._attention(site, h, model._attend_seq, pos)[0]

    whole = attn(m, params)
    part = build_model(share_cfg(cfg, n))
    total = 0
    for s in range(n):
        p = dict(params, shared=dict(params["shared"], **{
            k: {"kernel": cols(params["shared"][k]["kernel"], s, n)}
            for k in ("q", "k", "v")}, o={
            "kernel": cols(params["shared"]["o"]["kernel"], s, n, 1)}))
        total = total + attn(part, p)
    np.testing.assert_allclose(total, whole, atol=1e-4, rtol=1e-4)


def test_mlp_and_adapter_shares_add_up_to_the_uncut_sublayer(uncut):
    cfg, m, params = uncut
    n = 4  # a quarter of the columns a share; the adapter's A whole
    a = jax.random.normal(jax.random.PRNGKey(2), (2, 6, cfg.d_model))

    def mlp(model, p):
        site, sp, _, _ = model._sites(p, 1)
        return model._mlp(site, sp, a)

    whole = mlp(m, params)
    part = build_model(share_cfg(cfg, n))
    total = 0
    for s in range(n):
        sh, ap = params["shared"], params["apps"]
        p = dict(params, shared=dict(
            sh, gate_up={"kernel": gate_cols(sh["gate_up"]["kernel"], s, n)},
            down={"kernel": cols(sh["down"]["kernel"], s, n, 1)}),
            apps=dict(ap, adapter_B={
                "kernel": gate_cols(ap["adapter_B"]["kernel"], s, n)}))
        total = total + mlp(part, p)
    np.testing.assert_allclose(total, whole, atol=1e-4, rtol=1e-4)


def test_mamba_shares_add_up_to_the_uncut_mixer(uncut):
    """Two shares of one whole group each, so each share's gated-norm
    statistic is its whole group's (at four shares a chip holds half a
    group and takes the other half's sum of squares from its neighbour)."""
    from repro.nn.ssm import mamba_block

    cfg, m, params = uncut
    n = 2
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 8, cfg.d_model))
    mixer = jax.tree.map(lambda a: a[0], params["blocks"]["mixer"])
    whole = mamba_block(mixer, cfg, CTX, x)
    pcfg = share_cfg(cfg, 1, mamba_shards=n)
    di, N = cfg.ssm.expand * cfg.d_model, cfg.ssm.d_state

    def channels(a, s, axis=-1):  # x channels, then this group's B and C
        xs = cols(jax.lax.slice_in_dim(a, 0, di, axis=axis), s, n, axis)
        b = jax.lax.slice_in_dim(a, di + s * N, di + (s + 1) * N, axis=axis)
        c = jax.lax.slice_in_dim(a, di + n * N + s * N,
                                 di + n * N + (s + 1) * N, axis=axis)
        return jnp.concatenate([xs, b, c], axis)

    total = 0
    for s in range(n):
        k = {name: {"kernel": cols(mixer[name]["kernel"], s, n)}
             for name in ("wz", "wx", "wB", "wC", "wdt")}
        p = dict(mixer, **k, conv_w=channels(mixer["conv_w"], s),
                 conv_b=channels(mixer["conv_b"], s),
                 A_log=cols(mixer["A_log"], s, n),
                 dt_bias=cols(mixer["dt_bias"], s, n),
                 D=cols(mixer["D"], s, n),
                 norm={"scale": cols(mixer["norm"]["scale"], s, n)},
                 wo={"kernel": cols(mixer["wo"]["kernel"], s, n, 0)})
        total = total + mamba_block(p, pcfg, CTX, x)
    np.testing.assert_allclose(total, whole, atol=2e-3, rtol=2e-3)


def test_vocab_shares_concatenate_to_the_uncut_head(uncut):
    cfg, m, params = uncut
    n = 4
    x = jax.random.normal(jax.random.PRNGKey(4), (2, cfg.d_model))
    whole = m._logits(params, x)
    emb = params["embed"]["embedding"]
    parts = [build_model(dataclasses.replace(cfg, vocab=cfg.vocab // n))
             ._logits({"embed": {"embedding": cols(emb, s, n, 0)}}, x)
             for s in range(n)]
    np.testing.assert_allclose(jnp.concatenate(parts, -1), whole,
                               atol=1e-5, rtol=1e-5)
