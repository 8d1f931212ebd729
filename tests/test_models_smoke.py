"""Per-architecture smoke tests: reduced same-family config, one forward
loss + one decode step on CPU, asserting output shapes and finiteness."""

import jax
import jax.numpy as jnp
import pytest

from repro.configs import ARCHS, get_config, get_smoke_config, SHAPES
from repro.models import build_model
from repro.nn import materialize, count_params
from repro.nn.layers import Ctx

KEY = jax.random.PRNGKey(0)
B, S = 2, 32


def _batch(cfg):
    b = {
        "tokens": jax.random.randint(KEY, (B, S), 0, cfg.vocab),
        "labels": jax.random.randint(jax.random.fold_in(KEY, 1), (B, S), 0,
                                     cfg.vocab),
    }
    if cfg.encoder_layers:
        b["memory"] = jax.random.normal(KEY, (B, cfg.encoder_len, cfg.d_model))
    if cfg.n_img_tokens:
        b["img_embeds"] = jax.random.normal(KEY, (B, cfg.n_img_tokens,
                                                  cfg.d_model))
    return b


@pytest.fixture(scope="module")
def ctx():
    return Ctx()


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_loss_and_decode(arch, ctx):
    cfg = get_smoke_config(arch)
    model = build_model(cfg)
    params = materialize(model.param_specs(), KEY)
    loss, metrics = jax.jit(lambda p, b: model.loss(p, b, ctx))(
        params, _batch(cfg))
    assert loss.shape == ()
    assert bool(jnp.isfinite(loss)), f"{arch}: non-finite loss"

    cache = materialize(model.cache_specs(B, S), KEY)
    cache = dict(cache, pos=jnp.asarray(S - 1, jnp.int32))
    tok = jax.random.randint(KEY, (B, 1), 0, cfg.vocab)
    logits, new_cache = jax.jit(
        lambda p, c, t: model.decode_step(p, c, t, ctx))(params, cache, tok)
    assert logits.shape == (B, cfg.padded_vocab)
    assert bool(jnp.all(jnp.isfinite(logits))), f"{arch}: non-finite logits"
    # cache advances
    assert int(new_cache["pos"]) == S


@pytest.mark.parametrize("arch", ARCHS)
def test_full_config_matches_assignment(arch):
    """The full configs carry the exact published dimensions."""
    cfg = get_config(arch)
    expect = {
        "llama4-maverick-400b-a17b": (48, 5120, 40, 8, 8192, 202048),
        "granite-moe-3b-a800m": (32, 1536, 24, 8, 512, 49155),
        "deepseek-coder-33b": (62, 7168, 56, 8, 19200, 32256),
        "qwen1.5-4b": (40, 2560, 20, 20, 6912, 151936),
        "qwen2.5-3b": (36, 2048, 16, 2, 11008, 151936),
        "qwen3-0.6b": (28, 1024, 16, 8, 3072, 151936),
        "whisper-medium": (24, 1024, 16, 16, 4096, 51865),
        "mamba2-130m": (24, 768, 0, 0, 0, 50280),
        "llava-next-mistral-7b": (32, 4096, 32, 8, 14336, 32000),
        "zamba2-7b": (81, 3584, 32, 32, 14336, 32000),
    }[arch]
    got = (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_ff,
           cfg.vocab)
    assert got == expect, f"{arch}: {got} != {expect}"


def test_moe_arch_extras():
    l4 = get_config("llama4-maverick-400b-a17b")
    assert l4.moe.n_experts == 128 and l4.moe.top_k == 1
    gr = get_config("granite-moe-3b-a800m")
    assert gr.moe.n_experts == 40 and gr.moe.top_k == 8
    mm = get_config("mamba2-130m")
    assert mm.ssm.d_state == 128
    zb = get_config("zamba2-7b")
    assert zb.ssm.d_state == 64 and zb.ssm.n_groups == 2
    assert zb.hybrid_layer_ids == (6, 11, 17, 23, 29, 35, 41, 47, 53, 59, 65,
                                   71, 77)
    assert (zb.n_shared_attn_blocks, zb.adapter_rank, zb.head_dim) == \
        (2, 128, 224)
    lv = get_config("llava-next-mistral-7b")
    assert lv.window == 4096
    qw = get_config("qwen1.5-4b")
    assert qw.qkv_bias
    q3 = get_config("qwen3-0.6b")
    assert q3.qk_norm


def test_param_count_sanity():
    """Full-config parameter counts land near the published sizes."""
    import math
    from repro.nn.module import count_params

    targets = {  # (arch, nominal params, tolerance fraction)
        "deepseek-coder-33b": (33e9, 0.15),
        "qwen2.5-3b": (3.1e9, 0.25),
        "qwen3-0.6b": (0.6e9, 0.4),
        "mamba2-130m": (130e6, 0.4),
        "llava-next-mistral-7b": (7.1e9, 0.15),
        "granite-moe-3b-a800m": (3.4e9, 0.3),
    }
    for arch, (target, tol) in targets.items():
        cfg = get_config(arch)
        n = count_params(build_model(cfg).param_specs())
        assert abs(n - target) / target < tol, f"{arch}: {n/1e9:.2f}B vs {target/1e9:.2f}B"


def test_long_500k_eligibility():
    """DESIGN.md §7: SSM/hybrid/SWA run long_500k; full-attention skip."""
    eligible = {a: get_config(a).sub_quadratic for a in ARCHS}
    assert eligible["mamba2-130m"] and eligible["zamba2-7b"]
    assert eligible["llava-next-mistral-7b"]  # sliding window 4096
    for a in ("qwen3-0.6b", "deepseek-coder-33b", "whisper-medium",
              "llama4-maverick-400b-a17b"):
        assert not eligible[a]


def test_materialize_is_stable_across_processes():
    """Per-leaf init keys come from a digest of the leaf path, not Python's
    per-process salted ``hash``: two processes with different hash seeds
    materialize the same parameters from the same key."""
    import os
    import subprocess
    import sys

    code = (
        "import jax, numpy as np\n"
        "from repro.configs import get_smoke_config\n"
        "from repro.models import build_model\n"
        "from repro.nn import materialize\n"
        "m = build_model(get_smoke_config('mamba2-130m'))\n"
        "p = materialize(m.param_specs(), jax.random.PRNGKey(0))\n"
        "print(repr([float(np.abs(np.asarray(l, np.float64)).sum())\n"
        "            for l in jax.tree.leaves(p)]))\n")
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    sums = []
    for seed in ("1", "2"):
        env = dict(os.environ, PYTHONHASHSEED=seed, JAX_PLATFORMS="cpu",
                   PYTHONPATH=src)
        r = subprocess.run([sys.executable, "-c", code], env=env,
                           capture_output=True, text=True, timeout=300)
        assert r.returncode == 0, r.stderr
        sums.append(r.stdout.strip().splitlines()[-1])
    assert sums[0] == sums[1]
