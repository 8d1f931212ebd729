"""End-to-end PCILT decode: layer-scanned fused GEMV for the projections.

Covers the PR 5 tentpole:

* full-PCILT ``MambaLM.decode_step`` (conv + every projection a table
  fetch) against the fake-quant dense oracle — the fetch is exact on the
  quantized grid, so a decode step whose projections fake-quantize their
  inputs before the dense matmul must match the stacked-table fetch to
  float tolerance — at batch ∈ {1, 4};
* the ``fused_gemv_stacked`` autotune-key contract: keys carry ``L`` and
  the *local* ``G`` (``G/D`` under a mesh), and a failed tune records
  strict-JSON ``us: null``;
* the typed ``ValueError`` at the ``build_pcilt`` / ``convert_mamba_decode``
  boundary when ``cfg.pcilt`` is unset;
* dispatch-boundary rejections of the ``stacked=`` operand
  (``SegmentPlan``, shared pools, wrong rank).

The multi-shard parity tests (model ∈ {2, 4}) are marked ``slow`` — plain
tier-1 deselects them via the ``-m "not slow"`` default (pytest.ini) so the
suite's wall time stays flat; the CI multi-device job (and a slow-marked
subprocess wrapper for local runs) executes them on 8 forced host devices.
"""

import dataclasses
import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FORCE_FLAG = "--xla_force_host_platform_device_count=8"


def _device_count() -> int:
    import jax

    return jax.device_count()


MULTI = _device_count() >= 8
multi_device = pytest.mark.skipif(
    not MULTI,
    reason="needs 8 forced host devices (re-run via the subprocess wrapper)",
)

RNG = np.random.default_rng(7)
BITS, GROUP = 2, 2


@pytest.fixture
def tune_cache(tmp_path):
    from repro.kernels import autotune as atn

    path = str(tmp_path / "tiles.json")
    atn.reset_cache(path)
    atn.TIMING_RUNS = 0
    yield path
    atn.TIMING_RUNS = 0
    atn.reset_cache()


def _pcilt_cfg():
    from repro.configs import get_smoke_config
    from repro.configs.base import PCILTConfig
    import jax.numpy as jnp

    cfg = get_smoke_config("mamba2-130m")
    # f32 compute: the oracle compares a dense matmul against the table
    # fetch, so the only wanted difference is the quantization grid itself.
    return dataclasses.replace(cfg, pcilt=PCILTConfig(act_bits=BITS,
                                                      group=GROUP),
                               dtype=jnp.float32)


@pytest.fixture(scope="module")
def decode_problem(tmp_path_factory):
    """One converted smoke MambaLM shared by the parity tests (the table
    build and calibration prefill run once per module)."""
    import jax
    import jax.numpy as jnp
    from repro.core.serving import convert_mamba_decode
    from repro.kernels import autotune as atn
    from repro.models import build_model
    from repro.nn import materialize
    from repro.nn.layers import Ctx

    atn.reset_cache(str(tmp_path_factory.mktemp("tune") / "tiles.json"))
    cfg = _pcilt_cfg()
    model = build_model(cfg)
    key = jax.random.PRNGKey(0)
    params = materialize(model.param_specs(), key)
    ctx = Ctx()
    calib = jax.random.randint(key, (2, 16), 0, cfg.vocab)
    eng = convert_mamba_decode(model, params, calib)
    yield {"cfg": cfg, "model": model, "params": params, "ctx": ctx,
           "calib": calib, "eng": eng, "key": key}
    atn.reset_cache()


def _prefill(pb, B):
    import jax

    model, params, ctx = pb["model"], pb["params"], pb["ctx"]
    toks = jax.random.randint(pb["key"], (B, 16), 0, pb["cfg"].vocab)
    _, cache = model.prefill(params, {"tokens": toks}, ctx)
    tok = jax.random.randint(jax.random.fold_in(pb["key"], 1), (B, 1), 0,
                             pb["cfg"].vocab)
    return cache, tok


# ----------------------------------------------------------------------------
# Full-PCILT decode vs the fake-quant dense oracle (model=1)
# ----------------------------------------------------------------------------


@pytest.mark.parametrize("batch", [1, 4])
def test_full_pcilt_decode_matches_fakequant_oracle(decode_problem, batch):
    """Every projection a stacked-table fetch == every projection a dense
    matmul on fake-quantized inputs (exactness on the quantized grid,
    composed through the whole decode step), plus identical cache motion."""
    import jax
    import jax.numpy as jnp

    pb = decode_problem
    model, params, ctx, eng = pb["model"], pb["params"], pb["ctx"], pb["eng"]
    cache, tok = _prefill(pb, batch)
    logits, nc = eng.step(params, cache, tok)
    oracle_pc = dict(eng.pcilt, proj=dict(eng.pcilt["proj"],
                                          path="dense_fq"))
    l_oracle, nc_o = jax.jit(
        lambda p, c, t: model.decode_step(p, c, t, ctx, pcilt=oracle_pc)
    )(params, cache, tok)
    assert logits.shape == (batch, pb["cfg"].padded_vocab)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(l_oracle),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(nc["layers"]["ssd"]),
                               np.asarray(nc_o["layers"]["ssd"]),
                               rtol=2e-4, atol=2e-4)
    assert int(nc["pos"]) == int(nc_o["pos"])


def test_hostpacked_proj_path_matches_fused(decode_problem):
    """The host-packed projection baseline (per-layer table-slice copy +
    offset packing in HBM) computes the same decode step as the stacked
    fused kernel — it is the *same arithmetic*, only slower."""
    import jax

    pb = decode_problem
    model, params, ctx, eng = pb["model"], pb["params"], pb["ctx"], pb["eng"]
    cache, tok = _prefill(pb, 2)
    logits, _ = eng.step(params, cache, tok)
    host_pc = dict(eng.pcilt, proj=dict(eng.pcilt["proj"], path="kernel"))
    l_host, _ = jax.jit(
        lambda p, c, t: model.decode_step(p, c, t, ctx, pcilt=host_pc)
    )(params, cache, tok)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(l_host),
                               rtol=1e-4, atol=1e-4)


def test_convert_covers_all_projections(decode_problem):
    from repro.nn.ssm import PROJ_NAMES

    proj = decode_problem["eng"].pcilt["proj"]
    assert set(proj["tables"]) == set(PROJ_NAMES)
    L = decode_problem["cfg"].n_layers
    for name in PROJ_NAMES:
        t = proj["tables"][name]
        assert t.ndim == 4 and t.shape[0] == L
        assert t.shape[2] == (1 << (BITS * GROUP))
        assert proj["scales"][name].shape == (L,)
    assert decode_problem["eng"].table_bytes() > 0


# ----------------------------------------------------------------------------
# fused_gemv_stacked autotune-key contract
# ----------------------------------------------------------------------------


def _stacked_problem(L=3, n=32, O=24, B=4):
    import jax.numpy as jnp
    from repro.core import QuantSpec, build_grouped_tables

    spec = QuantSpec(BITS, symmetric=True)
    x = jnp.asarray(RNG.normal(size=(B, n)), jnp.float32)
    scales = jnp.asarray(0.1 + 0.05 * np.arange(L), jnp.float32)
    tabs = jnp.stack([
        build_grouped_tables(
            jnp.asarray(RNG.normal(size=(n, O)), jnp.float32),
            spec, scales[l], GROUP)
        for l in range(L)])
    return x, tabs, scales, spec


def test_stacked_matches_unstacked_per_layer(tune_cache):
    from repro.kernels import ops

    x, tabs, scales, spec = _stacked_problem()
    for l in range(tabs.shape[0]):
        want = ops.pcilt_fused_gemv(x, tabs[l], spec, scales[l], GROUP)
        got = ops.pcilt_fused_gemv_stacked(x, tabs, l, spec, scales[l],
                                           GROUP)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6, atol=1e-6)


def test_stacked_keys_carry_L_and_local_G(tune_cache):
    """Keys carry the stack depth L and the *local* segment count — tuning
    one device's ``[L, G/D, V, O]`` shard records under G/D, so caches
    tuned at different device counts never collide."""
    from repro.kernels import autotune as atn
    from repro.kernels import ops

    x, tabs, scales, spec = _stacked_problem(L=3, n=32)
    G = tabs.shape[1]
    ops.pcilt_fused_gemv_stacked(x, tabs, 0, spec, scales[0], GROUP,
                                 autotune=True)
    # the local-shard tune a 2-way mesh would dispatch (G/D segments)
    ops.pcilt_fused_gemv_stacked(x[:, : G // 2 * GROUP], tabs[:, : G // 2],
                                 0, spec, scales[0], GROUP, autotune=True)
    entries = json.load(open(tune_cache))
    keys = sorted(k for k in entries if k.startswith("fused_gemv_stacked|"))
    assert len(keys) == 2
    assert any(f"G={G}," in k and "L=3," in k for k in keys)
    assert any(f"G={G // 2}," in k and "L=3," in k for k in keys)
    # warm-cache contract: the recorded tiles dispatch with zero timing runs
    atn.reset_cache(tune_cache)
    atn.TIMING_RUNS = 0
    ops.pcilt_fused_gemv_stacked(x, tabs, 1, spec, scales[1], GROUP,
                                 autotune=True)
    assert atn.TIMING_RUNS == 0


def test_stacked_failed_tune_records_null(tune_cache, monkeypatch):
    """All candidates failing raises under the stacked key and records
    nothing — a kernel that runs at no tiling is a fault, not a cache entry;
    the untuned dispatch still runs on the heuristic tiles."""
    from repro.kernels import autotune as atn
    from repro.kernels import ops

    def boom(fn, reps, warmup):
        raise RuntimeError("no candidate can run")

    monkeypatch.setattr(atn, "_time_one", boom)
    x, tabs, scales, spec = _stacked_problem()
    with pytest.raises(RuntimeError, match="fused_gemv_stacked.*none of"):
        ops.pcilt_fused_gemv_stacked(x, tabs, 2, spec, scales[2], GROUP,
                                     autotune=True)
    assert not os.path.exists(tune_cache)
    out = ops.pcilt_fused_gemv_stacked(x, tabs, 2, spec, scales[2], GROUP)
    assert out.shape == (x.shape[0], tabs.shape[-1])


def test_stacked_candidates_mirror_dense_sweep():
    """The staged per-layer slice is byte-identical to the unstacked tile,
    so the stacked sweep starts with the dense sweep as a prefix (L never
    enters) — candidate 0 is still the heuristic no-tune fallback.  The
    batch-R extension may append row-split variants after the prefix, and
    those differ from the dense candidates only in (Bb, Gb) — R is a tuned
    axis, not a new staging strategy."""
    from repro.kernels import autotune as atn

    for B, G, V, O in [(1, 32, 16, 128), (8, 512, 16, 1024), (64, 32, 16, 256)]:
        for L in (2, 24):
            dense = atn.gemv_candidates(B, G, V, O)
            stacked = atn.stacked_gemv_candidates(B, L, G, V, O)
            assert stacked[:len(dense)] == dense
            assert stacked[0] == dense[0]  # heuristic fallback unchanged
            extra = stacked[len(dense):]
            dense_obs = {c.Ob for c in dense}
            for c in extra:
                assert c.Bb < dense[0].Bb and c.Bb % 8 == 0
                assert c.Ob in dense_obs


def test_stacked_candidates_sweep_row_tiles_at_large_B():
    """At serving batch sizes the R-aware sweep must offer genuine Bb
    sub-tiles (splitting the batch across grid rows), deduplicated and
    capped."""
    from repro.kernels import autotune as atn

    cands = atn.stacked_gemv_candidates(64, 3, 32, 16, 256)
    bbs = {c.Bb for c in cands}
    assert 64 in bbs  # full-batch tiles still present
    assert any(b < 64 for b in bbs), f"no row sub-tiles in {sorted(bbs)}"
    assert len(cands) == len(set(cands)) <= 8
    # B=1 stays minimal: the padded row tile is already the floor (8), so
    # the R sweep adds nothing
    small = atn.stacked_gemv_candidates(1, 3, 32, 16, 256)
    assert small == atn.gemv_candidates(1, 32, 16, 256)

    paired = atn.paired_stacked_gemv_candidates(64, 2, 8, 256, 128)
    pbbs = {c.Bb for c in paired}
    assert any(b < max(pbbs) for b in pbbs)
    assert len(paired) == len(set(paired)) <= 8


# ----------------------------------------------------------------------------
# Typed boundary errors
# ----------------------------------------------------------------------------


def test_build_pcilt_without_config_raises_actionable_error():
    import jax
    from repro.models import build_model
    from repro.nn import materialize

    cfg = dataclasses.replace(_pcilt_cfg(), pcilt=None)
    model = build_model(cfg)
    params = materialize(model.param_specs(), jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match=r"cfg\.pcilt.*PCILTConfig"):
        model.build_pcilt(params, 0.1)


def test_convert_mamba_decode_without_config_raises():
    import jax
    from repro.core.serving import convert_mamba_decode
    from repro.models import build_model
    from repro.nn import materialize

    cfg = dataclasses.replace(_pcilt_cfg(), pcilt=None)
    model = build_model(cfg)
    params = materialize(model.param_specs(), jax.random.PRNGKey(0))
    calib = jax.random.randint(jax.random.PRNGKey(1), (1, 8), 0, cfg.vocab)
    with pytest.raises(ValueError, match=r"cfg\.pcilt.*PCILTConfig"):
        convert_mamba_decode(model, params, calib)


def test_build_pcilt_conv_without_config_raises():
    from repro.nn.ssm import build_pcilt_conv

    cfg = dataclasses.replace(_pcilt_cfg(), pcilt=None)
    with pytest.raises(ValueError, match=r"cfg\.pcilt.*PCILTConfig"):
        build_pcilt_conv({}, cfg, 0.1)


def test_stacked_rejects_plan_shared_and_wrong_rank(tune_cache):
    import jax.numpy as jnp
    from repro.core import (QuantSpec, SegmentPlan,
                            build_shared_grouped_tables, pcilt_linear)

    x, tabs, scales, spec = _stacked_problem()
    n = x.shape[-1]
    with pytest.raises(ValueError, match="SegmentPlan"):
        pcilt_linear(x, tabs, spec, scales[0], GROUP,
                     plan=SegmentPlan.contiguous(n, GROUP), stacked=0)
    with pytest.raises(ValueError, match=r"\[L, G, V, O\]"):
        pcilt_linear(x, tabs[0], spec, scales[0], GROUP, stacked=0)
    st = build_shared_grouped_tables(
        jnp.asarray(RNG.normal(size=(n, 8)), jnp.float32), spec, scales[0],
        GROUP)
    with pytest.raises(ValueError, match="shared"):
        pcilt_linear(x, st, spec, scales[0], GROUP, stacked=0, path="shared")


# ----------------------------------------------------------------------------
# Multi-shard parity (slow tier: 8 forced host devices)
# ----------------------------------------------------------------------------


@pytest.mark.slow
@pytest.mark.skipif(MULTI, reason="already running with forced devices")
def test_decode_parity_reruns_with_forced_devices(tmp_path):
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " " + FORCE_FLAG).strip()
    env["PYTHONPATH"] = os.path.join(REPO, "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    env["REPRO_PCILT_TUNE_CACHE"] = str(tmp_path / "tiles.json")
    r = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-x",
         os.path.abspath(__file__), "-m", "slow or not slow"],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=1500,
    )
    assert r.returncode == 0, (
        f"decode parity suite failed under {FORCE_FLAG}:\n"
        f"{r.stdout}\n{r.stderr}")


@pytest.mark.slow
@multi_device
@pytest.mark.parametrize("model_shards", [2, 4])
def test_full_pcilt_decode_sharded_matches_single_device(
        decode_problem, tune_cache, model_shards):
    """Stacked proj tables sharded over the model axis (one psum per step)
    produce the same decode step as the single-device stack — and the
    shard-local tunes record under the local ``G/D`` key."""
    import jax
    import jax.numpy as jnp
    from repro.core.serving import convert_mamba_decode
    from repro.launch.mesh import make_decode_mesh

    pb = decode_problem
    model, params = pb["model"], pb["params"]
    cache, tok = _prefill(pb, 1)
    l_ref, nc_ref = pb["eng"].step(params, cache, tok)

    mesh = make_decode_mesh(model_shards)
    eng_m = convert_mamba_decode(model, params, pb["calib"], mesh=mesh)
    eng_m.tune(batch=1)
    proj = eng_m.pcilt["proj"]
    G = proj["tables"]["wz"].shape[1]
    entries = json.load(open(tune_cache))
    assert any(k.startswith("fused_gemv_stacked|")
               and f"G={G // model_shards}," in k for k in entries), \
        "tune must record the local shard's G"
    l_m, nc_m = eng_m.step(params, cache, tok)
    np.testing.assert_allclose(np.asarray(l_m), np.asarray(l_ref),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(nc_m["layers"]["ssd"]),
                               np.asarray(nc_ref["layers"]["ssd"]),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.slow
@multi_device
def test_sharded_stack_falls_back_when_axis_does_not_divide(decode_problem):
    """A mesh axis that does not divide G replicates (divisibility
    fallback) instead of failing — same contract as every other PCILT
    mesh path."""
    import jax
    from repro.core.serving import convert_mamba_decode
    from repro.launch.mesh import make_decode_mesh

    pb = decode_problem
    mesh = make_decode_mesh(3)  # 3 ∤ G for the smoke dims
    eng = convert_mamba_decode(pb["model"], pb["params"], pb["calib"],
                               mesh=mesh)
    cache, tok = _prefill(pb, 1)
    l_ref, _ = pb["eng"].step(pb["params"], cache, tok)
    l_m, _ = eng.step(pb["params"], cache, tok)
    np.testing.assert_allclose(np.asarray(l_m), np.asarray(l_ref),
                               rtol=2e-4, atol=2e-4)
