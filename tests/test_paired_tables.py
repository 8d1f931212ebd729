"""Paired multi-scalar (TL1-style) PCILT tables: ``[G/2, V^2, O]``.

Covers the PR 8 tentpole and the carried fused-path bugfixes:

* **bit-exactness sweep** — the paired build pre-sums each adjacent segment
  pair into one double-wide table entry, so on an exact-arithmetic grid
  (integer weights, power-of-two scale: every table entry is a dyadic
  rational and every f32 summation order is exact) the paired fetch must
  equal the unpaired fetch *bit for bit* — across V ∈ {2, 4}, odd and even
  G (the odd case pads a phantom segment whose table column is exactly
  zero), f32 and bf16 tables, batch ∈ {1, 4};
* the **seg-major stacked** kernel (``[G2, L, V^2, O]``, layer folded into
  the value axis under scalar prefetch) against per-layer unstacked fetches;
* full paired **decode vs the fake-quant dense oracle** and vs the unpaired
  engine, through ``convert_mamba_decode(paired=True)``;
* the ``fused_gemv_paired*`` **autotune-key contract**: keys carry the
  paired-space G and V, warm caches dispatch with zero timing runs, and a
  failed tune records strict-JSON ``us: null``;
* **generalized SegmentPlans on the fused path** (bugfix: previously a
  hard raise) — the in-VMEM plan gather vs the host ``plan.pack()`` paths,
  including skipped (-1) and reused positions;
* **scalar-level SharedTables** (bugfix: previously ``materialize()`` +
  gather) — routed through the 1-wide segment pool on both ``gather`` and
  ``shared`` paths, dense tables never expanded in HBM;
* slow-marked **multi-shard paired decode parity** at model ∈ {2, 4}
  (seg-axis-0 sharded stacks, one psum per step).
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

RNG = np.random.default_rng(11)


@pytest.fixture
def tune_cache(tmp_path):
    from repro.kernels import autotune as atn

    path = str(tmp_path / "tiles.json")
    atn.reset_cache(path)
    atn.TIMING_RUNS = 0
    yield path
    atn.TIMING_RUNS = 0
    atn.reset_cache()


# ----------------------------------------------------------------------------
# Builder arithmetic + bit-exactness vs the unpaired tables
# ----------------------------------------------------------------------------


def _exact_problem(bits, group, G_dense, O, batch):
    """Integer weights on a power-of-two scale: exact arithmetic, so the
    paired and unpaired summation orders must agree bit-for-bit."""
    import jax.numpy as jnp
    from repro.core import QuantSpec

    n = G_dense * group
    w = jnp.asarray(RNG.integers(-2, 3, size=(n, O)), jnp.float32)
    x = jnp.asarray(RNG.normal(size=(batch, n)), jnp.float32)
    # 1-bit symmetric is rejected (no zero-straddling 2-value grid)
    spec = QuantSpec(bits=bits, symmetric=bits > 1)
    return w, x, spec, jnp.float32(0.5)


def test_paired_entry_is_sum_of_the_pair():
    """T2[s, e + o*V] == T[2s, e] + T[2s+1, o] — the little-endian pair
    index matching the fused kernels' ``_pack_flat`` shift-or."""
    import jax.numpy as jnp
    from repro.core import QuantSpec
    from repro.core.pcilt import build_grouped_tables, build_paired_tables

    bits, group, O = 2, 2, 6
    w, _, spec, scale = _exact_problem(bits, group, G_dense=4, O=O, batch=1)
    V = 1 << (bits * group)
    t = build_grouped_tables(w, spec, scale, group)     # [4, V, O]
    t2 = build_paired_tables(w, spec, scale, group)     # [2, V^2, O]
    assert t2.shape == (2, V * V, O)
    for s in range(2):
        for e in range(V):
            for o in range(V):
                np.testing.assert_array_equal(
                    np.asarray(t2[s, e + o * V]),
                    np.asarray(t[2 * s, e] + t[2 * s + 1, o]))


@pytest.mark.parametrize("batch", [1, 4])
@pytest.mark.parametrize("table_dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("G_dense", [5, 6])  # odd G exercises the phantom
@pytest.mark.parametrize("bits,group", [(1, 1), (2, 1), (1, 2)])  # V ∈ {2,4}
def test_paired_matches_unpaired_bit_exact(tune_cache, bits, group, G_dense,
                                           table_dtype, batch):
    import jax.numpy as jnp
    from repro.core.lut_layers import pcilt_linear
    from repro.core.pcilt import build_grouped_tables, build_paired_tables

    w, x, spec, scale = _exact_problem(bits, group, G_dense, O=8, batch=batch)
    dt = jnp.dtype(table_dtype)
    # integer-valued entries scaled by 0.5 are exactly representable in bf16
    t_u = build_grouped_tables(w, spec, scale, group).astype(dt)
    t_p = build_paired_tables(w, spec, scale, group).astype(dt)
    out_u = pcilt_linear(x, t_u, spec, scale, group, path="fused")
    out_p = pcilt_linear(x, t_p, spec, scale, group, path="fused",
                         paired=True)
    np.testing.assert_array_equal(np.asarray(out_u), np.asarray(out_p))
    # the reference paths agree too (gather runs the paired layout as a
    # plain 2*group-wide grouped fetch)
    out_g = pcilt_linear(x, t_p, spec, scale, group, path="gather",
                         paired=True)
    np.testing.assert_array_equal(np.asarray(out_u), np.asarray(out_g))


def test_odd_g_phantom_column_is_exactly_zero():
    """Odd G pads a phantom segment: the last paired table must be constant
    along the phantom (odd) half of the pair index — the phantom's
    contribution is exactly zero for every code."""
    import jax.numpy as jnp
    from repro.core.pcilt import build_paired_tables

    bits, group, O = 2, 2, 4
    w, _, spec, scale = _exact_problem(bits, group, G_dense=5, O=O, batch=1)
    V = 1 << (bits * group)
    t2 = build_paired_tables(w, spec, scale, group)
    assert t2.shape[0] == 3  # ceil(5 / 2)
    last = np.asarray(t2[-1]).reshape(V, V, O)  # [off_odd, off_even, O]
    for o in range(1, V):
        np.testing.assert_array_equal(last[o], last[0])


# ----------------------------------------------------------------------------
# Seg-major stacked kernel
# ----------------------------------------------------------------------------


def _stacked_paired_problem(L=3, n=24, O=16, B=4, bits=2, group=2):
    import jax.numpy as jnp
    from repro.core import QuantSpec
    from repro.core.pcilt import build_paired_stacked_tables

    spec = QuantSpec(bits=bits, symmetric=True)
    ws = jnp.asarray(RNG.normal(size=(L, n, O)), jnp.float32)
    scales = jnp.asarray(0.1 + 0.05 * np.arange(L), jnp.float32)
    x = jnp.asarray(RNG.normal(size=(B, n)), jnp.float32)
    tabs = build_paired_stacked_tables(ws, spec, scales, group)
    return x, ws, tabs, scales, spec, group


def test_paired_stacked_matches_unstacked_per_layer(tune_cache):
    """The seg-major stack fetches the identical table rows as the per-layer
    paired tables — same entries, same summation order, bit-equal."""
    from repro.core.pcilt import build_paired_tables
    from repro.kernels import ops

    x, ws, tabs, scales, spec, group = _stacked_paired_problem()
    for l in range(tabs.shape[1]):
        t_l = build_paired_tables(ws[l], spec, scales[l], group)
        want = ops.pcilt_fused_gemv_paired(x, t_l, spec, scales[l], group)
        got = ops.pcilt_fused_gemv_paired_stacked(x, tabs, l, spec,
                                                  scales[l], group)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_paired_stacked_reference_path_matches_fused(tune_cache):
    from repro.core.lut_layers import pcilt_linear

    x, ws, tabs, scales, spec, group = _stacked_paired_problem()
    for l in range(tabs.shape[1]):
        got = pcilt_linear(x, tabs, spec, scales[l], group, path="fused",
                           paired=True, stacked=l)
        ref = pcilt_linear(x, tabs, spec, scales[l], group, path="gather",
                           paired=True, stacked=l)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


# ----------------------------------------------------------------------------
# Full paired decode vs the fake-quant dense oracle
# ----------------------------------------------------------------------------

BITS, GROUP = 2, 2


def _pcilt_cfg():
    import jax.numpy as jnp
    from repro.configs import get_smoke_config
    from repro.configs.base import PCILTConfig

    cfg = get_smoke_config("mamba2-130m")
    return dataclasses.replace(cfg, pcilt=PCILTConfig(act_bits=BITS,
                                                      group=GROUP),
                               dtype=jnp.float32)


@pytest.fixture(scope="module")
def paired_problem(tmp_path_factory):
    """One smoke MambaLM converted both paired and unpaired (the table
    builds and calibration prefill run once per module)."""
    import jax
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
    eng_u = convert_mamba_decode(model, params, calib)
    eng_p = convert_mamba_decode(model, params, calib, paired=True)
    yield {"cfg": cfg, "model": model, "params": params, "ctx": ctx,
           "calib": calib, "eng_u": eng_u, "eng_p": eng_p, "key": key}
    atn.reset_cache()


def _prefill(pb, B):
    import jax

    model, params, ctx = pb["model"], pb["params"], pb["ctx"]
    toks = jax.random.randint(pb["key"], (B, 16), 0, pb["cfg"].vocab)
    _, cache = model.prefill(params, {"tokens": toks}, ctx)
    tok = jax.random.randint(jax.random.fold_in(pb["key"], 1), (B, 1), 0,
                             pb["cfg"].vocab)
    return cache, tok


def test_paired_bundle_layout(paired_problem):
    from repro.nn.ssm import PROJ_NAMES

    pb = paired_problem
    proj = pb["eng_p"].pcilt["proj"]
    assert proj["paired"] is True
    L = pb["cfg"].n_layers
    V2 = 1 << (2 * BITS * GROUP)
    for name in PROJ_NAMES:
        t = proj["tables"][name]
        assert t.ndim == 4 and t.shape[1] == L and t.shape[2] == V2
        # half the fetch count of the dense stack for the same projection
        t_u = pb["eng_u"].pcilt["proj"]["tables"][name]
        assert t.shape[0] == -(-t_u.shape[1] // 2)


@pytest.mark.parametrize("batch", [1, 4])
def test_paired_decode_matches_fakequant_oracle(paired_problem, batch):
    import jax

    pb = paired_problem
    model, params, ctx = pb["model"], pb["params"], pb["ctx"]
    eng = pb["eng_p"]
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


def test_paired_decode_matches_unpaired(paired_problem):
    pb = paired_problem
    cache, tok = _prefill(pb, 2)
    l_u, _ = pb["eng_u"].step(pb["params"], cache, tok)
    l_p, _ = pb["eng_p"].step(pb["params"], cache, tok)
    np.testing.assert_allclose(np.asarray(l_p), np.asarray(l_u),
                               rtol=2e-4, atol=2e-4)


def test_paired_integrity_localizes_per_layer(paired_problem):
    """Seg-major stacks checksum along axis 1 — a single flipped entry in
    one layer's slice is caught at that layer and no other."""
    import jax.numpy as jnp

    pb = paired_problem
    eng = pb["eng_p"]
    assert eng.verify_integrity() == []
    orig = eng.pcilt["proj"]["tables"]["wx"]
    t = np.asarray(orig).copy()
    t[0, 1, 3, 0] += 1.0
    eng.pcilt["proj"]["tables"]["wx"] = jnp.asarray(t)
    try:
        assert ("wx", 1) in eng.verify_layer(1)
        assert eng.verify_layer(0) == []
    finally:
        eng.pcilt["proj"]["tables"]["wx"] = orig
    assert eng.verify_integrity() == []


# ----------------------------------------------------------------------------
# fused_gemv_paired* autotune-key contract
# ----------------------------------------------------------------------------


def test_paired_keys_carry_paired_space_dims(tune_cache):
    """Keys record the staged operand's geometry: segment *pairs* and the
    squared cardinality — and a warm cache dispatches with zero timing."""
    from repro.core.pcilt import build_paired_tables
    from repro.kernels import autotune as atn
    from repro.kernels import ops

    w, x, spec, scale = _exact_problem(2, GROUP, G_dense=6, O=8, batch=4)
    t_p = build_paired_tables(w, spec, scale, GROUP)
    G2, V2 = t_p.shape[0], t_p.shape[1]
    ops.pcilt_fused_gemv_paired(x, t_p, spec, scale, GROUP, autotune=True)
    entries = json.load(open(tune_cache))
    keys = [k for k in entries if k.startswith("fused_gemv_paired|")]
    assert len(keys) == 1
    assert f"G={G2}," in keys[0] and f"V={V2}," in keys[0]
    assert f"g={GROUP}" in keys[0] and "bits=2" in keys[0]
    atn.reset_cache(tune_cache)
    atn.TIMING_RUNS = 0
    ops.pcilt_fused_gemv_paired(x, t_p, spec, scale, GROUP, autotune=True)
    assert atn.TIMING_RUNS == 0


def test_paired_stacked_key_carries_L(tune_cache):
    from repro.kernels import ops

    x, ws, tabs, scales, spec, group = _stacked_paired_problem(L=3)
    ops.pcilt_fused_gemv_paired_stacked(x, tabs, 0, spec, scales[0], group,
                                        autotune=True)
    entries = json.load(open(tune_cache))
    key = next(k for k in entries
               if k.startswith("fused_gemv_paired_stacked|"))
    assert "L=3," in key and f"G={tabs.shape[0]}," in key
    assert f"V={tabs.shape[2]}," in key


def test_paired_failed_tune_records_null(tune_cache, monkeypatch):
    """All candidates failing raises and records nothing; the untuned
    dispatch still runs on candidate 0."""
    from repro.kernels import autotune as atn
    from repro.kernels import ops

    def boom(fn, reps, warmup):
        raise RuntimeError("no candidate can run")

    monkeypatch.setattr(atn, "_time_one", boom)
    w, x, spec, scale = _exact_problem(2, GROUP, G_dense=6, O=8, batch=4)
    from repro.core.pcilt import build_paired_tables

    t_p = build_paired_tables(w, spec, scale, GROUP)
    with pytest.raises(RuntimeError, match="fused_gemv_paired.*none of"):
        ops.pcilt_fused_gemv_paired(x, t_p, spec, scale, GROUP,
                                    autotune=True)
    assert not os.path.exists(tune_cache)
    out = ops.pcilt_fused_gemv_paired(x, t_p, spec, scale, GROUP)
    assert out.shape == (x.shape[0], t_p.shape[-1])


def test_paired_rejects_plan_shared_pool_and_shared_path(tune_cache):
    import jax.numpy as jnp
    from repro.core import QuantSpec
    from repro.core.lut_layers import pcilt_linear
    from repro.core.offsets import SegmentPlan
    from repro.core.pcilt import build_paired_tables

    w, x, spec, scale = _exact_problem(2, GROUP, G_dense=4, O=8, batch=2)
    t_p = build_paired_tables(w, spec, scale, GROUP)
    with pytest.raises(ValueError, match="plan"):
        pcilt_linear(x, t_p, spec, scale, GROUP, paired=True,
                     plan=SegmentPlan.contiguous(4, GROUP))
    with pytest.raises(ValueError, match="shared"):
        pcilt_linear(x, t_p, spec, scale, GROUP, paired=True, path="shared")


# ----------------------------------------------------------------------------
# Bugfix: generalized SegmentPlans run on the fused path
# ----------------------------------------------------------------------------


def test_plan_fused_matches_packed_reference(tune_cache):
    """A plan with a skipped slot (-1) and a reused position executes fused
    via the in-VMEM plan gather and matches the host plan.pack() paths."""
    import jax.numpy as jnp
    from repro.core import QuantSpec
    from repro.core.lut_layers import pcilt_linear
    from repro.core.offsets import SegmentPlan
    from repro.core.pcilt import build_grouped_tables

    spec = QuantSpec(bits=BITS, symmetric=True)
    scale = jnp.float32(0.25)
    # 3 segments over a 5-wide input: position 2 reused, one slot unused
    plan = SegmentPlan(index=np.asarray(
        [[0, 1], [2, -1], [2, 3]], np.int32))
    w = jnp.asarray(RNG.normal(size=(5, 8)), jnp.float32)
    tables = build_grouped_tables(w, spec, scale, GROUP, plan=plan)
    x = jnp.asarray(RNG.normal(size=(3, 5)), jnp.float32)
    out_f = pcilt_linear(x, tables, spec, scale, GROUP, plan=plan,
                         path="fused")
    out_g = pcilt_linear(x, tables, spec, scale, GROUP, plan=plan,
                         path="gather")
    np.testing.assert_allclose(np.asarray(out_f), np.asarray(out_g),
                               rtol=1e-6, atol=1e-6)


def test_plan_grid_mismatch_raises(tune_cache):
    import jax.numpy as jnp
    from repro.core import QuantSpec
    from repro.core.lut_layers import pcilt_linear
    from repro.core.offsets import SegmentPlan
    from repro.core.pcilt import build_grouped_tables

    spec = QuantSpec(bits=BITS, symmetric=True)
    w = jnp.asarray(RNG.normal(size=(8, 8)), jnp.float32)
    tables = build_grouped_tables(w, spec, jnp.float32(0.25), GROUP)
    x = jnp.asarray(RNG.normal(size=(2, 8)), jnp.float32)
    with pytest.raises(ValueError, match="plan grid"):
        # 3 segments vs the tables' 4
        pcilt_linear(x, tables, spec, jnp.float32(0.25), GROUP,
                     plan=SegmentPlan.contiguous(6, GROUP), path="fused")


# ----------------------------------------------------------------------------
# Bugfix: scalar SharedTables execute via the 1-wide segment pool
# ----------------------------------------------------------------------------


def test_scalar_shared_tables_route_through_pool(tune_cache):
    """A scalar-level SharedTables passed to pcilt_linear executes through
    ``as_grouped_pool()`` (the fused shared kernel / pointer gather) and
    matches the materialize() dense oracle on both paths."""
    import jax.numpy as jnp
    from repro.core import QuantSpec, fake_quant
    from repro.core.lut_layers import pcilt_linear
    from repro.core.pcilt import build_shared_tables

    spec = QuantSpec(bits=BITS, symmetric=True)
    scale = jnp.float32(0.25)
    # low-cardinality weights: the dedup regime the pool targets
    w = jnp.asarray(RNG.integers(-1, 2, size=(12, 8)), jnp.float32)
    st = build_shared_tables(w, spec, scale)
    x = jnp.asarray(RNG.normal(size=(3, 12)), jnp.float32)
    want = fake_quant(x, spec, scale) @ w
    for path in ("gather", "shared"):
        got = pcilt_linear(x, st, spec, scale, group=1, path=path)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
    # the pool is built once and cached on the instance
    assert st._grouped is not None
    assert st.as_grouped_pool() is st._grouped
    assert st._grouped.group == 1


# ----------------------------------------------------------------------------
# Multi-shard paired parity (slow tier: 8 forced host devices)
# ----------------------------------------------------------------------------


@pytest.mark.slow
@pytest.mark.skipif(MULTI, reason="already running with forced devices")
def test_paired_parity_reruns_with_forced_devices(tmp_path):
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
        f"paired parity suite failed under {FORCE_FLAG}:\n"
        f"{r.stdout}\n{r.stderr}")


@pytest.mark.slow
@multi_device
@pytest.mark.parametrize("model_shards", [2, 4])
def test_paired_decode_sharded_matches_single_device(
        paired_problem, tune_cache, model_shards):
    """Seg-major paired stacks shard on axis 0 (segment pairs) over the
    model axis — one psum per step — and match the single-device engine;
    the shard-local tunes record under the local ``G2/D`` key."""
    from repro.core.serving import convert_mamba_decode
    from repro.launch.mesh import make_decode_mesh

    pb = paired_problem
    model, params = pb["model"], pb["params"]
    cache, tok = _prefill(pb, 1)
    l_ref, nc_ref = pb["eng_p"].step(params, cache, tok)

    mesh = make_decode_mesh(model_shards)
    eng_m = convert_mamba_decode(model, params, pb["calib"], mesh=mesh,
                                 paired=True)
    eng_m.tune(batch=1)
    proj = eng_m.pcilt["proj"]
    assert proj["paired"] is True
    G2 = proj["tables"]["wz"].shape[0]
    entries = json.load(open(tune_cache))
    assert any(k.startswith("fused_gemv_paired_stacked|")
               and f"G={G2 // model_shards}," in k for k in entries), \
        "tune must record the local shard's paired-space G"
    l_m, nc_m = eng_m.step(params, cache, tok)
    np.testing.assert_allclose(np.asarray(l_m), np.asarray(l_ref),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(nc_m["layers"]["ssd"]),
                               np.asarray(nc_ref["layers"]["ssd"]),
                               rtol=2e-4, atol=2e-4)
