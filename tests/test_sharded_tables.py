"""Multi-device tier: mesh-sharded PCILT tables for tensor-parallel decode.

Asserts parity of the sharded gather / fused / shared execution paths
against the single-device reference for GEMV and conv2d — including G not
divisible by the mesh axis (replication fallback) and the batch=1 decode
regime — plus the sharded autotune-key contract (local-shard shapes, no
collision across device counts, ``us: null`` on failed tunes under a mesh).

This file wants ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the
CI multi-device job exports it).  When collected in a single-device process
— e.g. the plain tier-1 run — the device-hungry tests skip and one wrapper
test re-executes this very file under pytest in a subprocess with the flag
set, so the tier is exercised either way.
"""

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


# ----------------------------------------------------------------------------
# Subprocess wrapper: single-device collection re-executes this file forced.
# ----------------------------------------------------------------------------


@pytest.mark.skipif(MULTI, reason="already running with forced devices")
def test_suite_reruns_with_forced_devices(tmp_path):
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " " + FORCE_FLAG).strip()
    env["PYTHONPATH"] = os.path.join(REPO, "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    env["REPRO_PCILT_TUNE_CACHE"] = str(tmp_path / "tiles.json")
    r = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-x", os.path.abspath(__file__)],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=1500,
    )
    assert r.returncode == 0, (
        f"sharded suite failed under {FORCE_FLAG}:\n{r.stdout}\n{r.stderr}")


# ----------------------------------------------------------------------------
# Shared fixtures / helpers (all imports of jax stay inside so the outer
# single-device collection never pays for them).
# ----------------------------------------------------------------------------

RNG = np.random.default_rng(11)
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


def _mesh(model):
    from repro.launch.mesh import make_decode_mesh

    return make_decode_mesh(model)


def _spec_scale(x):
    from repro.core import QuantSpec, calibrate

    spec = QuantSpec(BITS)
    return spec, calibrate(x, spec)


def _int_weights(n, O):
    """Integer weights (paired with ``scale=1.0``): every table entry,
    partial product and partial sum is then a small exact integer in f32, so
    *any* summation order — single adder tree or per-shard partials + psum —
    produces bit-identical results.  This is what lets the parity asserts
    below be bitwise."""
    return np.asarray(RNG.integers(-4, 5, size=(n, O)), np.float32)


def _codebook_weights(n, O, X, integers=True):
    G = n // GROUP
    if integers:
        cb = RNG.integers(-4, 5, size=(X, GROUP, O)).astype(np.float32)
    else:
        cb = RNG.normal(size=(X, GROUP, O)).astype(np.float32)
    return cb[RNG.integers(0, X, G)].reshape(n, O)


def _gemv_problem(B=4, n=64, O=48, shared=False, integers=True):
    import jax.numpy as jnp
    from repro.core import build_grouped_tables, build_shared_grouped_tables

    x = jnp.asarray(np.abs(RNG.normal(size=(B, n))), jnp.float32)
    w = _codebook_weights(n, O, X=5, integers=integers) if shared else (
        _int_weights(n, O) if integers
        else np.asarray(RNG.normal(size=(n, O)), np.float32))
    w = jnp.asarray(w)
    spec, s = _spec_scale(x)
    if integers:
        s = jnp.float32(1.0)  # integer grid: exact arithmetic, see _int_weights
    if shared:
        T = build_shared_grouped_tables(w, spec, s, GROUP)
    else:
        T = build_grouped_tables(w, spec, s, GROUP)
    return x, T, spec, s


# ----------------------------------------------------------------------------
# Parity: sharded gather / fused / shared vs the single-device reference.
# ----------------------------------------------------------------------------


@multi_device
@pytest.mark.parametrize("model", [1, 2, 4, 8])
@pytest.mark.parametrize("path", ["gather", "fused", "shared"])
def test_gemv_parity_bitwise(model, path):
    """Exact-arithmetic GEMV: the sharded result is bit-identical to the
    single-device gather reference at every device count."""
    from repro.core import pcilt_linear

    x, T, spec, s = _gemv_problem(shared=(path == "shared"), integers=True)
    ref = pcilt_linear(x, T, spec, s, GROUP, path="gather")
    got = pcilt_linear(x, T, spec, s, GROUP, path=path, mesh=_mesh(model))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


@multi_device
@pytest.mark.parametrize("model", [2, 8])
@pytest.mark.parametrize("path", ["gather", "onehot", "kernel", "fused", "shared"])
def test_gemv_parity_gaussian(model, path):
    """Gaussian weights: allclose parity for every execution path."""
    from repro.core import pcilt_linear

    x, T, spec, s = _gemv_problem(shared=(path == "shared"), integers=False)
    ref = pcilt_linear(x, T, spec, s, GROUP, path="gather")
    got = pcilt_linear(x, T, spec, s, GROUP, path=path, mesh=_mesh(model))
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@multi_device
@pytest.mark.parametrize("model", [1, 2, 4, 8])
@pytest.mark.parametrize("path", ["gather", "fused", "shared"])
def test_conv2d_parity(model, path):
    """Strided-SAME conv2d (non-congruent extent — the PR 2 stride fix
    regime) stays allclose to the single-device gather reference."""
    import jax.numpy as jnp
    from repro.core import build_shared_grouped_tables, pcilt_conv2d

    B, H, W, C, kh, kw, Co = 2, 9, 9, 4, 3, 3, 16
    x = jnp.asarray(np.abs(RNG.normal(size=(B, H, W, C))), jnp.float32)
    f = jnp.asarray(RNG.normal(size=(kh, kw, C, Co)), jnp.float32)
    spec, s = _spec_scale(x)
    tables = None
    if path == "shared":
        tables = build_shared_grouped_tables(
            jnp.asarray(_codebook_weights(kh * kw * C, Co, X=4,
                                          integers=False)),
            spec, s, GROUP)
    ref = pcilt_conv2d(x, f, spec, s, GROUP, stride=2, tables=tables,
                       path="gather")
    got = pcilt_conv2d(x, f, spec, s, GROUP, stride=2, tables=tables,
                       path=path, mesh=_mesh(model))
    assert got.shape == ref.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@multi_device
@pytest.mark.parametrize("path", ["gather", "fused", "shared"])
def test_decode_batch1(path):
    """The decode regime proper: batch=1 GEMV, 4-way tensor parallel."""
    from repro.core import pcilt_linear

    x, T, spec, s = _gemv_problem(B=1, shared=(path == "shared"))
    ref = pcilt_linear(x, T, spec, s, GROUP, path="gather")
    got = pcilt_linear(x, T, spec, s, GROUP, path=path, mesh=_mesh(4))
    assert got.shape == (1, ref.shape[-1])
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


@multi_device
@pytest.mark.parametrize("path", ["gather", "fused", "shared"])
def test_divisibility_fallback(path):
    """G=12 over an 8-way model axis: falls back to replication — the exact
    single-device code path, so the result is bitwise identical."""
    import jax.numpy as jnp
    from repro.core import (build_grouped_tables, build_shared_grouped_tables,
                            mesh_shard_count, pcilt_linear)

    n, O = 24, 32  # G = 12, not divisible by 8
    x = jnp.asarray(np.abs(RNG.normal(size=(3, n))), jnp.float32)
    spec, s = _spec_scale(x)
    if path == "shared":
        T = build_shared_grouped_tables(
            jnp.asarray(_codebook_weights(n, O, X=3)), spec, s, GROUP)
    else:
        T = build_grouped_tables(jnp.asarray(_int_weights(n, O)), spec, s,
                                 GROUP)
    mesh = _mesh(8)
    assert mesh_shard_count(mesh, "model", 12) == 1
    ref = pcilt_linear(x, T, spec, s, GROUP, path=path)
    got = pcilt_linear(x, T, spec, s, GROUP, path=path, mesh=mesh)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


@multi_device
def test_table_pspec_divisibility_fallback():
    """The nn.module rule table applies the same fallback: a G the model
    axis does not divide replicates instead of sharding."""
    from jax.sharding import PartitionSpec as P
    from repro.nn.module import ShardingRules, pcilt_table_pspec

    rules = ShardingRules.for_mesh(_mesh(8))
    assert pcilt_table_pspec(64, rules=rules) == P("model", None, None)
    assert pcilt_table_pspec(12, rules=rules) == P(None, None, None)


# ----------------------------------------------------------------------------
# Sharded conv with in-VMEM im2col (PR 4): the fused/shared conv kernels run
# under shard_map with a seg_offset per shard — no host im2col detour.
# ----------------------------------------------------------------------------


@multi_device
@pytest.mark.parametrize("model", [1, 2, 4, 8])
@pytest.mark.parametrize("path", ["fused", "shared"])
def test_conv2d_in_vmem_im2col_bitwise(model, path):
    """Integer weights + scale=1: the sharded conv route (in-VMEM im2col per
    shard, one psum) is *bitwise* identical to the single-device gather
    reference at every device count — each shard's partial sum is exact, so
    summation order cannot matter."""
    import jax.numpy as jnp
    from repro.core import build_shared_grouped_tables, pcilt_conv2d

    B, H, W, C, kh, kw, Co = 2, 8, 8, 4, 3, 3, 16
    x = jnp.asarray(np.abs(RNG.normal(size=(B, H, W, C))), jnp.float32)
    n = kh * kw * C  # G = 18: shards at 1/2, falls back at 4/8 (18 % 4 != 0)
    spec, _ = _spec_scale(x)
    s = jnp.float32(1.0)  # integer grid: exact arithmetic (see _int_weights)
    tables = None
    if path == "shared":
        w = _codebook_weights(n, Co, X=4)
        tables = build_shared_grouped_tables(jnp.asarray(w), spec, s, GROUP)
        f = jnp.asarray(np.asarray(w).reshape(kh, kw, C, Co))
    else:
        f = jnp.asarray(_int_weights(n, Co).reshape(kh, kw, C, Co))
    ref = pcilt_conv2d(x, f, spec, s, GROUP, tables=tables, path="gather")
    got = pcilt_conv2d(x, f, spec, s, GROUP, tables=tables, path=path,
                       mesh=_mesh(model))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


@multi_device
@pytest.mark.parametrize("model", [2, 4])
def test_conv2d_in_vmem_im2col_strided_allclose(model):
    """Gaussian weights, stride-2 SAME (non-congruent extent): the in-VMEM
    sharded route stays allclose to the reference — G = 100 divides both
    tested model-axis sizes, so this genuinely shards."""
    import jax.numpy as jnp
    from repro.core import mesh_shard_count, pcilt_conv2d

    B, H, W, C, kh, kw, Co = 2, 9, 9, 8, 5, 5, 24
    x = jnp.asarray(np.abs(RNG.normal(size=(B, H, W, C))), jnp.float32)
    f = jnp.asarray(RNG.normal(size=(kh, kw, C, Co)), jnp.float32)
    spec, s = _spec_scale(x)
    mesh = _mesh(model)
    assert mesh_shard_count(mesh, "model", kh * kw * C // GROUP) == model
    ref = pcilt_conv2d(x, f, spec, s, GROUP, stride=2, path="gather")
    got = pcilt_conv2d(x, f, spec, s, GROUP, stride=2, path="fused",
                       mesh=mesh)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@multi_device
def test_sharded_conv_keys_local_shard_shape(tune_cache):
    """The conv kernels dispatched under shard_map consult the autotune
    cache with the *local* G — pre-tuning on the local shard shape with a
    concrete seg_offset populates exactly the key the sharded trace hits."""
    import jax.numpy as jnp
    from repro.core import build_grouped_tables, pcilt_conv2d
    from repro.kernels import ops
    from repro.kernels import autotune as atn

    B, H, W, C, kh, kw, Co, model = 1, 6, 6, 4, 3, 3, 16, 2
    x = jnp.asarray(np.abs(RNG.normal(size=(B, H, W, C))), jnp.float32)
    f = jnp.asarray(_int_weights(kh * kw * C, Co).reshape(kh, kw, C, Co))
    spec, _ = _spec_scale(x)
    s = jnp.float32(1.0)
    T = build_grouped_tables(f.reshape(-1, Co), spec, s, GROUP)
    G = T.shape[0]  # 18
    Gl = G // model
    ops.pcilt_fused_conv2d(x, T[:Gl], spec, s, GROUP, kh, kw,
                           seg_offset=0, n_total=G * GROUP, autotune=True)
    entries = json.load(open(tune_cache))
    keys = [k for k in entries if k.startswith("fused_conv2d|")]
    assert len(keys) == 1 and f"G={Gl}," in keys[0], keys
    # the sharded execution is a pure cache hit on that local key
    atn.TIMING_RUNS = 0
    got = pcilt_conv2d(x, f, spec, s, GROUP, path="fused", mesh=_mesh(model))
    ref = pcilt_conv2d(x, f, spec, s, GROUP, path="gather")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    assert atn.TIMING_RUNS == 0


@multi_device
def test_conv_layer_tune_keys_local_shard_shape(tune_cache):
    """Regression: PCILTConv2d.tune under a mesh must record the *local*
    shard's shape key (like PCILTLinear.tune) — the key the sharded
    shard_map dispatch actually looks up — and the later sharded call must
    be a pure cache hit."""
    import jax.numpy as jnp
    from repro.core import pcilt_conv2d
    from repro.core.serving import convert_conv_kernel
    from repro.kernels import autotune as atn

    model = 2
    x = jnp.asarray(np.abs(RNG.normal(size=(2, 8, 8, 4))), jnp.float32)
    f = jnp.asarray(_int_weights(3 * 3 * 4, 8).reshape(3, 3, 4, 8))
    spec, _ = _spec_scale(x)
    s = jnp.float32(1.0)  # exact arithmetic -> bitwise parity
    conv = convert_conv_kernel(f, spec, s, group=GROUP, mesh=_mesh(model))
    conv.tune(x)  # G = 18 -> local G 9
    entries = json.load(open(tune_cache))
    keys = [k for k in entries if k.startswith("fused_conv2d|")]
    assert len(keys) == 1 and "G=9," in keys[0], keys
    atn.reset_cache(tune_cache)
    atn.TIMING_RUNS = 0
    ref = pcilt_conv2d(x, f, spec, s, GROUP, path="gather")
    np.testing.assert_array_equal(np.asarray(conv(x, path="fused")),
                                  np.asarray(ref))
    assert atn.TIMING_RUNS == 0, "sharded dispatch missed the tuned entry"


@multi_device
def test_conv_layer_shared_mesh_preshards_pool(tune_cache):
    """A shared PCILTConv2d converted with mesh= shards and places the pool
    at conversion (offline), keeps per-device memory at local-pool scale,
    and tunes the local-shard shared_conv2d key."""
    import jax.numpy as jnp
    from repro.core import pcilt_conv2d
    from repro.core.serving import convert_conv_kernel

    model, n, Co = 2, 36, 8
    w = _codebook_weights(n, Co, X=4)
    f = jnp.asarray(np.asarray(w).reshape(3, 3, 4, Co))
    x = jnp.asarray(np.abs(RNG.normal(size=(2, 8, 8, 4))), jnp.float32)
    spec, _ = _spec_scale(x)
    s = jnp.float32(1.0)
    conv = convert_conv_kernel(f, spec, s, group=GROUP, shared=True,
                               mesh=_mesh(model))
    assert conv.shard_pools is not None
    assert conv.shard_pools.n_shards == model
    assert conv.per_device_table_bytes() <= conv.table_bytes()
    conv.tune(x)
    keys = [k for k in json.load(open(tune_cache))
            if k.startswith("shared_conv2d|")]
    assert len(keys) == 1 and "G=9," in keys[0], keys
    ref = pcilt_conv2d(x, f, spec, s, GROUP, path="gather")
    np.testing.assert_array_equal(np.asarray(conv(x, path="shared")),
                                  np.asarray(ref))


# ----------------------------------------------------------------------------
# Fused dwconv1d under the multi-device tier: plain parity (the kernel is
# unsharded — depthwise has no segment axis — but must coexist with forced
# multi-device platforms).
# ----------------------------------------------------------------------------


@multi_device
def test_fused_dwconv1d_parity_under_forced_devices():
    import jax.numpy as jnp
    from repro.core import pcilt_depthwise_conv1d

    x = jnp.asarray(np.abs(RNG.normal(size=(2, 24, 8))), jnp.float32)
    f = jnp.asarray(RNG.normal(size=(4, 8)), jnp.float32)
    spec, s = _spec_scale(x)
    ref = pcilt_depthwise_conv1d(x, f, spec, s, path="gather")
    got = pcilt_depthwise_conv1d(x, f, spec, s, path="fused")
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


# ----------------------------------------------------------------------------
# Sharded shared pools: local-X memory scaling and structure.
# ----------------------------------------------------------------------------


@multi_device
def test_shard_pool_memory_scales_with_local_cardinality():
    """Segments arranged so each half of the layer references only half the
    codebook: per-shard pools keep local X = X/2 rows and per-device memory
    drops accordingly, while the materialized tables stay identical."""
    import jax.numpy as jnp
    from repro.core import (build_shared_grouped_tables,
                            shard_shared_grouped_tables)

    n, O, X = 64, 32, 4
    G = n // GROUP
    cb = RNG.integers(-4, 5, size=(X, GROUP, O)).astype(np.float32)
    picks = np.concatenate([RNG.integers(0, 2, G // 2),
                            RNG.integers(2, 4, G // 2)])
    w = jnp.asarray(cb[picks].reshape(n, O))
    x = jnp.asarray(np.abs(RNG.normal(size=(2, n))), jnp.float32)
    spec, s = _spec_scale(x)
    st = build_shared_grouped_tables(w, spec, s, GROUP)
    assert st.pool_cardinality == X
    sp = shard_shared_grouped_tables(st, 2)
    assert sp.shard_cards == (2, 2) and sp.max_cardinality == 2
    assert sp.local_pool_bytes() < st.pool_bytes()
    np.testing.assert_array_equal(np.asarray(sp.materialize()),
                                  np.asarray(st.materialize()))


@multi_device
def test_shard_pool_mesh_mismatch_raises():
    from repro.core import pcilt_linear, shard_shared_grouped_tables

    x, st, spec, s = _gemv_problem(shared=True)
    sp = shard_shared_grouped_tables(st, 4)
    with pytest.raises(ValueError, match="4 shards"):
        pcilt_linear(x, sp, spec, s, GROUP, path="shared", mesh=_mesh(2))
    with pytest.raises(ValueError, match="mesh"):
        pcilt_linear(x, sp, spec, s, GROUP, path="shared")
    with pytest.raises(ValueError, match="shared"):
        pcilt_linear(x, sp, spec, s, GROUP, path="fused", mesh=_mesh(4))


@multi_device
def test_generalized_plan_refuses_to_shard():
    """A generalized SegmentPlan cannot shard along contiguous G-blocks:
    combining plan= with a sharding mesh raises instead of silently keeping
    full per-device table residency."""
    import jax.numpy as jnp
    from repro.core import SegmentPlan, build_grouped_tables, pcilt_linear

    x, T, spec, s = _gemv_problem()
    plan = SegmentPlan(
        np.array([[1, 0], [3, 2], [5, 4], [7, 6]], np.int32))
    Tp = build_grouped_tables(jnp.asarray(_int_weights(8, 16)), spec, s,
                              GROUP, plan=plan)
    with pytest.raises(ValueError, match="cannot be sharded"):
        pcilt_linear(x[:, :8], Tp, spec, s, GROUP, plan=plan, path="gather",
                     mesh=_mesh(4))
    # mesh=None executes the plan replicated, as the error message says
    out = pcilt_linear(x[:, :8], Tp, spec, s, GROUP, plan=plan, path="gather")
    assert out.shape == (x.shape[0], 16)


# ----------------------------------------------------------------------------
# Serving conversion: placement, per-device memory, local-shard autotune.
# ----------------------------------------------------------------------------


@multi_device
def test_convert_kernel_mesh_places_table_shards():
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.core import pcilt_linear
    from repro.core.serving import convert_kernel

    n, O, D = 64, 48, 4
    x = jnp.asarray(np.abs(RNG.normal(size=(2, n))), jnp.float32)
    w = jnp.asarray(_int_weights(n, O))
    spec, s = _spec_scale(x)
    s = jnp.float32(1.0)  # exact arithmetic -> bitwise parity
    lin = convert_kernel(w, spec, s, GROUP, mesh=_mesh(D))
    assert lin.shard_count == D
    assert lin.tables.sharding.spec == P("model", None, None)
    assert lin.tables.addressable_shards[0].data.shape[0] == lin.n_segments // D
    assert lin.per_device_table_bytes() * D == lin.table_bytes()
    ref = pcilt_linear(x, jnp.asarray(np.asarray(lin.tables)), spec, s, GROUP)
    for path in ("gather", "fused"):
        np.testing.assert_array_equal(np.asarray(lin(x, path=path)),
                                      np.asarray(ref))


@multi_device
def test_convert_kernel_mesh_shared_pool():
    import jax.numpy as jnp
    from repro.core.serving import convert_kernel

    n, O, D = 64, 32, 4
    x = jnp.asarray(np.abs(RNG.normal(size=(2, n))), jnp.float32)
    w = jnp.asarray(_codebook_weights(n, O, X=5))
    spec, s = _spec_scale(x)
    s = jnp.float32(1.0)  # exact arithmetic -> bitwise parity
    ref_lin = convert_kernel(w, spec, s, GROUP, shared=True)
    lin = convert_kernel(w, spec, s, GROUP, shared=True, mesh=_mesh(D))
    assert lin.shard_pools is not None and lin.shard_pools.n_shards == D
    # shared-path memory follows the padded *local* pool, never G
    assert lin.per_device_table_bytes() <= lin.table_bytes()
    for path in ("gather", "shared"):
        np.testing.assert_array_equal(
            np.asarray(lin(x, path=path)),
            np.asarray(ref_lin(x, path="gather")))


@multi_device
def test_tune_keys_local_shard_shape_no_collision(tune_cache):
    """Caches tuned at different device counts key on the local shard shape
    and must not collide: both entries coexist and both later dispatches are
    pure hits."""
    import jax.numpy as jnp
    from repro.core.serving import convert_kernel
    from repro.kernels import autotune as atn

    n, O = 64, 48  # G = 32 -> local G 8 at model=4, 16 at model=2
    x = jnp.asarray(np.abs(RNG.normal(size=(4, n))), jnp.float32)
    w = jnp.asarray(_int_weights(n, O))
    spec, s = _spec_scale(x)
    s = jnp.float32(1.0)  # exact arithmetic -> bitwise parity
    outs = {}
    for model in (4, 2):
        lin = convert_kernel(w, spec, s, GROUP, mesh=_mesh(model))
        outs[model] = np.asarray(lin.tune(x))
    np.testing.assert_array_equal(outs[4], outs[2])
    entries = json.load(open(tune_cache))
    keys = sorted(k for k in entries if k.startswith("fused_gemv|"))
    assert len(keys) == 2, f"expected one key per device count, got {keys}"
    assert any("G=8," in k for k in keys) and any("G=16," in k for k in keys)
    assert not any("G=32," in k for k in keys), "global-shape key leaked"
    # warm cache: re-tuning both device counts performs zero timing runs
    atn.reset_cache(tune_cache)
    atn.TIMING_RUNS = 0
    for model in (4, 2):
        convert_kernel(w, spec, s, GROUP, mesh=_mesh(model)).tune(x)
    assert atn.TIMING_RUNS == 0


@multi_device
def test_tune_under_mesh_records_null_on_failure(tune_cache, monkeypatch):
    """A sharded tune whose candidates all fail raises, naming the
    local-shard key, and records nothing; the untuned sharded call still
    executes."""
    import jax.numpy as jnp
    from repro.core.serving import convert_kernel
    from repro.kernels import autotune as atn

    def boom(fn, reps, warmup):
        raise RuntimeError("no candidate can run")

    monkeypatch.setattr(atn, "_time_one", boom)
    x = jnp.asarray(np.abs(RNG.normal(size=(4, 64))), jnp.float32)
    w = jnp.asarray(_int_weights(64, 48))
    spec, s = _spec_scale(x)
    lin = convert_kernel(w, spec, s, GROUP, mesh=_mesh(4))
    with pytest.raises(RuntimeError, match=r"fused_gemv\|.*G=8,"):
        lin.tune(x)
    assert not os.path.exists(tune_cache)
    out = lin(x, path="fused")  # the untuned sharded dispatch
    assert out.shape == (4, 48)
