"""Shared-pool (extension 3) fused path: the segment-dedup builder, the
pointer-resolving Pallas kernels, and their dispatch through the autotune
lookup table.  ``path="shared"`` must be bit-consistent with the
``SharedGroupedTables`` pointer-gather reference (f32 accumulation
tolerance) across symmetric/asymmetric specs at 2–4 bits."""

import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    QuantSpec, calibrate, build_grouped_tables, build_shared_grouped_tables,
    pcilt_linear, shared_pool_bytes,
)
from repro.core.lut_layers import pcilt_conv2d
from repro.kernels import autotune as atn
from repro.kernels import ops

RNG = np.random.default_rng(3)


@pytest.fixture(autouse=True)
def _isolated_tune_cache(tmp_path):
    atn.reset_cache(str(tmp_path / "tiles.json"))
    atn.TIMING_RUNS = 0
    yield
    atn.TIMING_RUNS = 0
    atn.reset_cache()


def _codebook_weights(n, O, group, X):
    """[n, O] weights whose [group, O] segments are drawn from an X-entry
    codebook — the weight-clustered / low-cardinality regime ext. 3 targets."""
    G = -(-n // group)
    cb = RNG.normal(size=(X, group, O))
    w = cb[RNG.integers(0, X, G)].reshape(G * group, O)[:n]
    return jnp.asarray(w, jnp.float32)


# ----------------------------------------------------------------------------
# Builder
# ----------------------------------------------------------------------------


def test_builder_dedups_and_materializes_exactly():
    spec = QuantSpec(2)
    w = _codebook_weights(24, 10, group=2, X=4)
    st = build_shared_grouped_tables(w, spec, 0.5, group=2)
    assert st.pool_cardinality <= 4 and st.n_segments == 12
    T = build_grouped_tables(w, spec, 0.5, group=2)
    np.testing.assert_array_equal(np.asarray(st.materialize()), np.asarray(T))


def test_builder_generic_fn_matches_grouped():
    from repro.core import log_mul_fn

    spec = QuantSpec(2)
    w = _codebook_weights(8, 5, group=2, X=2)
    st = build_shared_grouped_tables(w, spec, 0.7, group=2, fn=log_mul_fn)
    T = build_grouped_tables(w, spec, 0.7, group=2, fn=log_mul_fn)
    np.testing.assert_allclose(np.asarray(st.materialize()), np.asarray(T),
                               rtol=1e-6, atol=1e-6)


def test_pool_memory_accounting():
    spec = QuantSpec(2)
    group, O = 2, 16
    w = _codebook_weights(64, O, group=group, X=3)
    st = build_shared_grouped_tables(w, spec, 0.5, group=group)
    X, G = st.pool_cardinality, st.n_segments
    want = shared_pool_bytes(X, spec.bits, group, O, 4, n_segments=G)
    assert st.pool_bytes() == want
    assert st.dense_bytes() == G * (1 << (spec.bits * group)) * O * 4
    assert st.dedup_ratio > 5  # G=32 vs X<=3: order-of-magnitude shrink


# ----------------------------------------------------------------------------
# GEMV parity: path="shared" vs the pointer-gather reference
# ----------------------------------------------------------------------------


@pytest.mark.parametrize("bits,symmetric", [
    (2, False), (2, True), (3, False), (3, True), (4, False), (4, True),
])
def test_shared_gemv_parity_specs(bits, symmetric):
    spec = QuantSpec(bits, symmetric=symmetric)
    B, n, O, group = 8, 24, 40, 2
    lo = -2.0 if symmetric else 0.0
    x = jnp.asarray(RNG.uniform(lo, 3, (B, n)), jnp.float32)
    w = _codebook_weights(n, O, group, X=5)
    s = calibrate(x, spec)
    st = build_shared_grouped_tables(w, spec, s, group)
    want = pcilt_linear(x, st, spec, s, group, path="gather")
    got = pcilt_linear(x, st, spec, s, group, path="shared")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("B,n,O,group,X", [
    (7, 30, 130, 2, 3),    # odd B, non-128-multiple O
    (3, 36, 257, 3, 4),    # G=12 with non-trivial splits
    (1, 16, 5, 1, 2),      # decode-style B=1, group=1
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_shared_gemv_parity_shapes(B, n, O, group, X, dtype):
    spec = QuantSpec(2)
    x = jnp.asarray(RNG.uniform(0, 3, (B, n)), jnp.float32)
    w = _codebook_weights(n, O, group, X)
    s = calibrate(x, spec)
    st = build_shared_grouped_tables(w, spec, s, group)
    want = pcilt_linear(x, st, spec, s, group, path="gather")
    st.pool = st.pool.astype(dtype)
    got = pcilt_linear(x, st, spec, s, group, path="shared")
    tol = dict(rtol=1e-4, atol=1e-4) if dtype == jnp.float32 else \
        dict(rtol=5e-2, atol=5e-1)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **tol)


def test_shared_matches_dense_fused():
    """The two fused pipelines agree: the pool resolves to the same tables."""
    spec = QuantSpec(2)
    B, n, O, group = 8, 32, 48, 2
    x = jnp.asarray(RNG.uniform(0, 3, (B, n)), jnp.float32)
    w = _codebook_weights(n, O, group, X=4)
    s = calibrate(x, spec)
    st = build_shared_grouped_tables(w, spec, s, group)
    dense = pcilt_linear(x, st.materialize(), spec, s, group, path="fused")
    got = pcilt_linear(x, st, spec, s, group, path="shared")
    np.testing.assert_allclose(np.asarray(got), np.asarray(dense),
                               rtol=1e-5, atol=1e-5)


def test_shared_path_requires_pool_and_rejects_plans():
    from repro.core import SegmentPlan

    spec = QuantSpec(2)
    x = jnp.asarray(RNG.uniform(0, 3, (4, 8)), jnp.float32)
    w = _codebook_weights(8, 6, 2, X=2)
    s = calibrate(x, spec)
    T = build_grouped_tables(w, spec, s, 2)
    with pytest.raises(ValueError, match="shared"):
        pcilt_linear(x, T, spec, s, 2, path="shared")
    st = build_shared_grouped_tables(w, spec, s, 2)
    with pytest.raises(ValueError, match="fused"):
        pcilt_linear(x, st, spec, s, 2, path="fused")
    with pytest.raises(ValueError, match="contiguous"):
        pcilt_linear(x, st, spec, s, 2, plan=SegmentPlan.contiguous(8, 2),
                     path="shared")


# ----------------------------------------------------------------------------
# Conv parity
# ----------------------------------------------------------------------------


@pytest.mark.parametrize("B,H,W,C,kh,kw,stride,O,bits,group,padding", [
    (2, 8, 8, 3, 3, 3, 1, 5, 2, 2, "SAME"),     # ragged n=27 -> pad_n
    (1, 9, 7, 4, 3, 3, 2, 12, 2, 2, "SAME"),    # strided, odd spatial
    (1, 8, 8, 2, 3, 3, 2, 6, 2, 2, "SAME"),     # strided, even spatial
    (2, 8, 8, 2, 5, 5, 1, 6, 4, 2, "VALID"),    # 5x5 paper filter, 4-bit
    (1, 6, 6, 4, 3, 3, 1, 130, 3, 3, "SAME"),   # non-128-multiple O
])
def test_shared_conv2d_parity(B, H, W, C, kh, kw, stride, O, bits, group,
                              padding):
    spec = QuantSpec(bits)
    x = jnp.asarray(RNG.uniform(0, 2, (B, H, W, C)), jnp.float32)
    n = kh * kw * C
    w = _codebook_weights(n + (-n) % group, O, group, X=4)
    f = jnp.asarray(np.asarray(w)[:n].reshape(kh, kw, C, O), jnp.float32)
    s = calibrate(x, spec)
    want = pcilt_conv2d(x, f, spec, s, group, stride=stride, padding=padding,
                        path="gather")
    got = pcilt_conv2d(x, f, spec, s, group, stride=stride, padding=padding,
                       path="shared")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def _cycled_pool(n, O, group, X, spec, s):
    """A shared pool of exactly ``X`` rows: the ``[group, O]`` segments
    cycle through an ``X``-entry codebook (``n`` weight rows, zero rows
    past ``n`` up to the group multiple)."""
    G = -(-n // group)
    cb = RNG.normal(size=(X, group, O))
    w = cb[np.arange(G) % X].reshape(G * group, O)
    w[n:] = 0.0
    st = build_shared_grouped_tables(jnp.asarray(w, jnp.float32), spec, s,
                                     group)
    return w[:n], st


@pytest.mark.parametrize("B,n,O,group,X", [
    (5, 40, 300, 2, 13),    # last output tile 44 of 128 columns
    (16, 24, 200, 2, 12),   # 16 rows, as the served head; tile 72 of 128
    (3, 30, 129, 3, 7),     # one column past a whole tile
])
def test_shared_gemv_ragged_tile_reads_pool_in_place(B, n, O, group, X):
    """The kernel reads the stored ``[X, V, O]`` pool with a ragged last
    output tile (``O`` no multiple of ``Ob``) and a pool of rows no
    multiple of 8, against the pointer-gather reference."""
    spec = QuantSpec(2)
    x = jnp.asarray(RNG.uniform(0, 3, (B, n)), jnp.float32)
    s = calibrate(x, spec)
    _, st = _cycled_pool(n, O, group, X, spec, s)
    assert st.pool.shape[0] == X and O % 128
    want = pcilt_linear(x, st, spec, s, group, path="gather")
    G = st.n_segments
    got = ops.pcilt_shared_gemv(x, st.pool, st.seg_idx, spec, s, group,
                                tiles=(8, G, 128))
    assert got.shape == (B, O)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("B,H,W,C,kh,kw,stride,O,X", [
    (1, 6, 6, 3, 3, 3, 1, 200, 5),   # last output tile 72 of 128
    (2, 7, 5, 2, 3, 3, 2, 131, 11),  # strided; three columns past a tile
])
def test_shared_conv2d_ragged_tile_reads_pool_in_place(B, H, W, C, kh, kw,
                                                       stride, O, X):
    """The conv kernel shares the GEMV's pool reader: ragged last output
    tile, pool rows no multiple of 8, against the gather reference."""
    spec, group = QuantSpec(2), 2
    x = jnp.asarray(RNG.uniform(0, 2, (B, H, W, C)), jnp.float32)
    n = kh * kw * C
    s = calibrate(x, spec)
    w, st = _cycled_pool(n, O, group, X, spec, s)
    assert st.pool.shape[0] % 8 and O % 128
    f = jnp.asarray(w.reshape(kh, kw, C, O), jnp.float32)
    want = pcilt_conv2d(x, f, spec, s, group, stride=stride, path="gather")
    got = ops.pcilt_shared_conv2d(x, st.pool, st.seg_idx, spec, s, group,
                                  kh, kw, stride=stride,
                                  tiles=(1, st.n_segments, 128))
    assert got.shape == want.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_shared_conv2d_prebuilt_pool_bf16():
    from repro.core.pcilt import build_shared_grouped_tables as build

    spec = QuantSpec(2)
    B, H, W, C, kh, kw, O, group = 2, 8, 8, 2, 3, 3, 6, 2
    x = jnp.asarray(RNG.uniform(0, 2, (B, H, W, C)), jnp.float32)
    n = kh * kw * C
    w = _codebook_weights(n, O, group, X=3)
    f = jnp.asarray(np.asarray(w).reshape(kh, kw, C, O), jnp.float32)
    s = calibrate(x, spec)
    st = build(jnp.asarray(w), spec, s, group)
    want = pcilt_conv2d(x, f, spec, s, group, path="gather")
    st.pool = st.pool.astype(jnp.bfloat16)
    got = pcilt_conv2d(x, f, spec, s, group, tables=st, path="shared")
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=5e-2, atol=5e-1)


# ----------------------------------------------------------------------------
# Dispatch: autotune lookup table with the X-carrying shape keys
# ----------------------------------------------------------------------------


def test_shared_dispatch_tunes_once_with_x_key(tmp_path):
    path = str(tmp_path / "tiles.json")
    atn.reset_cache(path)
    spec = QuantSpec(2)
    B, n, O, group = 8, 24, 32, 2
    x = jnp.asarray(RNG.uniform(0, 3, (B, n)), jnp.float32)
    w = _codebook_weights(n, O, group, X=3)
    s = calibrate(x, spec)
    st = build_shared_grouped_tables(w, spec, s, group)
    out1 = ops.pcilt_shared_gemv(x, st.pool, st.seg_idx, spec, s, group,
                                 autotune=True)
    assert atn.TIMING_RUNS > 0
    entries = json.load(open(path))
    key = next(iter(entries))
    assert key.startswith("shared_gemv") and f"X={st.pool_cardinality}" in key

    # "Second process": warm cache, zero timing runs, same result.
    atn.reset_cache(path)
    atn.TIMING_RUNS = 0
    out2 = ops.pcilt_shared_gemv(x, st.pool, st.seg_idx, spec, s, group,
                                 autotune=True)
    assert atn.TIMING_RUNS == 0
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2), rtol=1e-6)


def test_shared_candidate_generators_valid():
    for B, G, V, O, X in [(1, 7, 4, 3, 2), (8, 512, 16, 1024, 16),
                          (128, 24, 256, 384, 5)]:
        cands = atn.shared_gemv_candidates(B, G, V, O, X)
        assert cands and all(G % c.Gb == 0 for c in cands)
        assert any(c.Gb == G for c in cands)  # stage-everything always present
    for Ho, G, V, O, X in [(5, 9, 16, 12, 3), (28, 100, 16, 350, 7)]:
        cands = atn.shared_conv2d_candidates(Ho, G, V, O, X)
        assert cands and all(G % c.Gb == 0 and Ho % c.row_tile == 0
                             for c in cands)
        assert any(c.Gb == G for c in cands)


# ----------------------------------------------------------------------------
# Serving conversion
# ----------------------------------------------------------------------------


def test_convert_kernel_shared_roundtrip():
    from repro.core.serving import convert_kernel

    spec = QuantSpec(2)
    x = jnp.asarray(RNG.uniform(0, 1, (4, 24)), jnp.float32)
    k = jnp.asarray(np.asarray(_codebook_weights(24, 32, 2, X=4)), jnp.float32)
    s = calibrate(x, spec)
    lin = convert_kernel(k, spec, s, group=2, shared=True)
    assert lin.tables is None and lin.shared is not None
    want = lin(x, path="gather")
    got = lin(x, path="shared")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
    # the deployed representation is the pool, not the dense tables
    dense = lin.shared.dense_bytes()
    assert lin.table_bytes() < dense
    with pytest.raises(ValueError, match="shared"):
        lin(x, path="fused")


def test_convert_kernel_weight_bits_enables_dedup():
    """Low-bit weight quantization lowers segment cardinality — the ext.-3
    precondition — and the shared layer still matches the dense reference."""
    from repro.core.serving import convert_kernel

    spec = QuantSpec(2)
    x = jnp.asarray(RNG.uniform(0, 1, (4, 32)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(32, 1)), jnp.float32)
    s = calibrate(x, spec)
    lin = convert_kernel(k, spec, s, group=2, weight_bits=2, shared=True)
    # group*out = 2 values from a 4-level grid -> <= 16 distinct segments
    # against G = 16; random draws collide, so the pool strictly shrinks.
    assert lin.shared.pool_cardinality < lin.shared.n_segments
    ref = convert_kernel(k, spec, s, group=2, weight_bits=2)
    np.testing.assert_allclose(
        np.asarray(lin(x, path="shared")),
        np.asarray(ref(x, path="gather")), rtol=1e-4, atol=1e-4)


def test_serving_tune_shared_populates_cache(tmp_path):
    from repro.core.serving import convert_kernel

    atn.reset_cache(str(tmp_path / "tiles.json"))
    spec = QuantSpec(2)
    x = jnp.asarray(RNG.uniform(0, 1, (4, 24)), jnp.float32)
    k = jnp.asarray(np.asarray(_codebook_weights(24, 32, 2, X=4)), jnp.float32)
    s = calibrate(x, spec)
    lin = convert_kernel(k, spec, s, group=2, shared=True)
    want = lin(x, path="gather")
    got = lin.tune(x)
    assert atn.TIMING_RUNS > 0
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
    atn.TIMING_RUNS = 0
    np.testing.assert_allclose(np.asarray(lin(x, path="shared")),
                               np.asarray(want), rtol=1e-4, atol=1e-4)
    assert atn.TIMING_RUNS == 0
