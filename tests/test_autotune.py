"""Persistent tile-autotune lookup table: miss -> tune-once-and-record,
hit -> zero-cost dispatch (zero timing runs), across simulated processes."""

import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import QuantSpec, calibrate, build_grouped_tables
from repro.kernels import autotune as atn
from repro.kernels import ops, ref

RNG = np.random.default_rng(7)


@pytest.fixture
def tune_cache(tmp_path):
    """Point the autotuner at a private cache file; restore afterwards."""
    path = str(tmp_path / "tiles.json")
    atn.reset_cache(path)
    atn.TIMING_RUNS = 0
    yield path
    atn.TIMING_RUNS = 0
    atn.reset_cache()


def _problem(B=8, n=64, O=256, bits=2, group=2):
    spec = QuantSpec(bits)
    x = jnp.asarray(RNG.uniform(0, 3, (B, n)), jnp.float32)
    w = jnp.asarray(RNG.normal(size=(n, O)), jnp.float32)
    s = calibrate(x, spec)
    T = build_grouped_tables(w, spec, s, group)
    return x, T, spec, s, group


def test_miss_tunes_then_hit_is_free(tune_cache):
    x, T, spec, s, group = _problem()
    out1 = ops.pcilt_fused_gemv(x, T, spec, s, group, autotune=True)
    assert atn.TIMING_RUNS > 0, "cache miss must time candidates"
    assert os.path.exists(tune_cache)
    entry = next(iter(json.load(open(tune_cache)).values()))
    assert entry["candidates"] >= 1 and entry["tiles"]["Gb"] >= 1

    # "Second process": fresh in-memory cache loaded from the same file.
    atn.reset_cache(tune_cache)
    atn.TIMING_RUNS = 0
    out2 = ops.pcilt_fused_gemv(x, T, spec, s, group, autotune=True)
    assert atn.TIMING_RUNS == 0, "warm cache must perform zero timing runs"
    np.testing.assert_allclose(out1, out2, rtol=1e-6)


def test_round_trip_returns_same_tiles(tune_cache):
    x, T, spec, s, group = _problem()
    B, O = x.shape[0], T.shape[-1]
    G, V = T.shape[0], T.shape[1]
    key = atn.shape_key("fused_gemv", dtype=T.dtype, backend="cpu",
                        B=B, G=G, V=V, O=O, g=group, bits=spec.bits)
    ops.pcilt_fused_gemv(x, T, spec, s, group, autotune=True)
    first = atn.lookup(key)
    assert first is not None
    atn.reset_cache(tune_cache)
    assert atn.lookup(key) == first


def test_lookup_only_dispatch_never_times(tune_cache):
    """Without autotune=True a miss falls back to the heuristic silently."""
    x, T, spec, s, group = _problem()
    ops.pcilt_fused_gemv(x, T, spec, s, group)  # autotune defaults off
    assert atn.TIMING_RUNS == 0
    assert not os.path.exists(tune_cache)


def test_host_kernels_route_through_cache(tune_cache):
    """Host-packed gemv/conv2d dispatch also tunes and stays correct."""
    off = jnp.asarray(RNG.integers(0, 16, (8, 12)), jnp.int32)
    tab = jnp.asarray(RNG.normal(size=(12, 16, 40)), jnp.float32)
    got = ops.pcilt_gemv(off, tab, autotune=True)
    assert atn.TIMING_RUNS > 0
    np.testing.assert_allclose(got, ref.pcilt_gemv_ref(off, tab),
                               rtol=1e-5, atol=1e-5)
    runs_after_gemv = atn.TIMING_RUNS
    offc = jnp.asarray(RNG.integers(0, 8, (1, 6, 6, 3)), jnp.int32)
    tabc = jnp.asarray(RNG.normal(size=(3, 8, 20)), jnp.float32)
    gotc = ops.pcilt_conv2d(offc, tabc, autotune=True)
    assert atn.TIMING_RUNS > runs_after_gemv
    np.testing.assert_allclose(gotc, ref.pcilt_conv2d_ref(offc, tabc),
                               rtol=1e-5, atol=1e-5)
    # both hits on re-dispatch
    atn.TIMING_RUNS = 0
    ops.pcilt_gemv(off, tab, autotune=True)
    ops.pcilt_conv2d(offc, tabc, autotune=True)
    assert atn.TIMING_RUNS == 0


def test_serving_tune_populates_cache(tune_cache):
    from repro.core.serving import convert_kernel

    spec = QuantSpec(2)
    x = jnp.asarray(RNG.uniform(0, 1, (4, 24)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(24, 32)), jnp.float32)
    lin = convert_kernel(k, spec, calibrate(x, spec), group=2)
    want = lin(x, path="gather")
    got = lin.tune(x)
    assert atn.TIMING_RUNS > 0
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    atn.TIMING_RUNS = 0
    np.testing.assert_allclose(lin(x, path="fused"), want,
                               rtol=1e-4, atol=1e-4)
    assert atn.TIMING_RUNS == 0


def test_concurrent_saves_keep_newest_per_key(tune_cache):
    """Regression: a process must merge back only keys it recorded itself.

    Two interleaved caches share one file.  Cache B re-records key "a" after
    cache A loaded the stale copy; when A later records its own key "b", A's
    save must not clobber B's newer "a" with A's stale startup copy ("last
    writer wins per key only")."""
    t = atn.TileConfig(Bb=8, Gb=1, Ob=128)
    seed = atn.TileCache(tune_cache)
    seed.record("a", atn.TileConfig(Bb=8, Gb=1, Ob=1), 5.0, 1)

    cache_a = atn.TileCache(tune_cache)  # loads a@v1
    cache_b = atn.TileCache(tune_cache)  # loads a@v1
    newer = atn.TileConfig(Bb=16, Gb=2, Ob=256)
    cache_b.record("a", newer, 3.0, 2)   # concurrent tuner updates "a"
    cache_a.record("b", t, 7.0, 1)       # we only recorded "b"

    final = atn.TileCache(tune_cache)
    assert final.lookup("a") == newer, "stale startup copy clobbered newer entry"
    assert final.lookup("b") == t


def test_failed_tune_records_null_not_nan(tune_cache):
    """An all-candidates-failed tune raises (a kernel the backend cannot
    run at any tiling must not be hidden behind an untimed fallback) and
    records nothing; an untimed entry that is recorded is strict JSON
    (us: null), never a bare NaN token that breaks strict parsers/jq."""
    cands = [atn.TileConfig(Bb=8, Gb=1, Ob=128)]

    def bench(cfg):
        raise RuntimeError("no candidate can run")

    with pytest.raises(RuntimeError, match="none of 1 candidate"):
        atn.tune("k|dtype=float32|backend=cpu", cands, bench)
    assert not os.path.exists(tune_cache)
    atn.get_cache().record("k|dtype=float32|backend=cpu", cands[0], None, 0)
    raw = open(tune_cache).read()
    assert "NaN" not in raw
    entry = json.loads(raw)["k|dtype=float32|backend=cpu"]  # strict parse ok
    assert entry["us"] is None and entry["candidates"] == 0
    # lookup tolerates the null timing and returns the recorded tiles
    atn.reset_cache(tune_cache)
    assert atn.lookup("k|dtype=float32|backend=cpu") == cands[0]


def test_record_sanitizes_nonfinite_us(tune_cache):
    atn.get_cache().record("k2", atn.TileConfig(Bb=8, Gb=1, Ob=128),
                           float("nan"), 1)
    assert json.load(open(tune_cache))["k2"]["us"] is None


def test_legacy_nan_cache_file_does_not_break_record(tune_cache):
    """A tiles.json written by older code with a bare `us: NaN` entry
    (json.load accepts it) must not crash later record()s under
    allow_nan=False — the legacy timing is rewritten as null."""
    with open(tune_cache, "w") as f:
        json.dump({"legacy": {"tiles": {"Bb": 8, "Gb": 1, "Ob": 128},
                              "us": float("nan"), "candidates": 1}}, f)
    cache = atn.TileCache(tune_cache)
    cache.record("fresh", atn.TileConfig(Bb=8, Gb=2, Ob=128), 4.2, 1)
    raw = open(tune_cache).read()
    assert "NaN" not in raw
    entries = json.loads(raw)
    assert entries["legacy"]["us"] is None and entries["fresh"]["us"] == 4.2
    assert atn.TileCache(tune_cache).lookup("legacy") is not None


def test_candidate_generators_valid():
    for B, G, V, O in [(1, 7, 4, 3), (8, 512, 16, 1024), (128, 24, 256, 384)]:
        cands = atn.gemv_candidates(B, G, V, O)
        assert cands and all(G % c.Gb == 0 for c in cands)
    for Ho, G, V, O in [(5, 9, 16, 12), (28, 100, 16, 350)]:
        cands = atn.conv2d_candidates(Ho, G, V, O)
        assert cands and all(G % c.Gb == 0 and Ho % c.row_tile == 0
                             for c in cands)
    for T, C, V, k in [(16, 6, 256, 4), (1, 192, 256, 4), (130, 129, 16, 2)]:
        cands = atn.dwconv1d_candidates(T, C, V, k)
        assert cands and all(T % c.Bb == 0 and C % c.Ob == 0 for c in cands)


# ----------------------------------------------------------------------------
# Analytic VMEM scratch bound (_fit_scratch_gb): replaces try-compile pruning.
# ----------------------------------------------------------------------------


def _shared_onehot_bytes(cfg, B, V, X, itemsize):
    """Per-grid-step scratch of the shared GEMV at tiling ``cfg``: f32
    one-hot [Bb, Gb, V] + f32 counts [Bb, V, X] + staged [V, X, Ob] pool."""
    return (cfg.Bb * cfg.Gb * V * 4 + cfg.Bb * V * X * 4
            + V * X * cfg.Ob * itemsize)


def test_fit_scratch_gb_basic_properties():
    # divides G, respects the budget, never below 1
    for G, R, V in [(512, 128, 16), (100, 800, 16), (7, 8, 256)]:
        gb = atn._fit_scratch_gb(G, R, V)
        assert G % gb == 0 and gb >= 1
        assert R * gb * V * 4 <= atn.SCRATCH_BUDGET or gb == 1
    # a degenerate budget still yields a dispatchable tile
    assert atn._fit_scratch_gb(64, 10**6, 10**6, budget=1) == 1
    # fixed bytes eat into the budget monotonically
    a = atn._fit_scratch_gb(1 << 16, 128, 16, fixed_bytes=0)
    b = atn._fit_scratch_gb(1 << 16, 128, 16, fixed_bytes=atn.SCRATCH_BUDGET // 2)
    assert b <= a


def test_shared_candidates_all_fit_budget():
    """Every candidate the analytic bound admits must fit the configured
    scratch budget — the acceptance contract that makes try-compile pruning
    unnecessary."""
    B, G, V, O, X = 8, 1 << 14, 256, 1024, 16  # one-hot at Gb=G would be ~16 GB
    itemsize = 4
    cands = atn.shared_gemv_candidates(B, G, V, O, X, itemsize)
    assert cands
    for c in cands:
        assert _shared_onehot_bytes(c, B, V, X, itemsize) <= atn.SCRATCH_BUDGET, c
        assert G % c.Gb == 0


def test_bounded_sweep_strictly_smaller_when_bound_bites():
    """On an oversized problem the bounded generator emits strictly fewer
    candidates than the unbounded (old try-compile) sweep; an infinite
    budget reproduces the old sweep exactly."""
    B, G, V, O, X = 8, 1 << 14, 256, 1024, 16
    old = atn.shared_gemv_candidates(B, G, V, O, X, 4,
                                     scratch_budget=float("inf"))
    new = atn.shared_gemv_candidates(B, G, V, O, X, 4)
    assert len(new) < len(old), (len(new), len(old))
    # same on the conv flavor
    old_c = atn.shared_conv2d_candidates(28, 1 << 14, 256, 1024, X, 4,
                                         scratch_budget=float("inf"))
    new_c = atn.shared_conv2d_candidates(28, 1 << 14, 256, 1024, X, 4)
    assert len(new_c) < len(old_c)


def test_bound_never_prunes_recorded_case_winners():
    """On the recorded CPU-interpret problems (the BENCH shapes — small
    enough that everything fits) the bounded candidate list must contain
    every candidate of the unbounded sweep, so the tile the exhaustive
    sweep would have picked is never pruned."""
    recorded = [
        # (B, G, V, O, X): BENCH_pr2 decode-GEMV and conv5x5 shared shapes
        (8, 512, 16, 1024, 16),
        (8, 100, 16, 1024, 8),
        (1, 8, 16, 48, 5),
    ]
    for B, G, V, O, X in recorded:
        unbounded = atn.shared_gemv_candidates(B, G, V, O, X, 4,
                                               scratch_budget=float("inf"))
        bounded = atn.shared_gemv_candidates(B, G, V, O, X, 4)
        assert bounded == unbounded, (B, G, V, O, X)
    for Ho, G, V, O, X in [(14, 100, 16, 64, 8), (6, 18, 16, 16, 4)]:
        unbounded = atn.shared_conv2d_candidates(Ho, G, V, O, X, 4, Wo=16,
                                                 scratch_budget=float("inf"))
        bounded = atn.shared_conv2d_candidates(Ho, G, V, O, X, 4, Wo=16)
        assert bounded == unbounded, (Ho, G, V, O, X)
    # dense fused generators: same retention contract on recorded shapes
    for B, G, V, O in [(8, 512, 16, 1024), (16, 16, 16, 24)]:
        assert atn.gemv_candidates(B, G, V, O) == atn.gemv_candidates(
            B, G, V, O, scratch_budget=float("inf"))


def test_bounded_tunes_select_no_slower_tiles_on_recorded_cases(tune_cache):
    """End-to-end: tuning with the bounded sweep on a recorded-size problem
    picks a tile that times no slower than the unbounded sweep's winner
    (identical candidate lists => identical winner modulo timing noise; we
    assert the recorded tile is a member of the unbounded sweep)."""
    x, T, spec, s, group = _problem(B=8, n=64, O=256)
    ops.pcilt_fused_gemv(x, T, spec, s, group, autotune=True)
    key = atn.shape_key("fused_gemv", dtype=T.dtype, backend="cpu",
                        B=8, G=T.shape[0], V=T.shape[1], O=256, g=group,
                        bits=spec.bits)
    winner = atn.lookup(key)
    assert winner is not None
    unbounded = atn.gemv_candidates(8, T.shape[0], T.shape[1], 256, 4,
                                    scratch_budget=float("inf"))
    assert winner in unbounded


def _quarantined(path):
    """Timestamp-sorted (oldest first) quarantine files for ``path``."""
    base = os.path.basename(path) + ".corrupt-"
    d = os.path.dirname(path) or "."
    names = [n for n in os.listdir(d) if n.startswith(base)
             and n[len(base):].isdigit()]
    return [os.path.join(d, n)
            for n in sorted(names, key=lambda n: int(n[len(base):]))]


def test_corrupt_cache_warns_quarantines_and_recovers(tune_cache, caplog):
    """A truncated/garbled cache file must never crash or silently reset:
    the load warns (naming the path and the parse error), preserves the
    original bytes at a timestamped ``<path>.corrupt-<ns>``, and the cache
    keeps working."""
    import logging

    from repro.runtime.faults import FaultInjector

    x, T, spec, s, group = _problem()
    ops.pcilt_fused_gemv(x, T, spec, s, group, autotune=True)
    with open(tune_cache, "rb") as f:
        garbled = f.read()[: 10]  # truncated mid-JSON

    FaultInjector().garble_file(tune_cache, "truncate")
    with open(tune_cache, "rb") as f:
        garbled = f.read()
    with caplog.at_level(logging.WARNING, logger="repro.autotune"):
        cache = atn.reset_cache(tune_cache)
    msgs = [r.getMessage() for r in caplog.records
            if r.name == "repro.autotune"]
    assert any(tune_cache in m and "corrupt" in m for m in msgs), msgs
    # original bytes preserved for post-mortem, live path starts empty
    qfiles = _quarantined(tune_cache)
    assert len(qfiles) == 1, qfiles
    with open(qfiles[0], "rb") as f:
        assert f.read() == garbled
    assert not os.path.exists(tune_cache)

    # the cache still records and persists after recovery
    atn.TIMING_RUNS = 0
    ops.pcilt_fused_gemv(x, T, spec, s, group, autotune=True)
    assert atn.TIMING_RUNS > 0  # entry was lost with the corrupt file
    assert cache.lookup(next(iter(json.load(open(tune_cache))))) is not None


def test_quarantine_distinct_files_and_keeps_newest_three(tune_cache):
    """Repeated corruption must (a) never overwrite an earlier incident's
    post-mortem bytes — every quarantine gets a distinct timestamped name —
    and (b) never grow unbounded: only the newest
    ``QUARANTINE_KEEP`` (3) quarantined copies survive."""
    incidents = []
    for i in range(5):
        payload = b"not json at all #%d" % i
        with open(tune_cache, "wb") as f:
            f.write(payload)
        atn.reset_cache(tune_cache)
        qfiles = _quarantined(tune_cache)
        assert qfiles, f"incident {i} was not quarantined"
        with open(qfiles[-1], "rb") as f:
            assert f.read() == payload  # newest file = this incident's bytes
        incidents.append(qfiles[-1])
        assert not os.path.exists(tune_cache)
    assert len(set(incidents)) == 5  # distinct name per incident
    survivors = _quarantined(tune_cache)
    assert len(survivors) == atn.QUARANTINE_KEEP == 3
    # the survivors are exactly the three newest incidents, oldest pruned
    assert survivors == incidents[-3:]
