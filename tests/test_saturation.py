"""Calibration-drift sentinel: in-kernel saturation counters vs the host
oracle, EWMA drift classification, and online table recalibration.

The quantizer in every fused fetch kernel *clips* silently — an activation
outside ``[-amax, amax]`` maps to the edge code and the output is plausibly
wrong with no byte corrupted.  The counters close that hole: each monitored
kernel call also returns how many elements saturated and the peak
``|x|/scale`` ratio, reduced in VMEM.  These tests pin

* the host oracle (``quantize_with_stats``) to ``quantize``'s exact
  arithmetic and to first-principles saturation counting;
* every counter kernel to the host oracle, bit-exactly, across ragged
  shapes x f32/bf16 tables x batch {1, R} (padding invariance: group
  alignment, paired phantom segments, and causal pads all quantize to the
  in-range zero point, so kernel and host see identical statistics);
* the monitor's EWMA classification and typed drift response;
* online recalibration: hot-swapped tables bit-equal a fresh
  conversion-arithmetic build at the new scale, checksums re-recorded,
  layer repromoted — and the sticky cases (conv's global scale, exhausted
  budget) stay demoted;
* the serving engine end to end: inject drift -> sentinel fires -> demote
  -> recalibrate -> repromote, with no request lost.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (QuantSpec, build_grouped_tables, calibrate, quantize,
                        quantize_with_stats)
from repro.core.lut_layers import build_dwconv_tables
from repro.core.pcilt import (build_paired_stacked_tables,
                              build_paired_tables, table_checksum)
from repro.kernels import autotune as atn
from repro.kernels import ops

RNG = np.random.default_rng(11)


@pytest.fixture(autouse=True)
def _private_cache(tmp_path):
    atn.reset_cache(str(tmp_path / "tiles.json"))
    yield
    atn.reset_cache()


def _host_stats(x, spec, scale):
    _, c, r = quantize_with_stats(x, spec, scale)
    return int(c), np.float32(r)


# ----------------------------------------------------------------------------
# Host oracle
# ----------------------------------------------------------------------------


@pytest.mark.parametrize("bits", [2, 4])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_quantize_with_stats_codes_bit_equal_and_counts(bits, dtype):
    spec = QuantSpec(bits)
    x = jnp.asarray(RNG.normal(size=(7, 33)) * 3, dtype)
    scale = calibrate(x.astype(jnp.float32), spec) * 0.4  # force clipping
    codes, count, ratio = quantize_with_stats(x, spec, scale)
    assert codes.dtype == quantize(x, spec, scale).dtype
    np.testing.assert_array_equal(np.asarray(codes),
                                  np.asarray(quantize(x, spec, scale)))
    # first-principles count: round(x/scale)+zp outside [0, K-1]
    q = np.round(np.asarray(x, np.float64) / float(scale)) + spec.zero_point
    want = int(((q < 0) | (q > spec.cardinality - 1)).sum())
    assert count.dtype == jnp.int32 and int(count) == want > 0
    assert ratio.dtype == jnp.float32
    assert np.isclose(float(ratio),
                      float(np.abs(np.asarray(x, np.float64)).max())
                      / float(scale), rtol=1e-2)


def test_clip_edge_values_are_in_range():
    """An element exactly on the representable edge rounds to an edge code
    — in range.  Saturation means *beyond* the grid, not on its boundary."""
    spec = QuantSpec(2)
    scale = jnp.asarray(0.5, jnp.float32)
    edge = float(scale) * (spec.cardinality - 1 - spec.zero_point)
    x = jnp.asarray([[edge, -float(scale) * spec.zero_point, 0.0]],
                    jnp.float32)
    _, count, _ = quantize_with_stats(x, spec, scale)
    assert int(count) == 0
    _, count, _ = quantize_with_stats(x * 1.5, spec, scale)
    assert int(count) > 0


def test_zero_padding_invariance():
    """Zero slots quantize to the (in-range) zero point, so stats computed
    on padded and unpadded activations agree — the property that lets the
    kernels count over their padded tiles and still match the host."""
    spec = QuantSpec(2)
    x = jnp.asarray(RNG.normal(size=(3, 10)) * 2, jnp.float32)
    scale = calibrate(x, spec) * 0.3
    _, c0, r0 = quantize_with_stats(x, spec, scale)
    xp = jnp.concatenate([x, jnp.zeros((3, 6), x.dtype)], axis=1)
    _, c1, r1 = quantize_with_stats(xp, spec, scale)
    assert int(c0) == int(c1)
    assert float(r0) == float(r1)


# ----------------------------------------------------------------------------
# Kernel counters == host oracle (bit-exact)
# ----------------------------------------------------------------------------

SHAPES = [  # (n, O, L) — ragged O and layer counts
    (16, 32, 1),
    (24, 33, 2),
    (8, 100, 3),
]


@pytest.mark.parametrize("B", [1, 5])
@pytest.mark.parametrize("tdt", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("n,O,L", SHAPES)
def test_stacked_gemv_counters_match_host(B, tdt, n, O, L):
    spec, group = QuantSpec(2), 2
    ws = jnp.asarray(RNG.normal(size=(L, n, O)), jnp.float32)
    xs = jnp.asarray(RNG.normal(size=(L, B, n)) * 2.5, jnp.float32)
    scales = jnp.asarray(
        [float(calibrate(xs[l], spec)) * 0.5 for l in range(L)], jnp.float32)
    stack = jnp.stack([build_grouped_tables(ws[l], spec, scales[l], group)
                       for l in range(L)]).astype(tdt)
    for l in range(L):
        out, count, ratio = ops.pcilt_fused_gemv_stacked(
            xs[l], stack, l, spec, scales[l], group, with_stats=True)
        ref = ops.pcilt_fused_gemv_stacked(xs[l], stack, l, spec, scales[l],
                                           group)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
        hc, hr = _host_stats(xs[l], spec, scales[l])
        assert int(count) == hc > 0
        assert np.float32(ratio) == hr


@pytest.mark.parametrize("B", [1, 5])
@pytest.mark.parametrize("tdt", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("n,O", [(16, 32), (24, 33), (8, 100)])
def test_paired_gemv_counters_match_host(B, tdt, n, O):
    spec, group = QuantSpec(2), 2
    w = jnp.asarray(RNG.normal(size=(n, O)), jnp.float32)
    x = jnp.asarray(RNG.normal(size=(B, n)) * 2.5, jnp.float32)
    scale = calibrate(x, spec) * 0.5
    t = build_paired_tables(w, spec, scale, group).astype(tdt)
    out, count, ratio = ops.pcilt_fused_gemv_paired(
        x, t, spec, scale, group, with_stats=True)
    ref = ops.pcilt_fused_gemv_paired(x, t, spec, scale, group)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    hc, hr = _host_stats(x, spec, scale)
    assert int(count) == hc > 0
    assert np.float32(ratio) == hr


@pytest.mark.parametrize("B", [1, 5])
@pytest.mark.parametrize("tdt", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("n,O,L", [(16, 32, 2), (8, 33, 3)])
def test_paired_stacked_gemv_counters_match_host(B, tdt, n, O, L):
    spec, group = QuantSpec(2), 2
    ws = jnp.asarray(RNG.normal(size=(L, n, O)), jnp.float32)
    xs = jnp.asarray(RNG.normal(size=(L, B, n)) * 2.5, jnp.float32)
    scales = jnp.asarray(
        [float(calibrate(xs[l], spec)) * 0.5 for l in range(L)], jnp.float32)
    stack = build_paired_stacked_tables(ws, spec, scales, group).astype(tdt)
    for l in range(L):
        out, count, ratio = ops.pcilt_fused_gemv_paired_stacked(
            xs[l], stack, l, spec, scales[l], group, with_stats=True)
        ref = ops.pcilt_fused_gemv_paired_stacked(xs[l], stack, l, spec,
                                                  scales[l], group)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
        hc, hr = _host_stats(xs[l], spec, scales[l])
        assert int(count) == hc > 0
        assert np.float32(ratio) == hr


@pytest.mark.parametrize("B", [1, 4])
@pytest.mark.parametrize("tdt", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("T,C,padding", [(16, 24, "CAUSAL"), (9, 40, "CAUSAL"),
                                         (4, 24, "VALID")])
def test_dwconv1d_counters_match_host(B, tdt, T, C, padding):
    spec, k = QuantSpec(2), 4
    filters = jnp.asarray(RNG.normal(size=(k, C)), jnp.float32)
    x = jnp.asarray(RNG.normal(size=(B, T, C)) * 2.5, jnp.float32)
    scale = calibrate(x, spec) * 0.5
    t = build_dwconv_tables(filters, spec, scale).astype(tdt)
    out, count, ratio = ops.pcilt_fused_dwconv1d(
        x, t, spec, scale, k, padding=padding, with_stats=True)
    ref = ops.pcilt_fused_dwconv1d(x, t, spec, scale, k, padding=padding)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    hc, hr = _host_stats(x, spec, scale)
    assert int(count) == hc > 0
    assert np.float32(ratio) == hr


# ----------------------------------------------------------------------------
# Monitor: EWMA classification + recalibration (smoke Mamba model)
# ----------------------------------------------------------------------------


@pytest.fixture(scope="module")
def env():
    from repro.configs import get_smoke_config
    from repro.configs.base import PCILTConfig
    from repro.models import build_model
    from repro.nn import materialize
    from repro.nn.layers import Ctx

    cfg = get_smoke_config("mamba2-130m")
    cfg = dataclasses.replace(cfg, pcilt=PCILTConfig(act_bits=2, group=2),
                              dtype=jnp.float32)
    model = build_model(cfg)
    key = jax.random.PRNGKey(0)
    params = materialize(model.param_specs(), key)
    toks = jax.random.randint(key, (2, 16), 0, cfg.vocab)
    _, cache = model.prefill(params, {"tokens": toks}, Ctx())
    tok = jax.random.randint(jax.random.fold_in(key, 1), (2, 1), 0, cfg.vocab)
    return dict(cfg=cfg, model=model, params=params, cache=cache, tok=tok,
                calib=toks)


def _fresh(env):
    """A fresh conversion + monitor (recalibration tests mutate tables)."""
    from repro.core.serving import HealthMonitor, convert_mamba_decode

    eng = convert_mamba_decode(env["model"], env["params"], env["calib"])
    mon = HealthMonitor(eng, env["params"], oracle_every=0)
    return eng, mon


def test_monitored_step_bit_identical_and_stats_shapes(env):
    eng, mon = _fresh(env)
    L = env["cfg"].n_layers
    lo, ho = mon.ok_masks()
    out0, c0 = eng.step(env["params"], env["cache"], env["tok"], lo, ho)
    out1, c1, sat = eng.step(env["params"], env["cache"], env["tok"], lo, ho,
                             with_stats=True)
    np.testing.assert_array_equal(np.asarray(out0), np.asarray(out1))
    for a, b in zip(jax.tree.leaves(c0), jax.tree.leaves(c1)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for grid in ("in", "conv", "out"):
        assert sat[grid]["count"].shape == (L,)
        assert sat[grid]["count"].dtype == jnp.int32
        assert sat[grid]["ratio"].shape == (L,)
        assert sat[grid]["ratio"].dtype == jnp.float32


def test_demoted_layer_still_reports_stats(env):
    """The oracle branch computes the same host-side stats, so a demoted
    layer keeps feeding the sentinel — recovery stays observable."""
    eng, mon = _fresh(env)
    lo, ho = mon.ok_masks()
    _, _, sat0 = eng.step(env["params"], env["cache"], env["tok"], lo, ho,
                          with_stats=True)
    lo2 = lo.at[1].set(False)
    _, _, sat1 = eng.step(env["params"], env["cache"], env["tok"], lo2, ho,
                          with_stats=True)
    for grid in ("in", "conv", "out"):
        np.testing.assert_array_equal(np.asarray(sat0[grid]["count"]),
                                      np.asarray(sat1[grid]["count"]))


def test_ewma_classification_and_typed_demotion(env):
    eng, mon = _fresh(env)
    L = mon.n_layers
    z = {"count": np.zeros(L, np.int64), "ratio": np.zeros(L)}

    def sat(grid, layer, rate, ratio):
        s = {g: dict(z) for g in mon.grids}
        cnt = np.zeros(L, np.int64)
        cnt[layer] = int(rate * mon._sat_elems[grid])
        rat = np.zeros(L)
        rat[layer] = ratio
        s[grid] = {"count": cnt, "ratio": rat}
        return s

    # healthy: below both thresholds, forever
    assert mon.observe_saturation(0, sat("in", 0, 0.0, 0.8), rows=1) == []
    assert mon.saturation_state("in", 0) == "healthy"
    # sustained low-grade drift: crosses the EWMA threshold, not the hard one
    tick, breaches = 1, []
    while not breaches:
        assert tick < 50, "EWMA never crossed sat_drift"
        breaches = mon.observe_saturation(tick, sat("out", 1, 0.05, 2.0),
                                          rows=1)
        tick += 1
    assert breaches[0]["kind"] == "drift"
    assert breaches[0]["state"] == "drifting"
    assert breaches[0]["layer"] == 1 and breaches[0]["grid"] == "out"
    assert not mon.layer_ok[1]
    assert (1, "out") in mon.drift_pending
    # instant saturation: one breach of the hard threshold demotes now
    breaches = mon.observe_saturation(tick, sat("in", 0, 0.9, 30.0), rows=1)
    assert breaches and breaches[0]["state"] == "saturated"
    assert breaches[0]["layer"] == 0 and breaches[0]["grid"] == "in"
    # demoted layers are skipped (no demotion storm)
    assert mon.observe_saturation(tick + 1, sat("in", 0, 0.9, 30.0),
                                  rows=1) == []


def test_recalibration_hot_swaps_repromotes_and_reverifies(env):
    eng, mon = _fresh(env)
    DL = 1
    proj = eng.pcilt["proj"]
    old_scale = float(np.asarray(proj["scales"]["wo"][DL]))
    old_tab = np.asarray(proj["tables"]["wo"])[DL].copy()
    # as if the sentinel had seen "out" activations 8x past calibration
    mon.sat_peak["out"][DL] = 8.0
    mon.layer_ok[DL] = False
    ev = mon.recalibrate_layer(DL, "out", tick=3)
    assert ev["kind"] == "recalibrate"
    new_scale = float(np.asarray(proj["scales"]["wo"][DL]))
    assert new_scale > old_scale
    assert mon.layer_ok[DL] and mon.tainted
    assert int(mon.last_verified[DL]) == 3
    got = np.asarray(proj["tables"]["wo"])[DL]
    assert not np.array_equal(got, old_tab)
    # bit-equal to a fresh conversion-arithmetic build at the new scale
    wf = jnp.asarray(env["params"]["blocks"]["mixer"]["wo"]["kernel"][DL],
                     jnp.float32)
    pad = (-wf.shape[0]) % proj["group"]
    if pad:
        wf = jnp.concatenate([wf, jnp.zeros((pad, wf.shape[1]), wf.dtype)], 0)
    want = build_grouped_tables(wf, proj["spec"], new_scale, proj["group"])
    np.testing.assert_array_equal(got, np.asarray(want).astype(got.dtype))
    # integrity record re-recorded for the swapped slice — CRC verification
    # still passes (rehoist(verify=True) already ran inside recalibrate)
    assert eng.pcilt["integrity"]["proj"]["wo"][DL] == table_checksum(got)
    assert eng.verify_layer(DL) == []
    # untouched layer 0 kept its original bytes and record
    assert eng.verify_layer(0) == []


def test_conv_grid_and_exhausted_budget_stay_sticky(env):
    eng, mon = _fresh(env)
    mon.layer_ok[0] = False
    ev = mon.recalibrate_layer(0, "conv", tick=1)
    assert ev["kind"] == "drift_sticky"
    assert not mon.layer_ok[0]  # conv shares one global scale: stays demoted
    mon.layer_ok[1] = False
    mon.sat_peak["out"][1] = 4.0
    mon.recalibrations[1] = mon.max_recalibrations
    ev = mon.recalibrate_layer(1, "out", tick=2)
    assert ev["kind"] == "drift_sticky"
    assert not mon.layer_ok[1]


def test_rehoist_verify_raises_on_corrupt_tables(env):
    from repro.runtime.faults import FaultInjector

    eng, _ = _fresh(env)
    eng.rehoist(verify=True)  # clean bundle passes
    tabs = eng.pcilt["proj"]["tables"]
    tabs["wx"] = FaultInjector(seed=3).corrupt_table(tabs["wx"], n_flips=1)
    with pytest.raises(RuntimeError, match="integrity"):
        eng.rehoist(verify=True)


# ----------------------------------------------------------------------------
# Serving end to end: inject -> detect -> demote -> recalibrate -> repromote
# ----------------------------------------------------------------------------


def test_engine_drift_chaos_end_to_end(env):
    from repro.launch.serve import (DRIFT_LAYER, Engine, Request,
                                    _chaos_drift_plan)
    from repro.runtime.faults import FaultInjector

    cfg = env["cfg"]
    eng = Engine(cfg, max_len=64, slots=2, pcilt=True)
    assert eng.sentinel
    injector = FaultInjector(seed=0)
    eng.chaos = _chaos_drift_plan(eng, injector)
    rng = np.random.default_rng(0)
    reqs = [Request(i, rng.integers(2, cfg.vocab, size=6), max_new=4)
            for i in range(3)]
    stats = eng.run(reqs)
    assert all(r.outcome in ("served", "degraded") for r in reqs)
    events = stats["health_events"]
    demotions = [e for e in events if e["kind"] == "drift"]
    recals = [e for e in events if e["kind"] == "recalibrate"]
    assert demotions and all(e["layer"] == DRIFT_LAYER for e in demotions)
    assert recals, [e["kind"] for e in events]
    assert all(eng.monitor.layer_ok), "drifted layer was not repromoted"
    assert stats["recalibrations"] >= 1
    assert stats["rollbacks"] >= 1
    # the per-tick telemetry carries the sentinel block
    assert all("saturation" in t for t in stats["telemetry"])
    # drifted-range commits are marked: taint persists after recalibration
    assert stats["degraded"] >= 1
