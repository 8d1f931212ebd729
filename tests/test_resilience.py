"""Serving resilience: fault injection, table integrity, health-checked
degradation to the dense oracle, and checkpointed engine recovery.

The chaos contract under test (docs/resilience.md):

* every fault class is *detected* (zero false negatives for single-entry
  table flips, and for every error burst of <= 32 bits — properties of
  the table checksum's word-sum lane, tested exhaustively here);
* recoverable faults (step faults, poisoned state) restore-and-replay to
  **token-identical** output;
* table corruption demotes only the breached layer/head to its exact dense
  fake-quant oracle — serving continues, degraded and logged, never wrong;
* deadline-missed requests requeue with bounded retries, never silently
  lost.

The converted PCILT bundle is built once (module fixture) and shared via
nested-dict copies: corruption replaces dict entries, so copies isolate
tests without re-running the conversion.
"""

import dataclasses as dc
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_smoke_config
from repro.configs.base import PCILTConfig
from repro.core import fake_quant, table_checksum, stacked_checksums
from repro.core.quantization import QuantSpec, scale_from_amax
from repro.core.serving import (HealthMonitor, PCILTMambaDecode,
                                convert_kernel)
from repro.launch.serve import Engine, Request
from repro.launch.steps import make_ctx
from repro.nn.module import materialize
from repro.runtime.faults import FaultInjector

BITS, GROUP = 4, 2


def _cfg():
    cfg = get_smoke_config("mamba2-130m")
    return dc.replace(cfg, pcilt=PCILTConfig(act_bits=BITS, group=GROUP),
                      dtype=jnp.float32)


def _copy_bundle(obj):
    """Nested dict/list copy, arrays shared: corruption *replaces* entries,
    so a copy isolates a test's mutations from the donor bundle."""
    if isinstance(obj, dict):
        return {k: _copy_bundle(v) for k, v in obj.items()}
    if isinstance(obj, list):
        return [_copy_bundle(v) for v in obj]
    return obj


@pytest.fixture(scope="module")
def donor():
    """One converted PCILT engine; tests clone its bundle, never mutate it."""
    return Engine(_cfg(), max_len=64, slots=2, pcilt=True)


def _pcilt_engine(donor, **kw):
    return Engine(_cfg(), max_len=64, slots=2, pcilt=True,
                  pcilt_bundle=_copy_bundle(donor.pdecode.pcilt), **kw)


def _requests(cfg, n=3, max_new=4, deadline=None, seed=1):
    rng = np.random.default_rng(seed)
    return [Request(i, rng.integers(2, cfg.vocab, size=rng.integers(3, 7)),
                    max_new, deadline_s=deadline) for i in range(n)]


@pytest.fixture(scope="module")
def ref_run(donor):
    """Fault-free reference serving run (token ground truth)."""
    eng = _pcilt_engine(donor)
    reqs = _requests(eng.cfg)
    stats = eng.run(reqs)
    assert all(r.outcome == "served" for r in reqs)
    return [list(r.out) for r in reqs], stats


# ---- fault injector primitives ----------------------------------------------


def test_corrupt_table_flips_and_records():
    inj = FaultInjector(seed=3)
    t = jnp.arange(2 * 3 * 4, dtype=jnp.float32).reshape(2, 3, 4)
    bad = inj.corrupt_table(t, n_flips=3)
    diff = np.asarray(bad != t)
    assert bad.shape == t.shape and bad.dtype == t.dtype
    assert diff.sum() == 3
    (ev,) = inj.events
    assert ev["kind"] == "table_corruption" and len(ev["sites"]) == 3
    assert all(diff[s] for s in ev["sites"])


def test_flip_seg_idx_stays_in_pool_range():
    inj = FaultInjector(seed=0)
    seg = jnp.asarray(np.arange(16) % 8, jnp.int32)
    bad = inj.flip_seg_idx(seg, n_pool=8, n_flips=4)
    moved = np.nonzero(np.asarray(bad != seg))[0]
    assert len(moved) == 4
    assert np.asarray(bad).min() >= 0 and np.asarray(bad).max() < 8


def test_flip_seg_idx_single_row_pool_goes_out_of_range():
    inj = FaultInjector(seed=0)
    seg = jnp.zeros((5,), jnp.int32)
    bad = inj.flip_seg_idx(seg, n_pool=1, n_flips=1)
    # the only wrong pointer a 1-row pool admits is an out-of-range one
    assert int(np.asarray(bad).max()) == 1


def test_poison_plants_nan_and_inf():
    inj = FaultInjector(seed=1)
    x = jnp.zeros((4, 4), jnp.float32)
    assert int(jnp.isnan(inj.poison(x, "nan", n=3)).sum()) == 3
    assert int(jnp.isinf(inj.poison(x, "inf", n=2)).sum()) == 2
    assert [e["kind"] for e in inj.events] == ["activation_poison"] * 2


def test_garble_file_modes(tmp_path):
    inj = FaultInjector()
    p = str(tmp_path / "tiles.json")
    payload = json.dumps({"k": list(range(50))}).encode()
    for mode, check in [
        ("truncate", lambda b: 0 < len(b) < len(payload)),
        ("garbage", lambda b: b and b != payload),
        ("empty", lambda b: b == b""),
    ]:
        with open(p, "wb") as f:
            f.write(payload)
        inj.garble_file(p, mode)
        with open(p, "rb") as f:
            got = f.read()
        assert check(got), mode
        with pytest.raises(ValueError):
            json.loads(got.decode("utf-8", errors="strict") or "x")
    inj.garble_file(str(tmp_path / "absent.json"), "truncate")
    assert inj.events[-1]["absent"] is True


def test_maybe_fail_fires_once_then_replays_clean():
    inj = FaultInjector(fail_at=(5,))
    inj.maybe_fail(4)
    with pytest.raises(RuntimeError):
        inj.maybe_fail(5)
    inj.maybe_fail(5)  # replay after restore: clean
    assert [e["kind"] for e in inj.events] == ["step_fault"]


# ---- checksum integrity: zero false negatives --------------------------------


def _flip(a, i):
    flat = a.reshape(-1).copy()
    if np.issubdtype(flat.dtype, np.integer):
        flat[i] = flat[i] + 1
    else:
        old = float(np.float32(flat[i]))
        flat[i] = flat.dtype.type(old + (1.0 + abs(old)))
    return flat.reshape(a.shape)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16", "int32"])
def test_checksum_detects_every_single_entry_flip(dtype):
    """The checksum detects every change inside one 32-bit word and every
    error burst of <= 32 bits; a single flipped table entry is one of
    them.  Exhaustive: flip *every* entry, expect *every* flip detected — a
    measured zero false-negative rate, not a spot check."""
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.normal(size=(3, 4, 5)), getattr(jnp, dtype)) \
        if dtype != "int32" else jnp.asarray(
            rng.integers(0, 100, size=(3, 4, 5)), jnp.int32)
    base = table_checksum(a)
    host = np.asarray(a)
    misses = [i for i in range(host.size)
              if table_checksum(_flip(host, i)) == base]
    assert misses == []


def test_stacked_checksums_localize_the_corrupt_layer():
    rng = np.random.default_rng(1)
    t = jnp.asarray(rng.normal(size=(4, 3, 8)), jnp.float32)
    clean = stacked_checksums(t)
    assert len(clean) == 4
    inj = FaultInjector(seed=2)
    bad = np.asarray(t).copy()
    bad[2] = np.asarray(inj.corrupt_table(t[2], n_flips=1))
    dirty = stacked_checksums(jnp.asarray(bad))
    assert [i for i in range(4) if dirty[i] != clean[i]] == [2]


def _burst(host, start, mask):
    """``host`` with the bits of ``mask`` (bit 0 first) XORed into its byte
    stream from bit ``start`` on; bits count from each byte's least
    significant bit, the order of the little-endian word view."""
    bits = np.unpackbits(np.frombuffer(host.tobytes(), np.uint8),
                         bitorder="little")
    bits[start:start + len(mask)] ^= mask
    return np.frombuffer(np.packbits(bits, bitorder="little").tobytes(),
                         host.dtype).reshape(host.shape)


#: burst patterns of 1 to 32 bits; a burst's first and last bits flip
BURSTS = [[1], [1, 1], [1, 0, 0, 1], [1] * 7, [1] + [0] * 14 + [1],
          [1] * 16 + [0] + [1], [1, 0] * 15 + [1], [1] * 32,
          [1] + [0] * 30 + [1]]


@pytest.mark.parametrize("dtype,shape", [("float32", (2, 3)),
                                         ("int32", (5,)),
                                         ("bfloat16", (3, 4)),
                                         ("bfloat16", (3, 3))])
def test_checksum_detects_every_burst_of_up_to_32_bits(dtype, shape):
    """Exhaustive: every burst pattern at every start bit of the array's
    byte stream, so bursts inside one word, across a word boundary and
    (bfloat16, two entries to a word; the odd shape zero-pads its tail)
    over two packed entries are all caught by the word-sum lane alone."""
    rng = np.random.default_rng(3)
    a = jnp.asarray(rng.normal(size=shape) * 10, getattr(jnp, dtype))
    host = np.asarray(a)
    base = table_checksum(a)
    nbits = host.nbytes * 8
    lane1 = (1 << 32) - 1  # the record's low half is the word sum
    misses = [(start, len(mask)) for mask in BURSTS
              for start in range(nbits - len(mask) + 1)
              if table_checksum(_burst(host, start, np.uint8(mask))) & lane1
              == base & lane1]
    assert misses == []


@pytest.mark.parametrize("dtype", ["float32", "bfloat16", "int32"])
def test_checksum_detects_swapped_entries(dtype):
    """A swap leaves the word sum as it was; the mixed lane catches it.
    Every pair of unequal entries, bfloat16 pairs in one word included."""
    rng = np.random.default_rng(4)
    a = jnp.asarray(rng.integers(-50, 50, size=(4, 6)), getattr(jnp, dtype))
    host = np.asarray(a).reshape(-1)
    base = table_checksum(a)
    misses = []
    for i in range(host.size):
        for j in range(i + 1, host.size):
            if host[i] == host[j]:
                continue
            s = host.copy()
            s[i], s[j] = host[j], host[i]
            if table_checksum(s.reshape(a.shape)) == base:
                misses.append((i, j))
    assert misses == []


@pytest.mark.parametrize("paired", [False, True])
def test_verify_layer_checks_each_layer_against_its_stacked_record(
        donor, paired):
    """The monitor's one-call layer check reads each stack's slice in
    place (axis 0, or axis 1 for the seg-major paired stacks) and gives
    exactly the record ``stacked_checksums`` wrote for it."""
    from repro.core import slice_checksums
    from repro.core.serving import convert_mamba_decode
    from repro.models import build_model

    if paired:
        # INT2 keeps the paired V**2 tables small (INT4 would be 65536 rows)
        cfg = dc.replace(donor.cfg, pcilt=PCILTConfig(act_bits=2, group=2))
        calib = jax.random.randint(jax.random.PRNGKey(5), (2, 8), 0,
                                   cfg.vocab)
        pd = convert_mamba_decode(build_model(cfg), donor.params, calib,
                                  paired=True)
    else:
        pd = PCILTMambaDecode(donor.model, _copy_bundle(donor.pdecode.pcilt),
                              donor.pdecode.ctx)
    proj = pd.pcilt["proj"]
    assert bool(proj.get("paired")) is paired
    axis = 1 if paired else 0
    integ = pd.pcilt["integrity"]
    stacks = {"conv": (pd.pcilt["tables"], 0), **{
        name: (t, axis) for name, t in proj["tables"].items()}}
    recs = {"conv": integ["conv"], **integ["proj"]}
    for name, (t, a) in stacks.items():
        assert recs[name] == stacked_checksums(t, axis=a)
    for l in range(pd.model.cfg.n_layers):
        assert pd.verify_layer(l) == []
        assert slice_checksums(l, [t for t, _ in stacks.values()],
                               [a for _, a in stacks.values()]) == \
            [recs[name][l] for name in stacks]
        # a record off by one bit is a breach of that site alone
        name = list(stacks)[l % len(stacks)]
        recs[name][l] ^= 1
        assert pd.verify_layer(l) == [(name, l)]
        recs[name][l] ^= 1


_SHARDED = """
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from repro.core import stacked_checksums, table_checksum, slice_checksums
assert jax.device_count() == 8
mesh = Mesh(np.array(jax.devices()).reshape(4, 2), ("model", "data"))
rng = np.random.default_rng(6)
for dtype in ("float32", "bfloat16", "int32"):
    a = jnp.asarray(rng.normal(size=(8, 12, 4, 16)) * 50, dtype)
    for spec in (P("model"), P(None, "model"), P(None, "model", None, "data"),
                 P("data", None, None, "model")):
        s = jax.device_put(a, NamedSharding(mesh, spec))
        assert len({sh.device for sh in s.addressable_shards}) == 8
        assert table_checksum(s) == table_checksum(a), (dtype, spec)
        for axis in (0, 1):
            assert stacked_checksums(s, axis) == stacked_checksums(a, axis)
        assert slice_checksums(3, [s, s], [0, 1]) == \
            slice_checksums(3, [a, a], [0, 1])
print("SHARDED-OK")
"""


def test_sharded_record_equals_the_unsharded_one(tmp_path):
    """On 8 forced host devices, a table sharded over a mesh (segment axis,
    layer axis, two axes at once) reduces in place to the record of the
    same array on one device: the lanes are wrapping sums."""
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        " --xla_force_host_platform_device_count=8").strip()
    env["PYTHONPATH"] = os.path.join(repo, "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    r = subprocess.run([sys.executable, "-c", _SHARDED], env=env, cwd=repo,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0 and "SHARDED-OK" in r.stdout, r.stderr[-4000:]


# ---- converted-layer integrity ----------------------------------------------


def test_pcilt_linear_carries_and_verifies_integrity():
    rng = np.random.default_rng(0)
    k = jnp.asarray(rng.normal(size=(8, 16)), jnp.float32)
    spec = QuantSpec(bits=BITS, symmetric=True)
    scale = scale_from_amax(jnp.asarray(1.0), spec)
    lin = convert_kernel(k, spec, scale, GROUP, weight_bits=4, shared=True)
    assert set(lin.integrity) == {"pool", "seg_idx"}
    assert all(lin.verify_integrity().values())
    inj = FaultInjector(seed=0)
    lin.shared = dc.replace(
        lin.shared, pool=inj.corrupt_table(lin.shared.pool, n_flips=1))
    assert lin.verify_integrity()["pool"] is False
    assert lin.verify_integrity()["seg_idx"] is True


def test_decode_bundle_verified_at_load(donor):
    inj = FaultInjector(seed=0)
    bundle = _copy_bundle(donor.pdecode.pcilt)
    bundle["tables"] = inj.corrupt_table(bundle["tables"], n_flips=1)
    ctx = make_ctx(None, None, decode=True)
    with pytest.raises(RuntimeError, match="integrity"):
        PCILTMambaDecode(donor.model, bundle, ctx)
    # explicit opt-out (the chaos path): loads, detection deferred to the
    # monitor
    pd = PCILTMambaDecode(donor.model, bundle, ctx, verify=False)
    assert pd.verify_integrity() != []


def test_monitor_demotes_only_the_breached_layer(donor):
    inj = FaultInjector(seed=4)
    bundle = _copy_bundle(donor.pdecode.pcilt)
    pd = PCILTMambaDecode(donor.model, bundle, donor.pdecode.ctx)
    mon = HealthMonitor(pd, donor.params)
    for t in range(3):
        assert mon.on_tick(t) == []
    assert mon.last_verified.min() >= 0
    tabs = pd.pcilt["proj"]["tables"]
    bad_layer = 1
    full = np.asarray(tabs["wx"]).copy()
    full[bad_layer] = np.asarray(
        inj.corrupt_table(tabs["wx"][bad_layer], n_flips=1))
    tabs["wx"] = jnp.asarray(full)
    breaches = []
    for t in range(3, 3 + 2 * mon.n_layers):
        breaches += mon.on_tick(t)
    assert [b["layer"] for b in breaches] == [bad_layer]
    assert list(mon.layer_ok) == [l != bad_layer
                                  for l in range(mon.n_layers)]
    assert mon.head_ok  # head untouched
    # the breached layer stops being re-verified; healthy ones continue
    assert mon.on_tick(99) == []


def test_health_masks_exact_and_demoted_matches_oracle(donor):
    """All-healthy masks are bitwise-identical to running unmasked (the
    cond's live branch is the same fetch), and an all-demoted step matches
    the dense fake-quant oracle — 'degraded, never wrong'."""
    pd = donor.pdecode
    cfg = donor.cfg
    B = 2
    cache = materialize(donor.model.cache_specs(B, 16), jax.random.PRNGKey(7))
    cache = dict(cache, pos=jnp.asarray(1, jnp.int32))
    tok = jnp.full((B, 1), 3, jnp.int32)
    base, base_c = pd.step(donor.params, cache, tok)
    ones, ones_c = pd.step(donor.params, cache, tok,
                           layer_ok=jnp.ones((cfg.n_layers,), bool),
                           head_ok=jnp.asarray(True))
    np.testing.assert_array_equal(np.asarray(base), np.asarray(ones))
    np.testing.assert_array_equal(np.asarray(base_c["layers"]["ssd"]),
                                  np.asarray(ones_c["layers"]["ssd"]))

    demoted, _ = pd.step(donor.params, cache, tok,
                         layer_ok=jnp.zeros((cfg.n_layers,), bool),
                         head_ok=jnp.asarray(False))
    pc_fq = _copy_bundle(pd.pcilt)
    pc_fq["proj"]["path"] = "dense_fq"
    oracle_step = jax.jit(lambda p, c, t: donor.model.decode_step(
        p, c, t, pd.ctx, pcilt=pc_fq, head_ok=jnp.asarray(False)))
    want, _ = oracle_step(donor.params, cache, tok)
    assert np.all(np.isfinite(np.asarray(demoted)))
    np.testing.assert_allclose(np.asarray(demoted), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
    assert np.array_equal(np.argmax(np.asarray(demoted), -1),
                          np.argmax(np.asarray(want), -1))


# ---- engine: continuous batching correctness (satellite fix) ----------------


def test_prefill_overlap_matches_serial():
    """Two overlapping requests must produce the same tokens as serving them
    serially — regression for prefill ticks dropping active slots' sampled
    tokens (Mamba arch: per-slot recurrent state, position-free)."""
    cfg = get_smoke_config("mamba2-130m")
    mk = lambda: [Request(0, [5, 7, 9, 11, 13], 4), Request(1, [4, 6, 8], 4)]
    overlapped = Engine(cfg, max_len=64, slots=2)
    reqs = mk()
    overlapped.run(reqs)
    serial = Engine(cfg, max_len=64, slots=1)
    ref = mk()
    serial.run(ref)
    assert [r.out for r in reqs] == [q.out for q in ref]
    assert all(r.outcome == "served" for r in reqs)


# ---- engine: chaos ----------------------------------------------------------


def test_engine_restore_replay_token_identical(donor, ref_run):
    """Step fault + NaN-poisoned recurrent state: both detected, both
    recovered by checkpoint restore, and the replayed serving run is
    token-identical to the fault-free reference."""
    ref_tokens, _ = ref_run
    inj = FaultInjector(fail_at=(2,), seed=0)

    def poison_state(e):
        layers = e.cache["layers"]
        e.cache = dict(e.cache, layers=dict(
            layers, ssd=inj.poison(layers["ssd"], "nan", n=2)))

    eng = _pcilt_engine(donor, chaos={2: [lambda e: inj.maybe_fail(2)],
                                      9: [poison_state]})
    reqs = _requests(eng.cfg)
    stats = eng.run(reqs)
    assert not eng.chaos  # every scheduled fault fired
    assert stats["restarts"] == 2
    assert [e["kind"] for e in inj.events] == ["step_fault",
                                               "activation_poison"]
    assert [r.outcome for r in reqs] == ["served"] * len(reqs)
    assert [list(r.out) for r in reqs] == ref_tokens


def test_engine_corruption_degrades_never_lost(donor):
    """Corrupted projection stack + flipped head pointers: the monitor
    demotes the breached layer and the head, the engine rolls back to the
    last verified tick, and every request still completes."""
    inj = FaultInjector(seed=5)

    def corrupt_proj(e):
        tabs = e.pdecode.pcilt["proj"]["tables"]
        tabs["wx"] = inj.corrupt_table(tabs["wx"], n_flips=1)
        e.pdecode.rehoist()

    def flip_head(e):
        head = e.pdecode.pcilt["head"]
        head["seg_idx"] = inj.flip_seg_idx(
            head["seg_idx"], n_pool=head["pool"].shape[0])
        e.pdecode.rehoist()

    eng = _pcilt_engine(donor, chaos={3: [corrupt_proj], 6: [flip_head]})
    reqs = _requests(eng.cfg)
    stats = eng.run(reqs)
    assert not eng.chaos
    assert all(r.outcome in ("served", "degraded") for r in reqs)
    assert stats["rollbacks"] >= 1
    kinds = {e["kind"] for e in eng.monitor.events}
    assert kinds == {"layer", "head"}
    assert not eng.monitor.layer_ok.all() and not eng.monitor.head_ok
    # demotion is per-layer: the clean layer keeps fetching
    assert eng.monitor.layer_ok.sum() == eng.monitor.n_layers - 1


def test_engine_deadline_requeues_then_fails_bounded():
    """A request that can never meet its deadline is evicted, requeued with
    backoff, and failed after max_retries — bounded, never silently lost."""
    cfg = get_smoke_config("qwen3-0.6b")
    doomed = Request(0, np.asarray([5, 6, 7]), max_new=64, deadline_s=1e-4,
                     max_retries=1)
    fine = Request(1, np.asarray([3, 4]), max_new=3)
    eng = Engine(cfg, max_len=128, slots=2)
    stats = eng.run([doomed, fine])
    assert doomed.outcome == "failed"
    assert doomed.retries == doomed.max_retries + 1
    assert fine.outcome == "served" and len(fine.out) == 3
    assert stats["failed"] == 1 and stats["retried"] == 1
    assert stats["outcomes"] == {0: "failed", 1: "served"}


def test_deadline_eviction_races_checkpoint_restore(donor):
    """A step fault forces a restore to a checkpoint taken *before* a
    deadline eviction: the replay must re-run the eviction from restored
    state — the evicted request fails exactly once (retries never
    double-counted) and its slot state is never resurrected."""
    inj = FaultInjector(fail_at=(5,), seed=2)
    doomed = Request(0, np.asarray([5, 6, 7]), max_new=64, deadline_s=1e-4,
                     max_retries=0)
    fine = Request(1, np.asarray([3, 4]), max_new=4)
    # prefills cover steps 0..4, so the fault hits the first decode tick —
    # the restore target predates the eviction the same tick would commit
    eng = _pcilt_engine(donor, chaos={5: [lambda e: inj.maybe_fail(5)]})
    stats = eng.run([doomed, fine])
    assert not eng.chaos and stats["restarts"] == 1
    assert doomed.outcome == "failed"
    assert doomed.retries == doomed.max_retries + 1  # once, not per replay
    assert doomed.out == []  # evicted state never resurrected by the replay
    assert fine.outcome == "served" and len(fine.out) == 4
    assert all(r is None for r in eng.active) and eng.queue == []
    assert stats["outcomes"] == {0: "failed", 1: "served"}
    assert stats["slot_evictions"] == 1


def test_monitor_demotion_with_two_slots_mid_request(donor):
    """Table corruption lands while BOTH slots are mid-request: the breach
    rolls every slot back to the last verified tick and replays demoted —
    each request ends degraded with exactly its max_new tokens (no token
    lost or duplicated across the multi-slot rollback)."""
    inj = FaultInjector(seed=6)
    seen = {}

    def corrupt(e):
        seen["active"] = sum(r is not None for r in e.active)
        seen["partial"] = [len(r.out) for r in e.active if r is not None]
        tabs = e.pdecode.pcilt["proj"]["tables"]
        tabs["wx"] = inj.corrupt_table(tabs["wx"], n_flips=1)
        e.pdecode.rehoist()

    reqs = [Request(0, np.asarray([5, 6, 7]), max_new=8),
            Request(1, np.asarray([3, 4, 9]), max_new=8)]
    eng = _pcilt_engine(donor, chaos={7: [corrupt]})
    stats = eng.run(reqs)
    assert not eng.chaos
    assert seen["active"] == 2  # the breach hit with both slots mid-request
    assert all(n >= 1 for n in seen["partial"])
    assert [r.outcome for r in reqs] == ["degraded", "degraded"]
    assert [len(r.out) for r in reqs] == [8, 8]
    assert stats["rollbacks"] >= 1 and stats["degraded"] == 2
    assert eng.monitor.layer_ok.sum() == eng.monitor.n_layers - 1


def test_per_slot_count_executors_cached_and_dropped_on_rehoist(donor):
    """The decode engine hoists one jitted executor per (slot count, stats)
    pair (R is a tuned, keyed axis; the counter outputs change the result
    pytree): repeat lookups hit the cache, distinct row counts and the
    monitored variant get distinct executors, and rehoist drops them all
    for lazy rebuild."""
    pd = donor.pdecode
    e1, e2 = pd.executor(1), pd.executor(2)
    es = pd.executor(1, stats=True)
    assert pd.executor(1) is e1 and pd.executor(2) is e2
    assert pd.executor(1, stats=True) is es
    assert e1 is not e2 and es is not e1
    assert set(pd._execs) == {(1, False), (2, False), (1, True)}
    pd.rehoist()
    assert pd._execs == {}  # stale closures dropped, rebuilt on next step
    assert pd.executor(2) is not e2
