"""The served kernels compile for a TPU v5e at mamba2-130m widths.

Each case compiles one Pallas kernel of the served decode path for a
described (not attached) ``v5e:2x2`` topology — the chip's own compiler, no
chip — at the published widths (d768, d_inner 1536, d_state 128, 24 heads,
vocab 50280, INT2 g2 tables, 4 decode slots) and the tiles the untuned
dispatch picks, and checks the program holds the kernel as a
``tpu_custom_call``.  Interpret-mode tests prove the kernel bodies are
right; only these prove the chip accepts them.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import autotune as atn
from repro.kernels import ops
from repro.kernels.pcilt_dwconv1d import pcilt_fused_dwconv1d_pallas
from repro.kernels.pcilt_fused import pcilt_fused_gemv_stacked_pallas
from repro.kernels.pcilt_gemv import default_tiles
from repro.kernels.pcilt_shared import pcilt_shared_gemv_pallas

L, D, D_INNER, N_STATE, HEADS, VOCAB = 24, 768, 1536, 128, 24, 50288
BITS, GROUP, K = 2, 2, 4
V = 1 << (BITS * GROUP)
SLOTS = 4
ZP = (1 << BITS) // 2


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A described-chip compile is written to the persistent cache but
    cannot be read back without a chip — keep it out of the cache."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


@pytest.mark.parametrize("counters", [False, True])
@pytest.mark.parametrize("shards", [1, 4])
@pytest.mark.parametrize("n,O", [(D, D_INNER), (D, N_STATE), (D, HEADS),
                                 (D_INNER, D)],
                         ids=["wz_wx", "wB_wC", "wdt", "wo"])
def test_stacked_gemv_compiles(one_chip, no_compile_cache, n, O, shards,
                               counters):
    """``shards=4``: one device's share of segment-sharded tables on a
    4-chip ``model`` axis (the kernel sees ``G/4`` segments)."""
    n, G = n // shards, n // GROUP // shards
    tiles = ops._fit_tiles(default_tiles(SLOTS, G, V, O), SLOTS, G, O)
    Bp = atn._round_up(SLOTS, tiles[0])
    Op = atn._round_up(O, tiles[2]) if O >= 128 else O
    _compile(lambda l, x, s, t: pcilt_fused_gemv_stacked_pallas(
        l, x, s, t, bits=BITS, zero_point=ZP, group=GROUP, tiles=tiles,
        counters=counters),
        one_chip, ((1,), jnp.int32), ((Bp, n), jnp.float32),
        ((1, 1), jnp.float32), ((L, G, V, Op), jnp.float32))


@pytest.mark.parametrize("counters", [False, True])
@pytest.mark.parametrize("batch", [SLOTS, 8])
def test_dwconv1d_compiles(one_chip, no_compile_cache, batch, counters):
    C = D_INNER + 2 * N_STATE
    Vc = 1 << (BITS * K)
    cfg = atn.dwconv1d_candidates(1, C, Vc, K, B=batch)[0]
    tiles = (cfg.Bb, cfg.Ob)
    _compile(lambda x, s, t: pcilt_fused_dwconv1d_pallas(
        x, s, t, bits=BITS, zero_point=ZP, k=K, tiles=tiles,
        counters=counters),
        one_chip, ((batch, K, C), jnp.float32), ((1, 1), jnp.float32),
        ((C, Vc), jnp.float32))


def test_shared_head_compiles(one_chip, no_compile_cache):
    G = D // GROUP
    X = G  # random weights: no two [group, vocab] segments coincide
    cfg = atn.shared_gemv_candidates(SLOTS, G, V, VOCAB, X)[0]
    tiles = ops._fit_tiles((cfg.Bb, cfg.Gb, cfg.Ob), SLOTS, G, VOCAB)
    Bp = atn._round_up(SLOTS, tiles[0])
    _compile(lambda x, s, i, p: pcilt_shared_gemv_pallas(
        x, s, i, p, bits=BITS, zero_point=ZP, group=GROUP, tiles=tiles),
        one_chip, ((Bp, D), jnp.float32), ((1, 1), jnp.float32),
        ((1, G), jnp.int32), ((X, V, VOCAB), jnp.float32))


@pytest.mark.parametrize("d,vocab", [(D, VOCAB), (3584, 8000)],
                         ids=["mamba2-130m", "zamba2-7b.tp4"])
def test_shared_head_reads_the_pool_in_place(one_chip, no_compile_cache,
                                             monkeypatch, d, vocab):
    """The head's fetch at 16 slots over a stored ``[X, V, O]`` pool (1.24
    GB at mamba2-130m's widths, 0.92 GB at zamba2-7b.tp4's 1792 rows and
    8000-token share of the vocabulary) makes no copy of the pool: the
    kernel reads it as stored, ragged last output tile and all."""
    from repro.core import QuantSpec

    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    G = X = d // GROUP
    spec = QuantSpec(bits=BITS, symmetric=True)
    args = [jax.ShapeDtypeStruct(s, t, sharding=one_chip) for s, t in
            [((16, d), jnp.float32), ((X, V, vocab), jnp.float32),
             ((G,), jnp.int32), ((), jnp.float32)]]
    compiled = jax.jit(lambda x, p, i, s: ops.pcilt_shared_gemv(
        x, p, i, spec, s, GROUP)).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


@pytest.mark.parametrize("paired", [False, True])
def test_layer_checksum_reads_the_slices_in_place(one_chip, no_compile_cache,
                                                  paired):
    """The monitor's one-call layer check over every table stack at the
    published widths: the dynamic slices fuse into the reductions, so the
    program's scratch stays far below one layer's slices (122 MB
    unpaired)."""
    from repro.core.pcilt import _slice_lanes

    C, Vc = D_INNER + 2 * N_STATE, 1 << (BITS * K)
    outs = {"wz": D_INNER, "wx": D_INNER, "wB": N_STATE, "wC": N_STATE,
            "wdt": HEADS}
    proj = [(D, O) for O in outs.values()] + [(D_INNER, D)]
    Ls = 4 if paired else L  # 24 layers of V**2-row pairs exceed 16 GB
    if paired:
        shapes = [(n // GROUP // 2, Ls, V * V, O) for n, O in proj]
    else:
        shapes = [(Ls, n // GROUP, V, O) for n, O in proj]
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
            for s in [(Ls, C, Vc)] + shapes]
    layer = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    axes = (0,) + (1 if paired else 0,) * len(shapes)
    mem = _slice_lanes.lower(layer, *args, axes=axes).compile() \
        .memory_analysis()
    assert mem.temp_size_in_bytes < 1 << 20


def test_head_checksum_reads_the_pool_in_place(one_chip, no_compile_cache):
    """The head check over the 1.24 GB shared pool and its pointers makes
    no copy of the pool."""
    from repro.core.pcilt import _table_lanes

    G = D // GROUP
    pool = jax.ShapeDtypeStruct((G, V, VOCAB), jnp.float32, sharding=one_chip)
    idx = jax.ShapeDtypeStruct((G,), jnp.int32, sharding=one_chip)
    mem = _table_lanes.lower(pool, idx).compile().memory_analysis()
    assert mem.temp_size_in_bytes < 1 << 20
