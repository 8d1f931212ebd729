"""The Zamba2 family: its configuration against the catalog's published
numbers, its operations and bytes pinned at ``zamba2-7b.tp4``'s share, a
whole tiny run of the harness against the reference, and the clean exit of
a program without the hybrid's table path."""

import os

import pytest

from bench import model, run
from bench.tests.test_bench_run import MIX

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DATA = os.path.join(os.path.dirname(__file__), "data")

#: Zyphra/Zamba2-7B-Instruct config.json, as the catalog of public
#: architectures reads it (every number the configuration file keeps)
PUBLISHED = {
    "adapter_rank": 128, "attention_head_dim": 224,
    "attention_hidden_size": 7168, "chunk_size": 256,
    "ffn_hidden_size": 14336, "hidden_size": 3584,
    "hybrid_layer_ids": [6, 11, 17, 23, 29, 35, 41, 47, 53, 59, 65, 71, 77],
    "intermediate_size": 14336, "kv_channels": 112, "mamba_d_conv": 4,
    "mamba_d_state": 64, "mamba_expand": 2, "mamba_headdim": 64,
    "mamba_ngroups": 2, "max_position_embeddings": 4096,
    "n_mamba_heads": 112, "num_attention_heads": 32, "num_hidden_layers": 81,
    "num_key_value_heads": 32, "num_logits_to_keep": 1, "num_mem_blocks": 2,
    "num_query_groups": 32, "rms_norm_eps": 1e-05, "rope_theta": 10000,
    "time_step_floor": 0.0001, "time_step_max": 0.1, "time_step_min": 0.001,
    "vocab_size": 32000,
}


def tp4():
    return model.load_config(os.path.join(BENCH, "configs",
                                          "zamba2-7b.tp4.json"))


@pytest.fixture(scope="module")
def zamba2():
    return model.family(tp4())


def test_the_configuration_keeps_the_published_numbers():
    """Every published number is the file's, but the keys it lists in
    ``reduced``, whose published values stand under ``published``; no
    width is among them; the share it holds is a quarter of the layer."""
    conf = tp4()
    reduced = set(conf["reduced"])
    assert set(conf["published"]) == reduced
    for key, value in PUBLISHED.items():
        if key in reduced:
            assert conf["published"][key] == value, key
            assert conf[key] != value, key
        else:
            assert conf[key] == value, key
    assert not reduced & set(model.family(conf).WIDTHS)
    for key in ("num_attention_heads", "num_key_value_heads",
                "num_query_groups", "n_mamba_heads", "vocab_size"):
        assert conf[key] * 4 == PUBLISHED[key], key
    assert conf["ffn_columns_held"] * 4 == PUBLISHED["ffn_hidden_size"]
    held = conf["layers_held"]
    assert len(held) == conf["num_hidden_layers"] == 6
    assert [i for i in PUBLISHED["hybrid_layer_ids"] if i in held] == [17]


#: ``(calls, bytes, ops)`` summed over one step's calls of each kernel, by
#: reader group and table itemsize, at 16 rows
CALLS = {
    4: {"gemv": {"pcilt_stacked_gemv_sat": (18, 304390144, 1189085184.0),
                 "pcilt_stacked_gemv": (27, 143714816, 544309248.0)},
        "shared": {"pcilt_stacked_gemv_sat": (6, 146120704, 572522496.0),
                   "pcilt_stacked_gemv": (3, 53682176, 209190912.0)},
        "head": {"pcilt_shared_gemv": (1, 58092544, 229376000.0)}},
    2: {"gemv": {"pcilt_stacked_gemv_sat": (18, 155754496, 1189085184.0),
                 "pcilt_stacked_gemv": (27, 75676160, 544309248.0)},
        "shared": {"pcilt_stacked_gemv_sat": (6, 74555392, 572522496.0),
                   "pcilt_stacked_gemv": (3, 27533312, 209190912.0)},
        "head": {"pcilt_shared_gemv": (1, 58092544, 229376000.0)}},
}


@pytest.mark.parametrize("itemsize", [4, 2])
def test_kernel_calls_are_pinned(zamba2, itemsize):
    got = {g: {k: (len(c), sum(b for b, _ in c), sum(o for _, o in c))
               for k, c in kernels.items()}
           for g, kernels in zamba2.kernel_calls(tp4(), 16, itemsize).items()}
    assert got == CALLS[itemsize]


def test_sites_flops_and_head_bytes_are_pinned(zamba2):
    conf = tp4()
    assert zamba2.mamba_sites(conf) == {
        "wz": (3584, 1792), "wx": (3584, 1792), "wB": (3584, 64),
        "wC": (3584, 64), "wdt": (3584, 28), "wo": (1792, 3584)}
    assert zamba2.shared_sites(conf)["q"] == (7168, 1792)
    assert zamba2.shared_sites(conf)["gate_up"] == (3584, 7168)
    assert zamba2.gemv_call(conf, 7168, 1792, 16, 4) == (26263552,
                                                         102760448.0)
    assert zamba2.head_staged_bytes(conf, 1792, 4) == 2752512000.0
    assert zamba2.dense_flops_per_token(conf) == 490692608.0
    assert zamba2.STEP_KERNEL == "pcilt_shared_gemv"


def test_a_tiny_run_agrees_with_the_reference():
    """The harness serves the tiny hybrid through the program's tables and
    the comparison finds every served token at the reference's best."""
    conf = model.load_config(os.path.join(DATA, "zamba2-tiny.json"))
    man = dict(run.manifest(), workloads=[
        {"name": "tiny.batch", "config": "zamba2-tiny", "traffic": "batch",
         "chips": 1, "why": "a CPU size"}])
    out = run.run_cell("tiny.batch", 2**32 + 21, 2.0, False, man=man,
                       conf=conf, mix=MIX)
    assert out["correct"] is True
    assert out["checks"]["mismatch_share"]["value"] == 0.0
    assert out["attempted"] > 0 and out["failed"] == 0


def test_a_program_without_the_hybrid_tables_exits_at_once(zamba2,
                                                           monkeypatch):
    from repro.models.hybrid import HybridLM

    monkeypatch.delattr(HybridLM, "build_pcilt")
    with pytest.raises(SystemExit, match="serves no PCILT tables"):
        zamba2.make_params(tp4(), 1)
