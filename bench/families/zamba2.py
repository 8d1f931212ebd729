"""The Zamba2 family: Mamba2 layers with shared attention+MLP blocks at the
configuration's hybrid layers and a tied logits head, served by the
program's ``HybridLM`` through PCILT tables.  The plain reference is
``zamba2_reference.py`` beside this file; ``mamba2.py`` documents each
hook that ``bench/model.py`` lists in ``HOOKS``.

A configuration holds one chip's share of a tensor-parallel host and one
pipeline stage of layers (``layers_held``): its attention heads
(``num_attention_heads``), MLP columns (``ffn_columns_held``), Mamba heads
(``n_mamba_heads``, within the groups they sit in) and vocabulary rows
(``vocab_size``); the published sizes stand beside them under
``published``.

The weights are the benchmark's: one jitted call draws every leaf from the
run's seed on the device, in float32, with Zamba2's initialisation
(``Zamba2PreTrainedModel``): every Linear and Conv1d weight N(0, 0.02),
biases 0, the embedding N(0, 0.02), ``A_log = log(1..heads)``, ``dt``
log-uniform in ``[time_step_min, time_step_max]`` floored at
``time_step_floor`` and stored as its inverse softplus, ``D`` and every
norm weight 1.  The layout is the program's parameter tree.

Kernel bytes follow ``bench/roofline.py``'s rule, as in ``mamba2.py``: one
call of each projection site, the least a correct lookup moves.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Tuple

from bench import model
from bench.families import zamba2_reference as reference

#: sizes a configuration of this family never cuts
WIDTHS = ("hidden_size", "attention_hidden_size", "attention_head_dim",
          "kv_channels", "ffn_hidden_size", "intermediate_size",
          "mamba_d_state", "mamba_headdim", "mamba_expand", "mamba_d_conv",
          "mamba_ngroups", "adapter_rank", "chunk_size")
#: share of the compared served tokens whose logit may lie below the
#: reference's best: sound runs read 0.0 on every seed (TPU v5e), tables
#: held in bfloat16 0.809; the limit is mamba2's, between the two with room
#: on both sides (PERF.md gives the readings)
MAX_MISMATCH_SHARE = 0.007
LIMITS = {"mismatch_share": MAX_MISMATCH_SHARE}
#: the Mamba mixers' projections, by the stacked-GEMV kernel that runs
#: them (with the drift sentinel on, one site per quantizer grid carries
#: the saturation counters: the ``_sat`` kernel)
MAMBA_KERNELS = {"pcilt_stacked_gemv_sat": ("wx", "wo"),
                 "pcilt_stacked_gemv": ("wz", "wB", "wC", "wdt")}
#: the shared block's projection sites, by kernel
SHARED_KERNELS = {"pcilt_stacked_gemv_sat": ("q", "o", "gate_up",
                                             "adapter_B", "down", "linear"),
                  "pcilt_stacked_gemv": ("k", "v", "adapter_A")}
HEAD_KERNEL = "pcilt_shared_gemv"
#: the kernel that runs once per decode-path step: the logits head
STEP_KERNEL = HEAD_KERNEL
#: the quantizer grids the program's table build takes scales for
GRIDS = ("in", "out") + reference.GRIDS
F32 = 4


def require_program() -> None:
    """Exit non-zero at once if the program has no table path for the
    hybrid (an older checkout), instead of building for minutes."""
    from repro.models.hybrid import HybridLM

    if not all(hasattr(HybridLM, a) for a in ("build_pcilt", "pcilt_sites")):
        raise SystemExit("the program's HybridLM serves no PCILT tables: "
                         "this checkout cannot run the zamba2 family")


# -- the program's model and the weights -------------------------------------

def hybrid_layers(conf: Dict) -> Tuple[int, ...]:
    """The hybrid layers among those held, numbered from the first held."""
    held = conf["layers_held"]
    return tuple(i - held[0] for i in conf["hybrid_layer_ids"] if i in held)


def program_config(conf: Dict):
    """The program's ``ModelConfig`` of this file: the published structure
    of ``conf["program"]["base"]`` at the file's sizes and share."""
    from repro.configs.base import PCILTConfig
    from repro.launch.serve import serve_config

    q = conf["pcilt"]
    base = serve_config(conf["program"]["base"], full=True, pcilt=True)
    ssm = dataclasses.replace(
        base.ssm, d_state=conf["mamba_d_state"],
        head_dim=conf["mamba_headdim"], n_groups=conf["mamba_ngroups"],
        conv_kernel=conf["mamba_d_conv"], expand=conf["mamba_expand"],
        chunk=conf["chunk_size"], heads_held=conf["n_mamba_heads"])
    return dataclasses.replace(
        base, name=conf["name"], n_layers=conf["num_hidden_layers"],
        d_model=conf["hidden_size"], n_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_key_value_heads"],
        head_dim=conf["attention_head_dim"], d_ff=conf["ffn_columns_held"],
        vocab=conf["vocab_size"], ssm=ssm,
        hybrid_layer_ids=hybrid_layers(conf),
        n_shared_attn_blocks=conf["num_mem_blocks"],
        adapter_rank=conf["adapter_rank"], rope_theta=conf["rope_theta"],
        tie_embeddings=conf["tie_word_embeddings"],
        norm_eps=conf["rms_norm_eps"],
        pcilt=PCILTConfig(act_bits=q["act_bits"], group=q["group"]))


def make_params(conf: Dict, seed: int):
    """Every weight, drawn from ``seed`` on the device in one jitted call."""
    require_program()
    import jax
    import jax.numpy as jnp

    d = reference.dims(conf)
    D, L, A = d.d_model, d.n_layer, len(d.hybrid)
    H, gn = d.nheads, d.ngroups * d.d_state
    hd = d.attn_heads * d.attn_head_dim
    F, r, nb = d.ffn, d.rank, d.blocks
    key = jax.random.PRNGKey(
        int(model.seed_ints(seed, model.STREAM_WEIGHTS)[0] >> 1))
    std = 0.02

    @jax.jit
    def init(key):
        ks = iter(jax.random.split(key, 32))

        def w(*shape):
            return std * jax.random.normal(next(ks), shape, jnp.float32)

        dt = jnp.exp(jax.random.uniform(
            next(ks), (L, H), jnp.float32, math.log(conf["time_step_min"]),
            math.log(conf["time_step_max"])))
        dt = jnp.maximum(dt, conf["time_step_floor"])
        mixer = {
            "wz": {"kernel": w(L, D, d.d_inner)},
            "wx": {"kernel": w(L, D, d.d_inner)},
            "wB": {"kernel": w(L, D, gn)}, "wC": {"kernel": w(L, D, gn)},
            "wdt": {"kernel": w(L, D, H)},
            "conv_w": w(L, d.d_conv, d.conv_dim),
            "conv_b": jnp.zeros((L, d.conv_dim), jnp.float32),
            "A_log": jnp.broadcast_to(
                jnp.log(jnp.arange(1, H + 1, dtype=jnp.float32)), (L, H)),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "D": jnp.ones((L, H), jnp.float32),
            "norm": {"scale": jnp.ones((L, d.d_inner), jnp.float32)},
            "wo": {"kernel": w(L, d.d_inner, D)},
        }
        shared = {
            "ln": {"scale": jnp.ones((nb, 2 * D), jnp.float32)},
            "q": {"kernel": w(nb, 2 * D, hd)},
            "k": {"kernel": w(nb, 2 * D, hd)},
            "v": {"kernel": w(nb, 2 * D, hd)},
            "o": {"kernel": w(nb, hd, D)},
            "ln_mlp": {"scale": jnp.ones((nb, D), jnp.float32)},
            "gate_up": {"kernel": w(nb, D, 2 * F)},
            "down": {"kernel": w(nb, F, D)},
        }
        apps = {"adapter_A": {"kernel": w(A, D, r)},
                "adapter_B": {"kernel": w(A, r, 2 * F)},
                "linear": {"kernel": w(A, D, D)}}
        return {
            "embed": {"embedding": w(d.vocab, D)},
            "blocks": {"ln": {"scale": jnp.ones((L, D), jnp.float32)},
                       "mixer": mixer},
            "shared": shared, "apps": apps,
            "ln_f": {"scale": jnp.ones((D,), jnp.float32)},
        }

    return init(key)


def calibrate(conf: Dict, params, seed: int):
    """The activation scales, from the reference's float pass over the
    seed's calibration tokens."""
    import jax
    import jax.numpy as jnp

    d = reference.dims(conf)
    return jax.jit(lambda p, t: reference.calibrate(p, t, d))(
        params, jnp.asarray(model.calib_tokens(conf, seed)))


# -- the served engine -------------------------------------------------------

def build(conf: Dict, params, scales, table_dtype: str, engine_class, clock,
          slots: int):
    """The program's tables (``HybridLM.build_pcilt``, the configuration's
    own format) from the weights and scales, and an ``engine_class``
    serving them on ``slots`` slots of ``kv_positions`` each."""
    require_program()
    import jax.numpy as jnp

    from repro.models import build_model

    cfg = program_config(conf)
    q = conf["pcilt"]
    if q["head"] != "shared":
        raise SystemExit(f"unsupported head {q['head']!r}")
    bundle = build_model(cfg).build_pcilt(
        params, scales["conv"], proj_scales={g: scales[g] for g in GRIDS},
        proj_path="fused", table_dtype=jnp.dtype(table_dtype),
        head_scale=scales["head"], head_weight_bits=q["head_weight_bits"])
    eng = engine_class(cfg, max_len=conf["kv_positions"], slots=slots,
                       pcilt=True, pcilt_bundle=bundle, clock=clock)
    # the engine draws its own weights from a fixed key; serve the seed's
    eng.params = params
    eng.monitor.params = params
    return eng


def warm_up(eng) -> None:
    """The monitor's dense-oracle probe of every site of the first layer
    and of the first shared-block application."""
    model_ = eng.pdecode.model
    for unit in (0, model_.cfg.n_layers):
        for name, (stack, _) in model_.pcilt_sites().items():
            if model_.site_index(stack, unit) is not None:
                eng.monitor._oracle_check(unit, name)


def table_itemsize(eng) -> int:
    """Bytes of one entry of the projection tables the engine serves."""
    return next(iter(
        eng.pdecode.pcilt["proj"]["tables"].values())).dtype.itemsize


# -- operations and bytes ----------------------------------------------------

def mamba_sites(conf: Dict) -> Dict[str, Tuple[int, int]]:
    """``{name: (n, O)}`` of the six projections of one Mamba layer."""
    d = reference.dims(conf)
    gn = d.ngroups * d.d_state
    return {"wz": (d.d_model, d.d_inner), "wx": (d.d_model, d.d_inner),
            "wB": (d.d_model, gn), "wC": (d.d_model, gn),
            "wdt": (d.d_model, d.nheads), "wo": (d.d_inner, d.d_model)}


def shared_sites(conf: Dict) -> Dict[str, Tuple[int, int]]:
    """``{name: (n, O)}`` of the projection sites of one application of a
    shared block."""
    d = reference.dims(conf)
    D, hd, F = d.d_model, d.attn_heads * d.attn_head_dim, d.ffn
    return {"q": (2 * D, hd), "k": (2 * D, hd), "v": (2 * D, hd),
            "o": (hd, D), "gate_up": (D, 2 * F), "adapter_A": (D, d.rank),
            "adapter_B": (d.rank, 2 * F), "down": (F, D), "linear": (D, D)}


def values(conf: Dict) -> int:
    """``V``: table rows per segment."""
    q = conf["pcilt"]
    return 1 << (q["act_bits"] * q["group"])


def gemv_call(conf: Dict, n: int, O: int, rows: int,
              itemsize: int) -> Tuple[float, float]:
    """``(bytes, ops)`` one stacked-GEMV call over an ``[n, O]`` site must
    move and do at a batch of ``rows``."""
    G = math.ceil(n / conf["pcilt"]["group"])
    return (G * O * itemsize + rows * n * F32 + rows * O * F32,
            float(rows * G * O))


def gemv_staged_bytes(conf: Dict, n: int, O: int, itemsize: int) -> float:
    """Table bytes today's kernel stages for one call: every row."""
    G = math.ceil(n / conf["pcilt"]["group"])
    return float(G * values(conf) * O * itemsize)


def _calls(conf: Dict):
    """``(kernel, (n, O), group)`` of every stacked-GEMV call of one step,
    ``group`` ``"mamba"`` or ``"shared"``."""
    L, A = conf["num_hidden_layers"], len(hybrid_layers(conf))
    m, s = mamba_sites(conf), shared_sites(conf)
    out = []
    for kernel, names in MAMBA_KERNELS.items():
        out += [(kernel, m[n], "mamba") for n in names] * L
    for kernel, names in SHARED_KERNELS.items():
        out += [(kernel, s[n], "shared") for n in names] * A
    return out


def head_call(conf: Dict, rows: int, itemsize: int) -> Tuple[float, float]:
    """``(bytes, ops)`` of one shared-pool head call: one pool row per
    segment, the segment pointers, activations in, logits out."""
    d, V = conf["hidden_size"], conf["vocab_size"]
    G = math.ceil(d / conf["pcilt"]["group"])
    return (G * V * itemsize + G * 4 + rows * d * F32 + rows * V * F32,
            float(rows * G * V))


def head_staged_bytes(conf: Dict, pool_rows: int, itemsize: int) -> float:
    """Bytes today's head call moves in its pool: the per-call transpose
    reads and writes the whole ``[X, V, O]`` pool, and the kernel stages
    it once more."""
    return 3.0 * pool_rows * values(conf) * conf["vocab_size"] * itemsize


def kernel_calls(conf: Dict, rows: int, itemsize: int
                 ) -> Dict[str, Dict[str, List[Tuple[float, float]]]]:
    """``{reader: {kernel: [(bytes, ops), ...]}}``, one entry per call in
    a step: ``gemv`` every stacked-GEMV call (the mixers' and the shared
    block's), ``shared`` the shared block's alone (``shared_roofline``
    takes their time from the trace's ``shared`` scope), ``head`` the
    logits head."""
    out: Dict[str, Dict[str, List[Tuple[float, float]]]] = {
        "gemv": {}, "shared": {},
        "head": {HEAD_KERNEL: [head_call(conf, rows, F32)]}}
    for kernel, (n, O), group in _calls(conf):
        call = gemv_call(conf, n, O, rows, itemsize)
        out["gemv"].setdefault(kernel, []).append(call)
        if group == "shared":
            out["shared"].setdefault(kernel, []).append(call)
    return out


def bytes_per_step(conf: Dict, eng, rows: int) -> str:
    """The log line of the bytes one step stages and the least it must
    move, in the GEMVs (the shared block's among them) and in the head."""
    itemsize = table_itemsize(eng)
    pool_rows = int(eng.pdecode.pcilt["head"]["pool"].shape[0])
    calls = _calls(conf)
    staged = sum(gemv_staged_bytes(conf, n, O, itemsize)
                 for _, (n, O), _ in calls)
    least = sum(gemv_call(conf, n, O, rows, itemsize)[0]
                for _, (n, O), _ in calls)
    shared = sum(gemv_staged_bytes(conf, n, O, itemsize)
                 for _, (n, O), g in calls if g == "shared")
    return (f"bytes per step: GEMV tables staged {staged:.0f} (shared block "
            f"{shared:.0f}), GEMV least {least:.0f}; head staged "
            f"{head_staged_bytes(conf, pool_rows, F32):.0f}, head least "
            f"{head_call(conf, rows, F32)[0]:.0f}")


def dense_flops_per_token(conf: Dict) -> float:
    """Matmul FLOPs of one token through the dense model the tables
    replace: every projection site and the tied head (the attention
    core's, under 1% at these lengths, left out)."""
    L, A = conf["num_hidden_layers"], len(hybrid_layers(conf))
    per_layer = sum(n * O for n, O in mamba_sites(conf).values())
    per_app = sum(n * O for n, O in shared_sites(conf).values())
    return 2.0 * (L * per_layer + A * per_app
                  + conf["hidden_size"] * conf["vocab_size"])


# -- the comparison ----------------------------------------------------------

def gaps(conf: Dict, params, scales, inputs, targets):
    """``(gap [B, T], step)`` of the reference's teacher-forced pass
    (``zamba2_reference.gaps``)."""
    import jax

    d = reference.dims(conf)
    return jax.jit(lambda p, s, x, y: reference.gaps(p, s, x, y, d))(
        params, scales, inputs, targets)


# -- the step for a described chip -------------------------------------------

def compile_step(conf: Dict, rows: int, sharding):
    """The program's monitored decode step (``HybridLM.decode_step`` with
    the tables as arguments and the sentinel's counters on, as the
    ``Engine`` runs it) at ``rows`` slots of ``kv_positions``, compiled for
    ``sharding``."""
    import jax
    import jax.numpy as jnp

    from repro.core import QuantSpec
    from repro.launch.steps import make_ctx
    from repro.models import build_model
    from repro.nn.module import shape_structs

    cfg = program_config(conf)
    m = build_model(cfg)
    d = reference.dims(conf)
    g, V = conf["pcilt"]["group"], values(conf)

    def sds(shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sharding)

    stack = {"layer": d.n_layer, "block": d.blocks, "app": len(d.hybrid)}
    kernels = m.site_kernels(jax.eval_shape(lambda: make_params(conf, 0)))
    params = jax.tree.map(lambda a: sds(a.shape, a.dtype),
                          jax.eval_shape(lambda: make_params(conf, 0)))
    cache = jax.tree.map(lambda a: sds(a.shape, a.dtype),
                         shape_structs(m.cache_specs(rows,
                                                     conf["kv_positions"])))
    spec = QuantSpec(bits=cfg.pcilt.act_bits, symmetric=True)
    sites = m.pcilt_sites()
    G_head = math.ceil(d.d_model / g)
    arrays = {
        "conv": sds((d.n_layer, d.conv_dim, 1 << (conf["pcilt"]["act_bits"]
                                                  * d.d_conv))),
        "conv_scale": sds(()),
        "proj": {n: sds((k.shape[0], math.ceil(k.shape[1] / g), V,
                         k.shape[2])) for n, k in kernels.items()},
        "proj_scales": {n: sds((stack[sites[n][0]],)) for n in kernels},
        "pool": sds((G_head, V, d.vocab)),
        "seg_idx": sds((G_head,), jnp.int32),
        "head_scale": sds(()), "head_step": sds(()),
        "kernel_q": sds((d.d_model, d.vocab)),
    }

    def bundle(a):
        return {
            "tables": a["conv"], "scale": a["conv_scale"], "spec": spec,
            "proj": {"tables": a["proj"], "scales": a["proj_scales"],
                     "spec": spec, "group": g, "path": "fused", "mesh": None,
                     "mesh_axis": "model", "paired": False},
            "head": {"pool": a["pool"], "seg_idx": a["seg_idx"], "group": g,
                     "spec": spec, "scale": a["head_scale"],
                     "step": a["head_step"], "kernel_q": a["kernel_q"],
                     "n": d.d_model}}

    ctx = make_ctx(None, None, decode=True)
    fn = jax.jit(lambda p, c, t, o, h, a: m.decode_step(
        p, c, t, ctx, pcilt=bundle(a), layer_ok=o, head_ok=h,
        with_stats=True))
    return fn.lower(params, cache, sds((rows, 1), jnp.int32),
                    sds((m.n_units,), jnp.bool_), sds((), jnp.bool_),
                    arrays).compile()
