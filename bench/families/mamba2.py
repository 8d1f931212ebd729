"""The Mamba2 family: a stack of Mamba2 blocks with a tied logits head,
served by the program's ``MambaLM`` through PCILT tables.

The harness (``bench/run.py``, ``bench/compile_check.py`` and the readers
in ``bench/metrics``) knows nothing of the architecture; it asks this
module for the hooks that ``bench/model.py`` lists in ``HOOKS``.  The plain
reference is ``mamba2_reference.py`` beside this file.

The weights are the benchmark's: one jitted call draws every leaf from the
run's seed on the device, in float32 (the type they are served in), with
the published Mamba2 initialisation (``mamba_ssm``'s ``Mamba2`` and
``MixerModel``): embedding N(0, 0.02); in and out projections and the conv
uniform in +-1/sqrt(fan_in), the out projection divided by sqrt(n_layer);
``A = -U(1, 16)``; ``dt`` log-uniform in [0.001, 0.1] stored as its inverse
softplus; ``D`` and every norm weight 1.  The layout is the program's
parameter tree, so the same arrays feed the program and the reference.

Kernel bytes follow ``bench/roofline.py``'s rule: the least a correct
lookup moves.  Today's kernels stage all ``V`` rows of every segment, so
their shares of that roofline read low (about ``1/V`` of the bandwidth
they use).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Tuple

from bench import model
from bench.families import mamba2_reference as reference

#: sizes a configuration of this family never cuts
WIDTHS = ("d_model", "d_state", "headdim", "expand", "d_intermediate",
          "ngroups")
#: share of the compared served tokens whose logit may lie below the
#: reference's best: sound runs read 0.0030 at most, tables held in
#: bfloat16 0.0125 or more (PERF.md gives the readings)
MAX_MISMATCH_SHARE = 0.007
LIMITS = {"mismatch_share": MAX_MISMATCH_SHARE}
#: the stacked-GEMV kernel names and the projections each runs (with the
#: drift sentinel on, ``wx`` and ``wo`` carry the saturation counters)
GEMV_KERNELS = {"pcilt_stacked_gemv_sat": ("wx", "wo"),
                "pcilt_stacked_gemv": ("wz", "wB", "wC", "wdt")}
HEAD_KERNEL = "pcilt_shared_gemv"
#: the kernel that runs once per decode-path step: the logits head
STEP_KERNEL = HEAD_KERNEL
F32 = 4


# -- the program's model and the weights -------------------------------------

def program_config(conf: Dict):
    """The program's ``ModelConfig`` for this file: the served format of
    ``conf["program"]["base"]`` with every size taken from the file."""
    from repro.configs.base import PCILTConfig
    from repro.launch.serve import serve_config

    s, q = conf["ssm_cfg"], conf["pcilt"]
    base = serve_config(conf["program"]["base"], full=True, pcilt=True)
    ssm = dataclasses.replace(
        base.ssm, d_state=s["d_state"], head_dim=s["headdim"],
        n_groups=s["ngroups"], conv_kernel=s["d_conv"], expand=s["expand"],
        chunk=s["chunk_size"])
    cfg = dataclasses.replace(
        base, name=conf["name"], n_layers=conf["n_layer"],
        d_model=conf["d_model"], vocab=conf["vocab_size"], ssm=ssm,
        tie_embeddings=conf["tie_embeddings"], norm_eps=conf["norm_epsilon"],
        pcilt=PCILTConfig(act_bits=q["act_bits"], group=q["group"]))
    if cfg.padded_vocab != model.padded_vocab(conf):
        raise ValueError(f"program pads the vocabulary to {cfg.padded_vocab}, "
                         f"the file to {model.padded_vocab(conf)}")
    return cfg


def make_params(conf: Dict, seed: int):
    """Every weight, drawn from ``seed`` on the device in one jitted call."""
    import jax
    import jax.numpy as jnp

    s = conf["ssm_cfg"]
    d, L = conf["d_model"], conf["n_layer"]
    di = s["expand"] * d
    H = di // s["headdim"]
    gn = s["ngroups"] * s["d_state"]
    conv_dim = di + 2 * gn
    k = s["d_conv"]
    Vp = model.padded_vocab(conf)
    key = jax.random.PRNGKey(
        int(model.seed_ints(seed, model.STREAM_WEIGHTS)[0] >> 1))

    def uni(key, shape, bound):
        return jax.random.uniform(key, shape, jnp.float32, -bound, bound)

    @jax.jit
    def init(key):
        ks = iter(jax.random.split(key, 16))
        lin = {name: {"kernel": uni(next(ks), (L, d, o), 1 / math.sqrt(d))}
               for name, o in (("wz", di), ("wx", di), ("wB", gn),
                               ("wC", gn), ("wdt", H))}
        dt = jnp.exp(jax.random.uniform(next(ks), (L, H), jnp.float32,
                                        math.log(1e-3), math.log(0.1)))
        dt = jnp.maximum(dt, 1e-4)
        mixer = dict(
            lin,
            conv_w=uni(next(ks), (L, k, conv_dim), 1 / math.sqrt(k)),
            conv_b=uni(next(ks), (L, conv_dim), 1 / math.sqrt(k)),
            A_log=jnp.log(jax.random.uniform(next(ks), (L, H), jnp.float32,
                                             1.0, 16.0)),
            dt_bias=dt + jnp.log(-jnp.expm1(-dt)),
            D=jnp.ones((L, H), jnp.float32),
            norm={"scale": jnp.ones((L, di), jnp.float32)},
            wo={"kernel": uni(next(ks), (L, di, d), 1 / math.sqrt(di))
                / math.sqrt(L)})
        return {
            "embed": {"embedding": 0.02 * jax.random.normal(
                next(ks), (Vp, d), jnp.float32)},
            "blocks": {"ln": {"scale": jnp.ones((L, d), jnp.float32)},
                       "mixer": mixer},
            "ln_f": {"scale": jnp.ones((d,), jnp.float32)},
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
    """The program's tables (``MambaLM.build_pcilt``, the configuration's
    own table format) from the weights and scales, and an
    ``engine_class`` serving them on ``slots`` slots by ``clock``."""
    import jax.numpy as jnp

    from repro.models import build_model

    cfg = program_config(conf)
    q = conf["pcilt"]
    if q["head"] != "shared":
        raise SystemExit(f"unsupported head {q['head']!r}")
    bundle = build_model(cfg).build_pcilt(
        params, scales["conv"], proj_scales={"in": scales["in"],
                                             "out": scales["out"]},
        proj_path="fused", table_dtype=jnp.dtype(table_dtype),
        head_scale=scales["head"], head_weight_bits=q["head_weight_bits"])
    eng = engine_class(cfg, slots=slots, pcilt=True, pcilt_bundle=bundle,
                       clock=clock)
    # the engine draws its own weights from a fixed key; serve the seed's
    eng.params = params
    eng.monitor.params = params
    return eng


def warm_up(eng) -> None:
    """The monitor's dense-oracle probe of each projection."""
    from repro.nn.ssm import PROJ_NAMES

    for name in PROJ_NAMES:
        eng.monitor._oracle_check(0, name)


def table_itemsize(eng) -> int:
    """Bytes of one entry of the projection tables the engine serves."""
    return next(iter(
        eng.pdecode.pcilt["proj"]["tables"].values())).dtype.itemsize


def bytes_per_step(conf: Dict, eng, rows: int) -> str:
    """The log line of the bytes one step stages and the least it must
    move, in the GEMVs of every layer and in the head."""
    itemsize = table_itemsize(eng)
    pool_rows = int(eng.pdecode.pcilt["head"]["pool"].shape[0])
    staged = sum(gemv_staged_bytes(conf, n, itemsize)
                 for n in projections(conf)) * conf["n_layer"]
    least = sum(gemv_call(conf, n, rows, itemsize)[0]
                for n in projections(conf)) * conf["n_layer"]
    return (f"bytes per step: GEMV tables staged {staged:.0f}, GEMV least "
            f"{least:.0f}; head staged "
            f"{head_staged_bytes(conf, pool_rows, F32):.0f}, head least "
            f"{head_call(conf, rows, F32)[0]:.0f}")


# -- the comparison ----------------------------------------------------------

def gaps(conf: Dict, params, scales, inputs, targets):
    """``(gap [B, T], step)`` of the reference's teacher-forced pass
    (``mamba2_reference.gaps``)."""
    import jax

    d = reference.dims(conf)
    return jax.jit(lambda p, s, x, y: reference.gaps(p, s, x, y, d))(
        params, scales, inputs, targets)


# -- operations and bytes ----------------------------------------------------

def projections(conf: Dict) -> Dict[str, Tuple[int, int]]:
    """``{name: (n, O)}`` of the six decode projections of one layer."""
    s = conf["ssm_cfg"]
    d = conf["d_model"]
    di = s["expand"] * d
    gn = s["ngroups"] * s["d_state"]
    return {"wz": (d, di), "wx": (d, di), "wB": (d, gn), "wC": (d, gn),
            "wdt": (d, di // s["headdim"]), "wo": (di, d)}


def values(conf: Dict) -> int:
    """``V``: table rows per segment."""
    q = conf["pcilt"]
    return 1 << (q["act_bits"] * q["group"])


def gemv_call(conf: Dict, name: str, rows: int,
              itemsize: int) -> Tuple[float, float]:
    """``(bytes, ops)`` one stacked-GEMV call of projection ``name`` must
    move and do at a batch of ``rows``."""
    n, O = projections(conf)[name]
    G = math.ceil(n / conf["pcilt"]["group"])
    return (G * O * itemsize + rows * n * F32 + rows * O * F32,
            float(rows * G * O))


def gemv_staged_bytes(conf: Dict, name: str, itemsize: int) -> float:
    """Table bytes today's kernel stages for one call: every row."""
    n, O = projections(conf)[name]
    G = math.ceil(n / conf["pcilt"]["group"])
    return float(G * values(conf) * O * itemsize)


def head_call(conf: Dict, rows: int, itemsize: int) -> Tuple[float, float]:
    """``(bytes, ops)`` of one shared-pool head call: one pool row per
    segment, the segment pointers, activations in, logits out."""
    d, Vp = conf["d_model"], model.padded_vocab(conf)
    G = math.ceil(d / conf["pcilt"]["group"])
    return (G * Vp * itemsize + G * 4 + rows * d * F32 + rows * Vp * F32,
            float(rows * G * Vp))


def head_staged_bytes(conf: Dict, pool_rows: int, itemsize: int) -> float:
    """Bytes today's head call moves in its pool: the per-call transpose
    reads and writes the whole ``[X, V, O]`` pool, and the kernel stages
    it once more."""
    return 3.0 * pool_rows * values(conf) * model.padded_vocab(conf) * itemsize


def kernel_calls(conf: Dict, rows: int, itemsize: int
                 ) -> Dict[str, Dict[str, List[Tuple[float, float]]]]:
    """``{reader: {kernel: [(bytes, ops), ...]}}``: one call of each kernel
    at a batch of ``rows`` with tables of ``itemsize`` bytes, under the
    roofline reader that charges it (``gemv`` for ``gemv_roofline``,
    ``head`` for ``head_roofline``).  A kernel that runs several
    projections lists one call of each; its calls are charged their mean."""
    return {"gemv": {k: [gemv_call(conf, n, rows, itemsize) for n in names]
                     for k, names in GEMV_KERNELS.items()},
            "head": {HEAD_KERNEL: [head_call(conf, rows, F32)]}}


def dense_flops_per_token(conf: Dict) -> float:
    """Matmul FLOPs of one token through the dense model the tables
    replace: the six projections of every layer and the tied head."""
    per_layer = sum(n * O for n, O in projections(conf).values())
    return 2.0 * (conf["n_layer"] * per_layer
                  + conf["d_model"] * conf["vocab_size"])


# -- the step for a described chip -------------------------------------------

def _step_shapes(conf: Dict, rows: int, sharding):
    """``(model, cfg, params, cache, tokens, layer_ok, head_ok, arrays)``
    as shapes."""
    import jax
    import jax.numpy as jnp

    from repro.models import build_model
    from repro.nn.module import shape_structs

    cfg = program_config(conf)
    m = build_model(cfg)
    s = conf["ssm_cfg"]
    d, L = conf["d_model"], conf["n_layer"]
    di = s["expand"] * d
    conv = di + 2 * s["ngroups"] * s["d_state"]
    V, g = values(conf), conf["pcilt"]["group"]
    Vp = model.padded_vocab(conf)
    G_head = math.ceil(d / g)

    def sds(shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sharding)

    params = jax.tree.map(lambda a: sds(a.shape, a.dtype),
                          jax.eval_shape(lambda: make_params(conf, 0)))
    cache = jax.tree.map(lambda a: sds(a.shape, a.dtype),
                         shape_structs(m.cache_specs(rows, 256)))
    arrays = {
        "conv": sds((L, conv, 1 << (conf["pcilt"]["act_bits"]
                                    * s["d_conv"]))),
        "conv_scale": sds(()),
        "proj": {n: sds((L, math.ceil(k / g), V, o)) for n, (k, o)
                 in projections(conf).items()},
        "proj_scales": {n: sds((L,)) for n in projections(conf)},
        "pool": sds((G_head, V, Vp)), "seg_idx": sds((G_head,), jnp.int32),
        "head_scale": sds(()), "head_step": sds(()),
        "kernel_q": sds((d, Vp)),
    }
    return (m, cfg, params, cache, sds((rows, 1), jnp.int32),
            sds((L,), jnp.bool_), sds((), jnp.bool_), arrays)


def _bundle(arrays, cfg):
    """The table bundle ``MambaLM.build_pcilt`` returns, from its arrays."""
    from repro.core import QuantSpec

    spec = QuantSpec(bits=cfg.pcilt.act_bits, symmetric=True)
    g = cfg.pcilt.group
    return {
        "tables": arrays["conv"], "scale": arrays["conv_scale"], "spec": spec,
        "proj": {"tables": arrays["proj"], "scales": arrays["proj_scales"],
                 "spec": spec, "group": g, "path": "fused", "mesh": None,
                 "mesh_axis": "model", "paired": False},
        "head": {"pool": arrays["pool"], "seg_idx": arrays["seg_idx"],
                 "group": g, "spec": QuantSpec(bits=cfg.pcilt.act_bits,
                                               symmetric=True),
                 "scale": arrays["head_scale"], "step": arrays["head_step"],
                 "kernel_q": arrays["kernel_q"],
                 "n": arrays["kernel_q"].shape[0]},
    }


def compile_step(conf: Dict, rows: int, sharding):
    """The program's monitored decode step (``MambaLM.decode_step`` with
    the tables as arguments and the drift sentinel's counters on, as the
    ``Engine`` runs it) at ``rows`` slots, compiled for ``sharding``."""
    import jax

    from repro.launch.steps import make_ctx

    m, cfg, params, cache, tok, ok, hok, arrays = _step_shapes(
        conf, rows, sharding)
    ctx = make_ctx(None, None, decode=True)
    fn = jax.jit(lambda p, c, t, o, h, a: m.decode_step(
        p, c, t, ctx, pcilt=_bundle(a, cfg), layer_ok=o, head_ok=h,
        with_stats=True))
    return fn.lower(params, cache, tok, ok, hok, arrays).compile()
