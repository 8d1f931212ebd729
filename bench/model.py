"""A configuration file, turned into the benchmark's weights and the
program's model configuration.

The weights are the benchmark's: one jitted call draws every leaf from the
run's seed on the device, in float32 (the type they are served in), with
the published Mamba2 initialisation (``mamba_ssm``'s ``Mamba2`` and
``MixerModel``): embedding N(0, 0.02); in and out projections and the conv
uniform in +-1/sqrt(fan_in), the out projection divided by sqrt(n_layer);
``A = -U(1, 16)``; ``dt`` log-uniform in [0.001, 0.1] stored as its inverse
softplus; ``D`` and every norm weight 1.  The layout is the program's
parameter tree, so the same arrays feed the program and the reference.
"""

from __future__ import annotations

import dataclasses
import json
import math
from typing import Dict

import numpy as np

#: streams of the seed: one per thing drawn from it
STREAM_WEIGHTS, STREAM_CALIB, STREAM_TRAFFIC, STREAM_SAMPLE = range(4)


def load_config(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def seed_ints(seed: int, stream: int, n: int = 1) -> np.ndarray:
    """``n`` uint32 words for one stream of a seed of any size."""
    return np.random.SeedSequence([int(seed), stream]).generate_state(n)


def rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(seed_ints(seed, stream, 4))


def padded_vocab(conf: Dict) -> int:
    m = conf["pad_vocab_size_multiple"]
    return -(-conf["vocab_size"] // m) * m


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
    if cfg.padded_vocab != padded_vocab(conf):
        raise ValueError(f"program pads the vocabulary to {cfg.padded_vocab}, "
                         f"the file to {padded_vocab(conf)}")
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
    Vp = padded_vocab(conf)
    key = jax.random.PRNGKey(int(seed_ints(seed, STREAM_WEIGHTS)[0] >> 1))

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


def calib_tokens(conf: Dict, seed: int) -> np.ndarray:
    """The calibration batch ``[2, 16]``, the size the program calibrates
    on, drawn from the seed."""
    return rng(seed, STREAM_CALIB).integers(
        0, conf["vocab_size"], size=(2, 16)).astype(np.int32)
