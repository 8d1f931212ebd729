#!/usr/bin/env python3
"""Compile a cell's monitored decode step for a described TPU v5e, with no
chip attached, and print what the chip's compiler reports.

    JAX_PLATFORMS=cpu python3 bench/compile_check.py mamba2-130m 32

The step is the program's own (``MambaLM.decode_step`` with the tables as
arguments and the drift sentinel's counters on, as the ``Engine`` runs it)
at the configuration file's widths and the given slot count.  It prints
the kernels the program holds as ``tpu_custom_call``s and
``memory_analysis()``: the bytes of arguments, outputs and temporaries of
one step.  Nothing runs, so this gives no time and no result.
"""

from __future__ import annotations

import math
import os
import re
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import model, roofline  # noqa: E402


def step_shapes(conf, rows: int, sharding):
    """``(params, cache, tokens, layer_ok, head_ok, arrays)`` as shapes."""
    import jax
    import jax.numpy as jnp

    from repro.models import build_model
    from repro.nn.module import shape_structs

    cfg = model.program_config(conf)
    m = build_model(cfg)
    s = conf["ssm_cfg"]
    d, L = conf["d_model"], conf["n_layer"]
    di = s["expand"] * d
    conv = di + 2 * s["ngroups"] * s["d_state"]
    V, g = roofline.values(conf), conf["pcilt"]["group"]
    Vp = model.padded_vocab(conf)
    G_head = math.ceil(d / g)

    def sds(shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sharding)

    params = jax.tree.map(lambda a: sds(a.shape, a.dtype),
                          jax.eval_shape(lambda: model.make_params(conf, 0)))
    cache = jax.tree.map(lambda a: sds(a.shape, a.dtype),
                         shape_structs(m.cache_specs(rows, 256)))
    arrays = {
        "conv": sds((L, conv, 1 << (conf["pcilt"]["act_bits"]
                                    * s["d_conv"]))),
        "conv_scale": sds(()),
        "proj": {n: sds((L, math.ceil(k / g), V, o)) for n, (k, o)
                 in roofline.projections(conf).items()},
        "proj_scales": {n: sds((L,)) for n in roofline.projections(conf)},
        "pool": sds((G_head, V, Vp)), "seg_idx": sds((G_head,), jnp.int32),
        "head_scale": sds(()), "head_step": sds(()),
        "kernel_q": sds((d, Vp)),
    }
    return (m, cfg, params, cache, sds((rows, 1), jnp.int32),
            sds((L,), jnp.bool_), sds((), jnp.bool_), arrays)


def bundle(arrays, cfg):
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


def compile_step(conf, rows: int, sharding):
    import jax

    from repro.launch.steps import make_ctx

    m, cfg, params, cache, tok, ok, hok, arrays = step_shapes(
        conf, rows, sharding)
    ctx = make_ctx(None, None, decode=True)
    fn = jax.jit(lambda p, c, t, o, h, a: m.decode_step(
        p, c, t, ctx, pcilt=bundle(a, cfg), layer_ok=o, head_ok=h,
        with_stats=True))
    return fn.lower(params, cache, tok, ok, hok, arrays).compile()


def main(argv) -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from repro.kernels import ops

    name, rows = argv[0], int(argv[1])
    conf = model.load_config(os.path.join(HERE, "configs", name + ".json"))
    jax.config.update("jax_enable_compilation_cache", False)
    ops.on_tpu = lambda: True  # compile the kernels, not their interpreter
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    compiled = compile_step(conf, rows, SingleDeviceSharding(topo.devices[0]))
    text = compiled.as_text()
    calls = {}
    for ln in text.splitlines():
        if "tpu_custom_call" in ln:
            mm = re.match(r"\s*(?:ROOT\s+)?%?([A-Za-z_][\w.]*?)(?:\.\d+)?\s*=",
                          ln)
            key = mm.group(1) if mm else "?"
            calls[key] = calls.get(key, 0) + 1
    print(f"{name} rows={rows}: tpu_custom_calls {calls}")
    print(f"{name} rows={rows}: {compiled.memory_analysis()}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
