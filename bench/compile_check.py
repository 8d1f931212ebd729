#!/usr/bin/env python3
"""Compile a cell's monitored decode step for a described TPU v5e, with no
chip attached, and print what the chip's compiler reports.

    JAX_PLATFORMS=cpu python3 bench/compile_check.py mamba2-130m 32

The step is the one the configuration's family compiles (``compile_step``
of ``bench/families/<family>.py``: the program's own monitored decode step,
as the ``Engine`` runs it) at the file's widths and the given slot count.
It prints the kernels the program holds as ``tpu_custom_call``s and
``memory_analysis()``: the bytes of arguments, outputs and temporaries of
one step.  Nothing runs, so this gives no time and no result.
"""

from __future__ import annotations

import os
import re
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import model  # noqa: E402


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
    compiled = model.family(conf).compile_step(
        conf, rows, SingleDeviceSharding(topo.devices[0]))
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
