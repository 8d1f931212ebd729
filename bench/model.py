"""A configuration file, the streams of a run's seed, and the module of the
configuration's architecture family.

A configuration names its family under ``"family"``; the harness loads
``bench/families/<family>.py`` by path (as ``run.reader`` loads a metric's
reader), so a new family is a new file there and nothing else.  What the
harness asks of a family module is :data:`HOOKS`; ``families/mamba2.py``
documents each.
"""

from __future__ import annotations

import importlib.util
import json
import os
from typing import Dict

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
#: where a configuration's family module is looked for
FAMILIES = os.path.join(HERE, "families")
#: what ``bench/run.py``, ``bench/compile_check.py`` and the metric readers
#: take from a family module
HOOKS = ("WIDTHS", "LIMITS", "STEP_KERNEL", "make_params", "calibrate",
         "build", "warm_up", "table_itemsize", "bytes_per_step", "gaps",
         "kernel_calls", "dense_flops_per_token", "compile_step")

#: streams of the seed: one per thing drawn from it
STREAM_WEIGHTS, STREAM_CALIB, STREAM_TRAFFIC, STREAM_SAMPLE = range(4)


def load_config(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def family(conf: Dict):
    """The module of the configuration's ``"family"``, loaded from
    ``FAMILIES``; exits naming the file it looked for if there is none."""
    name = conf.get("family")
    where = os.path.join(FAMILIES, f"{name or '<family>'}.py")
    if not name:
        raise SystemExit(f"configuration {conf.get('name')!r} names no "
                         f"\"family\": the harness looks for {where}")
    if not os.path.isfile(where):
        raise SystemExit(f"no family module {where} for configuration "
                         f"{conf.get('name')!r}")
    spec = importlib.util.spec_from_file_location("bench_family_" + name,
                                                  where)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    missing = [h for h in HOOKS if not hasattr(mod, h)]
    if missing:
        raise SystemExit(f"family module {where} lacks {', '.join(missing)}")
    return mod


def seed_ints(seed: int, stream: int, n: int = 1) -> np.ndarray:
    """``n`` uint32 words for one stream of a seed of any size."""
    return np.random.SeedSequence([int(seed), stream]).generate_state(n)


def rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(seed_ints(seed, stream, 4))


def padded_vocab(conf: Dict) -> int:
    m = conf["pad_vocab_size_multiple"]
    return -(-conf["vocab_size"] // m) * m


def calib_tokens(conf: Dict, seed: int) -> np.ndarray:
    """The calibration batch ``[2, 16]``, the size the program calibrates
    on, drawn from the seed."""
    return rng(seed, STREAM_CALIB).integers(
        0, conf["vocab_size"], size=(2, 16)).astype(np.int32)
