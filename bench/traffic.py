"""The one traffic generator: reads a mix's parameters from
``bench/traffic/<name>.json`` and makes its requests from a seed.

A mix file holds::

    {"loop": "closed" | "open", "slots": 32,
     "requests": 96,                      # closed: offered at once
     "rate": 0.5, "burst": 1,             # open: requests/s, group size
     "prompt": {"median": 16, "sigma": 1.0, "min": 4, "max": 64},
     "output": {"median": 256, "sigma": 1.0, "min": 32, "max": 1024}}

Lengths are lognormal (``median``, log-space ``sigma``) clipped to
``[min, max]``.  Every seed gets the same lengths and inter-arrival gaps,
taken at evenly spaced quantiles of their distributions, in one fixed
shuffled order; the seed draws the token ids.  The order is not the
seed's: which requests finish first decides how many refills fall inside
a window, and with the order drawn from the seed, six seeds of the batch
mix read 141 to 335 tokens/s while two runs of one seed agreed to the
token (TPU v5 lite).

Open-loop arrivals follow the Poisson arithmetic of the program's
``runtime/traffic.py`` (exponential gaps at ``rate``; with ``burst`` > 1,
groups of ``burst`` simultaneous arrivals with the gaps stretched by
``burst``, so the mean rate stays ``rate``).  An open mix makes enough
requests to cover ``seconds`` with a quarter to spare.
"""

from __future__ import annotations

import json
import math
import os
from statistics import NormalDist
from typing import Dict, List, NamedTuple, Optional

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


class Req(NamedTuple):
    rid: int
    arrival_s: Optional[float]  # offset from the window's start; None: closed
    prompt: np.ndarray
    max_new: int


def load(name: str) -> Dict:
    with open(os.path.join(HERE, "traffic", name + ".json")) as f:
        return json.load(f)


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(spec: Dict, n: int) -> np.ndarray:
    """The ``n`` lengths of a lognormal spec, at evenly spaced quantiles."""
    z = np.array([NormalDist().inv_cdf(p) for p in _quantiles(n)])
    x = np.round(spec["median"] * np.exp(spec["sigma"] * z))
    return np.clip(x, spec["min"], spec["max"]).astype(np.int64)


def gaps(rate: float, n: int) -> np.ndarray:
    """``n`` exponential inter-arrival gaps at ``rate``, at evenly spaced
    quantiles."""
    if rate <= 0:
        raise ValueError(f"rate must be positive, got {rate}")
    return -np.log1p(-_quantiles(n)) / rate


def count(mix: Dict, seconds: float) -> int:
    if mix["loop"] == "closed":
        return int(mix["requests"])
    return int(math.ceil(mix["rate"] * seconds * 1.25)) + 1


#: seeds the one order of lengths and gaps that every run shares
ORDER_SEED = 20260417


def generate(mix: Dict, vocab: int, seconds: float,
             rng: np.random.Generator) -> List[Req]:
    """The requests of one run, in arrival order; ``rng`` (the run's seed)
    draws the token ids."""
    n = count(mix, seconds)
    order = np.random.default_rng(ORDER_SEED)
    plen = order.permutation(lengths(mix["prompt"], n))
    olen = order.permutation(lengths(mix["output"], n))
    arrivals = [None] * n
    if mix["loop"] == "open":
        burst = int(mix.get("burst", 1))
        groups = -(-n // burst)
        g = order.permutation(gaps(mix["rate"] / burst, groups))
        arrivals = np.repeat(np.cumsum(g), burst)[:n].tolist()
    elif mix["loop"] != "closed":
        raise ValueError(f"loop must be closed or open, got {mix['loop']!r}")
    return [Req(i, arrivals[i],
                rng.integers(2, vocab, size=int(plen[i])).astype(np.int32),
                int(olen[i])) for i in range(n)]
