"""Operations and bytes of the served kernels, and the chip's peaks.

The bytes a kernel call is charged are the **least that any correct
implementation of the lookup must move**, never what today's kernel
stages: one table row of ``O`` entries per segment per call, in the dtype
the tables are held in, plus the activations in and the outputs out.
Today's kernels stage all ``V`` rows of every segment, so their shares of
this roofline read low (about ``1/V`` of the bandwidth they use), and a
kernel that fetched only the selected rows could approach 100% but never
pass it.  The operations are one add per fetched entry per row.

A roofline share is the least time, the larger of bytes over the peak
bandwidth and operations over the peak rate, divided by the measured
kernel time.  The peaks are ``peaks.json``'s, keyed by ``device_kind``; a
device not in that table is an error.
"""

from __future__ import annotations

import json
import math
import os
from typing import Dict, Tuple

from bench.model import padded_vocab

HERE = os.path.dirname(os.path.abspath(__file__))

#: the stacked-GEMV kernel names and the projections each runs (with the
#: drift sentinel on, ``wx`` and ``wo`` carry the saturation counters)
GEMV_KERNELS = {"pcilt_stacked_gemv_sat": ("wx", "wo"),
                "pcilt_stacked_gemv": ("wz", "wB", "wC", "wdt")}
HEAD_KERNEL = "pcilt_shared_gemv"
F32 = 4


def peaks(device_kind: str) -> Dict[str, float]:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json")
    return table[device_kind]


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
    d, Vp = conf["d_model"], padded_vocab(conf)
    G = math.ceil(d / conf["pcilt"]["group"])
    return (G * Vp * itemsize + G * 4 + rows * d * F32 + rows * Vp * F32,
            float(rows * G * Vp))


def head_staged_bytes(conf: Dict, pool_rows: int, itemsize: int) -> float:
    """Bytes today's head call moves in its pool: the per-call transpose
    reads and writes the whole ``[X, V, O]`` pool, and the kernel stages
    it once more."""
    return 3.0 * pool_rows * values(conf) * padded_vocab(conf) * itemsize


def least_time(nbytes: float, ops: float, pk: Dict[str, float]) -> float:
    return max(nbytes / pk["hbm_bytes_per_s"], ops / pk["bf16_flops"])


def dense_flops_per_token(conf: Dict) -> float:
    """Matmul FLOPs of one token through the dense model the tables
    replace: the six projections of every layer and the tied head."""
    per_layer = sum(n * O for n, O in projections(conf).values())
    return 2.0 * (conf["n_layer"] * per_layer
                  + conf["d_model"] * conf["vocab_size"])
