"""The chip's peaks, and a kernel call's least time.

The bytes and operations of one call of each kernel are the family's
(``kernel_calls`` of ``bench/families/<family>.py``).  The bytes a call is
charged are the **least that any correct implementation of the lookup must
move**, never what today's kernel stages: one table row of ``O`` entries
per segment per call, in the dtype the tables are held in, plus the
activations in and the outputs out.  A kernel that fetched only the
selected rows could approach 100% of this roofline but never pass it.  The
operations are one add per fetched entry per row.

A roofline share is the least time, the larger of bytes over the peak
bandwidth and operations over the peak rate, divided by the measured
kernel time.  The peaks are ``peaks.json``'s, keyed by ``device_kind``; a
device not in that table is an error.
"""

from __future__ import annotations

import json
import os
from typing import Dict

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> Dict[str, float]:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json")
    return table[device_kind]


def least_time(nbytes: float, ops: float, pk: Dict[str, float]) -> float:
    return max(nbytes / pk["hbm_bytes_per_s"], ops / pk["bf16_flops"])
