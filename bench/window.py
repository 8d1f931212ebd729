"""Arithmetic over the measured window, on the times the harness recorded.

``token_times`` maps each request to the clock times at which its output
tokens were committed, in order; ``arrivals`` maps each request to its
scheduled arrival (``None`` for a closed loop).  The window is ``[w0, w1]``.
"""

from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np


def tokens(token_times: Dict[int, List[float]], w0: float, w1: float) -> int:
    """Output tokens committed inside the window."""
    return sum(w0 <= t <= w1 for ts in token_times.values() for t in ts)


def tok_s(token_times, w0, w1) -> float:
    return tokens(token_times, w0, w1) / (w1 - w0)


def gaps(token_times, w0, w1) -> List[float]:
    """Every gap between consecutive output tokens of one request, both
    inside the window."""
    out = []
    for ts in token_times.values():
        inside = [t for t in ts if w0 <= t <= w1]
        out.extend(b - a for a, b in zip(inside, inside[1:]))
    return out


def ttfts(arrivals: Dict[int, Optional[float]],
          token_times: Dict[int, List[float]], w0, w1) -> List[float]:
    """Time from scheduled arrival to first token of every request that
    arrived in the window; one with no first token by ``w1`` counts at its
    wait so far."""
    out = []
    for rid, a in arrivals.items():
        if a is None or not w0 <= a <= w1:
            continue
        ts = token_times.get(rid) or []
        first = ts[0] if ts and ts[0] <= w1 else w1
        out.append(first - a)
    return out


def percentile(values, q: float) -> Optional[float]:
    return float(np.percentile(values, q)) if len(values) else None


def spans(span_list, name: str, w0, w1) -> List[float]:
    """Durations of the spans called ``name`` that started in the window."""
    return [b - a for n, a, b in span_list if n == name and w0 <= a <= w1]
