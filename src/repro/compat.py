"""Tracer detection for eager-vs-traced dispatch.

The autotuner times kernels only on concrete inputs — never under a ``jit``
trace — so the dispatch wrappers need to tell the two apart.
"""

from __future__ import annotations

import jax

__all__ = ["is_tracer"]


def is_tracer(x) -> bool:
    """True when ``x`` is an abstract value of an ongoing trace."""
    return isinstance(x, jax.core.Tracer)
