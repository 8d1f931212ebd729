"""JAX's persistent compilation cache, kept where the caller can find it
again.

Launchers call :func:`enable_compile_cache` before their first compile (never
at import).  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it
and nothing else is configured; otherwise the cache lives at
``<checkout>/.jax_cache`` — a fixed path, because the path is part of what a
later process must find.
"""

from __future__ import annotations

import os

__all__ = ["CHECKOUT", "enable_compile_cache"]

#: the repository root (``src/repro/launch`` -> three levels up)
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(CHECKOUT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
