"""Production mesh construction.

v5e pod topology: 16×16 = 256 chips per pod; the multi-pod mesh adds a
leading "pod" axis (2 pods = 512 chips) used purely as an extra
data-parallel axis (batch shards over ("pod", "data")) — cross-pod traffic
is then only the gradient reduction, which is the right thing to put on the
slower inter-pod links.

A FUNCTION, not a module constant: importing this module never touches jax
device state (the dry-run sets XLA_FLAGS before any jax import; tests run
on 1 device).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_host_mesh", "make_decode_mesh"]


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over whatever devices exist (tests / local runs)."""
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def make_decode_mesh(model: int = 0):
    """Tensor-parallel decode mesh: all of ``model`` on one axis, data=1.

    The batch-starved decode GEMV has no batch to shard; what needs sharding
    is the *weight state* — for PCILT layers the ``[G, V, O]`` tables, whose
    segment axis shards over ``"model"`` (``nn.module.DEFAULT_RULES``
    ``"table_seg"``) with the partial adder-tree sums psum'd.  ``model=0``
    (default) spans every local device; tests pass 1/2/4/8 under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8``.
    """
    return make_host_mesh(1, model or jax.device_count())
