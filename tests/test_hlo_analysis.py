"""The trip-count-aware HLO analyzer against analytically-known costs."""

import jax
from jax.sharding import AxisType
import jax.numpy as jnp

from repro.launch.hlo_analysis import analyze_hlo


def _scan_matmul(L=8, d=128, b=64):
    def model(ws, x):
        def body(c, w):
            return c @ w, ()
        y, _ = jax.lax.scan(body, x, ws)
        return y.sum()
    return model, jnp.zeros((L, d, d)), jnp.zeros((b, d)), 2 * b * d * d * L


def test_forward_flops_exact():
    model, ws, x, expect = _scan_matmul()
    c = jax.jit(model).lower(ws, x).compile()
    got = analyze_hlo(c.as_text())["flops"]
    assert abs(got - expect) / expect < 1e-6


def test_grad_flops_3x():
    model, ws, x, expect = _scan_matmul()
    c = jax.jit(jax.grad(model)).lower(ws, x).compile()
    got = analyze_hlo(c.as_text())["flops"]
    assert abs(got - 3 * expect) / (3 * expect) < 1e-6


def test_trip_count_scales_with_layers():
    m8, ws8, x, e8 = _scan_matmul(L=8)
    m16, ws16, _, e16 = _scan_matmul(L=16)
    f8 = analyze_hlo(jax.jit(m8).lower(ws8, x).compile().as_text())["flops"]
    f16 = analyze_hlo(jax.jit(m16).lower(ws16, x).compile().as_text())["flops"]
    assert abs(f16 / f8 - 2.0) < 1e-6


def test_collectives_weighted_by_trips():
    import numpy as np
    from jax.sharding import PartitionSpec as P

    if len(jax.devices()) < 1:
        return
    mesh = jax.make_mesh((1,), ("data",),
                         axis_types=(AxisType.Auto,) * 1)

    def body(xl):
        def step(c, _):
            return jax.lax.psum(c, "data"), ()
        y, _ = jax.lax.scan(step, xl, None, length=5)
        return y

    f = jax.shard_map(body, mesh=mesh, in_specs=P(), out_specs=P())
    c = jax.jit(f).lower(jnp.zeros((4, 4))).compile()
    a = analyze_hlo(c.as_text())
    # psum of 64B fp32 × 5 trips (single-device AR may be optimized away;
    # accept either exact 5× weighting or a fully-elided collective)
    total = a["coll"]["all-reduce"]["count"]
    assert total in (0, 5), a["coll"]


def test_top_diagnostics_present():
    model, ws, x, _ = _scan_matmul()
    c = jax.jit(model).lower(ws, x).compile()
    a = analyze_hlo(c.as_text())
    assert "top_collectives" in a and "top_buffers" in a
    assert a["bytes_traffic_est"] > 0
