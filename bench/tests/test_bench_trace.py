"""The trace reduction, on a small recorded trace (``data/trace_small.pbtxt``
read through ``jax.profiler.ProfileData``, as a chip trace is)."""

import os

import pytest

from bench import trace

DATA = os.path.join(os.path.dirname(__file__), "data")


@pytest.fixture(scope="module")
def reduced():
    from jax.profiler import ProfileData

    with open(os.path.join(DATA, "trace_small.pbtxt")) as f:
        planes = ProfileData.from_text_proto(f.read()).planes
    return trace.reduce_planes(planes, 10e-6)


def test_busy_is_the_union_clipped_to_the_window_averaged_over_devices(
        reduced):
    # device 0: [1,5] + [6.5,8.5] + [10,11] us inside the window; device 1:
    # 4 us; the plane with no XLA Ops line is no device
    assert reduced["devices"] == 2
    assert reduced["window_s"] == pytest.approx(10e-6)
    assert reduced["busy_s"] == pytest.approx(5.5e-6)


def test_ops_are_named_from_their_hlo_text_with_self_time(reduced):
    ops = reduced["ops"]
    assert ops["pcilt_stacked_gemv_sat"] == (3, pytest.approx(7e-6))
    assert ops["fusion"] == (1, pytest.approx(2e-6))
    assert ops["pcilt_shared_gemv"] == (2, pytest.approx(2e-6))
    assert ops["while"] == (1, pytest.approx(1e-6))  # less its nested op
    assert "jit__lambda_" not in ops  # only the XLA Ops line


def test_idle_time_goes_to_the_innermost_span_the_host_was_in(reduced):
    assert reduced["idle"] == {"monitor": pytest.approx(1.5e-6),
                               "(none)": pytest.approx(1.5e-6)}
    assert sum(reduced["idle"].values()) == pytest.approx(10e-6 - 7e-6)


def test_a_trace_without_the_window_mark_is_refused():
    from jax.profiler import ProfileData

    with open(os.path.join(DATA, "trace_small.pbtxt")) as f:
        text = f.read().replace('"bench.window_open"', '"elsewhere"')
    with pytest.raises(ValueError):
        trace.reduce_planes(ProfileData.from_text_proto(text).planes, 1e-5)


def test_op_name():
    assert trace.op_name("%cond.65.clone = (f32[16]) conditional(...)") == \
        "cond"
    assert trace.op_name("fusion.12") == "fusion"
    assert trace.op_name("pcilt_stacked_gemv_sat") == "pcilt_stacked_gemv_sat"


def test_union_and_top():
    assert trace.union([(5, 6), (1, 3), (2, 4)]) == [(1, 4), (5, 6)]
    assert trace.top({"a": 1.0, "b": 3.0, "c": 2.0}, 2) == [["b", 3.0],
                                                             ["c", 2.0]]
