"""The reduction of the program's spans and device scopes, on a small
recorded trace (``data/trace_program.pbtxt``, whose header lays out its
events), and the metrics that read it."""

import os
from types import SimpleNamespace

import pytest

from bench import program_trace as pt
from bench import run

DATA = os.path.join(os.path.dirname(__file__), "data")
WINDOW_S = 20e-6
READERS = ("crc_ms.batch", "crc_idle_share.batch", "step_host_idle_ms.batch",
           "head_device_ms.batch", "blocks_device_ms.batch")


def serialized(name):
    from jax.profiler import ProfileData

    with open(os.path.join(DATA, name)) as f:
        return ProfileData.text_proto_to_serialized_xspace(f.read())


@pytest.fixture(scope="module")
def red():
    from jax.profiler import ProfileData

    raw = serialized("trace_program.pbtxt")
    return pt.reduce_planes(ProfileData.from_serialized_xspace(raw).planes,
                            pt.op_names(raw), WINDOW_S)


def approx(d):
    return {k: pytest.approx(v * 1e-6) for k, v in d.items()}


def test_the_window_spans_keep_their_args(red):
    assert [(n, a, b, x) for n, a, b, x in red["spans"]][:2] == [
        ("serve.tick", 6500, 19000, {"tick": 3, "active": 2, "queue": 5}),
        ("serve.step", 7000, 10000, {"phase": "prefill", "step": 8})]
    assert pt.count(red, "step") == 1  # the step begun before the window
    assert pt.count(red, "monitor") == 1
    assert pt.span_seconds(red, "monitor.crc_layer", "monitor.crc_head") \
        == pytest.approx(5.5e-6)


def test_idle_is_cut_at_span_edges_by_the_innermost_program_span(red):
    # idle [2, 2.5], [6.5, 8.5], [10, 13], [14, 20] us; a piece outside
    # every program span goes to the innermost harness span, else (none)
    assert red["idle"] == approx({
        "serve.step": 0.5, "serve.tick": 2.5, "serve.step.dispatch": 1.0,
        "serve.step.gate": 0.5, "serve.monitor": 1.5,
        "serve.monitor.crc_layer": 1.5, "serve.integrity.crc32": 2.0,
        "serve.monitor.crc_head": 1.0, "(none)": 0.5, "bench.commit": 0.5})
    assert sum(red["idle"].values()) == pytest.approx(WINDOW_S - 8.5e-6)


def test_idle_under_a_span_holds_its_children(red):
    assert red["idle_under"] == approx({
        "serve.step": 2.0, "serve.tick": 10.0, "serve.step.dispatch": 1.0,
        "serve.step.gate": 0.5, "serve.monitor": 6.0,
        "serve.monitor.crc_layer": 3.5, "serve.integrity.crc32": 2.0,
        "serve.monitor.crc_head": 1.0})


def test_host_self_time_covers_the_window(red):
    assert red["host"]["serve.step"] == pytest.approx(5.4e-6)
    assert red["host"]["bench.decode_step"] == pytest.approx(0.1e-6)
    assert red["host"]["bench.commit"] == pytest.approx(1.5e-6)  # clipped
    assert sum(red["host"].values()) == pytest.approx(WINDOW_S)


def test_device_self_time_by_scope_and_module(red):
    # the while less the op nested in it; ops straddling the window clipped
    # (embed [0, 2], head [20, 23]); one HLO text in two modules told apart
    # by program_id; an op with no op_name in no scope
    assert red["scopes"] == approx({"embed": 1.0, "blocks": 4.0,
                                    "head": 2.0, "(none)": 1.5})
    assert red["modules"] == approx({"jit_pcilt_decode_step": 7.5,
                                     "jit_dot_general": 1.0})
    assert red["module_scopes"]["jit_dot_general"] == approx({"(none)": 1.0})
    assert red["scope_ops"] == {
        "embed": approx({"add_fusion": 1.0}),
        "blocks": approx({"while": 2.0, "pcilt_stacked_gemv_sat": 2.0}),
        "head": approx({"fusion": 1.0, "copy": 1.0}),
        "(none)": approx({"copy-start": 0.5,
                          "fusion (jit(dot_general)/dot_general)": 1.0})}


def test_op_names_resolve_interned_strings_and_program_ids():
    names = pt.op_names(serialized("trace_program.pbtxt"))["/device:TPU:0"]
    gemv = "%pcilt_stacked_gemv_sat.7 = f32[16,768]{1,0} custom-call(" \
           "f32[16,1536]{1,0} %x)"
    assert names[(11, gemv)].endswith("/in_proj/pcilt_stacked_gemv_sat/"
                                      "pallas_call")
    fusion = "%fusion.1 = f32[16]{0} fusion(f32[16]{0} %y)"
    assert names[(22, fusion)] == "jit(dot_general)/dot_general"
    assert pt.scope(names[(11, fusion)]) == "head"


@pytest.mark.parametrize("op_name, want", [
    ("jit(pcilt_decode_step)/blocks/while/body/in_proj/dot", "blocks"),
    ("jit(f)/jit(g)/head/add", "head"),
    ("jit(pcilt_decode_step)/while", "(none)"),
    ("x", "(none)"),
    (None, "(none)"),
])
def test_scope(op_name, want):
    assert pt.scope(op_name) == want


def write_trace(tmp_path, name):
    d = tmp_path / "plugins" / "profile" / "1"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(serialized(name))


def test_the_readers_on_a_traced_run(tmp_path, monkeypatch):
    write_trace(tmp_path, "trace_program.pbtxt")
    monkeypatch.setattr(pt, "TRACE_DIR", str(tmp_path))
    ctx = SimpleNamespace(trace={}, seconds=WINDOW_S)
    got = {n: run.reader(n).read(ctx) for n in READERS}
    assert got == {"crc_ms.batch": pytest.approx(0.0055),
                   "crc_idle_share.batch": pytest.approx(22.5),
                   "step_host_idle_ms.batch": pytest.approx(0.002),
                   "head_device_ms.batch": pytest.approx(0.002),
                   "blocks_device_ms.batch": pytest.approx(0.004)}


@pytest.mark.parametrize("name", READERS)
@pytest.mark.parametrize("case", ["untraced", "no trace file",
                                  "no program spans"])
def test_a_reader_reads_none_without_a_program_trace(name, case, tmp_path,
                                                     monkeypatch):
    if case == "no program spans":  # a program without spans or scopes
        write_trace(tmp_path, "trace_small.pbtxt")
    monkeypatch.setattr(pt, "TRACE_DIR", str(tmp_path))
    ctx = SimpleNamespace(trace=None if case == "untraced" else {},
                          seconds=10e-6)
    assert run.reader(name).read(ctx) is None


def test_the_command_prints_the_breakdown(tmp_path, capsys):
    import json

    write_trace(tmp_path, "trace_program.pbtxt")
    assert pt.main([str(tmp_path), str(WINDOW_S)]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["steps"] == 1
    assert out["span_totals"]["serve.monitor"] == {"count": 1,
                                                   "ms": pytest.approx(7e-3)}
    assert out["scopes"]["blocks"] == pytest.approx(4e-6)
