"""Family modules: every configuration finds one with every hook; the
Mamba2 family's operations, bytes, limit and readings are pinned at
mamba2-130m's widths; and no harness module outside ``bench/families``
knows an architecture's names."""

import ast
import glob
import os
import re
from types import SimpleNamespace

import numpy as np
import pytest

from bench import model, run

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
CONFIGS = sorted(glob.glob(os.path.join(BENCH, "configs", "*.json"))
                 + glob.glob(os.path.join(BENCH, "tests", "data", "*.json")))
HARNESS = sorted(glob.glob(os.path.join(BENCH, "*.py"))
                 + glob.glob(os.path.join(BENCH, "metrics", "*.py")))


def conf130():
    return model.load_config(os.path.join(BENCH, "configs",
                                          "mamba2-130m.json"))


@pytest.fixture(scope="module")
def mamba2():
    return model.family(conf130())


@pytest.mark.parametrize("path", CONFIGS, ids=os.path.basename)
def test_every_configuration_has_a_family_with_every_hook(path):
    fam = model.family(model.load_config(path))
    assert all(hasattr(fam, hook) for hook in model.HOOKS)
    assert fam.WIDTHS and fam.LIMITS


# -- mamba2-130m's numbers, pinned -------------------------------------------

#: ``(bytes, ops)`` of one stacked-GEMV call at 16 rows and the table bytes
#: it stages, by projection and table itemsize
GEMV = {
    ("wz", 4): ((2506752, 9437184.0), 37748736.0),
    ("wx", 4): ((2506752, 9437184.0), 37748736.0),
    ("wB", 4): ((253952, 786432.0), 3145728.0),
    ("wC", 4): ((253952, 786432.0), 3145728.0),
    ("wdt", 4): ((87552, 147456.0), 589824.0),
    ("wo", 4): ((2506752, 9437184.0), 37748736.0),
    ("wz", 2): ((1327104, 9437184.0), 18874368.0),
    ("wx", 2): ((1327104, 9437184.0), 18874368.0),
    ("wB", 2): ((155648, 786432.0), 1572864.0),
    ("wC", 2): ((155648, 786432.0), 1572864.0),
    ("wdt", 2): ((69120, 147456.0), 294912.0),
    ("wo", 2): ((1327104, 9437184.0), 18874368.0),
}


@pytest.mark.parametrize("name, itemsize", sorted(GEMV))
def test_gemv_call_and_staged_bytes_are_pinned(mamba2, name, itemsize):
    call, staged = GEMV[name, itemsize]
    assert mamba2.gemv_call(conf130(), name, 16, itemsize) == call
    assert mamba2.gemv_staged_bytes(conf130(), name, itemsize) == staged


def test_head_dense_flops_and_limit_are_pinned(mamba2):
    conf = conf130()
    assert mamba2.values(conf) == 16
    assert mamba2.head_call(conf, 16, 4) == (80511488, 308969472.0)
    assert mamba2.head_staged_bytes(conf, 384, 4) == 3707633664.0
    assert mamba2.dense_flops_per_token(conf) == 257416704.0
    assert mamba2.LIMITS == {"mismatch_share": 0.007}
    assert mamba2.STEP_KERNEL == "pcilt_shared_gemv"


@pytest.mark.parametrize("itemsize", [4, 2])
def test_kernel_calls_charge_each_kernel_its_projections(mamba2, itemsize):
    calls = mamba2.kernel_calls(conf130(), 16, itemsize)
    assert calls == {
        "gemv": {"pcilt_stacked_gemv_sat": [GEMV["wx", itemsize][0],
                                            GEMV["wo", itemsize][0]],
                 "pcilt_stacked_gemv": [GEMV[n, itemsize][0] for n in
                                        ("wz", "wB", "wC", "wdt")]},
        "head": {"pcilt_shared_gemv": [(80511488, 308969472.0)]}}


def test_the_bytes_per_step_line_is_pinned(mamba2):
    pcilt = {"proj": {"tables": {"wz": np.zeros(1, np.float32)}},
             "head": {"pool": np.zeros((384, 1), np.float32)}}
    eng = SimpleNamespace(pdecode=SimpleNamespace(pcilt=pcilt))
    assert mamba2.table_itemsize(eng) == 4
    assert mamba2.bytes_per_step(conf130(), eng, 16) == (
        "bytes per step: GEMV tables staged 2883059712, GEMV least "
        "194777088; head staged 3707633664, head least 80511488")


#: a trace's kernel calls and seconds and a window's token times, and what
#: the readers make of them
OPS = {"pcilt_stacked_gemv_sat": (34464, 4.6142142289999635),
       "pcilt_stacked_gemv": (68928, 3.2213281499999282),
       "pcilt_shared_gemv": (1716, 2.887438007999998)}
READ = {"gemv_roofline": 2.179261504197463,
        "head_roofline": 5.842228864984342,
        "step_device_ms": 17.698135198135198,
        "mfu": 0.010904870063313336}


def reader_ctx(fam, prefix=""):
    times = {0: [0.02 * i for i in range(600)],
             1: [0.01 + 0.03 * i for i in range(400)]}
    return SimpleNamespace(
        conf=conf130(), family=fam, rows=16, table_itemsize=4,
        device_kind="TPU v5 lite", chips=1, token_times=times, w0=0.0,
        w1=11.0, trace={"ops": {prefix + k: v for k, v in OPS.items()},
                        "busy_s": 30.37, "window_s": 51.0})


@pytest.mark.parametrize("metric", sorted(READ))
def test_readers_are_pinned(mamba2, metric):
    assert run.reader(metric).read(reader_ctx(mamba2)) == READ[metric]


@pytest.mark.parametrize("metric", sorted(READ))
def test_readers_follow_a_family_under_other_kernel_names(monkeypatch,
                                                          metric):
    monkeypatch.setattr(model, "FAMILIES",
                        os.path.join(BENCH, "tests", "data", "families"))
    fam = model.family(dict(conf130(), family="mamba2_renamed"))
    read = run.reader(metric).read
    assert read(reader_ctx(fam, fam.PREFIX)) == READ[metric]
    if metric != "mfu":  # the only one of them that reads no kernel
        assert read(reader_ctx(fam)) is None


# -- the harness knows no family ---------------------------------------------

def banned_words():
    """What only a family module may name: the Mamba2 configuration's and
    program's names, its projections, and every kernel the program or the
    family names."""
    fam = model.family(conf130())
    words = {"ssm_cfg", "PROJ_NAMES", "build_pcilt", "pdecode",
             *fam.projections(conf130())}
    kernels = {fam.STEP_KERNEL}
    for group in fam.kernel_calls(conf130(), 1, 4).values():
        kernels |= set(group)
    for path in glob.glob(os.path.join(ROOT, "src", "repro", "kernels",
                                       "*.py")):
        with open(path) as f:
            kernels |= set(re.findall(r'\bname="(\w+)"', f.read()))
    assert {"pcilt_dwconv1d", "pcilt_stacked_gemv",
            "pcilt_shared_gemv"} <= kernels
    return re.compile(r"\b(?:%s)\b|\b(?:%s)" % (
        "|".join(sorted(words)), "|".join(sorted(kernels))))


def code_lines(path):
    """The file's lines with every docstring blanked."""
    with open(path) as f:
        src = f.read()
    lines = src.splitlines()
    for node in ast.walk(ast.parse(src)):
        if isinstance(node, (ast.Module, ast.ClassDef, ast.FunctionDef,
                             ast.AsyncFunctionDef)) and node.body:
            first = node.body[0]
            if isinstance(first, ast.Expr) and \
                    isinstance(first.value, ast.Constant) and \
                    isinstance(first.value.value, str):
                for i in range(first.lineno - 1, first.end_lineno):
                    lines[i] = ""
    return lines


@pytest.mark.parametrize("path", HARNESS,
                         ids=lambda p: os.path.relpath(p, BENCH))
def test_no_harness_module_outside_the_families_names_one(path):
    banned = banned_words()
    hits = [f"{i + 1}: {ln.strip()}" for i, ln in
            enumerate(code_lines(path)) if banned.search(ln)]
    assert not hits, hits


def test_the_static_check_sees_a_name_in_code_and_not_in_a_docstring(
        tmp_path):
    path = tmp_path / "probe.py"
    path.write_text('"""Runs pcilt_shared_gemv."""\n\n\ndef f(eng):\n'
                    '    """Reads ``eng.pdecode``."""\n'
                    '    return eng.tables["wo"]\n')
    hits = [ln for ln in code_lines(str(path)) if banned_words().search(ln)]
    assert hits == ['    return eng.tables["wo"]']
