"""``BENCHMARK.json`` against the rules its harness and its readers rely on:
names and units, every metric's reader, every cell's files and family
module, and that each per-layer metric's ``moves`` is reported in each cell
that reports it."""

import json
import os
import re

import pytest

from bench import model, run, traffic

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def man():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def cells_reporting(man, metric):
    return set(metric.get("workloads", [c["name"] for c in man["workloads"]]))


def test_top_level_keys_and_limits(man):
    assert set(man) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert 1 <= man["run_seconds"] <= 51
    for p in man["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p) and ".." not in p
    for word in man["command"]:
        assert not word.startswith("/") and ".." not in word


def test_names_and_units(man):
    groups = [man["configs"], man["workloads"], man["end_to_end"],
              man["per_layer"]]
    for group in groups:
        names = [e["name"] for e in group]
        assert len(names) == len(set(names))
        for n in names:
            assert NAME.match(n), n
    for m in man["end_to_end"] + man["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in man["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for c in man["workloads"]:
        assert NAME.match(c["traffic"]) and c["chips"] in (1, 4)
        assert 1 <= len(c["why"]) <= 200


def test_every_metric_has_a_reader(man):
    for m in man["end_to_end"] + man["per_layer"]:
        assert hasattr(run.reader(m["name"]), "read")


def test_every_cell_finds_its_files(man):
    used = set()
    for c in man["workloads"]:
        conf = model.load_config(run.config_file(man, c["config"]))
        assert conf["name"] == c["config"]
        assert traffic.load(c["traffic"])["loop"] in ("closed", "open")
        used.add(c["config"])
    assert used == {c["name"] for c in man["configs"]}
    for c in man["configs"]:
        conf = model.load_config(os.path.join(ROOT, c["file"]))
        assert c["file"].startswith(tuple(p + "/" for p in man["paths"]))
        assert c["reduced"] == conf["reduced"]
        fam = model.family(conf)
        assert all(hasattr(fam, hook) for hook in model.HOOKS)
        assert not set(c["reduced"]) & set(fam.WIDTHS)
        assert conf["source"] == c["source"]


def test_per_layer_metrics_move_a_metric_their_cells_report(man):
    e2e = {m["name"]: m for m in man["end_to_end"]}
    layers = {}
    for m in man["per_layer"]:
        assert m["moves"] in e2e
        assert cells_reporting(man, m) <= cells_reporting(man, e2e[m["moves"]])
        layers.setdefault(m["layer"], set()).add(m["name"])
    for c in man["workloads"]:
        mine = [m["name"] for m in man["end_to_end"]
                if c["name"] in cells_reporting(man, m)]
        assert "setup_s" in mine and len(mine) >= 2
        assert any(c["name"] in cells_reporting(man, m)
                   for m in man["per_layer"])


def test_metrics_for_selects_by_trace(man):
    cell = man["workloads"][0]["name"]
    e2e = [m["name"] for m in run.metrics_for(man, cell, False)]
    per = [m["name"] for m in run.metrics_for(man, cell, True)]
    assert e2e == [m["name"] for m in man["end_to_end"]
                   if cell in cells_reporting(man, m)]
    assert "setup_s" in e2e and not set(e2e) & set(per)
    other = dict(man, per_layer=[dict(man["per_layer"][0],
                                      workloads=["elsewhere"])])
    assert run.metrics_for(other, cell, True) == []
