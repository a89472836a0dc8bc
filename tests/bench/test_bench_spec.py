"""BENCHMARK.json is well formed and every name in it finds its file."""
import json
import os
import re

import pytest

from bench import spec
from conftest import ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bm():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_top_level(bm):
    assert set(bm) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert bm["command"] == ["python3", "bench/run.py"]
    assert 1 <= bm["run_seconds"] <= 51
    for p in bm["paths"]:
        assert os.path.isdir(os.path.join(ROOT, p))
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024


def test_configs_and_cells(bm):
    cfgs = {c["name"]: c for c in bm["configs"]}
    for c in bm["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and all(NAME.match(k)
                                             for k in c["reduced"])
        data = spec.load_json(os.path.join(ROOT, c["file"]))
        assert set(c["reduced"]) == set(data["reduced"])
        assert data["name"] == c["name"]
    used = set()
    pairs = set()
    for w in bm["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200 and "\n" not in w["why"]
        assert w["config"] in cfgs
        assert os.path.exists(spec.traffic_path(w["traffic"]))
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        used.add(w["config"])
    assert used == set(cfgs)


def test_metrics(bm):
    cells = {w["name"] for w in bm["workloads"]}
    names = [m["name"] for m in bm["end_to_end"] + bm["per_layer"]]
    assert len(names) == len(set(names))
    assert "setup_s" in {m["name"] for m in bm["end_to_end"]}
    for m in bm["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    layers = {}
    for m in bm["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
        assert os.path.exists(os.path.join(ROOT, "bench", "metrics",
                                           m["name"] + ".py"))
        # every cell that reports it reports the metric it moves
        for w in m.get("workloads", cells):
            cell = spec.load_cell(w)
            assert m["moves"] in {e["name"] for e in cell.end_to_end}
        layers.setdefault(m["layer"], []).append(m["name"])
    for w in cells:
        cell = spec.load_cell(w)
        e2e = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert cell.per_layer


def test_every_reader_loads(bm):
    got = spec.readers(bm["per_layer"])
    assert set(got) == {m["name"] for m in bm["per_layer"]}
    assert all(callable(f) for f in got.values())


def test_rooflines_have_a_step_mfu_beside_them(bm):
    """A kernel's roofline that moves an end-to-end metric has the whole
    step's share of the peak beside it, moving the same metric."""
    for m in bm["per_layer"]:
        if not m["name"].endswith("_roofline"):
            continue
        mfus = [x for x in bm["per_layer"] if "mfu" in x["name"]
                and x["moves"] == m["moves"]]
        assert mfus, m["name"]
        cells = set(m["workloads"])
        assert cells <= set().union(*(set(x["workloads"]) for x in mfus))
