"""The loader finds every cell, configuration, mix, metric, reader and
driver by the names in BENCHMARK.json, and refuses an unknown one."""

import json
import os
import re

import pytest
from bench_util import ROOT

from benchmark.harness import loader

MAN = loader.manifest()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
CELLS = [w["name"] for w in MAN["workloads"]]
METRICS = [m["name"] for m in MAN["per_layer"]]


@pytest.mark.parametrize("name", CELLS)
def test_cell_loads_with_its_files_and_its_family(name):
    cell = loader.load_cell(name)
    # the family names the cell's plain reference and its adapter
    for kind in ("references", "adapters"):
        loader.load_module(kind, cell["config"]["family"])
    assert cell["traffic"]["kind"] in ("train_job", "serve")
    driver = loader.load_module("drivers", cell["traffic"]["kind"])
    assert hasattr(driver, "Session")
    names = {m["name"] for m in cell["end_to_end"]}
    assert "setup_s" in names and len(names) >= 2
    assert cell["per_layer"], "a cell reports at least one per-layer metric"
    # every per-layer metric of the cell moves a metric the cell reports
    for m in cell["per_layer"]:
        assert m["moves"] in names, (m["name"], m["moves"])
    # the cell file repeats nothing the manifest says, and every limit
    # names the readings it was set from
    assert set(cell["cell"]) == {"trace_window", "check", "limits",
                                 "limits_from"}
    assert set(cell["cell"]["limits"]) == set(cell["cell"]["limits_from"])
    assert all(v is not None for v in cell["cell"]["limits"].values())


@pytest.mark.parametrize("name", METRICS)
def test_metric_file_names_a_reader_and_nothing_the_manifest_says(name):
    """Layer, unit, source, ``moves`` and the cells are the manifest's
    alone: a later PR adds a cell to a metric by appending to
    BENCHMARK.json, and may edit no file under benchmark/."""
    entry = next(m for m in MAN["per_layer"] if m["name"] == name)
    with open(os.path.join(ROOT, "benchmark", "metrics", name + ".json")) as f:
        mf = json.load(f)
    assert set(mf) == {"reader", "params"}
    reader = loader.load_module("readers", mf["reader"])
    assert callable(reader.read)
    e2e = {m["name"]: m for m in MAN["end_to_end"]}
    reported_in = e2e[entry["moves"]].get("workloads", CELLS)
    assert set(entry["workloads"]) <= set(reported_in)


def test_unknown_names_are_refused():
    with pytest.raises(loader.UnknownName, match="unknown workload"):
        loader.load_cell("no-such-cell")
    with pytest.raises(loader.UnknownName, match="no file"):
        loader.load_module("readers", "no_such_reader")
    with pytest.raises(loader.UnknownName, match="no file"):
        loader.load_module("drivers", "no_such_kind")


def test_manifest_keeps_to_the_contract():
    assert set(MAN) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert 1 <= MAN["run_seconds"] <= 51
    runs = 2 + 14 * 24
    assert runs * (MAN["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    four = [w for w in MAN["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(MAN["workloads"]) // 4)
    for w in MAN["workloads"]:
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
    pairs = [(w["config"], w["traffic"]) for w in MAN["workloads"]]
    assert len(set(pairs)) == len(pairs)
    assert any(m["name"] == "setup_s" for m in MAN["end_to_end"])
    for m in MAN["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
    for m in MAN["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert re.match(r"^[A-Za-z0-9_/%.\-]{1,16}$", m["unit"])
    names = ([m["name"] for m in MAN["end_to_end"] + MAN["per_layer"]]
             + CELLS + [c["name"] for c in MAN["configs"]])
    assert all(NAME.match(n) for n in names)
    assert len(set(names)) == len(names)
    for c in MAN["configs"]:
        assert c["file"].startswith("benchmark/")
        assert any(w["config"] == c["name"] for w in MAN["workloads"])
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 65536


def test_files_under_paths_are_named_from_name_characters():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for p in MAN["paths"]:
        for d, _, files in os.walk(os.path.join(ROOT, p)):
            if "__pycache__" in d:
                continue
            for f in files:
                rel = os.path.relpath(os.path.join(d, f), ROOT)
                assert ok.match(rel), rel
