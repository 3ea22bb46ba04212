"""Finds everything a cell needs by the names in ``BENCHMARK.json``.

The manifest is the index, and the only place that says it: a cell names
its configuration, traffic, chips and ``why``; a configuration names its
file; a metric names its layer, unit, source, the end-to-end metric it
moves and the cells it is read in.  A later PR extends those by appending
to ``BENCHMARK.json``; it edits no file under ``benchmark/``.  What else
belongs to one of them sits in a file of its own, found by that name:

    cells/<cell>.json      limits of the correctness check (and the
                           readings they were set from), trace window
    traffic/<mix>.json     ``kind`` (the driver) + the mix's parameters
    metrics/<metric>.json  ``reader`` + its parameters, nothing else
    drivers/<kind>.py, readers/<reader>.py, adapters/<family>.py,
    references/<family>.py, work/<kernel>.py

An unknown name is an error that says which file is missing.
"""

import importlib.util
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")


class UnknownName(LookupError):
    pass


def _read_json(path, what):
    if not os.path.isfile(path):
        raise UnknownName(f"{what}: no file {os.path.relpath(path, ROOT)}")
    with open(path) as f:
        return json.load(f)


def load_module(kind, name):
    """``benchmark/<kind>/<name>.py`` as a module (kind: drivers, readers,
    adapters, references, work)."""
    path = os.path.join(BENCH, kind, name + ".py")
    if not os.path.isfile(path):
        raise UnknownName(
            f"{kind[:-1] if kind.endswith('s') else kind} {name!r}: no "
            f"file {os.path.relpath(path, ROOT)}")
    spec = importlib.util.spec_from_file_location(
        f"benchmark.{kind}.{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def manifest(root=ROOT):
    return _read_json(os.path.join(root, "BENCHMARK.json"), "manifest")


def _entry(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    known = ", ".join(e["name"] for e in entries)
    raise UnknownName(f"unknown {what} {name!r} (BENCHMARK.json has: "
                      f"{known})")


def load_cell(name, man=None, root=ROOT):
    """Everything one run needs, as one dict:
    ``name, chips, why, config (file contents), traffic (file contents),
    cell (cells/<name>.json), end_to_end, per_layer`` (the manifest's
    entries for this cell, each per-layer one with its metric file under
    ``file``)."""
    man = man or manifest(root)
    bench = os.path.join(root, "benchmark")
    w = _entry(man["workloads"], name, "workload")
    c = _entry(man["configs"], w["config"], "config")
    config = _read_json(os.path.join(root, c["file"]),
                        f"config {c['name']!r}")
    traffic = _read_json(
        os.path.join(bench, "traffic", w["traffic"] + ".json"),
        f"traffic {w['traffic']!r}")
    cell = _read_json(os.path.join(bench, "cells", name + ".json"),
                      f"cell {name!r}")

    def here(m):
        return "workloads" not in m or name in m["workloads"]

    e2e = [m for m in man["end_to_end"] if here(m)]
    per_layer = []
    for m in man["per_layer"]:
        if here(m):
            f = _read_json(
                os.path.join(bench, "metrics", m["name"] + ".json"),
                f"metric {m['name']!r}")
            per_layer.append(dict(m, file=f))
    return dict(name=name, chips=w["chips"], why=w["why"], config=config,
                traffic=traffic, cell=cell, end_to_end=e2e,
                per_layer=per_layer)
