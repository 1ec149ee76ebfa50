"""``BENCHMARK.json`` against the contract the driver checks before any
run, as far as a file can show it; and that every name in it finds its
files."""

import json
import os
import re

import pytest

from benchmarks.lib import cells

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    return cells.benchmark_file()


def _line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level(bench):
    assert set(bench) == {
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer",
    }
    assert bench["paths"] == ["benchmarks"]
    assert bench["command"][:2] == ["python3", "benchmarks/run.py"]
    assert isinstance(bench["run_seconds"], int) and 1 <= bench["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(cells.ROOT, "BENCHMARK.json")) <= 64 * 1024
    # A full check with all 24 cells fits the driver's 43,200 s.
    assert 338 * (bench["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_configs(bench):
    names = [c["name"] for c in bench["configs"]]
    assert len(set(names)) == len(names) and 1 <= len(names) <= 24
    files = [c["file"] for c in bench["configs"]]
    assert len(set(files)) == len(files)
    used = {w["config"] for w in bench["workloads"]}
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert _line(c["source"]) and _line(c["why"])
        assert c["file"].startswith("benchmarks/")
        with open(os.path.join(cells.ROOT, c["file"])) as f:
            body = json.load(f)
        assert len(c["reduced"]) <= 16
        for key in c["reduced"]:
            assert NAME.match(key) and key in body
            # never a width
            assert not re.search(r"(_dim|_rank|hidden_size|intermediate|head)", key)
        assert sorted(body["reduced"]) == sorted(c["reduced"])
        for group in ("source", "family", "assumed", "departures", "guarantees",
                      "loss_tolerance", "training", "rehearsal"):
            assert group in body, (c["name"], group)
        assert body["source"] == c["source"]


def test_workloads(bench):
    ws = bench["workloads"]
    assert 2 <= len(ws) <= 24
    assert len({w["name"] for w in ws}) == len(ws)
    assert len({(w["config"], w["traffic"]) for w in ws}) == len(ws)
    for w in ws:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and _line(w["why"])
        cell = cells.load_cell(w["name"], bench=bench)
        assert cell.mix["name"] == w["traffic"]
        assert cell.mix["window_rows"] % cell.mix["batch_rows"] == 0
        n = 1
        for extent in cell.mix["mesh"].values():
            n *= extent
        assert n == w["chips"]
    assert sum(w["chips"] == 4 for w in ws) <= max(1, len(ws) // 4)


def test_metrics(bench):
    e2e, layers = bench["end_to_end"], bench["per_layer"]
    assert 1 <= len(e2e) <= 16 and 1 <= len(layers) <= 128
    names = [m["name"] for m in e2e + layers]
    assert len(set(names)) == len(names)
    all_cells = [w["name"] for w in bench["workloads"]]
    for m in e2e:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    assert next(m for m in e2e if m["name"] == "setup_s")["bound"] == 0.1
    where = {m["name"]: set(m.get("workloads", all_cells)) for m in e2e}
    for m in layers:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}
        assert m["source"] in SOURCES and _line(m["layer"])
        # reported only where the metric it moves is
        assert set(m.get("workloads", all_cells)) <= where[m["moves"]]
        assert callable(cells.layer_reader(m["name"]))
    for m in e2e + layers:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", all_cells)) <= set(all_cells)
    for cell in all_cells:
        c = cells.load_cell(cell, bench=bench)
        e = {m["name"] for m in c.end_to_end}
        assert "setup_s" in e and len(e) >= 2 and c.per_layer
        assert c.family.RATE_METRIC in e


def test_files_under_paths_are_named_from_a_names_characters():
    for root, dirs, files in os.walk(cells.HERE):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for f in files:
            assert re.match(r"^[A-Za-z0-9_.\-]+$", f), os.path.join(root, f)
