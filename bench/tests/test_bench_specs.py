"""BENCHMARK.json and the files it names: every cell, configuration,
mix, limit and reader is found by name, and a new pair is a new file."""
from __future__ import annotations

import json
import os
import re

import pytest

import bench_tiny
from bench import harness, reference

SPEC = json.load(open(os.path.join(bench_tiny.ROOT, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
CELLS = [w["name"] for w in SPEC["workloads"]]


def test_top_level_keys_and_command():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert SPEC["paths"] == ["bench"]
    assert isinstance(SPEC["run_seconds"], int)
    assert 1 <= SPEC["run_seconds"] <= 51
    cells = len(SPEC["workloads"])
    assert sum(w["chips"] == 4 for w in SPEC["workloads"]) <= cells // 2 or \
        sum(w["chips"] == 4 for w in SPEC["workloads"]) <= 1


def test_names_units_and_bounds():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in SPEC[group]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names), names
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert re.match(r"^[A-Za-z0-9_/%.-]{1,16}$", m["unit"]), m
        assert m["better"] in ("lower", "higher")
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in e2e.values():
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e
        assert set(m["workloads"]) <= set(CELLS)


def test_configs_name_their_files():
    for c in SPEC["configs"]:
        path = os.path.join(bench_tiny.ROOT, c["file"])
        assert c["file"].startswith("bench/configs/") and os.path.isfile(path)
        cfg = json.load(open(path))
        assert cfg["source"] == c["source"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        assert "guarantees" in cfg and "assumed" in cfg


@pytest.mark.parametrize("name", CELLS)
def test_every_cell_loads_by_name(name):
    cell = harness.load_cell(name)
    assert cell.chips == 1
    reported = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in reported and len(reported) >= 2
    assert cell.per_layer
    for m in cell.per_layer:
        assert callable(harness.load_reader(cell.bench_dir, m["name"]))
    # a limit for every number this cell's comparison gives, and no other
    wants = {"failed_queries", "wrong_selections", "wrong_deliveries"}
    for ops in cell.traffic["queries"].values():
        wants.add("mismatch_share" if reference.is_discrete(ops)
                  else "max_abs_err")
    assert set(cell.limits) == wants
    assert all(cell.limits[k] == 0 for k in ("failed_queries",
                                             "wrong_selections",
                                             "wrong_deliveries"))


def test_every_per_layer_metric_has_its_reader():
    for m in SPEC["per_layer"]:
        assert os.path.isfile(os.path.join(harness.BENCH, "metrics",
                                           m["name"] + ".py"))


def test_a_new_pair_is_new_files_only(tmp_path):
    """A configuration, mix and cell that exist only in a temporary
    directory load by name through the same code."""
    spec = bench_tiny.make(tmp_path)
    cell = harness.load_cell("tiny_device.tiny_blur", benchmark=spec,
                             bench_dir=str(tmp_path / "bench"))
    assert cell.config["engine"]["device_backend"] == "cpu"
    assert cell.traffic["clients"] == 2
    assert cell.limits == bench_tiny.LIMITS
    with pytest.raises(KeyError):
        harness.load_cell("no_such.cell", benchmark=spec,
                          bench_dir=str(tmp_path / "bench"))
