"""BENCHMARK.json and the files it names: every cell, configuration,
mix, limit and reader is found by name, and a new pair is a new file."""
from __future__ import annotations

import ast
import glob
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
    assert cell.chips in (1, 4)
    reported = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in reported and len(reported) >= 2
    assert cell.per_layer
    for m in cell.per_layer:
        assert callable(harness.load_reader(cell.bench_dir, m["name"]))
    # a limit for every number this cell's comparison gives, and no other
    wants = {"failed_queries", "wrong_selections", "wrong_deliveries"}
    for ops in cell.traffic["queries"].values():
        wants.add("mismatch_share"
                  if reference.is_discrete(ops, cell.bench_dir)
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
    # a configuration that owns an op: its files are found by the op's name
    owned = harness.load_cell("tiny_owned.tiny_embed", benchmark=spec,
                              bench_dir=str(tmp_path / "bench"))
    assert owned.ops == bench_tiny.OWNED_OPS
    for op in owned.ops:
        assert callable(harness.load_module(owned.bench_dir, "ops",
                                            op).install)
        ref = reference.own_op(op, owned.bench_dir)
        assert callable(ref.reference) and ref.DISCRETE is False
    for ops in owned.traffic["queries"].values():
        assert not reference.is_discrete(ops, owned.bench_dir)


def test_a_four_chip_cell_loads_by_name(tmp_path):
    """A cell on four chips, with a device worker per chip, loads by name
    like any other: the harness takes 1 or 4 chips."""
    spec_path = bench_tiny.make(tmp_path)
    bench = tmp_path / "bench"
    cfg = json.load(open(bench / "configs" / "tiny_device.json"))
    cfg["engine"]["num_device_workers"] = 4
    (bench / "configs" / "tiny_device_x4.json").write_text(json.dumps(cfg))
    (bench / "limits" / "tiny_device_x4.tiny_blur.json").write_text(
        json.dumps(bench_tiny.LIMITS))
    spec = json.load(open(spec_path))
    spec["configs"].append({**spec["configs"][0], "name": "tiny_device_x4"})
    spec["workloads"].append({"name": "tiny_device_x4.tiny_blur",
                              "config": "tiny_device_x4",
                              "traffic": "tiny_blur", "chips": 4,
                              "why": "test"})
    open(spec_path, "w").write(json.dumps(spec))
    cell = harness.load_cell("tiny_device_x4.tiny_blur", benchmark=spec_path,
                             bench_dir=str(bench))
    assert cell.chips == 4
    assert cell.config["engine"]["num_device_workers"] == 4
    assert cell.limits == bench_tiny.LIMITS


def _imported_modules(path):
    names = set()
    for node in ast.walk(ast.parse(open(path).read())):
        if isinstance(node, ast.Import):
            names.update(a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom) and node.module:
            names.add(node.module)
    return names


def test_references_import_nothing_of_the_program():
    """The plain references, the built-in ops' and every configuration's
    own, import nothing of ``repro``."""
    paths = ([os.path.join(harness.BENCH, "reference.py")]
             + glob.glob(os.path.join(harness.BENCH, "references", "*.py"))
             + glob.glob(os.path.join(bench_tiny.OWN_OP, "references",
                                      "*.py")))
    assert len(paths) >= 2
    for path in paths:
        mods = _imported_modules(path)
        assert not any(m == "repro" or m.startswith("repro.")
                       for m in mods), (path, mods)
