"""The comparison that decides ``correct`` fails what it must fail.

Whole runs of a throwaway benchmark on the CPU (``bench_tiny``), the
harness's look for a chip left out: a sound run is correct, and each
fault planted under the timed path, and the control (the reference in
bfloat16 put in the program's place), comes out not correct.
"""
from __future__ import annotations

import numpy as np
import pytest

import bench_tiny


def _perturb_row0(fn):
    def broken(batch, **kw):
        out = fn(batch, **kw)
        return out.at[0].add(1e-3)
    return broken


def _half_batch(fn):
    def broken(batch, **kw):
        half = max(1, batch.shape[0] // 2)
        out = fn(batch[:half], **kw)
        return batch.at[:half].set(out)
    return broken


@pytest.fixture
def device_paths(monkeypatch):
    from repro.query import device_backend

    def plant(wrap):
        monkeypatch.setitem(device_backend.DEVICE_BATCH_PATHS, "blur",
                            wrap(device_backend.DEVICE_BATCH_PATHS["blur"]))
    return plant


def test_sound_runs_are_correct(tmp_path):
    for cell in bench_tiny.CELLS:
        res = bench_tiny.run(tmp_path / cell, cell)
        assert res["correct"], res["checks"]
        assert res["attempted"] > 0 and res["failed"] == 0


def test_several_device_workers_warm_up_and_are_correct(tmp_path):
    """Four device workers (here all on the one CPU device): the warm-up
    drives the mix in bursts until one compiles nothing, and the run is
    correct."""
    logs = []
    res = bench_tiny.run(
        tmp_path, "tiny_device.tiny_blur", log=logs.append,
        engine_overrides={"tiny_device": {"num_device_workers": 4}})
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    bursts = [m for m in logs if m.startswith("warm-up burst")]
    assert bursts and " 0 compiles" in bursts[-1]


@pytest.mark.parametrize("fault", ["answer_altered", "half_batch"])
def test_device_segment_faults_fail(tmp_path, device_paths, fault):
    device_paths({"answer_altered": _perturb_row0,
                  "half_batch": _half_batch}[fault])
    res = bench_tiny.run(tmp_path, "tiny_device.tiny_blur")
    assert not res["correct"]
    assert res["checks"]["max_abs_err"]["value"] > \
        res["checks"]["max_abs_err"]["limit"]


def test_remote_op_skipped_fails(tmp_path):
    """A remote server that answers without running the op."""
    res = bench_tiny.run(
        tmp_path, "tiny_static.tiny_iq",
        engine_overrides={"tiny_static": {"transport": {
            "network_latency_s": 0.001, "execute_ops": False}}})
    assert not res["correct"]


def test_entity_left_out_fails(tmp_path, monkeypatch):
    """A selection that drops one face of each multi-face match."""
    from repro.query.metadata import MetadataStore
    find = MetadataStore.find

    def short(self, kind=None, constraints=None):
        out = find(self, kind, constraints)
        return out[:-1] if len(out) > 1 and "age" in (constraints or {}) \
            else out
    monkeypatch.setattr(MetadataStore, "find", short)
    res = bench_tiny.run(tmp_path, "tiny_device.tiny_blur")
    assert not res["correct"]
    assert res["checks"]["wrong_selections"]["value"] > 0


def test_entity_delivered_twice_fails(tmp_path, monkeypatch):
    from repro.core.session import QuerySession
    stream = QuerySession._stream

    def twice(self, ent):
        stream(self, ent)
        stream(self, ent)
    monkeypatch.setattr(QuerySession, "_stream", twice)
    res = bench_tiny.run(tmp_path, "tiny_device.tiny_blur")
    assert not res["correct"]
    assert res["checks"]["wrong_deliveries"]["value"] > 0


def test_own_op_weights_perturbed_fail(tmp_path, monkeypatch):
    """A configuration's own op installed with weights other than those
    the reference is given."""
    from bench import harness
    load = harness.load_module

    def perturbed(bench_dir, sub, name):
        mod = load(bench_dir, sub, name)
        if sub == "ops":
            install = mod.install

            def broken(engine, spec, weights, devices):
                return install(engine, spec,
                               {k: v * 1.001 for k, v in weights.items()},
                               devices)
            mod.install = broken
        return mod
    monkeypatch.setattr(harness, "load_module", perturbed)
    res = bench_tiny.run(tmp_path, "tiny_owned.tiny_embed")
    assert not res["correct"]
    assert res["failed"] == 0
    assert res["checks"]["max_abs_err"]["value"] > \
        res["checks"]["max_abs_err"]["limit"]


@pytest.mark.parametrize("cell", sorted(bench_tiny.CELLS))
def test_bfloat16_control_fails_the_limits(tmp_path, cell):
    """The reference computed in bfloat16, in the program's place, reads
    past the limits on every number it gives."""
    from bench import check
    res = bench_tiny.run(tmp_path, cell, control=True)
    assert res["correct"]
    control = res["_control"]
    assert control
    ok, table = check.decide(control, bench_tiny.LIMITS)
    assert not ok
    assert all(row["value"] > row["limit"] for row in table.values()), table
    assert np.isfinite(list(control.values())).all()
