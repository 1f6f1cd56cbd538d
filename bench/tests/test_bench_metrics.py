"""Each per-layer reader's arithmetic, fed from counter deltas and a
reduced trace, and its silence where it finds nothing to read."""
from __future__ import annotations

import pytest

import bench_tiny  # noqa: F401 — puts the repository on sys.path
from bench import counts
from bench.harness import BENCH, Readings, load_cell, load_reader


def _snaps(**deltas):
    start = {"time": 100.0}
    end = {"time": 110.0}
    for key, (a, b) in deltas.items():
        start[key], end[key] = a, b
    return {"start": start, "end": end,
            "trace_start": dict(start), "trace_end": dict(end)}


def _read(metric, cell, snaps, trace=None, peaks=None):
    r = Readings(load_cell(cell), snaps, trace, peaks)
    return load_reader(BENCH, metric)(r)


def test_device_idle_share():
    trace = {"busy_s": 0.75, "window_s": 3.0}
    assert _read("device_idle_share", "lfw_device.blur", _snaps(),
                 trace) == pytest.approx(75.0)
    assert _read("device_idle_share", "lfw_device.blur", _snaps()) is None


@pytest.mark.parametrize("metric,cell,kernel", [
    ("preprocess_roofline", "lfw_device.train_feed", "preprocess"),
    ("blur_roofline", "lfw_device.blur", "blur")])
def test_roofline_share(metric, cell, kernel):
    peaks = counts.peaks("TPU v5 lite")
    snaps = _snaps(**{"device.entities_run": (1000, 3000)})
    trace = {"busy_s": 0.02, "window_s": 3.0, "devices": 1}
    work = counts.kernel_work(
        kernel, next(iter(load_cell(cell).traffic["queries"].values())),
        (250, 250, 3))
    least, _ = counts.least_time_s(*work, peaks)
    assert _read(metric, cell, snaps, trace, peaks) == \
        pytest.approx(100 * 2000 * least / 0.02)
    # the other kernel's cell does no such work: nothing to read
    other = ("lfw_device.blur" if cell == "lfw_device.train_feed"
             else "lfw_device.train_feed")
    assert _read(metric, other, snaps, trace, peaks) is None
    assert _read(metric, "lfw_paper_async.iq_mix", snaps, trace,
                 peaks) is None
    # no trace, no peaks, or no entity run: nothing, never 0
    assert _read(metric, cell, snaps, None, peaks) is None
    assert _read(metric, cell, snaps, trace, None) is None
    assert _read(metric, cell, _snaps(**{"device.entities_run": (5, 5)}),
                 trace, peaks) is None


def test_segment_counters():
    snaps = _snaps(**{"device.h2d_bytes": (0, 6_000_000),
                      "device.entities_run": (10, 18),
                      "device.pad_rows": (3, 4),
                      "device.stacked_rows": (10, 17)})
    assert _read("segment_h2d_bytes_per_entity", "lfw_device.blur",
                 snaps) == pytest.approx(750_000)
    assert _read("segment_padding_waste", "lfw_device.blur",
                 snaps) == pytest.approx(12.5)
    assert _read("segment_h2d_bytes_per_entity", "lfw_paper_async.iq_mix",
                 _snaps()) is None
    assert _read("segment_padding_waste", "lfw_paper_async.iq_mix",
                 _snaps()) is None


def test_event_loop_and_remote_counters():
    snaps = _snaps(**{"loop.t3_busy_s": (1.0, 3.5),
                      "loop.native_busy_s": (2.0, 22.0),
                      "loop.native_workers": (8, 8),
                      "util.remote_dispatched": (100, 1100),
                      "util.remote_processed": (90, 1090)})
    cell = "lfw_paper_async.iq_mix"
    assert _read("thread3_busy_share", cell, snaps) == pytest.approx(25.0)
    assert _read("native_busy_share", cell, snaps) == pytest.approx(25.0)
    assert _read("remote_requests_per_entity", cell,
                 snaps) == pytest.approx(1.0)
    idle = _snaps(**{"util.remote_dispatched": (5, 5),
                     "util.remote_processed": (5, 5)})
    assert _read("remote_requests_per_entity", cell, idle) is None
