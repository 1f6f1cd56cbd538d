"""Roofline counts from shapes, and the table of peaks."""
from __future__ import annotations

import json

import pytest

import bench_tiny  # noqa: F401 — puts the repository on sys.path
from bench import counts
from bench.harness import Readings, load_cell

V5E = "TPU v5 lite"


def test_preprocess_counts_match_the_hand_numbers():
    ops, nbytes = counts.preprocess((250, 250, 3), (224, 224, 3))
    assert nbytes == 1_352_112            # 750,000 read + 602,112 written
    least, bound = counts.least_time_s(ops, nbytes, counts.peaks(V5E))
    assert bound == "bytes"
    assert least == pytest.approx(1_352_112 / 819e9)
    assert abs(least * 1e6 - 1.651) < 1e-3       # 1.6509 us


def test_blur_counts_match_the_hand_numbers():
    ops, nbytes = counts.blur((250, 250, 3), 5)
    assert nbytes == 1_500_000
    assert ops == 2 * 2 * 5 * 250 * 250 * 3
    least, bound = counts.least_time_s(ops, nbytes, counts.peaks(V5E))
    assert bound == "bytes"
    assert least == pytest.approx(1_500_000 / 819e9)
    assert abs(least * 1e6 - 1.831) < 1e-3       # 1.8315 us


def test_a_compute_bound_count_is_bound_by_operations():
    peak = counts.peaks(V5E)
    least, bound = counts.least_time_s(10**12, 1, peak)
    assert bound == "ops" and least == pytest.approx(1e12 / 197e12)


def test_kernel_work_follows_the_query():
    pre = [{"type": "resize", "width": 256, "height": 256},
           {"type": "crop", "x": 16, "y": 16, "width": 224, "height": 224},
           {"type": "normalize", "mean": 0.5, "std": 0.25}]
    blur = [{"type": "blur", "ksize": 5, "sigma_x": 1.5}]
    remote_blur = [{"type": "remote", "url": "u",
                    "options": {"id": "blur", "ksize": 5, "sigma_x": 1.5}}]
    shape = (250, 250, 3)
    assert counts.kernel_work("preprocess", pre, shape) == \
        counts.preprocess(shape, (224, 224, 3))
    assert counts.kernel_work("blur", blur, shape) == counts.blur(shape, 5)
    assert counts.kernel_work("blur", remote_blur, shape) == \
        counts.blur(shape, 5)
    assert counts.kernel_work("preprocess", blur, shape) is None
    assert counts.kernel_work("blur", pre, shape) is None


def test_peaks_name_their_source_and_refuse_an_unknown_kind(tmp_path):
    table = json.load(open(counts.PEAKS))
    assert "cloud.google.com/tpu" in table["source"]
    peak = counts.peaks(V5E)
    assert peak["flops_per_s"] == 197e12
    assert peak["hbm_bytes_per_s"] == 819e9
    assert peak["hbm_bytes"] == 16e9
    with pytest.raises(KeyError, match="no peaks"):
        counts.peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        counts.peaks("cpu")


def test_roofline_share_sums_busy_time_over_devices():
    """One device running N entities and four running N/4 each, at the
    same time per entity, read the same share: the trace's ``busy_s`` is
    the mean over its devices."""
    cell = load_cell("lfw_device.train_feed")
    peaks = counts.peaks(V5E)
    n, per_entity_s = 2000, 20e-6

    def share(devices):
        snaps = {k: {"device.entities_run": v} for k, v in (
            ("trace_start", 0), ("trace_end", n))}
        busy_each = n / devices * per_entity_s
        trace = {"busy_s": busy_each, "window_s": 3.0, "devices": devices}
        return counts.roofline_share(Readings(cell, snaps, trace, peaks),
                                     "preprocess")
    work = counts.kernel_work(
        "preprocess", cell.traffic["queries"]["preprocess"], (250, 250, 3))
    least, _ = counts.least_time_s(*work, peaks)
    assert share(1) == pytest.approx(100 * least / per_entity_s)
    assert share(4) == pytest.approx(share(1))
