"""The readers of the engine's own spans, waits and counts
(``util.trace.*``): their arithmetic on counter deltas, and their
silence where a denominator did not move, where the program has no such
record, or where no device trace was reduced."""
from __future__ import annotations

import pytest

import bench_tiny  # noqa: F401 — puts the repository on sys.path
from bench.harness import BENCH, Readings, load_cell, load_reader

TRACE = {"busy_s": 0.002, "window_s": 3.0}
IQ = "lfw_paper_async.iq_mix"
FEED = "lfw_device.train_feed"


def _snaps(**deltas):
    start = {"time": 100.0}
    end = {"time": 151.0}
    for key, (a, b) in deltas.items():
        start[key], end[key] = a, b
    return {"start": start, "end": end,
            "trace_start": dict(start), "trace_end": dict(end)}


def _read(metric, snaps, cell=FEED, trace=TRACE):
    return load_reader(BENCH, metric)(Readings(load_cell(cell), snaps,
                                               trace, None))


def _t(kind, name, key=None):
    return f"util.trace.{kind}.{name}" + (f".{key}" if key else "")


# (metric, its deltas, the value they give, the delta that is its
# denominator)
CASES = [
    ("submit_ms_per_entity",
     {_t("spans", "submit", "s"): (1.0, 1.5),
      _t("counts", "entities_planned"): (100, 1100)},
     0.5, _t("counts", "entities_planned")),
    ("admission_wait_ms",
     {_t("waits", "admission", "s"): (0.0, 2.0),
      _t("waits", "admission", "n"): (10, 410)},
     5.0, _t("waits", "admission", "n")),
    ("queue1_wait_ms",
     {_t("waits", "queue1", "s"): (3.0, 4.0),
      _t("waits", "queue1", "n"): (0, 500)},
     2.0, _t("waits", "queue1", "n")),
    ("queue2_wait_ms",
     {_t("waits", "queue2", "s"): (0.5, 0.75),
      _t("waits", "queue2", "n"): (7, 1007)},
     0.25, _t("waits", "queue2", "n")),
    ("segment_wait_ms",
     {_t("waits", "offload_inbox", "s"): (1.0, 3.0),
      _t("spans", "device_collect", "s"): (0.5, 2.5),
      _t("waits", "offload_inbox", "n"): (40, 240)},
     20.0, _t("waits", "offload_inbox", "n")),
    ("segment_host_ms_per_entity",
     {_t("spans", "device_stage", "s"): (1.0, 1.6),
      _t("spans", "device_fetch", "s"): (0.5, 0.8),
      _t("spans", "device_deliver", "s"): (0.1, 0.2),
      "device.entities_run": (24, 1024)},
     1.0, "device.entities_run"),
    ("remote_wait_ms",
     {_t("waits", "remote_inbox", "s"): (10.0, 610.0),
      _t("waits", "remote_inbox", "n"): (100, 400)},
     2000.0, _t("waits", "remote_inbox", "n")),
    ("remote_exec_ms",
     {_t("spans", "remote_exec", "s"): (2.0, 9.0),
      "util.remote_processed": (90, 590)},
     14.0, "util.remote_processed"),
    ("compiles_per_entity",
     {_t("counts", "compiles"): (400, 700),
      _t("counts", "entities_done"): (500, 3500)},
     0.1, _t("counts", "entities_done")),
]


@pytest.mark.parametrize("metric,deltas,value,denominator", CASES,
                         ids=[c[0] for c in CASES])
def test_reader_arithmetic(metric, deltas, value, denominator):
    assert _read(metric, _snaps(**deltas)) == pytest.approx(value)
    # the same numbers in another cell give the same value
    assert _read(metric, _snaps(**deltas), cell=IQ) == pytest.approx(value)


@pytest.mark.parametrize("metric,deltas,value,denominator", CASES,
                         ids=[c[0] for c in CASES])
def test_reader_is_silent_where_its_denominator_did_not_move(
        metric, deltas, value, denominator):
    still = dict(deltas)
    a, _ = still[denominator]
    still[denominator] = (a, a)
    assert _read(metric, _snaps(**still)) is None


@pytest.mark.parametrize("metric,deltas,value,denominator", CASES,
                         ids=[c[0] for c in CASES])
def test_reader_is_silent_without_the_programs_record(
        metric, deltas, value, denominator):
    # a program without these spans and counts (an engine whose
    # utilization() has no "trace") gives nothing to read, never 0
    kept = {k: v for k, v in deltas.items()
            if not k.startswith("util.trace.")}
    assert _read(metric, _snaps(**kept)) is None
    assert _read(metric, _snaps()) is None


@pytest.mark.parametrize("metric,deltas,value,denominator", CASES,
                         ids=[c[0] for c in CASES])
def test_reader_is_silent_where_no_device_trace_was_reduced(
        metric, deltas, value, denominator):
    assert _read(metric, _snaps(**deltas), trace=None) is None


def test_segment_wait_counts_the_hold_once_per_member():
    # 8 entities, each 1 ms in the inbox; one 2 ms hold of all 8, which
    # the engine records with weight 8: 16 ms of hold in all
    snaps = _snaps(**{_t("waits", "offload_inbox", "s"): (0.0, 0.008),
                      _t("spans", "device_collect", "s"): (0.0, 0.016),
                      _t("waits", "offload_inbox", "n"): (0, 8)})
    assert _read("segment_wait_ms", snaps) == pytest.approx(3.0)


def test_compiles_per_entity_reads_zero_compiles_as_zero():
    snaps = _snaps(**{_t("counts", "compiles"): (300, 300),
                      _t("counts", "entities_done"): (10, 90)})
    assert _read("compiles_per_entity", snaps) == 0.0
