"""Reduction of a profiler trace to busy time, top device operations and
labelled idle gaps: on a trace built by hand, whose numbers are known,
and on a small trace recorded on a TPU v5e by the benchmark itself."""
from __future__ import annotations

import os

import pytest

import bench_tiny  # noqa: F401 — puts the repository on sys.path
from bench import trace_reduce

RECORDED = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "testdata", "blur_v5e.xplane.pb.gz")


def _event(meta, start_ps, dur_ps, module=None):
    stats = (f' stats {{ metadata_id: 9 str_value: "{module}" }}'
             if module else "")
    return (f"events {{ metadata_id: {meta} offset_ps: {start_ps} "
            f"duration_ps: {dur_ps}{stats} }}")


def _plane(pid, name, line, events, names):
    metas = " ".join(f'event_metadata {{ key: {k} value {{ id: {k} '
                     f'name: "{n}" }} }}' for k, n in names.items())
    return (f'planes {{ id: {pid} name: "{name}" lines {{ id: 1 '
            f'name: "{line}" timestamp_ns: 0 {" ".join(events)} }} '
            f'{metas} stat_metadata {{ key: 9 value {{ id: 9 '
            f'name: "hlo_module" }} }} }}')


def _hand_trace():
    """Window 1..11 ms.  Device 0 runs fusion 0-3 ms (2 ms inside the
    window) and copy 5-6 ms, overlapping fusion 5.5-7 ms; host spans
    say the client waited 3-9 ms and submitted 9.5-11 ms."""
    ms = 1_000_000_000      # picoseconds
    from jax.profiler import ProfileData
    dev = _plane(1, "/device:TPU:0", "XLA Ops",
                 [_event(1, 0, 3 * ms, "jit_a"),
                  _event(2, 5 * ms, 1 * ms, "jit_b"),
                  _event(1, int(5.5 * ms), int(1.5 * ms), "jit_a")],
                 {1: "fusion.1", 2: "copy.2"})
    host = _plane(2, "/host:CPU", "python",
                  [_event(1, 1 * ms, 10 * ms), _event(2, 3 * ms, 6 * ms),
                   _event(3, int(9.5 * ms), int(1.5 * ms)),
                   _event(4, 0, 20 * ms)],
                  {1: "bench.traced_window", 2: "bench.wait",
                   3: "bench.submit", 4: "unrelated"})
    return ProfileData.from_text_proto(dev + host)


def test_hand_trace_numbers():
    r = trace_reduce.reduce_data(_hand_trace())
    assert r["devices"] == 1
    assert r["window_s"] == pytest.approx(0.010)
    # union inside the window: 1-3 ms and 5-7 ms
    assert r["busy_s"] == pytest.approx(0.004)
    ops = dict((n, t) for n, t in r["device_ops"])
    assert ops == pytest.approx({"jit_a/fusion.1": 0.0035,
                                 "jit_b/copy.2": 0.001})
    assert [n for n, _ in r["device_ops"]] == ["jit_a/fusion.1",
                                               "jit_b/copy.2"]
    # gaps 3-5 ms (wait) and 7-11 ms (wait 2 ms, submit 1.5 ms)
    assert r["idle_gaps"] == [["bench.wait", pytest.approx(0.004)],
                              ["bench.wait", pytest.approx(0.002)]]
    assert r["spans"]["bench.wait"] == pytest.approx(0.006)
    assert "unrelated" not in r["spans"]


def test_union_merges_overlaps():
    assert trace_reduce._union([(5, 7), (1, 3), (2, 4), (7, 8)]) == \
        [[1, 4], [5, 8]]


def test_no_device_plane_is_an_error():
    from jax.profiler import ProfileData
    host = _plane(2, "/host:CPU", "python", [_event(1, 0, 10)],
                  {1: "bench.wait"})
    with pytest.raises(ValueError, match="no /device:TPU"):
        trace_reduce.reduce_data(ProfileData.from_text_proto(host))


def test_recorded_v5e_trace():
    """A 50 ms traced window of ``lfw_device.blur`` on one v5e, recorded
    with ``bench/run.py --trace 1 --trace-seconds 0.05 --keep-trace`` and
    gzipped."""
    assert os.path.getsize(RECORDED) < 400_000
    r = trace_reduce.reduce(RECORDED)
    # the run's own reading of this trace
    assert r["busy_s"] == pytest.approx(6.5838e-05)
    assert r["window_s"] == pytest.approx(0.05039922)
    assert r["devices"] == 1
    assert r["window_s"] == pytest.approx(0.05, rel=0.2)
    assert 0 < r["busy_s"] < r["window_s"]
    ops = r["device_ops"]
    assert 0 < len(ops) <= 10
    # HLO text shortened to name and kind; the Pallas blur kernel on top
    assert ops[0][0] == "%branch_0_fun.1 custom-call"
    assert all(" = " not in name for name, _ in ops)
    times = [t for _, t in ops]
    assert times == sorted(times, reverse=True)
    assert sum(times) >= r["busy_s"] * 0.99 or len(ops) == 10
    gaps = r["idle_gaps"]
    assert 0 < len(gaps) <= 10
    assert all(t > 0 for _, t in gaps)
    assert sum(t for _, t in gaps) <= r["window_s"] - r["busy_s"] + 1e-9
    assert any(name.startswith("bench.") for name, _ in gaps)
