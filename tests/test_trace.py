"""The engine's spans, waits and counts (``repro.core.trace``): their
aggregates, the queues that record waits, thread safety, agreement
with the engine's own counters on a real run, and the ``vdms.*`` host
events they leave in a profiler trace."""
import glob
import os
import queue
import sys
import threading
import time

import jax
import numpy as np
import pytest

from repro.core.engine import VDMSAsyncEngine
from repro.core.remote import RemoteServer, TransportModel
from repro.core.trace import COUNTS, SPANS, WAITS, TimedQueue, Tracer
from repro.query.dispatch import collect_microbatch

FAST = TransportModel(network_latency_s=0.001, service_time_s=0.0)
REMOTE_Q = [{"type": "remote", "url": "http://udf/grayscale",
             "options": {"id": "grayscale"}}]
PREPROCESS = [{"type": "resize", "width": 20, "height": 24},
              {"type": "crop", "x": 2, "y": 3, "width": 12, "height": 10},
              {"type": "normalize", "mean": 0.4, "std": 0.25}]
ON_DEVICE = {o["type"]: {"device": 1e-9, "native": 10.0, "remote": 10.0,
                         "batcher": 10.0} for o in PREPROCESS}


def _find(ops, category="trace"):
    return [{"FindImage": {"constraints": {"category": ["==", category]},
                           "operations": ops}}]


def _add_images(eng, n, size=24, category="trace"):
    rng = np.random.default_rng(3)
    for i in range(n):
        eng.add_entity("image", rng.uniform(0, 1, (size, size, 3))
                       .astype(np.float32), {"category": category, "i": i})


def _delta(a, b, kind, name, key="n"):
    if kind == "counts":
        return b[kind][name] - a[kind][name]
    return b[kind][name][key] - a[kind][name][key]


# ------------------------------------------------------------ aggregates
def test_stats_name_every_span_wait_and_count_from_the_start():
    st = Tracer().stats()
    assert set(st) == {"spans", "waits", "counts"}
    assert set(st["spans"]) == set(SPANS)
    assert set(st["waits"]) == set(WAITS)
    assert set(st["counts"]) == set(COUNTS)
    assert all(v == {"n": 0, "s": 0.0} for v in st["spans"].values())
    assert all(v == {"n": 0, "s": 0.0} for v in st["waits"].values())
    assert all(st["counts"][n] == 0 for n in COUNTS if n != "compiles")


def test_span_wait_and_count_aggregates():
    tr = Tracer()
    for _ in range(2):
        with tr.span("submit", qid="7"):
            time.sleep(0.02)
    with tr.span("device_collect", qid="7", eid="e1") as span:
        time.sleep(0.01)
        span.weight = 4          # a hold shared by four members
    tr.wait("queue1", 0.25)
    tr.wait("queue1", 0.5)
    tr.count("entities_planned", 5)
    tr.count("entities_planned")
    st = tr.stats()
    assert st["spans"]["submit"]["n"] == 2
    assert 0.04 <= st["spans"]["submit"]["s"] < 1.0
    collect = st["spans"]["device_collect"]
    assert collect["n"] == 4 and 0.04 <= collect["s"] < 1.0
    assert st["waits"]["queue1"] == {"n": 2, "s": 0.75}
    assert st["counts"]["entities_planned"] == 6
    with pytest.raises(KeyError):
        tr.wait("no_such_wait", 1.0)


def test_span_records_when_its_body_raises():
    tr = Tracer()
    with pytest.raises(ValueError):
        with tr.span("expand", qid="1"):
            raise ValueError("boom")
    assert tr.stats()["spans"]["expand"]["n"] == 1


def test_compiles_are_counted_process_wide():
    a, b = Tracer(), Tracer()
    before = a.stats()["counts"]["compiles"]
    jax.jit(lambda x: x * 3 + 1)(np.arange(7, dtype=np.float32)) \
        .block_until_ready()
    after_a = a.stats()["counts"]["compiles"]
    assert after_a >= before + 1
    assert b.stats()["counts"]["compiles"] == after_a


# ----------------------------------------------------------- the queues
def test_timed_queue_records_each_items_wait():
    tr = Tracer()
    q = TimedQueue(tr, "queue2")
    q.put(("dispatch", 1))
    q.put(("dispatch", 2))
    time.sleep(0.05)
    assert q.get() == ("dispatch", 1)
    assert q.get_nowait() == ("dispatch", 2)
    w = tr.stats()["waits"]["queue2"]
    assert w["n"] == 2 and w["s"] >= 0.1
    with pytest.raises(queue.Empty):
        q.get(timeout=0.01)
    assert tr.stats()["waits"]["queue2"]["n"] == 2   # nothing taken


def test_timed_queue_records_gets_with_a_timeout_in_a_microbatch():
    tr = Tracer()
    q = TimedQueue(tr, "offload_inbox")
    for i in range(4):
        q.put(i)
    first = q.get()
    group, stop = collect_microbatch(q, first, size=8, max_wait_s=0.05)
    assert group == [0, 1, 2, 3] and not stop
    assert tr.stats()["waits"]["offload_inbox"]["n"] == 4


def test_eight_writers_lose_nothing():
    tr = Tracer()
    q = TimedQueue(tr, "remote_inbox")
    per = 500

    def writer():
        for _ in range(per):
            with tr.span("remote_exec", qid="w"):
                pass
            tr.wait("queue1", 0.001)
            tr.count("entities_done")
            q.put(1)
            q.get()

    threads = [threading.Thread(target=writer) for _ in range(8)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)      # switch threads as often as it can
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    st = tr.stats()
    assert st["spans"]["remote_exec"]["n"] == 8 * per
    assert st["waits"]["queue1"]["n"] == 8 * per
    assert st["waits"]["queue1"]["s"] == pytest.approx(8 * per * 0.001)
    assert st["waits"]["remote_inbox"]["n"] == 8 * per
    assert st["counts"]["entities_done"] == 8 * per


# ------------------------------------------------------ a run on the CPU
def test_static_engine_counts_match_its_own_counters():
    n = 12
    eng = VDMSAsyncEngine(num_remote_servers=2, transport=FAST,
                          num_native_workers=2)
    try:
        _add_images(eng, n)
        eng.execute(_find(REMOTE_Q), timeout=60)    # compiles the op
        u0 = eng.utilization()
        res = eng.execute(_find(REMOTE_Q), timeout=60)
        assert len(res["entities"]) == n and res["stats"]["failed"] == 0
        u1 = eng.utilization()
    finally:
        eng.shutdown()
    a, b = u0["trace"], u1["trace"]
    # a straggler's reissue runs on a second server too: processed
    # counts every run, as the spans do (one entity per request)
    processed = u1["remote_processed"] - u0["remote_processed"]
    assert processed >= n
    assert _delta(a, b, "spans", "remote_exec") == processed
    assert _delta(a, b, "spans", "remote_transport") == processed
    assert _delta(a, b, "waits", "remote_inbox") >= processed
    assert _delta(a, b, "waits", "queue1") >= n
    assert _delta(a, b, "waits", "queue2") >= 2 * n   # dispatch + reply
    assert _delta(a, b, "counts", "entities_done") == n
    assert _delta(a, b, "counts", "entities_planned") == n
    assert _delta(a, b, "spans", "submit") == 1
    assert _delta(a, b, "spans", "expand") == 1
    assert _delta(a, b, "spans", "submit", "s") >= \
        _delta(a, b, "spans", "expand", "s")
    # static placement: nothing reaches the device backend or admission
    assert _delta(a, b, "spans", "device_stage") == 0
    assert _delta(a, b, "waits", "admission") == 0


def test_device_engine_counts_match_its_own_counters():
    n = 10
    eng = VDMSAsyncEngine(num_remote_servers=1, transport=FAST,
                          dispatch="cost", device_backend="cpu",
                          cost_overrides=ON_DEVICE, device_batch_size=4,
                          device_max_wait_ms=20.0, admission="queue",
                          max_inflight_entities=4)
    try:
        _add_images(eng, n)
        u0, d0 = eng.utilization(), eng.dispatch_stats()["device"]
        res = eng.execute(_find(PREPROCESS), timeout=60)
        assert len(res["entities"]) == n and res["stats"]["failed"] == 0
        u1, d1 = eng.utilization(), eng.dispatch_stats()["device"]
    finally:
        eng.shutdown()
    a, b = u0["trace"], u1["trace"]
    groups = d1["groups_run"] - d0["groups_run"]
    assert groups >= 3                    # at most 4 in flight at once
    for span in ("device_stage", "device_settle", "device_fetch",
                 "device_deliver"):
        assert _delta(a, b, "spans", span) == groups, span
    assert d1["entities_run"] - d0["entities_run"] == n
    assert _delta(a, b, "waits", "offload_inbox") == n
    assert _delta(a, b, "spans", "device_collect") == n  # once per member
    # the cap of 4 parks the rest of the phase: every entity passes the
    # pending lane once, and the parked ones wait for a slot
    assert _delta(a, b, "waits", "admission") == n
    assert _delta(a, b, "waits", "admission", "s") > 0
    assert _delta(a, b, "counts", "entities_done") == n
    assert _delta(a, b, "spans", "remote_exec") == 0


def test_utilization_has_no_modelled_transport_counter():
    eng = VDMSAsyncEngine()
    try:
        assert "remote_transport_busy_s" not in eng.utilization()
        assert not hasattr(eng.pool.servers[0], "transport_busy_s")
        assert eng.dispatch_stats() == {"mode": "static"}
    finally:
        eng.shutdown()
    server = RemoteServer(0, FAST)
    try:
        assert isinstance(server.inbox, TimedQueue)
    finally:
        server.kill()


# ------------------------------------------------------ the trace itself
def _host_events(trace_dir):
    from jax.profiler import ProfileData
    path = max(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                         recursive=True), key=os.path.getmtime)
    events = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("vdms."):
                    events.setdefault(ev.name, []).append(
                        {k: v for k, v in ev.stats})
    return events


def test_profiler_trace_holds_vdms_host_events_with_their_query(tmp_path):
    static = VDMSAsyncEngine(num_remote_servers=2, transport=FAST,
                             num_native_workers=2)
    device = VDMSAsyncEngine(dispatch="cost", device_backend="cpu",
                             cost_overrides=ON_DEVICE,
                             device_max_wait_ms=5.0)
    try:
        _add_images(static, 4)
        _add_images(device, 4)
        static.execute(_find(REMOTE_Q), timeout=60)     # compiles outside
        device.execute(_find(PREPROCESS), timeout=60)   # the trace
        jax.profiler.start_trace(str(tmp_path))
        try:
            static.execute(_find(REMOTE_Q), timeout=60)
            device.execute(_find(PREPROCESS), timeout=60)
        finally:
            jax.profiler.stop_trace()
    finally:
        static.shutdown()
        device.shutdown()
    events = _host_events(str(tmp_path))
    for name in ("vdms.submit", "vdms.expand", "vdms.remote_exec",
                 "vdms.remote_transport", "vdms.device_stage",
                 "vdms.device_fetch", "vdms.native", "vdms.thread3"):
        assert name in events, sorted(events)
    for name in ("vdms.submit", "vdms.remote_exec", "vdms.device_stage"):
        assert all("qid" in stats for stats in events[name]), name
    assert any("eid" in stats for stats in events["vdms.remote_exec"])
    assert all("n" in stats for stats in events["vdms.device_stage"])
