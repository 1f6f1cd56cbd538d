"""Cross-session remote coalescing: window batching, reply fan-out,
per-query cancellation inside shared batches, and batch-aware remote
accounting (cost_batch, entity-weighted load, straggler estimate).

Timing-independence: tests that need work coalesced into one batch use
a window far longer than any test run (nothing auto-flushes) and drive
the flush themselves — poll ``pending_coalesced()`` until the expected
entities are buffered, then ``flush_coalesced()``.  No assertion depends
on wall-clock windows, so CI speed cannot change what gets grouped."""
import queue
import threading
import time

import numpy as np
import pytest
from concurrent.futures import CancelledError

from repro.core.engine import VDMSAsyncEngine
from repro.core.entity import Entity
from repro.core.pipeline import make_op
from repro.core.remote import (RemoteServerPool, TransportModel,
                               _batch_size)

FAST = TransportModel(network_latency_s=0.001, service_time_s=0.002)

# a window no test waits out: grouping is decided by explicit flushes
NEVER_MS = 600_000.0

REMOTE_PIPE = [
    {"type": "resize", "width": 24, "height": 24},
    {"type": "remote", "url": "http://s/box", "options": {"id": "facedetect_box"}},
    {"type": "threshold", "value": 0.4},
]


def _mk_engine(**kw):
    kw.setdefault("num_remote_servers", 2)
    kw.setdefault("transport", FAST)
    return VDMSAsyncEngine(**kw)


def _add_images(eng, n=8, size=32, category="lfw"):
    rng = np.random.default_rng(0)
    for i in range(n):
        img = rng.uniform(0, 1, (size, size, 3)).astype(np.float32)
        eng.add_entity("image", img, {"category": category, "idx": i})


def _find(category="lfw", ops=REMOTE_PIPE):
    return [{"FindImage": {"constraints": {"category": ["==", category]},
                           "operations": ops}}]


def _flush_at(eng, expect: int, timeout: float = 30.0):
    """Wait until exactly ``expect`` entities sit in open coalescing
    groups, then force-dispatch them as batches (deterministic stand-in
    for window expiry)."""
    deadline = time.monotonic() + timeout
    while eng.pending_coalesced() < expect and time.monotonic() < deadline:
        time.sleep(0.002)
    assert eng.pending_coalesced() == expect, \
        f"buffered {eng.pending_coalesced()}, expected {expect}"
    eng.flush_coalesced()


def _execute_flushed(eng, query, expect: int, timeout: float = 60.0, **kw):
    """execute() against a never-expiring window: submit, flush once the
    expected remote fan-out is buffered, then collect."""
    fut = eng.submit(query, **kw)
    _flush_at(eng, expect, timeout)
    return fut.result(timeout=timeout)


# ------------------------------------------------------------ coalescing
def test_coalesced_results_match_per_entity_dispatch():
    eng_per = _mk_engine()
    eng_co = _mk_engine(coalesce_window_ms=NEVER_MS)
    try:
        _add_images(eng_per, 16)
        _add_images(eng_co, 16)
        r_per = eng_per.execute(_find(), timeout=60)
        r_co = _execute_flushed(eng_co, _find(), expect=16)
        assert list(r_per["entities"]) == list(r_co["entities"])
        for eid in r_per["entities"]:
            np.testing.assert_array_equal(np.asarray(r_per["entities"][eid]),
                                          np.asarray(r_co["entities"][eid]))
        u = eng_co.utilization()
        # exactly one flush of all 16: one batched request
        assert u["coalesced_batches"] == 1
        assert u["coalesced_entities"] == 16
        assert u["remote_dispatched"] == 1
        assert eng_per.utilization()["remote_dispatched"] == 16
    finally:
        eng_per.shutdown()
        eng_co.shutdown()


def test_window_off_by_default_keeps_per_entity_dispatch():
    eng = _mk_engine()
    try:
        _add_images(eng, 6)
        eng.execute(_find(), timeout=60)
        u = eng.utilization()
        assert u["coalesced_batches"] == 0
        assert u["remote_dispatched"] == 6      # one request per entity
    finally:
        eng.shutdown()


def test_window_expiry_flushes_without_explicit_flush():
    # the wall-clock expiry path still works end to end (completion and
    # correctness only — nothing here asserts WHAT got grouped, which is
    # the timing-dependent part the explicit-flush tests pin down)
    eng_per = _mk_engine()
    eng = _mk_engine(coalesce_window_ms=10)
    try:
        _add_images(eng_per, 6)
        _add_images(eng, 6)
        r_per = eng_per.execute(_find(), timeout=60)
        r = eng.execute(_find(), timeout=60)
        assert r["stats"]["failed"] == 0
        for eid in r_per["entities"]:
            np.testing.assert_array_equal(np.asarray(r_per["entities"][eid]),
                                          np.asarray(r["entities"][eid]))
    finally:
        eng_per.shutdown()
        eng.shutdown()


def test_flush_coalesced_with_nothing_buffered_is_harmless():
    eng = _mk_engine(coalesce_window_ms=NEVER_MS)
    try:
        _add_images(eng, 4)
        eng.flush_coalesced()                  # empty flush: no-op
        assert eng.pending_coalesced() == 0
        r = _execute_flushed(eng, _find(), expect=4)
        assert r["stats"]["failed"] == 0
    finally:
        eng.shutdown()


def test_max_batch_flushes_before_any_window():
    # coalesce_max_batch caps a group even while the window never
    # expires: 8 entities with max_batch 4 dispatch as two full batches
    # without a single explicit flush
    eng = _mk_engine(coalesce_window_ms=NEVER_MS, coalesce_max_batch=4)
    try:
        _add_images(eng, 8)
        r = eng.execute(_find(), timeout=60)
        assert r["stats"]["failed"] == 0
        u = eng.utilization()
        assert u["coalesced_batches"] == 2
        assert u["coalesced_entities"] == 8
        assert u["remote_dispatched"] == 2
    finally:
        eng.shutdown()


def test_entities_from_different_sessions_share_one_batch():
    eng = _mk_engine(coalesce_window_ms=NEVER_MS, coalesce_max_batch=64)
    try:
        _add_images(eng, 4)
        _execute_flushed(eng, _find(), expect=4, cache=False)   # jit warmup
        base = eng.utilization()["coalesced_entities"]
        futs = [eng.submit(_find()) for _ in range(2)]
        _flush_at(eng, expect=8)       # both sessions buffered together
        for f in futs:
            r = f.result(timeout=60)
            assert r["stats"]["failed"] == 0
        grouped = eng.utilization()["coalesced_entities"] - base
        assert grouped == 8            # one batch mixed the two sessions
    finally:
        eng.shutdown()


def test_cancel_drops_only_that_querys_members_from_shared_batch():
    eng = _mk_engine(num_remote_servers=1,
                     coalesce_window_ms=NEVER_MS, coalesce_max_batch=64)
    try:
        _add_images(eng, 6)
        doomed = eng.submit(_find())
        kept = eng.submit(_find())
        # both sessions' remote ops sit buffered in ONE open group; the
        # cancel lands while they are still buffered, so the flush must
        # drop exactly doomed's six members and dispatch kept's six
        deadline = time.monotonic() + 30
        while eng.pending_coalesced() < 12 and time.monotonic() < deadline:
            time.sleep(0.002)
        assert eng.pending_coalesced() == 12
        assert doomed.cancel()
        with pytest.raises(CancelledError):
            doomed.result(timeout=5)
        eng.flush_coalesced()
        r = kept.result(timeout=60)
        assert r["stats"]["matched"] == 6
        assert r["stats"]["failed"] == 0
        assert eng.utilization()["coalesced_entities"] == 6  # kept's only
        deadline = time.monotonic() + 10
        while eng.pool.inflight and time.monotonic() < deadline:
            time.sleep(0.01)
        assert not eng.pool.inflight
        # engine stays healthy for follow-up queries
        r2 = _execute_flushed(eng, _find(), expect=6)
        assert r2["stats"]["failed"] == 0
    finally:
        eng.shutdown()


def test_coalescing_composes_with_result_cache():
    eng = _mk_engine(coalesce_window_ms=NEVER_MS, cache_capacity=256)
    try:
        _add_images(eng, 8)
        r1 = _execute_flushed(eng, _find(), expect=8)   # populates cache
        r2 = eng.execute(_find(), timeout=60)           # full hits: no
        assert r2["stats"]["cache_full_hits"] == 8      # remote work at all
        assert eng.pending_coalesced() == 0
        for eid in r1["entities"]:
            np.testing.assert_array_equal(np.asarray(r1["entities"][eid]),
                                          np.asarray(r2["entities"][eid]))
    finally:
        eng.shutdown()


# ------------------------------------- batch-aware remote accounting
def test_batched_request_sleeps_cost_batch_not_cost_sum():
    t = TransportModel(network_latency_s=0.05, service_time_s=0.001,
                       execute_ops=False)
    pool = RemoteServerPool(1, t)
    try:
        op = make_op("grayscale")
        ents = [Entity(str(i), "image", np.zeros((8, 8, 3), np.float32),
                       ops=[op]) for i in range(4)]
        reply: queue.Queue = queue.Queue()
        pool.dispatch(ents, op, reply)
        tag, req, payload = reply.get(timeout=10)
        assert tag == "ok" and len(payload) == 4
        per_payload_sum = sum(t.cost(e.data.nbytes) for e in ents)
        batch_cost = t.cost_batch([e.data.nbytes for e in ents])
        # one remote_transport span for the batched request: it slept
        # cost_batch (a sleep lasts at least its argument) ...
        slept = pool.tracer.stats()["spans"]["remote_transport"]
        assert slept["n"] == 1
        assert slept["s"] >= batch_cost - 1e-6
        # ... and the amortization is real: one latency, not four
        assert slept["s"] < per_payload_sum - 0.1
    finally:
        pool.shutdown()


def test_server_load_counts_entities_not_requests():
    t = TransportModel(network_latency_s=0.2, execute_ops=False)
    pool = RemoteServerPool(1, t)
    try:
        op = make_op("grayscale")
        reply: queue.Queue = queue.Queue()
        batch = [Entity(str(i), "image", np.zeros((4, 4, 3), np.float32),
                        ops=[op]) for i in range(5)]
        pool.dispatch(batch, op, reply)
        single = Entity("s", "image", np.zeros((4, 4, 3), np.float32), ops=[op])
        pool.dispatch(single, op, reply)
        assert pool.servers[0].load() == 6      # 5 + 1 entities pending
        for _ in range(2):
            reply.get(timeout=10)
        deadline = time.monotonic() + 5
        while pool.servers[0].load() and time.monotonic() < deadline:
            time.sleep(0.01)
        assert pool.servers[0].load() == 0
    finally:
        pool.shutdown()


def test_straggler_estimate_amortizes_batches():
    t = TransportModel(network_latency_s=0.0, service_time_s=0.01,
                       execute_ops=False)
    pool = RemoteServerPool(1, t)
    try:
        op = make_op("grayscale")
        reply: queue.Queue = queue.Queue()
        batch = [Entity(str(i), "image", np.zeros((4, 4, 3), np.float32),
                        ops=[op]) for i in range(8)]
        assert _batch_size(pool.inflight[pool.dispatch(batch, op, reply)]) == 8
        tag, req, payload = reply.get(timeout=10)
        est_before = pool._lat_est
        pool.handle_response(tag, req, payload)
        # the 8-entity batch took ~8x service time, but the estimate moves
        # toward the amortized per-entity latency, not the batch wall
        assert pool._lat_est <= 0.9 * est_before + 0.1 * 0.05
    finally:
        pool.shutdown()
