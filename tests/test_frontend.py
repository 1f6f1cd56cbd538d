"""Protocol conformance + chaos suite for the network serving front-end.

Conformance is transcript-based: each scenario drives the wire through
``WireClient``, normalizes the frames it saw (volatile fields —
durations, retry estimates, load snapshots — are canonicalized), and
compares against a golden transcript in ``tests/wire_golden/``.  A
failure prints the unified diff.  Regenerate after an intentional
protocol change with::

    WIRE_GOLDEN_UPDATE=1 PYTHONPATH=src python -m pytest tests/test_frontend.py

The chaos half storms the frontend with concurrent clients that
disconnect mid-stream (and, fronting the ShardedEngine, lose a shard
mid-query) and asserts the serving contract: surviving clients get the
exact in-process results, no admission slot leaks, inflight stays
bounded.  Admission v2 (tenant fair shares, cost-aware charging) is
unit-tested here too — the wire is where those knobs got their door.
"""
from __future__ import annotations

import difflib
import json
import os
import re
import threading
import time

import numpy as np
import pytest

from repro.cluster.engine import ShardedEngine
from repro.core.engine import VDMSAsyncEngine
from repro.core.remote import TransportModel
from repro.query.admission import AdmissionController, OverloadError
from repro.serving.frontend import WireClient, WireFrontend

GOLDEN_DIR = os.path.join(os.path.dirname(__file__), "wire_golden")
FAST = TransportModel(network_latency_s=0.0005, service_time_s=0.0005)
SLOW = TransportModel(network_latency_s=0.005, service_time_s=0.05)

# deterministic server shape for every golden transcript: one native
# worker + FIFO scheduling means entity frames arrive in enqueue order
DET = dict(num_remote_servers=1, num_native_workers=1,
           fair_scheduling=False, transport=FAST)


def _fill(eng, n=3, size=8, seed=7):
    rng = np.random.default_rng(seed)
    for _ in range(n):
        eng.add_entity(
            "image",
            rng.integers(0, 255, (size, size, 3)).astype(np.float32),
            {"category": "wire"})


def _find(ops=({"type": "flip", "axis": "vertical"},)):
    return [{"FindImage": {"constraints": {"category": ["==", "wire"]},
                           "operations": list(ops)}}]


# ------------------------------------------------- transcript machinery
_RETRY_RE = re.compile(r"retry_after_s=[^\s)]+")


def _normalize(frames):
    """Canonicalize the volatile parts of a transcript: wall-clock
    durations, retry estimates (load-dependent), and load snapshots.
    Everything else — including the base64 entity payloads — must match
    the golden byte-for-byte."""
    out = []
    for event, payload in frames:
        p = json.loads(json.dumps(payload))
        if isinstance(p.get("stats"), dict) and "duration_s" in p["stats"]:
            p["stats"]["duration_s"] = 0.0
        if "retry_after_s" in p:
            p["retry_after_s"] = ("<positive>" if p["retry_after_s"] > 0
                                  else p["retry_after_s"])
        p.pop("load", None)
        if isinstance(p.get("message"), str):
            p["message"] = _RETRY_RE.sub("retry_after_s=<n>", p["message"])
        out.append([event, p])
    return out


def _check_golden(name: str, frames):
    got = json.dumps(_normalize(frames), indent=1, sort_keys=True) + "\n"
    path = os.path.join(GOLDEN_DIR, name + ".json")
    if os.environ.get("WIRE_GOLDEN_UPDATE"):
        os.makedirs(GOLDEN_DIR, exist_ok=True)
        with open(path, "w") as f:
            f.write(got)
        return
    assert os.path.exists(path), (
        f"golden transcript {path} missing — run the suite once with "
        f"WIRE_GOLDEN_UPDATE=1 to record it")
    with open(path) as f:
        want = f.read()
    if got != want:
        diff = "\n".join(difflib.unified_diff(
            want.splitlines(), got.splitlines(),
            fromfile=f"wire_golden/{name}.json", tofile="observed",
            lineterm=""))
        pytest.fail(f"wire transcript diverged from golden:\n{diff}")


def _serve(engine):
    return WireFrontend(engine).start()


@pytest.mark.parametrize("shape", [(), (1,), (2, 3)])
def test_wire_ndarray_coding_keeps_shape(shape):
    """Pinned counterexample of the chunking property: a 0-d array must
    come back 0-d, not as shape (1,)."""
    from repro.serving.wire import from_jsonable, to_jsonable
    arr = np.arange(int(np.prod(shape)), dtype=np.float32).reshape(shape)
    back = from_jsonable(json.loads(json.dumps(to_jsonable({"a": arr}))))
    assert back["a"].shape == shape
    assert back["a"].dtype == arr.dtype
    assert np.array_equal(back["a"], arr)


# ============================================ golden conformance suite
def test_golden_submit_stream_complete():
    eng = VDMSAsyncEngine(**DET)
    try:
        _fill(eng, n=3)
        front = _serve(eng)
        try:
            with WireClient(front.address) as c:
                one = c.submit(_find(), rid="q-stream")
                one.wait_terminal(30)
                # two commands in one query: entity frames carry
                # cmd_index, the complete frame carries final key order
                two = c.submit(
                    [{"FindImage": {"constraints": {"category":
                                                    ["==", "wire"]},
                      "operations": [{"type": "flip", "axis": "vertical"}]}},
                     {"FindImage": {"constraints": {"category":
                                                    ["==", "wire"]},
                      "operations": [{"type": "rotate", "k": 1}]}}],
                    rid="q-two-cmds")
                two.wait_terminal(30)
            _check_golden("submit_stream_complete", one.frames + two.frames)
        finally:
            front.close()
    finally:
        eng.shutdown()


def test_golden_error_frames():
    eng = VDMSAsyncEngine(**dict(DET, transport=SLOW))
    try:
        _fill(eng, n=1)
        front = _serve(eng)
        try:
            with WireClient(front.address) as c:
                # a query the engine cannot parse: error frame, conn lives
                bad_cmd = c.submit([{"ExplodeImage": {}}], rid="q-bad-cmd")
                bad_cmd.wait_terminal(30)
                # well-formed submit missing its query: rejected by rid
                c.send_raw(b'event: submit\n'
                           b'data: {"rid": "q-no-query"}\n\n')
                no_query = c.next_orphan(timeout=10)
                # rid reuse while the first query is still in flight (a
                # completed query's rid is free again — token lifetime
                # is query lifetime — so collide mid-flight)
                slow = c.submit(_find(ops=({"type": "remote", "url": "u",
                                            "options": {"id": "flip"}},)),
                                rid="q-dup")
                c.send_raw(b'event: submit\n'
                           b'data: {"query": [], "rid": "q-dup"}\n\n')
                ev, _ = slow.wait_terminal(30)
                assert ev == "error"   # the collision poisons only q-dup
                assert c.ping(), "semantic rejections keep the connection"
            _check_golden("error_frames",
                          bad_cmd.frames + [no_query] + slow.frames)
        finally:
            front.close()
    finally:
        eng.shutdown()


def test_golden_overload_429():
    """The saturated engine answers over the wire with the 429 frame +
    retry-after; once capacity frees, the same query completes."""
    eng = VDMSAsyncEngine(**DET, admission="shed", max_inflight_entities=2)
    try:
        _fill(eng, n=2)
        # deterministically saturate the ledger: a pre-ingest claim holds
        # both slots without any racing in-flight work
        eng.admission_ctl.reserve("hold", 2, first_phase=True)
        front = _serve(eng)
        try:
            with WireClient(front.address) as c:
                shed = c.submit(_find(), rid="q-shed")
                shed.wait_terminal(30)
                eng.admission_ctl.drop_query("hold")
                retry = c.submit(_find(), rid="q-retry")
                retry.wait_terminal(30)
            _check_golden("overload_429", shed.frames + retry.frames)
            # and the client rebuilds the typed exception
            with pytest.raises(OverloadError) as ei:
                shed.result(1)
            assert ei.value.retry_after_s > 0
        finally:
            front.close()
    finally:
        eng.shutdown()


def test_golden_tenant_quota():
    """Per-tenant quota exhaustion: bronze (weight 1 of 4 → 2 of 8
    slots) is rejected with the tenant-tagged 429 while gold's share
    still admits — the engine is NOT full, bronze's share is."""
    eng = VDMSAsyncEngine(**DET, admission="shed", max_inflight_entities=8,
                          admission_tenants={"gold": 3.0, "bronze": 1.0})
    try:
        _fill(eng, n=2)
        eng.admission_ctl.reserve("hold", 3, first_phase=True,
                                  tenant="bronze")
        front = _serve(eng)
        try:
            with WireClient(front.address) as c:
                bronze = c.submit(_find(), tenant="bronze", rid="q-bronze")
                bronze.wait_terminal(30)
                gold = c.submit(_find(), tenant="gold", rid="q-gold")
                gold.wait_terminal(30)
            _check_golden("tenant_quota", bronze.frames + gold.frames)
            assert bronze.frames[-1][0] == "overload"
            assert bronze.frames[-1][1]["tenant"] == "bronze"
            assert gold.frames[-1][0] == "complete"
        finally:
            front.close()
    finally:
        eng.shutdown()


def test_golden_malformed_frames():
    """Grammar violations: unknown event, non-JSON data, structureless
    bytes — each answered with an error frame, then the connection is
    dropped (no resync on a framed stream).  Semantically-invalid but
    well-formed frames (submit without rid) keep the connection."""
    eng = VDMSAsyncEngine(**DET)
    try:
        front = _serve(eng)
        collected = []
        try:
            for raw in (b"event: nonsense\ndata: {}\n\n",
                        b"event: submit\ndata: not json at all\n\n",
                        b"no grammar here whatsoever\n\n"):
                c = WireClient(front.address)
                c.send_raw(raw)
                collected.append(c.next_orphan(timeout=10))
                assert c.disconnected.wait(10), \
                    "grammar violation must drop the connection"
                c.close()
            c = WireClient(front.address)
            c.send_raw(b'event: submit\ndata: {"query": []}\n\n')
            collected.append(c.next_orphan(timeout=10))
            assert c.ping(), "semantic rejection must keep the connection"
            c.close()
            _check_golden("malformed_frames", collected)
        finally:
            front.close()
    finally:
        eng.shutdown()


# =============================================== live serving contract
def test_wire_result_byte_identical_to_inprocess():
    eng = VDMSAsyncEngine(**DET)
    try:
        _fill(eng, n=4)
        ref = eng.execute(_find())
        front = _serve(eng)
        try:
            with WireClient(front.address) as c:
                fut = c.submit(_find())
                got = fut.result(30)
            assert [e for e, _ in fut.frames][:1] == ["submitted"]
            assert list(got["entities"]) == list(ref["entities"])
            for eid, arr in ref["entities"].items():
                w = got["entities"][eid]
                assert w.dtype == arr.dtype and w.shape == arr.shape
                assert np.array_equal(w, arr)
        finally:
            front.close()
    finally:
        eng.shutdown()


def test_cancel_frame_reaches_session():
    eng = VDMSAsyncEngine(**dict(DET, transport=SLOW))
    try:
        _fill(eng, n=6)
        front = _serve(eng)
        try:
            with WireClient(front.address) as c:
                fut = c.submit(_find(
                    ops=({"type": "remote", "url": "u",
                          "options": {"id": "flip"}},)))
                time.sleep(0.05)
                fut.cancel()
                terminal, _ = fut.wait_terminal(30)
                assert terminal == "cancelled"
        finally:
            front.close()
        # the engine is healthy afterwards: nothing leaked
        assert len(eng.execute(_find())["entities"]) == 6
    finally:
        eng.shutdown()


def test_disconnect_cancels_and_frees_admission_slots():
    """A client that dies mid-stream must not leak admission slots:
    disconnect → cancel → drop_query zeroes the ledger."""
    eng = VDMSAsyncEngine(**dict(DET, transport=SLOW), admission="shed",
                          max_inflight_entities=6)
    try:
        _fill(eng, n=6)
        front = _serve(eng)
        try:
            c = WireClient(front.address)
            c.submit(_find(ops=({"type": "remote", "url": "u",
                                 "options": {"id": "flip"}},)))
            time.sleep(0.08)          # mid-stream: remote ops in flight
            c.drop()
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:
                st = eng.admission_ctl.stats()
                if (st["inflight"], st["pending"], st["reserved"]) \
                        == (0, 0, 0):
                    break
                time.sleep(0.01)
            st = eng.admission_ctl.stats()
            assert (st["inflight"], st["pending"], st["reserved"]) \
                == (0, 0, 0), f"leaked admission ledger: {st}"
            # full capacity is usable again
            assert len(eng.execute(_find())["entities"]) == 6
        finally:
            front.close()
    finally:
        eng.shutdown()


def test_saturated_engine_still_serves_cache_hits():
    """Acceptance: while the ledger is saturated, a cache-servable
    query completes over the wire (instant entities consume no
    capacity) and a cache-bypassing one gets the 429."""
    eng = VDMSAsyncEngine(**DET, cache_capacity=64, admission="shed",
                          max_inflight_entities=4)
    try:
        _fill(eng, n=3)
        front = _serve(eng)
        try:
            with WireClient(front.address) as c:
                warm = c.submit(_find()).result(30)       # populate cache
                eng.admission_ctl.reserve("hold", 4, first_phase=True)
                served = c.submit(_find()).result(30)     # cache-served
                assert served["stats"]["cache_full_hits"] == 3
                for eid in warm["entities"]:
                    assert np.array_equal(served["entities"][eid],
                                          warm["entities"][eid])
                with pytest.raises(OverloadError) as ei:
                    c.submit(_find(), cache=False).result(30)
                assert ei.value.retry_after_s > 0
        finally:
            front.close()
    finally:
        eng.shutdown()


def test_frontend_fronts_sharded_engine():
    eng = ShardedEngine(num_shards=3, replica_factor=2, **DET)
    try:
        _fill(eng, n=6)
        ref = eng.execute(_find())
        front = _serve(eng)
        try:
            with WireClient(front.address) as c:
                got = c.execute(_find(), timeout=30)
            assert list(got["entities"]) == list(ref["entities"])
            for eid, arr in ref["entities"].items():
                assert np.array_equal(got["entities"][eid], arr)
        finally:
            front.close()
    finally:
        eng.shutdown()


# ======================================================== chaos storms
@pytest.mark.parametrize("seed", range(3))
def test_chaos_storm_disconnects_never_leak_slots(seed):
    """Seeded storm: concurrent wire clients, a subset dying abruptly
    mid-stream.  Survivors get the exact in-process result, the
    admission ledger drains to zero, and inflight never exceeded the
    cap."""
    rng = np.random.default_rng(seed)
    eng = VDMSAsyncEngine(
        num_remote_servers=2, num_native_workers=2, fair_scheduling=True,
        transport=TransportModel(network_latency_s=0.002,
                                 service_time_s=0.004),
        admission="queue", max_inflight_entities=8,
        admission_queue_cap=4096)
    try:
        _fill(eng, n=6, seed=seed)
        q = _find(ops=({"type": "remote", "url": "u",
                        "options": {"id": "flip"}},))
        ref = eng.execute(q)
        front = _serve(eng)
        clients, droppers, results, errors = [], [], {}, []
        try:
            n_clients = 10
            drop_idx = set(rng.choice(n_clients, size=4, replace=False)
                           .tolist())
            barrier = threading.Barrier(n_clients)

            def run(i):
                try:
                    c = WireClient(front.address)
                    clients.append(c)
                    barrier.wait(timeout=10)
                    fut = c.submit(q)
                    if i in drop_idx:
                        time.sleep(float(rng.uniform(0.0, 0.05)))
                        c.drop()
                        droppers.append(i)
                        return
                    results[i] = fut.result(60)
                    c.close()
                except Exception as e:  # noqa: BLE001 — collected below
                    errors.append((i, e))

            threads = [threading.Thread(target=run, args=(i,), daemon=True)
                       for i in range(n_clients)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120)
            assert not errors, f"surviving clients failed: {errors}"
            assert len(droppers) == 4 and len(results) == 6
            for res in results.values():
                assert list(res["entities"]) == list(ref["entities"])
                for eid, arr in ref["entities"].items():
                    assert np.array_equal(res["entities"][eid], arr)
            deadline = time.monotonic() + 15
            while time.monotonic() < deadline:
                st = eng.admission_ctl.stats()
                if (st["inflight"], st["pending"], st["reserved"]) \
                        == (0, 0, 0):
                    break
                time.sleep(0.01)
            st = eng.admission_ctl.stats()
            assert (st["inflight"], st["pending"], st["reserved"]) \
                == (0, 0, 0), f"leaked admission ledger: {st}"
            assert st["peak_inflight"] <= 8
        finally:
            front.close()
    finally:
        eng.shutdown()


@pytest.mark.parametrize("seed", range(2))
def test_chaos_storm_sharded_kill_shard_mid_query(seed):
    """The sharded variant: clients storm the wire while a shard dies
    mid-query (and two clients drop).  At replica_factor=2 every
    surviving client still gets the full, exact result set."""
    rng = np.random.default_rng(100 + seed)
    eng = ShardedEngine(
        num_shards=3, replica_factor=2, num_remote_servers=1,
        num_native_workers=1, fair_scheduling=False,
        transport=TransportModel(network_latency_s=0.001,
                                 service_time_s=0.01))
    try:
        _fill(eng, n=6, seed=seed)
        q = _find(ops=({"type": "remote", "url": "u",
                        "options": {"id": "flip"}},))
        ref = eng.execute(q)
        front = _serve(eng)
        results, errors, droppers = {}, [], []
        try:
            n_clients = 6
            drop_idx = set(rng.choice(n_clients, size=2, replace=False)
                           .tolist())
            barrier = threading.Barrier(n_clients + 1)

            def run(i):
                try:
                    c = WireClient(front.address)
                    barrier.wait(timeout=10)
                    fut = c.submit(q)
                    if i in drop_idx:
                        time.sleep(float(rng.uniform(0.0, 0.03)))
                        c.drop()
                        droppers.append(i)
                        return
                    results[i] = fut.result(120)
                    c.close()
                except Exception as e:  # noqa: BLE001
                    errors.append((i, e))

            threads = [threading.Thread(target=run, args=(i,), daemon=True)
                       for i in range(n_clients)]
            for t in threads:
                t.start()
            barrier.wait(timeout=10)
            time.sleep(float(rng.uniform(0.005, 0.03)))
            victim = int(rng.integers(0, 3))
            eng.kill_shard(victim)
            for t in threads:
                t.join(timeout=180)
            assert not errors, f"surviving clients failed: {errors}"
            assert len(results) == n_clients - 2
            for res in results.values():
                assert list(res["entities"]) == list(ref["entities"])
                assert res["stats"]["failed"] == 0
                for eid, arr in ref["entities"].items():
                    assert np.array_equal(res["entities"][eid], arr)
            assert victim not in eng.cluster_stats()["live_shards"]
        finally:
            front.close()
    finally:
        eng.shutdown()


# ==================================== admission v2: tenants + cost units
class _E:
    def __init__(self, qid):
        self.query_id = qid


class _Tracker:
    def __init__(self, est):
        self._est = est

    def mean_estimate(self):
        return self._est


def test_cost_aware_charges_estimated_work_seconds():
    ctl = AdmissionController(max_inflight=100, policy="shed",
                              cost_aware=True, cost_cap_s=2.0)
    ctl.bind(loop=None, pool=None, launch=None,
             tracker=_Tracker(0.5))
    assert ctl.unit_charge(1) == 0.5 and ctl.unit_charge(4) == 2.0
    # 3 one-op entities = 1.5s of the 2.0s budget
    admitted = ctl.admit_phase("a", [_E("a") for _ in range(3)], 0,
                               first_phase=True, n_ops=1)
    assert len(admitted) == 3
    assert ctl.stats()["cost"]["inflight_cost_s"] == pytest.approx(1.5)
    # 2 more would charge 1.0s against 0.5s free — shed, with the
    # deficit itself as the retry estimate (entity count is nowhere
    # near the 100 cap: the COST budget did the rejecting)
    with pytest.raises(OverloadError) as ei:
        ctl.admit_phase("b", [_E("b"), _E("b")], 0, first_phase=True,
                        n_ops=1)
    assert 0 < ei.value.retry_after_s <= 60
    # releasing one entity (its stamped 0.5s) makes room for one more
    ents = admitted[:1]
    ctl.note_done(ents[0])
    assert ctl.stats()["cost"]["inflight_cost_s"] == pytest.approx(1.0)
    ok = ctl.admit_phase("c", [_E("c"), _E("c")], 0, first_phase=True,
                         n_ops=1)
    assert len(ok) == 2
    for e in admitted[1:] + ok:
        ctl.note_done(e)
    st = ctl.stats()
    assert st["cost"]["inflight_cost_s"] == pytest.approx(0.0)
    assert st["inflight"] == 0


def test_cost_aware_wider_pipelines_charge_more():
    ctl = AdmissionController(max_inflight=100, policy="shed",
                              cost_aware=True, cost_cap_s=1.0)
    ctl.bind(loop=None, pool=None, launch=None, tracker=_Tracker(0.2))
    # a single 6-op entity charges 1.2s > 1.0s cap: never fits
    with pytest.raises(OverloadError) as ei:
        ctl.admit_phase("a", [_E("a")], 0, first_phase=True, n_ops=6)
    assert ei.value.retry_after_s == float("inf")
    # the same entity with 4 ops (0.8s) fits
    assert len(ctl.admit_phase("a", [_E("a")], 0, first_phase=True,
                               n_ops=4)) == 1


def test_tenant_fair_share_math_and_exemption():
    ctl = AdmissionController(max_inflight=8, policy="shed",
                              tenant_weights={"gold": 3.0, "bronze": 1.0})
    assert ctl._tenant_cap_locked("gold") == pytest.approx(6.0)
    assert ctl._tenant_cap_locked("bronze") == pytest.approx(2.0)
    # an unlisted tenant joins the denominator at the default weight
    assert ctl._tenant_cap_locked("stranger") == pytest.approx(8.0 / 5.0)
    # bronze can hold its 2 slots...
    assert len(ctl.admit_phase("b1", [_E("b1"), _E("b1")], 0,
                               first_phase=True, tenant="bronze")) == 2
    # ...but not a third
    with pytest.raises(OverloadError) as ei:
        ctl.admit_phase("b2", [_E("b2")], 0, first_phase=True,
                        tenant="bronze")
    assert ei.value.tenant == "bronze"
    # gold and the exempt empty tenant are untouched by bronze's state
    assert len(ctl.admit_phase("g1", [_E("g1")] * 3, 0,
                               first_phase=True, tenant="gold")) == 3
    assert len(ctl.admit_phase("p1", [_E("p1")] * 3, 0,
                               first_phase=True)) == 3


def test_tenant_anti_starvation_first_phase_always_lands():
    """A tenant holding nothing is admitted even when one phase exceeds
    its share — a small share must throttle, never starve outright."""
    ctl = AdmissionController(max_inflight=8, policy="shed",
                              tenant_weights={"tiny": 0.1, "big": 10.0})
    assert ctl._tenant_cap_locked("tiny") < 1.0
    admitted = ctl.admit_phase("t1", [_E("t1"), _E("t1")], 0,
                               first_phase=True, tenant="tiny")
    # the phase is accepted (usage was zero) but only trickles: one
    # entity runs, the second parks until tiny frees its own share
    assert len(admitted) == 1
    assert ctl.stats()["pending"] == 1
    with pytest.raises(OverloadError):  # a second QUERY is throttled
        ctl.admit_phase("t2", [_E("t2")], 0, first_phase=True,
                        tenant="tiny")
    drained = ctl.note_done(admitted[0])
    assert len(drained) == 1           # usage hit zero → parked ent runs
    ctl.note_done(drained[0])
    # fully drained → the next phase lands again
    assert len(ctl.admit_phase("t3", [_E("t3")], 0, first_phase=True,
                               tenant="tiny")) == 1


def test_queue_drain_skips_overcap_tenant_and_repushes():
    """Under "queue", an over-share tenant's parked entities are
    skipped (not dropped) by the drain while another tenant's work
    behind them proceeds; they drain once the tenant frees its own
    share."""
    ctl = AdmissionController(max_inflight=4, policy="queue",
                              tenant_weights={"a": 1.0, "b": 1.0})
    # tenant a parks 4; share is 2, so only 2 drain
    got = ctl.admit_phase("qa", [_E("qa") for _ in range(4)], 0,
                          first_phase=True, tenant="a")
    assert len(got) == 2
    st = ctl.stats()
    assert st["pending"] == 2
    assert st["tenants"]["a"]["used_units"] == pytest.approx(2.0)
    # tenant b's later arrival jumps the blocked a-entities
    got_b = ctl.admit_phase("qb", [_E("qb")], 0, first_phase=True,
                            tenant="b")
    assert len(got_b) == 1
    # a completes one → exactly one parked a-entity drains
    drained = ctl.note_done(got[0])
    assert len(drained) == 1 and drained[0].query_id == "qa"
    assert ctl.stats()["pending"] == 1
    # drop the rest: ledger zeroes including per-tenant units
    ctl.drop_query("qa")
    ctl.drop_query("qb")
    st = ctl.stats()
    assert (st["inflight"], st["pending"], st["reserved"]) == (0, 0, 0)
    assert st["tenants"]["a"]["used_units"] == 0.0
    assert st["tenants"]["b"]["used_units"] == 0.0


def test_admission_v2_knobs_validated():
    with pytest.raises(ValueError):
        AdmissionController(max_inflight=4, policy="shed",
                            tenant_weights={})
    with pytest.raises(ValueError):
        AdmissionController(max_inflight=4, policy="shed",
                            tenant_weights={"a": 0.0})
    with pytest.raises(ValueError):
        AdmissionController(max_inflight=4, policy="shed",
                            cost_aware=True)          # no budget
    with pytest.raises(ValueError):
        AdmissionController(max_inflight=4, policy="shed",
                            cost_cap_s=1.0)           # budget unused
    with pytest.raises(ValueError):
        VDMSAsyncEngine(admission_tenants={"a": 1.0})
    with pytest.raises(ValueError):
        VDMSAsyncEngine(admission_cost_aware=True,
                        admission_cost_cap_s=1.0)


def test_tenant_quota_end_to_end_over_engine():
    """submit(tenant=) threads through session → launch → controller,
    and the default empty tenant stays byte-identically exempt."""
    eng = VDMSAsyncEngine(**dict(DET, transport=SLOW), admission="shed",
                          max_inflight_entities=8,
                          admission_tenants={"gold": 3.0, "bronze": 1.0})
    try:
        _fill(eng, n=4)
        q = _find(ops=({"type": "remote", "url": "u",
                        "options": {"id": "flip"}},))
        # bronze's first query (4 entities > its 2-slot share) lands via
        # anti-starvation and occupies the share...
        fut = eng.submit(q, tenant="bronze")
        time.sleep(0.05)
        # ...so its second query sheds with the tenant-tagged overload
        with pytest.raises(OverloadError) as ei:
            eng.submit(q, tenant="bronze")
        assert ei.value.tenant == "bronze"
        # while gold's untouched share still admits alongside
        gold = eng.submit(q, tenant="gold")
        assert len(gold.result(60)["entities"]) == 4
        assert len(fut.result(60)["entities"]) == 4
        # drained: per-tenant units returned to zero, the exempt
        # default lane was never subject to any of it
        assert len(eng.submit(q).result(60)["entities"]) == 4
        st = eng.admission_ctl.stats()
        assert st["tenants"]["bronze"]["used_units"] == 0.0
        assert st["tenants"]["gold"]["used_units"] == 0.0
    finally:
        eng.shutdown()


def test_frontend_close_joins_accept_thread():
    """Regression: close() alone did not wake the thread blocked in
    accept() (Linux close-vs-accept semantics), so every frontend
    teardown burned the full join timeout and leaked the accept thread.
    Shutting the listener down first must make close prompt and the
    thread joined."""
    eng = VDMSAsyncEngine(**DET)
    try:
        front = _serve(eng)
        time.sleep(0.05)          # let the accept loop block
        t0 = time.monotonic()
        front.close()
        took = time.monotonic() - t0
        assert not front._accept_thread.is_alive()
        assert took < 2.0, f"close() took {took:.1f}s (join timeout burn)"
    finally:
        eng.shutdown()
