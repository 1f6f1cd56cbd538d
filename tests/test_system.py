"""End-to-end behaviour tests for the VDMS-Async engine (the paper's
system): query execution, pipeline ordering, multi-client concurrency,
fault tolerance, and architecture-comparison invariants."""
import threading
import time

import numpy as np
import pytest

from repro.core.engine import VDMSAsyncEngine
from repro.core.entity import Entity, ERD
from repro.core.executors import FrameExecutor, PooledExecutor, SyncExecutor
from repro.core.pipeline import make_op
from repro.core.remote import RemoteServerPool, TransportModel

FAST = TransportModel(network_latency_s=0.001, service_time_s=0.002)


def _mk_engine(**kw):
    kw.setdefault("num_remote_servers", 2)
    kw.setdefault("transport", FAST)
    return VDMSAsyncEngine(**kw)


def _add_images(eng, n=10, size=32):
    rng = np.random.default_rng(0)
    ids = []
    for i in range(n):
        img = rng.uniform(0, 1, (size, size, 3)).astype(np.float32)
        ids.append(eng.add_entity("image", img, {
            "category": "lfw", "name": f"p{i}", "age": 20 + i}))
    return ids


PIPE = [
    {"type": "resize", "width": 24, "height": 24},
    {"type": "remote", "url": "http://s/box", "options": {"id": "facedetect_box"}},
    {"type": "threshold", "value": 0.4},
]


def test_query_returns_all_matching_entities():
    eng = _mk_engine()
    try:
        _add_images(eng, 10)
        res = eng.execute([{"FindImage": {
            "constraints": {"category": ["==", "lfw"]},
            "operations": PIPE}}], timeout=60)
        assert res["stats"]["matched"] == 10
        assert res["stats"]["failed"] == 0
        assert len(res["entities"]) == 10
        for arr in res["entities"].values():
            assert np.asarray(arr).shape == (24, 24, 3)
            # threshold output is binary
            vals = np.unique(np.asarray(arr).round(3))
            assert set(vals).issubset({0.0, 1.0})
    finally:
        eng.shutdown()


def test_constraint_filtering():
    eng = _mk_engine()
    try:
        _add_images(eng, 10)
        res = eng.execute([{"FindImage": {
            "constraints": {"age": [">=", 25, "<", 28]},
            "operations": [{"type": "grayscale"}]}}], timeout=30)
        assert res["stats"]["matched"] == 3  # ages 25,26,27
    finally:
        eng.shutdown()


def test_pipeline_order_preserved():
    """resize->crop != crop->resize; engine must respect user order."""
    eng = _mk_engine()
    try:
        rng = np.random.default_rng(1)
        img = rng.uniform(0, 1, (40, 40, 3)).astype(np.float32)
        eng.add_entity("image", img, {"category": "x"})
        r1 = eng.execute([{"FindImage": {
            "constraints": {"category": ["==", "x"]},
            "operations": [{"type": "resize", "width": 20, "height": 20},
                           {"type": "crop", "x": 0, "y": 0,
                            "width": 10, "height": 10}]}}], timeout=30)
        (arr1,) = list(r1["entities"].values())
        assert np.asarray(arr1).shape == (10, 10, 3)
    finally:
        eng.shutdown()


def test_multi_client_concurrent_queries():
    eng = _mk_engine(num_remote_servers=4)
    try:
        _add_images(eng, 12)
        results = {}

        def client(cid):
            results[cid] = eng.execute([{"FindImage": {
                "constraints": {"category": ["==", "lfw"]},
                "operations": PIPE}}], timeout=120)

        threads = [threading.Thread(target=client, args=(i,)) for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert len(results) == 4
        for r in results.values():
            assert r["stats"]["matched"] == 12
            assert r["stats"]["failed"] == 0
    finally:
        eng.shutdown()


def test_failure_retry_and_elastic_scale():
    # a transport slow enough that the query is still in flight when
    # server 0 dies 20 ms in (8 requests over 3 servers, ~10 ms each)
    eng = _mk_engine(num_remote_servers=3, transport=TransportModel(
        network_latency_s=0.001, service_time_s=0.01))
    try:
        _add_images(eng, 8)

        def killer():
            time.sleep(0.02)
            eng.pool.kill_server(0)

        kill = threading.Thread(target=killer)
        kill.start()
        res = eng.execute([{"FindImage": {
            "constraints": {"category": ["==", "lfw"]},
            "operations": PIPE}}], timeout=120)
        kill.join()
        assert res["stats"]["failed"] == 0
        assert eng.pool.live_count() == 2
        eng.scale_remote(5)
        assert eng.pool.live_count() == 5
        res2 = eng.execute([{"FindImage": {
            "constraints": {"category": ["==", "lfw"]},
            "operations": PIPE}}], timeout=120)
        assert res2["stats"]["failed"] == 0
    finally:
        eng.shutdown()


def test_async_matches_sync_results():
    """The event-driven engine must produce byte-identical results to the
    synchronous VDMS baseline."""
    pool = RemoteServerPool(2, FAST)
    rng = np.random.default_rng(2)
    imgs = [rng.uniform(0, 1, (32, 32, 3)).astype(np.float32) for _ in range(6)]
    ops = [make_op("resize", {"width": 24, "height": 24}),
           make_op("facedetect_box", {}, where="remote"),
           make_op("grayscale")]

    sync_ents = [Entity(str(i), "image", img.copy(), ops=list(ops))
                 for i, img in enumerate(imgs)]
    SyncExecutor(pool).run(sync_ents)

    eng = _mk_engine(num_remote_servers=2)
    try:
        for i, img in enumerate(imgs):
            eng.add_entity("image", img, {"category": "c", "idx": i})
        res = eng.execute([{"FindImage": {
            "constraints": {"category": ["==", "c"]},
            "operations": [
                {"type": "resize", "width": 24, "height": 24},
                {"type": "remote", "url": "u", "options": {"id": "facedetect_box"}},
                {"type": "grayscale"}]}}], timeout=60)
        by_idx = {eng.meta.get(eid)["idx"]: arr
                  for eid, arr in res["entities"].items()}
        for i, ent in enumerate(sync_ents):
            np.testing.assert_allclose(np.asarray(by_idx[i]),
                                       np.asarray(ent.data), atol=1e-6)
    finally:
        eng.shutdown()
        pool.shutdown()


def test_fused_pipeline_matches_unfused():
    eng_f = _mk_engine(fuse_native=True)
    eng_u = _mk_engine(fuse_native=False)
    try:
        rng = np.random.default_rng(3)
        img = rng.uniform(0, 1, (32, 32, 3)).astype(np.float32)
        q = [{"FindImage": {"constraints": {"category": ["==", "z"]},
                            "operations": [
                                {"type": "resize", "width": 16, "height": 16},
                                {"type": "grayscale"},
                                {"type": "threshold", "value": 0.5}]}}]
        eng_f.add_entity("image", img, {"category": "z"})
        eng_u.add_entity("image", img, {"category": "z"})
        (a,) = list(eng_f.execute(q, timeout=30)["entities"].values())
        (b,) = list(eng_u.execute(q, timeout=30)["entities"].values())
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)
    finally:
        eng_f.shutdown()
        eng_u.shutdown()


def test_video_pipeline_executors_agree():
    pool = RemoteServerPool(2, FAST)
    rng = np.random.default_rng(4)
    vid = rng.uniform(0, 1, (4, 24, 24, 3)).astype(np.float32)
    ops = [make_op("grayscale"), make_op("threshold", {"value": 0.5})]
    e1 = Entity("v1", "video", vid.copy(), ops=list(ops))
    e2 = Entity("v2", "video", vid.copy(), ops=list(ops))
    SyncExecutor(pool).run([e1])
    FrameExecutor(pool, workers=2).run([e2])
    np.testing.assert_allclose(np.asarray(e1.data), np.asarray(e2.data),
                               atol=1e-6)
    pool.shutdown()


def test_add_image_with_operations():
    eng = _mk_engine()
    try:
        rng = np.random.default_rng(5)
        img = rng.uniform(0, 1, (30, 30, 3)).astype(np.float32)
        res = eng.execute([{"AddImage": {
            "properties": {"category": "new"},
            "data": img,
            "operations": [{"type": "resize", "width": 10, "height": 10}]}}],
            timeout=30)
        (arr,) = list(res["entities"].values())
        assert np.asarray(arr).shape == (10, 10, 3)
        # stored entity is the processed one
        found = eng.execute([{"FindImage": {
            "constraints": {"category": ["==", "new"]}, "operations": []}}],
            timeout=30)
        (arr2,) = list(found["entities"].values())
        assert np.asarray(arr2).shape == (10, 10, 3)
    finally:
        eng.shutdown()
