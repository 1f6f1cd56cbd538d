"""Remote servers run each op as one cached compiled program per (op,
params, shape, dtype) where the input allows, and eagerly otherwise:
results equal to eager ``run_op``, one program per signature shared by
every server, the ``remote_compiled`` / ``remote_eager`` counts, and the
``remote_compiled_share`` reader."""
import importlib.util
import json
import os
import queue
import sys
import threading

import numpy as np
import pytest

from repro.core.engine import VDMSAsyncEngine
from repro.core.entity import Entity
from repro.core.pipeline import (_fused_chain, compilable, make_op,
                                 parse_operations, run_compiled, run_op)
from repro.core.remote import RemoteServerPool, TransportModel
from repro.core.udf import register_udf

ROOT = os.path.join(os.path.dirname(__file__), "..")
IQ_MIX = json.load(open(os.path.join(ROOT, "bench", "traffic",
                                     "iq_mix.json")))
NO_WAIT = TransportModel(network_latency_s=0.0, service_time_s=0.0)
EXACT = {"crop", "caption", "facedetect_box", "facedetect_mask",
         "manipulation"}


def _remote_cases():
    """(case id, op, input shape) for every remote op of the mix; the
    Fig-8 chain's box sees the resized 500x400 image."""
    cases = []
    for name, ops in sorted(IQ_MIX["queries"].items()):
        shape = (250, 250, 3)
        for op in parse_operations(ops):
            if op.where == "remote":
                cases.append(pytest.param(op, shape, id=f"{name}-{op.name}"))
            elif op.name == "resize":
                shape = (op.kwargs["height"], op.kwargs["width"], 3)
    return cases


def _counts(pool):
    c = pool.tracer.stats()["counts"]
    return c["remote_compiled"], c["remote_eager"]


def _serve(pool, ents, op, batched=False):
    """Dispatch ``ents`` (one request each, or one batched request) and
    return the results by eid."""
    reply: queue.Queue = queue.Queue()
    if batched:
        pool.dispatch(list(ents), op, reply)
    else:
        for e in ents:
            pool.dispatch(e, op, reply)
    out = {}
    for _ in range(1 if batched else len(ents)):
        tag, req, payload = reply.get(timeout=120)
        status, result = pool.handle_response(tag, req, payload)
        assert status == "done", (status, result)
        if batched:
            out.update({e.eid: r for e, r in zip(req.entity, result)})
        else:
            out[req.entity.eid] = result
    return out


def _images(n, shape, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.uniform(0, 1, shape).astype(np.float32) for _ in range(n)]


@pytest.mark.parametrize("op,shape", _remote_cases())
def test_every_remote_op_of_the_mix_matches_eager(op, shape):
    pool = RemoteServerPool(2, NO_WAIT)
    try:
        imgs = _images(2, shape)
        assert all(compilable(op, im) for im in imgs)
        ents = [Entity(str(i), "image", im) for i, im in enumerate(imgs)]
        got = _serve(pool, ents, op)
        assert _counts(pool) == (2, 0)
    finally:
        pool.shutdown()
    for e, im in zip(ents, imgs):
        want = np.asarray(run_op(op, im))
        out = np.asarray(got[e.eid])
        assert out.dtype == np.float32 and out.shape == want.shape
        if op.name in EXACT:
            np.testing.assert_array_equal(out, want)
        else:
            np.testing.assert_allclose(out, want, rtol=0, atol=1e-6)


def test_two_servers_share_one_program_per_signature():
    # a shape no other test uses, so the first request is a cache miss
    op = make_op("grayscale", where="remote")
    imgs = _images(40, (29, 31, 3), seed=1)
    ents = [Entity(str(i), "image", im) for i, im in enumerate(imgs)]
    pool = RemoteServerPool(2, NO_WAIT)
    try:
        info0 = _fused_chain.cache_info()
        got = _serve(pool, ents, op)
        info1 = _fused_chain.cache_info()
        assert [s.processed for s in pool.servers] == [20, 20]
        assert _counts(pool) == (40, 0)
    finally:
        pool.shutdown()
    assert info1.misses - info0.misses == 1
    assert info1.hits - info0.hits == 39
    for e, im in zip(ents, imgs):
        np.testing.assert_array_equal(np.asarray(got[e.eid]),
                                      np.asarray(run_op(op, im)))


def test_concurrent_first_misses_build_one_program():
    # more threads than cores, switching every microsecond: threads that
    # miss at once must still leave one program and one miss
    op = make_op("threshold", {"value": 0.37}, where="remote")
    img = _images(1, (23, 19, 3), seed=6)[0]
    want = np.asarray(run_op(op, img))
    n = 2 * (os.cpu_count() or 2) + 2
    start = threading.Barrier(n)
    outs = [None] * n

    def worker(i):
        start.wait(timeout=60)
        outs[i] = np.asarray(run_compiled(op, img))

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        info0 = _fused_chain.cache_info()
        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        info1 = _fused_chain.cache_info()
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    assert info1.misses - info0.misses == 1
    assert info1.hits - info0.hits == n - 1
    for out in outs:
        np.testing.assert_array_equal(out, want)


def _double(img, **_):
    return np.asarray(img) * 2.0


register_udf("rc_user_double", _double)
IMG = _images(1, (24, 24, 3), seed=2)[0]
VIDEO = np.stack(_images(3, (16, 16, 3), seed=3))


@pytest.mark.parametrize("op,data", [
    pytest.param(make_op("rc_user_double", {"k": 1}, where="remote"), IMG,
                 id="user_udf"),
    pytest.param(make_op("activityrecognition", where="remote"), IMG,
                 id="activityrecognition"),
    pytest.param(make_op("grayscale", where="remote"), VIDEO, id="video"),
    pytest.param(make_op("box", {"x": 2, "y": 3, "width": 10, "height": 8,
                                 "color": [1.0, 0.0, 0.0]}, where="remote"),
                 IMG, id="list_param"),
])
def test_untraceable_ops_run_eagerly(op, data):
    assert not compilable(op, data)
    pool = RemoteServerPool(2, NO_WAIT)
    try:
        ents = [Entity(str(i), "image", data) for i in range(2)]
        got = _serve(pool, ents, op)
        assert _counts(pool) == (0, 2)
    finally:
        pool.shutdown()
    want = np.asarray(run_op(op, data))
    for e in ents:
        np.testing.assert_array_equal(np.asarray(got[e.eid]), want)


def test_batched_request_equals_the_per_entity_path():
    op = make_op("blur", {"ksize": 5, "sigma_x": 1.5}, where="remote")
    imgs = _images(5, (32, 40, 3), seed=4)
    pool = RemoteServerPool(2, NO_WAIT)
    try:
        single = _serve(pool, [Entity(str(i), "image", im)
                               for i, im in enumerate(imgs)], op)
        batch = _serve(pool, [Entity(str(i), "image", im)
                              for i, im in enumerate(imgs)], op, batched=True)
        assert _counts(pool) == (10, 0)
    finally:
        pool.shutdown()
    assert sorted(batch) == sorted(single)
    for eid, r in single.items():
        np.testing.assert_array_equal(np.asarray(batch[eid]), np.asarray(r))


def test_execute_ops_off_returns_the_data_untouched():
    op = make_op("grayscale", where="remote")
    pool = RemoteServerPool(1, TransportModel(network_latency_s=0.0,
                                              execute_ops=False))
    try:
        got = _serve(pool, [Entity("a", "image", IMG)], op)
        assert _counts(pool) == (0, 0)
    finally:
        pool.shutdown()
    assert got["a"] is IMG


def test_engine_reports_the_counts_in_its_trace():
    n = 6
    eng = VDMSAsyncEngine(num_remote_servers=2, transport=NO_WAIT,
                          num_native_workers=2)
    try:
        for i, im in enumerate(_images(n, (24, 24, 3), seed=5)):
            eng.add_entity("image", im, {"category": "rc", "i": i})
        q = [{"FindImage": {"constraints": {"category": ["==", "rc"]},
                            "operations": [
                                {"type": "remote", "url": "u",
                                 "options": {"id": "grayscale"}}]}}]
        c0 = eng.utilization()["trace"]["counts"]
        res = eng.execute(q, timeout=60)
        c1 = eng.utilization()["trace"]["counts"]
        processed = eng.utilization()["remote_processed"]
    finally:
        eng.shutdown()
    assert res["stats"]["failed"] == 0 and len(res["entities"]) == n
    # a straggler's reissue runs on a second server and counts again
    assert c1["remote_compiled"] - c0["remote_compiled"] == processed >= n
    assert c1["remote_eager"] == c0["remote_eager"]


def _reader():
    path = os.path.join(ROOT, "bench", "metrics", "remote_compiled_share.py")
    spec = importlib.util.spec_from_file_location("remote_compiled_share",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class _Readings:
    def __init__(self, deltas, trace=True):
        self.trace = {"busy_s": 0.1, "window_s": 3.0} if trace else None
        self._deltas = deltas

    def delta(self, key, span="window"):
        return self._deltas.get(key)


@pytest.mark.parametrize("deltas,trace,want", [
    ({"compiled": 300, "eager": 100}, True, 75.0),
    ({"compiled": 40, "eager": 0}, True, 100.0),
    ({"compiled": 40, "eager": 0}, False, None),    # no device trace
    ({"compiled": 0, "eager": 0}, True, None),      # denominator still
    ({}, True, None),                               # no such counts
])
def test_remote_compiled_share_reader(deltas, trace, want):
    keys = {f"util.trace.counts.remote_{k}": v for k, v in deltas.items()}
    got = _reader()(_Readings(keys, trace))
    assert got == (pytest.approx(want) if want is not None else None)
