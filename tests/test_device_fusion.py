"""Device-resident segment fusion: fused-vs-per-op-vs-native result
equivalence, boundary-granular prefix resume, the residency-priced
router DP, the bounded jit cache, padding-waste accounting,
multi-device spreading, and the fused preprocessing kernel."""
import queue
import threading
import time

import numpy as np
import pytest

from repro.core.engine import VDMSAsyncEngine
from repro.core.entity import Entity
from repro.core.pipeline import make_op
from repro.core.remote import TransportModel
from repro.core.result_cache import op_signature
from repro.query.admission import OverloadError
from repro.query.device_backend import (DeviceBackend, DeviceCostModel,
                                        MultiDeviceBackend)
from repro.query.dispatch import Backend, BackendRouter, OpCostTracker

FAST = TransportModel(network_latency_s=0.001, service_time_s=0.002)

# index/comparison ops only — bit-exact under any execution strategy,
# so fused / per-op / native responses compare byte-for-byte
EXACT_PIPE = [
    {"type": "crop", "x": 2, "y": 2, "width": 16, "height": 16},
    {"type": "rotate", "k": 1},
    {"type": "flip", "axis": "horizontal"},
    {"type": "threshold", "value": 0.5},
]

# the fused-preprocessing prefix + a float tail: compares allclose
PREPROCESS_PIPE = [
    {"type": "resize", "width": 20, "height": 24},
    {"type": "crop", "x": 2, "y": 3, "width": 12, "height": 10},
    {"type": "normalize", "mean": 0.4, "std": 0.25},
    {"type": "blur", "ksize": 3, "sigma_x": 1.0},
]

# pin every EXACT_PIPE op onto the device: the whole chain is one segment
ALL_DEVICE = {o["type"]: {"device": 1e-9, "native": 10.0, "remote": 10.0,
                          "batcher": 10.0}
              for o in EXACT_PIPE}
ALL_DEVICE_PRE = {o["type"]: {"device": 1e-9, "native": 10.0,
                              "remote": 10.0, "batcher": 10.0}
                  for o in PREPROCESS_PIPE}


def _mk_engine(**kw):
    kw.setdefault("num_remote_servers", 2)
    kw.setdefault("transport", FAST)
    return VDMSAsyncEngine(**kw)


def _add_images(eng, n=6, size=24, category="fuse", seed=5):
    rng = np.random.default_rng(seed)
    for i in range(n):
        img = rng.uniform(0, 1, (size, size, 3)).astype(np.float32)
        eng.add_entity("image", img, {"category": category, "idx": i})


def _find(category="fuse", ops=EXACT_PIPE):
    return [{"FindImage": {"constraints": {"category": ["==", category]},
                           "operations": ops}}]


def _entities(res):
    return {eid: np.asarray(v) for eid, v in res["entities"].items()}


# --------------------------------------------------- result equivalence
def test_fused_segment_matches_per_op_and_native_byte_identically():
    # the whole 4-op EXACT_PIPE runs as ONE fused device program; its
    # responses must be byte-identical to both the per-op device path
    # and the native engine (index/comparison ops are strategy-exact)
    eng_nat = _mk_engine()
    eng_per = _mk_engine(dispatch="cost", device_backend=True,
                         device_fuse_segments=False,
                         cost_overrides=ALL_DEVICE,
                         device_max_wait_ms=50.0)
    eng_fus = _mk_engine(dispatch="cost", device_backend=True,
                         cost_overrides=ALL_DEVICE,
                         device_max_wait_ms=50.0)
    try:
        for e in (eng_nat, eng_per, eng_fus):
            _add_images(e)
        r_nat = _entities(eng_nat.execute(_find(), timeout=60))
        r_per = _entities(eng_per.execute(_find(), timeout=60))
        res_f = eng_fus.execute(_find(), timeout=60)
        assert res_f["stats"]["failed"] == 0
        r_fus = _entities(res_f)
        assert list(r_nat) == list(r_per) == list(r_fus)
        for eid in r_nat:
            np.testing.assert_array_equal(r_nat[eid], r_per[eid])
            np.testing.assert_array_equal(r_nat[eid], r_fus[eid])
        d = eng_fus.dispatch_stats()["device"]
        # one reply per entity for the whole chain: 6 entities, 24 ops
        assert d["entities_run"] == 6
        assert d["ops_run"] == 24
        assert d["fused_segments"] >= 1
        # fusion collapses transfers: the per-op engine moved the
        # payload once per op, the fused engine once per segment
        assert d["h2d_bytes"] < eng_per.dispatch_stats()["device"]["h2d_bytes"]
    finally:
        eng_nat.shutdown()
        eng_per.shutdown()
        eng_fus.shutdown()


def test_fused_preprocess_chain_matches_native_allclose():
    # resize->crop->normalize hits the registered chain fast path (one
    # fused kernel launch inside the segment program); float ops compare
    # allclose against the native engine
    eng_nat = _mk_engine()
    eng_fus = _mk_engine(dispatch="cost", device_backend=True,
                         cost_overrides=ALL_DEVICE_PRE,
                         device_max_wait_ms=50.0)
    try:
        for e in (eng_nat, eng_fus):
            _add_images(e, size=32)
        r_nat = _entities(eng_nat.execute(
            _find(ops=PREPROCESS_PIPE), timeout=60))
        res_f = eng_fus.execute(_find(ops=PREPROCESS_PIPE), timeout=60)
        assert res_f["stats"]["failed"] == 0
        r_fus = _entities(res_f)
        for eid in r_nat:
            np.testing.assert_allclose(r_nat[eid], r_fus[eid],
                                       rtol=1e-5, atol=1e-5)
        assert eng_fus.dispatch_stats()["device"]["fused_segments"] >= 1
    finally:
        eng_nat.shutdown()
        eng_fus.shutdown()


# ------------------------------------------------ segment-grouped inbox
def test_run_groups_partitions_by_segment_and_advances_whole_run():
    # unit-level: two entities sharing a 2-op device segment fuse into
    # one group; one with a different segment runs separately — each
    # reply advances the whole segment
    replies: queue.Queue = queue.Queue()
    dev = DeviceBackend(calibrate=False, fuse_segments=True)
    dev._reply_to = replies
    ops2 = [make_op("rotate", {"k": 1}), make_op("flip",
                                                 {"axis": "horizontal"})]
    ops1 = [make_op("rotate", {"k": 3})]
    rng = np.random.default_rng(3)
    ents = []
    for i in range(2):
        e = Entity(eid=f"a{i}", kind="image",
                   data=rng.uniform(0, 1, (8, 8, 3)).astype(np.float32),
                   ops=list(ops2), query_id="q")
        e.route = ["device", "device"]
        ents.append(e)
    lone = Entity(eid="b0", kind="image",
                  data=rng.uniform(0, 1, (8, 8, 3)).astype(np.float32),
                  ops=list(ops1), query_id="q")
    lone.route = ["device"]
    dev._run_groups(ents + [lone])
    got = {}
    for _ in range(3):
        kind, ent, res, err, advance = replies.get(timeout=5)
        assert kind == "device" and err is None
        got[ent.eid] = (np.asarray(res), advance)
    for e in ents:
        res, advance = got[e.eid]
        assert advance == 2
        np.testing.assert_array_equal(
            res, np.rot90(np.asarray(e.data), k=1)[:, ::-1])
    res, advance = got["b0"]
    assert advance == 1
    np.testing.assert_array_equal(res, np.rot90(np.asarray(lone.data), k=3))
    assert dev.groups_run == 2
    assert dev.fused_segments == 1
    assert dev.ops_run == 5


# --------------------------------------------- prefix resume at boundary
def test_prefix_resume_enters_mid_pipeline_device_segment():
    # query A caches the 2-op prefix; query B's 4-op pipeline resumes at
    # the boundary snapshot and its remaining tail runs as a fresh fused
    # device segment — results must equal the native engine's full run
    pins = {o["type"]: {"device": 1e-9, "native": 10.0, "remote": 10.0,
                        "batcher": 10.0} for o in EXACT_PIPE[2:]}
    eng_nat = _mk_engine()
    eng = _mk_engine(dispatch="cost", device_backend=True,
                     cache_capacity=64, cost_overrides=pins,
                     device_max_wait_ms=50.0)
    try:
        _add_images(eng_nat, n=4)
        _add_images(eng, n=4)
        r_a = eng.execute(_find(ops=EXACT_PIPE[:2]), timeout=60)
        assert r_a["stats"]["failed"] == 0
        r_nat = _entities(eng_nat.execute(_find(), timeout=60))
        r_b = eng.execute(_find(), timeout=60)
        assert r_b["stats"]["failed"] == 0
        assert r_b["stats"]["cache_prefix_hits"] == 4
        got = _entities(r_b)
        for eid_n, eid_b in zip(r_nat, got):
            np.testing.assert_array_equal(r_nat[eid_n], got[eid_b])
        d = eng.dispatch_stats()["device"]
        assert d["fused_segments"] >= 1        # flip+threshold tail
    finally:
        eng_nat.shutdown()
        eng.shutdown()


def test_fused_snapshot_lands_at_segment_boundary_only():
    # with the whole chain fused, the only cache entries are the
    # segment-boundary snapshot (== the final result here): a repeat
    # query is a FULL hit, and no per-op intermediates were recorded
    eng = _mk_engine(dispatch="cost", device_backend=True,
                     cache_capacity=64, cost_overrides=ALL_DEVICE,
                     device_max_wait_ms=50.0)
    try:
        _add_images(eng, n=3)
        eng.execute(_find(), timeout=60)
        entries_after_first = eng.cache_stats()["size"]
        r2 = eng.execute(_find(), timeout=60)
        assert r2["stats"]["cache_full_hits"] == 3
        # one boundary snapshot per entity — not one per op
        assert entries_after_first == 3
    finally:
        eng.shutdown()


# ------------------------------------------------- cancellation drains
def test_cancel_mid_fused_batch_drains_and_releases_admission_slots():
    eng = _mk_engine(dispatch="cost", device_backend=True,
                     cost_overrides=ALL_DEVICE,
                     device_max_wait_ms=100.0,
                     admission="shed", max_inflight_entities=16)
    try:
        _add_images(eng, n=10)
        fut = eng.submit(_find())
        time.sleep(0.02)          # let entities reach the device inbox
        assert fut.cancel()
        deadline = time.monotonic() + 10
        while (eng.loop.queue1.qsize() or eng.device_backend.pending()
               or eng.admission_stats()["inflight"]) \
                and time.monotonic() < deadline:
            time.sleep(0.01)
        assert eng.device_backend.pending() == 0
        assert eng.admission_stats()["inflight"] == 0   # no leaked slots
        # the full capacity is available again: a query needing every
        # slot admits and completes
        res = eng.execute(_find(), timeout=60)
        assert res["stats"]["matched"] == 10
        assert res["stats"]["failed"] == 0
    finally:
        eng.shutdown()


# --------------------------------------------------- residency-priced DP
class _FixedBackend(Backend):
    def __init__(self, name, cost):
        self.name = name
        self.cost = cost

    def can_run(self, op):
        return True

    def estimate(self, op, payload_bytes):
        return self.cost

    def queue_depth(self):
        return 0


def _warm_device(tracker, ops, *, fuse):
    dev = DeviceBackend(
        calibrate=False, tracker=tracker, batch_size=8, max_wait_s=0.002,
        fuse_segments=fuse,
        cost_model=DeviceCostModel(h2d_bytes_s=1e9, d2h_bytes_s=1e9,
                                   dispatch_latency_s=1e-4,
                                   compile_default_s=0.05))
    for op in ops:
        dev._runs[op_signature(op)] = 500      # compile long amortized
        tracker.observe(op, 1e-4, kind="device")
    return dev


def test_fusion_flips_placement_the_per_op_model_gives_to_native():
    # 3-op chain, 8 MB payload: per-op device pricing pays the ~16 ms
    # transfer on EVERY op (3 x 17 ms > 3 x 10 ms native), so the
    # per-op model keeps the chain native.  Residency pricing charges
    # the transfer once and the marginal ops at pure compute — the
    # same chain flips onto the device.  No overrides: this is the
    # estimate path itself.
    ops = [make_op("rotate", {"k": 1}),
           make_op("flip", {"axis": "horizontal"}),
           make_op("threshold", {"value": 0.5})]
    pb = 8_000_000

    tracker = OpCostTracker()
    per_op = _warm_device(tracker, ops, fuse=False)
    router = BackendRouter([_FixedBackend("native", 0.01), per_op],
                           tracker=tracker)
    assert router.route(ops, payload_bytes=pb) == ["native"] * 3

    tracker2 = OpCostTracker()
    fused = _warm_device(tracker2, ops, fuse=True)
    router2 = BackendRouter([_FixedBackend("native", 0.01), fused],
                            tracker=tracker2)
    assert router2.route(ops, payload_bytes=pb) == ["device"] * 3


def test_estimate_resident_is_pure_marginal_compute():
    tracker = OpCostTracker()
    op = make_op("rotate", {"k": 1})
    dev = _warm_device(tracker, [op], fuse=True)
    assert dev.resident_capable
    # no wait, transfer, compile, or backlog terms: just the device EWMA
    assert dev.estimate_resident(op, 8_000_000) == pytest.approx(1e-4)
    assert dev.estimate(op, 8_000_000) > dev.estimate_resident(op, 8_000_000)
    dev_off = _warm_device(OpCostTracker(), [op], fuse=False)
    assert not dev_off.resident_capable


# ----------------------------------------------------- bounded jit cache
def test_jit_cache_is_lru_bounded_with_eviction_counter():
    dev = DeviceBackend(calibrate=False, jit_cache_cap=2)
    a, b, c = object(), object(), object()
    assert dev._jit_lookup("ka", lambda: a) is a
    assert dev._jit_lookup("kb", lambda: b) is b
    dev._compiled.add(("ka", (4, 8, 8, 3)))
    assert dev._jit_lookup("ka", lambda: object()) is a   # hit, touched
    assert dev._jit_lookup("kc", lambda: c) is c          # evicts kb (LRU)
    assert dev.jit_evictions == 1
    assert set(dev._jit_cache) == {"ka", "kc"}
    assert dev._jit_lookup("ka", lambda: object()) is a   # survived, MRU
    dev._jit_lookup("kd", lambda: object())               # evicts kc
    dev._jit_lookup("ke", lambda: object())               # evicts ka
    assert dev.jit_evictions == 3
    # evicting a key also drops its per-shape compile marks
    assert not any(ck[0] == "ka" for ck in dev._compiled)
    assert set(dev._jit_cache) == {"kd", "ke"}
    assert dev.stats()["jit_entries"] == 2
    assert dev.stats()["jit_evictions"] == 3


# -------------------------------------------------- padding accounting
def test_padding_waste_accounted_and_singletons_skip_padding():
    dev = DeviceBackend(calibrate=False)
    op = make_op("rotate", {"k": 1})
    rng = np.random.default_rng(7)

    def ent(i):
        return Entity(eid=f"p{i}", kind="image",
                      data=rng.uniform(0, 1, (8, 8, 3)).astype(np.float32),
                      ops=[op], query_id="q")

    # 3 entities pad to the 4-bucket: 1 padded row of 4 computed
    res, _ = dev._run_native_batch(op, [ent(i) for i in range(3)])
    assert len(res) == 3
    assert dev.stacked_rows == 3 and dev.pad_rows == 1
    assert dev.stats()["padding_waste_frac"] == pytest.approx(0.25)
    # a singleton group skips the bucket machinery entirely
    res, _ = dev._run_native_batch(op, [ent(9)])
    assert len(res) == 1
    assert dev.stacked_rows == 4 and dev.pad_rows == 1
    assert dev.stats()["padding_waste_frac"] == pytest.approx(0.2)


# -------------------------------------------------------- multi-device
def test_multi_device_engine_spreads_and_aggregates_stats():
    eng_nat = _mk_engine()
    eng = _mk_engine(dispatch="cost", device_backend=True,
                     num_device_workers=2, cost_overrides=ALL_DEVICE,
                     device_max_wait_ms=50.0)
    try:
        assert isinstance(eng.device_backend, MultiDeviceBackend)
        _add_images(eng_nat, n=8)
        _add_images(eng, n=8)
        r_nat = _entities(eng_nat.execute(_find(), timeout=60))
        res = eng.execute(_find(), timeout=60)
        assert res["stats"]["failed"] == 0
        got = _entities(res)
        for eid in r_nat:
            np.testing.assert_array_equal(r_nat[eid], got[eid])
        d = eng.dispatch_stats()["device"]
        assert len(d["per_device"]) == 2
        assert d["entities_run"] == 8
        assert d["entities_run"] == sum(p["entities_run"]
                                        for p in d["per_device"])
        assert d["ops_run"] == 32
        for key in ("groups_run", "compiles", "h2d_bytes",
                    "padding_waste_frac"):
            assert key in d["per_device"][0]
    finally:
        eng_nat.shutdown()
        eng.shutdown()


def test_multi_device_submit_prefers_least_backlogged_worker():
    replies: queue.Queue = queue.Queue()
    w0 = DeviceBackend(calibrate=False)
    w1 = DeviceBackend(calibrate=False)
    multi = MultiDeviceBackend([w0, w1])
    # no worker threads: submits just land in inboxes
    w0._reply_to = w1._reply_to = replies
    w0.ledger.add(5.0)                      # w0 heavily backlogged
    op = make_op("rotate", {"k": 1})
    ent = Entity(eid="m0", kind="image",
                 data=np.zeros((4, 4, 3), np.float32), ops=[op],
                 query_id="q")
    multi.submit(ent)
    assert w1.pending() == 1 and w0.pending() == 0
    assert multi.queue_depth() == 1
    multi.note_placed(op)                   # charges the cheapest worker
    assert w1.ledger.backlog_s() > 0


# ------------------------------------------------------ knob validation
def test_fusion_and_worker_knobs_require_device_backend():
    before = threading.active_count()
    with pytest.raises(ValueError, match="device_fuse_segments"):
        _mk_engine(dispatch="cost", device_fuse_segments=True)
    with pytest.raises(ValueError, match="device_fuse_segments"):
        _mk_engine(device_fuse_segments=False)
    with pytest.raises(ValueError, match="num_device_workers"):
        _mk_engine(dispatch="cost", num_device_workers=2)
    with pytest.raises(ValueError, match="num_device_workers"):
        _mk_engine(dispatch="cost", device_backend=True,
                   num_device_workers=0)
    assert threading.active_count() == before


# ----------------------------------------------- fused preprocess kernel
def test_fused_preprocess_ref_is_exactly_the_composed_ops():
    import jax
    from repro.kernels.ops import fused_preprocess
    from repro.visual.ops import crop, normalize, resize
    rng = np.random.default_rng(0)
    img = rng.uniform(0, 1, (3, 32, 28, 3)).astype(np.float32)
    kw = dict(resize_h=24, resize_w=20, crop_x=2, crop_y=3,
              crop_w=12, crop_h=10, mean=0.4, std=0.25)
    fused = np.asarray(fused_preprocess(img, impl="ref", **kw))

    def one(im):
        im = resize(im, width=20, height=24)
        im = crop(im, x=2, y=3, width=12, height=10)
        return normalize(im, mean=0.4, std=0.25)

    composed = np.asarray(jax.vmap(one)(img))
    np.testing.assert_array_equal(fused, composed)


def test_fused_preprocess_pallas_matches_ref_in_interpret_mode():
    from repro.kernels.ops import fused_preprocess
    rng = np.random.default_rng(1)
    img = rng.uniform(0, 1, (2, 32, 28, 3)).astype(np.float32)
    kw = dict(resize_h=24, resize_w=20, crop_x=2, crop_y=3,
              crop_w=12, crop_h=10, mean=0.4, std=0.25)
    ref = np.asarray(fused_preprocess(img, impl="ref", **kw))
    interp = np.asarray(fused_preprocess(img, impl="pallas_interpret", **kw))
    np.testing.assert_allclose(ref, interp, rtol=1e-5, atol=1e-5)
    # crop-window clamping matches dynamic_slice semantics: an
    # out-of-range window shrinks/clamps instead of erroring
    kw_oob = dict(kw, crop_x=18, crop_w=12)     # x+w > resized width
    ref2 = np.asarray(fused_preprocess(img, impl="ref", **kw_oob))
    interp2 = np.asarray(fused_preprocess(img, impl="pallas_interpret",
                                          **kw_oob))
    np.testing.assert_allclose(ref2, interp2, rtol=1e-5, atol=1e-5)


def test_fused_preprocess_jitted_pallas_matches_ref():
    """Under jit (as the device segment program calls it) the kernel's
    interpolation matrices are host constants, not tracers."""
    import jax
    from repro.kernels.ops import fused_preprocess
    from repro.kernels.preprocess import fused_resize_crop_normalize_ref
    rng = np.random.default_rng(2)
    img = rng.uniform(0, 1, (2, 32, 28, 3)).astype(np.float32)
    kw = dict(resize_h=24, resize_w=20, crop_x=2, crop_y=3,
              crop_w=12, crop_h=10, mean=0.4, std=0.25)
    got = jax.jit(lambda x: fused_preprocess(
        x, impl="pallas_interpret", **kw))(img)
    want = fused_resize_crop_normalize_ref(img, **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_fused_preprocess_pallas_at_lfw_shape():
    """250x250x3 faces -> resize 256 -> crop 224 at (16, 16) ->
    normalize: the shape the chip smoke runs."""
    from repro.kernels.ops import fused_preprocess
    from repro.kernels.preprocess import fused_resize_crop_normalize_ref
    rng = np.random.default_rng(3)
    img = rng.uniform(0, 1, (2, 250, 250, 3)).astype(np.float32)
    kw = dict(resize_h=256, resize_w=256, crop_x=16, crop_y=16,
              crop_w=224, crop_h=224, mean=0.5, std=0.25)
    got = fused_preprocess(img, impl="pallas_interpret", **kw)
    want = fused_resize_crop_normalize_ref(img, **kw)
    assert got.shape == (2, 224, 224, 3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
