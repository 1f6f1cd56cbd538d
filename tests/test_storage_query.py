"""Blob store (LRU + disk spill), query language parsing, data pipeline."""
import os
import tempfile

import numpy as np
import pytest

from repro.dataio import ShardedLoader, lm_token_stream, synthetic_faces
from repro.query.language import parse_query
from repro.storage.store import BlobStore, BlobStoreFull


def test_blobstore_roundtrip():
    s = BlobStore()
    a = np.random.default_rng(0).uniform(size=(8, 8, 3)).astype(np.float32)
    s.put("x", a)
    np.testing.assert_array_equal(s.get("x"), a)
    assert "x" in s
    s.delete("x")
    assert "x" not in s
    with pytest.raises(KeyError):
        s.get("x")


def test_blobstore_spills_to_disk_and_reloads():
    with tempfile.TemporaryDirectory() as d:
        s = BlobStore(capacity_bytes=4096, spill_dir=d)
        arrs = {f"k{i}": np.full((16, 16), i, np.float32) for i in range(8)}
        for k, a in arrs.items():
            s.put(k, a)
        assert s.spills > 0
        for k, a in arrs.items():  # everything still retrievable
            np.testing.assert_array_equal(s.get(k), a)


def test_blobstore_without_spill_refuses_put_past_capacity():
    """No spill directory: a put past the cap fails loudly and every
    acknowledged blob stays readable (nothing is evicted)."""
    s = BlobStore(capacity_bytes=4096)
    arrs = {f"k{i}": np.full((16, 16), i, np.float32) for i in range(4)}
    for k, a in arrs.items():
        s.put(k, a)
    with pytest.raises(BlobStoreFull):
        s.put("k4", np.zeros((16, 16), np.float32))
    assert "k4" not in s
    for k, a in arrs.items():
        np.testing.assert_array_equal(s.get(k), a)
    s.put("k0", np.ones((16, 16), np.float32))   # same-size overwrite fits
    np.testing.assert_array_equal(s.get("k0"), 1.0)


def test_parse_query_validates():
    cmds = parse_query([{"FindImage": {
        "constraints": {"a": ["==", 1]},
        "operations": [{"type": "resize", "width": 4, "height": 4},
                       {"type": "remote", "url": "u",
                        "options": {"id": "blur", "ksize": 3}},
                       {"type": "udf", "port": 1, "options": {"id": "f"}}]}}])
    assert cmds[0].verb == "find" and cmds[0].kind == "image"
    ops = cmds[0].operations
    assert [o.where for o in ops] == ["native", "remote", "udf"]
    assert ops[1].kwargs == {"ksize": 3}
    with pytest.raises(ValueError):
        parse_query([{"Nope": {}}])
    with pytest.raises(ValueError):
        parse_query([{"FindImage": {}, "FindVideo": {}}])


def test_lm_token_stream_deterministic_and_in_range():
    a = lm_token_stream(4, 32, 1000, step=7)
    b = lm_token_stream(4, 32, 1000, step=7)
    np.testing.assert_array_equal(a, b)
    c = lm_token_stream(4, 32, 1000, step=8)
    assert not np.array_equal(a, c)
    assert a.min() >= 0 and a.max() < 1000


def test_synthetic_faces_deterministic():
    a = synthetic_faces(2, size=32, seed=5)
    b = synthetic_faces(2, size=32, seed=5)
    np.testing.assert_array_equal(a, b)
    assert a.shape == (2, 32, 32, 3)
    assert 0 <= a.min() and a.max() <= 1


def test_sharded_loader_prefetch_order():
    seen = []

    def make(step):
        seen.append(step)
        return {"x": np.full((2,), step, np.int32)}

    loader = ShardedLoader(make, prefetch=2, start_step=3)
    out = [next(loader) for _ in range(4)]
    loader.stop()
    assert [s for s, _ in out] == [3, 4, 5, 6]
    for s, b in out:
        assert b["x"][0] == s
