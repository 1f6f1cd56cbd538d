"""The paper-faithful response, pinned.

Two bit-exact workloads (crop, flip and rotate permute indices; threshold
compares untouched values: no arithmetic, so the bytes are the same on
every platform and jax version) run through the engine as it ships, with
a knob spelled out at its paper value, through a one-shard cluster and
through the wire front end. Each response is hashed (eid, shape, dtype,
bytes, in response order) and compared with a literal sha256 recorded
from the paper-faithful engine. A different hash is a change of the
engine's behaviour, not of this file.
"""
import hashlib

import numpy as np
import pytest

from repro.cluster import ShardedEngine
from repro.core.engine import VDMSAsyncEngine
from repro.core.remote import TransportModel
from repro.serving.frontend import WireClient, WireFrontend

STATIC_SHA256 = \
    "778564da3d5f5530f0f4761d6af9f4c901796a91ff38620f2b75dd8cfa03a1b0"
ADMISSION_SHA256 = \
    "f9acbed1c4ab5c567940180df17d50602bac3a1d022246822db92bb8c8095f25"

# (category, fill seed, face size, crop, rotate k)
_STATIC = ("dsp", 11, 32, (4, 4, 24, 24), 1)
_ADMISSION = ("adm", 23, 28, (2, 2, 20, 20), 3)


def _query(category, crop, k):
    x, y, w, h = crop
    pipe = [
        {"type": "crop", "x": x, "y": y, "width": w, "height": h},
        {"type": "remote", "url": "http://svc/flip",
         "options": {"id": "flip"}},
        {"type": "rotate", "k": k},
        {"type": "threshold", "value": 0.5},
    ]
    return [{"FindImage": {"constraints": {"category": ["==", category]},
                           "operations": pipe}}]


def _fill(eng, category, seed, size, n=8):
    rng = np.random.default_rng(seed)
    for i in range(n):
        img = rng.uniform(0, 1, (size, size, 3)).astype(np.float32)
        eng.add_entity("image", img, {"category": category, "idx": i})


def _sha256(entities: dict) -> str:
    h = hashlib.sha256()
    for eid in entities:
        arr = np.ascontiguousarray(np.asarray(entities[eid]))
        h.update(eid.encode())
        h.update(str(arr.shape).encode())
        h.update(str(arr.dtype).encode())
        h.update(arr.tobytes())
    return h.hexdigest()


def _wire_execute(eng, query):
    front = WireFrontend(eng).start()
    try:
        with WireClient(front.address) as client:
            return client.execute(query, timeout=600)
    finally:
        front.close()


_TRANSPORT = dict(num_remote_servers=2,
                  transport=TransportModel(network_latency_s=0.001,
                                           service_time_s=0.001))


@pytest.mark.parametrize("workload,make,execute,expected", [
    (_STATIC, lambda: VDMSAsyncEngine(**_TRANSPORT), None, STATIC_SHA256),
    (_STATIC, lambda: VDMSAsyncEngine(dispatch="static", **_TRANSPORT),
     None, STATIC_SHA256),
    (_STATIC, lambda: ShardedEngine(num_shards=1, replica_factor=1,
                                    **_TRANSPORT), None, STATIC_SHA256),
    (_STATIC, lambda: VDMSAsyncEngine(**_TRANSPORT), _wire_execute,
     STATIC_SHA256),
    (_ADMISSION, lambda: VDMSAsyncEngine(**_TRANSPORT), None,
     ADMISSION_SHA256),
    (_ADMISSION, lambda: VDMSAsyncEngine(admission="queue",
                                         max_inflight_entities=4,
                                         **_TRANSPORT), None,
     ADMISSION_SHA256),
], ids=["default", "static", "one_shard", "wire", "admission_none",
        "admission_queue"])
def test_response_matches_pinned_hash(workload, make, execute, expected):
    category, seed, size, crop, k = workload
    query = _query(category, crop, k)
    eng = make()
    try:
        _fill(eng, category, seed, size)
        if execute is None:
            res = eng.execute(query, timeout=600)
        else:
            res = execute(eng, query)
    finally:
        eng.shutdown()
    assert len(res["entities"]) == 8
    assert _sha256(res["entities"]) == expected
