"""Seeded generation of the collection and the traffic."""
from __future__ import annotations

import collections
import itertools
import json
import os

import numpy as np
import pytest

import bench_tiny  # noqa: F401 — puts the repository on sys.path
from bench import faces, traffic
from bench.harness import BENCH

BIG_SEED = 2**31 + 123_456


def _load(*path):
    return json.load(open(os.path.join(BENCH, *path)))


IQ_MIX = _load("traffic", "iq_mix.json")
TRAIN_FEED = _load("traffic", "train_feed.json")
COLLECTION = _load("configs", "lfw_device.json")["collection"]


def _take(seed, client, n, mix=IQ_MIX):
    return list(itertools.islice(
        traffic.stream(mix, COLLECTION, seed, client), n))


def test_same_seed_same_queries_and_another_seed_another_order():
    a = _take(BIG_SEED, 0, 120)
    assert a == _take(BIG_SEED, 0, 120)
    assert a != _take(BIG_SEED + 1, 0, 120)
    assert a != _take(BIG_SEED, 1, 120)


def _work(seed, client, n, mix=IQ_MIX):
    return [(q.template, q.age_hi - q.age_lo + 1)
            for q in _take(seed, client, n, mix)]


@pytest.mark.parametrize("mix", [IQ_MIX, TRAIN_FEED], ids=["iq", "feed"])
def test_every_seed_runs_the_same_mix_of_work(mix):
    """Every seed gives each client the same templates and window widths
    in the same order, and any T x W queries in a row of one client hold
    every (template, width) pair once."""
    lo, hi = mix["age_window"]
    per_cycle = len(mix["queries"]) * (hi - lo + 1)
    want = collections.Counter({(t, w): 1 for t in mix["queries"]
                                for w in range(lo, hi + 1)})
    for client in range(mix["clients"]):
        work = _work(0, client, 3 * per_cycle, mix)
        for seed in (7, BIG_SEED):
            assert _work(seed, client, 3 * per_cycle, mix) == work
        for start in (0, 1, per_cycle - 1):
            assert collections.Counter(
                work[start:start + per_cycle]) == want


def test_templates_interleave_within_and_across_clients():
    """Any T queries in a row of one client run every template once, and
    the clients at one step run different templates: every window holds
    the whole mix, whichever client is slow."""
    names = sorted(IQ_MIX["queries"])
    n = IQ_MIX["clients"]
    assert n <= len(names)
    streams = [[t for t, _ in _work(BIG_SEED, c, 3 * len(names))]
               for c in range(n)]
    for s in streams:
        for start in range(0, 2 * len(names)):
            assert sorted(s[start:start + len(names)]) == names
    for k in range(3 * len(names)):
        assert len({s[k] for s in streams}) == n


def test_an_unknown_loop_is_refused():
    with pytest.raises(ValueError):
        next(traffic.stream({**IQ_MIX, "loop": "open"}, COLLECTION, 1, 0))


def test_queries_stay_inside_the_collection():
    coll = COLLECTION
    for q in _take(BIG_SEED, 0, 300, TRAIN_FEED):
        assert coll["age_min"] <= q.age_lo <= q.age_hi <= coll["age_max"]
        assert q.category in coll["categories"]
        lo, hi = TRAIN_FEED["age_window"]
        assert lo <= q.age_hi - q.age_lo + 1 <= hi
        find = q.json()[0]["FindImage"]
        assert find["operations"] == TRAIN_FEED["queries"][q.template]


def test_checked_share_follows_the_mix():
    qs = _take(BIG_SEED, 0, 2000)
    share = sum(q.checked for q in qs) / len(qs)
    assert abs(share - 0.15) < 0.03


def test_faces_are_seeded_and_in_range():
    a = faces.generate(BIG_SEED, 5, 64)
    assert a.shape == (5, 64, 64, 3) and a.dtype == np.float32
    assert np.array_equal(a, faces.generate(BIG_SEED, 5, 64))
    assert not np.array_equal(a, faces.generate(BIG_SEED + 1, 5, 64))
    assert 0.0 <= a.min() and a.max() <= 1.0
    # the face: skin-toned pixels (red over green over blue) are present
    r, g, b = a[..., 0], a[..., 1], a[..., 2]
    assert ((r > g) & (g > b) & (r > 0.5)).mean() > 0.05


def test_every_seed_gives_the_same_metadata_histogram():
    cats = ["a", "b", "c", "d"]

    def hist(seed):
        rows = faces.properties(seed, 2048, cats, 18, 70)
        assert sorted(r["idx"] for r in rows) == list(range(2048))
        return collections.Counter((r["category"], r["age"]) for r in rows)
    h = hist(0)
    assert h == hist(BIG_SEED)
    assert set(h.values()) <= {9, 10}
    p0 = faces.properties(0, 2048, cats, 18, 70)
    p1 = faces.properties(BIG_SEED, 2048, cats, 18, 70)
    assert p0 != p1
    assert len(faces.select(p0, "a", 20, 24)) == \
        len(faces.select(p1, "a", 20, 24))
