"""Hot-query-path benchmarks: result cache + cross-session coalescing.

Tracks the perf trajectory of the two hot-path subsystems across PRs by
writing ``BENCH_hotpath.json`` at the repo root (uploaded as a CI
artifact on every push):

- ``hotpath_cache_repeat``:   repeated-pipeline workload; derived =
  cold-run wall over warm-run wall (full (eid, pipeline-signature) hits
  skip Queue_1 entirely).  Also asserts the cache-off response stays
  byte-identical to both cache-on runs.  The row's engine-lifetime
  ``hit_rate`` is exactly 0.5 by construction — the cold run misses
  every lookup (populating the cache) and the warm run hits every one,
  so the row also records the warm/cold split (``cold_misses`` /
  ``warm_hits`` / ``warm_hit_rate``) that the aggregate averages away.
- ``hotpath_coalesce``:       remote-op fan-out across concurrent
  sessions; derived = per-entity-dispatch wall over coalesced wall (one
  batched request per op signature per window, amortized via
  ``TransportModel.cost_batch``).

  PYTHONPATH=src python -m benchmarks.hotpath [--smoke | --full]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np

REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))

REMOTE_PIPE = [
    {"type": "resize", "width": 48, "height": 48},
    {"type": "remote", "url": "http://svc/box",
     "options": {"id": "facedetect_box"}},
    {"type": "threshold", "value": 0.4},
]


def _find(category="hot", ops=REMOTE_PIPE):
    return [{"FindImage": {"constraints": {"category": ["==", category]},
                           "operations": ops}}]


def _fill(eng, n, size, category="hot"):
    rng = np.random.default_rng(7)
    for i in range(n):
        img = rng.uniform(0, 1, (size, size, 3)).astype(np.float32)
        eng.add_entity("image", img, {"category": category, "idx": i})


def _entities_equal(a: dict, b: dict) -> bool:
    if list(a) != list(b):
        return False
    return all(np.array_equal(np.asarray(a[k]), np.asarray(b[k])) for k in a)


def run_cache(n_images=32, size=64):
    """Repeated-pipeline workload: cold populate vs warm full-hit run."""
    from repro.core.engine import VDMSAsyncEngine
    from repro.core.remote import TransportModel

    transport = TransportModel(network_latency_s=0.002, service_time_s=0.004)

    # reference: the engine exactly as it ships by default (cache off)
    ref_eng = VDMSAsyncEngine(num_remote_servers=2, transport=transport)
    try:
        _fill(ref_eng, n_images, size)
        ref_eng.execute(_find(), timeout=600)          # jit warmup
        t0 = time.monotonic()
        ref = ref_eng.execute(_find(), timeout=600)
        t_off = time.monotonic() - t0
    finally:
        ref_eng.shutdown()

    eng = VDMSAsyncEngine(num_remote_servers=2, transport=transport,
                          cache_capacity=4 * n_images + 64)
    try:
        _fill(eng, n_images, size)
        eng.execute(_find(), cache=False, timeout=600)  # jit warmup, no writes
        t0 = time.monotonic()
        cold = eng.execute(_find(), timeout=600)        # populates
        t_cold = time.monotonic() - t0
        stats_cold = eng.cache_stats()
        t0 = time.monotonic()
        warm = eng.execute(_find(), timeout=600)        # full hits
        t_warm = time.monotonic() - t0
        stats = eng.cache_stats()
    finally:
        eng.shutdown()
    warm_hits = stats["hits"] - stats_cold["hits"]
    warm_lookups = ((stats["hits"] + stats["prefix_hits"] + stats["misses"])
                    - (stats_cold["hits"] + stats_cold["prefix_hits"]
                       + stats_cold["misses"]))

    identical = (_entities_equal(ref["entities"], cold["entities"])
                 and _entities_equal(ref["entities"], warm["entities"]))
    return [{
        "name": "hotpath_cache_repeat",
        "us_per_call": t_warm / n_images * 1e6,
        "derived": t_cold / t_warm,
        "n_images": n_images,
        "cold_s": t_cold,
        "warm_s": t_warm,
        "cache_off_s": t_off,
        "entities_per_s_warm": n_images / t_warm,
        "full_hits": warm["stats"].get("cache_full_hits", 0),
        # engine-lifetime rate: 0.5 by construction (one all-miss cold
        # run + one all-hit warm run) — the split below is the signal
        "hit_rate": stats["hit_rate"],
        "cold_misses": stats_cold["misses"],
        "warm_hits": warm_hits,
        "warm_hit_rate": (warm_hits / warm_lookups if warm_lookups else 0.0),
        "identical_to_cache_off": identical,
    }]


def run_coalesce(fanout=32, sessions=2, size=48):
    """Per-entity remote dispatch vs cross-session coalescing at a
    fan-out of ``sessions * fanout`` remote ops.

    The regime is transport-bound (WAN-like 30 ms round trips): that is
    where amortizing the per-request latency via ``cost_batch`` pays.
    ``coalesce_max_batch`` stays well under the fan-out so batches still
    spread across servers — op compute inside a batch is serial, so
    unbounded batches would trade all server parallelism for latency
    amortization and lose in compute-bound regimes."""
    from repro.core.engine import VDMSAsyncEngine
    from repro.core.remote import TransportModel

    transport = TransportModel(network_latency_s=0.03,
                               service_time_s=0.0003)

    def wall(**kw):
        eng = VDMSAsyncEngine(num_remote_servers=2, transport=transport,
                              dispatch_policy="least_loaded", **kw)
        try:
            _fill(eng, fanout, size)
            eng.execute(_find(), timeout=600)          # jit warmup
            t0 = time.monotonic()
            futs = [eng.submit(_find()) for _ in range(sessions)]
            results = [f.result(timeout=600) for f in futs]
            dt = time.monotonic() - t0
            assert all(r["stats"]["failed"] == 0 for r in results)
            ref = results[0]["entities"]
            return dt, ref, eng.utilization()
        finally:
            eng.shutdown()

    t_per, ents_per, util_per = wall()
    t_co, ents_co, util_co = wall(coalesce_window_ms=5.0,
                                  coalesce_max_batch=16)
    return [{
        "name": f"hotpath_coalesce_f{fanout}x{sessions}",
        "us_per_call": t_co / (fanout * sessions) * 1e6,
        "derived": t_per / t_co,
        "fanout": fanout,
        "sessions": sessions,
        "per_entity_s": t_per,
        "coalesced_s": t_co,
        "entities_per_s_coalesced": fanout * sessions / t_co,
        "requests_per_entity": util_per["remote_dispatched"],
        "requests_coalesced": util_co["remote_dispatched"],
        "coalesced_batches": util_co["coalesced_batches"],
        "coalesced_entities": util_co["coalesced_entities"],
        "identical_to_per_entity": _entities_equal(ents_per, ents_co),
    }]


def run(smoke=True):
    """Run both hot-path suites and write repo-root BENCH_hotpath.json."""
    if smoke:
        rows = run_cache(n_images=24, size=48) + run_coalesce(fanout=32)
    else:
        rows = (run_cache(n_images=64, size=96)
                + run_coalesce(fanout=64, sessions=4))
    by_name = {r["name"]: r for r in rows}
    cache_row = by_name["hotpath_cache_repeat"]
    co_row = next(r for n, r in by_name.items() if n.startswith("hotpath_coalesce"))
    payload = {
        "smoke": smoke,
        "cache_speedup": cache_row["derived"],
        "coalesce_speedup": co_row["derived"],
        "entities_per_s_warm": cache_row["entities_per_s_warm"],
        "entities_per_s_coalesced": co_row["entities_per_s_coalesced"],
        "baseline_identical": (cache_row["identical_to_cache_off"]
                               and co_row["identical_to_per_entity"]),
        "rows": rows,
    }
    with open(os.path.join(REPO_ROOT, "BENCH_hotpath.json"), "w") as f:
        json.dump(payload, f, indent=2, default=float)
    return rows


def main():
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small sizes for CI (default unless --full)")
    ap.add_argument("--full", action="store_true")
    args = ap.parse_args()
    rows = run(smoke=not args.full)
    print("name,us_per_call,derived")
    for r in rows:
        print(f"{r['name']},{r['us_per_call']:.1f},{r['derived']:.4f}")


if __name__ == "__main__":
    main()
