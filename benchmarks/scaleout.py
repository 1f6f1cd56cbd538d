"""Scale-out benchmarks: the paper's kappa remote-server curve (Fig 29)
plus the sharded-cluster shard-count curve.

Writes repo-root ``BENCH_scaleout.json`` (uploaded as a CI artifact on
every push):

- ``scaleout_shardsN``: a fixed engine-bound workload (every entity
  costs one service-time slot on its shard's single remote server;
  ``execute_ops=False`` so the capacity is simulated with GIL-releasing
  sleeps and N shards genuinely serve in parallel on a 2-core CI box)
  run against a ``ShardedEngine`` at 1..8 shards.  Sharding partitions
  the entities across shards, so T(N) ~ T(1)/N up to ring imbalance and
  scatter/gather overhead.  ``derived`` is the linear-scaling
  efficiency ``(T(1)/T(N)) / N``.  Gates (``--check-baseline``):

    * efficiency at 4 shards >= ``EFFICIENCY_GATE`` (0.7);
    * the speedup curve is monotone: each shard count's gain is no
      worse than ``MONOTONE_SLACK`` x the previous count's gain.

- ``scaleout_shard_identity``: the shard-off tripwire.  The bit-exact
  ``dispatch_static_hash`` workload (index-permutation + comparison ops
  only) run through a **1-shard, replica_factor=1** ``ShardedEngine``
  with every cluster knob at its default: the response hash must match
  the recorded ``benchmarks/dispatch_static_baseline.json`` — the whole
  ring/scatter/gather/failover layer must be byte-invisible until a
  second shard exists.  ``--check-baseline`` fails CLOSED when the
  baseline file is missing.

- ``scaleout_kN``: the original kappa remote-server curve (paper
  Fig 29): one engine, kappa remote servers, T(1)/T(kappa) should grow
  linearly in kappa.  Reported, not gated (it predates the cluster
  layer and its slope is a property of the transport model).

  PYTHONPATH=src python -m benchmarks.scaleout
      [--smoke|--full] [--check-baseline]
"""
from __future__ import annotations

import argparse
import hashlib
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np

from benchmarks.common import image_set
from repro.core.remote import TransportModel

REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
BASELINE_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "dispatch_static_baseline.json")

SCALE_TRANSPORT = TransportModel(network_latency_s=0.0005,
                                 bandwidth_bytes_s=5e9,
                                 service_time_s=0.02)   # remote-bound

# shard curve: per-entity service time on the shard's single remote
# server dominates; execute_ops=False simulates that capacity with a
# GIL-releasing sleep so N shards genuinely overlap on a small CI box
# (same rationale as benchmarks/common.py SIM_TRANSPORT)
SHARD_TRANSPORT = TransportModel(network_latency_s=0.0005,
                                 bandwidth_bytes_s=5e9,
                                 service_time_s=0.006,
                                 execute_ops=False)

EFFICIENCY_GATE = 0.7    # linear-scaling efficiency floor at 4 shards
MONOTONE_SLACK = 0.90    # gain(N+1) must be >= slack * gain(N)


def _run_clients(eng, query, clients, *, expect, timeout=600):
    """Run ``clients`` concurrent execute() calls, capturing every
    response and exception per client — a client thread that swallowed
    its result (the old ``lambda: eng.execute(...)`` bug) would let a
    failed or short response time as if it had succeeded."""
    results: list = [None] * clients
    errors: list = [None] * clients

    def client(i):
        try:
            results[i] = eng.execute(query, timeout=timeout)
        except Exception as e:  # noqa: BLE001 — re-raised below, loudly
            errors[i] = e

    ts = [threading.Thread(target=client, args=(i,)) for i in range(clients)]
    t0 = time.monotonic()
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    wall = time.monotonic() - t0
    failed = [e for e in errors if e is not None]
    if failed:
        raise RuntimeError(f"{len(failed)}/{clients} bench clients "
                           f"raised: {failed[0]!r}") from failed[0]
    for i, res in enumerate(results):
        got = len(res["entities"])
        if got != expect or res["stats"]["failed"]:
            raise RuntimeError(
                f"bench client {i} returned {got}/{expect} entities "
                f"with {res['stats']['failed']} failed — short responses "
                f"must fail the bench, not silently pass")
    return wall


# ------------------------------------------------- kappa curve (Fig 29)
def run_kappa(kappas=(1, 2, 4, 8, 16, 32, 64), n_images=96, clients=4):
    """One engine, kappa remote servers (paper Fig 29): T(1)/T(kappa)
    should grow linearly in kappa.  The workload is IQ4 (face detect)
    under parallel clients; the remote-server capacity model dominates."""
    from repro.core.engine import VDMSAsyncEngine

    data = image_set(n_images, size=48)
    ops = [{"type": "remote", "url": "u", "options": {"id": "facedetect_box"}}]
    times = {}
    for k in kappas:
        # single Thread_2 + FIFO Queue_1: paper-faithful baseline so
        # T(1)/T(kappa) isolates remote scale-out, as in Fig 29
        eng = VDMSAsyncEngine(num_remote_servers=k, transport=SCALE_TRANSPORT,
                              dispatch_policy="least_loaded",
                              num_native_workers=1, fair_scheduling=False)
        try:
            for i, img in enumerate(data):
                eng.add_entity("image", img, {"category": "s", "idx": i})
            q = [{"FindImage": {"constraints": {"category": ["==", "s"]},
                                "operations": ops}}]
            eng.execute(q, timeout=600)  # warmup/compile
            times[k] = _run_clients(eng, q, clients, expect=n_images)
        finally:
            eng.shutdown()
    rows = []
    t1 = times[kappas[0]]
    for k in kappas:
        gain = t1 / times[k]
        rows.append({
            "name": f"scaleout_k{k}",
            "us_per_call": times[k] / (n_images * clients) * 1e6,
            "derived": gain / k,       # linear-scaling efficiency
            "gain": gain, "wall_s": times[k],
        })
    return rows


# ------------------------------------------------------ shard curve
def run_shards(shard_counts=(1, 2, 4, 8), n_images=96, clients=2,
               virtual_nodes=192, repeats=2):
    """Fixed workload against a ShardedEngine at growing shard counts.
    Each shard gets ONE simulated remote server, so per-shard capacity
    is constant and the only lever is how evenly the ring partitions
    the entities — T(N) tracks the most-loaded shard.  Each count takes
    the best of ``repeats`` timed runs (the capacity model is a sleep,
    so min wall is the noise-free reading on a loaded CI box)."""
    from repro.cluster import ShardedEngine

    rng = np.random.default_rng(7)
    data = [rng.uniform(0, 1, (32, 32, 3)).astype(np.float32)
            for _ in range(n_images)]
    ops = [{"type": "remote", "url": "u", "options": {"id": "facedetect_box"}}]
    q = [{"FindImage": {"constraints": {"category": ["==", "s"]},
                        "operations": ops}}]
    times, owned = {}, {}
    for n in shard_counts:
        eng = ShardedEngine(num_shards=n, replica_factor=1,
                            virtual_nodes=virtual_nodes,
                            num_remote_servers=1,
                            transport=SHARD_TRANSPORT,
                            dispatch_policy="least_loaded",
                            num_native_workers=1, fair_scheduling=False)
        try:
            for i, img in enumerate(data):
                eng.add_entity("image", img, {"category": "s", "idx": i})
            eng.execute(q, timeout=600)  # warmup/compile on every shard
            times[n] = min(_run_clients(eng, q, clients, expect=n_images)
                           for _ in range(repeats))
            cs = eng.cluster_stats()
            owned[n] = {"owned": {str(s): v["owned"]
                                  for s, v in cs["per_shard"].items()},
                        "imbalance": cs["imbalance"],
                        "failovers_total": cs["failovers_total"]}
        finally:
            eng.shutdown()
    rows = []
    t1 = times[shard_counts[0]]
    for n in shard_counts:
        gain = t1 / times[n]
        stats = owned[n]
        rows.append({
            "name": f"scaleout_shards{n}",
            "us_per_call": times[n] / (n_images * clients) * 1e6,
            "derived": gain / n,       # linear-scaling efficiency
            "gain": gain, "wall_s": times[n],
            "shards": n, "n_images": n_images, "clients": clients,
            "owned_primary": stats["owned"],
            "ring_imbalance": stats["imbalance"],
            "failovers_total": stats["failovers_total"],
        })
    return rows


# ----------------------------------------------- shard-off identity
def run_shard_identity():
    """The bit-exact dispatch_static_hash workload through a 1-shard,
    replica_factor=1 ShardedEngine with default cluster knobs: the
    response hash must match the recorded dispatch baseline — the
    cluster layer must be byte-invisible at one shard."""
    from repro.cluster import ShardedEngine

    transport = TransportModel(network_latency_s=0.001,
                               service_time_s=0.001)
    pipe = [
        {"type": "crop", "x": 4, "y": 4, "width": 24, "height": 24},
        {"type": "remote", "url": "http://svc/flip",
         "options": {"id": "flip"}},
        {"type": "rotate", "k": 1},
        {"type": "threshold", "value": 0.5},
    ]
    query = [{"FindImage": {"constraints": {"category": ["==", "dsp"]},
                            "operations": pipe}}]
    eng = ShardedEngine(num_shards=1, replica_factor=1,
                        num_remote_servers=2, transport=transport)
    try:
        rng = np.random.default_rng(11)   # same fill as dispatch_bench
        for i in range(8):
            img = rng.uniform(0, 1, (32, 32, 3)).astype(np.float32)
            eng.add_entity("image", img, {"category": "dsp", "idx": i})
        res = eng.execute(query, timeout=600)
    finally:
        eng.shutdown()
    h = hashlib.sha256()
    for eid in res["entities"]:
        arr = np.ascontiguousarray(np.asarray(res["entities"][eid]))
        h.update(eid.encode())
        h.update(str(arr.shape).encode())
        h.update(str(arr.dtype).encode())
        h.update(arr.tobytes())
    digest = h.hexdigest()
    recorded = None
    if os.path.exists(BASELINE_PATH):
        with open(BASELINE_PATH) as f:
            recorded = json.load(f).get("sha256")
    return [{
        "name": "scaleout_shard_identity",
        "us_per_call": 0.0,
        "derived": 1.0 if (recorded is None or digest == recorded) else 0.0,
        "shard_response_sha256": digest,
        "baseline_sha256": recorded,
        "shard_matches_baseline": (recorded is None or digest == recorded),
    }]


def run(smoke=True, kappas=None, n_images=None, clients=None):
    """Full suite; also writes repo-root BENCH_scaleout.json.  The
    legacy keyword arguments keep old call sites
    (``scaleout.run((1, 2, 4), n_images=48, clients=2)``) driving the
    kappa curve as before, on top of the shard curve + identity."""
    if smoke:
        shard_counts = (1, 2, 4)
        kappas = kappas or (1, 2, 4, 8)
        kn, kc = n_images or 48, clients or 2
    else:
        shard_counts = (1, 2, 4, 8)
        kappas = kappas or (1, 2, 4, 8, 16, 32, 64)
        kn, kc = n_images or 96, clients or 4
    rows = (run_shard_identity()
            + run_shards(shard_counts)
            + run_kappa(kappas, n_images=kn, clients=kc))
    ident = rows[0]
    shard_rows = [r for r in rows if r["name"].startswith("scaleout_shards")]
    eff4 = next((r["derived"] for r in shard_rows if r["shards"] == 4), None)
    payload = {
        "smoke": smoke,
        "shard_matches_baseline": ident["shard_matches_baseline"],
        "shard_counts": [r["shards"] for r in shard_rows],
        "shard_gains": [r["gain"] for r in shard_rows],
        "shard_efficiencies": [r["derived"] for r in shard_rows],
        "efficiency_at_4_shards": eff4,
        "efficiency_gate": EFFICIENCY_GATE,
        "rows": rows,
    }
    with open(os.path.join(REPO_ROOT, "BENCH_scaleout.json"), "w") as f:
        json.dump(payload, f, indent=2, default=float)
    return rows


def main():
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small sizes for CI (default unless --full)")
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--check-baseline", action="store_true",
                    help="exit non-zero unless the 1-shard cluster "
                         "response matches the recorded dispatch "
                         "baseline, the shard gain curve is monotone, "
                         "and 4-shard efficiency clears the gate")
    args = ap.parse_args()
    rows = run(smoke=not args.full)
    print("name,us_per_call,derived")
    for r in rows:
        print(f"{r['name']},{r['us_per_call']:.1f},{r['derived']:.4f}")
    if args.check_baseline:
        ident = next(r for r in rows
                     if r["name"] == "scaleout_shard_identity")
        if ident["baseline_sha256"] is None:
            # fail CLOSED, same discipline as dispatch_bench: a missing
            # baseline means the identity tripwire checks nothing
            print("FAIL: no recorded baseline at benchmarks/"
                  "dispatch_static_baseline.json; run dispatch_bench "
                  "--update-baseline first", file=sys.stderr)
            sys.exit(2)
        if not ident["shard_matches_baseline"]:
            print(f"FAIL: 1-shard cluster response hash "
                  f"{ident['shard_response_sha256']} != recorded "
                  f"baseline {ident['baseline_sha256']} — the cluster "
                  f"layer perturbed the shard-off response",
                  file=sys.stderr)
            sys.exit(2)
        shard_rows = [r for r in rows
                      if r["name"].startswith("scaleout_shards")]
        eff4 = next((r["derived"] for r in shard_rows
                     if r["shards"] == 4), None)
        if eff4 is None or eff4 < EFFICIENCY_GATE:
            print(f"FAIL: 4-shard linear-scaling efficiency "
                  f"{eff4} < {EFFICIENCY_GATE} gate", file=sys.stderr)
            sys.exit(2)
        for prev, cur in zip(shard_rows, shard_rows[1:]):
            if cur["gain"] < MONOTONE_SLACK * prev["gain"]:
                print(f"FAIL: shard curve not monotone — gain at "
                      f"{cur['shards']} shards ({cur['gain']:.2f}) "
                      f"regressed below {MONOTONE_SLACK} x gain at "
                      f"{prev['shards']} shards ({prev['gain']:.2f})",
                      file=sys.stderr)
                sys.exit(2)


if __name__ == "__main__":
    main()
