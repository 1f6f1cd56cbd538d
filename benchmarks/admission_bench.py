"""Admission-control benchmarks: bounded tail latency under a 10x
overload storm vs unbounded collapse.

Writes repo-root ``BENCH_admission.json`` (uploaded as a CI artifact on
every push):

- ``admission_storm``: a remote-bound workload submitted as a burst of
  ~10x the engine's in-flight capacity, run under three admission modes
  on identical data:

    * ``none``  — the unbounded default: every ``submit()`` is
      accepted, Queue_1 and the remote queues grow with the whole
      burst, and per-query p99 latency collapses to roughly the full
      backlog drain time (the synchronous-saturation failure mode the
      paper's async design escapes *per query* but not *across*
      queries);
    * ``shed``  — ``admission="shed", max_inflight_entities=N``:
      queries that do not fit under the cap fail fast with
      ``OverloadError`` + retry-after; admitted queries see near-
      uncontended latency.  The bench records that the controller's
      in-flight ledger never exceeded N (``shed_inflight_bounded``) and
      that admitted-query p99 stayed within 3x of the uncontended
      baseline (``shed_p99_within_3x``) — the two acceptance invariants
      the chaos tests also pin down;
    * ``queue`` — ``admission="queue"``: everything completes, overflow
      waits in the priority lane, in-flight stays bounded; p99 reflects
      queueing delay rather than collapse.

  ``derived`` is the headline ``p99_none / p99_shed`` — what shedding
  buys the queries the engine chooses to serve under overload.

- ``admission_none_hash``: a bit-exact workload (index-permutation +
  comparison ops only) run on a default-knob engine and on an engine
  with ``admission="queue"``: the default response must be
  hash-identical to the recorded baseline in
  ``benchmarks/admission_static_baseline.json`` (fail closed — the
  admission layer must never perturb the paper-faithful response), and
  the queue-admission response must be array-identical to it.

  PYTHONPATH=src python -m benchmarks.admission_bench [--smoke|--full]
      [--check-baseline] [--update-baseline]
"""
from __future__ import annotations

import argparse
import hashlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np

REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
BASELINE_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "admission_static_baseline.json")


def _fill(eng, n, size=24, category="adm"):
    rng = np.random.default_rng(23)
    for i in range(n):
        img = rng.uniform(0, 1, (size, size, 3)).astype(np.float32)
        eng.add_entity("image", img, {"category": category, "idx": i})


def _p(latencies, q):
    if not latencies:
        return 0.0
    return float(np.percentile(np.asarray(latencies), q))


def _entities_equal(a: dict, b: dict) -> bool:
    if list(a) != list(b):
        return False
    return all(np.array_equal(np.asarray(a[k]), np.asarray(b[k])) for k in a)


# ------------------------------------------------------- overload storm
def run_storm(fanout=4, max_inflight=16, storm_factor=10,
              service_ms=3.0, servers=4):
    """One burst of ``storm_factor * max_inflight`` entities against a
    ``max_inflight``-capacity engine, per admission mode."""
    from repro.core.engine import VDMSAsyncEngine
    from repro.core.remote import TransportModel
    from repro.query.admission import OverloadError

    transport = TransportModel(network_latency_s=0.001,
                               service_time_s=service_ms / 1000.0)
    pipe = [
        {"type": "resize", "width": 16, "height": 16},
        {"type": "remote", "url": "u", "options": {"id": "grayscale"}},
        {"type": "threshold", "value": 0.4},
    ]
    query = [{"FindImage": {"constraints": {"category": ["==", "adm"]},
                            "operations": pipe}}]
    n_queries = max(1, storm_factor * max_inflight // fanout)

    def arm(mode):
        kw = {}
        if mode != "none":
            kw = {"admission": mode, "max_inflight_entities": max_inflight,
                  "admission_queue_cap": 100_000}
        eng = VDMSAsyncEngine(num_remote_servers=servers,
                              transport=transport,
                              num_native_workers=2, **kw)
        try:
            _fill(eng, fanout)
            eng.execute(query, timeout=600)      # jit warmup
            # uncontended reference: one query at a time
            uncontended = []
            for _ in range(6):
                t0 = time.monotonic()
                eng.execute(query, timeout=600)
                uncontended.append(time.monotonic() - t0)
            # the storm: a burst of n_queries submits from one thread
            # (submit is O(fan-out) pointer work, so the burst lands in
            # milliseconds — the backlog, not the client, is the bottleneck)
            latencies, shed = [], 0
            pending = []
            t_burst = time.monotonic()
            for _ in range(n_queries):
                t0 = time.monotonic()
                try:
                    fut = eng.submit(query, cache=False)
                except OverloadError:
                    shed += 1
                    continue
                pending.append((t0, fut))
            for t0, fut in pending:
                fut.result(timeout=600)
                latencies.append(time.monotonic() - t0)
            wall = time.monotonic() - t_burst
            st = eng.admission_stats()
            return {
                "mode": mode,
                "uncontended_p99_s": _p(uncontended, 99),
                "storm_p50_s": _p(latencies, 50),
                "storm_p99_s": _p(latencies, 99),
                "completed": len(latencies),
                "shed": shed,
                "storm_wall_s": wall,
                "peak_inflight": st.get("peak_inflight"),
                "inflight_bounded": (st.get("peak_inflight", 0)
                                     <= max_inflight
                                     if mode != "none" else None),
            }
        finally:
            eng.shutdown()

    none_r = arm("none")
    shed_r = arm("shed")
    queue_r = arm("queue")
    base = max(1e-9, none_r["uncontended_p99_s"])
    row = {
        "name": f"admission_storm_x{storm_factor}_cap{max_inflight}",
        "us_per_call": shed_r["storm_p99_s"] * 1e6,
        # headline: the tail-latency collapse shedding avoids
        "derived": none_r["storm_p99_s"] / max(1e-9, shed_r["storm_p99_s"]),
        "fanout": fanout,
        "max_inflight_entities": max_inflight,
        "storm_queries": max(1, storm_factor * max_inflight // fanout),
        "none": none_r,
        "shed": shed_r,
        "queue": queue_r,
        "none_p99_ratio": none_r["storm_p99_s"] / base,
        "shed_p99_ratio": shed_r["storm_p99_s"]
        / max(1e-9, shed_r["uncontended_p99_s"]),
        "shed_inflight_bounded": bool(shed_r["inflight_bounded"]),
        "queue_inflight_bounded": bool(queue_r["inflight_bounded"]),
        "shed_count": shed_r["shed"],
    }
    row["shed_p99_within_3x"] = row["shed_p99_ratio"] <= 3.0
    return [row]


# ------------------------------------------------- static-response hash
def run_static_hash():
    """Hash the default engine's response on a bit-exact workload
    (crop/flip/rotate permute indices, threshold compares untouched
    values — identical bytes on every platform and jax version) and
    check an ``admission="queue"`` engine returns the identical arrays."""
    from repro.core.engine import VDMSAsyncEngine
    from repro.core.remote import TransportModel

    transport = TransportModel(network_latency_s=0.001,
                               service_time_s=0.001)
    pipe = [
        {"type": "crop", "x": 2, "y": 2, "width": 20, "height": 20},
        {"type": "remote", "url": "http://svc/flip",
         "options": {"id": "flip"}},
        {"type": "rotate", "k": 3},
        {"type": "threshold", "value": 0.5},
    ]
    query = [{"FindImage": {"constraints": {"category": ["==", "adm"]},
                            "operations": pipe}}]

    def response(**kw):
        eng = VDMSAsyncEngine(num_remote_servers=2, transport=transport,
                              **kw)
        try:
            _fill(eng, 8, size=28)
            return eng.execute(query, timeout=600)
        finally:
            eng.shutdown()

    ref = response()                       # engine exactly as it ships
    gated = response(admission="queue", max_inflight_entities=4)
    identical = _entities_equal(ref["entities"], gated["entities"])
    h = hashlib.sha256()
    for eid in ref["entities"]:
        arr = np.ascontiguousarray(np.asarray(ref["entities"][eid]))
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
        "name": "admission_none_hash",
        "us_per_call": 0.0,
        "derived": 1.0 if identical else 0.0,
        "none_response_sha256": digest,
        "baseline_sha256": recorded,
        "queue_matches_none": identical,
        "none_matches_baseline": (recorded is None or digest == recorded),
    }]


def run(smoke=True):
    # cap/servers ratio picks the admitted concurrency (cap/fanout
    # queries share `servers` lanes): 8/4 keeps admitted-query latency
    # ~2x uncontended, well inside the 3x acceptance gate, while the
    # unbounded arm still queues the whole 10x burst
    if smoke:
        rows = (run_storm(fanout=4, max_inflight=8, storm_factor=10,
                          service_ms=3.0, servers=4)
                + run_static_hash())
    else:
        rows = (run_storm(fanout=8, max_inflight=16, storm_factor=10,
                          service_ms=5.0, servers=8)
                + run_static_hash())
    storm = next(r for r in rows if r["name"].startswith("admission_storm"))
    hrow = next(r for r in rows if r["name"] == "admission_none_hash")
    payload = {
        "smoke": smoke,
        "p99_collapse_unbounded": storm["none_p99_ratio"],
        "p99_shed_vs_none": storm["derived"],
        "shed_p99_ratio": storm["shed_p99_ratio"],
        "shed_p99_within_3x": storm["shed_p99_within_3x"],
        "shed_inflight_bounded": storm["shed_inflight_bounded"],
        "queue_inflight_bounded": storm["queue_inflight_bounded"],
        "shed_count": storm["shed_count"],
        "none_response_sha256": hrow["none_response_sha256"],
        "none_matches_baseline": hrow["none_matches_baseline"],
        "queue_matches_none": hrow["queue_matches_none"],
        "rows": rows,
    }
    with open(os.path.join(REPO_ROOT, "BENCH_admission.json"), "w") as f:
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
                    help="exit non-zero unless the admission='none' "
                         "response hash matches benchmarks/"
                         "admission_static_baseline.json, the queue-"
                         "admission response is identical, shed kept "
                         "in-flight under the cap, and shed p99 stayed "
                         "within 3x of uncontended")
    ap.add_argument("--update-baseline", action="store_true",
                    help="record the current none-response hash as the "
                         "new baseline")
    args = ap.parse_args()
    rows = run(smoke=not args.full)
    print("name,us_per_call,derived")
    for r in rows:
        print(f"{r['name']},{r['us_per_call']:.1f},{r['derived']:.4f}")
    hrow = next(r for r in rows if r["name"] == "admission_none_hash")
    storm = next(r for r in rows if r["name"].startswith("admission_storm"))
    if args.update_baseline:
        with open(BASELINE_PATH, "w") as f:
            json.dump({"sha256": hrow["none_response_sha256"],
                       "note": "default-engine (admission='none') response "
                               "hash on the bit-exact admission_none_hash "
                               "workload; regenerate with "
                               "--update-baseline"},
                      f, indent=2)
        print(f"baseline updated: {hrow['none_response_sha256']}")
    if args.check_baseline:
        if hrow["baseline_sha256"] is None:
            # fail CLOSED: a missing baseline file means the tripwire
            # would be checking nothing
            print(f"FAIL: no recorded baseline at {BASELINE_PATH}; run "
                  f"with --update-baseline first", file=sys.stderr)
            sys.exit(2)
        if not hrow["none_matches_baseline"]:
            print(f"FAIL: none-response hash "
                  f"{hrow['none_response_sha256']} != recorded baseline "
                  f"{hrow['baseline_sha256']}", file=sys.stderr)
            sys.exit(2)
        if not hrow["queue_matches_none"]:
            print("FAIL: admission='queue' perturbed the response",
                  file=sys.stderr)
            sys.exit(2)
        if not (storm["shed_inflight_bounded"]
                and storm["queue_inflight_bounded"]):
            print("FAIL: in-flight entities exceeded "
                  "max_inflight_entities during the storm",
                  file=sys.stderr)
            sys.exit(2)
        if not storm["shed_p99_within_3x"]:
            print(f"FAIL: shed-arm p99 {storm['shed']['storm_p99_s']:.4f}s "
                  f"is {storm['shed_p99_ratio']:.1f}x its uncontended "
                  f"baseline (limit 3x)", file=sys.stderr)
            sys.exit(2)


if __name__ == "__main__":
    main()
