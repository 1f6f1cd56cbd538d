"""Resilience benchmarks: the fault-tolerance layer under a seeded
fault storm, plus the fault-off byte-identity tripwire.

Writes repo-root ``BENCH_resilience.json`` (uploaded as a CI artifact on
every push):

- ``resilience_identity``: the fault-injection-OFF tripwire.  Reuses
  the bit-exact ``dispatch_static_hash`` workload from
  ``benchmarks.dispatch_bench`` (index-permutation + comparison ops
  only, stable bytes on every platform): with every fault-tolerance
  knob at its default the engine's response hash must still match the
  recorded ``benchmarks/dispatch_static_baseline.json`` — the whole
  retry/backoff/heartbeat/breaker/fallback layer must be invisible
  until switched on.

- ``resilience_storm``: a seeded ~20% fault storm (error 12% + crash
  4% + latency 4%, :class:`~repro.distributed.fault.FaultInjector`
  seed ``0xFA17``, one server-death budgeted) against a fully-armed
  engine — ``dispatch="cost"`` with the remote op pinned onto the
  faulty remote pool, bounded-jitter retry backoff, heartbeat
  monitoring, circuit breakers, ``fallback="native"`` and
  ``admission="queue"`` under a hard in-flight cap.  The same
  workload runs fault-free on an identically-knobbed engine as the
  latency reference.  Gates (enforced under ``--check-baseline``):

    * ``completion_rate`` == 1.0 — every query completes with zero
      failed entities: injected faults degrade to *slower*, never to
      *failed*;
    * ``admission_leaks`` == 0 and ``peak_inflight`` <= the cap — the
      retry/fallback churn never leaks or overshoots admission slots;
    * ``p99_factor`` (storm p99 / fault-free p99) <= ``P99_GATE`` —
      degradation is bounded, not just eventual.

  PYTHONPATH=src python -m benchmarks.resilience_bench
      [--smoke|--full] [--check-baseline]
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

# storm p99 may exceed fault-free p99 by at most this factor.  Generous
# on purpose: the gate exists to catch unbounded degradation (a retry
# loop that never converges, a breaker that never closes), not to
# benchmark a noisy 2-core CI box's tail.
P99_GATE = 25.0

STORM_SEED = 0xFA17
INFLIGHT_CAP = 16


def _fill(eng, n, size=32, category="res"):
    rng = np.random.default_rng(23)
    for i in range(n):
        img = rng.uniform(0, 1, (size, size, 3)).astype(np.float32)
        eng.add_entity("image", img, {"category": category, "idx": i})


# -------------------------------------------------- fault-off identity
def run_identity():
    """Fault-tolerance layer present, every knob default: the static
    response hash must still match the recorded dispatch baseline."""
    from benchmarks.dispatch_bench import run_static_hash

    row = dict(run_static_hash()[0])
    row["name"] = "resilience_identity"
    return [row]


# ------------------------------------------------------- fault storm
def _storm_injector():
    from repro.distributed.fault import FaultInjector

    return FaultInjector(seed=STORM_SEED,
                         error_rate=0.12,
                         crash_rate=0.04,
                         latency_rate=0.04,
                         latency_s=0.05,
                         die_rate=0.005,
                         death_budget=1)


def run_storm(n_queries=24, n_images=8):
    from repro.core.engine import VDMSAsyncEngine
    from repro.core.remote import TransportModel

    transport = TransportModel(network_latency_s=0.004,
                               service_time_s=0.001)
    pipe = [
        {"type": "crop", "x": 4, "y": 4, "width": 24, "height": 24},
        {"type": "remote", "url": "http://svc/flip",
         "options": {"id": "flip"}},
        {"type": "rotate", "k": 1},
        {"type": "threshold", "value": 0.5},
    ]
    query = [{"FindImage": {"constraints": {"category": ["==", "res"]},
                            "operations": pipe}}]
    # pin the remote-tagged op onto the faulty remote pool so the storm
    # actually lands on it; when its breaker opens, the router's health
    # veto re-routes the op to the (deliberately expensive) native
    # fallback — the degradation path under test
    pinned = {"flip": {"remote": 1e-6, "native": 10.0, "batcher": 10.0}}

    def arm(injector):
        eng = VDMSAsyncEngine(
            num_remote_servers=3, transport=transport,
            num_native_workers=2,
            dispatch="cost", cost_overrides=pinned,
            admission="queue", max_inflight_entities=INFLIGHT_CAP,
            max_retries=4,
            retry_backoff_base_s=0.002, retry_backoff_max_s=0.05,
            heartbeat_timeout_s=0.25,
            fallback="native",
            breaker_enabled=True,
            fault_injector=injector)
        try:
            _fill(eng, n_images)
            futs = [eng.submit(query) for _ in range(n_queries)]
            t0 = time.monotonic()
            completed, failed_entities, durations = 0, 0, []
            for fut in futs:
                try:
                    res = fut.result(timeout=300)
                except Exception:  # noqa: BLE001 — counted, not raised
                    continue
                completed += 1
                failed_entities += res["stats"]["failed"]
                durations.append(res["stats"]["duration_s"])
            wall = time.monotonic() - t0
            adm = eng.admission_stats()
            ds = eng.dispatch_stats()
            return {
                "wall_s": wall,
                "completed": completed,
                "failed_entities": failed_entities,
                "p50_s": float(np.percentile(durations, 50))
                         if durations else float("inf"),
                "p99_s": float(np.percentile(durations, 99))
                         if durations else float("inf"),
                "peak_inflight": adm["peak_inflight"],
                "admission_leaks": adm["inflight"] + adm["pending"],
                "pool": ds.get("pool", {}),
                "breakers": {k: v["state"]
                             for k, v in ds.get("breakers", {}).items()},
                "breaker_trips": sum(v["trips"] for v in
                                     ds.get("breakers", {}).values()),
                "fallbacks": ds.get("fallbacks", 0),
                "injected": injector.stats() if injector else {},
            }
        finally:
            eng.shutdown()

    clean = arm(None)
    storm = arm(_storm_injector())
    p99_factor = (storm["p99_s"] / clean["p99_s"]
                  if clean["p99_s"] > 0 else float("inf"))
    pool = storm["pool"]
    return [{
        "name": f"resilience_storm_q{n_queries}",
        "us_per_call": storm["wall_s"] / n_queries * 1e6,
        "derived": storm["completed"] / n_queries,
        "completion_rate": storm["completed"] / n_queries,
        "failed_entities": storm["failed_entities"],
        "n_queries": n_queries,
        "entities_per_query": n_images,
        "inflight_cap": INFLIGHT_CAP,
        "peak_inflight": storm["peak_inflight"],
        "admission_leaks": storm["admission_leaks"],
        "clean_p50_s": clean["p50_s"],
        "clean_p99_s": clean["p99_s"],
        "storm_p50_s": storm["p50_s"],
        "storm_p99_s": storm["p99_s"],
        "p99_factor": p99_factor,
        "p99_gate": P99_GATE,
        "injected": storm["injected"],
        "retried": pool.get("retried", 0),
        "retries_delayed": pool.get("retries_delayed", 0),
        "beat_deaths": pool.get("beat_deaths", 0),
        "beat_requeued": pool.get("beat_requeued", 0),
        "live_servers": pool.get("live", 0),
        "breaker_trips": storm["breaker_trips"],
        "breakers_final": storm["breakers"],
        "fallbacks": storm["fallbacks"],
    }]


def run(smoke=True):
    if smoke:
        rows = run_identity() + run_storm(n_queries=24, n_images=8)
    else:
        rows = run_identity() + run_storm(n_queries=64, n_images=8)
    ident = rows[0]
    storm = rows[1]
    payload = {
        "smoke": smoke,
        "fault_off_matches_baseline": ident["static_matches_baseline"],
        "completion_rate": storm["completion_rate"],
        "p99_factor": storm["p99_factor"],
        "peak_inflight": storm["peak_inflight"],
        "admission_leaks": storm["admission_leaks"],
        "fallbacks": storm["fallbacks"],
        "rows": rows,
    }
    with open(os.path.join(REPO_ROOT, "BENCH_resilience.json"), "w") as f:
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
                    help="exit non-zero unless fault-off output matches "
                         "the recorded static baseline AND the storm "
                         "gates hold (100%% completion, no admission "
                         "leaks, bounded p99)")
    args = ap.parse_args()
    rows = run(smoke=not args.full)
    print("name,us_per_call,derived")
    for r in rows:
        print(f"{r['name']},{r['us_per_call']:.1f},{r['derived']:.4f}")
    if args.check_baseline:
        ident = next(r for r in rows if r["name"] == "resilience_identity")
        storm = next(r for r in rows
                     if r["name"].startswith("resilience_storm"))
        if ident["baseline_sha256"] is None:
            # fail CLOSED, same discipline as dispatch_bench: a missing
            # baseline means the identity tripwire checks nothing
            print("FAIL: no recorded baseline at benchmarks/"
                  "dispatch_static_baseline.json; run dispatch_bench "
                  "--update-baseline first", file=sys.stderr)
            sys.exit(2)
        if not ident["static_matches_baseline"]:
            print(f"FAIL: fault-off response hash "
                  f"{ident['static_response_sha256']} != recorded "
                  f"baseline {ident['baseline_sha256']} — the "
                  f"fault-tolerance layer perturbed the default engine",
                  file=sys.stderr)
            sys.exit(2)
        if storm["completion_rate"] != 1.0 or storm["failed_entities"]:
            print(f"FAIL: storm completion_rate="
                  f"{storm['completion_rate']:.3f}, failed_entities="
                  f"{storm['failed_entities']} (want 1.0 / 0: faults "
                  f"must degrade, never fail)", file=sys.stderr)
            sys.exit(2)
        if storm["admission_leaks"] != 0 \
                or storm["peak_inflight"] > storm["inflight_cap"]:
            print(f"FAIL: admission ledger leaked under the storm "
                  f"(leaks={storm['admission_leaks']}, peak="
                  f"{storm['peak_inflight']}, cap="
                  f"{storm['inflight_cap']})", file=sys.stderr)
            sys.exit(2)
        if storm["p99_factor"] > P99_GATE:
            print(f"FAIL: storm p99 is {storm['p99_factor']:.1f}x the "
                  f"fault-free p99 (gate {P99_GATE}x) — degradation is "
                  f"unbounded", file=sys.stderr)
            sys.exit(2)


if __name__ == "__main__":
    main()
