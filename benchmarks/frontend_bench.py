"""Serving front-end benchmarks: what the wire costs, and the gates
that keep it honest.

Writes repo-root ``BENCH_frontend.json`` (uploaded as a CI artifact on
every push):

- ``frontend_wire_identity``: the bit-exact static workload from
  ``dispatch_bench`` (crop/flip/rotate/threshold — index permutation +
  comparison only, stable bytes on every platform) executed over the
  wire protocol end-to-end.  The reassembled response is hashed exactly
  like the in-process one and must match BOTH the in-process response
  of the same engine AND the recorded baseline in
  ``benchmarks/dispatch_static_baseline.json`` — serving a query
  through the socket front-end must not perturb a single byte.

- ``frontend_wire_overhead``: the same workload run in-process and over
  the wire on identical engines; reports per-entity wire overhead
  (framing + base64 + socket round trip amortized over the response)
  and the time-to-first-result for each path — streaming should put
  the first entity in the client's hands well before the full response
  assembles.

- ``frontend_overload_gate``: a saturated admission ledger answered
  over the wire: the 429-equivalent ``overload`` frame must carry a
  positive, finite ``retry_after_s``, while a cache-servable query
  (instant entities consume no admission capacity) still completes on
  the same saturated engine.  Both verdicts are enforced under
  ``--check-baseline``.

  PYTHONPATH=src python -m benchmarks.frontend_bench [--smoke|--full]
      [--check-baseline]
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
                             "dispatch_static_baseline.json")

STATIC_PIPE = [
    {"type": "crop", "x": 4, "y": 4, "width": 24, "height": 24},
    {"type": "remote", "url": "http://svc/flip", "options": {"id": "flip"}},
    {"type": "rotate", "k": 1},
    {"type": "threshold", "value": 0.5},
]
STATIC_QUERY = [{"FindImage": {"constraints": {"category": ["==", "dsp"]},
                               "operations": STATIC_PIPE}}]


def _fill(eng, n, size, category="dsp"):
    rng = np.random.default_rng(11)
    for i in range(n):
        img = rng.uniform(0, 1, (size, size, 3)).astype(np.float32)
        eng.add_entity("image", img, {"category": category, "idx": i})


def _response_sha256(entities: dict) -> str:
    h = hashlib.sha256()
    for eid in entities:
        arr = np.ascontiguousarray(np.asarray(entities[eid]))
        h.update(eid.encode())
        h.update(str(arr.shape).encode())
        h.update(str(arr.dtype).encode())
        h.update(arr.tobytes())
    return h.hexdigest()


def _static_engine():
    from repro.core.engine import VDMSAsyncEngine
    from repro.core.remote import TransportModel

    return VDMSAsyncEngine(
        num_remote_servers=2,
        transport=TransportModel(network_latency_s=0.001,
                                 service_time_s=0.001))


# --------------------------------------------------------- wire identity
def run_wire_identity():
    """The static-hash workload through the socket: reassembled wire
    response vs in-process response vs recorded baseline hash."""
    from repro.serving.frontend import WireClient, WireFrontend

    eng = _static_engine()
    try:
        _fill(eng, 8, 32)
        inproc = eng.execute(STATIC_QUERY, timeout=600)
        front = WireFrontend(eng).start()
        try:
            with WireClient(front.address) as client:
                wired = client.execute(STATIC_QUERY, timeout=600)
        finally:
            front.close()
    finally:
        eng.shutdown()
    wire_sha = _response_sha256(wired["entities"])
    inproc_sha = _response_sha256(inproc["entities"])
    recorded = None
    if os.path.exists(BASELINE_PATH):
        with open(BASELINE_PATH) as f:
            recorded = json.load(f).get("sha256")
    return [{
        "name": "frontend_wire_identity",
        "us_per_call": 0.0,
        "derived": 1.0 if wire_sha == inproc_sha else 0.0,
        "wire_response_sha256": wire_sha,
        "inproc_response_sha256": inproc_sha,
        "baseline_sha256": recorded,
        "wire_matches_inproc": wire_sha == inproc_sha,
        "wire_matches_baseline": (recorded is None or wire_sha == recorded),
    }]


# -------------------------------------------------------- wire overhead
def run_wire_overhead(n_images=32, size=32, repeats=5):
    """Identical engines, identical workload: in-process submit vs the
    full wire round trip.  Reports amortized per-entity overhead and
    time-to-first-result on each path."""
    from repro.serving.frontend import WireClient, WireFrontend

    def _inproc_once(eng):
        first = []
        t0 = time.perf_counter()
        fut = eng.submit(STATIC_QUERY,
                         on_entity=lambda e: first.append(
                             time.perf_counter()) if not first else None)
        res = fut.result(600)
        t_total = time.perf_counter() - t0
        return t_total, (first[0] - t0 if first else t_total), res

    def _wire_once(client):
        t0 = time.perf_counter()
        fut = client.submit(STATIC_QUERY)
        first = None
        while True:
            event, _ = fut._pull(600)
            if event == "entity" and first is None:
                first = time.perf_counter()
            if event in ("complete", "overload", "error", "cancelled"):
                break
        res = fut.result(600)
        t_total = time.perf_counter() - t0
        return t_total, ((first or time.perf_counter()) - t0), res

    eng = _static_engine()
    try:
        _fill(eng, n_images, size)
        front = WireFrontend(eng).start()
        try:
            inproc_t, inproc_first, wire_t, wire_first = [], [], [], []
            with WireClient(front.address) as client:
                _inproc_once(eng)          # warm both paths once
                _wire_once(client)
                for _ in range(repeats):
                    t, f, ri = _inproc_once(eng)
                    inproc_t.append(t)
                    inproc_first.append(f)
                    t, f, rw = _wire_once(client)
                    wire_t.append(t)
                    wire_first.append(f)
        finally:
            front.close()
    finally:
        eng.shutdown()
    identical = list(ri["entities"]) == list(rw["entities"]) and all(
        np.array_equal(np.asarray(ri["entities"][k]),
                       np.asarray(rw["entities"][k]))
        for k in ri["entities"])
    t_in = float(np.median(inproc_t))
    t_wire = float(np.median(wire_t))
    overhead_per_entity_us = (t_wire - t_in) / n_images * 1e6
    return [{
        "name": f"frontend_wire_overhead_n{n_images}",
        "us_per_call": t_wire * 1e6,
        "derived": overhead_per_entity_us,
        "inproc_total_s": t_in,
        "wire_total_s": t_wire,
        "wire_overhead_per_entity_us": overhead_per_entity_us,
        "inproc_first_result_s": float(np.median(inproc_first)),
        "wire_first_result_s": float(np.median(wire_first)),
        "responses_identical": identical,
    }]


# -------------------------------------------------------- overload gate
def run_overload_gate():
    """Saturate the admission ledger, then hit the wire: the shed query
    must get the 429 frame with a positive finite retry_after_s while a
    cache-servable query completes on the same saturated engine."""
    from repro.core.engine import VDMSAsyncEngine
    from repro.core.remote import TransportModel
    from repro.query.admission import OverloadError
    from repro.serving.frontend import WireClient, WireFrontend

    eng = VDMSAsyncEngine(
        num_remote_servers=1,
        transport=TransportModel(network_latency_s=0.001,
                                 service_time_s=0.001),
        admission="shed", max_inflight_entities=4, cache_capacity=64)
    retry_after = None
    cache_served = False
    cache_hits = 0
    try:
        _fill(eng, 4, 24)
        front = WireFrontend(eng).start()
        try:
            with WireClient(front.address) as client:
                warm = client.execute(STATIC_QUERY, timeout=600)
                # deterministic saturation: claim every slot pre-ingest
                eng.admission_ctl.reserve("hold", 4, first_phase=True)
                try:
                    client.submit(STATIC_QUERY, cache=False).result(60)
                except OverloadError as e:
                    retry_after = e.retry_after_s
                served = client.execute(STATIC_QUERY, timeout=600)
                cache_hits = served["stats"].get("cache_full_hits", 0)
                cache_served = (
                    cache_hits == len(warm["entities"]) and
                    list(served["entities"]) == list(warm["entities"]))
        finally:
            front.close()
    finally:
        eng.shutdown()
    gate_ok = (retry_after is not None and 0 < retry_after < float("inf")
               and cache_served)
    return [{
        "name": "frontend_overload_gate",
        "us_per_call": 0.0,
        "derived": 1.0 if gate_ok else 0.0,
        "retry_after_s": retry_after,
        "overload_answered": retry_after is not None,
        "cache_served_while_saturated": cache_served,
        "cache_full_hits": cache_hits,
        "gate_ok": gate_ok,
    }]


def run(smoke=True):
    if smoke:
        rows = (run_wire_identity()
                + run_wire_overhead(n_images=16, size=32, repeats=3)
                + run_overload_gate())
    else:
        rows = (run_wire_identity()
                + run_wire_overhead(n_images=64, size=48, repeats=7)
                + run_overload_gate())
    by_name = {r["name"]: r for r in rows}
    ident = by_name["frontend_wire_identity"]
    over = next(r for n, r in by_name.items()
                if n.startswith("frontend_wire_overhead"))
    gate = by_name["frontend_overload_gate"]
    payload = {
        "smoke": smoke,
        "wire_matches_inproc": ident["wire_matches_inproc"],
        "wire_matches_baseline": ident["wire_matches_baseline"],
        "wire_response_sha256": ident["wire_response_sha256"],
        "wire_overhead_per_entity_us": over["wire_overhead_per_entity_us"],
        "wire_first_result_s": over["wire_first_result_s"],
        "inproc_first_result_s": over["inproc_first_result_s"],
        "overload_retry_after_s": gate["retry_after_s"],
        "cache_served_while_saturated": gate["cache_served_while_saturated"],
        "rows": rows,
    }
    with open(os.path.join(REPO_ROOT, "BENCH_frontend.json"), "w") as f:
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
                    help="exit non-zero unless the wire response hash "
                         "matches benchmarks/dispatch_static_baseline.json, "
                         "the in-process response, and the overload/cache "
                         "gates held")
    args = ap.parse_args()
    rows = run(smoke=not args.full)
    print("name,us_per_call,derived")
    for r in rows:
        print(f"{r['name']},{r['us_per_call']:.1f},{r['derived']:.4f}")
    if args.check_baseline:
        ident = next(r for r in rows
                     if r["name"] == "frontend_wire_identity")
        over = next(r for r in rows
                    if r["name"].startswith("frontend_wire_overhead"))
        gate = next(r for r in rows
                    if r["name"] == "frontend_overload_gate")
        if ident["baseline_sha256"] is None:
            # fail CLOSED: no recorded baseline means no tripwire
            print(f"FAIL: no recorded baseline at {BASELINE_PATH}; run "
                  f"dispatch_bench --update-baseline first",
                  file=sys.stderr)
            sys.exit(2)
        if not ident["wire_matches_baseline"]:
            print(f"FAIL: wire response hash "
                  f"{ident['wire_response_sha256']} != recorded baseline "
                  f"{ident['baseline_sha256']}", file=sys.stderr)
            sys.exit(2)
        if not ident["wire_matches_inproc"]:
            print("FAIL: wire response differs from in-process response",
                  file=sys.stderr)
            sys.exit(2)
        if not over["responses_identical"]:
            print("FAIL: overhead-arm wire response differs from "
                  "in-process response", file=sys.stderr)
            sys.exit(2)
        if not gate["gate_ok"]:
            print(f"FAIL: overload gate (retry_after_s="
                  f"{gate['retry_after_s']}, cache_served="
                  f"{gate['cache_served_while_saturated']})",
                  file=sys.stderr)
            sys.exit(2)
        print("baseline check OK: wire responses byte-identical, "
              "overload gate held")


if __name__ == "__main__":
    main()
