#!/usr/bin/env python3
"""Smoke of the query engine's main path on one TPU chip.

Drives the engine through its normal entry points at the shape of the
paper's source dataset (LFW: 250x250x3 faces) and checks what comes out:

1. chip check   — the first JAX device must be a TPU, else exit 1;
2. ingest       — 2,048 seeded synthetic faces (~1.43 GiB) with
                  category/age metadata, through ``add_entity`` and
                  ``AddImage`` submits;
3. static       — the paper-faithful engine (``dispatch="static"``): the
                  Fig-8 chain (resize -> remote facedetect_box ->
                  threshold) and IQ3 (remote blur) through ``submit()``,
                  one Fig-8 query again through ``WireFrontend`` +
                  ``WireClient`` on loopback, and the static response
                  hash against ``benchmarks/dispatch_static_baseline.json``;
4. device       — ``dispatch="cost", device_backend="tpu"`` with the
                  preprocess chain and blur pinned onto the device: the
                  fused Pallas preprocess kernel and the Pallas blur,
                  compared with float32 ``jax.numpy`` reference ops on
                  the host CPU;
5. every query  — every future resolved, no entity failed, no admission
                  slot or device work left behind.

Everything runs in this one process (a chip belongs to one process).
Wall times are printed as set-up times: they include compiles and are
not speed results.  The last line of stdout is the JSON result object;
any failed check exits 1 without it.

    python chip_smoke.py [--seed N]     # one chip
    python chip_smoke.py --four-chips   # device phase on 4 chips vs 1
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time
import traceback

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
N_FACES = 2048
FACE = 250
N_SUBMITTED = 16            # faces ingested through AddImage submits
CATEGORIES = ("celebrity", "athlete", "politician", "musician")
TIMEOUT_S = 600.0

# tolerances of the device path against float32 jnp on the host CPU
# (max abs error, images in [0, 1]):
# - blur: 25 f32 multiply-adds per output in a different order than the
#   reference; a few ulp of values <= 1, so 1e-5 leaves ~100 ulp.
# - preprocess: two f32 matmuls (contraction 250) on the MXU at HIGHEST
#   precision, then (x - 0.5) / 0.25, which scales the error by 4; a sum
#   of 250 rounded products is off by ~250 * 2^-24 ~ 1.5e-5 at worst
#   before that scaling, so 1e-4.
TOL = {"blur": 1e-5, "preprocess": 1e-4}

PREPROCESS = [{"type": "resize", "width": 256, "height": 256},
              {"type": "crop", "x": 16, "y": 16, "width": 224, "height": 224},
              {"type": "normalize", "mean": 0.5, "std": 0.25}]
BLUR = [{"type": "blur", "ksize": 5, "sigma_x": 1.5}]
# pin the device ops onto the device backend: every other backend is
# priced out, so the router places the whole chain there
DEVICE_PINS = {o["type"]: {"device": 1e-9, "native": 10.0, "remote": 10.0,
                           "batcher": 10.0}
               for o in PREPROCESS + BLUR}


def _find(category, age_lo, age_hi, ops):
    return [{"FindImage": {
        "constraints": {"category": ["==", category],
                        "age": [">=", age_lo, "<=", age_hi]},
        "operations": ops}}]


def _remote(name, **opt):
    return {"type": "remote", "url": "http://udf/" + name,
            "options": {"id": name, **opt}}


FIG8 = [{"type": "resize", "width": 400, "height": 500},
        _remote("facedetect_box"),
        {"type": "threshold", "value": 0.4}]
IQ3 = [_remote("blur", ksize=5, sigma_x=1.5)]

# (name, query) for each phase; each selects a few hundred faces
STATIC_QUERIES = {"fig8": _find("celebrity", 21, 40, FIG8),
                  "iq3_blur": _find("athlete", 21, 40, IQ3)}
WIRE_QUERY = _find("celebrity", 21, 24, FIG8)
DEVICE_QUERIES = {"preprocess": _find("politician", 21, 40, PREPROCESS),
                  "blur": _find("musician", 21, 40, BLUR)}


class Smoke:
    """Collects check results and per-phase wall times."""

    def __init__(self):
        self.failures: list[str] = []
        self.times: dict[str, float] = {}

    @contextlib.contextmanager
    def phase(self, name):
        print(f"--- phase {name}", flush=True)
        t0 = time.monotonic()
        try:
            yield
        except Exception as e:  # noqa: BLE001 — recorded, fails the run
            traceback.print_exc()
            self.failures.append(f"{name}: raised {e!r}")
        finally:
            dt = time.monotonic() - t0
            self.times[name] = dt
            print(f"[set-up time, not a speed result] {name}: {dt:.3f} s "
                  f"wall (compiles included)", flush=True)

    def check(self, ok, what):
        print(("ok    " if ok else "FAIL  ") + what, flush=True)
        if not ok:
            self.failures.append(what)


# ---------------------------------------------------------------- data
def make_dataset(seed: int, n: int = N_FACES, size: int = FACE):
    from repro.dataio import synthetic_faces
    faces = synthetic_faces(n, size=size, seed=seed)
    rng = np.random.default_rng(seed)
    ages = rng.integers(18, 71, n)
    props = [{"category": CATEGORIES[i % len(CATEGORIES)],
              "age": int(ages[i]), "idx": i} for i in range(n)]
    return faces, props


def ingest(eng, faces, props) -> dict:
    """All faces into ``eng``; the last N_SUBMITTED through AddImage
    submits.  Returns eid -> dataset index."""
    n_direct = len(faces) - N_SUBMITTED
    for i in range(n_direct):
        eng.add_entity("image", faces[i], props[i])
    futs = [eng.submit([{"AddImage": {"properties": props[i],
                                      "data": faces[i]}}])
            for i in range(n_direct, len(faces))]
    for f in futs:
        f.result(TIMEOUT_S)
    return {eid: eng.meta.get(eid)["idx"] for eid in eng.meta.find("image")}


def expected_matches(props, query) -> list[int]:
    c = query[0]["FindImage"]["constraints"]
    cat, (_, lo, _, hi) = c["category"][1], c["age"]
    return [p["idx"] for p in props
            if p["category"] == cat and lo <= p["age"] <= hi]


def run_queries(sm, eng, queries: dict) -> dict:
    """Submit every query at once; returns name -> response.  Checks
    each future resolved with no failed entity."""
    failed_msgs = []

    def on_entity(ent):
        if ent.failed:
            failed_msgs.append(str(ent.failed))

    futs = {name: eng.submit(q, on_entity=on_entity)
            for name, q in queries.items()}
    out = {}
    for name, fut in futs.items():
        res = fut.result(TIMEOUT_S)
        out[name] = res
        sm.check(res["stats"]["failed"] == 0,
                 f"{name}: {res['stats']['matched']} matched, "
                 f"{res['stats']['failed']} failed")
    if failed_msgs:
        print(f"first entity failure: {failed_msgs[0][:2000]}")
    return out


def check_counts(sm, name, res, props, query):
    want = expected_matches(props, query)
    got = len(res["entities"])
    sm.check(got == len(want) and got > 0,
             f"{name}: {got} entities returned, {len(want)} expected")


def check_no_leaks(sm, name, eng):
    adm = eng.admission_stats()
    sm.check(adm["inflight"] == 0 and adm["pending"] == 0
             and adm["reserved"] == 0,
             f"{name}: admission inflight={adm['inflight']} "
             f"pending={adm['pending']} reserved={adm['reserved']} "
             f"(admitted {adm['admitted']}, completed {adm['completed']})")
    sm.check(eng.active_sessions() == 0,
             f"{name}: {eng.active_sessions()} sessions left open")
    if eng.device_backend is not None:
        pending = eng.device_backend.pending()
        sm.check(pending == 0, f"{name}: {pending} entities left on device")


def max_abs_err(got: dict, eids: dict, ref: np.ndarray, order: list) -> float:
    pos = {idx: k for k, idx in enumerate(order)}
    err = 0.0
    for eid, arr in got.items():
        want = ref[pos[eids[eid]]]
        arr = np.asarray(arr)
        if arr.shape != want.shape:
            return float("inf")
        err = max(err, float(np.max(np.abs(arr.astype(np.float64)
                                           - want.astype(np.float64)))))
    return err


def cpu_reference(faces, order, op: str) -> np.ndarray:
    """float32 jax.numpy of the composed native ops on the host CPU."""
    import jax
    from repro.kernels import ops as kops
    cpu = jax.devices("cpu")[0]
    x = jax.device_put(faces[np.asarray(order)], cpu)
    if op == "blur":
        (bl,) = BLUR
        out = kops.gaussian_blur(x, bl["ksize"], bl["sigma_x"], impl="ref")
    else:
        rs, cr, nm = PREPROCESS
        out = kops.fused_preprocess(
            x, resize_h=rs["height"], resize_w=rs["width"],
            crop_x=cr["x"], crop_y=cr["y"], crop_w=cr["width"],
            crop_h=cr["height"], mean=nm["mean"], std=nm["std"],
            impl="ref")
    return np.asarray(out)


# ------------------------------------------------------------- phases
def static_phase(sm, faces, props):
    from benchmarks.dispatch_bench import run_static_hash
    from repro.core.engine import VDMSAsyncEngine
    from repro.core.remote import TransportModel
    from repro.serving.frontend import WireClient, WireFrontend

    eng = VDMSAsyncEngine(
        num_remote_servers=2,
        transport=TransportModel(network_latency_s=0.001),
        admission="queue", max_inflight_entities=512)
    try:
        with sm.phase("ingest (static engine)"):
            eids = ingest(eng, faces, props)
            sm.check(len(eids) == len(faces),
                     f"ingest: {len(eids)} faces stored, "
                     f"{faces.nbytes / 2**30:.3f} GiB")
        with sm.phase("static: Fig-8 chain + IQ3 blur (first run)"):
            res = run_queries(sm, eng, STATIC_QUERIES)
            for name, q in STATIC_QUERIES.items():
                check_counts(sm, name, res[name], props, q)
            fig8 = res["fig8"]["entities"]
            binary = all(np.all((np.asarray(a) == 0) | (np.asarray(a) == 1))
                         and np.asarray(a).shape == (500, 400, 3)
                         for a in fig8.values())
            sm.check(binary, "fig8: every result is a binary 500x400x3 "
                             "threshold mask")
            fig8_ref = _fig8_cpu_reference(faces, eids, fig8)
            sm.check(fig8_ref <= 1e-3,
                     f"fig8: {fig8_ref:.2e} of pixels differ from the CPU "
                     f"reference (limit 1e-3: a pixel flips only where "
                     f"its resized value is within float error of the "
                     f"threshold or the detector's argmax ties)")
            order = sorted(eids[e] for e in res["iq3_blur"]["entities"])
            err = max_abs_err(res["iq3_blur"]["entities"], eids,
                              cpu_reference(faces, order, "blur"), order)
            print(f"max abs error blur (static engine, remote op): {err:.3e}")
            sm.check(err <= TOL["blur"],
                     f"iq3_blur: max abs error {err:.3e} <= {TOL['blur']}")
        with sm.phase("static: wire frontend on loopback"):
            front = WireFrontend(eng).start()
            client = WireClient(front.address)
            try:
                wired = client.execute(WIRE_QUERY, timeout=TIMEOUT_S)
            finally:
                client.close()
                front.close()
            local = eng.submit(WIRE_QUERY).result(TIMEOUT_S)
            same = (list(wired["entities"]) == list(local["entities"])
                    and all(np.array_equal(np.asarray(wired["entities"][k]),
                                           np.asarray(v))
                            for k, v in local["entities"].items()))
            sm.check(same and len(local["entities"]) > 0,
                     f"wire: {len(wired['entities'])} entities, "
                     f"byte-identical to the in-process response")
        with sm.phase("static: response hash"):
            row = run_static_hash()[0]
            print(f"static response sha256 {row['static_response_sha256']}")
            sm.check(row["baseline_sha256"] is not None
                     and row["static_matches_baseline"]
                     and row["static_matches_default_engine"],
                     f"static hash matches baseline "
                     f"{row['baseline_sha256']}")
        check_no_leaks(sm, "static engine", eng)
    finally:
        eng.shutdown()


def _fig8_cpu_reference(faces, eids, got) -> float:
    """Fraction of Fig-8 output pixels that differ from the same ops run
    with jax.numpy on the host CPU."""
    import jax
    from repro.core.pipeline import parse_operations, run_op
    ops = parse_operations(FIG8)
    cpu = jax.devices("cpu")[0]
    diff = total = 0
    with jax.default_device(cpu):
        for eid, arr in got.items():
            x = faces[eids[eid]]
            for op in ops:
                x = run_op(op, x)
            diff += int(np.sum(np.asarray(x) != np.asarray(arr)))
            total += np.asarray(arr).size
    return diff / max(total, 1)


def device_phase(sm, faces, props, *, platform="tpu",
                 num_device_workers=None, label="device"):
    """The device backend with the preprocess chain and blur pinned on
    it.  Returns name -> {eid: array}."""
    import jax
    from jax.sharding import SingleDeviceSharding
    from repro.core.engine import VDMSAsyncEngine
    from repro.core.remote import TransportModel
    from repro.query.device_backend import MultiDeviceBackend

    kw = {} if num_device_workers is None else {
        "num_device_workers": num_device_workers}
    eng = VDMSAsyncEngine(
        dispatch="cost", device_backend=platform, cost_overrides=DEVICE_PINS,
        num_remote_servers=1, transport=TransportModel(),
        device_max_wait_ms=20.0, admission="queue",
        max_inflight_entities=512, **kw)
    out = {}
    try:
        with sm.phase(f"ingest ({label} engine)"):
            eids = ingest(eng, faces, props)
        with sm.phase(f"{label}: preprocess chain + blur (first run)"):
            res = run_queries(sm, eng, DEVICE_QUERIES)
        with sm.phase(f"{label}: compare with CPU reference"):
            for name, q in DEVICE_QUERIES.items():
                check_counts(sm, name, res[name], props, q)
                got = res[name]["entities"]
                order = sorted(eids[e] for e in got)
                err = max_abs_err(got, eids,
                                  cpu_reference(faces, order, name), order)
                print(f"max abs error {name} (device backend, {label}): "
                      f"{err:.3e}")
                sm.check(err <= TOL[name],
                         f"{name}: max abs error {err:.3e} <= {TOL[name]}")
                out[name] = {e: np.asarray(a) for e, a in got.items()}
        with sm.phase(f"{label}: kernels in the segment programs"):
            dev = eng.device_backend
            workers = (dev.workers if isinstance(dev, MultiDeviceBackend)
                       else [dev])
            # the programs the workers ran, keyed by segment signature
            programs = {key: (w.device, program) for w in workers
                        for key, program in w._jit_cache.items()}
            sm.check(len(programs) == len(DEVICE_QUERIES),
                     f"{len(programs)} segment programs built")
            for key, (device, program) in programs.items():
                arg = jax.ShapeDtypeStruct(
                    (8, FACE, FACE, 3), np.float32,
                    sharding=SingleDeviceSharding(device))
                text = program.lower(arg).compile().as_text()
                calls = text.count('custom_call_target="tpu_custom_call"')
                names = "->".join(sig[0] for sig in key)
                sm.check(calls >= 1, f"segment {names}: {calls} Pallas "
                                     f"call(s) in the compiled program")
        stats = eng.dispatch_stats()["device"]
        want_ents = sum(len(v) for v in out.values())
        sm.check(stats["platform"] == platform and stats["calibrated"]
                 and stats["errors"] == 0 and stats["fused_segments"] > 0
                 and stats["h2d_bytes"] > 0
                 and stats["entities_run"] == want_ents,
                 f"{label} stats: platform={stats['platform']} "
                 f"calibrated={stats['calibrated']} "
                 f"errors={stats['errors']} "
                 f"fused_segments={stats['fused_segments']} "
                 f"entities_run={stats['entities_run']} "
                 f"compiles={stats['compiles']} "
                 f"h2d_bytes={stats['h2d_bytes']} "
                 f"d2h_bytes={stats['d2h_bytes']}")
        for p in stats.get("per_device", []):
            print(f"  worker {p['device']}: platform={p['platform']} "
                  f"groups_run={p['groups_run']} "
                  f"entities_run={p['entities_run']} "
                  f"errors={p['errors']}")
        out["_stats"] = stats
        check_no_leaks(sm, f"{label} engine", eng)
    finally:
        eng.shutdown()
    return out


def compare_runs(sm, a: dict, b: dict, what: str):
    same = True
    for name in DEVICE_QUERIES:
        x, y = a.get(name, {}), b.get(name, {})
        same = same and list(x) == list(y) and len(x) > 0 and all(
            np.array_equal(x[k], y[k]) for k in x)
    sm.check(same, what)


# --------------------------------------------------------------- main
def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the device phase, on all four chips "
                         "and on one worker, and compare them")
    args = ap.parse_args()

    import jax
    devices = jax.devices()
    first = devices[0]
    if first.platform != "tpu":
        print(f"FAIL: no TPU: jax.devices()[0] is {first.platform} "
              f"({first.device_kind})", file=sys.stderr)
        return 1
    if args.four_chips and len(devices) < 4:
        print(f"FAIL: --four-chips needs 4 TPU devices, found "
              f"{len(devices)}", file=sys.stderr)
        return 1
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    from repro.launch.compile_cache import use_compile_cache
    print(f"devices: {len(devices)} x {first.device_kind} "
          f"({first.platform}); compile cache: {use_compile_cache()}")

    sm = Smoke()
    with sm.phase("generate dataset"):
        faces, props = make_dataset(args.seed)
    if not sm.failures:
        if args.four_chips:
            multi = device_phase(sm, faces, props, label="device x4")
            per = multi.get("_stats", {}).get("per_device", [])
            distinct = {p["device"] for p in per
                        if p["platform"] == "tpu" and p["groups_run"] > 0}
            sm.check(len(distinct) == 4,
                     f"groups ran on {len(distinct)} distinct TPU devices")
            single = device_phase(sm, faces, props, num_device_workers=1,
                                  label="device x1")
            compare_runs(sm, multi, single,
                         "4-worker results identical to 1-worker results")
        else:
            static_phase(sm, faces, props)
            device_phase(sm, faces, props)

    print("set-up times (s, wall, compiles included; not speed results): "
          + json.dumps({k: round(v, 3) for k, v in sm.times.items()}))
    if sm.failures:
        print(f"FAILED: {len(sm.failures)} check(s)", file=sys.stderr)
        for f in sm.failures:
            print(f"  {f}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True,
                      "device": {"platform": first.platform,
                                 "kind": first.device_kind,
                                 "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
