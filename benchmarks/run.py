"""Benchmark harness — one function per paper table/figure.
Prints ``name,us_per_call,derived`` CSV (plus a JSON sidecar with the
full per-row details under experiments/bench/).

  PYTHONPATH=src python -m benchmarks.run            # fast suite
  PYTHONPATH=src python -m benchmarks.run --full     # larger sizes
  PYTHONPATH=src python -m benchmarks.run --only scaleout
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


def main() -> None:
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None,
                    help="image|video|cputrace|scaleout|roofline|fusion|"
                         "serving|native_pool|hotpath|dispatch")
    args = ap.parse_args()

    from benchmarks import cpu_trace, image_suite, scaleout, video_suite
    from benchmarks import roofline as roofline_mod

    suites = {}
    if args.full:
        suites["image"] = lambda: (image_suite.run_c1(48)
                                   + image_suite.run_c2(48)
                                   + image_suite.run_c3(24, clients=(2, 4, 8)))
        suites["video"] = lambda: (video_suite.run_c1(6, 8)
                                   + video_suite.run_c2(6, 8)
                                   + video_suite.run_c3(4, 6, clients=(2, 4)))
        # kappa remote-server curve + sharded-cluster shard curve +
        # shard-off identity; also writes repo-root BENCH_scaleout.json
        suites["scaleout"] = lambda: scaleout.run(smoke=False)
    else:
        suites["image"] = lambda: (
            image_suite.run_c1(16, queries=dict(list(
                image_suite.image_queries().items())[:4]))
            + image_suite.run_c2(16) + image_suite.run_c3(8, clients=(2, 4)))
        suites["video"] = lambda: (
            video_suite.run_c1(3, 4, queries=dict(list(
                video_suite.video_queries().items())[:3]))
            + video_suite.run_c2(3, 4) + video_suite.run_c3(2, 3, clients=(2,)))
        suites["scaleout"] = lambda: scaleout.run(smoke=True)
    suites["cputrace"] = lambda: cpu_trace.run()
    from benchmarks import serving_bench
    suites["serving"] = lambda: serving_bench.run()
    suites["native_pool"] = lambda: serving_bench.run_native_pool(
        n_images=48 if args.full else 24,
        sessions=4 if args.full else 2)
    from benchmarks import hotpath
    # also writes repo-root BENCH_hotpath.json (perf trajectory across PRs)
    suites["hotpath"] = lambda: hotpath.run(smoke=not args.full)
    from benchmarks import dispatch_bench
    # also writes repo-root BENCH_dispatch.json (cost-router speedup vs
    # all-native/static + the static-response hash tripwire)
    suites["dispatch"] = lambda: dispatch_bench.run(smoke=not args.full)
    suites["fusion"] = lambda: (
        image_suite.run_c2(16, fuse=False)
        + [dict(r, name=r["name"] + "_fused")
           for r in image_suite.run_c2(16, fuse=True, batch_remote=8)])
    if os.path.isdir("experiments/dryrun_final"):
        suites["roofline"] = roofline_mod.run

    rows = []
    for name, fn in suites.items():
        if args.only and name != args.only:
            continue
        print(f"# running suite: {name}", file=sys.stderr, flush=True)
        try:
            rows.extend(fn())
        except Exception as e:  # noqa: BLE001 — report and continue
            import traceback
            traceback.print_exc()
            rows.append({"name": f"{name}_FAILED", "us_per_call": -1,
                         "derived": 0.0, "error": str(e)})

    os.makedirs("experiments/bench", exist_ok=True)
    with open("experiments/bench/results.json", "w") as f:
        json.dump(rows, f, indent=2, default=float)

    print("name,us_per_call,derived")
    for r in rows:
        print(f"{r['name']},{r['us_per_call']:.1f},{r['derived']:.4f}")


if __name__ == "__main__":
    main()
