#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip this process holds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``; its configuration, traffic
mix, limits and per-layer readers are files under ``bench/`` found by
name.  The run generates the collection from ``--seed``, warms up, drives
the window for ``--seconds``, checks what the window produced against the
plain reference, and prints the compared numbers with their limits as
the last lines of stderr and one JSON object as the last line of stdout.
``--trace 0`` reports the end-to-end metrics, ``--trace 1`` the
per-layer ones from a profiler trace of part of the window.

It exits non-zero, and prints no result, where JAX finds no TPU or fewer
chips than the cell asks for.
"""
from __future__ import annotations

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402 — the set-up clock starts before imports
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-seconds", type=float, default=3.0,
                    help="length of the traced part of the window")
    ap.add_argument("--keep-trace", default=None,
                    help="write the trace here and keep it")
    args = ap.parse_args(argv)

    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness
    cell = harness.load_cell(args.workload)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        log(f"no TPU: jax.devices()[0] is {devices[0].platform} "
            f"({devices[0].device_kind}); the benchmark runs on the chip "
            f"only")
        return 2
    if len(devices) < cell.chips:
        log(f"cell {cell.name} needs {cell.chips} chips, JAX finds "
            f"{len(devices)}")
        return 2
    log(f"devices: {len(devices)} x {devices[0].device_kind}; "
        f"compile cache: {harness.use_compile_cache()}")
    result = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                         t_process=T_PROCESS, devices=devices[:cell.chips],
                         log=log, trace_seconds=args.trace_seconds,
                         keep_trace=args.keep_trace)
    harness.print_result(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
