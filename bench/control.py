#!/usr/bin/env python3
"""Readings that the limits of ``bench/limits/<cell>.json`` are set from.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds <n> [<n> ...]

For each seed, in this one process (which holds the chip): one run of
the cell at its own load and sizes, whose compared numbers are the
program's readings, and the control on the same compared entities: the
plain reference computed in bfloat16 on the chip, the next precision
below the float32 the configurations state, put in the program's place
(a configuration's own op by its ``references/<op>.py``, given the same
weights).
One JSON line per seed on stdout; the benchmark's own runs never run the
control.
"""
from __future__ import annotations

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness
    cell = harness.load_cell(args.workload)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"no TPU: jax.devices()[0] is {devices[0].platform}",
              file=sys.stderr)
        return 2
    harness.use_compile_cache()
    for seed in args.seeds:
        res = harness.run(cell, seed, args.seconds, False,
                          t_process=time.monotonic(),
                          devices=devices[:cell.chips],
                          log=lambda m: print(m, file=sys.stderr, flush=True),
                          control=True)
        print(json.dumps(harness.finite({
            "cell": cell.name, "seed": seed, "correct": res["correct"],
            "attempted": res["attempted"],
            "program": {k: v["value"] for k, v in res["checks"].items()},
            "control": res["_control"]})), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
