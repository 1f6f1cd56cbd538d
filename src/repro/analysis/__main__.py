"""CLI: ``python -m repro.analysis <paths> [options]``.

Examples::

    python -m repro.analysis src/                     # human-readable
    python -m repro.analysis src/ --format=json       # machine-readable
    python -m repro.analysis src/ --check-baseline    # CI gate
    python -m repro.analysis src/ --dot locks.dot     # lock-order graph
    python -m repro.analysis src/ --write-baseline    # accept current

Exit status: 0 when clean (or every finding is baselined under
``--check-baseline``), 1 when live findings remain, 2 on usage errors.
"""
from __future__ import annotations

import argparse
import json
import sys

from repro.analysis.runner import (check_baseline, load_baseline,
                                   run_analysis, write_baseline)

DEFAULT_BASELINE = "analysis_baseline.json"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis",
        description="Static concurrency & convention analyzer")
    ap.add_argument("paths", nargs="+", help="files or directories to scan")
    ap.add_argument("--format", choices=("text", "json"), default="text")
    ap.add_argument("--check-baseline", action="store_true",
                    help="fail only on findings not in the baseline")
    ap.add_argument("--baseline", default=DEFAULT_BASELINE,
                    help=f"baseline path (default: {DEFAULT_BASELINE})")
    ap.add_argument("--write-baseline", action="store_true",
                    help="accept all current findings into the baseline")
    ap.add_argument("--json-out", default=None,
                    help="also write the full JSON report here")
    ap.add_argument("--dot", default=None,
                    help="write the lock-order graph as DOT here")
    ap.add_argument("--ref-dirs", nargs="*", default=["tests", "bench"],
                    help="dirs scanned for knob references")
    args = ap.parse_args(argv)

    import os
    ref_dirs = [d for d in args.ref_dirs if os.path.isdir(d)]
    result = run_analysis(args.paths, ref_dirs=ref_dirs)

    if args.dot:
        with open(args.dot, "w", encoding="utf-8") as fh:
            fh.write(result.graph.to_dot())

    new, stale = result.findings, []
    baseline_note = ""
    if args.check_baseline:
        try:
            baseline = load_baseline(args.baseline)
        except FileNotFoundError:
            print(f"error: baseline {args.baseline!r} not found "
                  f"(run with --write-baseline to create it)",
                  file=sys.stderr)
            return 2
        except (ValueError, json.JSONDecodeError) as e:
            print(f"error: bad baseline {args.baseline!r}: {e}",
                  file=sys.stderr)
            return 2
        new, stale = check_baseline(result, baseline)
        baseline_note = (f" ({len(result.findings) - len(new)} baselined"
                         + (f", {len(stale)} stale baseline entries"
                            if stale else "") + ")")

    if args.json_out:
        report = result.to_dict()
        report["new_findings"] = [f.to_dict() for f in new]
        report["stale_baseline"] = stale
        with open(args.json_out, "w", encoding="utf-8") as fh:
            json.dump(report, fh, indent=2)
            fh.write("\n")

    if args.write_baseline:
        write_baseline(args.baseline, result)
        print(f"wrote {len(result.findings)} fingerprint(s) to "
              f"{args.baseline}")
        return 0

    if args.format == "json":
        report = result.to_dict()
        report["new_findings"] = [f.to_dict() for f in new]
        report["stale_baseline"] = stale
        json.dump(report, sys.stdout, indent=2)
        print()
    else:
        for f in new:
            print(f.render())
        if args.check_baseline and stale:
            for fp in stale:
                print(f"note: stale baseline entry {fp} (no longer fires)")
        n_sup = len(result.suppressed)
        print(f"{result.files} file(s): {len(new)} finding(s)"
              + baseline_note
              + (f", {n_sup} waived" if n_sup else ""))
    return 1 if new else 0


if __name__ == "__main__":
    sys.exit(main())
