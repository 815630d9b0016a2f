"""Benchmark driver: one module per paper table/figure + the roofline reader.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--only NAME]

Prints ``name,us_per_call,derived`` CSV rows (harness contract) and writes
JSON artifacts to benchmarks/results/.
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback

SUITES = ("storage", "fig8", "fig9", "table5", "table6", "fig11",
          "roofline")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="reduced sizes (CI-fast)")
    ap.add_argument("--only", default=None,
                    help=f"comma-separated subset of {SUITES}")
    args = ap.parse_args(argv)
    suites = args.only.split(",") if args.only else list(SUITES)

    rows: list[str] = []
    failures = []
    print("name,us_per_call,derived")
    for name in suites:
        mod_name = f"benchmarks.roofline" if name == "roofline" \
            else f"benchmarks.bench_{name}"
        t0 = time.time()
        try:
            mod = __import__(mod_name, fromlist=["run"])
            before = len(rows)
            mod.run(rows, quick=args.quick)
            for row in rows[before:]:
                print(row)
            print(f"# {name}: {time.time()-t0:.1f}s", file=sys.stderr)
        except Exception:
            failures.append(name)
            print(f"# {name} FAILED:\n{traceback.format_exc()}",
                  file=sys.stderr)
    if failures:
        print(f"# FAILED suites: {failures}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
