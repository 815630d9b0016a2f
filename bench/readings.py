"""Readings that a cell's correctness limits are set from, in one process.

    python bench/readings.py --workload <name> --seconds 10 --seeds 1 2 3 \
        --control-seeds 4 5 6 [--precision default]

Runs the cell once per seed and prints one JSON line per run with the
compared numbers. The data and the traffic are the configuration's in every
run (a serving cell's traffic drawn for the manifest's ``run_seconds`` and
sent for ``--seconds``, so the checked statements are those of a
benchmark run); the seed draws the synopsis's sample (``BuildParams.seed``), so each
sound run reads another synopsis of the same table, as successive rebuilds
of a deployment would. For each of ``--control-seeds`` the synopsis is built
with the configuration's ``control`` build parameters instead: a run that
breaks one guarantee the configuration states (its sample size), which the
comparison has to refuse. ``--precision default`` runs every launch of the
weightings kernel at the MXU's default precision instead of the stated
``HIGHEST``: the step down that would tempt a later change. The
benchmark's own runs never run either.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[0] = str(ROOT)
sys.path.insert(1, str(ROOT / "src"))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seconds", type=float, default=10.0)
    p.add_argument("--seeds", type=int, nargs="*", default=[])
    p.add_argument("--control-seeds", type=int, nargs="*", default=[])
    p.add_argument("--precision", choices=("highest", "default"),
                   default="highest")
    args = p.parse_args(argv)

    from bench import harness as hs
    from bench import serving
    from repro.device import use_compile_cache

    hs.pin_compile_cache()
    use_compile_cache()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if args.precision == "default":
        from repro.kernels.weightings import weightings

        weightings._PRECISION = jax.lax.Precision.DEFAULT
    cell = hs.cell(args.workload)
    kind = hs.load_module(hs.BENCH / "kinds" / f"{cell.kind}.py",
                          f"bench_kind_{cell.kind}")
    control = cell.config["control"]["build_params"]
    runs = [(s, {"seed": s}) for s in args.seeds] + \
        [(s, {**control, "seed": s}) for s in args.control_seeds]
    for seed, params in runs:
        t0 = time.perf_counter()
        if hasattr(kind, "run"):
            result, checks, lines = kind.run(cell, seed, args.seconds, False,
                                             t0, build_params=params)
        else:
            result, checks, lines = serving.run(
                cell, kind, seed, args.seconds, False, t0,
                build_params=params,
                horizon=hs.manifest()["run_seconds"])
        for line in lines:
            print(line, file=sys.stderr)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control": len(params) > 1,
                          "precision": args.precision,
                          "correct": result["correct"],
                          "metrics": result["metrics"], "checks": checks,
                          "device": result["device"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
