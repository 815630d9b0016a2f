"""Run one benchmark cell once.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout, on a machine with the chips the cell asks for.
Set-up (device start, data generation from the seed, GreedyGD compression,
synopsis build, statement generation, warm-up of every shape the window can
launch) counts as ``setup_s``; then the window runs for ``--seconds``, and
the answers it produced are checked against the exact reference. With
``--trace 0`` the last line of stdout carries the cell's end-to-end metrics,
with ``--trace 1`` its per-layer metrics read from spans, counters and a
device trace of the window. The compared numbers, each beside its limit,
are the last lines of stderr and the last key of the result.

Exits non-zero, printing no result, where JAX finds no TPU or fewer chips
than the cell needs, or where the manifest or a file it names is missing.
"""
from __future__ import annotations

import time

T_PROC0 = time.perf_counter()

import argparse  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[0] = str(ROOT)                     # import the benchmark as ``bench``
sys.path.insert(1, str(ROOT / "src"))       # and the system under test


def parse(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    from bench import harness as hs

    try:
        cell = hs.cell(args.workload)
        kind = hs.load_module(hs.BENCH / "kinds" / f"{cell.kind}.py",
                              f"bench_kind_{cell.kind}")
        hs.pin_compile_cache()
        from repro.device import use_compile_cache

        cache = use_compile_cache()
        import jax

        # Every program the window can launch is kept, however quickly it
        # compiled, so later runs in this checkout load it instead.
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        print(f"compile cache: {cache}", file=sys.stderr)
        if hasattr(kind, "run"):
            out = kind.run(cell, args.seed, args.seconds, bool(args.trace),
                           T_PROC0)
        else:
            from bench import serving

            out = serving.run(cell, kind, args.seed, args.seconds,
                              bool(args.trace), T_PROC0)
    except (hs.BenchError, ImportError) as exc:
        print(f"bench: {exc}", file=sys.stderr)
        return 2
    result, checks, lines = out
    hs.emit(result, checks, lines)
    return 0


if __name__ == "__main__":
    sys.exit(main())
