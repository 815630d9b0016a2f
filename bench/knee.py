"""Knee sweep of an open-loop cell: one set-up, then the cell's traffic at
each of a list of rates, in one process on the chip.

    python bench/knee.py --workload <open cell> --seed <n> --seconds 15 \
        --rates 100 200 400 800

For each rate it prints the p95 latency from due time (failed or refused
statements count as beyond any limit), the p95 of the first and the second
half of the window, the refused count and the backlog (statements submitted
and not yet answered) at the window's close. The knee is the highest rate
the server sustains without a queue building: nothing refused, a
second-half p95 within twice the first half's, and a p95 within twice the
p95 at the lowest rate swept and within ``--limit-ms`` (default 100, the
server's ``slow_query_ms``). Above it the queue, not the work, sets the
tail, and the tail swings from run to run. A cell is then set at 0.8 of
the knee, a rate written into its traffic file.
"""
from __future__ import annotations

import time

T_PROC0 = time.perf_counter()

import argparse  # noqa: E402
import concurrent.futures  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[0] = str(ROOT)
sys.path.insert(1, str(ROOT / "src"))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, default=15.0)
    p.add_argument("--rates", type=float, nargs="+", required=True)
    p.add_argument("--limit-ms", type=float, default=100.0)
    args = p.parse_args(argv)

    import numpy as np

    from bench import harness as hs
    from bench import serving, stats
    from repro.device import use_compile_cache

    hs.pin_compile_cache()
    use_compile_cache()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = hs.cell(args.workload)
    kind = hs.load_module(hs.BENCH / "kinds" / f"{cell.kind}.py",
                          f"bench_kind_{cell.kind}")
    device = hs.device_info(cell.chips)
    table = hs.generate_table(cell.config)
    sample = hs.Sample(table, args.seed)
    srv = serving.build_server(cell, table)
    name = cell.config["table"]
    scheds = [kind.schedule(dict(cell.traffic, rate_per_s=rate), sample,
                            hs.rng(args.seed + i, "traffic"), args.seconds)
              for i, rate in enumerate(args.rates)]
    groups = kind.warmup(cell.traffic, sample, hs.rng(args.seed, "warm"),
                         srv.admission.max_batch,
                         {st for sched in scheds for _, st in sched})
    serving.warm(srv, name, groups)
    print(f"set-up {time.perf_counter() - T_PROC0:.1f} s on {device}",
          file=sys.stderr, flush=True)
    rows = []
    for rate, sched in zip(args.rates, scheds):
        due = np.asarray([d for d, _ in sched])
        sqls = [s.sql(name) for _, s in sched]
        t0 = time.perf_counter()
        futs, done, late, _ = serving.drive_open(srv, sqls, due, t0, None)
        backlog = sum(1 for f in futs if not f.done())
        concurrent.futures.wait(futs, timeout=serving.DRAIN_S)
        outs = [serving.outcome(f) for f in futs]
        lat = np.asarray([(done[j] - t0 - due[j]) * 1e3
                          if outs[j][0] is not None else math.inf
                          for j in range(len(futs))])
        half = due < args.seconds / 2
        row = {"rate": rate, "statements": len(futs),
               "statements_per_s": len(futs) / args.seconds,
               "p50_ms": stats.percentile(lat, 50),
               "p95_ms": stats.percentile(lat, 95),
               "p95_first_half_ms": stats.percentile(lat[half], 95),
               "p95_second_half_ms": stats.percentile(lat[~half], 95),
               "refused": sum(1 for o in outs if o[2]),
               "failed": sum(1 for o in outs if o[1]),
               "backlog_at_close": backlog,
               "submit_late_p99_ms": stats.percentile(late, 99) * 1e3}
        light = rows[0]["p95_ms"] if rows else row["p95_ms"]
        row["ok"] = (row["p95_ms"] <= min(args.limit_ms, 2 * light)
                     and not row["refused"] and not row["failed"]
                     and row["p95_second_half_ms"]
                     <= 2 * row["p95_first_half_ms"])
        rows.append(row)
        print(json.dumps({k: (hs.finite(v) if isinstance(v, float) else v)
                          for k, v in row.items()}), flush=True)
    srv.close()
    ok = [r["rate"] for r in rows if r["ok"]]
    print(json.dumps({"workload": args.workload, "device": device,
                      "knee": max(ok) if ok else None}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
