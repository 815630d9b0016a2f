"""Bring-up smoke: the AQP server's main path, end to end, on one TPU chip.

Run from the repository root on a machine with a TPU:

    python chip_smoke.py

One process, the only one that touches JAX. Phases, each printing its wall
time and counts on one line:

  device        the first JAX device must be a TPU; anything else exits
                non-zero before any other phase runs (no fallback).
  ingest        registers ``power`` (500,000 x 10) and ``flights``
                (500,000 x 12, nulls and categoricals) through
                ``AQPServer.register_table`` with GreedyGD compression and the
                default ``BuildParams``.
  serve         generated queries, AND-chain waves and one GROUP BY through
                ``submit``/``query_batch`` in auto mode, which must resolve to
                the Pallas kernel. Fused launches must happen on both tables,
                no wave may fail, and every answer must match the same
                catalog served in ``mode="numpy"`` to rtol=1e-4, atol=1e-6.
                The median relative error against the exact engine is
                printed, not gated.
  construction  rebuilds ``power`` with ``BuildParams(use_pallas=True)``; the
                synopsis must be bit-identical to the default build.

The last line of stdout is ``{"ok": true, "device": {...}}``, printed only
when every phase passed.
"""
from __future__ import annotations

import collections
import dataclasses
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

RTOL, ATOL = 1e-4, 1e-6        # tests/test_serving.py's kernel tolerance
N_ROWS = 500_000
N_GENERATED = 150              # generated queries per table
WAVE = 64                      # queries per AND-chain wave (max_batch)
SEED = 0


class SmokeFailure(Exception):
    """A phase's check failed."""


def log(phase: str, t0: float, **counts):
    fields = " ".join(f"{k}={v}" for k, v in counts.items())
    print(f"phase={phase} wall_s={time.perf_counter() - t0:.3f} {fields}",
          flush=True)


def check_device() -> dict:
    import jax

    t0 = time.perf_counter()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SmokeFailure(f"JAX platform is {dev.platform!r}, not 'tpu': "
                           "this smoke runs only on a TPU chip")
    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}
    log("device", t0, platform=info["platform"],
        kind=repr(info["kind"]), count=info["count"])
    return info


def ingest(srv, name: str, table: dict, params):
    t0 = time.perf_counter()
    srv.register_table(name, table, params=params, use_compression=True)
    fw = srv.catalog.resolve(name)
    log("ingest", t0, table=name, rows=len(next(iter(table.values()))),
        cols=len(table), compress_s=f"{fw.timings['compress_s']:.3f}",
        build_s=f"{fw.timings['build_synopsis_s']:.3f}",
        synopsis_bytes=fw.size_bytes())


# Same-shape AND chains: each wave is one plan shape, so it fuses into one
# batched launch. (agg, column, [(column, op), ...]) per table.
AND_CHAINS = {
    "power": [
        ("AVG", "global_active_power",
         [("voltage", ">"), ("global_intensity", "<")]),
        ("SUM", "sub_metering_3",
         [("global_active_power", ">="), ("voltage", "<"),
          ("global_reactive_power", ">")]),
    ],
    "flights": [
        ("AVG", "arr_delay", [("distance", ">"), ("dep_delay", "<")]),
        ("COUNT", "air_time",
         [("distance", "<="), ("taxi_out", ">"), ("sched_dep", ">=")]),
    ],
}
GROUP_BY = {"flights": ["SELECT AVG(arr_delay) FROM flights "
                        "WHERE distance > 500 GROUP BY airline"]}


def and_chain_waves(name: str, table: dict, rng) -> list[list[str]]:
    import numpy as np

    waves = []
    for func, col, preds in AND_CHAINS[name]:
        wave = []
        for _ in range(WAVE):
            conds = []
            for pcol, op in preds:
                x = np.asarray(table[pcol], np.float64)
                v = np.quantile(x[np.isfinite(x)], rng.uniform(0.1, 0.9))
                conds.append(f"{pcol} {op} {v:.3f}")
            wave.append(f"SELECT {func}({col}) FROM {name} WHERE "
                        + " AND ".join(conds))
        waves.append(wave)
    return waves


def _close(a, b) -> bool:
    """Both None at the same places, else within RTOL/ATOL."""
    import numpy as np

    if (a is None) != (b is None):
        return False
    return a is None or bool(np.isclose(a, b, rtol=RTOL, atol=ATOL))


def _tol_ratio(a, b) -> float:
    """|a - b| over the allowed deviation (<= 1 passes)."""
    if a is None or b is None:
        return 0.0
    return abs(a - b) / (ATOL + RTOL * abs(b))


def compare(got, want) -> tuple[int, float]:
    """Count answers outside tolerance; the worst tolerance ratio."""
    bad, worst = 0, 0.0
    for g, w in zip(got, want):
        if g.failed or w.failed:
            bad += 1
            continue
        if w.groups is not None:
            pairs = [(g.groups.get(k, (None,) * 3), v)
                     for k, v in w.groups.items()]
            bad += int(set(g.groups) != set(w.groups))
        else:
            pairs = [(g.as_tuple(), w.as_tuple())]
        for gt, wt in pairs:
            for a, b in zip(gt, wt):
                bad += int(not _close(a, b))
                worst = max(worst, _tol_ratio(a, b))
    return bad, worst


def serve(srv, ref, name: str, table: dict, rng):
    import numpy as np

    from repro.aqp.exact import ExactEngine
    from repro.aqp.queries import generate_queries, relative_error

    t0 = time.perf_counter()
    generated = generate_queries(table, N_GENERATED, seed=SEED,
                                 table_name=name)
    waves = and_chain_waves(name, table, rng)
    group_by = GROUP_BY.get(name, [])
    t_gen = time.perf_counter() - t0

    t1 = time.perf_counter()
    got = srv.query_batch(generated)
    for wave in waves:                       # streaming: futures per query
        futures = [srv.submit(sql) for sql in wave]
        srv.flush()
        got += [f.result() for f in futures]
    got += srv.query_batch(group_by)
    t_serve = time.perf_counter() - t1
    sqls = generated + [s for w in waves for s in w] + group_by

    want = ref.query_batch(sqls)
    bad, worst = compare(got, want)

    exact = ExactEngine(table)
    errs = [relative_error(r.estimate, exact.query(s))
            for s, r in zip(sqls, got) if r.groups is None]
    st = srv.stats()
    tm = st["tables"][name]
    faults = st["totals"]["faults"]
    log("serve", t0, table=name, mode=srv.scheduler.mode,
        queries=len(sqls), generated=len(generated),
        and_chain=sum(len(w) for w in waves), group_by=len(group_by),
        executed=tm["queries_executed"], batched=tm["batched"],
        fallback=tm["fallback"], wave_errors=faults["wave_errors"],
        query_errors=faults["query_errors"], outside_tol=bad,
        worst_tol_ratio=f"{worst:.4g}",
        median_rel_err_pct=f"{float(np.median(errs)):.4g}",
        gen_s=f"{t_gen:.3f}", serve_s=f"{t_serve:.3f}")
    if srv.scheduler.mode != "pallas":
        raise SmokeFailure(f"scheduler auto mode is {srv.scheduler.mode!r}")
    if tm["batched"] <= 0:
        raise SmokeFailure(f"{name}: no fused kernel launch happened")
    if faults["wave_errors"] or faults["query_errors"]:
        raise SmokeFailure(f"{name}: execution errors were caught: {faults}")
    if bad:
        raise SmokeFailure(f"{name}: {bad} answers outside rtol={RTOL}, "
                           f"atol={ATOL} of mode='numpy' (worst ratio "
                           f"{worst:.4g})")


def _same(a, b) -> bool:
    import numpy as np

    a, b = np.asarray(a), np.asarray(b)
    return (a.shape == b.shape and a.dtype == b.dtype
            and np.array_equal(a, b, equal_nan=a.dtype.kind == "f"))


def construction(srv):
    from repro.core.build import build_pairwise_hist

    t0 = time.perf_counter()
    fw = srv.catalog.resolve("power")
    params = dataclasses.replace(fw.params, use_pallas=True)
    syn = build_pairwise_hist(fw.compressed, fw.preprocessed.columns, params)
    t_build = time.perf_counter() - t0
    base = fw.synopsis
    fields = [_same(a, b) for h, g in zip(syn.hists, base.hists)
              for a, b in zip(h, g)]
    same_keys = set(syn.pairs) == set(base.pairs)
    if same_keys:
        fields += [_same(a, b) for k in base.pairs
                   for a, b in zip(syn.pairs[k], base.pairs[k])]
    fields.append(_same(syn.chi2_table, base.chi2_table))
    mismatched = fields.count(False) + int(not same_keys)
    log("construction", t0, table="power", use_pallas=True,
        build_s=f"{t_build:.3f}", pairs=len(syn.pairs),
        arrays_compared=len(fields), mismatched=mismatched,
        pair_mode=syn.build_stats.get("mode", ""))
    if mismatched:
        raise SmokeFailure(f"use_pallas=True build differs from the default "
                           f"build in {mismatched} arrays")


def run_phases(n_rows: int = N_ROWS, params=None, mode=None):
    """Ingest, serve and construction phases on the current JAX backend.

    ``mode=None`` is the server's auto mode, as a user gets it.
    """
    import numpy as np

    from repro.aqp.datasets import load
    from repro.core.types import BuildParams
    from repro.serve.aqp import AQPServer

    params = params or BuildParams()
    tables = {name: load(name, n=n_rows) for name in ("power", "flights")}
    srv = AQPServer(mode=mode)
    ref = AQPServer(catalog=srv.catalog, mode="numpy")
    try:
        for name, table in tables.items():
            ingest(srv, name, table, params)
        rng = np.random.default_rng(SEED)
        for name, table in tables.items():
            serve(srv, ref, name, table, rng)
        construction(srv)
    finally:
        ref.close()
        srv.close()


def main() -> int:
    t0 = time.perf_counter()
    try:
        from repro.device import use_compile_cache
    except ImportError as exc:
        print(f"chip_smoke: cannot import the repository's code: {exc}",
              file=sys.stderr)
        return 2
    cache_dir = use_compile_cache()
    import jax

    events = collections.Counter()
    jax.monitoring.register_event_listener(
        lambda event, **_: events.update([event]))
    try:
        device = check_device()
        run_phases()
    except SmokeFailure as exc:
        print(f"chip_smoke: FAILED: {exc}", file=sys.stderr)
        return 1
    log("total", t0, compile_cache=cache_dir,
        cache_hits=events["/jax/compilation_cache/cache_hits"],
        cache_misses=events["/jax/compilation_cache/cache_misses"])
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
