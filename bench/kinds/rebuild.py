"""Back-to-back synopsis rebuilds from the table's ``CompressedTable``.

Set-up ingests the generated table once (GreedyGD compression and a first
build, which compiles every program a build uses). The window then rebuilds
the synopsis through ``AQPFramework.ingest_compressed`` from the same
compressed store and sampling seed, one build after another; no build
starts after ``--seconds``. ``build_s`` is the whole window, up to the end of
the last build, over the builds.

Each timed build's synopsis answers a fixed set of probe statements
(``probes``: templates in the ``open_templates`` form, ``probe_statements``
of them, vetted like open traffic) on the program's host query path. The
table, the build's sample and the probes are the configuration's, so every
seed does the same work and reads the same errors. The compared numbers are
the worst build's median relative error of the AVG probes against the
exact reference, the builds that failed, and the builds that left the
synopsis they started from in place. AVG, because it is the aggregate that
a smaller sample moves: a ratio, the histograms' own coarseness largely
cancels in it, and a build from 1 % of the sample reads 14 times the sound
error there. The COUNT and SUM probes read no better from the whole sample
than from 1 % of it: their error is the histograms' resolution on the
low-cardinality columns they filter (month, day, the sub-meterings), which
no sample size changes, and a literal on one of those values splits its
mass across a bin (PERF.md).

Parameters (``bench/traffic/<name>.json``): ``probes``,
``probe_statements``, ``literal_quantiles``, ``min_sample_rows``,
``limits``.
"""
from __future__ import annotations

import gc
import shutil
import tempfile
import time

import numpy as np

from bench import harness as hs
from bench import stats, tracing
from bench.kinds.open_templates import shares, statement
from bench.reference import ExactTable


def probes(traffic: dict, sample, rng) -> list:
    tpls = traffic["probes"]
    counts = shares(int(traffic["probe_statements"]), np.ones(len(tpls)))
    q_lo, q_hi = traffic["literal_quantiles"]
    out = []
    for t, c in zip(tpls, counts):
        lits = sample.vetted(t["preds"], int(c), rng, q_lo, q_hi,
                             traffic["min_sample_rows"])
        out += [statement(t, row) for row in lits]
    return out


def build_seconds(t0: float, builds) -> float:
    """The window up to the end of the last build, over the builds; with
    no build finished, beyond any limit."""
    if not builds:
        return hs.finite(float("inf"))
    return (builds[-1][1] - t0) / len(builds)


def run(cell, seed: int, seconds: float, trace: bool, t_proc0: float,
        rows: int | None = None, build_params: dict | None = None,
        require_tpu: bool = True, check_workers: int = 8):
    from repro.aqp.engine import AQPFramework
    from repro.core.query import QueryEngine
    from repro.core.types import BuildParams

    traffic = cell.traffic
    name = cell.config["table"]
    device = hs.device_info(cell.chips, require_tpu)
    counter = hs.CompileCounter()
    lines = []

    table = hs.generate_table(cell.config, rows)
    params = dict(cell.config["build_params"])
    params.update(build_params or {})
    fw = AQPFramework(params=BuildParams(**params),
                      use_compression=cell.config["compression"]
                      == "greedygd")
    t_data = time.perf_counter()
    fw.ingest(table)
    lines.append(f"set-up: device_data={t_data - t_proc0!r} " + " ".join(
        f"{k}={v!r}" for k, v in fw.timings.items()
        if k in ("preprocess_s", "compress_s", "build_synopsis_s")))
    columns = fw.preprocessed.columns
    compressed = fw.compressed
    fixed = hs.content_seed(cell.config)
    probe_stmts = probes(traffic, hs.Sample(table, fixed),
                         hs.rng(fixed, "traffic"))
    sqls = [s.sql(name) for s in probe_stmts]
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    if trace:
        tracing.start(trace_dir)
    gc.collect()
    t_sync = tracing.sync_mark() if trace else None
    counter.start()
    t0 = time.perf_counter()
    setup_s = t0 - t_proc0

    builds, failed = [], 0
    previous = fw.synopsis
    while time.perf_counter() < t0 + seconds:
        b0 = time.perf_counter()
        try:
            fw.ingest_compressed(compressed, columns)
        except Exception as exc:          # a failed build is counted
            failed += 1
            lines.append(f"build failed: {exc!r}")
            break
        builds.append((b0, time.perf_counter(), dict(fw.timings),
                       fw.synopsis))
    # A build that left the synopsis it started from in place built nothing.
    unchanged = 0
    for b in builds:
        unchanged += b[3] is previous
        previous = b[3]
    t_end = builds[-1][1] if builds else time.perf_counter()
    compiles = counter.stop()
    device["memory_peak_bytes"] = hs.memory_peak()
    reduced = None
    if trace:
        tracing.stop()
        reduced = tracing.Reduced(tracing.read_xplane(trace_dir), t_sync,
                                  t0, t_end)
        shutil.rmtree(trace_dir, ignore_errors=True)
    n = len(builds)
    build_s = build_seconds(t0, [(b[0], b[1]) for b in builds])
    lines.append(f"window: builds={n} compilations={compiles} "
                 f"retraces={counter.traces} "
                 f"build_s={build_s!r} setup_s={setup_s!r} "
                 + " ".join(f"b{i}={b[1] - b[0]!r}"
                            for i, b in enumerate(builds)))

    # Reference: every timed build's synopsis answers the probes.
    del fw, previous
    gc.collect()
    exact = ExactTable(table).answers(probe_stmts, workers=check_workers)
    funcs = sorted({st.func for st in probe_stmts})
    worst = dict.fromkeys(["all"] + funcs,
                          0.0 if builds else hs.finite(float("inf")))
    for syn in {id(b[3]): b[3] for b in builds}.values():
        engine = QueryEngine(syn)
        errs = hs.rel_errors(zip([engine.query(q) for q in sqls], exact))
        worst["all"] = max(worst["all"], stats.median(errs))
        for f in funcs:
            worst[f] = max(worst[f], stats.median(
                [e for e, st in zip(errs, probe_stmts) if st.func == f]))
    checks = hs.compared({"failed_builds": failed,
                          "unchanged_builds": unchanged,
                          "window_compilations": compiles,
                          "avg_err_p50_pct": worst["AVG"]}, traffic["limits"])
    lines.append(f"check probes: statements={len(sqls)} builds={n} "
                 + " ".join(f"p50_{k}_pct={v!r}" for k, v in worst.items()))

    result = {"correct": hs.passed(checks), "attempted": n + failed,
              "failed": failed, "device": device}
    if trace:
        from bench.serving import RunView, per_layer

        view = RunView(table=name, explains=[], stats0=None,
                       stats1=None, reduced=reduced, launches=[],
                       device_kind=device["kind"], t0=t0, t1=t_end)
        view.builds = [b[2] for b in builds]
        result["metrics"] = per_layer(cell, view)
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        result["breakdown"] = {
            "device_ops": [[k, v] for k, v in reduced.top_ops(10)],
            "idle_gaps": [[k, v] for k, v in reduced.gaps_by_label(
                [("synopsis build", [(b[0], b[1]) for b in builds])])]}
    else:
        metrics = {"setup_s": setup_s, "build_s": build_s}
        result["metrics"] = {m["name"]: {"value": metrics[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell.end_to_end}
    return result, checks, lines
