"""Open-loop linked-view exploration sessions, in IDEBench's style.

Parameters (``bench/traffic/<name>.json``):

    rate_per_s         session arrivals per second, Poisson
    interactions       [lo, hi]: interactions per session, uniform
    think_s            mean think time between interactions, exponential
    brush_columns      columns a brush ranges over
    views              [{"func", "agg", "group_by"?}, ...]: the statements
                       of one interaction, all due at its instant
    literal_quantiles  [lo, hi]: each literal is a fresh quantile in range;
                       no statement text repeats within a run
    min_sample_rows    a brush must select this many sample rows

Each interaction is one brush: a range ``lo <= col <= hi`` on each of
1-4 brush columns (how many is uniform, then which), and every linked view
re-queries under it at once. The next interaction is due a think time after
the previous one's due time, whatever the answers. Sessions arrive from well
before the window opens, so those in progress at its start are at a
steady-state point of their lives and the load does not ramp up.

Every seed gets the same statements at the same due times, so seeds change
the order, not the work: ``run`` deals whole interactions to the due
instants, so each burst stays one brush with every view.

Besides the answers' error against the reference, ``run`` compares the
window's answers with the same statements answered in float64 on the host
over the same synopsis: ``kernel_dev_max_pct``, the largest difference as a
percent of that statement's largest answer. It holds the arithmetic the
configuration states (the kernel at ``HIGHEST``), which the synopsis's own
error is too wide to show.
"""
from __future__ import annotations

import concurrent.futures
import gc
import itertools
import math
import shutil
import tempfile
import time

import numpy as np

from bench import harness as hs
from bench import serving, stats, tracing
from bench.reference import ExactTable
from bench.statements import Stmt, conj, decimals

BURN_IN = 4.0        # sessions start this many longest-session means early


def interaction_times(traffic: dict, rng, seconds: float) -> np.ndarray:
    """Due times in ``[0, seconds)`` of every interaction, ascending.

    Sessions arrive as a Poisson process over ``[-burn, seconds)``, where
    ``burn`` is ``BURN_IN`` times the mean length of the longest session:
    a session that started before the window and is still running at its
    start was drawn with the others, at a uniform point of its life."""
    lo, hi = traffic["interactions"]
    think = float(traffic["think_s"])
    burn = BURN_IN * hi * think
    n = rng.poisson(traffic["rate_per_s"] * (burn + seconds))
    starts = rng.uniform(-burn, seconds, n)
    lengths = rng.integers(lo, hi + 1, n)
    gaps = rng.exponential(think, int(lengths.sum()))
    out = []
    at = 0
    for start, length in zip(starts, lengths):
        steps = gaps[at:at + length]
        steps[0] = 0.0                   # the first is due on arrival
        at += length
        out.append(start + np.cumsum(steps))
    due = np.concatenate(out) if out else np.empty(0)
    return np.sort(due[(due >= 0) & (due < seconds)])


def subsets(columns: list) -> list:
    """Every brush (column subset) with its share: the size uniform over
    1..len(columns), then the subset uniform among those of that size."""
    k = len(columns)
    out = []
    for size in range(1, k + 1):
        combos = list(itertools.combinations(columns, size))
        out += [(combo, 1.0 / k / len(combos)) for combo in combos]
    return out


def brush_preds(cols) -> list:
    return [(c, op) for c in cols for op in (">=", "<=")]


def brushes(traffic: dict, cols, m: int, sample, rng,
            rounds: int = 64) -> np.ndarray:
    """``m`` distinct literal vectors of brushes over ``cols``, in random
    order: on each column a range between two quantiles drawn uniformly
    from ``literal_quantiles``, the lower first, rounded like the column.
    Each brush selects at least ``min_sample_rows`` sample rows."""
    q_lo, q_hi = traffic["literal_quantiles"]
    preds = brush_preds(cols)
    have = np.empty((0, len(preds)))
    for _ in range(rounds):
        need = m - len(have)
        if need <= 0:
            break
        u = np.sort(rng.uniform(q_lo, q_hi, size=(2 * need, len(cols), 2)),
                    axis=2).reshape(2 * need, len(preds))
        lits = np.stack([sample.literals(c, u[:, p])
                         for p, (c, _) in enumerate(preds)], axis=1)
        rows = traffic["min_sample_rows"]
        keep = hs.counts(sample.part, preds, lits) >= rows
        rest = np.flatnonzero(~keep)
        keep[rest] = hs.counts(sample.exact, preds, lits[rest]) >= rows
        have = np.unique(np.concatenate([have, lits[keep]]), axis=0)
    if len(have) < m:
        raise hs.BenchError(f"cannot draw {m} distinct brushes over {cols} "
                            f"that select {traffic['min_sample_rows']} of "
                            f"{sample.rows} sample rows")
    return rng.permutation(have)[:m]


def view_stmt(view: dict, cols, lits) -> Stmt:
    where = conj(*[(c, op, float(v))
                   for (c, op), v in zip(brush_preds(cols), lits)])
    return Stmt(view["func"], view["agg"], where, view.get("group_by"))


def schedule(traffic: dict, sample, rng, seconds: float) -> list:
    due = interaction_times(traffic, rng, seconds)
    shapes = subsets(traffic["brush_columns"])
    pick = rng.choice(len(shapes), size=len(due), p=[p for _, p in shapes])
    counts = np.bincount(pick, minlength=len(shapes))
    pools = [iter(brushes(traffic, cols, int(c), sample, rng)) if c else None
             for (cols, _), c in zip(shapes, counts)]
    out = []
    for d, b in zip(due, pick):
        cols = shapes[b][0]
        lits = next(pools[b])
        out += [(float(d), view_stmt(v, cols, lits))
                for v in traffic["views"]]
    return out


def widened(sample, cols, lits: np.ndarray) -> np.ndarray:
    """The same brushes with each bound moved half a quantization step
    outward: they select the same rows, and no literal lies on the
    column's grid, so no window statement repeats one."""
    steps = [10.0 ** -decimals(sample.exact.num[c][sample.exact.finite[c]])
             for c in cols]
    return lits + np.tile([-0.5, 0.5], len(cols)) * np.repeat(steps, 2)


def warmup(traffic: dict, sample, rng, wave: int, avoid: set) -> list:
    """For each view under each brush, a group of statements at every size
    up to the most that a wave of ``wave`` window statements holds of it:
    its share of the mix, as a binomial draw, six standard deviations up.
    A GROUP BY statement launches its leaves even alone, so its groups start
    at one statement, the others at two. Each size takes the first
    statements of one list of brushes drawn like the window's and then
    ``widened``, so none is in ``avoid``."""
    views = traffic["views"]
    groups = []
    for cols, share in subsets(traffic["brush_columns"]):
        p = share / len(views)
        most = min(wave, math.ceil(wave * p
                                   + 6 * math.sqrt(wave * p * (1 - p))))
        rows = widened(sample, cols,
                       brushes(traffic, cols, most, sample, rng))
        for view in views:
            stmts = [view_stmt(view, cols, r) for r in rows]
            if set(stmts) & avoid:
                raise hs.BenchError(f"a warm-up statement of {view} under "
                                    f"a brush of {cols} is in the window")
            least = 1 if view.get("group_by") else 2
            groups += [stmts[:size] for size in range(least, most + 1)]
    return groups


def dealt(stmts: list, k: int, rng, width: int) -> list:
    """``serving.shuffled`` over whole interactions: the ``width``
    statements of each (consecutive in the schedule) stay together, so the
    due instant they are dealt to gets one brush with every view."""
    if k % width or len(stmts) % width:
        raise hs.BenchError(f"{k} checked statements or {len(stmts)} in all "
                            f"are not whole interactions of {width}")
    blocks = [stmts[i:i + width] for i in range(0, len(stmts), width)]
    return [st for block in serving.shuffled(blocks, k // width, rng)
            for st in block]


def deviation_pct(served, host) -> float:
    """The largest difference between two answers of one statement (scalars
    or GROUP BY dicts, a group on one side only counting its value), as a
    percent of the host answer's largest magnitude."""
    if isinstance(served, dict) or isinstance(host, dict):
        a, b = served or {}, host or {}
        pairs = [(a.get(g, 0.0), b.get(g, 0.0)) for g in set(a) | set(b)]
    else:
        pairs = [(served, host)]
    pairs = [(float(x), float(y)) for x, y in pairs
             if x is not None and y is not None]
    if not pairs:
        return 0.0
    scale = max(abs(y) for _, y in pairs)
    diff = max(abs(x - y) for x, y in pairs)
    return 0.0 if diff == 0 else 100.0 * diff / max(scale, 1e-300)


def estimate(res):
    """A result's estimate: a scalar, ``{category: estimate}`` for a GROUP
    BY, or None where there is no result."""
    if res is None:
        return None
    if res.groups is not None:
        return {str(g): t[0] for g, t in res.groups.items()}
    return res.estimate


def host_answers(srv, table: str, stmts: list) -> list:
    """The statements answered by a second server over ``srv``'s catalog in
    ``numpy`` mode: every plan on the host in float64, no kernel."""
    from repro.serve.aqp import AQPServer

    host = AQPServer(catalog=srv.catalog, mode="numpy")
    try:
        return [estimate(r) for r in
                host.query_batch([st.sql(table) for st in stmts])]
    finally:
        host.close()


def run(cell, seed: int, seconds: float, trace: bool, t_proc0: float,
        rows: int | None = None, mode=None, build_params: dict | None = None,
        require_tpu: bool = True, check_workers: int = 8,
        horizon: float | None = None):
    """One run of the cell: ``serving.run``'s set-up, window and check,
    with the interactions ``dealt`` whole and ``kernel_dev_max_pct`` among
    the compared numbers. The traffic is drawn for ``horizon`` seconds
    (the manifest's ``run_seconds`` if longer than ``seconds``), so a
    shorter run checks the statements of a benchmark run."""
    traffic = cell.traffic
    name = cell.config["table"]
    horizon = horizon or max(seconds, float(hs.manifest()["run_seconds"]))
    device = hs.device_info(cell.chips, require_tpu)
    counter = hs.CompileCounter()
    lines = []

    marks = [("start", hs.now())]
    table = hs.generate_table(cell.config, rows)
    fixed = hs.content_seed(cell.config)
    sample = hs.Sample(table, fixed)
    marks.append(("data", hs.now()))
    srv = serving.build_server(cell, table, mode=mode, trace=trace,
                               build_params=build_params)
    marks.append(("ingest", hs.now()))
    ingest = {k: v for k, v in srv.catalog.resolve(name).timings.items()
              if k in ("preprocess_s", "compress_s", "build_synopsis_s")}
    sched = schedule(traffic, sample, hs.rng(fixed, "traffic"), horizon)
    k = int(traffic["check_statements"])
    stmts = dealt([st for _, st in sched], k, hs.rng(seed, "order"),
                  len(traffic["views"]))
    groups = warmup(traffic, sample, hs.rng(fixed, "warm"),
                    srv.admission.max_batch, set(stmts))
    due = np.asarray([d for d, _ in sched if d < seconds], float)
    stmts = stmts[:len(due)]
    sqls = [st.sql(name) for st in stmts]
    marks.append(("traffic", hs.now()))
    warmed = serving.warm(srv, name, groups, mode)
    marks.append(("warm", hs.now()))
    recorder = None
    if trace and srv.scheduler.fastpath is not None:
        recorder = serving.LaunchRecorder(srv.scheduler.fastpath)
        srv.scheduler.fastpath = recorder
    submit_spans = [] if trace else None
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    if trace:
        tracing.start(trace_dir)
    gc.collect()
    pauses = serving.GcPauses()
    stats0 = srv.stats()
    t_sync = tracing.sync_mark() if trace else None
    counter.start()
    pauses.on = True
    t0 = time.perf_counter()
    setup_s = t0 - t_proc0

    futs, done, late, cpu = serving.drive_open(srv, sqls, due, t0,
                                               submit_spans)
    t_end = t0 + seconds
    pending = [f for f in futs if not f.done()]
    concurrent.futures.wait(pending, timeout=max(
        0.0, t_end + serving.DRAIN_S - time.perf_counter()))
    compiles = counter.stop()
    pauses.close()
    stats1 = srv.stats()
    reduced = None
    if trace:
        tracing.stop()
        reduced = tracing.Reduced(tracing.read_xplane(trace_dir), t_sync,
                                  t0, t_end)
        shutil.rmtree(trace_dir, ignore_errors=True)
    device["memory_peak_bytes"] = hs.memory_peak()

    outs = [serving.outcome(f) for f in futs]
    n = len(futs)
    failed = sum(1 for _, f, _ in outs if f)
    refused = sum(1 for _, _, rf in outs if rf)
    lat = serving.latencies_ms(due, done, [o[0] is not None for o in outs],
                               t0)
    p95 = stats.percentile(lat, 95)
    metrics = {"p95_ms": hs.finite(p95)}
    lines.append(f"open loop: statements={n} p50_ms="
                 f"{stats.percentile(lat, 50)!r} p95_ms={p95!r} "
                 f"submit_late_p99_ms={stats.percentile(late, 99) * 1e3!r}")
    lines.append(f"stall: {serving.stall(due, late, cpu)} "
                 f"gc_full={pauses.full} "
                 f"gc_longest_ms={pauses.longest * 1e3!r}")
    lines.append("set-up: device=" + repr(marks[0][1] - t_proc0) + " "
                 + " ".join(f"{b[0]}={b[1] - a[1]!r}"
                            for a, b in zip(marks, marks[1:]))
                 + " " + " ".join(f"{k_}={v!r}" for k_, v in ingest.items()))
    view = serving.RunView(table=name, stats0=stats0, stats1=stats1)
    lines.append(f"fused: batched={view.table_delta('batched')} "
                 f"fallback={view.table_delta('fallback')}")
    lines.append(f"window: compilations={compiles} "
                 f"retraces={counter.traces} failed={failed} "
                 f"refused={refused} warm_statements={warmed} "
                 f"setup_s={setup_s!r}")

    explains = [res.explain for res, _, _ in outs
                if res is not None and res.explain is not None]
    view = serving.RunView(table=name, explains=explains, stats0=stats0,
                           stats1=stats1, reduced=reduced,
                           launches=recorder.launches if recorder else [],
                           device_kind=device["kind"], t0=t0, t1=t_end)
    if trace:
        inflight = [(a, done[i]) for i, (a, _) in enumerate(submit_spans)
                    if np.isfinite(done[i])]
        view.labels = serving.gap_labels(srv, recorder, submit_spans,
                                         inflight)
    # The checked statements on the host, over the window's synopsis.
    k = min(k, n)
    host = host_answers(srv, name, stmts[:k])
    dev = max([deviation_pct(estimate(outs[i][0]), host[i])
               for i in range(k) if outs[i][0] is not None], default=0.0)
    srv.close()
    del srv
    gc.collect()

    # Reference: exact answers of the window's first ``k`` statements,
    # computed after the program's state is freed.
    exact = ExactTable(table).answers(stmts[:k], workers=check_workers)
    errs = hs.rel_errors([(outs[i][0], ex) for i, ex in enumerate(exact)])
    rel_p50 = stats.median(errs)
    metrics["rel_err_p50_pct"] = rel_p50
    numbers = {"unanswered": failed, "window_compilations": compiles,
               "rel_err_p50_pct": rel_p50, "kernel_dev_max_pct": dev,
               **hs.func_medians(errs, stmts[:k])}
    checks = hs.compared(numbers, traffic["limits"])
    lines.append(f"check sample: statements={k} errors={len(errs)} "
                 + " ".join(f"{n_}={v!r}" for n_, v in numbers.items()))

    result = {"correct": hs.passed(checks), "attempted": n,
              "failed": failed + refused, "device": device}
    if trace:
        result["metrics"] = serving.per_layer(cell, view)
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        result["breakdown"] = {
            "device_ops": [[k_, v] for k_, v in reduced.top_ops(10)],
            "idle_gaps": [[k_, v] for k_, v in
                          reduced.gaps_by_label(view.labels)]}
    else:
        metrics["setup_s"] = setup_s
        result["metrics"] = {m["name"]: {"value": metrics[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell.end_to_end}
    return result, checks, lines
