"""Serving cells: set up an ``AQPServer`` over the configuration's table,
warm it up through its own entry points, drive the traffic through
``AQPServer.submit`` for the window, then check the answers against the
reference.

A serving kind module (``bench/kinds/<kind>.py``) supplies the traffic:

    schedule(traffic, sample, rng, seconds) -> [(due_s, Stmt)], ascending
    warmup(traffic, sample, rng, wave, avoid) -> [[Stmt]]: groups of
        statements of one plan shape, at every size that a wave of ``wave``
        window statements can hold of it; none is in ``avoid`` (the
        window's statements), so no window statement finds its answer cached
"""
from __future__ import annotations

import concurrent.futures
import functools
import gc
import math
import shutil
import tempfile
import time

import numpy as np

from bench import harness as hs
from bench import stats, tracing
from bench.reference import ExactTable

DRAIN_S = 60.0        # how long past the window an answer may still come
TRACE_BUFFER = 1 << 20


# ------------------------------------------------------------- launches

class LaunchRecorder:
    """Stands in for the scheduler's ``FastPath`` in a traced run: passes
    every call through and records each fused launch's interval on
    ``perf_counter`` and its logical shapes (queries x bound variants, bins
    of the executed column, and each predicate pair's histogram shape)."""

    def __init__(self, fastpath):
        self._fp = fastpath
        self.launches = []

    def __getattr__(self, name):
        return getattr(self._fp, name)

    def batch(self, ph, agg_col, trees, corrected):
        from repro.core.weightings import flat_and_leaves

        t0 = time.perf_counter()
        out = self._fp.batch(ph, agg_col, trees, corrected)
        t1 = time.perf_counter()
        if out is not None:
            leaves = flat_and_leaves(trees[0]) or []
            cols = sorted({lf.col for lf in leaves} - {agg_col})
            if cols:
                pairs = [tuple(int(d) for d in ph.pair(agg_col, j).H.shape)
                         for j in cols]
                self.launches.append((t0, t1, 3 * len(trees),
                                      int(ph.hists[agg_col].k), pairs))
        return out


# ------------------------------------------------------------- set-up

def build_server(cell, table: dict, mode=None, trace: bool = False,
                 build_params: dict | None = None):
    from repro.core.types import BuildParams
    from repro.serve.aqp import AQPServer

    params = dict(cell.config["build_params"])
    params.update(build_params or {})
    srv = AQPServer(mode=mode, trace_enabled=trace,
                    trace_buffer=TRACE_BUFFER if trace else 65536)
    srv.tracer.annotate_jax = trace
    srv.register_table(cell.config["table"], table,
                       params=BuildParams(**params),
                       use_compression=cell.config["compression"]
                       == "greedygd")
    return srv


def warm(srv, table: str, groups: list, mode=None) -> int:
    """Send each group of statements to the program as one wave, so that
    set-up compiles whatever the window's waves can launch, whatever the
    program's launch policy. For each group size a second ``AQPServer``
    over the same catalog serves the groups of that size: its admission
    fires a wave as soon as a whole group is queued (``max_batch``), and
    the compiled programs and the synopsis's device stacks it makes are the
    process's, shared with the window's server. Returns the statements
    sent."""
    from repro.serve.aqp import AQPServer

    by_size: dict[int, list] = {}
    for group in groups:
        by_size.setdefault(len(group), []).append(group)
    sent = 0
    for size, same in sorted(by_size.items()):
        helper = AQPServer(catalog=srv.catalog, mode=mode,
                           max_wait_ms=1000.0, max_batch=size)
        try:
            for group in same:
                helper.query_batch([st.sql(table) for st in group])
                sent += size
        finally:
            helper.close()
    # The window's server plans each group's shape itself once, so its own
    # plan templates are warm too.
    for group in groups:
        srv.query(group[0].sql(table))
    return sent


# ------------------------------------------------------------- drivers

def _stamp(done: np.ndarray, i: int, _fut):
    done[i] = time.perf_counter()


def drive_open(srv, sqls: list, due: np.ndarray, t0: float,
               spans: list | None):
    """Submit statement ``i`` at ``t0 + due[i]`` whatever the server is
    doing. Returns futures, resolution times, how late each submit was and
    the process's CPU seconds at each submit (a late submit with little CPU
    spent before it means the process was not running)."""
    n = len(sqls)
    done = np.full(n, np.nan)
    late = np.zeros(n)
    cpu = np.zeros(n)
    futs = []
    for i in range(n):
        target = t0 + due[i]
        delay = target - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        ts = time.perf_counter()
        cpu[i] = time.process_time()
        late[i] = ts - target
        fut = srv.submit(sqls[i])
        if spans is not None:
            spans.append((ts, time.perf_counter()))
        fut.add_done_callback(functools.partial(_stamp, done, i))
        futs.append(fut)
    return futs, done, late, cpu


class GcPauses:
    """The interpreter's garbage collections between ``start`` and
    ``stop``: how many of the oldest generation, and the longest pause."""

    def __init__(self):
        self.on = False
        self.full = 0
        self.longest = 0.0
        self._t = None
        gc.callbacks.append(self._cb)

    def _cb(self, phase, info):
        if not self.on:
            return
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.longest = max(self.longest, time.perf_counter() - self._t)
            self.full += info.get("generation") == 2
            self._t = None

    def close(self):
        self.on = False
        gc.callbacks.remove(self._cb)


def stall(due: np.ndarray, late: np.ndarray, cpu: np.ndarray) -> str:
    """The latest submit of the window: how late, the wall time since the
    previous submit and the process's CPU time in it."""
    i = int(np.argmax(late))
    text = (f"submit_late_max_ms={float(late[i]) * 1e3!r} "
            f"at_s={float(due[i])!r}")
    if i == 0:
        return text
    wall = float((due[i] + late[i]) - (due[i - 1] + late[i - 1]))
    return (f"{text} gap_wall_ms={wall * 1e3!r} "
            f"gap_cpu_ms={float(cpu[i] - cpu[i - 1]) * 1e3!r}")


def shuffled(stmts: list, k: int, rng) -> list:
    """``stmts`` in the order ``rng`` draws, the first ``k`` among
    themselves and the rest among themselves: every seed sends the same
    statements at the same times, and the leading ones, which the check
    compares, are one set."""
    head = [stmts[i] for i in rng.permutation(min(k, len(stmts)))]
    tail = [stmts[k + i] for i in rng.permutation(max(0, len(stmts) - k))]
    return head + tail


def latencies_ms(due, done, ok, t0: float) -> list[float]:
    """Latency of each statement from its due time ``t0 + due[i]`` to its
    resolution ``done[i]``; a failed, refused or unanswered one (``ok``
    false) is beyond any limit."""
    return [(done[i] - (t0 + due[i])) * 1e3 if ok[i] else math.inf
            for i in range(len(due))]


def outcome(fut):
    """(result or None, failed, refused) of a settled or pending future."""
    if not fut.done():
        return None, True, False
    if fut.exception() is not None:
        return None, True, False
    res = fut.result()
    if getattr(res, "rejected", False):
        return None, False, True
    if getattr(res, "failed", False) or getattr(res, "expired", False):
        return None, True, False
    return res, False, False


# ------------------------------------------------------------- the run

def run(cell, kind, seed: int, seconds: float, trace: bool, t_proc0: float,
        rows: int | None = None, mode=None, build_params: dict | None = None,
        require_tpu: bool = True, check_workers: int = 8,
        horizon: float | None = None):
    """One run of a serving cell. Returns ``(result, checks, lines)``.

    The traffic is drawn for ``horizon`` seconds (``seconds`` if None) and
    the statements due in the first ``seconds`` are sent: a shorter run
    checks the same leading statements as a run of the whole horizon."""
    traffic = cell.traffic
    name = cell.config["table"]
    device = hs.device_info(cell.chips, require_tpu)
    counter = hs.CompileCounter()
    lines = []

    marks = [("start", hs.now())]
    table = hs.generate_table(cell.config, rows)
    fixed = hs.content_seed(cell.config)
    sample = hs.Sample(table, fixed)
    marks.append(("data", hs.now()))
    srv = build_server(cell, table, mode=mode, trace=trace,
                       build_params=build_params)
    marks.append(("ingest", hs.now()))
    ingest = {k: v for k, v in srv.catalog.resolve(name).timings.items()
              if k in ("preprocess_s", "compress_s", "build_synopsis_s")}
    sched = kind.schedule(traffic, sample, hs.rng(fixed, "traffic"),
                          horizon or seconds)
    k = int(traffic["check_statements"])
    stmts = shuffled([st for _, st in sched], k, hs.rng(seed, "order"))
    groups = kind.warmup(traffic, sample, hs.rng(fixed, "warm"),
                         srv.admission.max_batch, set(stmts))
    due = np.asarray([d for d, _ in sched if d < seconds], float)
    stmts = stmts[:len(due)]
    sqls = [st.sql(name) for st in stmts]
    marks.append(("traffic", hs.now()))
    warmed = warm(srv, name, groups, mode)
    marks.append(("warm", hs.now()))
    recorder = None
    if trace and srv.scheduler.fastpath is not None:
        recorder = LaunchRecorder(srv.scheduler.fastpath)
        srv.scheduler.fastpath = recorder
    submit_spans = [] if trace else None
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    if trace:
        tracing.start(trace_dir)
    gc.collect()
    pauses = GcPauses()
    stats0 = srv.stats()
    t_sync = tracing.sync_mark() if trace else None
    counter.start()
    pauses.on = True
    t0 = time.perf_counter()
    setup_s = t0 - t_proc0

    futs, done, late, cpu = drive_open(srv, sqls, due, t0, submit_spans)
    t_end = t0 + seconds
    pending = [f for f in futs if not f.done()]
    concurrent.futures.wait(pending, timeout=max(
        0.0, t_end + DRAIN_S - time.perf_counter()))
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

    # Outcomes and latency from due time.
    outs = [outcome(f) for f in futs]
    n = len(futs)
    failed = sum(1 for _, f, _ in outs if f)
    refused = sum(1 for _, _, rf in outs if rf)
    lat = latencies_ms(due, done, [o[0] is not None for o in outs], t0)
    p95 = stats.percentile(lat, 95)
    metrics = {"p95_ms": hs.finite(p95)}
    lines.append(f"open loop: statements={n} p50_ms="
                 f"{stats.percentile(lat, 50)!r} p95_ms={p95!r} "
                 f"submit_late_p99_ms={stats.percentile(late, 99) * 1e3!r}")
    lines.append(f"stall: {stall(due, late, cpu)} gc_full={pauses.full} "
                 f"gc_longest_ms={pauses.longest * 1e3!r}")
    lines.append("set-up: device=" + repr(marks[0][1] - t_proc0) + " "
                 + " ".join(f"{b[0]}={b[1] - a[1]!r}"
                            for a, b in zip(marks, marks[1:]))
                 + " " + " ".join(f"{k_}={v!r}" for k_, v in ingest.items()))
    view = RunView(table=name, stats0=stats0, stats1=stats1)
    lines.append(f"fused: batched={view.table_delta('batched')} "
                 f"fallback={view.table_delta('fallback')}")
    lines.append(f"window: compilations={compiles} "
                 f"retraces={counter.traces} failed={failed} "
                 f"refused={refused} warm_statements={warmed} "
                 f"setup_s={setup_s!r}")

    explains = [res.explain for res, _, _ in outs
                if res is not None and res.explain is not None]
    view = RunView(table=name, explains=explains, stats0=stats0,
                   stats1=stats1, reduced=reduced,
                   launches=recorder.launches if recorder else [],
                   device_kind=device["kind"], t0=t0, t1=t_end)
    if trace:
        inflight = [(a, done[i]) for i, (a, _) in enumerate(submit_spans)
                    if np.isfinite(done[i])]
        view.labels = gap_labels(srv, recorder, submit_spans, inflight)
    srv.close()
    del srv
    gc.collect()

    # Reference: exact answers of the window's first ``k`` statements (the
    # same set in every run, in the seed's order), computed after the
    # program's state is freed.
    k = min(k, n)
    exact = ExactTable(table).answers(stmts[:k], workers=check_workers)
    errs = hs.rel_errors([(outs[i][0], ex) for i, ex in enumerate(exact)])
    rel_p50 = stats.median(errs)
    metrics["rel_err_p50_pct"] = rel_p50
    numbers = {"unanswered": failed, "window_compilations": compiles,
               "rel_err_p50_pct": rel_p50,
               **hs.func_medians(errs, stmts[:k])}
    checks = hs.compared(numbers, traffic["limits"])
    lines.append(f"check sample: statements={k} errors={len(errs)} "
                 + " ".join(f"{n}={v!r}" for n, v in numbers.items()))

    result = {"correct": hs.passed(checks), "attempted": n,
              "failed": failed + refused, "device": device}
    if trace:
        result["metrics"] = per_layer(cell, view)
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


def gap_labels(srv, recorder, submit_spans, inflight) -> list:
    """What the host was doing, highest priority first, on perf_counter.
    An idle stretch that no label covers had no statement in flight."""
    by_name: dict[str, list] = {}
    for sp in srv.tracer.spans():
        if sp.track == "worker" and sp.t1 > sp.t0:
            by_name.setdefault(sp.name, []).append((sp.t0, sp.t1))
    launches = [(a, b) for a, b, *_ in recorder.launches] if recorder else []
    return [
        ("fused launch: beta assembly, kernel, aggregation", launches),
        ("fused group (wave_group)", by_name.get("wave_group", [])),
        ("per-query host execution (single_exec)",
         by_name.get("single_exec", []) + by_name.get("group_exec", [])),
        ("submit: plan and admit", submit_spans or []),
        ("statement in flight outside these spans (queue, wave assembly)",
         inflight),
    ]


class RunView:
    """What a per-layer metric reader may look at."""

    def __init__(self, **kw):
        self.labels = []
        self.builds = []
        self.__dict__.update(kw)

    def table_delta(self, key: str) -> float:
        a = self.stats0["tables"].get(self.table, {}).get(key, 0)
        b = self.stats1["tables"].get(self.table, {}).get(key, 0)
        return b - a

    def stage(self, name: str) -> list:
        return [e[f"{name}_ms"] for e in self.explains]


def per_layer(cell, view) -> dict:
    """Run each per-layer reader of the cell; a reader that finds nothing
    returns None and its metric is left out."""
    out = {}
    for m in cell.per_layer:
        mod = hs.load_module(hs.BENCH / "metrics" / f"{m['name']}.py",
                             "bench_metric_" + m["name"].replace(".", "_"))
        value = mod.read(view)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out
