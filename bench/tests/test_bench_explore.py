"""The exploration cell on ``flights``: its session traffic is fixed by the
configuration and keeps its bursts, its GROUP BY answers are the engine's,
and what decides ``correct`` passes a sound run and refuses the control,
at a size a test run can hold."""
import collections

import numpy as np
import pytest

from bench import harness as hs
from bench import serving
from bench.statements import columns_of
from bench.tests.test_bench_correct import control_params

NAME = "flights-explore-bursty"
ROWS = 50_000
RUN_ROWS = 1_000_000   # whole runs: a tenth of it the configuration's sample
SECONDS = 3.0


@pytest.fixture(scope="module")
def cell():
    return hs.cell(NAME)


@pytest.fixture(scope="module")
def kind(cell):
    return hs.load_module(hs.BENCH / "kinds" / f"{cell.kind}.py",
                          "bench_kind_sessions")


@pytest.fixture(scope="module")
def table(cell):
    return hs.generate_table(cell.config, rows=ROWS)


@pytest.fixture(scope="module")
def sample(table):
    return hs.Sample(table, 7, rows=20_000)


def view_of(st):
    return st.func, st.agg, st.group_by


def test_every_seed_gets_the_same_statements_and_due_times(cell, kind,
                                                           sample):
    fixed = hs.content_seed(cell.config)
    a = kind.schedule(cell.traffic, sample, hs.rng(fixed, "traffic"), 6.0)
    b = kind.schedule(cell.traffic, sample, hs.rng(fixed, "traffic"), 6.0)
    assert a == b
    other = kind.schedule(cell.traffic, sample,
                          hs.rng(fixed + 1, "traffic"), 6.0)
    assert a != other
    stmts = [st for _, st in a]
    k = 40
    width = len(cell.traffic["views"])
    for seed in (1, 2**31 + 7):
        sent = kind.dealt(stmts, k, hs.rng(seed, "order"), width)
        assert sorted(map(repr, sent)) == sorted(map(repr, stmts))
        assert set(sent[:k]) == set(stmts[:k])     # the checked set is fixed
    sqls = [st.sql("flights") for st in stmts]
    assert len(set(sqls)) == len(sqls)     # fresh literals: no cache hits


def test_each_due_instant_gets_one_whole_interaction(cell, kind, sample):
    """Whatever the seed, the statements dealt to one due instant are one
    brush under every view, as IDEBench sends an interaction."""
    sched = kind.schedule(cell.traffic, sample, hs.rng(4, "traffic"), 6.0)
    width = len(cell.traffic["views"])
    due = [d for d, _ in sched]
    views = [(v["func"], v["agg"], v.get("group_by"))
             for v in cell.traffic["views"]]
    for seed in (3, 2**31 + 11):
        sent = kind.dealt([st for _, st in sched], 2 * width,
                          hs.rng(seed, "order"), width)
        assert sent != [st for _, st in sched]
        for i in range(0, len(sent), width):
            burst = sent[i:i + width]
            assert len(set(due[i:i + width])) == 1
            assert [view_of(st) for st in burst] == views
            assert len({st.where for st in burst}) == 1
    with pytest.raises(hs.BenchError):
        kind.dealt([st for _, st in sched], width + 1, hs.rng(3, "order"),
                   width)


def test_interactions_are_bursts_of_every_view_under_one_brush(cell, kind,
                                                               sample):
    sched = kind.schedule(cell.traffic, sample, hs.rng(3, "traffic"), 6.0)
    assert [d for d, _ in sched] == sorted(d for d, _ in sched)
    bursts = collections.defaultdict(list)
    for d, st in sched:
        bursts[d].append(st)
    views = [(v["func"], v["agg"], v.get("group_by"))
             for v in cell.traffic["views"]]
    assert len(views) == 4 and sum(v[2] is not None for v in views) == 2
    for stmts in bursts.values():
        assert [view_of(st) for st in stmts] == views
        assert len({st.where for st in stmts}) == 1
        cols = columns_of(stmts[0].where)
        assert 1 <= len(cols) <= 4
        assert cols <= set(cell.traffic["brush_columns"])
        assert sample.count(stmts[0].where) \
            >= cell.traffic["min_sample_rows"]
    counts = collections.Counter(view_of(st) for _, st in sched)
    assert set(counts.values()) == {len(bursts)}   # each view exactly once


def test_sessions_start_in_their_steady_state(cell, kind):
    """At a rate high enough to count on, the first second of the window
    carries the mean load within a few standard deviations: sessions in
    progress at the start were drawn, so the load does not ramp up."""
    lo, hi = cell.traffic["interactions"]
    traffic = dict(cell.traffic, rate_per_s=8.0)
    mean = traffic["rate_per_s"] * (lo + hi) / 2      # interactions per s
    firsts, totals = [], []
    for seed in range(4):
        due = kind.interaction_times(traffic, hs.rng(seed, "traffic"), 20.0)
        firsts.append(np.count_nonzero(due < 1.0))
        totals.append(len(due) / 20.0)
    sigma = np.sqrt(mean)
    assert all(abs(n - mean) < 4 * sigma for n in firsts), (firsts, mean)
    assert abs(np.mean(totals) - mean) < 0.1 * mean


def test_warmup_groups_cover_each_view_and_brush_at_every_size(cell, kind,
                                                               sample):
    window = {st for _, st in kind.schedule(cell.traffic, sample,
                                            hs.rng(3, "traffic"), 6.0)}
    groups = kind.warmup(cell.traffic, sample, hs.rng(3, "warm"), 64,
                         window)
    sizes = collections.defaultdict(list)
    for g in groups:
        assert len(set(g)) == len(g) and not set(g) & window
        shape = {(view_of(st), frozenset(columns_of(st.where)))
                 for st in g}
        assert len(shape) == 1
        sizes[shape.pop()].append(len(g))
    assert len(sizes) == 4 * 15          # every view under every brush
    for (view, cols), got in sizes.items():
        first = 1 if view[2] is not None else 2
        assert got == list(range(first, max(got) + 1))
    # The four-column brush holds a quarter of the interactions, so its
    # groups reach past its mean share of a wave of 64.
    assert max(len(g) for g in groups) > 64 / 16


def serve(fw, sqls, mode):
    from repro.serve.aqp import AQPServer

    srv = AQPServer(mode=mode, max_wait_ms=50.0)
    srv.register("flights", fw)
    try:
        return srv.query_batch(sqls), srv.stats()["tables"]["flights"]
    finally:
        srv.close()


@pytest.mark.parametrize("mode", ["numpy", "ref", "pallas"])
def test_group_by_answers_are_the_engines(cell, kind, table, sample, mode):
    """The served GROUP BY answers, leaves fused in one wave, are the
    engine's sequential per-category loop: bit for bit without a kernel,
    within float32 rounding of the fused launch with one (its jitted
    reference, or the Pallas kernel interpreted)."""
    from repro.aqp.engine import AQPFramework
    from repro.core.types import BuildParams

    fw = AQPFramework(BuildParams(n_samples=ROWS, seed=1)).ingest(table)
    sched = kind.schedule(cell.traffic, sample, hs.rng(5, "traffic"), 2.0)
    stmts = [st for _, st in sched if st.group_by is not None][:6]
    sqls = [st.sql("flights") for st in stmts]
    got, stats = serve(fw, sqls, mode)
    for sql, res in zip(sqls, got):
        plan = fw.engine.plan_sql(sql)
        oracle = fw.engine.execute(plan.func, plan.agg_col, plan.tree,
                                   plan.group_by).groups
        assert set(res.groups) == set(oracle), sql
        if mode == "numpy":
            assert res.groups == oracle, sql
        else:
            for value, triple in oracle.items():
                np.testing.assert_allclose(res.groups[value], triple,
                                           rtol=1e-4, atol=1e-6,
                                           err_msg=f"{sql} [{value}]")
    gb = stats["group_by"]
    assert gb["leaves_executed"] == 14 * len(sqls)
    fused = 0 if mode == "numpy" else gb["leaves_executed"]
    assert (gb["leaves_fused"], gb["leaves_unfused"]) \
        == (fused, gb["leaves_executed"] - fused)


def test_negative_group_averages_are_groups(cell, table):
    """A category whose AVG is below zero is answered, as the reference
    and SQL have it; brushes on short departure delays give such groups."""
    from bench.reference import ExactTable
    from bench.statements import Stmt, conj
    from repro.aqp.engine import AQPFramework
    from repro.core.types import BuildParams

    st = Stmt("AVG", "arrival_delay",
              conj(("departure_delay", ">=", -4.0),
                   ("departure_delay", "<=", -2.0)), "airline")
    exact = ExactTable(table).answer(st)
    assert exact and all(v < 0 for v in exact.values())
    fw = AQPFramework(BuildParams(n_samples=ROWS, seed=1)).ingest(table)
    res = fw.query(st.sql("flights"))
    assert set(res.groups) == set(exact)


def run_cell(seed: int, control: bool = False, mode: str = "ref"):
    cell = hs.cell(NAME)
    kind = hs.load_module(hs.BENCH / "kinds" / f"{cell.kind}.py",
                          "bench_kind_sessions_run")
    n_samples = cell.config["build_params"]["n_samples"]
    params = (control_params(cell, n_samples) if control
              else {"n_samples": n_samples})
    _, checks, lines = kind.run(cell, seed, SECONDS, False, hs.now(),
                                rows=RUN_ROWS, mode=mode,
                                build_params=params, require_tpu=False,
                                check_workers=2)
    return checks, lines


def test_sound_run_is_correct_and_compiles_nothing_in_the_window():
    checks, lines = run_cell(2**31 + 5)
    assert hs.passed(checks), (checks, lines)
    assert checks["window_compilations"]["value"] == 0
    assert 0 < checks["kernel_dev_max_pct"]["value"] \
        < checks["kernel_dev_max_pct"]["limit"] / 10


def test_control_is_refused():
    checks, lines = run_cell(2**31 + 5, control=True, mode="numpy")
    assert not hs.passed(checks), (checks, lines)


def test_fused_arithmetic_off_by_a_thousandth_is_refused(monkeypatch):
    """Kernel weights off by up to 0.1 %, as a step down in the kernel's
    precision leaves them, stay within the synopsis's own error, so only
    ``kernel_dev_max_pct`` refuses them."""
    from repro.core import fastpath

    launch = fastpath.batched_weightings

    def off(*args, **kw):
        out = np.asarray(launch(*args, **kw))
        return out * (1.0 + 1e-3 * np.cos(np.arange(out.shape[-1])))

    monkeypatch.setattr(fastpath, "batched_weightings", off)
    checks, lines = run_cell(2**31 + 5)
    assert checks["kernel_dev_max_pct"]["value"] \
        > checks["kernel_dev_max_pct"]["limit"], (checks, lines)
    assert checks["rel_err_p50_pct"]["value"] \
        <= checks["rel_err_p50_pct"]["limit"], (checks, lines)
    assert not hs.passed(checks)


@pytest.mark.parametrize("served, host, want", [
    (10.0, 10.0, 0.0),
    (10.001, 10.0, 0.01),
    ({"AA": 5.0, "DL": -20.002}, {"AA": 5.0, "DL": -20.0}, 0.01),
    ({"AA": 5.0, "DL": 2.0}, {"AA": 5.0}, 40.0),
    (None, 3.0, 0.0),
])
def test_deviation_is_a_share_of_the_statements_largest_answer(
        kind, served, host, want):
    assert kind.deviation_pct(served, host) == pytest.approx(want)


@pytest.mark.parametrize("name", [m["name"] for m in hs.cell(NAME).per_layer
                                  if m["name"].endswith(".explore")])
def test_readers_find_nothing_where_the_program_records_nothing(name):
    """Each of the cell's readers returns None, and does not raise, on a
    run of a program without the group stage, launches or counters (the
    parent's program, an untraced run)."""
    stats = {"tables": {"flights": {}}}
    view = serving.RunView(table="flights", explains=[{"plan_ms": 0.1}],
                           stats0=stats, stats1=stats, reduced=None,
                           launches=[], device_kind="TPU v5 lite",
                           t0=0.0, t1=30.0)
    mod = hs.load_module(hs.BENCH / "metrics" / f"{name}.py",
                         "bench_metric_" + name.replace(".", "_"))
    assert mod.read(view) is None


def test_flights_data_is_the_configurations_with_the_files_pattern(cell):
    """The stand-in has the published 31 columns, is the same in every run,
    and keeps the file's shares and NULL pattern: carriers by their
    published counts, NULL arrival delay exactly on cancelled or diverted
    flights, a cancellation reason exactly on cancelled ones, and delay
    causes only on arrivals 15 minutes late, summing to the delay."""
    gen = hs.load_module(hs.BENCH / "data" / "flights.py", "bench_data_fl")
    a = hs.generate_table(cell.config, rows=60_000)
    b = hs.generate_table(cell.config, rows=60_000)
    assert list(a) == cell.config["columns"] == list(gen.COLUMNS)
    assert len(a) == 31
    for col in a:
        assert np.array_equal(a[col], b[col], equal_nan=a[col].dtype.kind
                              == "f")
    share = np.mean(a["airline"] == "WN")
    assert share == pytest.approx(gen.AIRLINES["WN"] / gen.ROWS, abs=0.01)
    gone = (a["cancelled"] == 1) | (a["diverted"] == 1)
    assert np.array_equal(np.isnan(a["arrival_delay"]), gone)
    assert np.array_equal(a["cancellation_reason"] != None,  # noqa: E711
                          a["cancelled"] == 1)
    late = a["arrival_delay"] >= 15
    causes = np.stack([a[c] for c in gen.CAUSES], axis=1)
    assert np.array_equal(~np.isnan(causes).any(axis=1), late)
    assert np.array_equal(causes[late].sum(axis=1),
                          a["arrival_delay"][late])
    assert set(np.unique(a["day_of_week"][(a["month"] == 1)
                                          & (a["day"] == 1)])) == {4.0}
