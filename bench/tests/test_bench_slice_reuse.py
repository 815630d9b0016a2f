"""The per-launch slice memo on the ``flights`` exploration cell: a GROUP
BY's fused leaves share their brush, so the served answers are those of
the launch without the memo while fewer slices are evaluated than needed;
and the ``slice_reuse_pct.open`` reader reads that share."""
import pytest

from bench import harness as hs
from bench import serving

NAME = "flights-explore-bursty"
ROWS = 50_000
READER = "slice_reuse_pct.open"


@pytest.fixture(scope="module")
def cell():
    return hs.cell(NAME)


@pytest.fixture(scope="module")
def framework(cell):
    from repro.aqp.engine import AQPFramework
    from repro.core.types import BuildParams

    table = hs.generate_table(cell.config, rows=ROWS)
    return AQPFramework(BuildParams(n_samples=ROWS, seed=1)).ingest(table)


@pytest.fixture(scope="module")
def sqls(cell):
    kind = hs.load_module(hs.BENCH / "kinds" / f"{cell.kind}.py",
                          "bench_kind_sessions_reuse")
    sample = hs.Sample(hs.generate_table(cell.config, rows=ROWS), 7,
                       rows=20_000)
    sched = kind.schedule(cell.traffic, sample, hs.rng(5, "traffic"), 2.0)
    return [st.sql("flights") for _, st in sched
            if st.group_by is not None][:6]


def serve(fw, sqls):
    from repro.serve.aqp import AQPServer

    srv = AQPServer(mode="ref", max_wait_ms=50.0)
    srv.register("flights", fw)
    try:
        return srv.query_batch(sqls), srv.stats()["tables"]["flights"]
    finally:
        srv.close()


def test_served_group_by_answers_are_the_launch_without_the_memo(
        framework, sqls, monkeypatch):
    from repro.core import fastpath

    got, stats = serve(framework, sqls)
    betas = stats["betas"]
    assert 0 < betas["computed"] < betas["slices"]

    monkeypatch.setattr(fastpath, "_leaf_key", lambda leaf: object())
    want, plain = serve(framework, sqls)
    assert plain["betas"]["computed"] == plain["betas"]["slices"] \
        == betas["slices"]
    for sql, a, b in zip(sqls, got, want):
        assert len(a.groups) > 1, sql
        assert a.groups == b.groups, sql


def view(stats0, stats1):
    return serving.RunView(table="flights", explains=[], stats0=stats0,
                           stats1=stats1, reduced=None, launches=[],
                           device_kind="TPU v5 lite", t0=0.0, t1=30.0)


def reader():
    return hs.load_module(hs.BENCH / "metrics" / f"{READER}.py",
                          "bench_metric_" + READER.replace(".", "_"))


@pytest.mark.parametrize("stats0, stats1, want", [
    ({"tables": {"flights": {}}}, {"tables": {"flights": {}}}, None),
    ({"tables": {}}, {"tables": {"flights": {"betas": {
        "slices": 4, "computed": 4}}}}, None),
    ({"tables": {"flights": {"betas": {"slices": 10, "computed": 9}}}},
     {"tables": {"flights": {"betas": {"slices": 10, "computed": 9}}}},
     None),
    ({"tables": {"flights": {"betas": {"slices": 10, "computed": 9}}}},
     {"tables": {"flights": {"betas": {"slices": 52, "computed": 25}}}},
     100.0 * (1 - 16 / 42)),
    ({"tables": {"power": {"betas": {"slices": 0, "computed": 0}}}},
     {"tables": {"power": {"betas": {"slices": 8, "computed": 8}}}},
     None),
])
def test_slice_reuse_reads_the_windows_share(stats0, stats1, want):
    """None where the program keeps no ``betas`` counters (the parent's)
    or the window made no launch; else the share of needed slices that
    were not evaluated, from the cell's own table."""
    got = reader().read(view(stats0, stats1))
    assert got == (None if want is None else pytest.approx(want))
