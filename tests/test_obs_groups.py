"""GROUP BY group assembly in the wave's resolve: its ``group`` span, the
``group`` stage of the statement's explain, and the leaf counters."""
import numpy as np
import pytest

from repro.aqp.engine import AQPFramework
from repro.core.types import BuildParams
from repro.serve.aqp import AQPServer

CATEGORIES = 6
GROUPED = [f"SELECT AVG(b) FROM t WHERE a > {v} GROUP BY g"
           for v in (40, 120, 200)] + [
    "SELECT COUNT(*) FROM t WHERE b < 110 GROUP BY g"]
PLAIN = ["SELECT AVG(b) FROM t WHERE a > 300",
         "SELECT SUM(a) FROM t WHERE b < 90"]


@pytest.fixture(scope="module")
def framework():
    rng = np.random.default_rng(9)
    n = 8_000
    table = {
        "a": rng.integers(0, 400, n).astype(float),
        "b": np.abs(rng.normal(100, 30, n)).round(),
        "g": np.array([f"c{i}" for i in range(CATEGORIES)])[
            rng.integers(0, CATEGORIES, n)],
    }
    return AQPFramework(BuildParams(n_samples=4_000, seed=2),
                        use_compression=False).ingest(table)


def serve(framework, mode="ref", trace=True):
    srv = AQPServer(mode=mode, trace_enabled=trace, max_wait_ms=200.0)
    srv.register("t", framework)
    results = srv.query_batch(GROUPED + PLAIN)
    srv.close()
    return srv, results


@pytest.fixture(scope="module")
def traced(framework):
    return serve(framework)


def worker(srv, name):
    return [s for s in srv.tracer.spans()
            if s.track == "worker" and s.name == name]


def test_group_span_per_statement_inside_its_waves_resolve(traced):
    srv, results = traced
    groups = worker(srv, "group")
    assert len(groups) == len(GROUPED)
    resolves = {s.attrs["wave"]: s for s in worker(srv, "resolve")}
    by_leaves = sorted(len(r.groups) for r in results[:len(GROUPED)])
    assert sorted(s.attrs["groups"] for s in groups) == by_leaves
    for span in groups:
        assert span.attrs["leaves"] == CATEGORIES
        assert span.attrs["executed"] + span.attrs["cached"] == CATEGORIES
        outer = resolves[span.attrs["wave"]]
        assert outer.t0 <= span.t0 <= span.t1 <= outer.t1


def test_explain_has_the_group_stage_of_grouped_statements(traced):
    srv, results = traced
    for res in results[:len(GROUPED)]:
        exp = res.explain
        assert exp["leaves"] == CATEGORIES
        assert 0.0 <= exp["group_ms"] <= exp["resolve_ms"]
    for res in results[len(GROUPED):]:
        assert "group_ms" not in res.explain
        assert "leaves" not in res.explain
    stage = srv.stats()["totals"]["stages"]["group"]
    assert stage["p50_ms"] is not None


@pytest.mark.parametrize("mode", ["ref", "numpy"])
def test_leaf_counters_split_fused_from_unfused(framework, mode):
    srv, _ = serve(framework, mode=mode)
    gb = srv.stats()["tables"]["t"]["group_by"]
    leaves = CATEGORIES * len(GROUPED)
    assert gb["leaves_executed"] == leaves
    fused = leaves if mode == "ref" else 0
    assert (gb["leaves_fused"], gb["leaves_unfused"]) == (fused,
                                                          leaves - fused)


def test_untraced_records_no_group_span_or_stage(framework):
    srv, results = serve(framework, trace=False)
    assert not srv.tracer.spans()
    assert all(res.explain is None for res in results)
    assert srv.stats()["totals"]["stages"]["group"]["p50_ms"] is None
    # The leaf counters are always on, like batched and fallback.
    gb = srv.stats()["tables"]["t"]["group_by"]
    assert gb["leaves_fused"] == CATEGORIES * len(GROUPED)
