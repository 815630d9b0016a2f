"""Spans of the admission worker's waves and of the fused launch, the
fallback-reason counters, and the host spans of the pair phase."""
import numpy as np
import pytest

from repro.aqp.engine import AQPFramework
from repro.core.types import BuildParams
from repro.serve.aqp import AQPServer

WAVE_CHILDREN = ("hold", "assemble", "execute", "resolve")
FUSED_CHILDREN = ("betas", "launch", "widen")

# Two fusable shapes (a pair predicate on c, on b), a lone statement of a
# third shape and an OR tree, which has no plan shape at all.
WORKLOAD = ([f"SELECT AVG(b) FROM t WHERE a > {i} AND c < 30"
             for i in range(20, 60, 4)]
            + [f"SELECT SUM(a) FROM t WHERE b > {i}" for i in range(60, 90, 6)]
            + ["SELECT COUNT(*) FROM t WHERE c > 4",
               "SELECT COUNT(*) FROM t WHERE a > 5 OR b > 3"])


@pytest.fixture(scope="module")
def table():
    rng = np.random.default_rng(5)
    n = 8_000
    return {
        "a": rng.integers(0, 400, n).astype(float),
        "b": np.abs(rng.normal(100, 30, n)).round(),
        "c": rng.integers(0, 40, n).astype(float),
    }


@pytest.fixture(scope="module")
def framework(table):
    params = BuildParams(n_samples=4_000, seed=1)
    return AQPFramework(params=params, use_compression=False).ingest(table)


def _serve(framework, sqls, trace=True, waves=2):
    """Serve ``sqls`` in ``waves`` flushed waves plus one streamed wave
    that the admission policy fires; returns (server, results)."""
    srv = AQPServer(mode="ref", trace_enabled=trace, max_wait_ms=200.0)
    srv.register("t", framework)
    out = []
    step = -(-len(sqls) // waves)
    for lo in range(0, len(sqls), step):
        out += srv.query_batch(sqls[lo:lo + step])
    futs = [srv.submit(f"SELECT SUM(b) FROM t WHERE a < {v}")
            for v in (300, 310, 320)]
    out += [f.result(timeout=60) for f in futs]
    srv.close()
    return srv, out


@pytest.fixture(scope="module")
def traced(framework):
    return _serve(framework, WORKLOAD)


def _worker(srv, name):
    return [s for s in srv.tracer.spans()
            if s.track == "worker" and s.name == name]


def _inside(span, outer):
    return outer.t0 <= span.t0 <= span.t1 <= outer.t1


def test_wave_children_tile_inside_their_wave(traced):
    srv, _ = traced
    waves = _worker(srv, "wave")
    assert len(waves) >= 3
    ids = [w.attrs["wave"] for w in waves]
    assert len(set(ids)) == len(ids)
    assert ids == sorted(ids)            # monotonic per admission queue
    for w in waves:
        assert {"cause", "size", "depth", "oldest_wait_ms"} <= set(w.attrs)
        kids = sorted((s for name in WAVE_CHILDREN
                       for s in _worker(srv, name)
                       if s.attrs["wave"] == w.attrs["wave"]),
                      key=lambda s: s.t0)
        assert [s.name for s in kids] == list(WAVE_CHILDREN)
        assert all(_inside(s, w) for s in kids)
        for a, b in zip(kids, kids[1:]):
            assert a.t1 <= b.t0          # children do not overlap
    # the streamed wave was held open by policy, not flushed
    assert any(w.attrs["cause"] == "timeout" for w in waves)


def test_query_execute_span_names_its_wave(traced):
    srv, results = traced
    waves = {w.attrs["wave"] for w in _worker(srv, "wave")}
    execs = [s for s in srv.tracer.spans()
             if s.cat == "query" and s.name == "execute"]
    assert len(execs) == len(results)
    assert {s.attrs["wave"] for s in execs} <= waves
    assert {r.explain["wave"] for r in results} <= waves
    assert all("kernel_share_ms" not in r.explain for r in results)


def test_fused_children_cover_the_fused_span(traced):
    srv, _ = traced
    fused = _worker(srv, "fused")
    assert len(fused) >= 3
    kids = [s for name in FUSED_CHILDREN for s in _worker(srv, name)]
    waves = {w.attrs["wave"] for w in _worker(srv, "wave")}
    for f in fused:
        assert {"table", "col", "queries"} <= set(f.attrs)
        assert f.attrs["wave"] in waves
        mine = sorted((s for s in kids if _inside(s, f)), key=lambda s: s.t0)
        assert [s.name for s in mine] == list(FUSED_CHILDREN)
        for a, b in zip(mine, mine[1:]):
            assert a.t1 <= b.t0
        covered = sum(s.t1 - s.t0 for s in mine)
        assert covered >= 0.95 * (f.t1 - f.t0)
        launch = mine[1]
        assert launch.attrs["queries"] == f.attrs["queries"]
        assert launch.attrs["variants"] == 3
    # no span of the removed kernel fence remains
    assert not _worker(srv, "kernel")


def test_fallback_reasons_sum_to_fallback(traced):
    srv, _ = traced
    t = srv.stats()["tables"]["t"]
    reasons = [t["fallback_lone"], t["fallback_unfusable"],
               t["fallback_declined"]]
    assert sum(reasons) == t["fallback"]
    assert t["fallback_unfusable"] >= 1      # the OR tree
    assert t["fallback_lone"] >= 1           # WHERE c > 4, alone of its shape
    assert t["batched"] >= 10


def test_declined_group_counts_as_declined(framework, monkeypatch):
    from repro.core.fastpath import FastPath

    monkeypatch.setattr(FastPath, "batch", lambda self, *a, **kw: None)
    srv = AQPServer(mode="ref", max_wait_ms=1000.0)     # one flushed wave
    srv.register("t", framework)
    try:
        srv.query_batch(WORKLOAD[:4])
        t = srv.stats()["tables"]["t"]
    finally:
        srv.close()
    assert t["batched"] == 0
    assert t["fallback_declined"] == t["fallback"] == 4


@pytest.mark.parametrize("mode", ["ref", "numpy"])
def test_numpy_mode_and_groupby_reasons_still_sum(table, mode):
    groups = np.array([f"g{i % 5}" for i in range(len(table["a"]))])
    params = BuildParams(n_samples=4_000, seed=1)
    fw = AQPFramework(params=params, use_compression=False).ingest(
        dict(table, g=groups))
    srv = AQPServer(mode=mode)
    srv.register("t", fw)
    try:
        srv.query_batch(WORKLOAD[:6] + [
            "SELECT AVG(b) FROM t WHERE a > 100 GROUP BY g",
            "SELECT COUNT(*) FROM t WHERE a > 5 OR b > 3"])
        t = srv.stats()["tables"]["t"]
    finally:
        srv.close()
    assert (t["fallback_lone"] + t["fallback_unfusable"]
            + t["fallback_declined"]) == t["fallback"]
    if mode == "numpy":                      # no fused path at all
        assert t["fallback_unfusable"] == t["fallback"] == 8


def test_disabled_tracer_records_nothing_and_answers_match(framework,
                                                           traced):
    _, traced_out = traced
    srv, plain_out = _serve(framework, WORKLOAD, trace=False)
    assert srv.tracer.n_recorded == 0
    assert len(plain_out) == len(traced_out)
    for a, b in zip(plain_out, traced_out):
        assert a.as_tuple() == b.as_tuple()
        assert a.explain is None


def test_pair_phase_host_spans_and_published_timeline(table):
    params = BuildParams(n_samples=4_000, seed=1)
    fw = AQPFramework(params=params, use_compression=True).ingest(table)
    first = fw.timings["build_timeline"]
    fw.ingest_compressed(fw.compressed, fw.preprocessed.columns)
    for events in (first, fw.timings["build_timeline"]):
        phase = [e for e in events if e["name"] == "pair_phase"]
        assert len(phase) == 1
        p0, p1 = phase[0]["t0"], phase[0]["t1"]
        for name in ("pair_presort", "pair_metadata", "compact_launch"):
            inner = [e for e in events if e["name"] == name]
            assert inner, name
            assert all(p0 <= e["t0"] <= e["t1"] <= p1 for e in inner), name
        assert {"sample", "refine_1d", "union_regrid", "folds"} <= {
            e["name"] for e in events}
