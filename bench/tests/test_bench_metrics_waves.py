"""The per-layer readers of the program's wave, fallback and build-timeline
records: each reads a finite value from a small traced run of its cell on
the CPU and nothing from a run whose program lacks the record; and the
program's ``launch`` spans give the shapes the ``LaunchRecorder`` records."""
import math

import numpy as np
import pytest

from bench import harness as hs
from bench import serving
from bench.tests.test_bench_correct import small

ROWS = 40_000
REBUILD_ROWS = 600_000   # a year of minutes: every probe selects enough rows
READERS = {"power-dash-steady": ("assemble_ms_p50.open", "lone_pct.open"),
           "power-rebuild": ("pair_host_s.build",)}


def reader(name: str):
    return hs.load_module(hs.BENCH / "metrics" / f"{name}.py",
                          "bench_metric_" + name.replace(".", "_"))


@pytest.fixture(scope="module")
def dash_run():
    cell = small(hs.cell("power-dash-steady"))
    kind = hs.load_module(hs.BENCH / "kinds" / f"{cell.kind}.py",
                          "bench_kind_" + cell.kind)
    result, _, _ = serving.run(cell, kind, 3, 2.0, True, hs.now(), rows=ROWS,
                               mode="ref", build_params={"n_samples": ROWS},
                               require_tpu=False, check_workers=2)
    return result


@pytest.fixture(scope="module")
def rebuild_run():
    cell = hs.cell("power-rebuild")
    kind = hs.load_module(hs.BENCH / "kinds" / "rebuild.py",
                          "bench_kind_rebuild")
    result, _, _ = kind.run(cell, 3, 1.0, True, hs.now(), rows=REBUILD_ROWS,
                            build_params={"n_samples": 8_000},
                            require_tpu=False, check_workers=2)
    return result


@pytest.mark.parametrize("name", READERS["power-dash-steady"])
def test_serving_reader_reads_a_traced_run(dash_run, name):
    value = dash_run["metrics"][name]["value"]
    assert math.isfinite(value) and value >= 0


def test_lone_share_is_a_share(dash_run):
    assert 0 <= dash_run["metrics"]["lone_pct.open"]["value"] <= 100


def test_pair_host_reader_reads_a_traced_build(rebuild_run):
    host = rebuild_run["metrics"]["pair_host_s.build"]["value"]
    assert math.isfinite(host) and host > 0
    assert host < rebuild_run["metrics"]["pair_phase_s.build"]["value"]


def test_readers_find_nothing_without_the_program_records():
    view = serving.RunView(
        table="t", explains=[{"assemble_ms": 1.0, "execute_ms": 2.0}],
        stats0={"tables": {"t": {"batched": 1, "fallback": 1}}},
        stats1={"tables": {"t": {"batched": 5, "fallback": 4}}})
    view.builds = [{"build_pairs_s": 1.0, "build_phase_s": {}}]
    for names in READERS.values():
        for name in names:
            assert reader(name).read(view) is None, name


def test_pair_host_is_the_phase_minus_its_launches():
    mod = reader("pair_host_s.build")
    events = [{"name": "pair_phase", "t0": 10.0, "t1": 20.0},
              {"name": "pair_presort", "t0": 10.0, "t1": 11.0},
              {"name": "compact_launch", "t0": 11.5, "t1": 15.0},
              {"name": "compact_launch", "t0": 14.0, "t1": 16.0},
              {"name": "pair_metadata", "t0": 17.0, "t1": 18.5},
              {"name": "folds", "t0": 20.0, "t1": 21.0}]
    assert mod.host_seconds(events) == pytest.approx(10.0 - 4.5 - 1.5)
    view = serving.RunView(table="t")
    view.builds = [{"build_timeline": events}, {"build_timeline": []}]
    assert mod.read(view) == pytest.approx(4.0)


def test_assemble_reads_one_value_per_wave():
    view = serving.RunView(table="t", explains=[
        {"wave": 1, "assemble_ms": 0.2}, {"wave": 1, "assemble_ms": 0.2},
        {"wave": 1, "assemble_ms": 0.2}, {"wave": 2, "assemble_ms": 0.9},
        {"wave": 3, "assemble_ms": 0.5}])
    assert reader("assemble_ms_p50.open").read(view) == pytest.approx(0.5)


def test_launch_spans_give_the_recorders_shapes():
    """One server with both installed: the program's ``launch`` span of
    every fused launch carries the shapes the benchmark's recorder
    records for it, in the same order, inside the recorder's interval."""
    from repro.aqp.engine import AQPFramework
    from repro.core.types import BuildParams
    from repro.serve.aqp import AQPServer

    rng = np.random.default_rng(7)
    n = 8_000
    table = {"a": rng.integers(0, 400, n).astype(float),
             "b": np.abs(rng.normal(100, 30, n)).round(),
             "c": rng.integers(0, 40, n).astype(float)}
    fw = AQPFramework(params=BuildParams(n_samples=4_000, seed=1),
                      use_compression=False).ingest(table)
    srv = AQPServer(mode="ref", trace_enabled=True)
    srv.register("t", fw)
    recorder = serving.LaunchRecorder(srv.scheduler.fastpath)
    srv.scheduler.fastpath = recorder
    try:
        srv.query_batch(
            [f"SELECT AVG(b) FROM t WHERE a > {i} AND c < 30"
             for i in range(20, 60, 5)]
            + [f"SELECT SUM(a) FROM t WHERE b > {i}" for i in range(60, 90)]
            + [f"SELECT COUNT(*) FROM t WHERE c < {i}" for i in (9, 19)]
            + ["SELECT AVG(c) FROM t WHERE c > 3 AND c < 30",
               "SELECT AVG(c) FROM t WHERE c > 5 AND c < 31"])
        srv.query_batch([f"SELECT AVG(a) FROM t WHERE b > {i} AND c > 2"
                         for i in range(70, 74)])
    finally:
        srv.close()
    spans = [s for s in srv.tracer.spans()
             if s.track == "worker" and s.name == "launch"]
    fused = [s for s in srv.tracer.spans() if s.name == "fused"]
    assert len(recorder.launches) >= 3
    # groups whose predicates are all on the aggregated column launch
    # nothing, and neither side records a launch for them
    assert len(fused) > len(spans) == len(recorder.launches)
    for sp, (t0, t1, q, k1, pairs) in zip(spans, recorder.launches):
        a = sp.attrs
        assert (a["queries"] * a["variants"], a["k1"]) == (q, k1)
        assert [tuple(p) for p in a["pairs"]] == pairs
        assert t0 <= sp.t0 <= sp.t1 <= t1
