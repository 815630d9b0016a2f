"""The benchmark's arithmetic: latency from due time, build time over the
window, the bound's spread, operation and byte counts, the peaks table, the
reduction of a device trace recorded on one TPU v5e, and the manifest."""
import json
import math
import re
import statistics

import numpy as np
import pytest

from bench import costs, harness as hs, stats, tracing
from bench.kinds.rebuild import build_seconds
from bench.serving import latencies_ms

DATA = hs.BENCH / "tests" / "data"
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_latency_counts_from_due_time_and_failures_miss_every_limit():
    t0 = 100.0
    due = np.array([0.0, 0.5, 1.0, 1.5])
    done = np.array([100.010, 100.600, 101.002, np.nan])
    ok = [True, True, True, False]
    lat = latencies_ms(due, done, ok, t0)
    assert lat[:3] == pytest.approx([10.0, 100.0, 2.0])
    assert lat[3] == math.inf
    # nearest rank: the 95th percentile of four is the largest, the failure
    assert stats.percentile(lat, 95) == math.inf
    assert stats.percentile(lat, 50) == pytest.approx(10.0)
    assert hs.finite(stats.percentile(lat, 95)) == 1e12


def test_percentile_is_nearest_rank_over_every_value():
    xs = list(range(1, 101))
    assert stats.percentile(xs, 95) == 95
    assert stats.percentile(xs, 50) == 50
    assert stats.percentile([7.0], 95) == 7.0
    with pytest.raises(ValueError):
        stats.percentile([], 95)


def test_build_seconds_is_the_window_to_the_last_build_over_builds():
    t0 = 10.0
    builds = [(10.0, 22.0), (22.0, 34.5), (34.5, 47.0)]
    assert build_seconds(t0, builds) == pytest.approx(37.0 / 3)
    # a build that outlasts the window still counts whole
    assert build_seconds(t0, builds[:1]) == pytest.approx(12.0)
    assert build_seconds(t0, []) == 1e12


def test_spread_matches_statistics_quartiles():
    xs = [10.0, 10.2, 9.9, 10.4, 10.1, 9.8]
    q1, q2, q3 = statistics.quantiles(xs, n=4)
    assert stats.spread(xs) == pytest.approx((q3 - q1) / q2)


def test_weightings_count_from_logical_shapes():
    # one pair: beta (q x ky) @ H^T (ky x kx), clip, p (q x kx) @ fold^T
    flops, nbytes = costs.weightings_launch(6, 100, [(40, 30)])
    assert flops == 2 * 6 * 30 * 40 + 2 * 6 * 40 + 2 * 6 * 40 * 100 + 6 * 100
    assert nbytes == 4 * (6 * 100 + 40 * 30 + 40 * 100 + 40 + 6 * 30)
    two = costs.weightings_launch(6, 100, [(40, 30), (40, 30)])
    assert two[0] == 2 * flops
    assert two[1] == 2 * nbytes - 4 * 6 * 100


def test_peaks_table_and_roofline_bound():
    peak = costs.peaks("TPU v5 lite")
    assert peak["flops_per_s"] == 197e12
    assert peak["hbm_bytes_per_s"] == 819e9
    assert "source" in peak
    with pytest.raises(KeyError):
        costs.peaks("cpu")
    t, bound = costs.least_seconds(197e12, 1, peak)
    assert (t, bound) == (pytest.approx(1.0), "compute")
    t, bound = costs.least_seconds(1, 819e9, peak)
    assert (t, bound) == (pytest.approx(1.0), "memory")


@pytest.fixture(scope="module")
def chip_trace():
    """Five seconds of ``power-dash-steady`` traced on one TPU v5e, in the
    compact form ``tracing.read_xplane`` keeps, with the run's sync time,
    window, fused launches and host spans."""
    events = tracing.load(str(DATA / "trace_power-dash-steady.json.gz"))
    meta = json.loads((DATA / "trace_power-dash-steady.meta.json")
                      .read_text())
    return events, meta


def test_trace_reduction_busy_idle_and_gaps(chip_trace):
    events, meta = chip_trace
    red = tracing.Reduced(events, meta["t_sync"], meta["t0"], meta["t1"])
    assert red.chips == 1
    assert red.window_s == pytest.approx(5.0)
    busy = tracing.union([(s, s + d) for _, s, d in
                          events["device"]["/device:TPU:0"]])
    inside = tracing.clip(busy, red.w0, red.w1)
    assert red.busy_s == pytest.approx(tracing.total(inside) / 1e9)
    assert 0 < red.busy_s < red.window_s
    assert red.idle_share() == pytest.approx(1 - red.busy_s / 5.0)
    ops = dict(red.top_ops(10))
    assert max(ops, key=ops.get) == "batched_weightings_pallas.1"
    gaps = red.gaps_by_label([(label, spans)
                              for label, spans in meta["labels"]])
    # every idle nanosecond goes to exactly one label
    assert sum(s for _, s in gaps) == pytest.approx(
        red.window_s - red.busy_s, rel=1e-9)
    assert {label for label, _ in gaps} >= {
        "fused launch: beta assembly, kernel, aggregation",
        "per-query host execution (single_exec)"}


def test_interval_arithmetic():
    a = tracing.union([(0, 4), (3, 6), (8, 10)])
    assert a.tolist() == [[0, 6], [8, 10]]
    b = tracing.union([(1, 2), (5, 9)])
    assert tracing.intersect(a, b).tolist() == [[1, 2], [5, 6], [8, 9]]
    assert tracing.subtract(a, b).tolist() == [[0, 1], [2, 5], [9, 10]]
    assert tracing.total(tracing.clip(a, 2, 9)) == 5


def test_roofline_reader_on_the_chip_trace(chip_trace):
    events, meta = chip_trace
    red = tracing.Reduced(events, meta["t_sync"], meta["t0"], meta["t1"])
    mod = hs.load_module(hs.BENCH / "metrics" / "weightings_roofline.open.py",
                         "bench_metric_roofline_test")

    class View:
        reduced = red
        launches = [tuple(x) for x in meta["launches"]]
        device_kind = "TPU v5 lite"
        t0, t1 = meta["t0"], meta["t1"]

    share = mod.read(View)
    assert 0 < share <= 100
    kernel_s = sum(e - s for _, s, e in red.op_events(mod.is_kernel)) / 1e9
    least = sum(costs.least_seconds(*costs.weightings_launch(q, k1, pairs),
                                    costs.peaks("TPU v5 lite"))[0]
                for _, _, q, k1, pairs in View.launches)
    assert share == pytest.approx(100 * least / kernel_s, rel=0.05)
    View.launches = []
    assert mod.read(View) is None         # nothing to read: no metric


def test_manifest_follows_the_contract():
    man = hs.manifest()
    e2e = {m["name"]: m for m in man["end_to_end"]}
    cells = {w["name"]: w for w in man["workloads"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in man["end_to_end"] + man["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in man["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for w in man["workloads"]:
        assert NAME.match(w["name"]) and w["chips"] in (1, 4)
        assert 0 < len(w["why"]) <= 200
        assert (hs.BENCH / "traffic" / f"{w['traffic']}.json").is_file()
    reports = {c: {m for m, e in e2e.items()
                   if "workloads" not in e or c in e["workloads"]}
               for c in cells}
    for m in man["per_layer"]:
        assert m["moves"] in e2e
        assert 0 < len(m["layer"]) <= 200 and "\n" not in m["layer"]
        assert (hs.BENCH / "metrics" / f"{m['name']}.py").is_file()
        for c in m["workloads"]:
            assert m["moves"] in reports[c], (m["name"], c)
    for c in cells:
        assert "setup_s" in reports[c] and len(reports[c]) >= 2
        assert any(c in m["workloads"] for m in man["per_layer"])
    for cfg in man["configs"]:
        assert (hs.ROOT / cfg["file"]).is_file()
        assert any(w["config"] == cfg["name"] for w in man["workloads"])
    assert len(json.dumps(man)) < 64 * 1024
