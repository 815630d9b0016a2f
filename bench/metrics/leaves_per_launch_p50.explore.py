"""Median plans per fused kernel launch in the window: statements and
GROUP BY leaves alike, from the benchmark's launch recorder (each launch
carries three bound variants of every plan)."""
from bench import stats


def read(run):
    plans = [q // 3 for a, b, q, *_ in run.launches
             if run.t0 <= a and b <= run.t1]
    return stats.median(plans) if plans else None
