"""Median of the explain ``execute`` stage (the wave's scheduler call: fused
launches, beta assembly and per-query host aggregation) over the window's
answered statements, open-loop cells."""
from bench import stats


def read(run):
    xs = run.stage("execute")
    return stats.median(xs) if xs else None
