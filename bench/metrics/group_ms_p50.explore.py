"""Median of the explain ``group`` stage (a GROUP BY statement's leaf
gathering and group assembly in its wave's resolve) over the window's
answered GROUP BY statements. None where the program records no such
stage."""
from bench import stats


def read(run):
    xs = [e["group_ms"] for e in run.explains if "group_ms" in e]
    return stats.median(xs) if xs else None
