"""Median of the explain ``plan`` stage (submit to planned: fingerprint,
template or plan cache, cold parse) over the window's answered statements,
open-loop cells."""
from bench import stats


def read(run):
    xs = run.stage("plan")
    return stats.median(xs) if xs else None
