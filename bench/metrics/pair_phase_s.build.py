"""Mean pair-refinement time per timed build (``build_stats
["pair_phase_s"]``, published as the framework's ``build_pairs_s``): the
compacting 2-D refinement of ``core/build.py`` and ``core/refine.py``."""
from bench import stats


def read(run):
    xs = [b["build_pairs_s"] for b in run.builds if "build_pairs_s" in b]
    return stats.median(xs) if xs else None
