"""Median over the timed builds of the pair phase's host time: the build
timeline's ``pair_phase`` interval (``timings["build_timeline"]``) minus
the union of its device launches through their transfers back
(``compact_launch``, ``pair_metadata``). What is left is the presorts,
launch preparation and bookkeeping between launches."""
from bench import stats, tracing

LAUNCHES = ("compact_launch", "pair_metadata")


def host_seconds(events) -> float | None:
    phase = [(e["t0"], e["t1"]) for e in events if e["name"] == "pair_phase"]
    if not phase:
        return None
    launches = [(e["t0"], e["t1"]) for e in events if e["name"] in LAUNCHES]
    return tracing.total(tracing.subtract(tracing.union(phase),
                                          tracing.union(launches)))


def read(run):
    xs = [host_seconds(b["build_timeline"]) for b in run.builds
          if "build_timeline" in b]
    xs = [x for x in xs if x is not None]
    return stats.median(xs) if xs else None
