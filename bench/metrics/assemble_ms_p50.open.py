"""Median over the window's admission waves of the wave's ``assemble``
stage (drain to scheduler entry: deadline expiry, re-plans, the deferred
template binds, GROUP BY leaf lookups). Read from the explain of each
answered statement: every statement of a wave carries the wave's id and
the same stage, so one value per wave; open-loop cells."""
from bench import stats


def read(run):
    waves = {e["wave"]: e["assemble_ms"] for e in run.explains
             if e.get("wave") is not None}
    return stats.median(waves.values()) if waves else None
