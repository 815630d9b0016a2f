"""Share of executed statements that ran unfused because their plan shape
was alone (below the scheduler's ``min_group``) in their wave: window
deltas of the server's ``fallback_lone`` over ``batched + fallback`` for
the cell's table, open-loop cells."""


def read(run):
    if "fallback_lone" not in run.stats1["tables"].get(run.table, {}):
        return None
    total = run.table_delta("batched") + run.table_delta("fallback")
    return 100.0 * run.table_delta("fallback_lone") / total if total else None
