"""Share of executed statements answered through a fused kernel launch:
window deltas of the server's ``batched`` and ``fallback`` counters for the
cell's table, open-loop cells."""


def read(run):
    fused = run.table_delta("batched")
    total = fused + run.table_delta("fallback")
    return 100.0 * fused / total if total else None
