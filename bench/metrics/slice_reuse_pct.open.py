"""Share of the leaf coverage slices that the window's fused launches
needed but did not evaluate, because an equal leaf of another plan in the
same launch already had: window deltas of the server's ``betas`` counters
for the cell's table, open-loop cells."""


def read(run):
    before, after = (
        (stats or {}).get("tables", {}).get(run.table, {}).get("betas")
        for stats in (run.stats0, run.stats1))
    if before is None or after is None:
        return None
    slices = after["slices"] - before["slices"]
    if not slices:
        return None
    return 100.0 * (1.0 - (after["computed"] - before["computed"]) / slices)
