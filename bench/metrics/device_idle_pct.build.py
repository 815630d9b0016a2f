"""Share of the traced build window in which no operation ran on the
device: 1 - (union of device op intervals) / window."""


def read(run):
    if run.reduced is None:
        return None
    return 100.0 * run.reduced.idle_share()
