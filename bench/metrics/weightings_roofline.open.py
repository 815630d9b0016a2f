"""Roofline share of the query-batched weightings kernel, open-loop cells.

For every fused launch of the window the least time the chip could take is
the larger of its operations over peak FLOP/s and its bytes over peak HBM
bytes/s (``bench/costs.py``, from the launch's logical shapes); the share
is the sum of those least times over the device time of the kernel events
that ran inside the launches' host intervals. Launches with no kernel event
in the trace are left out on both sides.
"""
import numpy as np

from bench import costs

KERNEL = "batched_weightings_pallas"


def is_kernel(name: str) -> bool:
    return name.split(":")[-1].startswith(KERNEL)


def read(run):
    if run.reduced is None or not run.launches:
        return None
    events = run.reduced.op_events(is_kernel)
    if not events:
        return None
    peak = costs.peaks(run.device_kind)
    starts = np.asarray([s for _, s, _ in events])
    spans = np.asarray([e - s for _, s, e in events])
    least = spent = 0.0
    for a, b, q, k1, pairs in run.launches:
        if a < run.t0 or b > run.t1:
            continue
        i, j = np.searchsorted(starts, [run.reduced.to_ns(a),
                                        run.reduced.to_ns(b)])
        if j <= i:
            continue
        spent += float(spans[i:j].sum()) / 1e9
        least += costs.least_seconds(*costs.weightings_launch(q, k1, pairs),
                                     peak)[0]
    return 100.0 * least / spent if spent else None
