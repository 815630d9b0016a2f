"""Device trace: recording, reading, and the reduction to metrics.

A traced run records a ``jax.profiler`` trace of its window with the Python
tracer off. ``read_xplane`` keeps what the reduction needs: every device
operation (plane ``/device:...``, line ``XLA Ops``) and the benchmark's own
host annotations (names starting ``bench.``). The reduction works on that
compact form, so a committed trace recorded on the chip checks it.

Clock: ``bench.sync`` is a host annotation opened at a known
``time.perf_counter()`` reading; its start on the profiler clock maps every
host interval the benchmark or the server recorded on ``perf_counter`` onto
the trace.
"""
from __future__ import annotations

import glob
import gzip
import json
import os

import numpy as np

SYNC = "bench.sync"
OPS_LINE = "XLA Ops"


def start(log_dir: str):
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def stop():
    import jax

    jax.profiler.stop_trace()


def sync_mark() -> float:
    """Open and close the sync annotation; returns its perf_counter time."""
    import time

    import jax

    t = time.perf_counter()
    with jax.profiler.TraceAnnotation(SYNC):
        pass
    return t


def short(name: str) -> str:
    """An HLO op's name without its signature: ``%fusion.3 = f32[8] ...``
    becomes ``fusion.3``."""
    return name.split(" = ", 1)[0].lstrip("%")


def op_name(ev) -> str:
    """``<hlo module>:<op>`` where the event names its module, so a kernel
    is found by the jitted function that launched it."""
    module = dict(ev.stats).get("hlo_module")
    return f"{module}:{ev.name}" if module else ev.name


def read_xplane(log_dir: str) -> dict:
    """The newest trace under ``log_dir`` in compact form::

        {"device": {plane: [[op, start_ns, dur_ns], ...]},
         "host": [[annotation, start_ns, dur_ns], ...]}
    """
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no trace under {log_dir}")
    data = ProfileData.from_file(files[-1])
    out = {"device": {}, "host": []}
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    out["device"].setdefault(plane.name, []).extend(
                        [op_name(ev), float(ev.start_ns),
                         float(ev.duration_ns)]
                        for ev in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["host"].extend(
                    [ev.name, float(ev.start_ns), float(ev.duration_ns)]
                    for ev in line.events if ev.name.startswith("bench."))
    return out


def load(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


# ------------------------------------------------------------- intervals

def union(intervals) -> np.ndarray:
    """Sorted, merged (n, 2) array of [start, end) intervals."""
    arr = np.asarray(sorted(intervals), float).reshape(-1, 2)
    if not len(arr):
        return arr
    out = [list(arr[0])]
    for s, e in arr[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.asarray(out)


def clip(intervals: np.ndarray, t0: float, t1: float) -> np.ndarray:
    arr = np.clip(np.asarray(intervals, float).reshape(-1, 2), t0, t1)
    return arr[arr[:, 1] > arr[:, 0]]


def total(intervals: np.ndarray) -> float:
    return float(np.sum(intervals[:, 1] - intervals[:, 0])) if len(
        intervals) else 0.0


def intersect(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Intersection of two merged interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i, 0], b[j, 0]), min(a[i, 1], b[j, 1])
        if s < e:
            out.append((s, e))
        if a[i, 1] < b[j, 1]:
            i += 1
        else:
            j += 1
    return np.asarray(out, float).reshape(-1, 2)


def subtract(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """``a`` minus ``b``, both merged interval lists."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j, 1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k, 0] < e:
            if b[k, 0] > cur:
                out.append((cur, b[k, 0]))
            cur = max(cur, b[k, 1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return np.asarray(out, float).reshape(-1, 2)


# ------------------------------------------------------------- reduction

class Reduced:
    """A traced window reduced to device busy time, per-op time and idle
    gaps; host intervals on ``perf_counter`` map onto it by the sync mark.

    ``t0``/``t1`` bound the window in ``perf_counter`` seconds.
    """

    def __init__(self, events: dict, t_sync: float, t0: float, t1: float):
        sync = [s for n, s, _ in events["host"] if n == SYNC]
        if not sync:
            raise ValueError("trace holds no sync annotation")
        self._sync_ns = sync[0]
        self._t_sync = t_sync
        self.w0, self.w1 = self.to_ns(t0), self.to_ns(t1)
        self.window_s = (self.w1 - self.w0) / 1e9
        self.chips = max(1, len(events["device"]))
        ops = [(short(n), s, s + d) for plane in events["device"].values()
               for n, s, d in plane]
        self.ops = [(n, max(s, self.w0), min(e, self.w1)) for n, s, e in ops
                    if e > self.w0 and s < self.w1]
        busy = 0.0
        self.busy = {}
        for plane, evs in events["device"].items():
            u = clip(union([(s, s + d) for _, s, d in evs]), self.w0, self.w1)
            self.busy[plane] = u
            busy += total(u)
        self.busy_s = busy / self.chips / 1e9
        self.host = events["host"]

    def to_ns(self, t: float) -> float:
        return self._sync_ns + (t - self._t_sync) * 1e9

    def op_seconds(self) -> dict:
        out: dict[str, float] = {}
        for n, s, e in self.ops:
            out[n] = out.get(n, 0.0) + (e - s) / 1e9
        return out

    def top_ops(self, k: int = 10) -> list:
        return sorted(self.op_seconds().items(), key=lambda kv: -kv[1])[:k]

    def op_events(self, match) -> list:
        """Device events (name, start_ns, end_ns) whose name ``match``
        accepts, in start order."""
        return sorted((ev for ev in self.ops if match(ev[0])),
                      key=lambda ev: ev[1])

    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def gaps_by_label(self, labelled: list, k: int = 10) -> list:
        """Idle device time split by what the host was doing.

        ``labelled`` is ``[(label, [(t0, t1), ...]), ...]`` on perf_counter
        seconds, highest priority first: each idle stretch goes to the
        first label that covers it; what no label covers is
        ``"none recorded"``. Averaged over chips, top ``k`` by seconds.
        """
        secs: dict[str, float] = {}
        for busy in self.busy.values():
            idle = subtract(np.asarray([[self.w0, self.w1]]), busy)
            for label, spans in labelled:
                cover = clip(union([(self.to_ns(a), self.to_ns(b))
                                    for a, b in spans]), self.w0, self.w1)
                hit = intersect(idle, cover)
                if len(hit):
                    secs[label] = secs.get(label, 0.0) + total(hit) / 1e9
                    idle = subtract(idle, cover)
            rest = total(idle) / 1e9
            if rest > 0:
                secs["none recorded"] = secs.get("none recorded", 0.0) + rest
        out = [(n, s / self.chips) for n, s in secs.items()]
        return sorted(out, key=lambda kv: -kv[1])[:k]
