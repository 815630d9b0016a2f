"""Seeded stand-in for UCI "Individual household electric power consumption".

One row per minute: a timestamp split into ``ts``/``month``/``day`` and the
seven published measures, quantized as the published file quantizes them.
The distributions (a daily load curve, gamma-distributed active power,
voltage around 240 V) are assumed; the configuration lists them under
``assumed``. Copied from the program's dataset suite so that a change there
cannot move the benchmark's data; voltage keeps the published file's
0.01 V steps, where the suite rounds it to 0.1 V.
"""
from __future__ import annotations

import numpy as np


def generate(n: int, seed: int) -> dict:
    """``n`` rows as a column dict of float64 arrays, fixed by ``seed``."""
    rng = np.random.default_rng(seed)
    ts = np.arange(n, dtype=np.float64) * 60.0
    hour = (ts / 3600.0) % 24
    daily = (0.6 + 0.5 * np.exp(-((hour - 19) ** 2) / 8)
             + 0.2 * np.exp(-((hour - 7) ** 2) / 4))
    gap = np.round(np.abs(daily * rng.gamma(2.0, 0.6, n)), 3)
    grp = np.round(np.abs(rng.normal(0.12, 0.08, n)), 3)
    voltage = np.round(rng.normal(240.0, 3.2, n), 2)
    intensity = np.round(gap * 1000.0 / voltage / 0.95
                         + rng.normal(0, 0.2, n), 1)
    sub1 = np.round(np.clip(gap * rng.beta(2, 8, n) * 16, 0, None))
    sub2 = np.round(np.clip(gap * rng.beta(2, 6, n) * 13, 0, None))
    sub3 = np.round(np.clip(gap * rng.beta(4, 6, n) * 18, 0, None))
    day = np.floor(ts / 86400.0) % 31 + 1
    month = np.floor(ts / (86400.0 * 30)) % 12 + 1
    return {
        "ts": ts, "month": month, "day": day,
        "global_active_power": gap, "global_reactive_power": grp,
        "voltage": voltage, "global_intensity": intensity,
        "sub_metering_1": sub1, "sub_metering_2": sub2,
        "sub_metering_3": sub3,
    }
