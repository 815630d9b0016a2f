"""Operations and bytes of a kernel launch, from its logical shapes, and the
table of device peaks they are held against.

Logical shapes are the sizes of the work before any padding: a launch that
pads K to 128 lanes or Q to a power of two does no more useful work than
one that does not, so the count stays the same whatever implements it.
"""
from __future__ import annotations

import json
import pathlib

PEAKS_FILE = pathlib.Path(__file__).resolve().parent / "peaks.json"
F32 = 4


def peaks(device_kind: str) -> dict:
    """Peak FLOP/s and HBM bytes/s of one chip of this kind. A kind that is
    not in the table is an error, never a default."""
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks recorded for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def weightings_launch(q: int, k1: int, pairs) -> tuple[int, int]:
    """(FLOPs, bytes) of one query-batched weightings launch.

    ``q`` rows of beta (queries times bound variants), ``k1`` bins of the
    executed column, and per predicate pair ``(kx, ky)``: the pair
    histogram's rows (bins of the executed column's side) and columns. Per
    pair the kernel computes ``v = beta @ H^T`` (q x ky by ky x kx),
    ``p = clip(v / hx)`` and ``p @ fold^T`` (q x kx by kx x k1), and
    multiplies the product into the (q, k1) result. Bytes: H, fold, hx and
    beta read once, the result written once, all float32.
    """
    flops = 0
    nbytes = q * k1 * F32
    for kx, ky in pairs:
        flops += 2 * q * ky * kx          # beta @ H^T
        flops += 2 * q * kx               # divide + clip
        flops += 2 * q * kx * k1          # p @ fold^T
        flops += q * k1                   # running product
        nbytes += (kx * ky + kx * k1 + kx + q * ky) * F32
    return flops, nbytes


def least_seconds(flops: int, nbytes: int, peak: dict) -> tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    t_flop = flops / peak["flops_per_s"]
    t_byte = nbytes / peak["hbm_bytes_per_s"]
    return (t_flop, "compute") if t_flop >= t_byte else (t_byte, "memory")
