"""Pallas-TPU kernel: sub-bin histograms via one-hot matmuls on the MXU.

The chi-squared uniformity test bins every point of every 2-D cell into one
of ``s <= s_max`` equal-width sub-bins — a histogram over ``ncell * s_max``
flattened (cell, sub-bin) ids, recomputed every refinement round. On TPU a
``segment_sum`` scatter over that id space serializes; instead the flat id
is decomposed base-128 as ``flat = q * 128 + r`` and each grid step turns a
tile of TN rows into two one-hot matrices and accumulates

    H += one_hot(q_tile)^T  @  (one_hot(r_tile) * w_tile)

— a (KQ x TN) @ (TN x 128) systolic matmul whose 128-lane minor dimension
is exactly the MXU lane width (no padding waste on the one-hot columns).
The KQ axis is tiled too: a grid step owns a (TKQ, 128) slice of a pair's
accumulator and builds only the (TKQ, TN) one-hot for it, so the VMEM a step
needs (about ``TKQ * TN * 4`` bytes of one-hot, 2 MiB at TKQ = 512,
TN = 1024) is the same at every capacity rung. The whole plane is
``ncell * s_max * 4`` bytes (512 KiB at k2 = 64, 8 MiB at the k2 = 256
ceiling) and the one-hot of an untiled KQ 64 MiB at the ceiling, far past
VMEM. The price of tiling is that each row tile is read once per KQ tile.

This mirrors ``kernels/hist2d``: same row layout, same padding contract
(rows padded to the tile carry weight 0), same f32 accumulation (counts are
exact integers below 2^24).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

# Block indices must be int32: under ``jax_enable_x64`` a bare ``0`` in an
# index map traces as int64, and Mosaic then fails to lower the kernel.
_I0 = np.int32(0)

# KQ tile: rows of a pair's (KQ, 128) accumulator owned by one grid step.
KQ_TILE = 512


def _batched_kernel(q_ref, r_ref, w_ref, out_ref, *, tkq: int, tn: int):
    """One grid step = (pair p, KQ tile k, row tile t): accumulate rows of
    tile t whose q falls in tile k into pair p's (TKQ, 128) slice."""
    @pl.when(pl.program_id(2) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    q = q_ref[0] - pl.program_id(1) * tkq             # (1, TN) i32, tile-local
    r = r_ref[0]
    w = w_ref[0].astype(jnp.float32)
    rows_q = jax.lax.broadcasted_iota(jnp.int32, (tkq, tn), 0)
    rows_r = jax.lax.broadcasted_iota(jnp.int32, (128, tn), 0)
    oh_q = (rows_q == q).astype(jnp.float32)                       # (TKQ, TN)
    oh_r = (rows_r == r).astype(jnp.float32) * w                   # (128, TN)
    out_ref[0] += jax.lax.dot_general(
        oh_q, oh_r, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)            # (TKQ, 128)


@functools.partial(jax.jit, static_argnames=("kq", "tkq", "tn", "interpret"))
def batched_subbin_hist_pallas(q, r, weights, kq: int, tkq: int = KQ_TILE,
                               tn: int = 1024, interpret: bool = True):
    """Pair-batched flat-id histogram: (P, 1, N) -> (P, KQ, 128).

    ``q``/``r`` are the base-128 digits of the flattened (cell, sub-bin) id
    (``ops.py`` computes them); rows with out-of-histogram ids must carry
    weight 0. The grid is (P, KQ // tkq, N // tn) with row tiles innermost,
    so each (TKQ, 128) accumulator slice stays VMEM-resident across the row
    tiles that feed it.
    """
    p, _, n = q.shape
    assert n % tn == 0, "pad N to a multiple of the row tile in ops.py"
    assert kq % tkq == 0, "pad KQ to a multiple of the KQ tile in ops.py"
    grid = (p, kq // tkq, n // tn)
    row_spec = pl.BlockSpec((1, 1, tn), lambda pi, ki, ti: (pi, _I0, ti))
    return pl.pallas_call(
        functools.partial(_batched_kernel, tkq=tkq, tn=tn),
        grid=grid,
        in_specs=[row_spec, row_spec, row_spec],
        out_specs=pl.BlockSpec((1, tkq, 128),
                               lambda pi, ki, ti: (pi, ki, _I0)),
        out_shape=jax.ShapeDtypeStruct((p, kq, 128), jnp.float32),
        interpret=interpret,
    )(q, r, weights)
