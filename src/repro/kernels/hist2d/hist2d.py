"""Pallas-TPU kernel: pair-batched 2-D histograms via one-hot matmuls on
the MXU.

Scatter-adds serialize on TPU; instead each grid step turns a tile of TN
rows of one pair into two one-hot matrices and accumulates

    H_p += one_hot(bi_tile) @ (one_hot(bj_tile) * w_tile)^T

— a (KI x TN) @ (TN x KJ) systolic matmul. Each pair's (KI, KJ)
accumulator lives in VMEM across its row tiles (KI, KJ <= 512 -> <= 1 MiB
f32); row tiles stream HBM -> VMEM via BlockSpec.

This is the TPU adaptation of PairwiseHist construction's hot spot (DESIGN.md
§3): bin counting for d(d-1)/2 pair histograms over N_s sampled rows.
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


def _batched_kernel(bi_ref, bj_ref, w_ref, out_ref, *, ki: int, kj: int,
                    tn: int):
    """One grid step = (pair p, row tile t): accumulate into pair p's plane.

    Rows lie along lanes (blocks are (1, 1, TN)), so the one-hots are built
    transposed, (K, TN), and contracted over TN.
    """
    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    bi = bi_ref[0]                                     # (1, TN) i32
    bj = bj_ref[0]
    w = w_ref[0].astype(jnp.float32)
    rows_i = jax.lax.broadcasted_iota(jnp.int32, (ki, tn), 0)
    rows_j = jax.lax.broadcasted_iota(jnp.int32, (kj, tn), 0)
    oh_i = (rows_i == bi).astype(jnp.float32)                      # (KI, TN)
    oh_j = (rows_j == bj).astype(jnp.float32) * w                  # (KJ, TN)
    out_ref[0] += jax.lax.dot_general(
        oh_i, oh_j, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)            # (KI, KJ)


@functools.partial(jax.jit, static_argnames=("ki", "kj", "tn", "interpret"))
def batched_hist2d_pallas(bi, bj, weights, ki: int, kj: int, tn: int = 1024,
                          interpret: bool = True):
    """Pair-batched 2-D histogram: (P, 1, N) indices/weights -> (P, KI, KJ).

    The grid is (P, N // tn); each pair's accumulator plane lives in VMEM
    across its row tiles (tiles are the innermost grid dimension, so a
    pair's steps are contiguous and the revisited output block stays
    resident). The unit middle axis makes each (1, 1, tn) block's last two
    dimensions equal the array's or a lane multiple, as Mosaic requires for
    any P. Rows with out-of-histogram indices must carry weight 0.
    """
    p, _, n = bi.shape
    assert n % tn == 0, "pad N to a multiple of the row tile in ops.py"
    grid = (p, n // tn)
    row_spec = pl.BlockSpec((1, 1, tn), lambda pi, ti: (pi, _I0, ti))
    return pl.pallas_call(
        functools.partial(_batched_kernel, ki=ki, kj=kj, tn=tn),
        grid=grid,
        in_specs=[row_spec, row_spec, row_spec],
        out_specs=pl.BlockSpec((1, ki, kj), lambda pi, ti: (pi, _I0, _I0)),
        out_shape=jax.ShapeDtypeStruct((p, ki, kj), jnp.float32),
        interpret=interpret,
    )(bi, bj, weights)
