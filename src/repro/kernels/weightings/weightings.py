"""Pallas-TPU kernel: query-batched fused multi-predicate weightings
(§5.3, Eq. 28).

The paper's query path runs ~3 small ops per predicate (mat-vec, divide,
fold) plus a combine — at sub-ms latencies the launch/dispatch overhead
dominates. This kernel fuses the whole AND-chain of Q same-shape queries:

    grid step l (one per predicate):
        v     = beta_l @ H_l^T        (Q x K2) @ (K2 x K2)   [MXU]
        p_row = clip(v / hx_l, 0, 1)                          [VPU]
        p1    = p_row @ fold_l^T      (Q x K2) @ (K1 x K2)^T  [MXU]
        acc  *= p1                    running product         [VPU]

One launch per group of queries instead of ~3 ops x n_predicates per
query. The (Q, K1) accumulator stays resident in VMEM across the whole
grid; H/beta/hx/fold stream per predicate. Everything is padded to
128-lane multiples by ops.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

# Block indices must be int32: under ``jax_enable_x64`` (which ``repro.core``
# turns on) a bare ``0`` in an index map traces as int64, and Mosaic then
# fails to lower the kernel.
_I0 = np.int32(0)

# Full f32 contraction. Mosaic's default for f32 operands is one bf16 pass,
# which on a v5e left answers up to 1e-2 relative off the f64 reference;
# H holds counts in the thousands, which bf16's 8-bit mantissa rounds.
_PRECISION = jax.lax.Precision.HIGHEST


def _batched_kernel(h_ref, beta_ref, hx_ref, fold_ref, out_ref):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        out_ref[...] = jnp.ones_like(out_ref)

    hmat = h_ref[0]                        # (K2, K2)
    beta = beta_ref[0]                     # (Q, K2)
    hx = hx_ref[0]                         # (1, K2)
    fold = fold_ref[0]                     # (K1, K2)
    v = jax.lax.dot_general(beta, hmat, (((1,), (1,)), ((), ())),
                            precision=_PRECISION,
                            preferred_element_type=jnp.float32)  # (Q, K2)
    p_row = jnp.clip(v / jnp.maximum(hx, 1e-30), 0.0, 1.0)
    p1 = jax.lax.dot_general(p_row, fold, (((1,), (1,)), ((), ())),
                             precision=_PRECISION,
                             preferred_element_type=jnp.float32)  # (Q, K1)
    out_ref[...] *= p1


@functools.partial(jax.jit, static_argnames=("interpret",))
def batched_weightings_pallas(h_stack, beta, fold, hx, interpret: bool = True):
    """One launch for a whole plan-shape group.

    h_stack (L,K2,K2) f32, beta (L,Q,K2), fold (L,K1,K2), hx (L,K2).
    Returns (Q, K1): per-query prod_l fold_l(clip(H_l beta_ql / hx_l, 0, 1)).

    One grid step per predicate; the per-query mat-vec is a (Q,K2)x(K2,K2)
    matmul, so the MXU amortizes per-query dispatch as the grid amortizes
    per-predicate ops. The (Q,K1) accumulator stays resident in VMEM across
    the grid.
    """
    el, k2, _ = h_stack.shape
    q = beta.shape[1]
    k1 = fold.shape[1]
    hx2 = hx[:, None, :]                   # (L, 1, K2)
    return pl.pallas_call(
        _batched_kernel,
        grid=(el,),
        in_specs=[
            pl.BlockSpec((1, k2, k2), lambda l: (l, _I0, _I0)),
            pl.BlockSpec((1, q, k2), lambda l: (l, _I0, _I0)),
            pl.BlockSpec((1, 1, k2), lambda l: (l, _I0, _I0)),
            pl.BlockSpec((1, k1, k2), lambda l: (l, _I0, _I0)),
        ],
        out_specs=pl.BlockSpec((q, k1), lambda l: (_I0, _I0)),
        out_shape=jax.ShapeDtypeStruct((q, k1), jnp.float32),
        interpret=interpret,
    )(h_stack, beta, hx2, fold)
