"""Jitted wrapper: padding, MXU-friendly K alignment, backend dispatch."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.hist2d.hist2d import batched_hist2d_pallas
from repro.kernels.hist2d.ref import batched_hist2d_ref


def _round_up(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult


def batched_hist2d(bi, bj, weights, ki: int, kj: int, *,
                   use_pallas: bool = True, interpret: bool | None = None,
                   tn: int = 1024):
    """Pair-batched weighted 2-D histograms: (P, N) -> (P, KI, KJ).

    This is the construction hot loop's inner op (one call per refinement
    round bins *every* pair), mirroring ``weightings.batched_weightings``:
    jnp oracle (dtype-preserving scatter-add) vs Pallas one-hot-matmul
    kernel with K dims padded to 128 lanes and N padded to the row tile.
    Padding is value-safe: padded rows carry weight 0 and padded K
    rows/columns are sliced away. Traceable under jit (static shapes).

    Power-of-two bucketing contract: the batch dimension P is fixed by the
    caller's chunking — ``BuildParams.pair_chunk`` rounds DOWN to a power
    of two (the chunk is a ``pair_chunk * k2^2 * s2_max`` memory *ceiling*,
    so bucketing must never exceed it), and the final partial chunk of a
    build buckets its launch size likewise, so jit recompiles stay bounded
    at ``log2(pair_chunk)`` variants per K shape. Compare
    ``weightings.ops.q_bucket``, the serving-side analogue, which buckets
    UP (padding there is cheaper than a lost fusion opportunity).
    """
    bi = jnp.asarray(bi, jnp.int32)
    bj = jnp.asarray(bj, jnp.int32)
    weights = jnp.asarray(weights)
    if not use_pallas:
        return batched_hist2d_ref(bi, bj, weights, ki, kj)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    p, n = bi.shape
    n_pad = _round_up(max(n, tn), tn)
    ki_pad = _round_up(ki, 128)
    kj_pad = _round_up(kj, 128)
    if n_pad != n:
        pad = ((0, 0), (0, n_pad - n))
        bi = jnp.pad(bi, pad)
        bj = jnp.pad(bj, pad)
        weights = jnp.pad(weights, pad)  # zero weight => no contribution
    out = batched_hist2d_pallas(bi[:, None], bj[:, None],
                                weights.astype(jnp.float32)[:, None],
                                ki_pad, kj_pad, tn=tn,
                                interpret=bool(interpret))
    return out[:, :ki, :kj].astype(weights.dtype)
