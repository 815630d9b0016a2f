"""Jitted wrapper: flat-id decomposition, padding, backend dispatch."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.subbin.ref import batched_subbin_hist_ref
from repro.kernels.subbin.subbin import (KQ_TILE,
                                         batched_subbin_hist_pallas)


def _round_up(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult


def batched_subbin_hist(cell, sub, weights, ncell: int, s_max: int, *,
                        use_pallas: bool = True,
                        interpret: bool | None = None, tn: int = 1024):
    """Pair-batched sub-bin histograms: (P, N) -> (P, ncell, s_max).

    This is the chi-squared inner scatter of 2-D refinement (the one
    remaining per-round scatter after the bin counts moved to
    ``hist2d.batched_hist2d``): every valid point of pair ``p`` adds its
    weight to ``out[p, cell, sub]``. Rows that must not contribute (null
    rows, padding) carry weight 0; indices are clipped, never trusted.

    Dispatch mirrors ``hist2d.batched_hist2d``: a dtype-preserving
    ``segment_sum`` jnp oracle (bit-for-bit against the legacy in-loop
    scatter — construction compares exact integer counts) vs the Pallas
    one-hot-matmul kernel. For the kernel the flattened id
    ``cell * s_max + sub`` is decomposed base-128 (``q = id // 128``,
    ``r = id % 128``) so the one-hot minor dimension is exactly the MXU
    lane width; the (KQ, 128) planes are sliced back to (ncell, s_max).
    N pads to the row tile with weight-0 rows; the batch dimension P
    follows the caller's power-of-two bucketing contract (see
    ``hist2d/ops.py``).
    """
    cell = jnp.asarray(cell, jnp.int32)
    sub = jnp.asarray(sub, jnp.int32)
    weights = jnp.asarray(weights)
    if not use_pallas:
        return batched_subbin_hist_ref(cell, sub, weights, ncell, s_max)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    p, n = cell.shape
    k = ncell * s_max
    kq = _round_up(-(-k // 128), 8)       # ceil(k/128), sublane-aligned
    tkq = min(kq, KQ_TILE)
    kq = _round_up(kq, tkq)
    flat = (jnp.clip(cell, 0, ncell - 1) * s_max
            + jnp.clip(sub, 0, s_max - 1))
    q = flat // 128
    r = flat % 128
    n_pad = _round_up(max(n, tn), tn)
    w = weights.astype(jnp.float32)
    if n_pad != n:
        pad = ((0, 0), (0, n_pad - n))
        q = jnp.pad(q, pad)
        r = jnp.pad(r, pad)
        w = jnp.pad(w, pad)               # zero weight => no contribution
    out = batched_subbin_hist_pallas(q[:, None], r[:, None], w[:, None],
                                     kq, tkq=tkq, tn=tn,
                                     interpret=bool(interpret))
    out = out.reshape(p, kq * 128)[:, :k].reshape(p, ncell, s_max)
    return out.astype(weights.dtype)
