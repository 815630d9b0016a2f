"""Pure-jnp oracle for the pair-batched hist2d kernel."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def batched_hist2d_ref(bi, bj, weights, ki: int, kj: int):
    """Pair-batched oracle: (P, N) indices/weights -> (P, KI, KJ) with
    H[p, a, b] = sum_n w[p, n] [bi[p, n] == a][bj[p, n] == b]
    (out-of-range rows must carry weight 0).

    It *preserves the weight dtype*: synopsis construction feeds f64
    ones/flags and compares counts bit-for-bit against the sequential
    per-pair ``segment_sum`` path (counts are exact integers, so the f32
    Pallas path agrees too for N < 2^24).
    """
    def one(bi_p, bj_p, w_p):
        h = jnp.zeros((ki, kj), weights.dtype)
        return h.at[jnp.clip(bi_p, 0, ki - 1), jnp.clip(bj_p, 0, kj - 1)].add(w_p)

    return jax.vmap(one)(bi, bj, weights)
