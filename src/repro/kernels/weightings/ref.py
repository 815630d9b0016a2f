"""Pure-jnp oracle for the query-batched fused weightings kernel."""
from __future__ import annotations

import jax.numpy as jnp


def batched_weightings_ref(h_stack, beta, fold, hx):
    """Query-batched fused weightings — Eq. 25/27/28 over Q queries at once.

    The (H, fold, hx) stacks depend only on the (agg column, predicate
    columns) plan shape, so a group of queries sharing that shape shares
    them; only beta varies per query.

    h_stack: (L, K2, K2)   shared pair-count matrices
    beta:    (Q, L, K2)    per-query coverage vectors
    fold:    (L, K1, K2)   shared one-hot gathers
    hx:      (L, K2)       shared pair x-row totals
    Returns  (Q, K1) per-query probability products.
    """
    v = jnp.einsum("lab,qlb->qla", h_stack, beta)            # (Q, L, K2)
    p_row = jnp.clip(v / jnp.maximum(hx, 1e-30)[None], 0.0, 1.0)
    p1 = jnp.einsum("lka,qla->qlk", fold, p_row)             # (Q, L, K1)
    return jnp.prod(p1, axis=1)
