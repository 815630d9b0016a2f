"""Fused weightings kernel (query hot spot): one query-batched launch,
with a Pallas kernel and a jnp oracle; a single query is the batch at
Q=1. See ``ops.py`` for the padding and the ``q_bucket`` power-of-two
bucketing contract shared with the serving batch scheduler."""
from repro.kernels.weightings.ops import batched_weightings, q_bucket  # noqa: F401
