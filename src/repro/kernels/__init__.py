"""Pallas-TPU kernels for the paper's compute hot-spots (DESIGN.md §3),
each one batched launch:

  * ``hist2d`` — pair-batched 2-D bin counting as one-hot matmuls on the
    MXU (construction);
  * ``subbin`` — pair-batched chi-squared sub-bin counting, the same way
    (construction);
  * ``weightings`` — query-batched fused multi-predicate H@beta -> fold ->
    Hadamard product chain (query execution: "a handful of small matmuls"
    per query fused into ONE kernel launch per group of queries).

Each package: ``<name>.py`` (pl.pallas_call + BlockSpec), ``ops.py`` (jit
wrapper with padding, power-of-two launch bucketing and CPU-interpret
fallback), ``ref.py`` (pure-jnp oracle).
"""
