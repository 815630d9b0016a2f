"""2-D bin-counting kernel (construction hot spot): one pair-batched
launch, with a Pallas one-hot-matmul kernel and a scatter-add jnp oracle.
See ``ops.py`` for the padding and power-of-two bucketing contracts."""
from repro.kernels.hist2d.ops import batched_hist2d  # noqa: F401
