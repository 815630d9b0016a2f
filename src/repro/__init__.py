"""repro — PairwiseHist AQP framework on JAX, with a multi-arch LM substrate.

Layout:
  repro.core      — the paper's contribution (PairwiseHist synopsis + queries)
  repro.gd        — GreedyGD compression substrate
  repro.aqp       — end-to-end AQP engine, datasets, baselines, exact engine
  repro.kernels   — batched Pallas TPU kernels (hist2d, subbin, weightings) + refs
  repro.models    — 10 assigned LM architectures
  repro.sharding  — logical-axis sharding rules
  repro.train     — optimizer, train step, telemetry, grad compression
  repro.serve     — prefill/decode serving
  repro.ckpt      — fault-tolerant checkpointing
  repro.data      — data pipelines
  repro.configs   — architecture configs
  repro.launch    — mesh / dryrun / train / serve entry points

NOTE: importing `repro` has no JAX side effects (no x64 flag, no device init).
`repro.core` enables x64 at import (AQP needs int64/float64 domains); the LM
stack never imports `repro.core` and uses explicit dtypes throughout.
"""

__version__ = "1.0.0"
