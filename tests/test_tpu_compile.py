"""Compile every Pallas kernel of the served and construction paths for a
described TPU v5e chip, at the shapes the system runs.

Nothing runs: the TPU compiler that ships with JAX compiles for a chip that
is described, not attached, so Mosaic's lowering, tiling and VMEM checks
run here without one. ``repro.core`` is imported first because every AQP
process imports it, and it turns ``jax_enable_x64`` on; the kernels must
lower under that setting. The topology is described inside a fixture (only
one process may load the TPU library, so never at import), and JAX's
persistent compilation cache is off around the compiles: an entry written
for the described chip cannot be read back without one.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import pytest

import repro.core  # noqa: F401  (x64 on, as in every AQP process)
from repro.kernels.hist2d import batched_hist2d
from repro.kernels.subbin import batched_subbin_hist
from repro.kernels.weightings.weightings import batched_weightings_pallas

N_ROWS = 100_352         # BuildParams.n_samples padded to the 1024-row tile
PAIRS = 8                # BuildParams.pair_chunk
K1, K2, L = 512, 256, 4  # k1_cap, k2_cap, predicates per AND chain
S2_MAX = 32              # BuildParams.s2_max


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # no compiler logs on disk
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        cache_was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", cache_was)
            compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def test_x64_is_on():
    assert jax.config.jax_enable_x64


# q_bucket's floor, a 14-leaf GROUP BY launch's bucket, the largest group.
@pytest.mark.parametrize("q", [8, 16, 256])
def test_batched_weightings_compiles(one_chip, q):
    fn = functools.partial(batched_weightings_pallas, interpret=False)
    _compile(fn, one_chip,
             ((L, K2, K2), jnp.float32), ((L, q, K2), jnp.float32),
             ((L, K1, K2), jnp.float32), ((L, K2), jnp.float32))


def test_batched_hist2d_compiles(one_chip):
    # Through the ops wrapper, as refinement calls it: f64 weights, padding
    # and the (P, 1, N) row layout included.
    fn = functools.partial(batched_hist2d, ki=K2, kj=K2, use_pallas=True,
                           interpret=False)
    _compile(fn, one_chip,
             ((PAIRS, N_ROWS), jnp.int32), ((PAIRS, N_ROWS), jnp.int32),
             ((PAIRS, N_ROWS), jnp.float64))


@pytest.mark.parametrize("k2", [64, 128, 256])
def test_batched_subbin_hist_compiles(one_chip, k2):
    # Every rung of the capacity ladder up to BuildParams.k2_cap.
    fn = functools.partial(batched_subbin_hist, ncell=k2 * k2, s_max=S2_MAX,
                           use_pallas=True, interpret=False)
    _compile(fn, one_chip,
             ((PAIRS, N_ROWS), jnp.int32), ((PAIRS, N_ROWS), jnp.int32),
             ((PAIRS, N_ROWS), jnp.float64))
