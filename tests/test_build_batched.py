"""Pair-batched 2-D construction vs the sequential per-pair oracle, on a
table with a constant column and a NULL-heavy column.

The served pair phase (build.build_pairs_compact: refine.refine_2d_compact
and pair_metadata_batch) must be *bit-for-bit* equal to the per-pair host
loop (build.build_pairs_sequential, built by conftest._sequential through
the module-private build._build_pairwise_hist) in oracle (numpy/jnp) mode:
every count is an exact integer and every float statistic is computed by
the same ops on the same values. Covers NaN-masked rows, constant columns,
the K2-capacity guard, slot-count bucketing, and the adaptive capacity
ladder.
"""
import dataclasses

import numpy as np
import pytest

from conftest import _assert_same_synopsis, _cols, _sequential
from repro.core.build import build_pairwise_hist
from repro.core.types import BuildParams


def _table(n=6000, seed=7):
    rng = np.random.default_rng(seed)
    c0 = rng.integers(0, 500, n).astype(float)
    c1 = np.abs(rng.normal(300, 80, n)).round()
    c2 = (c1 * 2 + rng.normal(0, 25, n)).round()   # correlated with c1
    c3 = rng.zipf(1.7, n).clip(1, 40).astype(float)
    c3[rng.random(n) < 0.05] = np.nan              # NULL-heavy column
    c4 = np.full(n, 7.0)                           # constant column
    return np.stack([c0, c1, c2, c3, c4], 1)


@pytest.fixture(scope="module")
def data():
    return _table()


@pytest.fixture(scope="module")
def seq_synopsis(data):
    params = BuildParams(n_samples=data.shape[0], k2_cap=64, s2_max=16)
    return _sequential(data, _cols(data.shape[1]), params)


def test_batched_equals_sequential_bitforbit(data, seq_synopsis):
    params = BuildParams(n_samples=data.shape[0], k2_cap=64, s2_max=16,
                         pair_chunk=4)
    batched = build_pairwise_hist(data, _cols(data.shape[1]), params)
    _assert_same_synopsis(seq_synopsis, batched)


def test_chunk_bucketing_invariance(data, seq_synopsis):
    """Slot count (incl. non-pow2, rounded down) never changes bits."""
    for chunk in (1, 2, 3, 16):
        params = BuildParams(n_samples=data.shape[0], k2_cap=64, s2_max=16,
                             pair_chunk=chunk)
        batched = build_pairwise_hist(data, _cols(data.shape[1]), params)
        _assert_same_synopsis(seq_synopsis, batched)


def test_capacity_ladder_escalation(data):
    """A tiny first rung forces the guard to bind and the capped pairs to
    re-run one rung up; the escalated result must still match the
    sequential loop run directly at full capacity."""
    p_seq = BuildParams(n_samples=data.shape[0], k2_cap=128, s2_max=16)
    p_esc = dataclasses.replace(p_seq, pair_chunk=4, k2_start=4)
    seq = _sequential(data, _cols(data.shape[1]), p_seq)
    esc = build_pairwise_hist(data, _cols(data.shape[1]), p_esc)
    _assert_same_synopsis(seq, esc)


def test_k2_capacity_guard(data):
    """At a deliberately tiny k2_cap the guard binds in both paths; the
    batched ladder is pinned at K2 and must reproduce the capped bins."""
    params = BuildParams(n_samples=data.shape[0], k2_cap=8, s2_max=16)
    seq = _sequential(data, _cols(data.shape[1]), params)
    bat = build_pairwise_hist(data, _cols(data.shape[1]), params)
    _assert_same_synopsis(seq, bat)
    for pr in bat.pairs.values():
        assert int(pr.kx) <= 8 and int(pr.ky) <= 8


def test_all_nan_pair_column():
    """A column that is NULL on every row yields empty pair histograms
    without breaking either path."""
    rng = np.random.default_rng(0)
    n = 2000
    data = np.stack([rng.integers(0, 100, n).astype(float),
                     np.full(n, np.nan),
                     np.abs(rng.normal(50, 10, n)).round()], 1)
    params = BuildParams(n_samples=n, k2_cap=32, s2_max=16)
    seq = _sequential(data, _cols(3), params)
    bat = build_pairwise_hist(data, _cols(3), params)
    _assert_same_synopsis(seq, bat)
    assert bat.columns[1].n_null == n
    assert float(bat.pairs[(0, 1)].H.sum()) == 0.0


def test_build_does_not_mutate_caller_columns(data):
    cols = _cols(data.shape[1])
    params = BuildParams(n_samples=data.shape[0], k2_cap=32, s2_max=16)
    syn = build_pairwise_hist(data, cols, params)
    assert all(c.n_null == 0 for c in cols), \
        "build_pairwise_hist mutated the caller's ColumnInfo list"
    assert syn.columns is not cols
    assert syn.columns[3].n_null > 0          # NaN column counted on the copy
    assert all(a is not b for a, b in zip(cols, syn.columns))


def test_device_presort_matches_host_presort():
    """The jitted presort (device-resident callers) and the host np.lexsort
    used by build must produce identical layouts — both are stable sorts on
    the same (+inf-keyed) keys, so every array matches exactly."""
    from repro.core.build import _presort_pairs_host
    from repro.core.refine import presort_pairs
    rng = np.random.default_rng(2)
    p, n = 3, 400
    x = rng.integers(0, 30, (p, n)).astype(float)   # many ties
    y = rng.integers(0, 30, (p, n)).astype(float)
    valid = rng.random((p, n)) < 0.9
    host = _presort_pairs_host(x, y, valid)
    import jax.numpy as jnp
    dev = presort_pairs(jnp.asarray(x), jnp.asarray(y), jnp.asarray(valid))
    for name, h, d in zip("xo1 vo1 new1 yo2 vo2 new2 perm12 perm21".split(),
                          host, dev):
        np.testing.assert_array_equal(h, np.asarray(d), err_msg=name)


def test_prep_columns_matches_per_column_reference():
    """Vectorized all-column prep == the straightforward per-column loop."""
    from repro.core.build import _prep_columns
    rng = np.random.default_rng(5)
    n, d = 500, 4
    sample = rng.normal(0, 10, (n, d)).round()
    sample[rng.random((n, d)) < 0.1] = np.nan
    sample[:, 2] = 3.0                         # constant column
    xs_all, up_all, nv, vmin, vmax = _prep_columns(sample)
    for i in range(d):
        x = sample[:, i].copy()
        nan = np.isnan(x)
        x[nan] = np.inf
        xs = np.sort(x)
        n_valid = int(x.size - nan.sum())
        new = np.empty(x.size, bool)
        new[0] = True
        new[1:] = xs[1:] != xs[:-1]
        up = np.concatenate([[0], np.cumsum(new)]).astype(np.int64)
        np.testing.assert_array_equal(xs_all[i], xs)
        np.testing.assert_array_equal(up_all[i], up)
        assert nv[i] == n_valid
        if n_valid:
            assert vmin[i] == xs[0] and vmax[i] == xs[n_valid - 1]
