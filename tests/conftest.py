import numpy as np
import pytest

# The helpers below import ``repro.core`` lazily, as the fixtures do: the
# import turns on jax x64 for the whole process.


def _cols(d):
    from repro.core.types import ColumnInfo
    return [ColumnInfo(name=f"c{i}", kind="int") for i in range(d)]


def _sequential_pairs(sample, hists, params, crit2, m_pts, stats, timeline):
    """The sequential oracle fitted to the pair-builder seam."""
    from repro.core.build import build_pairs_sequential
    stats["mode"] = "sequential"
    return build_pairs_sequential(sample, hists, params, crit2, m_pts)


def _sequential(data, cols, params):
    """The synopsis with its pair phase built by the sequential oracle."""
    from repro.core.build import _build_pairwise_hist
    return _build_pairwise_hist(data, cols, params, None, None,
                                _sequential_pairs)


def _assert_same_synopsis(a, b):
    for h1, h2 in zip(a.hists, b.hists):
        for f, x, y in zip(h1._fields, h1, h2):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                          err_msg=f"hist field {f}")
    assert set(a.pairs) == set(b.pairs)
    for key in a.pairs:
        for f, x, y in zip(a.pairs[key]._fields, a.pairs[key], b.pairs[key]):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                          err_msg=f"pair {key} field {f}")


@pytest.fixture(scope="session")
def small_table():
    rng = np.random.default_rng(1)
    n = 60_000
    c0 = rng.integers(0, 1000, n).astype(float)
    c1 = np.abs(rng.normal(300, 80, n)).round()
    c2 = (c1 * 3 + rng.normal(0, 30, n)).round()
    c3 = rng.zipf(1.7, n).clip(1, 40).astype(float)
    c3[rng.random(n) < 0.04] = np.nan
    return {"c0": c0, "c1": c1, "c2": c2, "c3": c3}


@pytest.fixture(scope="session")
def synopsis(small_table):
    from repro.core.build import build_pairwise_hist
    from repro.core.types import BuildParams, ColumnInfo
    data = np.stack(list(small_table.values()), 1)
    cols = [ColumnInfo(name=k, kind="int") for k in small_table]
    return build_pairwise_hist(data, cols, BuildParams(n_samples=30_000,
                                                       seed=3))


@pytest.fixture(scope="session")
def engine(synopsis):
    from repro.core.query import QueryEngine
    return QueryEngine(synopsis)


@pytest.fixture(scope="session")
def exact(small_table):
    from repro.aqp.exact import ExactEngine
    return ExactEngine(small_table)
