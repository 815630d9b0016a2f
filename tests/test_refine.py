"""Level-synchronous refinement vs the paper-faithful sequential oracle."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import chi2 as chi2lib
from repro.core import ref_sequential, refine


def _bfs_edges(x, init, m_pts, crit):
    K = 384
    xs = np.sort(x)
    up = np.concatenate([[0], np.cumsum(np.concatenate([[True],
                                                        xs[1:] != xs[:-1]]))])
    e0 = np.full(K + 1, np.inf)
    e0[: len(init)] = init
    edges, k = refine.refine_1d(jnp.asarray(xs), jnp.asarray(up),
                                jnp.asarray(e0), jnp.int32(len(init) - 1),
                                jnp.float64(m_pts), jnp.asarray(crit))
    return np.asarray(edges)[: int(k) + 1]


@pytest.mark.parametrize("dist", ["bimodal", "uniform", "zipf", "steps"])
def test_bfs_equals_sequential_recursion(dist):
    rng = np.random.default_rng(11)
    n = 4000
    x = {
        "bimodal": np.where(rng.random(n) < 0.4, rng.normal(50, 3, n),
                            rng.normal(200, 30, n)).round(),
        "uniform": rng.integers(0, 50, n).astype(float),
        "zipf": rng.zipf(1.6, n).clip(1, 500).astype(float),
        "steps": np.repeat(np.arange(8.0) * 100, n // 8)
        + rng.integers(0, 30, n),
    }[dist]
    crit = chi2lib.build_crit_table(0.001, 128)
    m_pts = 40
    init = np.array([x.min(), x.max()], float)
    e_seq, h, u, vmin, vmax = ref_sequential.build_1d_sequential(
        x, init, m_pts, crit)
    e_bfs = _bfs_edges(x, init, m_pts, crit)
    assert e_seq.size == e_bfs.size
    np.testing.assert_allclose(e_seq, e_bfs)


def test_refinement_invariants(synopsis):
    for hist in synopsis.hists:
        k = int(hist.k)
        edges = hist.edges[: k + 1]
        assert np.all(np.diff(edges) >= 0)
        assert np.all(hist.h >= 0)
        assert np.all(hist.u <= np.maximum(hist.h, 1))
        assert np.all(hist.vmin <= hist.vmax + 1e-12)
        assert np.all(hist.vmin >= edges[:-1] - 1e-9)
        assert np.all(hist.vmax <= edges[1:] + 1e-9)
        assert np.all(hist.cminus <= hist.cplus + 1e-12)
        assert np.all(hist.cminus >= hist.vmin - 1e-9)
        assert np.all(hist.cplus <= hist.vmax + 1e-9)


def test_pair_invariants(synopsis):
    for (i, j), pr in synopsis.pairs.items():
        np.testing.assert_allclose(pr.H.sum(1), pr.hx)
        np.testing.assert_allclose(pr.H.sum(0), pr.hy)
        # pair edges are a subset of the union-refined 1-D edges
        e1 = synopsis.hists[i].edges
        assert np.all(np.isin(np.round(pr.ex, 9), np.round(e1, 9)))
        e1j = synopsis.hists[j].edges
        assert np.all(np.isin(np.round(pr.ey, 9), np.round(e1j, 9)))
        # fold maps (1-D bin -> pair row) are monotone and in range
        assert np.all(np.diff(pr.fold_x) >= 0)
        assert np.all(np.diff(pr.fold_y) >= 0)
        assert pr.fold_x.shape[0] == int(synopsis.hists[i].k)
        assert pr.fold_y.shape[0] == int(synopsis.hists[j].k)
        assert pr.fold_x.max() < int(pr.kx)
        assert pr.fold_y.max() < int(pr.ky)


def test_uniform_data_is_not_split():
    rng = np.random.default_rng(5)
    x = rng.integers(0, 1000, 20000).astype(float)
    crit = chi2lib.build_crit_table(0.001, 128)
    e = _bfs_edges(x, np.array([x.min(), x.max()]), 200, crit)
    assert e.size - 1 <= 2  # uniform: essentially no refinement


def _bin_case(case):
    """(x, y, valid, ex, ey, kx, ky) over P pairs for one binning case;
    NaN marks a NULL, +inf pads every edge row to K+1."""
    rng = np.random.default_rng(3)
    n, K = 300, 16
    ks = {"multi_pair": [1, 4, 9]}.get(case, [5])
    p = len(ks)
    x = rng.integers(0, 21, (p, n)).astype(float)     # many ties
    y = rng.integers(0, 21, (p, n)).astype(float)
    valid = np.ones((p, n), bool)
    if case == "constant":
        x[:] = 7.0
    elif case == "null_suffix_x":
        x[rng.random((p, n)) < 0.2] = np.nan
    elif case == "null_suffix_y":
        y[rng.random((p, n)) < 0.2] = np.nan
    elif case == "inf_padded":
        x = rng.uniform(-5.0, 25.0, (p, n)).round(1)  # beyond both ends
    valid &= ~(np.isnan(x) | np.isnan(y))
    ex = np.full((p, K + 1), np.inf)
    ey = np.full((p, K + 1), np.inf)
    for i, k in enumerate(ks):
        if case == "constant":
            e = np.array([7.0, 7.0])
        elif case == "k1":
            e = np.array([0.0, 20.0])
        elif case == "first_last_edge":
            e = np.array([0.0, 3.5, 11.0, 20.0])      # 0 and 20 are data
        else:                                         # edges on tied values
            e = np.linspace(0.0, 20.0, k + 1).round()
        ex[i, : e.size] = e
        ey[i, : k + 1] = np.linspace(0.0, 20.0, k + 1)
    kx = (np.isfinite(ex).sum(1) - 1).astype(np.int32)
    ky = np.array(ks, np.int32)
    return x, y, valid, ex, ey, kx, ky


@pytest.mark.parametrize("case", [
    "ties_at_edges", "first_last_edge", "inf_padded", "k1", "constant",
    "null_suffix_x", "null_suffix_y", "multi_pair"])
def test_presort_bins_equal_searchsorted(case):
    """Binning from the presort (``_presort_bins`` on each order's sorted
    dimension, the permutations for the other) gives every valid row of
    both orders exactly ``searchsorted(e, v, "right") - 1``, clipped to
    ``[0, k-1]`` as the per-point search did."""
    from repro.core.build import _presort_pairs_host

    x, y, valid, ex, ey, kx, ky = _bin_case(case)
    xo1, vo1, _, yo2, vo2, _, perm12, perm21 = _presort_pairs_host(
        np.nan_to_num(x), np.nan_to_num(y), valid)
    bio1, _ = refine._presort_bins(jnp.asarray(xo1), jnp.asarray(vo1),
                                   jnp.asarray(ex), jnp.asarray(kx))
    bjo2, _ = refine._presort_bins(jnp.asarray(yo2), jnp.asarray(vo2),
                                   jnp.asarray(ey), jnp.asarray(ky))
    bio1, bjo2 = np.asarray(bio1), np.asarray(bjo2)
    bjo1 = np.take_along_axis(bjo2, perm12, axis=1)
    bio2 = np.take_along_axis(bio1, perm21, axis=1)

    def ref(v, e, k):
        return np.clip(np.searchsorted(e, v, side="right") - 1, 0,
                       max(int(k) - 1, 0))

    for p in range(x.shape[0]):
        kxk = np.where(valid[p], x[p], np.inf)
        kyk = np.where(valid[p], y[p], np.inf)
        for order, bi, bj in ((np.lexsort((kyk, kxk)), bio1, bjo1),
                              (np.lexsort((kxk, kyk)), bio2, bjo2)):
            ok = valid[p][order]
            np.testing.assert_array_equal(
                bi[p][ok], ref(x[p][order][ok], ex[p], kx[p]))
            np.testing.assert_array_equal(
                bj[p][ok], ref(y[p][order][ok], ey[p], ky[p]))
            assert bi[p].min() >= 0 and bi[p].max() <= max(kx[p] - 1, 0)
            assert bj[p].min() >= 0 and bj[p].max() <= max(ky[p] - 1, 0)
