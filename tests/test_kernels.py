"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.hist2d import batched_hist2d
from repro.kernels.hist2d.ref import batched_hist2d_ref
from repro.kernels.subbin import batched_subbin_hist
from repro.kernels.subbin.ref import batched_subbin_hist_ref
from repro.kernels.weightings import batched_weightings, q_bucket
from repro.kernels.weightings.ref import batched_weightings_ref
from repro.kernels.weightings.weightings import batched_weightings_pallas


def _np_hist2d(bi, bj, w, ki, kj):
    """One pair's weighted 2-D histogram by ``np.add.at``, in float64."""
    h = np.zeros((ki, kj))
    np.add.at(h, (bi, bj), np.asarray(w, np.float64))
    return h


@pytest.mark.parametrize("n,ki,kj", [
    (1000, 37, 53), (4096, 128, 256), (2048, 300, 17), (1024, 512, 512),
])
def test_hist2d_matches_ref(n, ki, kj):
    """One pair (P=1) through the Pallas kernel: the counts ``np.add.at``
    gives. (100, 8, 8) is ``test_batched_hist2d_matches_ref[1-100-8-8]``."""
    rng = np.random.default_rng(n + ki)
    bi = rng.integers(0, ki, n).astype(np.int32)
    bj = rng.integers(0, kj, n).astype(np.int32)
    w = rng.random(n).astype(np.float32)
    out = np.asarray(batched_hist2d(bi[None], bj[None], w[None], ki, kj,
                                    use_pallas=True))
    assert out.shape == (1, ki, kj)
    np.testing.assert_allclose(out[0], _np_hist2d(bi, bj, w, ki, kj),
                               rtol=1e-6)


@pytest.mark.parametrize("wdtype", [np.float32, np.float64, np.int32])
def test_hist2d_weight_dtypes(wdtype):
    """One pair (P=1) through the Pallas kernel, for each weight dtype:
    the counts ``np.add.at`` gives."""
    rng = np.random.default_rng(0)
    n, ki, kj = 500, 16, 16
    bi = rng.integers(0, ki, n).astype(np.int32)
    bj = rng.integers(0, kj, n).astype(np.int32)
    w = rng.integers(0, 3, n).astype(wdtype)
    out = np.asarray(batched_hist2d(bi[None], bj[None], w[None], ki, kj,
                                    use_pallas=True))
    assert out.shape == (1, ki, kj)
    np.testing.assert_allclose(out[0], _np_hist2d(bi, bj, w, ki, kj),
                               rtol=1e-6)
    assert float(out.sum()) == pytest.approx(float(w.sum()))


@pytest.mark.parametrize("p,n,ki,kj", [
    (1, 100, 8, 8), (3, 500, 37, 53), (2, 2048, 128, 256), (4, 1000, 300, 17),
])
def test_batched_hist2d_matches_ref(p, n, ki, kj):
    """Pair-batched Pallas kernel == jnp oracle == ``np.add.at`` per pair."""
    rng = np.random.default_rng(p * n + ki)
    bi = rng.integers(0, ki, (p, n)).astype(np.int32)
    bj = rng.integers(0, kj, (p, n)).astype(np.int32)
    w = rng.random((p, n)).astype(np.float32)
    out = batched_hist2d(bi, bj, w, ki, kj, use_pallas=True)
    ref = batched_hist2d_ref(jnp.asarray(bi), jnp.asarray(bj),
                             jnp.asarray(w), ki, kj)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    for pi in range(p):
        np.testing.assert_allclose(np.asarray(out)[pi],
                                   _np_hist2d(bi[pi], bj[pi], w[pi], ki, kj),
                                   rtol=1e-5, atol=1e-5)


def test_batched_hist2d_integer_counts_exact():
    """Construction feeds f64 ones/flags: counts must be exact integers and
    identical between the Pallas path (f32 accumulate) and the f64 oracle."""
    import repro.core  # noqa: F401  (enables jax x64 for the f64 oracle)
    rng = np.random.default_rng(1)
    p, n, k = 3, 4000, 24
    bi = rng.integers(0, k, (p, n)).astype(np.int32)
    bj = rng.integers(0, k, (p, n)).astype(np.int32)
    w = (rng.random((p, n)) < 0.9).astype(np.float64)  # 0/1 validity weights
    pal = np.asarray(batched_hist2d(bi, bj, w, k, k, use_pallas=True))
    ora = np.asarray(batched_hist2d(bi, bj, w, k, k, use_pallas=False))
    np.testing.assert_array_equal(pal, ora)
    assert ora.dtype == np.float64
    np.testing.assert_array_equal(ora, np.round(ora))
    assert float(ora.sum()) == float(w.sum())


@pytest.mark.parametrize("p,n,ncell,s_max", [
    (1, 100, 9, 8), (3, 500, 64, 16), (2, 2048, 256, 32), (4, 1000, 100, 5),
    (2, 3000, 3000, 32),        # KQ spans two KQ tiles, padded up to them
])
def test_batched_subbin_hist_matches_ref(p, n, ncell, s_max):
    """Sub-bin Pallas kernel (base-128 flat-id one-hot matmul) == oracle."""
    rng = np.random.default_rng(p * n + ncell)
    cell = rng.integers(0, ncell, (p, n)).astype(np.int32)
    sub = rng.integers(0, s_max, (p, n)).astype(np.int32)
    w = rng.random((p, n)).astype(np.float32)
    out = batched_subbin_hist(cell, sub, w, ncell, s_max, use_pallas=True)
    ref = batched_subbin_hist_ref(jnp.asarray(cell), jnp.asarray(sub),
                                  jnp.asarray(w), ncell, s_max)
    assert out.shape == (p, ncell, s_max)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_batched_subbin_hist_integer_counts_exact():
    """Refinement feeds f64 validity weights: counts must be exact integers
    and identical between the Pallas path (f32 accumulate) and the
    dtype-preserving segment-sum oracle; masked rows contribute nothing."""
    import repro.core  # noqa: F401  (enables jax x64 for the f64 oracle)
    rng = np.random.default_rng(1)
    p, n, ncell, s_max = 3, 4000, 64, 16
    cell = rng.integers(0, ncell, (p, n)).astype(np.int32)
    sub = rng.integers(0, s_max, (p, n)).astype(np.int32)
    w = (rng.random((p, n)) < 0.9).astype(np.float64)  # 0/1 validity weights
    pal = np.asarray(batched_subbin_hist(cell, sub, w, ncell, s_max,
                                         use_pallas=True))
    ora = np.asarray(batched_subbin_hist(cell, sub, w, ncell, s_max,
                                         use_pallas=False))
    np.testing.assert_array_equal(pal, ora)
    assert ora.dtype == np.float64
    np.testing.assert_array_equal(ora, np.round(ora))
    assert float(ora.sum()) == float(w.sum())
    # last-axis sum reproduces per-cell totals (the h_cell contract the
    # refinement loop relies on)
    totals = np.zeros((p, ncell))
    for pi in range(p):
        np.add.at(totals[pi], cell[pi], w[pi])
    np.testing.assert_array_equal(ora.sum(axis=2), totals)


def test_subbin_counts_matches_inline_scatter():
    """chi2.subbin_counts (kernel-backed) == the legacy in-loop masked
    segment_sum formulation, bit for bit, including null rows and
    zero-width (constant) cells."""
    import repro.core  # noqa: F401
    from repro.core import chi2 as chi2lib
    import jax
    rng = np.random.default_rng(4)
    p, n, k2, s_max = 2, 3000, 8, 16
    ncell = k2 * k2
    vals = jnp.asarray(rng.uniform(0, 100, (p, n)))
    lo = jnp.asarray(np.floor(rng.uniform(0, 50, (p, n))))
    width = jnp.asarray(rng.choice([0.0, 25.0, 50.0], (p, n)))
    cell = jnp.asarray(rng.integers(0, ncell, (p, n)), jnp.int32)
    u = jnp.asarray(rng.integers(0, 40, (p, ncell)).astype(np.float64))
    s = chi2lib.num_subbins(u, s_max)
    valid = jnp.asarray(rng.random((p, n)) < 0.9)

    got = chi2lib.subbin_counts(vals, lo, width, cell, s, valid,
                                ncell=ncell, s_max=s_max, use_pallas=False)

    s_pt = jnp.take_along_axis(s, cell, axis=1)
    frac = jnp.where(width > 0, (vals - lo) / width, 0.0)
    r = jnp.clip((frac * s_pt).astype(jnp.int32), 0, s_pt - 1)
    flat = jnp.where(valid, cell * s_max + r, ncell * s_max)
    ones = jnp.ones_like(vals)
    hbar = jax.vmap(lambda f, o: jax.ops.segment_sum(
        o, f, num_segments=ncell * s_max + 1))(flat, ones)
    want = hbar[:, :-1].reshape(p, ncell, s_max)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _per_query_oracle(h_stack, beta, fold, hx):
    """One query's weightings, prod_l fold_l(clip(H_l beta_l / hx_l, 0, 1))
    (Eq. 25/27/28), as a float32 einsum: beta (L, K2) -> (K1,)."""
    v = jnp.einsum("lab,lb->la", h_stack, beta)          # (L, K2)
    p_row = jnp.clip(v / jnp.maximum(hx, 1e-30), 0.0, 1.0)
    p1 = jnp.einsum("lka,la->lk", fold, p_row)           # (L, K1)
    return np.asarray(jnp.prod(p1, axis=0))


def _padded_device(x, shape):
    """``x`` zero-padded to ``shape`` and put on the device, as
    ``FastPath._get_stack`` keeps a synopsis's stacks."""
    out = np.zeros(shape, np.float32)
    out[tuple(slice(0, n) for n in x.shape)] = x
    return jnp.asarray(out)


def _parent_launch(H, beta, fold, hx):
    """The launch as it was before it became one round trip: every input
    staged and padded on the device, the result sliced there and copied."""
    q, el, k2 = beta.shape
    k1 = fold.shape[1]
    k2p, k1p = -(-k2 // 128) * 128, -(-k1 // 128) * 128
    bpad = np.zeros((el, q_bucket(q), k2p), np.float32)
    bpad[:, :q, :k2] = np.swapaxes(beta, 0, 1)
    out = batched_weightings_pallas(
        _padded_device(np.asarray(H), (el, k2p, k2p)), jnp.asarray(bpad),
        _padded_device(np.asarray(fold), (el, k1p, k2p)),
        _padded_device(np.asarray(hx), (el, k2p)), interpret=True)
    return np.asarray(out[:q, :k1])


# (q, el, k2, k1, stacks): "host" stacks are unpadded NumPy, "device" ones
# are 128-padded on the device, with beta padded to match, as FastPath
# hands them over.
@pytest.mark.parametrize("q,el,k2,k1,stacks", [
    pytest.param(1, 1, 16, 16, "host", id="1-1-16-16"),
    pytest.param(5, 3, 70, 90, "host", id="5-3-70-90"),
    pytest.param(17, 2, 200, 260, "host", id="17-2-200-260"),
    pytest.param(64, 4, 128, 128, "host", id="64-4-128-128"),
    (1, 3, 64, 80, "host"),
    (1, 5, 200, 260, "host"),
    (1, 2, 128, 128, "host"),
    (1, 4, 384, 400, "host"),
    (1, 1, 16, 16, "device"),
    (3, 2, 100, 130, "device"),
    (9, 3, 70, 90, "host"),
    (33, 1, 130, 200, "device"),
    (129, 2, 200, 260, "host"),
    (300, 3, 150, 300, "device"),
    (300, 1, 250, 70, "host"),
])
def test_batched_weightings_matches_per_query(q, el, k2, k1, stacks):
    """Query-batched kernel == per-query oracle, row by row, for both the
    Pallas path and the jitted-jnp path, a single query (Q=1) included;
    the Pallas launch returns a host array bit-identical to the kernel's
    device-sliced result."""
    rng = np.random.default_rng(q * k2 + el)
    H = (rng.random((el, k2, k2)) * 10).astype(np.float32)
    hx = H.sum(2) + 1.0
    fold = np.zeros((el, k1, k2), np.float32)
    idx = np.sort(rng.integers(0, k2, k1))
    for li in range(el):
        fold[li, np.arange(k1), idx] = 1
    beta = rng.random((q, el, k2)).astype(np.float32)
    seq = np.stack([_per_query_oracle(
        jnp.asarray(H), jnp.asarray(beta[qi]), jnp.asarray(fold),
        jnp.asarray(hx)) for qi in range(q)])
    args = (H, beta, fold, hx)
    if stacks == "device":
        k2p, k1p = -(-k2 // 128) * 128, -(-k1 // 128) * 128
        args = (_padded_device(H, (el, k2p, k2p)),
                np.pad(beta, ((0, 0), (0, 0), (0, k2p - k2))),
                _padded_device(fold, (el, k1p, k2p)),
                _padded_device(hx, (el, k2p)))
    for use_pallas in (True, False):
        out = batched_weightings(*args, use_pallas=use_pallas)
        assert isinstance(out, np.ndarray)
        assert out.shape == (q, args[2].shape[1])
        np.testing.assert_allclose(out[:, :k1], seq, rtol=1e-5, atol=1e-6)
        if use_pallas:
            np.testing.assert_array_equal(out, _parent_launch(*args))


class _CopyOnly:
    """Stands for the kernel's device result: the launch may copy it to the
    host once (``__array__``) and do nothing else with it."""

    def __init__(self, out):
        self._out = out
        self.copies = 0

    def __array__(self, dtype=None, copy=None):
        self.copies += 1
        return np.asarray(self._out)

    def __getattr__(self, name):
        if name.startswith("__array"):     # NumPy probing its protocols
            raise AttributeError(name)
        raise AssertionError(f"launch touched its device result: .{name}")

    def __getitem__(self, key):
        raise AssertionError("launch sliced its device result on the device")


def test_batched_launch_is_one_round_trip(monkeypatch):
    """A launch over FastPath's device-resident padded stacks dispatches
    the jitted kernel once and nothing else: the stacks pass through
    untouched, the betas reach it as host data (staged by the dispatch
    alone), and the result is copied back once and sliced on the host."""
    from repro.kernels.weightings import ops

    rng = np.random.default_rng(3)
    el, k2p, k1p, k1, q = 2, 256, 512, 500, 6
    H = _padded_device(rng.random((el, 200, 200)) * 10, (el, k2p, k2p))
    fold = _padded_device(np.eye(k1, 200)[None].repeat(el, 0),
                          (el, k1p, k2p))
    hx = _padded_device(rng.random((el, 200)) + 1, (el, k2p))
    beta = np.zeros((q, el, k2p), np.float32)
    beta[..., :200] = rng.random((q, el, 200))
    calls = []

    def spy(h_stack, bpad, fold_, hx_, interpret):
        calls.append((h_stack, bpad, fold_, hx_))
        res = _CopyOnly(batched_weightings_pallas(h_stack, bpad, fold_, hx_,
                                                  interpret=interpret))
        calls.append(res)
        return res

    monkeypatch.setattr(ops, "batched_weightings_pallas", spy)
    out = ops.batched_weightings(H, beta, fold, hx, interpret=True)
    (h_in, b_in, f_in, x_in), res = calls
    assert h_in is H and f_in is fold and x_in is hx
    assert type(b_in) is np.ndarray
    assert b_in.shape == (el, q_bucket(q), k2p) and b_in.dtype == np.float32
    assert res.copies == 1
    assert type(out) is np.ndarray and out.shape == (q, k1p)
    np.testing.assert_array_equal(out, _parent_launch(H, beta, fold, hx))


def test_fastpath_batch_launches_once(synopsis, monkeypatch):
    """FastPath.batch makes one kernel launch per plan-shape group, with
    its cached device stacks as they are, and decides interpret mode when
    it is built, not per launch."""
    import jax

    from repro.core.fastpath import FastPath
    from repro.core.query import QueryEngine
    from repro.kernels.weightings import ops

    fp = FastPath(use_pallas=True)
    eng = QueryEngine(synopsis)
    trees = [eng.plan_sql(f"SELECT SUM(c0) FROM t WHERE c1 > {250 + 9 * i}"
                          f" AND c2 < {950 - 11 * i}").tree
             for i in range(5)]
    want = FastPath(use_pallas=False).batch(synopsis, 0, trees, False)
    calls = []

    def spy(*args, interpret):
        calls.append((args, interpret))
        return batched_weightings_pallas(*args, interpret=interpret)

    def no_backend_query():
        raise AssertionError("interpret mode asked per launch")

    monkeypatch.setattr(ops, "batched_weightings_pallas", spy)
    monkeypatch.setattr(jax, "default_backend", no_backend_query)
    got = fp.batch(synopsis, 0, trees, corrected=False)
    (args, interpret), = calls
    stack = synopsis._fastpath_stacks[(0, (1, 2))]
    assert args[0] is stack[0] and args[2] is stack[1] and args[3] is stack[2]
    assert interpret is True
    for g, w in zip(got, want):
        for a, b in zip(g, w):
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)


def test_batched_weightings_ref_reduces_to_single():
    """Q=1 batched ref == the per-query oracle (the same einsum graph)."""
    rng = np.random.default_rng(11)
    el, k2, k1 = 2, 32, 40
    H = rng.random((el, k2, k2)).astype(np.float32)
    hx = H.sum(2) + 1.0
    fold = np.zeros((el, k1, k2), np.float32)
    fold[:, np.arange(k1), np.sort(rng.integers(0, k2, k1))] = 1
    beta = rng.random((1, el, k2)).astype(np.float32)
    one = batched_weightings_ref(jnp.asarray(H), jnp.asarray(beta),
                                 jnp.asarray(fold), jnp.asarray(hx))
    single = _per_query_oracle(jnp.asarray(H), jnp.asarray(beta[0]),
                               jnp.asarray(fold), jnp.asarray(hx))
    np.testing.assert_allclose(np.asarray(one[0]), single,
                               rtol=1e-6, atol=1e-7)


def test_batched_weightings_identity_predicate():
    """A beta of all-ones gives probability 1 in every bin (one query on
    the Pallas path)."""
    rng = np.random.default_rng(7)
    k2, k1 = 32, 32
    H = rng.integers(0, 5, (1, k2, k2)).astype(np.float32)
    hx = H.sum(2)
    fold = np.zeros((1, k1, k2), np.float32)
    fold[0, np.arange(k1), np.arange(k2)] = 1
    beta = np.ones((1, 1, k2), np.float32)
    out = batched_weightings(H, beta, fold, hx, use_pallas=True)[0]
    mask = hx[0] > 0
    np.testing.assert_allclose(out[mask], 1.0, rtol=1e-6)


def _pair_betas(ph, agg_col, pair_leaves, k2max):
    """(3, L, K2max) coverage matrix for one query's pair leaves, one leaf
    at a time: the oracle of ``FastPath._pair_betas_batch``."""
    from repro.core.fastpath import _slice_beta
    betas = np.zeros((3, len(pair_leaves), k2max), np.float32)
    for li, leaf in enumerate(pair_leaves):
        pr = ph.pair(agg_col, leaf.col)
        triple = _slice_beta(ph, leaf, pr.hy, pr.uy, pr.vminy, pr.vmaxy,
                             ph.columns[leaf.col].mu)
        for idx in range(3):
            betas[idx, li, :len(triple[idx])] = triple[idx]
    return betas


def test_pair_betas_batch_bit_for_bit(synopsis):
    """Vectorized per-leaf beta assembly (_pair_betas_batch) is bit-for-bit
    equal to stacking the per-query ``_pair_betas`` oracle, across
    operators, out-of-range literals and consolidated interval leaves."""
    from repro.core import weightings as wlib
    from repro.core.fastpath import FastPath
    fp = FastPath(use_pallas=False)
    rng = np.random.default_rng(5)
    agg = 0
    leaf_lists = []
    for qi in range(9):
        lo = float(rng.uniform(100, 500))
        leaves = [
            wlib.Leaf(1, rng.choice(["<", "<=", ">", ">=", "=", "!="]),
                      float(rng.uniform(-50, 700))),
            (wlib.Consolidated(2, [(lo, lo + 200.0)]) if qi % 3 == 0
             else wlib.Leaf(2, str(rng.choice(["<", ">"])),
                            float(rng.uniform(0, 1200)))),
        ]
        leaf_lists.append(leaves)
    k2max = 512
    batched = fp._pair_betas_batch(synopsis, agg, leaf_lists, k2max)
    seq = np.stack([_pair_betas(synopsis, agg, pls, k2max)
                    for pls in leaf_lists])
    np.testing.assert_array_equal(batched, seq)


def _brushes(qi, shape):
    """Pair leaves of GROUP BY leaf ``qi`` (agg column 0): the statement's
    brush, shared by its leaves, and the leaf's own ``c3 = value``."""
    from repro.core import weightings as wlib
    eq = wlib.Leaf(3, "=", float(qi + 1))
    if shape == "one_brush":
        return [wlib.Consolidated(1, [(200.0, 350.0)]), eq]
    if shape == "two_brushes":
        return [wlib.Consolidated(1, [(200.0, 350.0)]),
                wlib.Consolidated(2, [(500.0, 1100.0)]), eq]
    # Repeated and distinct interval sets across the plans.
    return [wlib.Consolidated(1, [(200.0, 350.0)] if qi % 3
                              else [(150.0, 260.0), (300.0, 420.0)]),
            wlib.Consolidated(2, [(100.0 * (qi % 4), 900.0)]), eq]


@pytest.mark.parametrize("shape, computed", [
    ("one_brush", 1 + 14), ("two_brushes", 2 + 14), ("mixed", 2 + 4 + 14)])
def test_pair_betas_batch_group_by_bit_for_bit(synopsis, shape, computed):
    """A GROUP BY's 14 leaf plans share their brush: each distinct interval
    set is evaluated once and copied to the other plans, bit for bit what
    stacking the per-plan ``_pair_betas`` gives."""
    from repro.core.fastpath import FastPath, _SliceMemo
    fp = FastPath(use_pallas=False)
    leaf_lists = [_brushes(qi, shape) for qi in range(14)]
    memo = _SliceMemo()
    batched = fp._pair_betas_batch(synopsis, 0, leaf_lists, 512, memo)
    seq = np.stack([_pair_betas(synopsis, 0, pls, 512)
                    for pls in leaf_lists])
    np.testing.assert_array_equal(batched, seq)
    assert memo.computed == computed


def _group_trees(same_col: bool, vary: bool = False):
    """14 GROUP BY leaf trees on agg column 0: a brush on c0 itself (a
    same-column leaf) or on c2, one on c1, and each leaf's ``c3 = v``.
    ``vary`` gives every third tree another c0 brush."""
    from repro.core import weightings as wlib

    def first(v):
        if not same_col:
            return wlib.Consolidated(2, [(500.0, 1100.0)])
        return wlib.Consolidated(0, [(50.0, 400.0)] if vary and v % 3 == 0
                                 else [(100.0, 600.0)])
    return [wlib.Node("and", [first(v),
                              wlib.Consolidated(1, [(200.0, 350.0)]),
                              wlib.Leaf(3, "=", float(v))])
            for v in range(1, 15)]


def test_fastpath_batch_memo_is_bit_for_bit(synopsis):
    """``FastPath.batch`` with same-column brushes repeated across the
    plans (two distinct ones) gives the triples of the path without the
    memo: per-plan ``_split_leaves`` and the stacked per-plan
    ``_pair_betas`` oracle."""
    from repro.core.fastpath import FastPath
    trees = _group_trees(same_col=True, vary=True)
    fp = FastPath(use_pallas=False)
    got = fp.batch(synopsis, 0, trees, corrected=False)
    assert fp.n_computed < fp.n_slices

    plain = FastPath(use_pallas=False)
    plain._split_leaves = (lambda ph, agg, tree, memo=None:
                           FastPath._split_leaves(plain, ph, agg, tree))
    plain._pair_betas_batch = (lambda ph, agg, lls, k2max, memo=None:
                               np.stack([_pair_betas(ph, agg, pls, k2max)
                                         for pls in lls]))
    want = plain.batch(synopsis, 0, trees, corrected=False)
    assert len(got) == len(want) == 14
    for g, w in zip(got, want):
        for a, b in zip(g, w):
            np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("same_col", [False, True])
def test_fastpath_batch_counts_slices(synopsis, same_col):
    """14 plans x (2 brushes + 1 ``=``) need 42 slices and evaluate 16:
    each brush once and each distinct literal once, whether a brush is on
    the agg column or a pair column; the traced ``betas`` span carries the
    launch's tally, and the counters add up over launches."""
    from repro.core.fastpath import FastPath
    from repro.obs.trace import Tracer
    tracer = Tracer(enabled=True)
    fp = FastPath(use_pallas=False, tracer=tracer)
    trees = _group_trees(same_col)
    assert fp.batch(synopsis, 0, trees, corrected=False) is not None
    assert (fp.n_slices, fp.n_computed) == (42, 16)
    (betas,) = [sp for sp in tracer.spans() if sp.name == "betas"]
    assert betas.attrs == {"slices": 42, "computed": 16}
    fp.batch(synopsis, 0, trees[:3], corrected=False)
    assert (fp.n_slices, fp.n_computed) == (42 + 9, 16 + 5)


def test_fastpath_batch_equals_single(synopsis):
    """FastPath.batch (one fused launch + vectorized betas) matches the
    NumPy reference weightings (``repro.core.weightings``) of each query:
    the launch contracts in float32 against the reference's float64."""
    from repro.core import weightings as wlib
    from repro.core.fastpath import FastPath
    from repro.core.query import QueryEngine
    fp = FastPath(use_pallas=False)
    eng = QueryEngine(synopsis)
    trees = [eng.plan_sql(f"SELECT COUNT(c0) FROM t WHERE c1 > {200 + 10 * i}"
                          f" AND c2 < {900 - 15 * i}").tree
             for i in range(6)]
    batch = fp.batch(synopsis, 0, trees, corrected=False)
    assert batch is not None
    for tree, triple in zip(trees, batch):
        single = wlib.weightings(synopsis, 0, tree)
        for got, want in zip(triple, single):
            np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-7)


@pytest.mark.parametrize("sql", [
    "SELECT COUNT(c0) FROM t WHERE c1 > 300 AND c2 < 900",
    "SELECT AVG(c2) FROM t WHERE c1 >= 250 AND c1 < 350",
    "SELECT SUM(c1) FROM t WHERE c2 <= 900 AND c0 < 500",
    "SELECT MIN(c1) FROM t WHERE c1 > 100",
    # OR: no fused launch; the engine answers on the reference path
    "SELECT AVG(c1) FROM t WHERE c0 < 100 OR c3 = 2",
])
def test_fastpath_equals_reference_engine(synopsis, sql):
    """An answer executed with ``FastPath.batch``'s weightings (the Pallas
    path) equals the engine's own NumPy reference answer."""
    from repro.core.fastpath import FastPath
    from repro.core.query import QueryEngine
    eng = QueryEngine(synopsis)
    plan = eng.plan_sql(sql)
    triples = FastPath(use_pallas=True).batch(synopsis, plan.exec_col,
                                              [plan.tree], eng.corrected)
    if " OR " in sql:
        assert triples is None
        return
    r1 = eng.execute_plan(plan)
    r2 = eng.execute_plan(plan, weightings=triples[0])
    np.testing.assert_allclose(r1.as_tuple(), r2.as_tuple(),
                               rtol=1e-5, atol=1e-6)
