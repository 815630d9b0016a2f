"""Convergence-compacting 2-D construction vs the sequential oracle.

The served pair phase (refine.refine_2d_compact driven by
build.build_pairs_compact — drain/backfill active set, shared per-column
presorts, per-pair capacity rungs) must be *bit-for-bit* equal to the
per-pair host loop (build.build_pairs_sequential, built by
conftest._sequential through the module-private build._build_pairwise_hist)
on every workload mix: each pair's refinement is the same deterministic
fixed-point iteration whatever the slot count, queue order, drain timing or
occupancy_min re-bucketing.
Covers correlated, independent, constant, NaN-heavy and K2-capped mixes
plus drain/backfill schedule invariants (every pair refined exactly once,
deterministic outputs, exact occupancy ledger).
"""
import dataclasses

import numpy as np
import pytest

from conftest import _assert_same_synopsis, _cols, _sequential
from repro.core.build import (_column_ranks, _pad_edges, _presort_pairs_host,
                              build_pairwise_hist)
from repro.core.types import BuildParams


def _mixed_table(n=5000, seed=7):
    """Deep (correlated) + shallow (independent) + constant + NaN-heavy."""
    rng = np.random.default_rng(seed)
    base = np.abs(rng.normal(300, 80, n))
    c0 = rng.integers(0, 500, n).astype(float)       # independent
    c1 = np.round(base)                              # correlated cluster
    c2 = np.round(base * 2 + rng.normal(0, 25, n))
    c3 = rng.zipf(1.7, n).clip(1, 40).astype(float)  # heavy tail + NULLs
    c3[rng.random(n) < 0.05] = np.nan
    c4 = np.full(n, 7.0)                             # constant
    return np.stack([c0, c1, c2, c3, c4], 1)


def _independent_table(n=4000, seed=11, d=4):
    rng = np.random.default_rng(seed)
    return np.stack([np.round(np.abs(rng.normal(100 * (i + 1), 20 + 10 * i,
                                                n))) for i in range(d)], 1)


@pytest.fixture(scope="module")
def mixed():
    return _mixed_table()


@pytest.fixture(scope="module")
def seq_mixed(mixed):
    params = BuildParams(n_samples=mixed.shape[0], k2_cap=64, s2_max=16)
    return _sequential(mixed, _cols(mixed.shape[1]), params)


def test_compact_equals_sequential_bitforbit(mixed, seq_mixed):
    params = BuildParams(n_samples=mixed.shape[0], k2_cap=64, s2_max=16,
                         pair_chunk=4)
    compact = build_pairwise_hist(mixed, _cols(mixed.shape[1]), params)
    assert compact.build_stats["mode"] == "compact"
    _assert_same_synopsis(seq_mixed, compact)


def test_slot_count_invariance(mixed, seq_mixed):
    """Slot count (and with it queue order / drain timing) never changes
    bits — the schedule-independence core of the compaction claim."""
    for chunk in (1, 2, 8):
        params = BuildParams(n_samples=mixed.shape[0], k2_cap=64, s2_max=16,
                             pair_chunk=chunk)
        compact = build_pairwise_hist(mixed, _cols(mixed.shape[1]), params)
        _assert_same_synopsis(seq_mixed, compact)


def test_occupancy_rebucket_invariance(mixed, seq_mixed):
    """occupancy_min early-exit + smaller relaunches resume mid-refinement
    pairs exactly; occupancy_min=1.0 re-buckets after every drain."""
    for occ in (0.5, 1.0):
        params = BuildParams(n_samples=mixed.shape[0], k2_cap=64, s2_max=16,
                             pair_chunk=4,
                             occupancy_min=occ)
        compact = build_pairwise_hist(mixed, _cols(mixed.shape[1]), params)
        _assert_same_synopsis(seq_mixed, compact)
        if occ == 1.0:
            assert compact.build_stats["compaction"]["relaunches"] > 0


def test_independent_columns(seq_mixed):
    data = _independent_table()
    p_seq = BuildParams(n_samples=data.shape[0], k2_cap=64, s2_max=16)
    p_cmp = dataclasses.replace(p_seq, pair_chunk=4)
    _assert_same_synopsis(_sequential(data, _cols(4), p_seq),
                          build_pairwise_hist(data, _cols(4), p_cmp))


def test_k2_capacity_guard(mixed):
    """At a tiny k2_cap the guard binds; the final rung must NOT early-drain
    capped pairs (their capped result is the real one) and must reproduce
    the sequential capped bins."""
    p_seq = BuildParams(n_samples=mixed.shape[0], k2_cap=8, s2_max=16)
    p_cmp = dataclasses.replace(p_seq, pair_chunk=4)
    seq = _sequential(mixed, _cols(mixed.shape[1]), p_seq)
    cmp_ = build_pairwise_hist(mixed, _cols(mixed.shape[1]), p_cmp)
    _assert_same_synopsis(seq, cmp_)
    for pr in cmp_.pairs.values():
        assert int(pr.kx) <= 8 and int(pr.ky) <= 8


def test_capacity_ladder_escalation_per_pair(mixed):
    """A tiny first rung forces guards to bind; only the capped pairs
    re-queue one rung up (per-pair escalation) and the result still matches
    the sequential loop at full capacity."""
    p_seq = BuildParams(n_samples=mixed.shape[0], k2_cap=128, s2_max=16)
    p_esc = dataclasses.replace(p_seq, pair_chunk=4, k2_start=4)
    seq = _sequential(mixed, _cols(mixed.shape[1]), p_seq)
    esc = build_pairwise_hist(mixed, _cols(mixed.shape[1]), p_esc)
    _assert_same_synopsis(seq, esc)
    comp = esc.build_stats["compaction"]
    assert comp["escalated_pairs"] > 0
    # escalation is per pair: strictly fewer pair-slots re-ran than a
    # whole-chunk re-run would have paid
    assert comp["escalated_pairs"] < len(esc.pairs)


def test_schedule_ledger_and_determinism(mixed):
    """Every pair drains exactly once (n_pairs results, occupancy ledger
    exact: pair_rounds <= slot_rounds, both positive) and repeated builds
    are identical."""
    params = BuildParams(n_samples=mixed.shape[0], k2_cap=64, s2_max=16,
                         pair_chunk=4)
    a = build_pairwise_hist(mixed, _cols(mixed.shape[1]), params)
    b = build_pairwise_hist(mixed, _cols(mixed.shape[1]), params)
    _assert_same_synopsis(a, b)
    d = mixed.shape[1]
    assert len(a.pairs) == d * (d - 1) // 2
    comp = a.build_stats["compaction"]
    assert 0 < comp["pair_rounds"] <= comp["slot_rounds"]
    assert comp["loop_rounds"] > 0
    assert a.build_stats["pair_launches"]


def test_rank_presort_matches_lexsort_presort():
    """The shared-rank composite-key presort is permutation-identical to
    the two-key float lexsort (stable sorts, order-isomorphic keys)."""
    rng = np.random.default_rng(2)
    p, n = 4, 500
    x = rng.integers(0, 25, (p, n)).astype(float)    # many ties
    y = rng.integers(0, 25, (p, n)).astype(float)
    valid = rng.random((p, n)) < 0.85
    sample = np.stack([x[0], y[0], x[1], y[1]], 1)   # rank source columns
    ranks = _column_ranks(sample)
    lex = _presort_pairs_host(x[:2], y[:2], valid[:2])
    rk = _presort_pairs_host(x[:2], y[:2], valid[:2],
                             np.stack([ranks[0], ranks[2]]),
                             np.stack([ranks[1], ranks[3]]))
    for name, h, r in zip("xo1 vo1 new1 yo2 vo2 new2 perm12 perm21".split(),
                          lex, rk):
        np.testing.assert_array_equal(h, r, err_msg=name)


@pytest.mark.parametrize("branch", ["lexsort", "rank"])
def test_presort_permutations_map_the_two_orders(branch):
    """``perm12``/``perm21`` carry each row between the two sort orders:
    y in (x, y) order is ``yo2[perm12]`` and x in (y, x) order is
    ``xo1[perm21]``, validity likewise, on both presort branches."""
    rng = np.random.default_rng(4)
    p, n = 3, 400
    x = rng.integers(0, 25, (p, n)).astype(float)    # many ties
    y = rng.integers(0, 25, (p, n)).astype(float)
    valid = rng.random((p, n)) < 0.85
    ranks = None
    if branch == "rank":
        r = _column_ranks(np.concatenate([x, y]).T)
        ranks = (r[:p], r[p:])
    xo1, vo1, _, yo2, vo2, _, perm12, perm21 = _presort_pairs_host(
        x, y, valid, *(ranks or ()))
    assert perm12.dtype == np.int32 and perm21.dtype == np.int32
    for q in range(p):
        kx = np.where(valid[q], x[q], np.inf)
        ky = np.where(valid[q], y[q], np.inf)
        o1, o2 = np.lexsort((ky, kx)), np.lexsort((kx, ky))
        np.testing.assert_array_equal(yo2[q][perm12[q]], y[q][o1])
        np.testing.assert_array_equal(vo2[q][perm12[q]], valid[q][o1])
        np.testing.assert_array_equal(xo1[q][perm21[q]], x[q][o2])
        np.testing.assert_array_equal(vo1[q][perm21[q]], valid[q][o2])
        np.testing.assert_array_equal(perm21[q][perm12[q]], np.arange(n))


def _nested_while_shapes(hlo: str) -> tuple[list, list]:
    """Result shapes of the ``while`` ops in optimised HLO text: those of
    the entry computation and those nested in any other computation."""
    entry, nested, in_entry = [], [], False
    for line in hlo.splitlines():
        if line and not line[0].isspace():
            in_entry = line.startswith("ENTRY")
        elif " while(" in line:
            shape = line.split(" = ", 1)[1].split(" while(", 1)[0]
            (entry if in_entry else nested).append(shape)
    return entry, nested


def test_compact_round_has_no_per_point_search():
    """No loop nested in ``refine_2d_compact``'s optimised HLO carries an
    integer (S, N) operand: the bounds of a per-point binary search. The
    searches of the K+1 edges into each sorted row may stay loops; their
    bounds are (S, K+1)."""
    import re

    import jax
    import jax.numpy as jnp

    from repro.core import refine

    P, S, N, K = 8, 4, 200, 16       # N equals no other dimension
    sd = jax.ShapeDtypeStruct
    f64, i32, b = jnp.float64, jnp.int32, jnp.bool_
    rows = [sd((P, N), t) for t in (f64, b, b, f64, b, b, i32, i32)]
    args = rows + [sd((P, K + 1), f64), sd((P, K + 1), f64),
                   sd((P,), i32), sd((P,), i32), sd((P,), i32),
                   sd((P,), b), sd((), i32), sd((), f64), sd((17,), f64),
                   sd((), f64)]
    hlo = refine.refine_2d_compact.lower(
        *args, n_slots=S, k2=K, s_max=16).compile().as_text()
    entry, nested = _nested_while_shapes(hlo)
    assert len(entry) == 1               # the drain/backfill loop
    per_point = re.compile(rf"\b[su]\d+\[\d+,{N}\]")
    assert not [s for s in nested if per_point.search(s)], nested


def test_refine_2d_compact_direct_invariants():
    """Drive refine_2d_compact directly: every pair drains exactly once
    with the same (ex, ey, kx, ky) as the single-pair refine_2d oracle,
    and the occupancy ledger is exact (sum of per-pair rounds ==
    active_rounds <= loop_rounds * slots)."""
    import jax.numpy as jnp

    from repro.core import chi2 as chi2lib
    from repro.core import refine

    rng = np.random.default_rng(5)
    n, n_pairs, k2 = 1500, 4, 32
    crit = jnp.asarray(chi2lib.build_crit_table(0.001, 16))
    base = np.abs(rng.normal(100, 30, n))
    xs = np.stack([np.round(base), np.round(base),
                   np.round(rng.uniform(0, 50, n)),
                   np.round(rng.uniform(0, 9, n))])
    ys = np.stack([np.round(base * 2 + rng.normal(0, 5, n)),
                   np.round(rng.uniform(0, 200, n)),
                   np.round(rng.uniform(0, 50, n) * 3 + base),
                   np.round(rng.uniform(0, 9, n))])
    valid = np.ones((n_pairs, n), bool)
    valid[1, rng.random(n) < 0.1] = False
    pres = _presort_pairs_host(xs, ys, valid)
    ex0 = np.stack([_pad_edges(np.array([x.min(), x.max()]), k2)
                    for x in xs])
    ey0 = np.stack([_pad_edges(np.array([y.min(), y.max()]), k2)
                    for y in ys])
    ones = np.ones(n_pairs, np.int32)
    m_pts = 25.0

    out = refine.refine_2d_compact(
        *(jnp.asarray(a) for a in pres), jnp.asarray(ex0), jnp.asarray(ey0),
        jnp.asarray(ones), jnp.asarray(ones),
        jnp.zeros(n_pairs, jnp.int32), jnp.zeros(n_pairs, bool),
        jnp.int32(n_pairs), jnp.float64(m_pts), crit, jnp.float64(0.0),
        n_slots=2, k2=k2, s_max=16, max_rounds=16)
    (oex, oey, okx, oky, _ocap, ornd, odone, _sp, sact,
     *_rest, loop_rounds, active_rounds) = [np.asarray(v) for v in out]
    assert odone.all() and not sact.any()
    assert int(active_rounds) == int(ornd.sum())
    assert int(active_rounds) <= int(loop_rounds) * 2

    for p in range(n_pairs):
        ex, ey, kx, ky = refine.refine_2d(
            jnp.asarray(xs[p]), jnp.asarray(ys[p]), jnp.asarray(valid[p]),
            jnp.asarray(ex0[p]), jnp.asarray(ey0[p]),
            jnp.int32(1), jnp.int32(1), jnp.float64(m_pts), crit,
            k2=k2, s_max=16, max_rounds=16)
        np.testing.assert_array_equal(oex[p], np.asarray(ex))
        np.testing.assert_array_equal(oey[p], np.asarray(ey))
        assert okx[p] == int(kx) and oky[p] == int(ky)


def test_all_nan_pair_column():
    """A column that is NULL on every row yields empty pair histograms
    through the compacted path too."""
    rng = np.random.default_rng(0)
    n = 2000
    data = np.stack([rng.integers(0, 100, n).astype(float),
                     np.full(n, np.nan),
                     np.abs(rng.normal(50, 10, n)).round()], 1)
    params = BuildParams(n_samples=n, k2_cap=32, s2_max=16)
    seq = _sequential(data, _cols(3), params)
    cmp_ = build_pairwise_hist(data, _cols(3), params)
    _assert_same_synopsis(seq, cmp_)
    assert float(cmp_.pairs[(0, 1)].H.sum()) == 0.0
