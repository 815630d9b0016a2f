"""Level-synchronous histogram refinement (TPU adaptation of Alg. 1 + 2).

The paper's ``RefineBin1D``/``RefineBin2D`` are data-dependent recursions. On
TPU we refine *every* bin of a histogram simultaneously per round inside a
``lax.while_loop`` over fixed-capacity, +inf-padded edge buffers:

  round:  (1) vectorized per-bin statistics (count, unique count, chi-squared
              over Terrell–Scott sub-bins) via a single ``searchsorted`` batch;
          (2) every bin failing the uniformity test inserts its midpoint;
          (3) edges <- sort(concat(edges, midpoints))[:capacity].

Because the paper splits at the *bin midpoint* (equal-width, §4.1), split
decisions in 1-D are independent across bins, so this BFS produces **exactly**
the same final bin set as the paper's depth-first recursion (verified against
a sequential NumPy oracle in tests). In 2-D, refinement order can matter
(row/column splits interact); the BFS is the deterministic, order-independent
variant of the same procedure.

All functions are jit-compatible with static capacities; 1-D refinement is
vmapped across columns. The 2-D path is *pair-batched*: pairs stack into
(P, N) tensors and one round refines every pair level-synchronously
(``_round_2d_batch``), with the per-round cell counts dispatching through
the batched hist2d kernel (``repro.kernels.hist2d.batched_hist2d``) and the
chi-squared sub-bin counts through the batched sub-bin kernel
(``repro.kernels.subbin`` via ``chi2.subbin_counts``) — Pallas one-hot
matmuls on TPU, dtype-preserving scatter/segment-sum oracles elsewhere.
``refine_2d_compact`` drives that round, convergence-compacting: a fixed
set of slots refines an arbitrarily long pending queue, draining each pair
the round it converges and backfilling its slot, so deep-refining pairs
never stall shallow ones (full occupancy until the queue runs dry).

Each pair is presorted once by (x, y) and (y, x) (``presort_pairs``), which
turns the per-round ``lexsort`` in ``_slice_unique`` into cheap
run-boundary flag sums — counts are exact integers, so the compacting
scheduler is bit-for-bit equal to the per-pair ``refine_2d`` loop
(asserted in tests). ``refine_2d``/``pair_metadata`` remain as the
single-pair reference path, the oracle ``build.build_pairs_sequential``
runs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import chi2 as chi2lib
from repro.kernels.hist2d import batched_hist2d

_INF = jnp.inf


# ---------------------------------------------------------------------------
# Shared vectorized bin statistics (1-D)
# ---------------------------------------------------------------------------


def bin_stats_1d(xs, uprefix, edges, k):
    """Per-bin (count, unique, vmin, vmax, lo_idx, hi_idx) from sorted data.

    xs:      (N,) sorted ascending; invalid entries (+inf) sorted last.
    uprefix: (N+1,) uprefix[n] = number of distinct values among xs[:n].
    edges:   (K+1,) sorted, +inf padded.
    k:       () number of valid bins.
    """
    K = edges.shape[0] - 1
    n = xs.shape[0]
    t = jnp.arange(K)
    left = jnp.searchsorted(xs, edges, side="left")      # (K+1,)
    right = jnp.searchsorted(xs, edges, side="right")    # (K+1,)
    lo = left[:-1]
    # Standard histogram convention: all bins half-open, last valid bin closed.
    hi = jnp.where(t == k - 1, right[1:], left[1:])
    valid = t < k
    lo = jnp.where(valid, lo, n)
    hi = jnp.where(valid, jnp.maximum(hi, lo), lo)
    h = (hi - lo).astype(jnp.float64)
    u = (uprefix[hi] - uprefix[lo]).astype(jnp.float64)
    vmin = xs[jnp.clip(lo, 0, n - 1)]
    vmax = xs[jnp.clip(hi - 1, 0, n - 1)]
    # Empty bins keep their edges as extrema (RefineBin1D line 4).
    eL, eR = edges[:-1], edges[1:]
    empty = h == 0
    vmin = jnp.where(empty, eL, vmin)
    vmax = jnp.where(empty, eR, vmax)
    return h, u, vmin, vmax, lo, hi


def chi2_stat_1d(xs, edges, k, h, u, lo, hi, s_max: int, crit_table):
    """Vectorized IsUniform over all bins: returns (chi2, crit, s).

    Sub-bin boundary positions come from one batched searchsorted of the
    (K, s_max-1) sub-edge matrix into the sorted column.
    """
    K = edges.shape[0] - 1
    n = xs.shape[0]
    eL, eR = edges[:-1], edges[1:]
    s = chi2lib.num_subbins(u, s_max)                           # (K,) i32
    r = jnp.arange(1, s_max)                                    # (s_max-1,)
    frac = r[None, :] / jnp.maximum(s[:, None], 1)              # (K, s_max-1)
    width = jnp.where(jnp.isfinite(eR - eL), eR - eL, 0.0)
    sub_edges = eL[:, None] + width[:, None] * frac
    pos = jnp.searchsorted(xs, sub_edges.reshape(-1), side="left")
    pos = pos.reshape(K, s_max - 1)
    in_range = r[None, :] < s[:, None]
    pos = jnp.where(in_range, pos, hi[:, None])
    pos = jnp.clip(pos, lo[:, None], hi[:, None])
    bounds = jnp.concatenate([lo[:, None], pos, hi[:, None]], axis=1)
    hbar = jnp.diff(bounds, axis=1).astype(jnp.float64)         # (K, s_max)
    expect = h / jnp.maximum(s.astype(jnp.float64), 1.0)
    rr = jnp.arange(s_max)
    live = rr[None, :] < s[:, None]
    num = jnp.where(live, (hbar - expect[:, None]) ** 2, 0.0)
    stat = jnp.sum(num, axis=1) / jnp.maximum(expect, 1e-30)
    crit = crit_table[jnp.clip(s, 0, crit_table.shape[0] - 1)]
    return stat, crit, s


# ---------------------------------------------------------------------------
# 1-D refinement
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("s_max", "max_rounds"))
def refine_1d(xs, uprefix, init_edges, n_init, min_points, crit_table,
              s_max: int = 128, max_rounds: int = 64):
    """Refine one column's histogram. Returns (edges, k).

    xs:         (N,) sorted values, invalid rows = +inf at the end.
    init_edges: (K+1,) initial edges (+inf padded), K = capacity.
    n_init:     () number of valid initial bins.
    min_points: M.
    """
    K = init_edges.shape[0] - 1

    def cond(state):
        _, _, n_split, rounds = state
        return (n_split > 0) & (rounds < max_rounds)

    def body(state):
        edges, k, _, rounds = state
        h, u, _, _, lo, hi = bin_stats_1d(xs, uprefix, edges, k)
        stat, crit, _ = chi2_stat_1d(xs, edges, k, h, u, lo, hi, s_max, crit_table)
        t = jnp.arange(K)
        eL, eR = edges[:-1], edges[1:]
        z = 0.5 * (eL + eR)
        splittable = (z > eL) & (z < eR) & jnp.isfinite(z)
        split = (
            (t < k)
            & (h >= min_points)      # "fewer than M tuples" -> no split
            & (u > 1.0)              # single unique value -> no split
            & (stat > crit)          # IsUniform -> no split
            & splittable
        )
        # Capacity guard: keep at most (K - k) new edges (first-come by index).
        avail = K - k
        rank = jnp.cumsum(split.astype(jnp.int32)) - 1
        split = split & (rank < avail)
        n_split = jnp.sum(split, dtype=jnp.int32)
        new = jnp.where(split, z, _INF)
        edges = jnp.sort(jnp.concatenate([edges, new]))[: K + 1]
        return edges, (k + n_split).astype(jnp.int32), n_split, rounds + 1

    state = (init_edges, n_init.astype(jnp.int32), jnp.int32(1), jnp.int32(0))
    edges, k, _, _ = jax.lax.while_loop(cond, body, state)
    return edges, k


@functools.partial(jax.jit, static_argnames=("s_max",))
def metadata_1d(xs, uprefix, edges, k, min_points, crit_table, mu,
                s_max: int = 128):
    """Final per-bin metadata for a refined 1-D histogram.

    Returns (h, u, vmin, vmax, c, cminus, cplus) — Eq. 10 for the centre
    bounds, midpoint c = (v+ + v-)/2.
    """
    h, u, vmin, vmax, _, _ = bin_stats_1d(xs, uprefix, edges, k)
    c = 0.5 * (vmin + vmax)
    cminus, cplus = centre_bounds(h, u, vmin, vmax, min_points, crit_table, mu,
                                  s_max=s_max)
    return h, u, vmin, vmax, c, cminus, cplus


def centre_bounds(h, u, vmin, vmax, min_points, crit_table, mu, s_max: int):
    """Weighted-centre bounds (Theorem 1 / Eq. 10).

    Non-passing bins (h < M): c± = v± ∓ (u-1)u·mu / (2h).
    Passing bins:            c± = v- + (s±1)δ/2 ± (δ/6)·sqrt(3·chi2_a·(s²-1)/h).
    """
    s = chi2lib.num_subbins(u, s_max).astype(jnp.float64)
    delta = (vmax - vmin) / jnp.maximum(s, 1.0)
    crit = crit_table[jnp.clip(s.astype(jnp.int32), 0, crit_table.shape[0] - 1)]
    crit = jnp.where(jnp.isfinite(crit), crit, 0.0)  # s<2 => degenerate bin
    hsafe = jnp.maximum(h, 1.0)

    spread = (delta / 6.0) * jnp.sqrt(3.0 * crit * (s**2 - 1.0) / hsafe)
    c_lo_pass = vmin + (s - 1.0) * delta / 2.0 - spread
    c_hi_pass = vmin + (s + 1.0) * delta / 2.0 + spread

    shift = (u - 1.0) * u * mu / (2.0 * hsafe)
    c_lo_fail = vmin + shift
    c_hi_fail = vmax - shift

    fail = h < min_points
    cminus = jnp.where(fail, c_lo_fail, c_lo_pass)
    cplus = jnp.where(fail, c_hi_fail, c_hi_pass)

    mid = 0.5 * (vmin + vmax)
    degenerate = u <= 1.0
    cminus = jnp.where(degenerate, mid, cminus)
    cplus = jnp.where(degenerate, mid, cplus)
    cminus = jnp.clip(cminus, vmin, vmax)
    cplus = jnp.clip(cplus, cminus, vmax)
    return cminus, cplus


# ---------------------------------------------------------------------------
# 2-D refinement
# ---------------------------------------------------------------------------


def _bin_index(vals, edges, k):
    """Bin index per point under the half-open-except-last convention."""
    idx = jnp.searchsorted(edges, vals, side="right") - 1
    return jnp.clip(idx, 0, jnp.maximum(k - 1, 0))


def _slice_unique(sort_primary, sort_value, valid, num_segments):
    """Unique-value counts per segment via lexsort + first-occurrence flags."""
    order = jnp.lexsort((sort_value, sort_primary))
    seg = sort_primary[order]
    val = sort_value[order]
    ok = valid[order]
    new_seg = jnp.concatenate([jnp.array([True]), seg[1:] != seg[:-1]])
    new_val = jnp.concatenate([jnp.array([True]), val[1:] != val[:-1]])
    first = (new_seg | new_val) & ok
    return jax.ops.segment_sum(first.astype(jnp.float64), seg,
                               num_segments=num_segments)


def _cell_chi2(vals, lo, width, cell, h_cell, u_cell, valid, k2: int,
               s_max: int, crit_table):
    """Per-cell chi-squared uniformity statistic along one dimension.

    vals/lo/width: per-point value + its cell's interval in this dimension.
    cell:          per-point flattened cell id in [0, k2*k2).
    h_cell/u_cell: per-cell totals (k2*k2,).
    """
    ncell = k2 * k2
    s = chi2lib.num_subbins(u_cell, s_max)                       # (ncell,)
    s_pt = s[cell]
    frac = jnp.where(width > 0, (vals - lo) / width, 0.0)
    r = jnp.clip((frac * s_pt).astype(jnp.int32), 0, s_pt - 1)
    flat = jnp.where(valid, cell * s_max + r, ncell * s_max)
    hbar = jax.ops.segment_sum(jnp.ones_like(vals), flat,
                               num_segments=ncell * s_max + 1)[:-1]
    hbar = hbar.reshape(ncell, s_max)
    sf = jnp.maximum(s.astype(jnp.float64), 1.0)
    expect = h_cell / sf
    rr = jnp.arange(s_max)
    live = rr[None, :] < s[:, None]
    num = jnp.where(live, (hbar - expect[:, None]) ** 2, 0.0)
    stat = jnp.sum(num, axis=1) / jnp.maximum(expect, 1e-30)
    crit = crit_table[jnp.clip(s, 0, crit_table.shape[0] - 1)]
    return stat, crit


@functools.partial(jax.jit, static_argnames=("k2", "s_max", "max_rounds"))
def refine_2d(x, y, valid, ex0, ey0, kx0, ky0, min_points, crit_table,
              k2: int, s_max: int = 32, max_rounds: int = 16):
    """Refine a pair histogram. Returns (ex, ey, kx, ky).

    x, y:   (N,) point coordinates (pre-processed domain); `valid` masks rows
            where either column is null.
    ex0/ey0: (K2+1,) initial edges = the columns' final 1-D edges (padded).
    """
    ncell = k2 * k2

    def cond(state):
        _, _, _, _, n_split, rounds = state
        return (n_split > 0) & (rounds < max_rounds)

    def body(state):
        ex, ey, kx, ky, _, rounds = state
        bi = _bin_index(x, ex, kx)
        bj = _bin_index(y, ey, ky)
        cell = bi * k2 + bj
        cell_m = jnp.where(valid, cell, ncell)
        ones = jnp.where(valid, 1.0, 0.0)
        h_cell = jax.ops.segment_sum(ones, cell_m, num_segments=ncell + 1)[:-1]

        ux_cell = _slice_unique(cell_m, x, valid, ncell + 1)[:-1]
        uy_cell = _slice_unique(cell_m, y, valid, ncell + 1)[:-1]

        lox, wx = ex[bi], ex[bi + 1] - ex[bi]
        loy, wy = ey[bj], ey[bj + 1] - ey[bj]
        stat_x, crit_x = _cell_chi2(x, lox, wx, cell, h_cell, ux_cell, valid,
                                    k2, s_max, crit_table)
        stat_y, crit_y = _cell_chi2(y, loy, wy, cell, h_cell, uy_cell, valid,
                                    k2, s_max, crit_table)

        eligible = h_cell > min_points                      # Alg. 1 line 17
        fail_x = eligible & (ux_cell > 1.0) & (stat_x > crit_x)
        fail_y = eligible & (uy_cell > 1.0) & (stat_y > crit_y)
        # "split applied to the least uniform column": larger excess ratio.
        exc_x = jnp.where(fail_x, stat_x / jnp.maximum(crit_x, 1e-30), -1.0)
        exc_y = jnp.where(fail_y, stat_y / jnp.maximum(crit_y, 1e-30), -1.0)
        pick_x = fail_x & (~fail_y | (exc_x >= exc_y))
        pick_y = fail_y & ~pick_x

        # A split in cell (ti, tj) along x inserts the midpoint of row ti's
        # interval — applying to the whole row (Fig. 5). Reduce cell->row.
        ti = jnp.arange(ncell) // k2
        tj = jnp.arange(ncell) % k2
        want_x = jax.ops.segment_max(pick_x.astype(jnp.int32), ti,
                                     num_segments=k2).astype(bool)
        want_y = jax.ops.segment_max(pick_y.astype(jnp.int32), tj,
                                     num_segments=k2).astype(bool)

        tK = jnp.arange(k2)
        zx = 0.5 * (ex[:-1] + ex[1:])
        zy = 0.5 * (ey[:-1] + ey[1:])
        ok_x = want_x & (tK < kx) & (zx > ex[:-1]) & (zx < ex[1:])
        ok_y = want_y & (tK < ky) & (zy > ey[:-1]) & (zy < ey[1:])
        rank_x = jnp.cumsum(ok_x.astype(jnp.int32)) - 1
        rank_y = jnp.cumsum(ok_y.astype(jnp.int32)) - 1
        ok_x = ok_x & (rank_x < (k2 - kx))
        ok_y = ok_y & (rank_y < (k2 - ky))
        nx = jnp.sum(ok_x, dtype=jnp.int32)
        ny = jnp.sum(ok_y, dtype=jnp.int32)

        ex = jnp.sort(jnp.concatenate([ex, jnp.where(ok_x, zx, _INF)]))[: k2 + 1]
        ey = jnp.sort(jnp.concatenate([ey, jnp.where(ok_y, zy, _INF)]))[: k2 + 1]
        return (ex, ey, (kx + nx).astype(jnp.int32), (ky + ny).astype(jnp.int32),
                (nx + ny).astype(jnp.int32), rounds + 1)

    state = (ex0, ey0, kx0.astype(jnp.int32), ky0.astype(jnp.int32),
             jnp.int32(1), jnp.int32(0))
    ex, ey, kx, ky, _, _ = jax.lax.while_loop(cond, body, state)
    return ex, ey, kx, ky


@functools.partial(jax.jit, static_argnames=("k2",))
def pair_metadata(x, y, valid, ex, ey, kx, ky, k2: int):
    """Final pair-histogram metadata (counts + per-dim slice aggregates).

    Fold maps (1-D union bin -> pair row) are computed host-side in
    repro.core.build.fold_to_rows after the 1-D grids are union-refined.
    """
    ncell = k2 * k2
    bi = _bin_index(x, ex, kx)
    bj = _bin_index(y, ey, ky)
    cell = jnp.where(valid, bi * k2 + bj, ncell)
    ones = jnp.where(valid, 1.0, 0.0)
    H = jax.ops.segment_sum(ones, cell, num_segments=ncell + 1)[:-1]
    H = H.reshape(k2, k2)

    big = jnp.float64(jnp.finfo(jnp.float64).max)
    row = jnp.where(valid, bi, k2)
    col = jnp.where(valid, bj, k2)

    def slice_meta(seg, vals, edges, k):
        hh = jax.ops.segment_sum(ones, seg, num_segments=k2 + 1)[:-1]
        vmin = jax.ops.segment_min(jnp.where(valid, vals, big), seg,
                                   num_segments=k2 + 1)[:-1]
        vmax = jax.ops.segment_max(jnp.where(valid, vals, -big), seg,
                                   num_segments=k2 + 1)[:-1]
        uu = _slice_unique(seg, vals, valid, k2 + 1)[:-1]
        empty = hh == 0
        vmin = jnp.where(empty, edges[:-1], vmin)
        vmax = jnp.where(empty, edges[1:], vmax)
        return hh, uu, vmin, vmax

    hx, ux, vminx, vmaxx = slice_meta(row, x, ex, kx)
    hy, uy, vminy, vmaxy = slice_meta(col, y, ey, ky)
    return H, hx, ux, vminx, vmaxx, hy, uy, vminy, vmaxy


# ---------------------------------------------------------------------------
# Pair-batched 2-D refinement (all pairs of a chunk in one while_loop)
# ---------------------------------------------------------------------------


@jax.jit
def presort_pairs(x, y, valid):
    """Per-pair lexsorts, done once per chunk (not per refinement round).

    x/y/valid: (P, N). Invalid rows sort to the tail (+inf keys). Returns
    the points of every pair in (x, y) order and in (y, x) order plus
    run-start flags:

      xo1/yo1/vo1/new1: values, validity and x-run starts in (x, y) order;
      xo2/yo2/vo2/new2: values, validity and y-run starts in (y, x) order.

    Within an x-run (equal x => equal x-bin in any grid), points are sorted
    by y, so equal y-bins are contiguous — a point starts a new (x-value,
    y-bin) group iff it starts a run or its y-bin differs from its
    predecessor. Summing those flags per cell gives the exact distinct-x
    count per cell with no per-round sort (ditto distinct-y via order 2).

    ``build._presort_pairs_host`` computes the same arrays host-side
    (numpy's sort is much faster than XLA:CPU's); this jitted version is
    the device counterpart it is tested against.
    """
    key_x = jnp.where(valid, x, _INF)
    key_y = jnp.where(valid, y, _INF)

    def one(kx, ky):
        return jnp.lexsort((ky, kx)), jnp.lexsort((kx, ky))

    o1, o2 = jax.vmap(one)(key_x, key_y)

    def take(a, o):
        return jnp.take_along_axis(a, o, axis=1)

    xo1, yo1, vo1 = take(x, o1), take(y, o1), take(valid, o1)
    xo2, yo2, vo2 = take(x, o2), take(y, o2), take(valid, o2)
    first = jnp.ones((x.shape[0], 1), bool)
    new1 = jnp.concatenate([first, xo1[:, 1:] != xo1[:, :-1]], axis=1)
    new2 = jnp.concatenate([first, yo2[:, 1:] != yo2[:, :-1]], axis=1)
    return xo1, yo1, vo1, new1, xo2, yo2, vo2, new2


def _bin_index_b(vals, edges, k):
    """(P, N) values x (P, K+1) edges -> per-point bin indices, per pair."""
    idx = jax.vmap(
        lambda v, e: jnp.searchsorted(e, v, side="right"))(vals, edges) - 1
    return jnp.clip(idx, 0, jnp.maximum(k[:, None] - 1, 0))


def _unique_flags(new_run, other_bin, valid):
    """First-occurrence flags of each (run, other-dim bin) group (f64)."""
    prev = jnp.concatenate([other_bin[:, :1], other_bin[:, :-1]], axis=1)
    return ((new_run | (other_bin != prev)) & valid).astype(jnp.float64)


def _chi2_from_hbar_b(hbar, h_cell, s, s_max: int, crit_table):
    """Batched tail of ``_cell_chi2``: identical float ops on (P, ncell)."""
    sf = jnp.maximum(s.astype(jnp.float64), 1.0)
    expect = h_cell / sf
    rr = jnp.arange(s_max)
    live = rr[None, None, :] < s[:, :, None]
    num = jnp.where(live, (hbar - expect[:, :, None]) ** 2, 0.0)
    stat = jnp.sum(num, axis=2) / jnp.maximum(expect, 1e-30)
    crit = crit_table[jnp.clip(s, 0, crit_table.shape[0] - 1)]
    return stat, crit


def _round_2d_batch(xo1, yo1, vo1, new1, xo2, yo2, vo2, new2,
                    ex, ey, kx, ky, min_points, crit_table, *,
                    k2: int, s_max: int, use_pallas: bool,
                    interpret: bool | None):
    """ONE level-synchronous refinement round over P pairs.

    The inner step of ``refine_2d_compact`` (drain/backfill active set),
    over its slots: per-cell statistics
    via the batched hist2d + sub-bin kernels, split selection, capacity
    guard, edge insertion. Returns (ex, ey, kx, ky, n_split, capped_round)
    with per-pair split and guard-bound flags for this round. Exactly the
    ops of the legacy per-pair ``refine_2d`` body on each pair's lane, so
    any scheduler built on it stays bit-for-bit equal to the sequential
    path.
    """
    p = xo1.shape[0]
    ncell = k2 * k2
    bio1 = _bin_index_b(xo1, ex, kx)
    bjo1 = _bin_index_b(yo1, ey, ky)
    bio2 = _bin_index_b(xo2, ex, kx)
    bjo2 = _bin_index_b(yo2, ey, ky)
    cell1 = bio1 * k2 + bjo1
    cell2 = bio2 * k2 + bjo2

    ux_cell = batched_hist2d(
        bio1, bjo1, _unique_flags(new1, bjo1, vo1), k2, k2,
        use_pallas=use_pallas, interpret=interpret).reshape(p, ncell)
    uy_cell = batched_hist2d(
        bio2, bjo2, _unique_flags(new2, bio2, vo2), k2, k2,
        use_pallas=use_pallas, interpret=interpret).reshape(p, ncell)
    s_x = chi2lib.num_subbins(ux_cell, s_max)
    s_y = chi2lib.num_subbins(uy_cell, s_max)

    lox = jnp.take_along_axis(ex, bio1, axis=1)
    wx = jnp.take_along_axis(ex, bio1 + 1, axis=1) - lox
    loy = jnp.take_along_axis(ey, bjo2, axis=1)
    wy = jnp.take_along_axis(ey, bjo2 + 1, axis=1) - loy
    hbar_x = chi2lib.subbin_counts(xo1, lox, wx, cell1, s_x, vo1,
                                   ncell=ncell, s_max=s_max,
                                   use_pallas=use_pallas, interpret=interpret)
    hbar_y = chi2lib.subbin_counts(yo2, loy, wy, cell2, s_y, vo2,
                                   ncell=ncell, s_max=s_max,
                                   use_pallas=use_pallas, interpret=interpret)
    h_cell = jnp.sum(hbar_x, axis=2)
    stat_x, crit_x = _chi2_from_hbar_b(hbar_x, h_cell, s_x, s_max, crit_table)
    stat_y, crit_y = _chi2_from_hbar_b(hbar_y, h_cell, s_y, s_max, crit_table)

    eligible = h_cell > min_points
    fail_x = eligible & (ux_cell > 1.0) & (stat_x > crit_x)
    fail_y = eligible & (uy_cell > 1.0) & (stat_y > crit_y)
    exc_x = jnp.where(fail_x, stat_x / jnp.maximum(crit_x, 1e-30), -1.0)
    exc_y = jnp.where(fail_y, stat_y / jnp.maximum(crit_y, 1e-30), -1.0)
    pick_x = fail_x & (~fail_y | (exc_x >= exc_y))
    pick_y = fail_y & ~pick_x

    # cell (ti, tj) -> whole row/column wants a split (Fig. 5).
    want_x = pick_x.reshape(p, k2, k2).any(axis=2)
    want_y = pick_y.reshape(p, k2, k2).any(axis=1)

    tK = jnp.arange(k2)[None, :]
    zx = 0.5 * (ex[:, :-1] + ex[:, 1:])
    zy = 0.5 * (ey[:, :-1] + ey[:, 1:])
    ok_x = want_x & (tK < kx[:, None]) & (zx > ex[:, :-1]) & (zx < ex[:, 1:])
    ok_y = want_y & (tK < ky[:, None]) & (zy > ey[:, :-1]) & (zy < ey[:, 1:])
    nwx = jnp.sum(ok_x, axis=1, dtype=jnp.int32)   # wanted, pre-guard
    nwy = jnp.sum(ok_y, axis=1, dtype=jnp.int32)
    capped_round = (nwx > k2 - kx) | (nwy > k2 - ky)
    rank_x = jnp.cumsum(ok_x.astype(jnp.int32), axis=1) - 1
    rank_y = jnp.cumsum(ok_y.astype(jnp.int32), axis=1) - 1
    ok_x = ok_x & (rank_x < (k2 - kx)[:, None])
    ok_y = ok_y & (rank_y < (k2 - ky)[:, None])
    nx = jnp.sum(ok_x, axis=1, dtype=jnp.int32)
    ny = jnp.sum(ok_y, axis=1, dtype=jnp.int32)

    ex = jnp.sort(jnp.concatenate(
        [ex, jnp.where(ok_x, zx, _INF)], axis=1), axis=1)[:, : k2 + 1]
    ey = jnp.sort(jnp.concatenate(
        [ey, jnp.where(ok_y, zy, _INF)], axis=1), axis=1)[:, : k2 + 1]
    return (ex, ey, (kx + nx).astype(jnp.int32), (ky + ny).astype(jnp.int32),
            (nx + ny).astype(jnp.int32), capped_round)


@functools.partial(jax.jit, static_argnames=("n_slots", "k2", "s_max",
                                             "max_rounds", "drain_capped",
                                             "use_pallas", "interpret"))
def refine_2d_compact(xo1, yo1, vo1, new1, xo2, yo2, vo2, new2,
                      ex0, ey0, kx0, ky0, rounds0, capped0, n_pending,
                      min_points, crit_table, occupancy_min, *,
                      n_slots: int, k2: int, s_max: int = 32,
                      max_rounds: int = 16, drain_capped: bool = False,
                      use_pallas: bool = False,
                      interpret: bool | None = None):
    """Convergence-compacting refinement: an S-slot active set over P pairs.

    A single ``lax.while_loop`` over a fixed chunk would run until the
    *slowest* pair of the chunk converges — deep-refining (correlated)
    pairs would lockstep-drag shallow ones. Here ``n_slots`` device-side
    slots refine one round per loop iteration; every iteration, slots
    whose pair converged this round (no splits, or ``max_rounds`` reached,
    or — when ``drain_capped`` — the capacity guard bound) **drain** into
    per-pair output buffers and **backfill** from the pending queue
    ``[next_ptr, n_pending)``, so the active set stays at full occupancy
    until the queue runs dry.

    Inputs are the presorted arrays of ALL P pending pairs plus per-pair
    start states (``ex0``/``ey0``/``kx0``/``ky0``/``rounds0``/``capped0``
    — fresh pairs have rounds 0, resumed pairs their partial state).
    ``n_pending`` (traced) is the real pair count; lanes beyond it are
    padding and are never fed. Because each pair's round trajectory is the
    deterministic ``_round_2d_batch`` fixed-point iteration, independent
    of slot assignment and of its slot neighbours, the drained results are
    **schedule-independent**: bit-for-bit equal to ``refine_2d`` on each
    pair alone, whatever the slot count, queue order or drain timing
    (asserted in tests/test_build_compact.py).

    ``drain_capped`` (static) drains a pair the moment its guard binds —
    used on non-final capacity rungs, where a capped result is discarded
    and the pair re-queued one rung up, so keeping it refining would only
    burn its slot. On the final rung it must be False (the capped result
    is the real, fully-refined K2-capped histogram).

    ``out_capped[p]`` is True iff pair p's K2-capacity guard ever dropped
    a wanted split. When False, the result is independent of ``k2`` (any
    capacity >= the final bin counts yields the same histogram), which is
    what lets construction refine at a small capacity rung first and
    re-queue only the capped pairs one rung up.

    ``occupancy_min`` (traced, 0 disables): once the queue is empty and
    fewer than ``ceil(occupancy_min * n_slots)`` slots remain active, the
    loop exits early — after at least one round, so every launch makes
    progress — and returns the unconverged slots' partial states for the
    caller to re-bucket into a smaller launch (``build.build_pairs_compact``).

    Returns ``(out_ex, out_ey, out_kx, out_ky, out_capped, out_rounds,
    out_done, slot_pair, slot_active, sex, sey, skx, sky, scapped, srounds,
    occ_hist, loop_rounds, active_rounds)`` — per-pair outputs (valid where
    ``out_done``), the live slot state for resumption, and occupancy
    telemetry (``active_rounds`` counts pair-rounds actually refined;
    ``loop_rounds * n_slots`` is the slot-rounds paid; ``occ_hist`` is an
    ``(n_slots + 1,)`` histogram of how many loop rounds ran with each
    possible active-slot count — the per-round occupancy distribution at
    fixed memory, feeding the build timeline).
    """
    P = xo1.shape[0]
    S = n_slots
    thr = jnp.ceil(occupancy_min * S).astype(jnp.int32)

    def fill(dst_mask, src_idx, cur):
        """Load per-pair start state into slots where ``dst_mask``."""
        idx = jnp.clip(src_idx, 0, P - 1)
        out = []
        for arr, val in cur:
            picked = arr[idx]
            m = dst_mask[:, None] if picked.ndim == 2 else dst_mask
            out.append(jnp.where(m, picked, val))
        return out

    slot_pair = jnp.minimum(jnp.arange(S, dtype=jnp.int32),
                            jnp.maximum(n_pending - 1, 0).astype(jnp.int32))
    active = jnp.arange(S) < n_pending
    sex = ex0[slot_pair]
    sey = ey0[slot_pair]
    skx = kx0[slot_pair].astype(jnp.int32)
    sky = ky0[slot_pair].astype(jnp.int32)
    scap = capped0[slot_pair]
    srnd = rounds0[slot_pair].astype(jnp.int32)
    out_ex = jnp.zeros_like(ex0)
    out_ey = jnp.zeros_like(ey0)
    out_kx = jnp.zeros(P, jnp.int32)
    out_ky = jnp.zeros(P, jnp.int32)
    out_capped = jnp.zeros(P, bool)
    out_rounds = jnp.zeros(P, jnp.int32)
    out_done = jnp.zeros(P, bool)
    state = (slot_pair, active, sex, sey, skx, sky, scap, srnd,
             jnp.minimum(jnp.int32(S), n_pending.astype(jnp.int32)),
             out_ex, out_ey, out_kx, out_ky, out_capped, out_rounds,
             out_done, jnp.zeros(S + 1, jnp.int32), jnp.int32(0),
             jnp.int32(0))

    def cond(st):
        (_, active, _, _, _, _, _, _, next_ptr,
         _, _, _, _, _, _, _, _, loop_rounds, _) = st
        n_act = jnp.sum(active, dtype=jnp.int32)
        exhausted = next_ptr >= n_pending
        return jnp.any(active) & ((loop_rounds == 0)
                                  | ~(exhausted & (n_act < thr)))

    def body(st):
        (slot_pair, active, sex, sey, skx, sky, scap, srnd, next_ptr,
         out_ex, out_ey, out_kx, out_ky, out_capped, out_rounds,
         out_done, occ_hist, loop_rounds, active_rounds) = st
        nex, ney, nkx, nky, n_split, cap_r = _round_2d_batch(
            xo1[slot_pair], yo1[slot_pair], vo1[slot_pair], new1[slot_pair],
            xo2[slot_pair], yo2[slot_pair], vo2[slot_pair], new2[slot_pair],
            sex, sey, skx, sky, min_points, crit_table, k2=k2, s_max=s_max,
            use_pallas=use_pallas, interpret=interpret)
        am = active
        sex = jnp.where(am[:, None], nex, sex)
        sey = jnp.where(am[:, None], ney, sey)
        skx = jnp.where(am, nkx, skx)
        sky = jnp.where(am, nky, sky)
        scap = scap | (cap_r & am)
        srnd = srnd + am.astype(jnp.int32)
        n_split = jnp.where(am, n_split, 0)

        conv = am & ((n_split == 0) | (srnd >= max_rounds))
        if drain_capped:
            conv = conv | (am & scap)

        # Drain: scatter converged slots into their pair's output lane
        # (index P for unconverged slots -> dropped).
        didx = jnp.where(conv, slot_pair, P)
        out_ex = out_ex.at[didx].set(sex, mode="drop")
        out_ey = out_ey.at[didx].set(sey, mode="drop")
        out_kx = out_kx.at[didx].set(skx, mode="drop")
        out_ky = out_ky.at[didx].set(sky, mode="drop")
        out_capped = out_capped.at[didx].set(scap, mode="drop")
        out_rounds = out_rounds.at[didx].set(srnd, mode="drop")
        out_done = out_done.at[didx].set(True, mode="drop")

        # Backfill: rank the drained slots and hand out pending pairs.
        offs = jnp.cumsum(conv.astype(jnp.int32)) - 1
        nidx = next_ptr + offs
        take = conv & (nidx < n_pending)
        slot_pair = jnp.where(take, nidx, slot_pair).astype(jnp.int32)
        active = jnp.where(conv, take, active)
        sex, sey, skx, sky, scap, srnd = fill(take, slot_pair, [
            (ex0, sex), (ey0, sey), (kx0.astype(jnp.int32), skx),
            (ky0.astype(jnp.int32), sky), (capped0, scap),
            (rounds0.astype(jnp.int32), srnd)])
        next_ptr = next_ptr + jnp.sum(take, dtype=jnp.int32)
        n_am = jnp.sum(am, dtype=jnp.int32)
        return (slot_pair, active, sex, sey, skx, sky, scap, srnd, next_ptr,
                out_ex, out_ey, out_kx, out_ky, out_capped, out_rounds,
                out_done, occ_hist.at[n_am].add(1), loop_rounds + 1,
                active_rounds + n_am)

    (slot_pair, active, sex, sey, skx, sky, scap, srnd, _next_ptr,
     out_ex, out_ey, out_kx, out_ky, out_capped, out_rounds, out_done,
     occ_hist, loop_rounds, active_rounds) = jax.lax.while_loop(
         cond, body, state)
    return (out_ex, out_ey, out_kx, out_ky, out_capped, out_rounds, out_done,
            slot_pair, active, sex, sey, skx, sky, scap, srnd,
            occ_hist, loop_rounds, active_rounds)


@functools.partial(jax.jit, static_argnames=("k2", "use_pallas", "interpret"))
def pair_metadata_batch(xo1, yo1, vo1, new1, xo2, yo2, vo2, new2,
                        ex, ey, kx, ky, *, k2: int,
                        use_pallas: bool = False,
                        interpret: bool | None = None):
    """Batched ``pair_metadata``: (P, ...) in, (P, ...) out, same values.

    The count matrix routes through the batched hist2d kernel; everything
    per-dimension comes from the presorted order *without scatters*: a
    row's points are a contiguous slice of the (x, y)-sorted array (bin
    index depends on x alone), so row extrema are the slice ends and
    distinct counts are prefix-sum differences of the run flags — exactly
    the values the legacy segment ops produce.
    """
    p, n = xo1.shape
    bio1 = _bin_index_b(xo1, ex, kx)
    bjo1 = _bin_index_b(yo1, ey, ky)
    ones1 = jnp.where(vo1, 1.0, 0.0)
    H = batched_hist2d(bio1, bjo1, ones1, k2, k2, use_pallas=use_pallas,
                       interpret=interpret)                    # (P, K2, K2)
    hx = H.sum(axis=2)
    hy = H.sum(axis=1)
    nv = jnp.sum(vo1, axis=1)                                  # (P,)

    def slice_meta(vals_sorted, valid_sorted, run_flags, edges, k):
        keyed = jnp.where(valid_sorted, vals_sorted, _INF)
        pos = jax.vmap(lambda kv, e: jnp.searchsorted(
            kv, e, side="left"))(keyed, edges)                 # (P, K2+1)
        t = jnp.arange(k2)[None, :]
        lo = pos[:, :-1]
        # Half-open bins except the last valid one (closed): its slice runs
        # to the end of the valid prefix.
        hi = jnp.where(t == k[:, None] - 1, nv[:, None], pos[:, 1:])
        hi = jnp.maximum(hi, lo)
        up = jnp.cumsum((run_flags & valid_sorted).astype(jnp.float64),
                        axis=1)
        up = jnp.concatenate([jnp.zeros((p, 1), jnp.float64), up], axis=1)
        uu = jnp.take_along_axis(up, hi, axis=1) - \
            jnp.take_along_axis(up, lo, axis=1)
        vmin = jnp.take_along_axis(vals_sorted,
                                   jnp.clip(lo, 0, n - 1), axis=1)
        vmax = jnp.take_along_axis(vals_sorted,
                                   jnp.clip(hi - 1, 0, n - 1), axis=1)
        return uu, vmin, vmax

    ux, vminx, vmaxx = slice_meta(xo1, vo1, new1, ex, kx)
    uy, vminy, vmaxy = slice_meta(yo2, vo2, new2, ey, ky)

    empty_x = hx == 0
    vminx = jnp.where(empty_x, ex[:, :-1], vminx)
    vmaxx = jnp.where(empty_x, ex[:, 1:], vmaxx)
    ux = jnp.where(empty_x, 0.0, ux)
    empty_y = hy == 0
    vminy = jnp.where(empty_y, ey[:, :-1], vminy)
    vmaxy = jnp.where(empty_y, ey[:, 1:], vmaxy)
    uy = jnp.where(empty_y, 0.0, uy)
    return H, hx, ux, vminx, vmaxx, hy, uy, vminy, vmaxy

