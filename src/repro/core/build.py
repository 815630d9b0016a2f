"""BuildPairwiseHist (Algorithm 1), level-synchronous TPU adaptation.

Pipeline:
  1. downsample the (pre-processed, integer-domain) dataset to N_s rows;
  2. all columns at once: one ``np.sort(axis=0)`` + vectorized unique-prefix,
     then ``refine_1d`` (vmapped across all columns — one kernel refines
     every column's histogram);
  3. pair-batched 2-D refinement: the d(d-1)/2 pairs stack into (P, N_s)
     tensors (bucketed to powers of two so jit compiles a bounded set of
     shapes) and refine level-synchronously on device, with results
     arriving in grouped device->host transfers — no per-pair ``int(kx)`` /
     ``np.asarray`` round-trips. The scheduler is
     **convergence-compacting** (``build_pairs_compact`` /
     ``refine.refine_2d_compact``): ``pair_chunk`` slots refine a
     device-resident pending queue, draining each pair the round it
     converges and backfilling its slot, so deep-refining (correlated)
     pairs never lockstep-drag shallow ones; per-column presorts are
     shared across all pairs (``_column_ranks``) and capacity-guard
     escalation re-queues only the capped pairs. Per-round bin counts
     dispatch through ``repro.kernels.hist2d.batched_hist2d`` and
     chi-squared sub-bin counts through ``repro.kernels.subbin`` (Pallas
     one-hot matmuls when ``params.use_pallas``; dtype-preserving jnp
     oracles otherwise). The per-pair host loop ``build_pairs_sequential``
     is the oracle: tests build through ``_build_pairwise_hist`` with it
     and assert bit-for-bit equal synopses (tests/test_build_batched.py,
     tests/test_build_compact.py).

Missing values (NaN) are excluded per-histogram: a row missing column i does
not contribute to hist(i) nor to any pair involving i — matching SQL
semantics (aggregates ignore NULL, comparisons with NULL are false).

``build_pairwise_hist`` never mutates its inputs: per-column null counts are
attached to *copies* of the caller's ``ColumnInfo`` objects (the synopsis
owns its own column list).
"""
from __future__ import annotations

import dataclasses
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import chi2 as chi2lib
from repro.core import refine
from repro.core.types import BuildParams, ColumnInfo, Hist1D, PairHist, PairwiseHist
from repro.gd.greedygd import CompressedTable, GreedyGD, decompress_rows
from repro.obs.timeline import BuildTimeline

def _prep_columns(sample: np.ndarray):
    """Sort all columns at once with NaN (missing) pushed to +inf at the tail.

    One ``np.sort(axis=0)`` over the (N, d) sample plus a vectorized
    unique-prefix replaces the former Python loop of d per-column sorts.
    Returns (xs_all (d, N), uprefix_all (d, N+1), n_valid (d,), vmin (d,),
    vmax (d,)).
    """
    x = np.asarray(sample, np.float64).copy()
    n, d = x.shape
    nan = np.isnan(x)
    x[nan] = np.inf
    xs = np.sort(x, axis=0)                       # (N, d)
    n_valid = (n - nan.sum(axis=0)).astype(np.int64)
    new = np.empty((n, d), bool)
    new[0] = True
    new[1:] = xs[1:] != xs[:-1]
    up = np.zeros((n + 1, d), np.int64)
    np.cumsum(new, axis=0, out=up[1:])
    has = n_valid > 0
    vmin = np.where(has, xs[0], 0.0)
    vmax = np.where(has, xs[np.maximum(n_valid - 1, 0), np.arange(d)], 0.0)
    return (np.ascontiguousarray(xs.T), np.ascontiguousarray(up.T),
            n_valid, vmin, vmax)


def fold_to_rows(edges_1d: np.ndarray, edges_pair: np.ndarray) -> np.ndarray:
    """Map each 1-D (union-grid) bin to the pair row containing it.

    Pair edges are a subset of the union grid, so containment is exact.
    """
    k1 = edges_1d.size - 1
    mids = 0.5 * (edges_1d[:-1] + edges_1d[1:])
    idx = np.searchsorted(edges_pair, mids, side="right") - 1
    return np.clip(idx, 0, max(edges_pair.size - 2, 0)).astype(np.int32)


def _init_edges(vmin: float, vmax: float, cap: int, n_take: int,
                seed_edges=None) -> tuple[np.ndarray, int]:
    """Initial bin edges: GD bases (downsampled to ceil(N_s/M)) or min/max."""
    if seed_edges is not None and len(seed_edges) > 2:
        e = np.unique(np.asarray(seed_edges, np.float64))
        e = e[(e > vmin) & (e < vmax)]
        if e.size > max(n_take - 2, 0):
            idx = np.linspace(0, e.size - 1, max(n_take - 2, 0)).round().astype(int)
            e = e[np.unique(idx)] if idx.size else e[:0]
        edges = np.concatenate([[vmin], e, [vmax]])
    else:
        edges = np.array([vmin, vmax], np.float64)
    edges = np.unique(edges)
    if edges.size == 1:  # constant column: single zero-width bin
        edges = np.array([edges[0], edges[0]], np.float64)
    edges = edges[: cap + 1]
    n_bins = edges.size - 1
    out = np.full(cap + 1, np.inf, np.float64)
    out[: edges.size] = edges
    return out, n_bins


def _pad_edges(e: np.ndarray, cap: int) -> np.ndarray:
    out = np.full(cap + 1, np.inf, np.float64)
    out[: min(e.size, cap + 1)] = e[: cap + 1]
    return out


def _pair_keys(d: int) -> list[tuple[int, int]]:
    """Pair keys (a, b), a < b, in the legacy loop's emission order."""
    return [(j, i) for i in range(1, d) for j in range(i)]


def _trim_pair(ex, ey, kx, ky, H, hx, ux, vminx, vmaxx, hy, uy, vminy,
               vmaxy) -> PairHist:
    """Trim one pair's fixed-capacity (host) arrays to its valid bins."""
    nkx, nky = int(kx), int(ky)
    return PairHist(
        ex=ex[: nkx + 1].copy(), ey=ey[: nky + 1].copy(),
        kx=np.int32(nkx), ky=np.int32(nky),
        H=H[:nkx, :nky].copy(),
        hx=hx[:nkx].copy(), ux=ux[:nkx].copy(),
        vminx=vminx[:nkx].copy(), vmaxx=vmaxx[:nkx].copy(),
        hy=hy[:nky].copy(), uy=uy[:nky].copy(),
        vminy=vminy[:nky].copy(), vmaxy=vmaxy[:nky].copy(),
        fold_x=np.zeros(0, np.int32), fold_y=np.zeros(0, np.int32),
    )


def build_pairs_sequential(sample: np.ndarray, hists: list, params,
                           crit2, m_pts: int) -> dict:
    """Per-pair host loop (one compiled function, P sequential launches
    with a blocking device->host sync per pair): the bit-for-bit oracle
    for ``build_pairs_compact``, reached from tests through
    ``_build_pairwise_hist``. Returns {(a, b): PairHist} without fold
    maps.
    """
    K2 = params.k2_cap
    sample_j = jnp.asarray(np.nan_to_num(sample, nan=0.0))
    nanmask = np.isnan(sample)
    raw_pairs = {}
    for a, b in _pair_keys(sample.shape[1]):
        valid = jnp.asarray(~(nanmask[:, a] | nanmask[:, b]))
        ex0 = jnp.asarray(_pad_edges(hists[a].edges, K2))
        ey0 = jnp.asarray(_pad_edges(hists[b].edges, K2))
        kx0 = jnp.int32(min(int(hists[a].k), K2))
        ky0 = jnp.int32(min(int(hists[b].k), K2))
        x = sample_j[:, a]
        y = sample_j[:, b]
        ex, ey, kx, ky = refine.refine_2d(
            x, y, valid, ex0, ey0, kx0, ky0, jnp.float64(m_pts), crit2,
            k2=K2, s_max=params.s2_max, max_rounds=params.max_rounds_2d)
        out = refine.pair_metadata(x, y, valid, ex, ey, kx, ky, k2=K2)
        raw_pairs[(a, b)] = _trim_pair(
            *(np.asarray(v) for v in (ex, ey, kx, ky) + tuple(out)))
    return raw_pairs


def _column_ranks(sample_nn: np.ndarray) -> np.ndarray:
    """Per-column dense ranks (d, N): ties share a rank, order preserved.

    One sort + one searchsorted *per column* — shared across every pair the
    column appears in. ``_presort_pairs_host`` composes two columns' ranks
    into a single int64 lexicographic key, so each pair pays one stable
    (radix) integer argsort instead of a two-key float ``np.lexsort``;
    before this, every column was re-lexsorted once per pair (d-1 times).
    """
    n, d = sample_nn.shape
    xs = np.sort(sample_nn, axis=0)
    ranks = np.empty((d, n), np.int64)
    for i in range(d):
        ranks[i] = np.searchsorted(xs[:, i], sample_nn[:, i], side="left")
    return ranks


def _presort_pairs_host(x, y, valid, rx=None, ry=None):
    """Host-side ``refine.presort_pairs`` (numpy's sort beats XLA:CPU's).

    Same layout and same (stable lexsort) semantics; done once per chunk —
    the per-round unique counts then need no sort at all.

    With ``rx``/``ry`` (per-pair rows of the shared ``_column_ranks``
    table) the two-key float lexsorts become single stable argsorts of the
    composite integer key ``rank_primary * (N+1) + rank_secondary``
    (invalid rows get the past-the-end sentinel ``(N+1)^2``, matching the
    +inf keys of the lexsort path). Ranks are order-isomorphic to values
    with identical ties and both sorts are stable, so the permutations —
    and therefore every output array — are identical to the lexsort path
    (asserted in tests/test_build_compact.py).
    """
    n_pairs, n = x.shape
    xo1 = np.empty_like(x)
    yo1 = np.empty_like(y)
    vo1 = np.empty_like(valid)
    xo2 = np.empty_like(x)
    yo2 = np.empty_like(y)
    vo2 = np.empty_like(valid)
    big = np.int64(n + 1) * np.int64(n + 1)
    for p in range(n_pairs):
        if rx is None:
            kx = np.where(valid[p], x[p], np.inf)
            ky = np.where(valid[p], y[p], np.inf)
            o1 = np.lexsort((ky, kx))
            o2 = np.lexsort((kx, ky))
        else:
            key1 = np.where(valid[p], rx[p] * np.int64(n + 1) + ry[p], big)
            key2 = np.where(valid[p], ry[p] * np.int64(n + 1) + rx[p], big)
            o1 = np.argsort(key1, kind="stable")
            o2 = np.argsort(key2, kind="stable")
        xo1[p], yo1[p], vo1[p] = x[p][o1], y[p][o1], valid[p][o1]
        xo2[p], yo2[p], vo2[p] = x[p][o2], y[p][o2], valid[p][o2]
    new1 = np.empty((n_pairs, n), bool)
    new1[:, 0] = True
    new1[:, 1:] = xo1[:, 1:] != xo1[:, :-1]
    new2 = np.empty((n_pairs, n), bool)
    new2[:, 0] = True
    new2[:, 1:] = yo2[:, 1:] != yo2[:, :-1]
    return xo1, yo1, vo1, new1, xo2, yo2, vo2, new2


def _pow2_floor(n: int) -> int:
    """Largest power of two <= n (n >= 1) — the chunk/slot bucketing rule
    (rounding DOWN honours the documented memory ceiling)."""
    return 1 << (max(1, n).bit_length() - 1)


def _pow2_ceil(n: int) -> int:
    """Smallest power of two >= n — the launch-size bucketing rule (tail
    launches pad up so jit sees a bounded set of shapes)."""
    return 1 << max(0, n - 1).bit_length()


def _cap_ladder(need: int, k2_cap: int, k2_start: int) -> list[int]:
    """Doubling capacity ladder: smallest rung fitting ``need`` up to k2_cap."""
    c = max(2, k2_start)
    while c < need:
        c *= 2
    c = min(c, k2_cap)
    ladder = [c]
    while c < k2_cap:
        c = min(c * 2, k2_cap)
        ladder.append(c)
    return ladder


# Pending pairs held device-resident per compacted launch, in units of the
# slot count: the compaction horizon (a deep pair can only be overlapped by
# pairs inside its group) and the (group * N) presort-upload memory bound.
_COMPACT_QUEUE = 4


def build_pairs_compact(sample: np.ndarray, hists: list, params,
                        crit2, m_pts: int, stats: dict | None = None,
                        timeline: BuildTimeline | None = None) -> dict:
    """Convergence-compacting 2-D construction (the served pair phase).

    Pairs feed through ``refine.refine_2d_compact`` in groups of up to
    ``_COMPACT_QUEUE`` chunks: ``pair_chunk`` slots refine while the rest
    of the group waits device-resident in the pending queue, so a slot
    whose pair converges is backfilled the same round instead of idling
    until the chunk's slowest pair finishes. The capacity ladder escalates
    *per pair*: only pairs whose guard bound re-queue one rung up.
    Per-column presorts are shared (``_column_ranks``) and each group's
    metadata runs as one batched launch.

    Results are bit-for-bit equal to ``build_pairs_sequential``: every
    pair's refinement is the same deterministic fixed-point iteration
    whatever the slot count, queue order, drain timing or ``occupancy_min``
    re-bucketing (asserted in tests/test_build_compact.py). Returns
    {(a, b): PairHist} without fold maps; records launch shapes and
    occupancy telemetry into ``stats``. When a ``timeline`` is passed,
    every device relaunch becomes a ``compact_launch`` interval carrying
    its drained/escalated/resumed counters plus ``rung_escalation`` and
    ``occupancy_rebucket`` markers — the per-round schedule ledger as an
    event stream instead of summed scalars — the host presorts become
    ``pair_presort`` intervals, and each rung's metadata launch, through
    its transfer back to the host, a ``pair_metadata`` interval.
    """
    K2 = params.k2_cap
    n_s, d = sample.shape
    keys = _pair_keys(d)
    sample_nn = np.nan_to_num(sample, nan=0.0)
    nanmask = np.isnan(sample)
    t_sort = time.perf_counter() if timeline is not None else 0.0
    ranks = _column_ranks(sample_nn)
    if timeline is not None:
        timeline.add("pair_presort", t_sort, time.perf_counter(), d=d)
    slots = _pow2_floor(int(params.pair_chunk))
    group_cap = slots * _COMPACT_QUEUE
    occupancy = float(params.occupancy_min)
    launches = []
    comp = {"loop_rounds": 0, "pair_rounds": 0, "slot_rounds": 0,
            "relaunches": 0, "escalated_pairs": 0, "occupancy_hist": {}}
    raw_pairs = {}

    for start in range(0, len(keys), group_cap):
        part = keys[start:start + group_cap]
        g = len(part)
        t_sort = time.perf_counter() if timeline is not None else 0.0
        x = np.empty((g, n_s), np.float64)
        y = np.empty((g, n_s), np.float64)
        valid = np.empty((g, n_s), bool)
        rx = np.empty((g, n_s), np.int64)
        ry = np.empty((g, n_s), np.int64)
        kx0g = np.ones(g, np.int32)
        ky0g = np.ones(g, np.int32)
        for p, (a, b) in enumerate(part):
            x[p] = sample_nn[:, a]
            y[p] = sample_nn[:, b]
            valid[p] = ~(nanmask[:, a] | nanmask[:, b])
            rx[p], ry[p] = ranks[a], ranks[b]
            kx0g[p] = min(int(hists[a].k), K2)
            ky0g[p] = min(int(hists[b].k), K2)
        pres = _presort_pairs_host(x, y, valid, rx, ry)
        if timeline is not None:
            timeline.add("pair_presort", t_sort, time.perf_counter(),
                         pairs=g)

        # Per-pair capacity rungs: each pair starts at the smallest ladder
        # rung that fits ITS initial grids, and capacity-guard escalation
        # re-queues only the capped pairs one rung up.
        ladder = _cap_ladder(2, K2, params.k2_start)
        queue: dict[int, list] = {}
        for gid in range(g):
            need = max(int(kx0g[gid]), int(ky0g[gid]))
            cap = next(c for c in ladder if c >= need or c == K2)
            queue.setdefault(cap, []).append(gid)
        final: dict[int, tuple] = {}  # gid -> (cap, ex, ey, kx, ky)
        for rung_i, cap in enumerate(ladder):
            pend = queue.pop(cap, [])
            if not pend:
                continue
            drain_capped = cap < K2
            # (gid, resume-state | None): fresh pairs start from their 1-D
            # grids; resumed pairs (occupancy_min re-buckets) continue their
            # partial refinement exactly where the previous launch left it.
            entries = [(gid, None) for gid in pend]
            first_launch = True
            while entries:
                size = _pow2_ceil(len(entries))
                s_eff = min(slots, size)
                idx = [gid for gid, _ in entries]
                idx += [idx[0]] * (size - len(idx))
                data = tuple(jnp.asarray(arr[idx]) for arr in pres)
                ex0 = np.full((size, cap + 1), np.inf, np.float64)
                ey0 = np.full((size, cap + 1), np.inf, np.float64)
                ex0[:, :2] = 0.0
                ey0[:, :2] = 0.0  # pad lanes: one empty bin, never fed
                kx0 = np.ones(size, np.int32)
                ky0 = np.ones(size, np.int32)
                rounds0 = np.zeros(size, np.int32)
                capped0 = np.zeros(size, bool)
                for p, (gid, st) in enumerate(entries):
                    a, b = part[gid]
                    if st is None:
                        ex0[p] = _pad_edges(hists[a].edges, cap)
                        ey0[p] = _pad_edges(hists[b].edges, cap)
                        kx0[p], ky0[p] = kx0g[gid], ky0g[gid]
                    else:
                        (ex0[p], ey0[p], kx0[p], ky0[p], rounds0[p],
                         capped0[p]) = st
                t_launch = time.perf_counter()
                out = refine.refine_2d_compact(
                    *data, jnp.asarray(ex0), jnp.asarray(ey0),
                    jnp.asarray(kx0), jnp.asarray(ky0),
                    jnp.asarray(rounds0), jnp.asarray(capped0),
                    jnp.int32(len(entries)), jnp.float64(m_pts), crit2,
                    jnp.float64(occupancy), n_slots=s_eff, k2=cap,
                    s_max=params.s2_max, max_rounds=params.max_rounds_2d,
                    drain_capped=drain_capped, use_pallas=params.use_pallas)
                host = jax.device_get(out)  # ONE grouped transfer
                (oex, oey, okx, oky, ocap, _ornd, odone, spair, sact,
                 sex, sey, skx, sky, scap, srnd, occ_hist, loop_rounds,
                 act_rounds) = host
                launches.append((s_eff, cap))
                comp["loop_rounds"] += int(loop_rounds)
                comp["pair_rounds"] += int(act_rounds)
                comp["slot_rounds"] += int(loop_rounds) * s_eff
                comp["relaunches"] += 0 if first_launch else 1
                for n_act, n_r in enumerate(occ_hist):
                    if n_r:
                        comp["occupancy_hist"][n_act] = \
                            comp["occupancy_hist"].get(n_act, 0) + int(n_r)
                escalated = 0
                for p, (gid, _) in enumerate(entries):
                    if not odone[p]:
                        continue  # still active in a slot: resumes below
                    if drain_capped and ocap[p]:
                        # Discard; re-queue one rung up (ladder[rung_i + 1]
                        # exists whenever drain_capped).
                        queue.setdefault(ladder[rung_i + 1], []).append(gid)
                        escalated += 1
                    else:
                        final[gid] = (cap, oex[p], oey[p], int(okx[p]),
                                      int(oky[p]))
                comp["escalated_pairs"] += escalated
                n_before = len(entries)
                entries = [
                    (entries[int(spair[s_i])][0],
                     (sex[s_i], sey[s_i], int(skx[s_i]), int(sky[s_i]),
                      int(srnd[s_i]), bool(scap[s_i])))
                    for s_i in range(s_eff) if sact[s_i]]
                if timeline is not None:
                    timeline.add(
                        "compact_launch", t_launch, time.perf_counter(),
                        cap=cap, slots=s_eff, pairs=n_before,
                        loop_rounds=int(loop_rounds),
                        pair_rounds=int(act_rounds),
                        drained=n_before - len(entries),
                        escalated=escalated, resumed=len(entries),
                        relaunch=not first_launch)
                    if escalated:
                        timeline.event("rung_escalation", from_cap=cap,
                                       to_cap=ladder[min(rung_i + 1,
                                                         len(ladder) - 1)],
                                       pairs=escalated)
                    if entries:
                        timeline.event("occupancy_rebucket",
                                       resumed=len(entries), cap=cap)
                first_launch = False

        # Metadata per rung (pairs that finished at the same capacity share
        # a bucketed launch; trim is capacity-independent).
        by_cap: dict[int, list] = {}
        for gid, (cap, *_rest) in final.items():
            by_cap.setdefault(cap, []).append(gid)
        for cap, gids in sorted(by_cap.items()):
            size = _pow2_ceil(len(gids))
            idx = gids + [gids[0]] * (size - len(gids))
            data = tuple(jnp.asarray(arr[idx]) for arr in pres)
            ex_m = np.full((size, cap + 1), np.inf, np.float64)
            ey_m = np.full((size, cap + 1), np.inf, np.float64)
            ex_m[:, :2] = 0.0
            ey_m[:, :2] = 0.0
            kx_m = np.ones(size, np.int32)
            ky_m = np.ones(size, np.int32)
            for p, gid in enumerate(gids):
                _c, fex, fey, fkx, fky = final[gid]
                ex_m[p, : fex.size] = fex
                ey_m[p, : fey.size] = fey
                kx_m[p], ky_m[p] = fkx, fky
            t_meta = time.perf_counter() if timeline is not None else 0.0
            meta = refine.pair_metadata_batch(
                *data, jnp.asarray(ex_m), jnp.asarray(ey_m),
                jnp.asarray(kx_m), jnp.asarray(ky_m), k2=cap,
                use_pallas=params.use_pallas)
            meta_h = jax.device_get(meta)
            if timeline is not None:
                timeline.add("pair_metadata", t_meta, time.perf_counter(),
                             cap=cap, pairs=len(gids))
            for p, gid in enumerate(gids):
                a, b = part[gid]
                raw_pairs[(a, b)] = _trim_pair(
                    ex_m[p], ey_m[p], kx_m[p], ky_m[p],
                    *(v[p] for v in meta_h))
    if stats is not None:
        stats["mode"] = "compact"
        stats["pair_launches"] = launches
        stats["compaction"] = comp
    return raw_pairs


def build_pairwise_hist(
    data: np.ndarray,
    columns: list[ColumnInfo],
    params: BuildParams | None = None,
    n_rows_full: int | None = None,
    seed_edges: list | None = None,
) -> PairwiseHist:
    """Construct the synopsis from a pre-processed (N, d) float64 matrix.

    ``data`` is in the *pre-processed* (GD) domain: non-negative integers as
    f64, NaN for missing — or a ``CompressedTable``, in which case only the
    N_s sampled rows are decoded (``decompress_rows``) and, with
    ``params.seed_from_bases``, the 1-D edges are seeded from the
    deduplicated bases (§3); the full raw matrix is never materialized.
    Because sampling draws row *indices* from ``params.seed`` and the decode
    is bit-exact, the compressed-input build is bit-for-bit identical to the
    raw build with ``GreedyGD.seed_edges`` passed in. ``seed_edges``
    (optional) are per-column initial edge candidates — typically
    reconstructed GreedyGD bases (§3). ``n_rows_full`` is N of the complete
    dataset when ``data`` is itself already a sample of something larger
    (IDEBench-style scale-up).

    The input ``columns`` list is left untouched; the returned synopsis
    carries copies with per-column null counts filled in.
    """
    return _build_pairwise_hist(data, columns, params, n_rows_full,
                                seed_edges, build_pairs_compact)


def _build_pairwise_hist(data, columns, params, n_rows_full, seed_edges,
                         build_pairs) -> PairwiseHist:
    """``build_pairwise_hist`` with its pair phase run by ``build_pairs``,
    which takes ``build_pairs_compact``'s arguments: that builder when
    served, the ``build_pairs_sequential`` oracle wrapped to fit in tests."""
    params = params or BuildParams()
    ct = data if isinstance(data, CompressedTable) else None
    if ct is not None:
        n_input = ct.n_rows
        d = ct.d
        if seed_edges is None and params.seed_from_bases:
            seed_edges = GreedyGD.seed_edges(ct)
    else:
        data = np.asarray(data, np.float64)
        n_input = int(data.shape[0])
        d = data.shape[1]
    n_total = n_input if n_rows_full is None else int(n_rows_full)
    if len(columns) != d:
        raise ValueError("columns metadata must match data width")
    # The timeline is always-on: construction is host-orchestrated with a
    # handful of device launches, so recording costs a few dict appends
    # against seconds of build — not worth a knob.
    timeline = BuildTimeline()

    # --- 1. sample ---------------------------------------------------------
    with timeline.phase("sample", n_rows=n_input, d=d):
        n_s = min(params.n_samples, n_input)
        if n_s < n_input:
            rng = np.random.default_rng(params.seed)
            rows = rng.choice(n_input, size=n_s, replace=False)
        else:
            rows = None
        if ct is not None:
            sample = decompress_rows(ct, rows)
        else:
            sample = data if rows is None else data[rows]
        m_pts = max(2, int(round(params.m_frac * n_s)))
        n_take = max(2, math.ceil(n_s / m_pts))
        s_max = max(params.s1_max, params.s2_max)
        crit_np = chi2lib.build_crit_table(params.alpha, s_max)
        crit = jnp.asarray(crit_np)
        crit1 = crit[: params.s1_max + 1]
        crit2 = crit[: params.s2_max + 1]

    # --- 2. one-dimensional histograms (vmapped across columns) ------------
    K1 = params.k1_cap
    with timeline.phase("refine_1d", d=d):
        xs_all, up_all, nv_all, vmin_all, vmax_all = _prep_columns(sample)
        columns = [dataclasses.replace(c, n_null=int(n_s - nv_all[i]))
                   for i, c in enumerate(columns)]
        e0_all = np.empty((d, K1 + 1), np.float64)
        n0_all = np.empty((d,), np.int32)
        mu_all = np.array([c.mu for c in columns], np.float64)
        for i in range(d):
            seed = None if seed_edges is None else seed_edges[i]
            if columns[i].kind == "categorical" and \
                    0 < len(columns[i].categories) <= max(n_take, 4):
                # One bin per category: categorical codes with near-equal
                # frequencies look "uniform" to the chi-squared test and would
                # otherwise never split, destroying groupwise discrimination.
                # (GD-bases seeding achieves the same: each category is a
                # base.) Half-integer edges isolate every code incl. the
                # last two.
                seed = np.arange(len(columns[i].categories) - 1) + 0.5
            e0_all[i], n0_all[i] = _init_edges(vmin_all[i], vmax_all[i], K1,
                                               n_take, seed)

        refine_v = jax.vmap(
            lambda xs, up, e0, n0: refine.refine_1d(
                xs, up, e0, n0, jnp.float64(m_pts), crit1,
                s_max=params.s1_max, max_rounds=params.max_rounds_1d))
        edges_j, k_j = refine_v(jnp.asarray(xs_all), jnp.asarray(up_all),
                                jnp.asarray(e0_all), jnp.asarray(n0_all))

        meta_v = jax.vmap(
            lambda xs, up, e, k, mu: refine.metadata_1d(
                xs, up, e, k, jnp.float64(m_pts), crit1, mu,
                s_max=params.s1_max))
        h_j, u_j, vmin_j, vmax_j, c_j, cm_j, cp_j = meta_v(
            jnp.asarray(xs_all), jnp.asarray(up_all), edges_j, k_j,
            jnp.asarray(mu_all))

        edges_np = np.asarray(edges_j)
        k_np = np.asarray(k_j)
        hists: list[Hist1D] = []
        for i in range(d):
            k = int(k_np[i])
            hists.append(Hist1D(
                edges=edges_np[i, : k + 1].copy(),
                k=np.int32(k),
                h=np.asarray(h_j)[i, :k].copy(),
                u=np.asarray(u_j)[i, :k].copy(),
                vmin=np.asarray(vmin_j)[i, :k].copy(),
                vmax=np.asarray(vmax_j)[i, :k].copy(),
                c=np.asarray(c_j)[i, :k].copy(),
                cminus=np.asarray(cm_j)[i, :k].copy(),
                cplus=np.asarray(cp_j)[i, :k].copy(),
            ))

    # --- 3. pair histograms (batched across pairs) -------------------------
    t_pairs = time.perf_counter()
    build_stats: dict = {}
    with timeline.phase("pair_phase"):
        raw_pairs = build_pairs(sample, hists, params, crit2, m_pts,
                                stats=build_stats, timeline=timeline)
    build_stats.update({
        "n_pairs": len(raw_pairs),
        "pair_phase_s": time.perf_counter() - t_pairs,
        "pair_chunk": params.pair_chunk,
        "from_compressed": ct is not None,
    })
    if ct is not None:
        build_stats["rows_decoded"] = int(n_s)

    # --- 4. refine 1-D grids to the union of their pairs' edge sets --------
    # Aggregation runs on the 1-D grid (Table 3); without this, a uniform
    # aggregation column would collapse to one bin and every conditional
    # AVG/SUM would see only the global midpoint. The union grid preserves
    # the 2-D refinement (this is what the paper's per-dimension 2-D bin
    # metadata, Fig. 4, buys). Fold maps: 1-D bin -> containing pair row.
    pairs: dict[tuple[int, int], PairHist] = {}
    t_regrid = time.perf_counter()
    for i in range(d):
        union = [hists[i].edges]
        for (a, b), pr in raw_pairs.items():
            if a == i:
                union.append(pr.ex)
            elif b == i:
                union.append(pr.ey)
        edges_u = np.unique(np.concatenate(union))
        edges_u = edges_u[np.isfinite(edges_u)]
        if edges_u.size == 1:  # constant column: keep its zero-width bin
            edges_u = np.repeat(edges_u, 2)
        if edges_u.size > K1 + 1:  # capacity: thin uniformly, keep extremes
            idx = np.linspace(0, edges_u.size - 1, K1 + 1).round().astype(int)
            edges_u = edges_u[np.unique(idx)]
        e_pad = np.full(K1 + 1, np.inf)
        e_pad[: edges_u.size] = edges_u
        k_u = edges_u.size - 1
        h_u, u_u, vmin_u, vmax_u, c_u, cm_u, cp_u = refine.metadata_1d(
            jnp.asarray(xs_all[i]), jnp.asarray(up_all[i]),
            jnp.asarray(e_pad), jnp.int32(k_u), jnp.float64(m_pts), crit1,
            jnp.float64(mu_all[i]), s_max=params.s1_max)
        hists[i] = Hist1D(
            edges=edges_u.copy(), k=np.int32(k_u),
            h=np.asarray(h_u)[:k_u].copy(), u=np.asarray(u_u)[:k_u].copy(),
            vmin=np.asarray(vmin_u)[:k_u].copy(),
            vmax=np.asarray(vmax_u)[:k_u].copy(),
            c=np.asarray(c_u)[:k_u].copy(),
            cminus=np.asarray(cm_u)[:k_u].copy(),
            cplus=np.asarray(cp_u)[:k_u].copy())

    timeline.add("union_regrid", t_regrid, time.perf_counter(), d=d)

    with timeline.phase("folds", n_pairs=len(raw_pairs)):
        for (a, b), pr in raw_pairs.items():
            pairs[(a, b)] = pr._replace(
                fold_x=fold_to_rows(hists[a].edges, pr.ex),
                fold_y=fold_to_rows(hists[b].edges, pr.ey))

    build_stats["timeline"] = timeline.events
    build_stats["phase_s"] = timeline.summary()

    return PairwiseHist(
        params=params,
        n_rows=n_total,
        n_sampled=n_s,
        columns=columns,
        hists=hists,
        pairs=pairs,
        chi2_table=crit_np,
        build_stats=build_stats,
    )
