"""Fused JAX/Pallas query fast path (beyond-paper optimization, §Perf).

The paper's execution model runs ~3 small ops per predicate (mat-vec, fold,
divide) plus a combine — at sub-ms latencies the launch/dispatch overhead
dominates. ``FastPath.batch`` stacks the AND-ed pair predicates of a group
of queries sharing a plan shape (same agg column, same pair-predicate
column set) and executes ONE fused kernel launch covering every query and
all three bound variants (estimate / lower / upper). Its caller is
``repro.serve.aqp.scheduler.BatchScheduler``, which hands each query's
triple to ``QueryEngine.execute_plan(plan, weightings=...)``.

Supported: AND trees of leaves (the dominant template in the paper's
workload). OR / nested trees return None -> the engine answers on the NumPy
reference path (repro.core.weightings), which is also the oracle in tests.
"""
from __future__ import annotations

import time

import jax
import numpy as np

from repro.core import coverage as covlib
from repro.core import weightings as wlib
from repro.kernels.weightings import batched_weightings
from repro.obs.trace import NOOP_SPAN

Z_98 = wlib.Z_98


def _slice_beta(ph, leaf, h, u, vmin, vmax, mu):
    if isinstance(leaf, wlib.Consolidated):
        beta = covlib.coverage_intervals(leaf.intervals, h, u, vmin, vmax, mu)
    else:
        beta = covlib.coverage_single(leaf.op, leaf.value, h, u, vmin, vmax)
    blo, bhi = covlib.coverage_bounds(
        beta, h, u, ph.params.min_points, ph.chi2_table, ph.params.s1_max)
    return beta, blo, bhi


def _leaf_key(leaf):
    """The inputs of a leaf's coverage triple on a slice of its column:
    leaves with equal keys get the same triple, bit for bit. Literals key
    by ``float.hex``, exact to the bit, so 0.0 and -0.0 stay apart."""
    if isinstance(leaf, wlib.Consolidated):
        return (leaf.col, tuple((float(lo).hex(), float(hi).hex())
                                for lo, hi in leaf.intervals))
    return (leaf.col, leaf.op, float(leaf.value).hex())


class _SliceMemo:
    """One fused launch's same-column triples by leaf, and its tally:
    ``slices`` the launch needs (one per plan per leaf) against those
    ``computed``. Lives for one ``FastPath.batch`` call."""

    def __init__(self):
        self._same_col = {}
        self.slices = 0
        self.computed = 0

    def tally(self) -> dict:
        return {"slices": self.slices, "computed": self.computed}

    def once(self, leaf, evaluate):
        """``evaluate(leaf)``, evaluated once per distinct same-column
        leaf of the launch."""
        key = _leaf_key(leaf)
        probs = self._same_col.get(key)
        if probs is None:
            probs = self._same_col[key] = evaluate(leaf)
            self.computed += 1
        return probs


def _round_up(x: int, mult: int = 128) -> int:
    return ((x + mult - 1) // mult) * mult


def _widen_clip(w, wlo, whi, ph, h, corrected):
    """Eq. 29 sampling widening + monotone clipping (same as the reference
    path). Broadcasts over leading batch dimensions: w/wlo/whi are (..., K1),
    h is (K1,)."""
    rho = ph.rho
    if rho < 1.0:
        fpc = (ph.n_rows - ph.n_sampled) / max(ph.n_rows - 1, 1)
        blo = np.divide(wlo, h, out=np.zeros_like(wlo), where=h > 0)
        bhi = np.divide(whi, h, out=np.zeros_like(whi), where=h > 0)
        var_lo = blo * (1.0 - blo) * fpc
        var_hi = bhi * (1.0 - bhi) * fpc
        if corrected:
            var_lo, var_hi = var_lo * h, var_hi * h
        wlo = wlo - Z_98 * np.sqrt(np.maximum(var_lo, 0.0))
        whi = whi + Z_98 * np.sqrt(np.maximum(var_hi, 0.0))
    wlo = np.clip(wlo, 0.0, w)
    whi = np.clip(whi, w, h)
    return w, wlo, whi


class FastPath:
    """Scheduler hook: (ph, agg_col, trees, corrected) -> one weightings
    triple per tree, from one fused launch.

    The padded (H, fold) stacks depend only on (agg column, predicate
    columns), NOT on the query literals — they are device-resident constants
    of the synopsis. We cache them per column set (on TPU they'd simply stay
    in HBM/VMEM); per query only the tiny beta vectors are assembled.

    ``tracer`` (an optional ``repro.obs.trace.Tracer``): when enabled,
    ``batch`` records its three parts on the "worker" lane — ``betas``,
    ``launch`` (with the launch's logical shapes) and ``widen``.

    ``n_slices`` / ``n_computed`` count, over every launch ``batch`` made,
    the leaf coverage slices the launches needed and those evaluated: a
    leaf repeated across a launch's plans (a GROUP BY's shared brush) is
    evaluated once. ``batch`` runs on the scheduler's one worker thread.
    """

    def __init__(self, use_pallas: bool = True, tracer=None):
        self.use_pallas = use_pallas
        self.tracer = tracer
        # Pallas interprets off the TPU; asked once here, not per launch.
        self.interpret = use_pallas and jax.default_backend() != "tpu"
        self.n_slices = 0
        self.n_computed = 0

    # ----------------------------------------------------------- shared stacks

    def _get_stack(self, ph, agg_col, pred_cols):
        # The stack cache lives ON the synopsis object: its lifetime is
        # exactly the synopsis's (a rebuild produces a new PairwiseHist, so
        # stale stacks can never be served and the old device arrays are
        # garbage-collected with the old synopsis). Keying an external dict
        # on id(ph) would leak per rebuild and could alias a recycled id.
        cache = getattr(ph, "_fastpath_stacks", None)
        if cache is None:
            cache = {}
            ph._fastpath_stacks = cache
        key = (agg_col, pred_cols)
        if key in cache:
            return cache[key]
        hist = ph.hists[agg_col]
        k1 = int(hist.k)
        prs = [ph.pair(agg_col, j) for j in pred_cols]
        k2max = _round_up(max(max(p.H.shape) for p in prs))
        k1p = _round_up(k1)
        el = len(prs)
        hpad = np.zeros((el, k2max, k2max), np.float32)
        hxpad = np.zeros((el, k2max), np.float32)
        fpad = np.zeros((el, k1p, k2max), np.float32)
        for li, pr in enumerate(prs):
            hpad[li, :pr.H.shape[0], :pr.H.shape[1]] = pr.H
            # per-row denominator = 1-D mass inside the row (incl. j-NULLs)
            denom = np.zeros(int(pr.kx))
            np.add.at(denom, pr.fold_x, hist.h)
            hxpad[li, :pr.H.shape[0]] = denom
            fpad[li, np.arange(k1), np.asarray(pr.fold_x)] = 1.0
        import jax.numpy as jnp
        entry = (jnp.asarray(hpad), jnp.asarray(fpad), jnp.asarray(hxpad),
                 k1, k2max)
        cache[key] = entry
        return entry

    def _split_leaves(self, ph, agg_col, tree, memo=None):
        """Pure-AND tree -> (same-col beta triples, pair leaves) or None.

        With a ``_SliceMemo`` (one launch's), a same-column leaf already
        met in the launch reuses its clipped triple, and the memo tallies
        the tree's slices."""
        leaves = wlib.flat_and_leaves(tree)
        if leaves is None:
            return None
        hist = ph.hists[agg_col]
        same_col = [[], [], []]   # per variant: (k1,) probs for j == agg_col
        pair_leaves = []

        def clipped(leaf):
            triple = _slice_beta(ph, leaf, hist.h, hist.u, hist.vmin,
                                 hist.vmax, ph.columns[leaf.col].mu)
            return [np.clip(b, 0.0, 1.0) for b in triple]

        for leaf in leaves:
            if leaf.col == agg_col:
                probs = (clipped(leaf) if memo is None
                         else memo.once(leaf, clipped))
                for idx in range(3):
                    same_col[idx].append(probs[idx])
            else:
                pair_leaves.append(leaf)
        if memo is not None:
            memo.slices += len(leaves)
        # Canonical (sorted-column) leaf order: queries then share one
        # cached stack per column set regardless of the order predicates
        # appeared in the WHERE clause.
        pair_leaves.sort(key=lambda lf: lf.col)
        return same_col, pair_leaves

    def _pair_betas_batch(self, ph, agg_col, leaf_lists, k2max, memo=None):
        """(B, 3, L, K2max) coverage stack for B same-shape queries.

        Vectorized per-leaf beta assembly: the B leaves on pair column
        ``li`` share the slice metadata (h, u, v-, v+), so simple-op leaves
        stack their literals into ONE broadcasted ``coverage_single`` +
        ``coverage_bounds`` evaluation per (column, operator) group, in
        place of one Python call per query per leaf.
        Consolidated (interval-set) leaves are evaluated once per distinct
        interval set on the column, and their rows copied to every query
        that carries the same set (a GROUP BY's leaves share the brush).
        Bit-for-bit equal to evaluating each query's leaves one at a time
        (same elementwise arithmetic, broadcast over a leading batch axis).
        ``memo`` (a ``_SliceMemo``) tallies the slices evaluated.
        """
        nq = len(leaf_lists)
        el = len(leaf_lists[0])
        betas = np.zeros((nq, 3, el, k2max), np.float32)
        computed = 0
        for li in range(el):
            leaves = [pls[li] for pls in leaf_lists]
            col = leaves[0].col
            pr = ph.pair(agg_col, col)
            h, u = pr.hy, pr.uy
            vmin, vmax = pr.vminy, pr.vmaxy
            k = len(np.asarray(h))
            mu = ph.columns[col].mu
            by_op: dict[str, list] = {}
            first: dict = {}        # interval set -> its first query's row
            dst, src = [], []
            for qi, leaf in enumerate(leaves):
                if isinstance(leaf, wlib.Consolidated):
                    row = first.setdefault(_leaf_key(leaf), qi)
                    if row != qi:
                        dst.append(qi)
                        src.append(row)
                        continue
                    triple = _slice_beta(ph, leaf, h, u, vmin, vmax, mu)
                    for idx in range(3):
                        betas[qi, idx, li, :k] = triple[idx]
                    computed += 1
                else:
                    by_op.setdefault(leaf.op, []).append(qi)
            if dst:
                betas[dst, :, li] = betas[src, :, li]
            for op, qis in by_op.items():
                values = np.array([[leaves[qi].value] for qi in qis],
                                  float)                       # (Bg, 1)
                beta = covlib.coverage_single(op, values, h, u, vmin, vmax)
                blo, bhi = covlib.coverage_bounds(
                    beta, h, u, ph.params.min_points, ph.chi2_table,
                    ph.params.s1_max)
                rows = np.asarray(qis)
                for idx, arr in enumerate((beta, blo, bhi)):
                    betas[rows, idx, li, :k] = arr
                computed += len(qis)
        if memo is not None:
            memo.computed += computed
        return betas

    # ------------------------------------------------------------- query batch

    def batch(self, ph, agg_col, trees, corrected):
        """One fused launch for B same-shape queries (x3 bound variants).

        Every tree must be a pure AND with an identical pair-predicate column
        *set* (same-column leaves are free to differ — they apply as
        elementwise products outside the kernel). Returns a list of
        (w, wlo, whi) triples aligned with ``trees``, or None if any tree is
        ineligible (caller falls back to per-query execution).

        Each distinct leaf's coverage triple is evaluated once per call and
        placed in every plan that carries the leaf; ``n_slices`` and
        ``n_computed`` grow by the call's tally.

        Traced, it records three spans that tile the call: ``betas``
        (leaf split, stack fetch, beta assembly; attrs ``slices`` and
        ``computed``, the call's tally), ``launch`` (the kernel
        call through the copy back to the host: one dispatch, which also
        stages the betas, and one copy of the padded result, the only wait
        for the device; attrs ``queries``, ``variants``, ``k1``, ``k2max``
        and ``pairs``, each pair's ``H`` shape) and ``widen`` (per-query
        products and bound widening). With ``annotate_jax`` a
        ``jax.profiler.TraceAnnotation`` named ``aqp.fused:<col>`` covers
        ``launch``.
        """
        tracer = self.tracer
        tracing = tracer is not None and tracer.enabled
        t0 = time.perf_counter() if tracing else 0.0
        memo = _SliceMemo()
        splits = []
        pair_cols = None
        for tree in trees:
            split = self._split_leaves(ph, agg_col, tree, memo)
            if split is None:
                return None
            same_col, pair_leaves = split
            cols = tuple(lf.col for lf in pair_leaves)   # already sorted
            if len(set(cols)) != len(cols):
                return None  # duplicate pair col: un-consolidated shape
            if pair_cols is None:
                pair_cols = cols
            elif cols != pair_cols:
                return None
            splits.append((same_col, pair_leaves))

        hist = ph.hists[agg_col]
        h = np.asarray(hist.h, np.float64)
        nq = len(splits)

        if pair_cols:
            hpad, fpad, hxpad, k1c, k2max = self._get_stack(
                ph, agg_col, pair_cols)
            betas = self._pair_betas_batch(
                ph, agg_col, [pls for _, pls in splits], k2max,
                memo)                                           # (B,3,L,K2)
            flat = betas.reshape(nq * 3, len(pair_cols), k2max)
            t1 = time.perf_counter() if tracing else 0.0
            annotation = NOOP_SPAN
            if tracing and tracer.annotate_jax:
                import jax.profiler
                annotation = jax.profiler.TraceAnnotation(
                    f"aqp.fused:{agg_col}")
            with annotation:
                prob1 = batched_weightings(
                    hpad, flat, fpad, hxpad, use_pallas=self.use_pallas,
                    interpret=self.interpret)[:, :k1c]
            prob1 = prob1.reshape(nq, 3, k1c)               # (B, 3, K1)
            if tracing:
                t2 = time.perf_counter()
                tracer.add("betas", t0, t1, track="worker",
                           attrs=memo.tally())
                tracer.add("launch", t1, t2, track="worker", attrs={
                    "queries": nq, "variants": 3, "k1": k1c,
                    "k2max": k2max,
                    "pairs": [tuple(int(n) for n in ph.pair(agg_col, j)
                                    .H.shape) for j in pair_cols]})
        else:
            prob1 = np.ones((nq, 3, int(hist.k)))
            if tracing:               # no pair predicate: nothing to launch
                t2 = time.perf_counter()
                tracer.add("betas", t0, t2, track="worker",
                           attrs=memo.tally())

        self.n_slices += memo.slices
        self.n_computed += memo.computed
        out = []
        for qi, (same_col, _) in enumerate(splits):
            triple = []
            for idx in range(3):
                w = h * np.asarray(prob1[qi, idx], np.float64)
                for prob in same_col[idx]:
                    w = w * prob
                triple.append(w)
            out.append(_widen_clip(*triple, ph, h, corrected))
        if tracing:
            tracer.add("widen", t2, time.perf_counter(), track="worker")
        return out

