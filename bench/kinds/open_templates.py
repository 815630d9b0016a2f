"""Open-loop dashboard traffic over fixed statement templates.

Parameters (``bench/traffic/<name>.json``):

    rate_per_s         statements per second, Poisson arrivals
    templates          [{"func", "agg", "preds": [[col, op], ...]}, ...]
    zipf_s             template popularity ~ 1 / rank^s, in file order
    literal_quantiles  [lo, hi]: each literal is a fresh quantile in range;
                       no statement text repeats within a run
    min_sample_rows    a statement must select this many sample rows

Every seed gets the same number of statements and the same count of each
template (the rounded Zipf shares), in its own order and arrival times, so
seeds change which literals and when, not how much work.
"""
from __future__ import annotations

import math

import numpy as np

from bench.statements import Stmt, conj


def shares(n: int, weights: np.ndarray) -> np.ndarray:
    """``n`` split by ``weights`` into whole counts (largest remainder)."""
    raw = n * weights / weights.sum()
    counts = np.floor(raw).astype(int)
    rest = n - counts.sum()
    counts[np.argsort(-(raw - counts), kind="stable")[:rest]] += 1
    return counts


def arrivals(n: int, seconds: float, rng) -> np.ndarray:
    """``n`` Poisson arrivals conditioned to fall inside ``[0, seconds)``."""
    cum = np.cumsum(rng.exponential(size=n + 1))
    return cum[:n] / cum[n] * seconds


def zipf(k: int, s: float) -> np.ndarray:
    return 1.0 / np.arange(1, k + 1) ** s


def statement(tpl: dict, lits) -> Stmt:
    return Stmt(tpl["func"], tpl["agg"],
                conj(*[(c, op, float(v))
                       for (c, op), v in zip(tpl["preds"], lits)]))


def distinct(traffic: dict, preds, m: int, sample, rng,
             rounds: int = 32) -> np.ndarray:
    """``m`` distinct vetted literal vectors, in random order: no two
    statements of a template are the same text, so none is answered from
    the result cache."""
    q_lo, q_hi = traffic["literal_quantiles"]
    have = np.empty((0, len(preds)))
    for _ in range(rounds):
        if len(have) >= m:
            break
        more = sample.vetted(preds, 2 * (m - len(have)), rng, q_lo, q_hi,
                             traffic["min_sample_rows"])
        have = np.unique(np.concatenate([have, more]), axis=0)
    if len(have) < m:
        raise ValueError(f"fewer than {m} distinct statements over {preds}")
    return rng.permutation(have)[:m]


def schedule(traffic: dict, sample, rng, seconds: float) -> list:
    tpls = traffic["templates"]
    n = int(round(traffic["rate_per_s"] * seconds))
    counts = shares(n, zipf(len(tpls), traffic["zipf_s"]))
    pools = [iter(distinct(traffic, t["preds"], int(c), sample, rng))
             for t, c in zip(tpls, counts)]
    order = rng.permutation(np.repeat(np.arange(len(tpls)), counts))
    due = arrivals(n, seconds, rng)
    return [(float(d), statement(tpls[t], next(pools[t])))
            for d, t in zip(due, order)]


def warmup(traffic: dict, sample, rng, wave: int, avoid: set) -> list:
    """For each template, a group of statements at every size from 2 to
    the most that a wave of ``wave`` window statements holds of it: its
    share of the mix, as a binomial draw, six standard deviations up. Every
    statement is vetted like the window's, and none is in ``avoid``."""
    tpls = traffic["templates"]
    share = zipf(len(tpls), traffic["zipf_s"])
    share = share / share.sum()
    groups = []
    for tpl, p in zip(tpls, share):
        most = min(wave, math.ceil(wave * p
                                   + 6 * math.sqrt(wave * p * (1 - p))))
        sizes = range(2, most + 1)
        need = sum(sizes)
        stmts = [st for st in (statement(tpl, row) for row in distinct(
                     traffic, tpl["preds"], 2 * need, sample, rng))
                 if st not in avoid]
        if len(stmts) < need:
            raise ValueError(f"too few warm-up statements for {tpl}")
        at = 0
        for size in sizes:
            groups.append(stmts[at:at + size])
            at += size
    return groups
