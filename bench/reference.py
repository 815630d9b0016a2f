"""The plain reference: exact answers over the whole generated table.

Evaluates the benchmark's structured statements (``statements.Stmt``)
directly with NumPy in float64, under SQL NULL semantics: a comparison with
NULL (NaN) is false, aggregates skip NULL, ``COUNT(col)`` counts non-NULL
values and ``COUNT(*)`` counts rows. A GROUP BY answer maps each category
that has a non-NULL aggregate (and, for COUNT, a positive one) to it.

It imports nothing of the program and takes nothing the program made: only
the table the benchmark generated from its seed.
"""
from __future__ import annotations

import concurrent.futures

import numpy as np

from bench.statements import columns_of


def relative_error(est, exact) -> float:
    """The paper's relative error in percent (as ``aqp/queries.py`` defines
    it): 100 for a missing answer, 0 or 100 where the exact answer is 0."""
    if est is None or exact is None:
        return 100.0
    if exact == 0:
        return 0.0 if abs(est) < 1e-9 else 100.0
    return abs(est - exact) / abs(exact) * 100.0


class ExactTable:
    """A generated table held for exact evaluation."""

    def __init__(self, table: dict):
        self.n = len(next(iter(table.values())))
        self.num: dict[str, np.ndarray] = {}
        self.finite: dict[str, np.ndarray] = {}
        self.has_null: dict[str, bool] = {}
        self.text: dict[str, np.ndarray] = {}    # categorical columns
        self._coded: dict[str, tuple] = {}
        for name, col in table.items():
            arr = np.asarray(col)
            if arr.dtype.kind in ("U", "S", "O"):
                self.text[name] = arr
            else:
                x = arr.astype(np.float64)
                self.num[name] = x
                self.finite[name] = np.isfinite(x)
                self.has_null[name] = not self.finite[name].all()

    def coded(self, col: str) -> tuple:
        """(sorted categories, int32 code of each row) of a categorical
        column, worked out on first use: most statements touch none."""
        if col not in self._coded:
            cats, codes = np.unique(self.text[col].astype(str),
                                    return_inverse=True)
            self._coded[col] = (cats, codes.astype(np.int32))
        return self._coded[col]

    def mask(self, tree) -> np.ndarray:
        if tree is None:
            return np.ones(self.n, bool)
        if tree[0] == "cmp":
            return self._cmp(*tree[1:])
        masks = [self.mask(ch) for ch in tree[1]]
        out = masks[0].copy()
        for m in masks[1:]:
            if tree[0] == "and":
                out &= m
            else:
                out |= m
        return out

    def _cmp(self, col: str, op: str, value) -> np.ndarray:
        if col in self.text:
            cats, codes = self.coded(col)
            hit = np.flatnonzero(cats == str(value))
            eq = (codes == hit[0]) if hit.size else np.zeros(self.n, bool)
            if op == "=":
                return eq
            if op == "!=":
                return ~eq
            raise ValueError(f"range comparison on categorical column {col}")
        x = self.num[col]
        v = float(value)
        with np.errstate(invalid="ignore"):
            out = {"<": np.less, "<=": np.less_equal, ">": np.greater,
                   ">=": np.greater_equal, "=": np.equal,
                   "!=": np.not_equal}[op](x, v)
        if self.has_null[col]:
            out &= self.finite[col]
        return out

    def _agg(self, func: str, col: str, mask: np.ndarray):
        if func == "COUNT":
            if col == "*" or col in self.text:
                return float(np.count_nonzero(mask))
            return float(np.count_nonzero(mask & self.finite[col]))
        v = self.num[col][mask & self.finite[col]]
        if v.size == 0:
            return None
        return float({"SUM": np.sum, "AVG": np.mean, "MIN": np.min,
                      "MAX": np.max, "MEDIAN": np.median,
                      "VAR": np.var}[func](v))

    def _group(self, func: str, col: str, gcol: str, mask: np.ndarray):
        cats, codes = self.coded(gcol)
        k = len(cats)
        if col == "*" or col in self.text:
            valid = mask
        else:
            valid = mask & self.finite[col]
        counts = np.bincount(codes[valid], minlength=k)
        if func in ("COUNT", "SUM", "AVG"):
            out = {}
            sums = (np.bincount(codes[valid], weights=self.num[col][valid],
                                minlength=k) if func != "COUNT" else None)
            for g in range(k):
                if func == "COUNT":
                    if counts[g] > 0:
                        out[str(cats[g])] = float(counts[g])
                elif counts[g] > 0:
                    out[str(cats[g])] = float(
                        sums[g] if func == "SUM" else sums[g] / counts[g])
            return out
        out = {}
        for g in np.flatnonzero(counts):
            r = self._agg(func, col, mask & (codes == g))
            if r is not None:
                out[str(cats[g])] = r
        return out

    def answer(self, stmt):
        """Exact answer: a float, None (no qualifying value), or a dict
        category -> float for GROUP BY."""
        mask = self.mask(stmt.where)
        if stmt.group_by is not None:
            return self._group(stmt.func, stmt.agg, stmt.group_by, mask)
        return self._agg(stmt.func, stmt.agg, mask)

    def answers(self, stmts, workers: int = 8) -> list:
        """Exact answers of many statements; NumPy releases the interpreter
        lock on whole-column passes, so threads run them side by side."""
        used = set()
        for st in stmts:
            used |= columns_of(st.where) | {st.group_by}
        for col in used & set(self.text):     # coded once, before the threads
            self.coded(col)
        with concurrent.futures.ThreadPoolExecutor(workers) as pool:
            return list(pool.map(self.answer, stmts))


def errors(estimate, exact) -> list[float]:
    """Relative errors (%) of one answer: one for a scalar, one per group of
    either side for GROUP BY (a group on one side only counts 100)."""
    if isinstance(exact, dict) or isinstance(estimate, dict):
        est = estimate or {}
        ex = exact or {}
        return [relative_error(est.get(g), ex.get(g))
                for g in sorted(set(est) | set(ex))]
    return [relative_error(estimate, exact)]
