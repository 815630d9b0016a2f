"""The benchmark's exact evaluator agrees with the program's exact engine."""
import math

import numpy as np
import pytest

from bench import harness as hs
from bench.reference import ExactTable, relative_error
from bench.statements import Stmt

AGGS = ("COUNT", "SUM", "AVG", "MIN", "MAX", "MEDIAN", "VAR")


def _random_tree(rng, sample, cols, cats):
    conds = []
    for _ in range(int(rng.integers(1, 4))):
        col = str(rng.choice(cols + list(cats)))
        if col in cats:
            conds.append(("cmp", col, str(rng.choice(["=", "!="])),
                          str(rng.choice(cats[col]))))
        else:
            v = float(sample.literals(col, rng.uniform(0.05, 0.95, 1))[0])
            conds.append(("cmp", col, str(rng.choice(
                ["<", "<=", ">", ">=", "=", "!="])), v))
    if len(conds) == 1:
        return conds[0]
    if rng.random() < 0.3:
        return ("and", (conds[0], ("or", tuple(conds[1:]))))
    return ("and", tuple(conds)) if rng.random() < 0.7 else \
        ("or", tuple(conds))


@pytest.mark.parametrize("category", [False, True])
def test_exact_agrees_with_program(category):
    from repro.aqp.exact import ExactEngine

    cfg = hs.load_json(hs.BENCH / "configs" / "power.json")
    table = hs.generate_table(cfg, rows=20_000)
    if category:        # a categorical column, for = / != and GROUP BY
        table["phase"] = np.random.default_rng(1).choice(
            np.array(["L1", "L2", "L3", "N"]), size=20_000, p=[.4, .3, .2, .1])
    sample = hs.Sample(table, 21, rows=20_000)
    ours, theirs = ExactTable(table), ExactEngine(table)
    cats = {c: sample.categories(c) for c in ours.text}
    nums = list(ours.num)
    rng = np.random.default_rng(0)
    for i in range(120):
        group = "phase" if category and i % 3 == 0 else None
        func = str(rng.choice(AGGS))
        agg = "*" if func == "COUNT" and i % 5 == 0 else str(rng.choice(nums))
        st = Stmt(func, agg, _random_tree(rng, sample, nums, cats), group)
        want = theirs.query(st.sql(cfg["table"]))
        got = ours.answer(st)
        if isinstance(want, dict):
            assert set(got) == set(want)
            for g in want:
                assert math.isclose(got[g], want[g], rel_tol=1e-9,
                                    abs_tol=1e-9)
        elif want is None:
            assert got is None
        else:
            assert math.isclose(got, want, rel_tol=1e-9, abs_tol=1e-9)


def test_relative_error_matches_paper_formula():
    from repro.aqp.queries import relative_error as program

    for est, ex in [(1.0, 2.0), (None, 1.0), (1.0, None), (0.0, 0.0),
                    (1e-3, 0.0), (-3.0, 2.0)]:
        assert relative_error(est, ex) == program(est, ex)
