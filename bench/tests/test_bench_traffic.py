"""The benchmark's data and traffic are fixed by the seed."""
import numpy as np
import pytest

from bench import harness as hs
from bench.statements import columns_of

@pytest.fixture(scope="module")
def power():
    cfg = hs.load_json(hs.BENCH / "configs" / "power.json")
    table = hs.generate_table(cfg, rows=200_000)
    return cfg, table, hs.Sample(table, 7, rows=20_000)


@pytest.mark.parametrize("config", ["power"])
def test_data_is_the_configurations_in_every_run(config):
    cfg = hs.load_json(hs.BENCH / "configs" / f"{config}.json")
    a = hs.generate_table(cfg, rows=5000)
    b = hs.generate_table(cfg, rows=5000)
    assert list(a) == cfg["columns"]
    for col in a:
        assert np.array_equal(a[col], b[col], equal_nan=a[col].dtype.kind
                              == "f")
    other = hs.load_module(hs.BENCH / "data" / f"{cfg['generator']}.py",
                           "bench_data_other").generate(5000,
                                                        cfg["data_seed"] + 1)
    assert any(not np.array_equal(a[c], other[c]) for c in a)


def test_open_templates_schedule(power):
    _, _, sample = power
    cell = hs.cell("power-dash-steady")
    kind = hs.load_module(hs.BENCH / "kinds" / "open_templates.py", "k_ot")
    traffic = dict(cell.traffic, rate_per_s=50)
    a = kind.schedule(traffic, sample, hs.rng(11, "traffic"), 4.0)
    b = kind.schedule(traffic, sample, hs.rng(11, "traffic"), 4.0)
    c = kind.schedule(traffic, sample, hs.rng(12, "traffic"), 4.0)
    assert a == b and a != c
    assert len(a) == len(c) == 200
    assert all(0 <= d < 4.0 for d, _ in a)
    assert [d for d, _ in a] == sorted(d for d, _ in a)

    def mix(s):
        return sorted((st.func, st.agg, tuple(sorted(columns_of(st.where))))
                      for _, st in s)
    assert mix(a) == mix(c)               # same work, another order
    sqls = [st.sql("power") for _, st in a]
    assert len(set(sqls)) == len(sqls)    # fresh literals: no cache hits
    for _, st in a:
        assert sample.count(st.where) >= traffic["min_sample_rows"]


def test_warmup_groups_cover_each_template_at_every_size(power):
    _, _, sample = power
    cell = hs.cell("power-dash-steady")
    kind = hs.load_module(hs.BENCH / "kinds" / "open_templates.py", "k_ot2")
    traffic = dict(cell.traffic, rate_per_s=50)
    window = {st for _, st in kind.schedule(traffic, sample,
                                            hs.rng(11, "traffic"), 4.0)}
    groups = kind.warmup(traffic, sample, hs.rng(11, "warm"), 64, window)
    assert groups == kind.warmup(traffic, sample, hs.rng(11, "warm"), 64,
                                 window)

    def shape(st):
        return st.func, st.agg, tuple(c[1:3] for c in st.where[1])
    sizes = {}
    for g in groups:
        assert len({shape(st) for st in g}) == 1
        sizes.setdefault(shape(g[0]), []).append(len(g))
    assert len(sizes) == len(traffic["templates"])
    for got in sizes.values():
        assert got == list(range(2, max(got) + 1))
    # The most popular template's groups reach past its mean share of a
    # wave of 64; the rarest still come in pairs.
    assert max(len(g) for g in groups) > 64 * 0.35
    stmts = [st for g in groups for st in g]
    assert len(set(stmts)) == len(stmts) and not set(stmts) & window
    for st in stmts[::50]:
        assert sample.count(st.where) >= traffic["min_sample_rows"]


def test_vetted_literals_select_enough_rows(power):
    _, _, sample = power
    preds = [["voltage", ">"], ["global_intensity", "<"]]
    lits = sample.vetted(preds, 50, hs.rng(5, "traffic"), 0.1, 0.9, 100)
    assert lits.shape == (50, 2)
    assert np.all(hs.counts(sample.exact, preds, lits) >= 100)


def test_seed_shuffles_the_same_statements_and_keeps_the_checked_set(
        power):
    from bench import serving

    _, _, sample = power
    kind = hs.load_module(hs.BENCH / "kinds" / "open_templates.py", "k_ot3")
    traffic = dict(hs.cell("power-dash-steady").traffic, rate_per_s=50)
    stmts = [st for _, st in kind.schedule(traffic, sample,
                                           hs.rng(11, "traffic"), 4.0)]
    a = serving.shuffled(stmts, 30, hs.rng(1, "order"))
    b = serving.shuffled(stmts, 30, hs.rng(1, "order"))
    c = serving.shuffled(stmts, 30, hs.rng(2, "order"))
    assert a == b and a != c
    assert sorted(map(repr, a)) == sorted(map(repr, stmts))
    assert sorted(map(repr, c[:30])) == sorted(map(repr, stmts[:30]))
    assert a[:30] != stmts[:30]
