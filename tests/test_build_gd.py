"""Differential tests: GD-native construction vs the raw-matrix build.

``build_pairwise_hist`` accepts a ``CompressedTable`` directly: it samples
row *indices* from ``params.seed``, decodes only those rows (bit-exact),
and seeds the 1-D edges from the deduplicated bases. The build is therefore
bit-for-bit identical to the raw build with ``GreedyGD.seed_edges`` passed
in — asserted here field by field — and answers the accuracy corpus of
``test_query_accuracy.py`` within the exact same tolerances. A spy on the
decode path proves the full raw matrix is never materialized.
"""
import numpy as np
import pytest

from repro.core import storage
from repro.core.build import build_pairwise_hist
from repro.core.query import QueryEngine
from repro.core.types import BuildParams
from repro.gd.greedygd import GreedyGD
from repro.gd.preprocess import preprocess_table

from test_query_accuracy import CASES


@pytest.fixture(scope="module")
def gd_setup(small_table):
    pp = preprocess_table(small_table)
    ct = GreedyGD().compress(pp.data)
    return pp, ct


@pytest.fixture(scope="module")
def gd_synopsis(gd_setup):
    pp, ct = gd_setup
    return build_pairwise_hist(ct, pp.columns,
                               BuildParams(n_samples=30_000, seed=3))


def _assert_synopses_identical(a, b):
    assert a.n_rows == b.n_rows and a.n_sampled == b.n_sampled
    for ha, hb in zip(a.hists, b.hists):
        assert int(ha.k) == int(hb.k)
        for field in ("edges", "h", "u", "vmin", "vmax", "c",
                      "cminus", "cplus"):
            assert np.array_equal(getattr(ha, field), getattr(hb, field)), field
    assert set(a.pairs) == set(b.pairs)
    for key, pa in a.pairs.items():
        pb = b.pairs[key]
        for field in ("ex", "ey", "H", "hx", "hy", "ux", "uy", "vminx",
                      "vmaxx", "vminy", "vmaxy", "fold_x", "fold_y"):
            assert np.array_equal(getattr(pa, field), getattr(pb, field)), \
                (key, field)


def test_gd_build_bit_identical_to_raw_seeded(gd_setup, gd_synopsis):
    """Same seed, same sample indices, lossless row decode: the compressed
    build must equal the raw+seed_edges build bit for bit."""
    pp, ct = gd_setup
    raw = build_pairwise_hist(pp.data, pp.columns,
                              BuildParams(n_samples=30_000, seed=3),
                              seed_edges=GreedyGD.seed_edges(ct))
    _assert_synopses_identical(gd_synopsis, raw)
    assert gd_synopsis.build_stats["from_compressed"] is True
    assert raw.build_stats["from_compressed"] is False


@pytest.mark.parametrize("sql,tol_pct", CASES)
def test_gd_build_accuracy_on_corpus(gd_synopsis, exact, sql, tol_pct):
    """The GD-built synopsis answers the accuracy corpus within the same
    tolerances the raw build is held to in test_query_accuracy.py."""
    res = QueryEngine(gd_synopsis).query(sql)
    truth = exact.query(sql)
    assert res.estimate is not None
    err = abs(res.estimate - truth) / max(abs(truth), 1e-9) * 100
    assert err < tol_pct, (sql, res.estimate, truth)


def test_gd_build_decodes_only_the_sample(gd_setup, monkeypatch):
    """Building from a CompressedTable touches exactly the N_s sampled rows
    — never the full matrix, never the full-decode API."""
    pp, ct = gd_setup
    import repro.core.build as buildmod
    calls = []
    real = buildmod.decompress_rows

    def spy(ct_, rows=None):
        calls.append(None if rows is None else len(rows))
        return real(ct_, rows)

    monkeypatch.setattr(buildmod, "decompress_rows", spy)

    def forbid(self, ct_):
        raise AssertionError("full decompress() called during GD-native build")

    monkeypatch.setattr(GreedyGD, "decompress", forbid)
    ph = build_pairwise_hist(ct, pp.columns,
                             BuildParams(n_samples=5000, seed=1))
    assert calls == [5000]
    assert ph.build_stats["rows_decoded"] == 5000 < ct.n_rows
    assert ph.build_stats["from_compressed"] is True


def test_gd_build_storage_roundtrip_bit_exact(gd_synopsis):
    """encode/decode of a GD-built synopsis reproduces every stored field
    (and the re-derived fold maps) exactly."""
    blob = storage.encode(gd_synopsis)
    info = storage.blob_info(blob)
    assert info["bytes"] == len(blob)
    assert info["n_rows"] == gd_synopsis.n_rows
    assert info["d"] == gd_synopsis.d
    ph2 = storage.decode(blob)
    assert ph2.n_rows == gd_synopsis.n_rows
    for h1, h2 in zip(gd_synopsis.hists, ph2.hists):
        for field in ("edges", "h", "u", "vmin", "vmax"):
            assert np.array_equal(getattr(h1, field), getattr(h2, field)), field
    for key, p1 in gd_synopsis.pairs.items():
        p2 = ph2.pairs[key]
        for field in ("ex", "ey", "H", "hx", "hy", "ux", "uy", "vminx",
                      "vmaxx", "vminy", "vmaxy", "fold_x", "fold_y"):
            assert np.array_equal(getattr(p1, field), getattr(p2, field)), \
                (key, field)


def test_ingest_compressed_builds_without_raw(gd_setup):
    """AQPFramework.ingest_compressed: synopsis straight from an
    already-compressed table (the cold catalog's rebuild path)."""
    from repro.aqp.engine import AQPFramework
    pp, ct = gd_setup
    fw = AQPFramework(BuildParams(n_samples=10_000, seed=3))
    fw.ingest_compressed(ct, pp.columns)
    assert fw.preprocessed is None
    assert fw.timings["build_from_compressed"] is True
    res = fw.query("SELECT COUNT(*) FROM t WHERE c1 > 300")
    assert res.estimate is not None and res.estimate > 0


def test_seed_from_bases_off_still_correct(gd_setup):
    """seed_from_bases=False builds from min/max edges only — different
    binning, still a valid synopsis (sanity for the knob)."""
    pp, ct = gd_setup
    ph = build_pairwise_hist(ct, pp.columns,
                             BuildParams(n_samples=10_000, seed=3,
                                         seed_from_bases=False))
    assert ph.build_stats["from_compressed"] is True
    res = QueryEngine(ph).query("SELECT COUNT(*) FROM t WHERE c1 > 300")
    assert res.estimate is not None and res.estimate > 0


@pytest.mark.parametrize("where, want", [
    ("year >= 2015", 1.0), ("year = 2015", 1.0), ("year < 2016", 1.0),
    ("year > 2015", 0.0), ("year < 2015", 0.0),
    ("x >= 0 AND year = 2015", None)])
def test_constant_column_keeps_its_rows(where, want):
    """A column with one value (the flights table's ``year``) keeps a bin
    holding every row, so a predicate on it selects all rows or none."""
    rng = np.random.default_rng(0)
    n = 6_000
    table = {"year": np.full(n, 2015.0),
             "x": rng.normal(size=n).round(2),
             "g": np.array(["p", "q"])[rng.integers(0, 2, n)]}
    from repro.aqp.engine import AQPFramework

    fw = AQPFramework(BuildParams(n_samples=3_000, seed=1),
                      use_compression=True).ingest(table)
    got = fw.query(f"SELECT COUNT(*) FROM t WHERE {where}").estimate
    if want is None:
        want = fw.query("SELECT COUNT(*) FROM t WHERE x >= 0").estimate / n
    assert got == pytest.approx(want * n)
    assert fw.query("SELECT AVG(year) FROM t WHERE x >= 0").estimate \
        == pytest.approx(2015.0)
