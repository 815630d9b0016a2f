"""GreedyGD compression + preprocessing."""
import numpy as np
import pytest

from repro.gd.greedygd import GreedyGD, decompress_rows
from repro.gd.preprocess import preprocess_column, preprocess_table


def _roundtrip_bit_exact(data):
    gd = GreedyGD(search_rows=500)
    ct = gd.compress(data)
    rec = gd.decompress(ct)
    assert rec.shape == data.shape
    assert np.array_equal(np.isnan(rec), np.isnan(data))
    ok = ~np.isnan(data)
    assert data[ok].tobytes() == rec[ok].tobytes()   # bit-exact, not approx
    return ct


def test_preprocess_float_to_int():
    codes, info = preprocess_column(np.array([10.22, 10.25, 9.99]), "x")
    assert info.scale == 100.0
    assert info.kind == "float"
    np.testing.assert_allclose(codes, [23.0, 26.0, 0.0])
    # literal encoding matches data encoding (§5.1)
    assert info.encode(10.22) == 23.0
    assert info.decode(23.0) == 10.22


def test_preprocess_categorical_frequency_ranked():
    codes, info = preprocess_column(
        np.array(["b", "a", "b", "b", "c", "a"]), "x")
    assert info.categories[0] == "b"       # most frequent -> code 0
    assert info.encode("b") == 0.0
    assert info.encode("zzz") != info.encode("zzz")  # NaN: unseen literal


@pytest.mark.parametrize("values, codes, categories", [
    (np.array(["b", "a", "b", "c", "a", "b"]), [0, 1, 0, 2, 1, 0],
     ("b", "a", "c")),
    (np.array(["x", None, "y", float("nan"), "y", 3], dtype=object),
     [2, np.nan, 0, np.nan, 0, 1], ("y", "3", "x")),
    (np.array([None, None], dtype=object), [np.nan, np.nan], ()),
    (np.array([b"q", b"p", b"q"]), [0, 1, 0], ("b'q'", "b'p'")),
])
def test_preprocess_categorical_codes_and_nulls(values, codes, categories):
    """Codes rank categories by frequency, ties in sorted order; None and
    NaN in an object column are NULL; other values count by ``str``."""
    got, info = preprocess_column(values, "x")
    np.testing.assert_array_equal(got, np.asarray(codes, float))
    assert info.kind == "categorical"
    assert info.categories == categories


def test_preprocess_missing():
    codes, info = preprocess_column(np.array([1.0, np.nan, 3.0]), "x")
    assert np.isnan(codes[1])
    assert codes[0] == 0.0 and codes[2] == 2.0


def test_compression_reduces_size_on_redundant_data():
    rng = np.random.default_rng(0)
    n = 50_000
    table = {
        "a": rng.integers(0, 8, n).astype(float) * 1000,  # 8 values
        "b": np.round(rng.normal(500, 3, n)),             # narrow
        "c": rng.integers(0, 4, n).astype(float),
    }
    pp = preprocess_table(table)
    gd = GreedyGD()
    ct = gd.compress(pp.data)
    assert ct.size_bytes() < ct.raw_size_bytes()
    rec = gd.decompress(ct)
    assert np.allclose(rec, pp.data)


def test_seed_edges_are_sorted_and_in_domain():
    rng = np.random.default_rng(1)
    data = np.stack([rng.integers(0, 1000, 10000).astype(float),
                     rng.integers(0, 50, 10000).astype(float)], 1)
    gd = GreedyGD()
    ct = gd.compress(data)
    for i, edges in enumerate(GreedyGD.seed_edges(ct)):
        assert np.all(np.diff(edges) > 0)
        assert edges.min() >= 0
        assert edges.max() <= data[:, i].max() + 1


@pytest.mark.parametrize("case", [
    "nan_pattern", "constant_cols", "single_row", "all_unique",
    "nan_only_col", "nibble_boundary",
])
def test_gd_lossless_edge_cases(case):
    """decompress(compress(x)) is bit-exact on the adversarial shapes the
    null bitmap / base split / nibble granularity each stress."""
    rng = np.random.default_rng(42)
    if case == "nan_pattern":
        data = rng.integers(0, 5000, (3000, 4)).astype(float)
        data[rng.random((3000, 4)) < 0.2] = np.nan
    elif case == "constant_cols":
        data = np.stack([np.full(500, 7.0), np.zeros(500),
                         rng.integers(0, 9, 500).astype(float)], 1)
    elif case == "single_row":
        data = np.array([[13.0, 0.0, 4095.0]])
    elif case == "all_unique":
        data = np.stack([np.arange(2000, dtype=float),
                         rng.permutation(2000).astype(float)], 1)
    elif case == "nan_only_col":
        data = rng.integers(0, 100, (200, 3)).astype(float)
        data[:, 1] = np.nan
    else:  # nibble_boundary: widths straddling 2**k - 1 / 2**k
        cols = [np.array([(1 << k) - 1, (1 << k), 0], float)
                for k in (4, 8, 12, 16)]
        data = np.stack(cols, 1)
    _roundtrip_bit_exact(data)


def test_decompress_rows_subset_matches_full():
    """Row-subset decode (any order, duplicates) slices the full decode —
    the invariant GD-native construction rests on."""
    rng = np.random.default_rng(5)
    data = rng.integers(0, 3000, (4000, 3)).astype(float) * 8 \
        + rng.integers(0, 8, (4000, 3))
    data[rng.random((4000, 3)) < 0.1] = np.nan
    ct = GreedyGD(search_rows=500).compress(data)
    full = GreedyGD().decompress(ct)
    rows = np.array([0, 3999, 17, 17, 2500, 1])       # dupes + unsorted
    sub = decompress_rows(ct, rows)
    assert full[rows].tobytes() == sub.tobytes()
    assert decompress_rows(ct, None).tobytes() == full.tobytes()


def test_seed_edges_invariants():
    """seed_edges: strictly increasing, within [0, column max], and
    invariant under row permutation (bases are a set, order-free)."""
    rng = np.random.default_rng(9)
    data = np.stack([rng.integers(0, 4000, 6000).astype(float),
                     rng.integers(0, 64, 6000).astype(float) * 64], 1)
    gd = GreedyGD(search_rows=6000)     # full-data plan: permutation-proof
    ct = gd.compress(data)
    edges = GreedyGD.seed_edges(ct)
    for i, e in enumerate(edges):
        assert np.all(np.diff(e) > 0)
        assert e.min() >= 0.0 and e.max() <= data[:, i].max()
    perm = rng.permutation(data.shape[0])
    edges_p = GreedyGD.seed_edges(gd.compress(data[perm]))
    for e1, e2 in zip(edges, edges_p):
        assert np.array_equal(e1, e2)


def test_gd_seeding_changes_initial_edges_not_correctness(small_table):
    from repro.aqp.engine import AQPFramework
    from repro.aqp.exact import ExactEngine
    from repro.core.types import BuildParams
    exact = ExactEngine(small_table)
    fw_gd = AQPFramework(BuildParams(n_samples=20_000),
                         use_compression=True).ingest(small_table)
    fw_raw = AQPFramework(BuildParams(n_samples=20_000),
                          use_compression=False).ingest(small_table)
    sql = "SELECT AVG(c1) FROM t WHERE c2 > 600"
    truth = exact.query(sql)
    for fw in (fw_gd, fw_raw):
        est = fw.query(sql).estimate
        assert abs(est - truth) / truth < 0.02
