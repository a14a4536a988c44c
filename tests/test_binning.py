"""BinMapper tests (reference src/io/bin.cpp FindBin semantics)."""

import numpy as np
import pytest

from lightgbm_tpu.binning import MissingType, bin_matrix, find_bin


def test_simple_numeric():
    vals = np.array([1.0, 2.0, 3.0, 4.0, 5.0] * 20)
    m = find_bin(vals, max_bin=255, min_data_in_bin=1)
    b = m.value_to_bin(np.array([1.0, 2.0, 3.0, 4.0, 5.0]))
    # distinct values -> distinct bins, monotone
    assert len(set(b.tolist())) == 5
    assert all(b[i] < b[i + 1] for i in range(4))


def test_monotone_mapping():
    rng = np.random.RandomState(0)
    vals = rng.randn(5000)
    m = find_bin(vals, max_bin=63, min_data_in_bin=3)
    xs = np.sort(rng.randn(100))
    bs = m.value_to_bin(xs)
    assert (np.diff(bs) >= 0).all()
    assert bs.max() < m.num_bin


def test_max_bin_respected():
    rng = np.random.RandomState(1)
    vals = rng.randn(10000)
    for mb in (15, 63, 255):
        m = find_bin(vals, max_bin=mb, min_data_in_bin=1)
        assert 1 < m.num_bin <= mb


def test_zero_gets_own_bin():
    vals = np.concatenate([np.zeros(50), np.linspace(-3, 3, 100)])
    m = find_bin(vals, max_bin=32, min_data_in_bin=1)
    zb = m.value_to_bin(np.array([0.0]))[0]
    nonzero = m.value_to_bin(np.array([-3.0, -0.1, 0.1, 3.0]))
    assert zb not in nonzero.tolist()
    assert m.default_bin == zb


def test_nan_bin():
    vals = np.array([1.0, 2.0, np.nan, 3.0, np.nan] * 10)
    m = find_bin(vals, max_bin=16, min_data_in_bin=1, use_missing=True)
    assert m.missing_type == MissingType.NAN
    b = m.value_to_bin(np.array([np.nan, 1.0]))
    assert b[0] == m.num_bin - 1  # trailing NaN bin
    assert b[1] != b[0]


def test_no_use_missing():
    vals = np.array([1.0, 2.0, np.nan, 3.0] * 10)
    m = find_bin(vals, max_bin=16, min_data_in_bin=1, use_missing=False)
    assert m.missing_type == MissingType.NONE
    # NaN folds into the zero bin
    assert m.value_to_bin(np.array([np.nan]))[0] == m.value_to_bin(
        np.array([0.0]))[0]


def test_categorical():
    vals = np.array([0, 1, 1, 2, 2, 2, 5, 5, 5, 5] * 10, dtype=np.float64)
    m = find_bin(vals, max_bin=32, min_data_in_bin=1, is_categorical=True)
    assert m.is_categorical
    b = m.value_to_bin(np.array([5.0, 2.0, 1.0, 0.0]))
    # bin 0 is no category's; then by descending frequency: 5 -> 1, 2 -> 2,
    # 1 -> 3, 0 -> 4
    assert b.tolist() == [1, 2, 3, 4]
    assert m.num_bin == 5 and m.bin_to_cat.tolist() == [-1, 5, 2, 1, 0]
    assert m.max_bin_share == pytest.approx(0.4)


@pytest.mark.parametrize("value", [99.0, 6.0, 3.0, -1.0, -7.0, 2.5, np.nan,
                                   np.inf, -np.inf, 1e12])
def test_categorical_bin0_takes_what_is_no_binned_category(value):
    """Unseen (above, just above and between the binned ids), negative,
    non-integer, missing and infinite values all land in bin 0, which the
    split search never sends left (PR 34)."""
    vals = np.array([0, 1, 1, 2, 2, 2, 5, 5, 5, 5] * 10, dtype=np.float64)
    m = find_bin(vals, max_bin=32, min_data_in_bin=1, is_categorical=True)
    assert m.value_to_bin(np.array([value, 5.0]))[0] == 0


def test_categorical_folded_categories_share_bin0():
    """Categories past the bin budget are folded into bin 0 and counted
    in its share; a column with one category and nothing else is trivial."""
    vals = np.repeat(np.arange(10.0), np.arange(10, 0, -1) * 10)
    m = find_bin(vals, max_bin=4, min_data_in_bin=1, is_categorical=True)
    assert m.num_bin == 4 and m.bin_to_cat.tolist() == [-1, 0, 1, 2]
    assert m.value_to_bin(np.array([0.0, 2.0, 3.0, 9.0])).tolist() == [1, 3, 0, 0]
    assert m.max_bin_share == pytest.approx(280 / 550)
    assert find_bin(np.full(50, 3.0), max_bin=8, is_categorical=True).is_trivial
    assert not find_bin(np.r_[np.full(50, 3.0), np.nan], max_bin=8,
                        is_categorical=True).is_trivial


def test_trivial_feature():
    m = find_bin(np.ones(100), max_bin=32)
    assert m.is_trivial


def test_bin_matrix_dtype():
    rng = np.random.RandomState(2)
    X = rng.randn(100, 3)
    mappers = [find_bin(X[:, j], max_bin=255, min_data_in_bin=1)
               for j in range(3)]
    binned = bin_matrix(X, mappers)
    assert binned.dtype == np.uint8
    assert binned.shape == (100, 3)


def test_bin_to_value_roundtrip():
    rng = np.random.RandomState(3)
    vals = rng.randn(1000)
    m = find_bin(vals, max_bin=63, min_data_in_bin=1)
    # threshold semantics: value <= bin_to_value(b) <=> bin(value) <= b
    for b in range(0, m.num_bin - 1, 7):
        thr = m.bin_to_value(b)
        xs = rng.randn(200)
        lhs = xs <= thr
        rhs = m.value_to_bin(xs) <= b
        assert (lhs == rhs).all()


def test_forced_bins(tmp_path):
    """forcedbins_filename (reference dataset_loader.cpp GetForcedBins):
    listed boundaries must appear among the feature's bin upper bounds."""
    import json
    import lightgbm_tpu as lgb
    from lightgbm_tpu.config import Config
    rng = np.random.RandomState(0)
    X = rng.rand(2000, 3)
    y = (X[:, 0] > 0.33).astype(float)
    fb = str(tmp_path / "forced.json")
    with open(fb, "w") as fh:
        json.dump([{"feature": 0, "bin_upper_bound": [0.3, 0.35, 0.4]}], fh)
    P = {"objective": "binary", "verbosity": -1, "max_bin": 16,
         "forcedbins_filename": fb}
    ds = lgb.Dataset(X, y, params=P)
    ds.construct(Config(P))
    ub = ds.bin_mappers[0].bin_upper_bound
    for b in (0.3, 0.35, 0.4):
        assert np.any(np.isclose(ub, b)), (b, ub)
    # still trains
    bst = lgb.train(P, lgb.Dataset(X, y), 3)
    assert np.isfinite(bst.predict(X[:10])).all()


def test_forced_bins_capped_and_zero_bin_preserved():
    """Forced bounds are capped at max_bin (reference caps too) and the
    dedicated zero/missing bin survives the merge."""
    from lightgbm_tpu.binning import find_bin
    rng = np.random.RandomState(0)
    v = rng.rand(5000) * 10
    m = find_bin(v, max_bin=8, forced_bounds=list(np.linspace(0.1, 9.9, 40)))
    assert m.num_bin <= 9
    v2 = np.concatenate([np.zeros(1000), rng.rand(4000)])
    m2 = find_bin(v2, max_bin=8, zero_as_missing=True,
                  forced_bounds=list(np.linspace(0.1, 0.9, 14)))
    assert m2.value_to_bin(np.array([0.0]))[0] != \
        m2.value_to_bin(np.array([0.2]))[0]
