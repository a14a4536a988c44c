"""``chipbench/datagen_ctr.py``: a block is a pure function of (seed, block);
the columns' missing shares and the top category's share follow the
configuration's tables; ids are not sorted by frequency."""

import numpy as np
import pytest

from chipbench import datagen_ctr as dg
from chipbench.tests import helpers_cat

SPEC = dg.CtrSpec(dict(helpers_cat.TINY_DATA, rows=2 * dg.BLOCK_ROWS + 1000))
TABLES = dg.Tables(SPEC)


def test_a_block_made_twice_is_equal_and_blocks_differ():
    x0, y0 = dg.block(SPEC, 7, 1, TABLES)
    x1, y1 = dg.block(SPEC, 7, 1)                 # tables made again
    assert x0.dtype == np.float32 and x0.shape == (dg.BLOCK_ROWS, 7)
    np.testing.assert_array_equal(x0, x1)
    np.testing.assert_array_equal(y0, y1)
    assert SPEC.blocks == 3 and dg.block(SPEC, 7, 2, TABLES)[0].shape == (1000, 7)
    assert not np.array_equal(np.nan_to_num(x0), np.nan_to_num(dg.block(SPEC, 8, 1, TABLES)[0]))
    big = 2**31 + 12345                           # the driver's seeds pass 32 signed bits
    np.testing.assert_array_equal(dg.block(SPEC, big, 0, TABLES)[1],
                                  dg.block(SPEC, big, 0, TABLES)[1])


def test_missing_shares_and_the_top_categorys_share_follow_the_tables():
    x, y = dg.block(SPEC, 3, 0, TABLES)
    miss = np.isnan(x).mean(axis=0)
    np.testing.assert_allclose(miss[:3], SPEC.int_missing, atol=0.01)
    np.testing.assert_allclose(miss[3:], SPEC.cat_missing, atol=0.01)
    assert 0.2 < y.mean() < 0.6
    for j, card in enumerate(SPEC.cardinality):
        col = x[:, 3 + j]
        ids = col[~np.isnan(col)].astype(np.int64)
        assert ids.min() >= 0 and ids.max() < SPEC.ids(j) and np.all(ids == col[~np.isnan(col)])
        p = np.arange(1, card + 1, dtype=np.float64) ** -SPEC.zipf
        want = max(p[0] / p.sum(), p[SPEC.ids(j) - 1:].sum() / p.sum())
        got = np.bincount(ids).max() / len(ids)
        assert got == pytest.approx(want, abs=0.01), j


def test_ids_are_not_sorted_by_frequency_and_the_permutation_is_fixed():
    x, _ = dg.block(SPEC, 3, 0, TABLES)
    col = x[:, 3 + 2]                              # 40 categories
    counts = np.bincount(col[~np.isnan(col)].astype(np.int64), minlength=40)
    by_freq = np.argsort(-counts, kind="stable")
    assert list(by_freq[:5]) != [0, 1, 2, 3, 4]
    a, b = dg.affine(2, 40)
    assert list(by_freq[:5]) == [(a * r + b) % 40 for r in range(5)]
    assert sorted((a * r + b) % 40 for r in range(40)) == list(range(40))
    for j, k in ((0, 3), (5, 65536), (9, 1460)):
        a, b = dg.affine(j, k)
        assert np.gcd(a, k) == 1 and 0 <= b < k


def test_rank_cdf_folds_the_tail_into_the_last_kept_id():
    cdf = dg.rank_cdf(1000, 10, 1.1)
    p = np.arange(1, 1001, dtype=np.float64) ** -1.1
    assert len(cdf) == 10 and cdf[-1] == 1.0
    assert cdf[0] == pytest.approx(p[0] / p.sum())
    assert cdf[-1] - cdf[-2] == pytest.approx(p[9:].sum() / p.sum())


def test_the_spec_refuses_tables_of_the_wrong_length():
    with pytest.raises(ValueError, match="cardinality has 3 entries"):
        dg.CtrSpec(dict(helpers_cat.TINY_DATA, cardinality=[3, 4, 5]))
    with pytest.raises(ValueError, match="unknown data generator"):
        dg.CtrSpec(dict(helpers_cat.TINY_DATA, generator="higgs_like"))
