"""``lgb.Dataset(data=[block, block, ...])``: a list of 2-D numpy row blocks
is the rows of one matrix.  It is binned block by block, each block from its
own dtype, into the mappers and bin codes the whole-matrix path gives on the
same rows, bit for bit, and no copy of the whole matrix is ever made."""

import tracemalloc

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.dataset import RowBlocks

N, F = 40_000, 12
PARAMS = {"max_bin": 255, "bin_construct_sample_cnt": 5000, "verbosity": -1}


@pytest.fixture(scope="module")
def matrix():
    rng = np.random.default_rng(5)
    X = rng.standard_normal((N, F)).astype(np.float32)
    X[::7, 3] = np.nan                     # a NaN bin
    X[:, 5] = np.round(X[:, 5] * 2)        # few distinct values
    X[:, 9] = 1.0                          # trivial: filtered out
    y = (X[:, 0] + X[:, 1] > 0).astype(np.float32)
    return X, y


def cut(X, bounds, dtypes=None):
    blocks = [X[a:b] for a, b in zip(bounds[:-1], bounds[1:])]
    if dtypes:
        blocks = [b.astype(d) for b, d in zip(blocks, dtypes)]
    return blocks


def assert_same_dataset(a, b):
    assert a.X_binned.dtype == b.X_binned.dtype and a.X_binned.flags.c_contiguous
    np.testing.assert_array_equal(a.X_binned, b.X_binned)
    np.testing.assert_array_equal(a.used_feature_map, b.used_feature_map)
    np.testing.assert_array_equal(a.metadata.label, b.metadata.label)
    assert len(a.bin_mappers) == len(b.bin_mappers)
    for ma, mb in zip(a.bin_mappers, b.bin_mappers):
        assert (ma.num_bin, ma.missing_type, ma.is_trivial, ma.default_bin) == \
            (mb.num_bin, mb.missing_type, mb.is_trivial, mb.default_bin)
        np.testing.assert_array_equal(ma.bin_upper_bound, mb.bin_upper_bound)
    assert a.fingerprint() == b.fingerprint()


@pytest.mark.parametrize("bounds, dtypes", [
    ([0, 16_384, 32_768, N], None),                           # equal blocks, ragged last
    ([0, 1, 9_999, 10_000, N], None),                         # a one-row block
    ([0, 15_000, 30_000, N], [np.float32, np.float64, np.float32]),
    ([0, N], [np.float64]),                                   # one block
], ids=["ragged_last", "one_row_block", "mixed_dtypes", "single_block"])
def test_blocks_bin_as_the_whole_matrix_does(matrix, bounds, dtypes):
    X, y = matrix
    whole = lgb.Dataset(X, y, params=PARAMS).construct()
    blocks = lgb.Dataset(cut(X, bounds, dtypes), y, params=PARAMS).construct()
    assert blocks.num_data() == N and blocks.num_feature() == F - 1
    assert_same_dataset(whole, blocks)
    # the same spans fed both: the per-layer readers read here too
    assert {"bin_find", "bin_matrix"} <= set(blocks.setup_seconds)


def test_blocks_sample_every_row_when_the_sample_is_the_matrix(matrix):
    X, y = matrix
    p = dict(PARAMS, bin_construct_sample_cnt=10 * N)
    assert_same_dataset(lgb.Dataset(X, y, params=p).construct(),
                        lgb.Dataset(cut(X, [0, 123, 20_000, N]), y, params=p).construct())


def test_no_copy_of_the_whole_matrix_is_made(matrix):
    """Peak traced memory of ``construct()`` on 64 float32 blocks stays
    under the bytes of the float32 matrix itself, so no copy of the whole
    exists in float32 or anything wider (what there is: the uint8 codes, one
    float64 block, the sample); the whole-matrix path holds float64 copies
    (the bound is live)."""
    X, y = matrix
    bounds = list(range(0, N, N // 64)) + [N]
    blocks = cut(X, bounds)
    matrix_f32 = X.nbytes

    def peak(data):
        ds = lgb.Dataset(data, y, params=dict(PARAMS, bin_construct_sample_cnt=2000))
        tracemalloc.start()
        try:
            ds.construct()
            return tracemalloc.get_traced_memory()[1]
        finally:
            tracemalloc.stop()

    assert peak(blocks) < matrix_f32
    assert peak(X) > 2 * matrix_f32          # float64 whole, at least once


def test_a_validation_set_in_blocks_takes_its_reference_bins(matrix):
    X, y = matrix
    train = lgb.Dataset(cut(X[:30_000], [0, 10_000, 30_000]), y[:30_000], params=PARAMS)
    valid_blocks = lgb.Dataset(cut(X[30_000:], [0, 4_000, 10_000]), y[30_000:],
                               reference=train, params=PARAMS).construct()
    valid_whole = train.create_valid(X[30_000:], y[30_000:]).construct()
    assert valid_blocks.bin_mappers is train.bin_mappers
    np.testing.assert_array_equal(valid_blocks.X_binned, valid_whole.X_binned)


def test_blocks_train_through_booster_update_as_the_matrix_does(matrix):
    X, y = matrix
    p = {"objective": "binary", "num_leaves": 7, "min_data_in_leaf": 20, "verbosity": -1,
         **PARAMS}
    texts = []
    for data in (X[:8_000], cut(X[:8_000], [0, 3_000, 8_000])):
        bst = lgb.Booster(params=p, train_set=lgb.Dataset(data, y[:8_000], params=p))
        for _ in range(3):
            bst.update()
        texts.append(bst.model_to_string())
    assert texts[0] == texts[1]


def test_linear_trees_keep_the_raw_columns_of_the_blocks(matrix):
    X, y = matrix
    p = dict(PARAMS, linear_tree=True)
    whole = lgb.Dataset(X[:5_000], y[:5_000], params=p).construct()
    blocks = lgb.Dataset(cut(X[:5_000], [0, 2_000, 5_000]), y[:5_000], params=p).construct()
    np.testing.assert_array_equal(whole.raw_used, blocks.raw_used)


def test_blocks_of_unequal_width_are_refused():
    with pytest.raises(ValueError, match="equal width"):
        lgb.Dataset([np.zeros((4, 3)), np.zeros((4, 2))], np.zeros(8)).construct()


def test_a_list_of_rows_is_still_a_matrix():
    """Only 2-D numpy arrays are row blocks; a list of 1-D rows or of lists
    is converted whole, as before."""
    rows = [np.array([float(i), float(i % 3)]) for i in range(60)]
    ds = lgb.Dataset(rows, np.arange(60) % 2, params={"min_data_in_bin": 1, "verbosity": -1}).construct()
    assert ds.num_data() == 60 and ds.num_feature() == 2


def test_take_rows_gathers_across_block_edges():
    X = np.arange(40, dtype=np.float32).reshape(20, 2)
    rb = RowBlocks([X[:7], X[7:8], X[8:]])
    assert rb.shape == (20, 2)
    idx = np.array([0, 6, 7, 8, 19])
    got = rb.take_rows(idx)
    assert got.dtype == np.float64
    np.testing.assert_array_equal(got, X[idx])
