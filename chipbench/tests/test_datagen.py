import numpy as np

from chipbench import datagen

SPEC = datagen.TabularSpec({"generator": "higgs_like", "rows": 600_000, "features": 5,
                            "holdout_rows": 1000, "label_noise": 0.5, "interaction": 0.3,
                            "weights_seed": 3})


def test_the_generator_is_a_pure_function_of_the_seed():
    big = 2**31 + 12345          # more than 32 signed bits hold
    X1, y1 = datagen.training_matrix(SPEC, big)
    X2, y2 = datagen.training_matrix(SPEC, big)
    assert np.array_equal(X1, X2) and np.array_equal(y1, y2)
    X3, _ = datagen.training_matrix(SPEC, big + 1)
    assert not np.array_equal(X1, X3)
    assert X1.dtype == np.float64 and X1.flags["C_CONTIGUOUS"]
    assert np.array_equal(X1, X1.astype(np.float32).astype(np.float64))   # f32 values
    assert 0.4 < y1.mean() < 0.6


def test_any_block_can_be_made_again_alone():
    X, y = datagen.training_matrix(SPEC, 7)
    assert SPEC.blocks == 3
    for b in range(SPEC.blocks):
        lo, hi = SPEC.block_range(b)
        xb, yb = datagen.block(SPEC, 7, b)
        assert np.array_equal(X[lo:hi], xb) and np.array_equal(y[lo:hi], yb)
    assert SPEC.block_range(2) == (2 * datagen.BLOCK_ROWS, 600_000)


def test_holdout_differs_from_training_rows_and_repeats():
    xh, yh = datagen.holdout(SPEC, 7)
    xh2, _ = datagen.holdout(SPEC, 7)
    X, _ = datagen.training_matrix(SPEC, 7)
    assert np.array_equal(xh, xh2) and xh.shape == (1000, 5)
    assert not np.array_equal(xh, X[:1000])
