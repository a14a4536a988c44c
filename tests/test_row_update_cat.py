"""The fused row-update kernel with categorical slots (PR 34): a slot
whose split is categorical tests its row's bin against the slot's 256-bit
left set, a numeric slot keeps its two compares.  Held bit for bit to the
splits applied one by one in numpy (the arithmetic of the XLA form in
learner/wave.py), in both pipelines, interpreted."""

import numpy as np
import pytest

import jax.numpy as jnp

from lightgbm_tpu.ops.histogram_pallas import (
    _cat_table, bin_rows_view, traced_kernels, wave_row_update_pallas)


def _plain(cols, rl, tab, is_cat, member):
    """One split after the other, as the XLA form decides a row."""
    rl = rl.copy()
    ch = np.full(rl.shape, -1, np.int8)
    for j in range(tab.shape[1]):
        thr, nanb, dleft, small, leaf, new, act = tab[:7, j]
        col = cols[j].astype(np.int64)
        num_go = np.where(col == nanb, dleft > 0, col <= thr)
        go = member[j][col] if is_cat[j] else num_go
        upd = (rl == leaf) & (act > 0)
        ch = np.where(upd & (go == (small > 0)), j, ch).astype(np.int8)
        rl = np.where(upd & ~go, new, rl).astype(rl.dtype)
    return rl, ch


def _case(kind, nan_dir, inactive, B, n=8192, f=9, W=6, seed=5):
    rng = np.random.RandomState(seed)
    bins = rng.randint(0, B, (f, n)).astype(np.uint8)
    feats = rng.permutation(f)[:W].astype(np.int32)
    is_cat = {"numeric": np.zeros(W, bool), "categorical": np.ones(W, bool),
              "mixed": np.arange(W) % 2 == 1}[kind]
    member = rng.rand(W, B) < 0.4
    member[:, 0] = False                    # bin 0 is never in a left set
    member[0, B - 1] = True                 # the last bin of the last word
    act = (rng.rand(W) < 0.6) if inactive else np.ones(W, bool)
    rl = rng.randint(0, 4, n).astype(np.int32)
    tab = np.stack([
        rng.randint(0, B, W), np.where(rng.rand(W) < 0.7, B - 1, -1),
        np.full(W, nan_dir), rng.randint(0, 2, W), rng.randint(0, 4, W),
        np.arange(4, 4 + W), act, np.zeros(W)]).astype(np.int32)
    return bins, feats, rl, tab, is_cat, member


@pytest.mark.parametrize("inactive", [False, True])
@pytest.mark.parametrize("nan_dir", [0, 1])
@pytest.mark.parametrize("kind,B", [
    ("numeric", 255), ("categorical", 255), ("mixed", 255), ("mixed", 40)])
def test_cat_row_update_bitwise(kind, B, nan_dir, inactive):
    bins, feats, rl, tab, is_cat, member = _case(kind, nan_dir, inactive, B)
    want_rl, want_ch = _plain(bins[feats], rl, tab, is_cat, member)
    cat = (jnp.asarray(is_cat), jnp.asarray(member))
    for pipeline in ("dma", "blockspec"):
        src = bin_rows_view(jnp.asarray(bins), pipeline)
        got_rl, got_ch = wave_row_update_pallas(
            src, jnp.asarray(rl), jnp.asarray(tab),
            feats=jnp.asarray(feats), cat=cat, pipeline=pipeline)
        np.testing.assert_array_equal(np.asarray(got_rl), want_rl, pipeline)
        np.testing.assert_array_equal(np.asarray(got_ch), want_ch, pipeline)
    if kind == "numeric":
        # all-numeric slots through the categorical entry = the numeric one
        num_rl, num_ch = wave_row_update_pallas(
            bin_rows_view(jnp.asarray(bins), "dma"), jnp.asarray(rl),
            jnp.asarray(tab), feats=jnp.asarray(feats), pipeline="dma")
        np.testing.assert_array_equal(np.asarray(num_rl), want_rl)
        np.testing.assert_array_equal(np.asarray(num_ch), want_ch)


def test_cat_table_words_and_kernel_name():
    """Bit ``b & 31`` of word ``b >> 5`` is bin b's membership; the
    categorical entry has a kernel name of its own and the numeric entry
    keeps the one the benchmark's breakdown reads."""
    W, B = 3, 255
    member = np.zeros((W, B), bool)
    member[0, [1, 31, 32, 254]] = True
    member[2, 200] = True
    tab = _cat_table(jnp.zeros((8, W), jnp.int32),
                     jnp.asarray([True, False, True]), jnp.asarray(member))
    tab = np.asarray(tab)
    assert tab.shape == (17, W)
    assert list(tab[8]) == [1, 0, 1]
    words = tab[9:].astype(np.int64) & 0xFFFFFFFF
    assert words[0, 0] == (1 << 1) | (1 << 31) and words[1, 0] == 1
    assert words[7, 0] == 1 << 30 and words[6, 2] == 1 << 8
    assert not words[:, 1].any()
    bins, feats, rl, tab8, is_cat, mem = _case("mixed", 0, False, 255)
    src = bin_rows_view(jnp.asarray(bins), "dma")
    wave_row_update_pallas(src, jnp.asarray(rl), jnp.asarray(tab8),
                           feats=jnp.asarray(feats), pipeline="dma",
                           cat=(jnp.asarray(is_cat), jnp.asarray(mem)))
    wave_row_update_pallas(src, jnp.asarray(rl), jnp.asarray(tab8),
                           feats=jnp.asarray(feats), pipeline="dma")
    names = traced_kernels()
    assert "lgbm_wave_row_update_dma_cat_w6_f9_kr8192_n8192" in names
    assert "lgbm_wave_row_update_dma_w6_f9_kr8192_n8192" in names
