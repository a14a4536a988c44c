"""The categorical sorted-subset search of ``ops/split.py`` (PR 37): it moves
its (nc, B) planes with the sort network and static shifts, and reads one
position a row as a masked sum.  Held here to a plain NumPy walk of the same
search, to a count of the index moves in its jaxpr, and, on whole trees, to
the gather form it replaced (kept in this file only, as the oracle)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lightgbm_tpu.ops import split
from lightgbm_tpu.ops.split import NEG_INF, SplitParams, best_split_per_feature

B = 256
F32 = np.float32
# powers of two wherever a constant divides, so that no rewriting of a
# division can round differently from NumPy's
PARAMS = dict(min_data_in_leaf=2, min_sum_hessian_in_leaf=1e-3, lambda_l2=1.0,
              cat_l2=8.0, cat_smooth=4.0, min_data_per_group=8,
              max_cat_to_onehot=4, max_cat_threshold=32, use_cat_subset=True)


# --------------------------------------------------------------------------
# the plain walk (feature_histogram.hpp FindBestThresholdCategoricalInner as
# ops/split.py states it), one column at a time, float32 like the program
# --------------------------------------------------------------------------

def _leaf_gain(g, h, l2):
    return F32(g * g / (h + l2)) if h + l2 > 0 else F32(0.0)


def _walk_column(hist, parent, nb, p, rand_bin=None):
    """(gain, left sums, left set, whether the winner came from the far end)
    of one categorical column: ``hist`` (B, 3), ``parent`` (3,)."""
    l2, cat_l2 = F32(p["lambda_l2"]), F32(p["lambda_l2"] + p["cat_l2"])
    min_cnt, min_h = F32(p["min_data_in_leaf"]), F32(p["min_sum_hessian_in_leaf"])
    mdpg, smooth = F32(p["min_data_per_group"]), F32(p["cat_smooth"])
    bins = np.arange(B)
    real = (bins >= 1) & (bins < nb)
    g, h, c = (np.where(real, hist[:, k], 0).astype(F32) for k in range(3))
    tot = parent.astype(F32)
    shift = _leaf_gain(tot[0], tot[1], l2)              # min_gain_to_split 0
    best, left, member, far = F32(NEG_INF), np.zeros(3, F32), np.zeros(B, bool), False

    def gain_of(lsum, l2_):
        r = tot - lsum
        return F32(_leaf_gain(lsum[0], lsum[1], l2_) + _leaf_gain(r[0], r[1], l2_) - shift), r

    if nb <= p["max_cat_to_onehot"]:                     # one against the rest
        for k in bins[real]:
            if rand_bin is not None and k != rand_bin:
                continue
            lsum = np.array([g[k], h[k], c[k]], F32)
            gain, r = gain_of(lsum, l2)
            if (lsum[2] >= min_cnt and r[2] >= min_cnt and lsum[1] >= min_h and r[1] >= min_h
                    and gain > 0 and gain > best):
                best, left, member = gain, lsum, bins == k
        return best, left, member, far

    valid = real & (c >= smooth)
    ratio = np.where(valid, g / (h + smooth), F32(1e30)).astype(F32)
    used = int(valid.sum())
    order = np.argsort(ratio, kind="stable")[:used]
    max_pos = min(p["max_cat_threshold"], (used + 1) // 2, used)
    for from_end in (False, True):
        seq = order[::-1] if from_end else order
        lsum, prev_group = np.zeros(3, F32), F32(-1.0)
        for i in range(max_pos):
            k = seq[i]
            lsum = lsum + np.array([g[k], h[k], c[k]], F32)
            group = np.floor(lsum[2] / mdpg)
            spaced, prev_group = group > prev_group, group
            if rand_bin is not None and i != rand_bin % max(max_pos, 1):
                continue
            gain, r = gain_of(lsum, cat_l2)
            if (lsum[2] >= min_cnt and lsum[1] >= min_h and r[2] >= max(min_cnt, mdpg)
                    and r[1] >= min_h and spaced and gain > 0 and gain > best):
                best, left, far = gain, lsum, from_end
                member = np.isin(bins, seq[:i + 1])
    return best, left, member, far


# --------------------------------------------------------------------------
# planes: whole counts and gradients in quarters, so every sum is exact in
# float32 whatever the order it is added in
# --------------------------------------------------------------------------

def _column(rng, nb, used, ties=False, far_end=False):
    """(B, 3) histogram of a categorical column with ``nb`` bins of which
    ``used`` hold at least ``cat_smooth`` rows."""
    c = np.zeros(B)
    cand = 1 + rng.permutation(nb - 1)[:used]
    c[1:nb] = rng.randint(0, 4, nb - 1)                  # under cat_smooth
    c[cand] = rng.randint(4, 60, used)
    c[0] = 25                                            # the missing ones
    g = rng.randint(-6, 7, B) * c / 4
    if far_end and used:                                 # a few heavy categories, all at the far end
        g = rng.randint(-1, 2, B) * c / 4
        g[cand[:3]] = 3 * c[cand[:3]]
    if ties and used > 3:                                # runs of equal ratios, equal and unequal sums
        for a, b in zip(cand[:used // 2:2], cand[1:used // 2:2]):
            c[b], g[b] = c[a], g[a]
        g[cand[used // 2:]] = c[cand[used // 2:]] / 2
    return np.stack([g, c / 2, c], axis=-1).astype(F32)


CASES = {
    "used_0": dict(used=0),
    "used_1": dict(used=1),
    "used_2": dict(used=2),
    "used_33": dict(used=33),
    "used_255": dict(used=255),
    "ties": dict(used=120, ties=True),
    "ties_all_positions_open": dict(used=60, ties=True, max_cat_threshold=256),
    "winner_from_the_far_end": dict(used=33, far_end=True, max_cat_threshold=4),
    "extra_trees": dict(used=33, extra_trees=True),
    "extra_trees_far_end": dict(used=33, far_end=True, extra_trees=True, max_cat_threshold=4),
    "all_columns_searched": dict(used=33, cat_idx=()),
    "vmap_over_children": dict(used=(0, 1, 2, 33, 255, 77), vmap=True),
    "vmap_far_end_and_ties": dict(used=(33, 120, 5), vmap=True, far_end=True, ties=True,
                                  max_cat_threshold=4),
}


@pytest.mark.parametrize("name", list(CASES))
def test_the_search_against_a_plain_walk(name):
    """Columns: 0 numeric, 1 categorical under ``max_cat_to_onehot`` (one
    against the rest), 2 and 3 categorical over it (the sorted-subset search),
    3 always with 255 candidates."""
    case = dict(CASES[name])
    rng = np.random.RandomState(100 + list(CASES).index(name))
    useds = case.pop("used")
    vmapped = case.pop("vmap", False)
    useds = useds if vmapped else (useds,)
    ties, far_end = case.pop("ties", False), case.pop("far_end", False)
    et = case.get("extra_trees", False)
    p = {**PARAMS, "cat_idx": (1, 2, 3), **case}
    sp = SplitParams(**p)
    nbs = np.array([B, 4, B, B], np.int32)
    is_cat = np.array([False, True, True, True])
    has_nan = np.array([True, False, False, False])
    hists = np.stack([np.stack([_column(rng, B, 200), _column(rng, 4, 3),
                                _column(rng, B, u, ties, far_end),
                                _column(rng, B, 255, ties)]) for u in useds])
    parents = hists[:, 2].sum(axis=1)
    rand = rng.randint(0, B, (len(useds), 4)).astype(np.int32)
    rand[:, 1] = rng.randint(1, 4, len(useds))

    def scan(h, s, r):
        return best_split_per_feature(h, s, jnp.asarray(nbs), jnp.asarray(is_cat),
                                      jnp.asarray(has_nan), sp, rand_bins=r if et else None)

    if vmapped:
        out = jax.vmap(scan)(hists, parents, rand)
    else:
        out = jax.tree.map(lambda a: a[None], scan(hists[0], parents[0], rand[0]))
    gain, left, member = (np.asarray(a) for a in (out.gain, out.left_sum, out.cat_member))

    seen_far, seen_split = False, 0
    for k in range(len(useds)):
        for j in (1, 2, 3):
            w_gain, w_left, w_member, far = _walk_column(
                hists[k, j], parents[k], nbs[j], p, rand[k, j] if et else None)
            assert gain[k, j] == w_gain, (k, j)
            np.testing.assert_array_equal(member[k, j], w_member, err_msg=str((k, j)))
            if w_gain > NEG_INF / 2:
                np.testing.assert_array_equal(left[k, j], w_left, err_msg=str((k, j)))
                seen_split += 1
            seen_far |= far
    assert seen_split >= (1 if et else 2)       # the case is no walk over nothing
    assert seen_far or not far_end or et


# --------------------------------------------------------------------------
# the gather form as it stood before PR 37: the oracle, in this file only
# --------------------------------------------------------------------------

def _gather_sorted_prefixes(ratio, used, planes):
    nc, b = ratio.shape
    order = jnp.argsort(ratio, axis=1, stable=True)
    rank = jnp.zeros((nc, b), jnp.int32).at[jnp.arange(nc)[:, None], order].set(
        jnp.broadcast_to(jnp.arange(b, dtype=jnp.int32)[None, :], (nc, b)))
    pos = jnp.arange(b, dtype=jnp.int32)[None, :]
    pos_used = pos < used[:, None]

    def fwd_bwd(plane):
        sh = jnp.take_along_axis(plane, order, axis=1)
        sh = jnp.where(pos_used, sh, 0.0)
        cumf = jnp.cumsum(sh, axis=1)
        total_used = cumf[:, -1:]
        bidx = used[:, None] - 2 - pos
        tb = jnp.take_along_axis(cumf, jnp.clip(bidx, 0, b - 1), 1)
        return cumf, total_used - jnp.where(bidx >= 0, tb, 0.0)

    return rank, [fwd_bwd(plane) for plane in planes]


@pytest.mark.parametrize("b", [256, 31, 5])
def test_the_sorts_and_shifts_give_the_gathers_bits(b):
    """Planes that are no whole numbers, heavy ties in the key, every ``used``
    from 0 to B: the places and the six prefix planes, bit for bit."""
    rng = np.random.RandomState(b)
    nc = b + 1
    used = np.arange(nc, dtype=np.int32)
    ratio = rng.randint(-3, 4, (nc, b)).astype(F32) / 3
    ratio = np.where(rng.rand(nc, b).argsort(1).argsort(1) < used[:, None], ratio, 1e30).astype(F32)
    planes = tuple(rng.randn(nc, b).astype(F32) for _ in range(3))
    new = jax.jit(split._ratio_sorted_prefixes)(ratio, used, planes)
    old = jax.jit(_gather_sorted_prefixes)(ratio, used, planes)
    for a, o in zip(jax.tree.leaves(new), jax.tree.leaves(old), strict=True):
        assert np.asarray(a).tobytes() == np.asarray(o).tobytes()


def _index_moves(jaxpr, shape):
    """Names of the gather and scatter equations, nested jaxprs included,
    that read or write an array of ``shape``."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name.startswith(("gather", "scatter")) and \
                eqn.invars[0].aval.shape == shape:
            found.append(eqn.primitive.name)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _index_moves(sub, shape)
    return found


def _scan_jaxpr(**kw):
    f, nc = 5, 3
    sp = SplitParams(**dict(PARAMS, cat_idx=(1, 2, 4), **kw))
    nb = jnp.full((f,), B, jnp.int32)
    ic = jnp.asarray([False, True, True, False, True])

    def fn(h, s, r, po):
        return split._best_split_impl(h, s, nb, ic, jnp.zeros((f,), bool), sp,
                                      parent_out=po, rand_bins=r)

    return jax.make_jaxpr(fn)(jnp.zeros((f, B, 3)), jnp.zeros((3,)),
                              jnp.zeros((f,), jnp.int32), jnp.zeros(())).jaxpr, (nc, B)


@pytest.mark.parametrize("kw", [{}, {"extra_trees": True}, {"path_smooth": 1.0}],
                         ids=["plain", "extra_trees", "path_smooth"])
def test_no_index_move_of_an_nc_by_b_plane(kw, monkeypatch):
    """No gather and no scatter reads or writes an (nc, B) plane (before PR 37:
    fifteen, seven of them in the oracle above); what is left moves whole rows
    between F-space and the categorical columns, or one bin a column of an
    (F, B) plane."""
    jaxpr, plane = _scan_jaxpr(**kw)
    assert _index_moves(jaxpr, plane) == []
    text = str(jaxpr)
    assert text.count(" sort[") == 2 and "dot_general" not in text
    # the counter counts: with the oracle patched in it finds the seven
    monkeypatch.setattr(split, "_ratio_sorted_prefixes", _gather_sorted_prefixes)
    jaxpr, plane = _scan_jaxpr(**kw)
    assert sorted(_index_moves(jaxpr, plane)) == ["gather"] * 6 + ["scatter"]


# --------------------------------------------------------------------------
# whole trees: the small categorical set-up of test_wave_cat.py, each form
# --------------------------------------------------------------------------

@pytest.mark.parametrize("grower", ["wave", "partition"])
def test_whole_trees_equal_the_gather_forms(grower, monkeypatch):
    from lightgbm_tpu.learner import serial
    from test_wave_cat import XLA, _shape, _train

    def model_text(form):
        if form is not None:
            monkeypatch.setattr(split, "_ratio_sorted_prefixes", form)
        serial._GROW_FN_CACHE.clear()
        jax.clear_caches()
        params = XLA if grower == "wave" else {"tree_grow_mode": "partition"}
        try:
            bst, _ = _train(params)
        finally:
            monkeypatch.undo()
            serial._GROW_FN_CACHE.clear()
            jax.clear_caches()
        assert sum(isinstance(v, tuple) and len(v) > 1 for t in _shape(bst) for _, v in t) >= 3
        return bst.model_to_string()

    traced = []

    def oracle(*a):
        traced.append(1)
        return _gather_sorted_prefixes(*a)

    assert model_text(None) == model_text(oracle)
    assert traced
