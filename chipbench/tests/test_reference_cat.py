"""``chipbench/reference_cat.py`` on a hand-made model text: three internal
nodes, one of them a bitset node and one a NaN-left numerical node."""

import numpy as np
import pytest

from chipbench import reference_cat as rc

# node 0: feature 1 categorical, left set {2, 37} (word 0 bit 2, word 1 bit 5)
# node 1 (left of 0): feature 0 numerical, <= 1.5, missing type NaN, default LEFT
# node 2 (right of 0): feature 0 numerical, <= 0.5, missing type NaN, default right
MODEL = """tree
version=v3
feature_infos=[0:9] -1:2:37:5:11

Tree=0
num_leaves=4
num_cat=1
split_feature=1 0 0
split_gain=10 5 2
threshold=0 1.5 0.5
decision_type=1 10 8
left_child=1 -1 -3
right_child=2 -2 -4
leaf_value=0.1 0.2 0.3 0.4
leaf_weight=1 1 1 1
leaf_count=2 2 2 2
internal_value=0 0 0
internal_weight=0 0 0
internal_count=8 4 4
cat_boundaries=0 2
cat_threshold=4 32
is_linear=0
shrinkage=1

end of trees
"""


def tree():
    trees, infos = rc.parse_model(MODEL)
    assert infos == ["[0:9]", "-1:2:37:5:11"]
    return trees[0], infos


def test_the_model_text_is_parsed_in_full():
    t, infos = tree()
    assert list(t.is_cat) == [True, False, False]
    assert list(t.default_left) == [False, True, False]
    assert list(t.missing_nan) == [False, True, True]
    assert list(t.left_sets[0]) == [2, 37] and t.left_sets[1] is None
    assert list(rc.binned_categories(infos, 1)) == [2, 37, 5, 11]
    with pytest.raises(ValueError, match="not categorical"):
        rc.binned_categories(infos, 0)
    assert rc.cat_features([t]) == [1]
    assert rc.cat_split_counts([t]) == (1, 3)
    rank, span = t.leaf_ranges()
    assert list(rank) == [0, 1, 2, 3] and span.tolist() == [[0, 4], [0, 2], [2, 4]]


@pytest.mark.parametrize("row, leaf", [
    ((1.0, 2.0), 0),           # in the set, <= 1.5
    ((2.0, 37.0), 1),          # in the set (second word), > 1.5
    ((np.nan, 2.0), 0),        # NaN follows node 1's default: left
    ((0.0, 5.0), 2),           # a binned category outside the set goes right, <= 0.5
    ((np.nan, 5.0), 3),        # NaN follows node 2's default: right
    ((0.0, np.nan), 2),        # a missing category goes right
    ((0.0, -1.0), 2),          # a negative one
    ((0.0, 99.0), 2),          # one never seen, past the bitset's words
    ((0.0, 3.0), 2),           # one never seen, inside the words
    ((0.0, 2.5), 2),           # no integer
    ((9.0, 1e12), 3),          # beyond any id
])
def test_the_walk_on_raw_values(row, leaf):
    t, _ = tree()
    assert t.walk(np.array([row], np.float32))[0] == leaf


def test_the_planted_walks_differ_where_they_should():
    t, infos = tree()
    x = np.array([(0.0, np.nan), (0.0, 99.0), (0.0, 37.0), (np.nan, 2.0)], np.float32)
    assert list(t.walk(x)) == [2, 2, 0, 0]
    # a missing or unseen category stands for the most frequent binned one (2: in the set)
    assert list(t.walk(x, rc.unknown_as_most_frequent([t], infos))) == [0, 0, 0, 0]
    assert list(rc.cut_left_sets(t, 1).walk(x)) == [2, 2, 2, 0]       # 37 cut away
    assert list(rc.flip_nan_direction(t).walk(x)) == [2, 2, 0, 1]     # NaN now right at node 1
    assert list(t.walk(x)) == [2, 2, 0, 0]                            # the tree itself unchanged


def test_the_published_search_one_vs_rest_and_sorted_subset():
    p = rc.Params({"objective": "binary", "learning_rate": 0.1, "min_data_in_leaf": 1,
                   "min_data_per_group": 1, "cat_smooth": 0.0, "cat_l2": 0.0,
                   "min_sum_hessian_in_leaf": 0.0, "max_cat_to_onehot": 4})
    # 4 bins: one-vs-rest; bin 0 never goes left though it would win
    hg = np.array([-30.0, 4.0, -6.0, 2.0])
    hh = np.array([10.0, 10.0, 10.0, 10.0])
    hc = np.array([100.0, 100.0, 100.0, 100.0])
    gain, left = rc.search(hg, hh, hc, p)
    parent = hg.sum() ** 2 / hh.sum()
    best = {t: hg[t] ** 2 / hh[t] + (hg.sum() - hg[t]) ** 2 / (hh.sum() - hh[t]) - parent
            for t in (1, 2, 3)}
    assert left == [max(best, key=best.get)] and gain == pytest.approx(max(best.values()))
    # 6 bins: sorted by grad / hess and scanned from both ends; the best subset is {3, 5}
    hg = np.array([0.0, 5.0, 4.0, -6.0, 1.0, -5.0])
    hh = np.full(6, 10.0)
    hc = np.full(6, 100.0)
    gain, left = rc.search(hg, hh, hc, p)
    assert sorted(left) == [3, 5]
    assert gain == pytest.approx(11.0 ** 2 / 20 + 10.0 ** 2 / 40 - 1.0 / 60)
    # cat_l2 enters the children's gains and not the parent's
    p.cat_l2 = 10.0
    gain2, _ = rc.search(hg, hh, hc, p)
    assert gain2 == pytest.approx(11.0 ** 2 / 30 + 10.0 ** 2 / 50 - 1.0 / 60)
    # at most max_cat_threshold categories on the scanned side
    p.max_cat_threshold = 1
    assert len(rc.search(hg, hh, hc, p)[1]) == 1
    # one-vs-rest alone finds less
    assert rc.search(hg, hh, hc, p, onehot_only=True)[0] < gain


def test_recompute_counts_gains_search_and_the_law():
    t, infos = tree()
    p = rc.Params({"objective": "binary", "learning_rate": 1.0, "min_data_in_leaf": 1,
                   "min_data_per_group": 1, "cat_smooth": 0.0, "cat_l2": 0.0,
                   "min_sum_hessian_in_leaf": 0.0})
    x = np.array([(1.0, 2.0), (1.0, 37.0), (2.0, 2.0), (np.nan, 5.0), (0.0, 5.0), (0.0, 11.0),
                  (1.0, np.nan), (3.0, 99.0)], np.float32)
    y = np.array([1.0, 1, 1, 0, 0, 0, 1, 0])
    leaf = t.walk(x)[None, :].astype(np.int16)
    cols = {1: np.where(np.isnan(x[:, 1]), -1, x[:, 1]).astype(np.int32)}
    ref = rc.recompute([t], leaf, y, p, infos, cols)
    assert list(ref["count"][0]) == [2, 1, 2, 3]
    g = 0.5 - y                                   # bias 0: p = 0.5, h = 0.25
    lg, rg = g[leaf[0] < 2].sum(), g[leaf[0] >= 2].sum()
    assert ref["gain"][0][0] == pytest.approx(lg ** 2 / 0.75 + rg ** 2 / 1.25 - 0.0)
    (node, best, best_oh), = ref["search"][0]
    assert node == 0 and best >= best_oh > 0
    # the program's left set {2, 37} is what the reference's own search finds here
    assert best == pytest.approx(ref["gain"][0][0])
    assert ref["violations"] == 0
    numbers = rc.followed_numbers([t], ref)
    assert numbers["cat_law_violations"] == 0.0
    assert numbers["cat_search_gap"] == 0.0       # the text states 10: no shortfall
    t.split_gain[0] = best / 4
    assert rc.followed_numbers([t], ref)["cat_search_gap"] == pytest.approx(0.75)
    assert rc.cat_search_gap([t], ref["search"], lambda k, node, oh: oh) == \
        pytest.approx((best - best_oh) / best)
    # the law: a child under min_data_in_leaf, a set larger than max_cat_threshold,
    # a category the column never holds
    p.min_data_in_leaf, p.max_cat_threshold = 4, 1
    assert rc.recompute([t], leaf, y, p, infos, cols)["violations"] == 2
    t.left_sets[0] = np.array([2, 38])
    p.min_data_in_leaf, p.max_cat_threshold = 1, 32
    assert rc.recompute([t], leaf, y, p, infos, cols)["violations"] == 1
