import numpy as np
import pytest

from chipbench import reference

MODEL = """tree
version=v3
num_class=1

Tree=0
num_leaves=3
num_cat=0
split_feature=0 1
split_gain=10 4
threshold=0.5 -1.25
decision_type=0 0
left_child=1 -1
right_child=-3 -2
leaf_value=0.1 0.2 -0.3
leaf_count=2 1 2
shrinkage=0.1

Tree=1
num_leaves=1
num_cat=0
leaf_value=0.05
leaf_count=5
shrinkage=0.1

end of trees

feature_importances:
"""


def test_parse_and_walk_a_hand_written_model():
    trees = reference.parse_model(MODEL)
    assert [t.num_leaves for t in trees] == [3, 1]
    x = np.array([[0.5, -1.25], [0.5, -1.0], [0.6, 0.0], [-3.0, -2.0], [9.0, 9.0]], np.float32)
    # value <= threshold goes left: row 0 -> node 1 -> leaf 0; row 1 -> leaf 1; row 2 -> leaf 2
    assert trees[0].walk(x).tolist() == [0, 1, 2, 0, 2]
    assert trees[1].walk(x).tolist() == [0] * 5
    np.testing.assert_allclose(reference.predict_raw(trees, x),
                               [0.15, 0.25, -0.25, 0.15, -0.25])
    assert trees[0].children_sums(np.array([1.0, 2.0, 4.0])).tolist() == [7.0, 3.0]


def test_recompute_by_hand():
    trees = reference.parse_model(MODEL)
    leaf = np.array([[0, 1, 2, 0, 2]], np.int16)
    y = np.array([1.0, 0.0, 1.0, 1.0, 0.0])
    ref = reference.recompute(trees, leaf, y, reference.Params(
        {"objective": "binary", "learning_rate": 0.1}))
    bias = np.log(0.6 / 0.4)
    assert ref["bias"] == pytest.approx(bias)
    p = 0.6
    g, h = p - y, np.full(5, p * (1 - p))
    G = np.array([g[0] + g[3], g[1], g[2] + g[4]])
    H = np.array([2, 1, 2]) * h[0]
    np.testing.assert_allclose(ref["out"][0], -G / H * 0.1)
    assert ref["count"][0].tolist() == [2, 1, 2]
    gain = lambda a, b: a * a / b
    root = gain(G[0] + G[1], H[0] + H[1]) + gain(G[2], H[2]) - gain(G.sum(), H.sum())
    node1 = gain(G[0], H[0]) + gain(G[1], H[1]) - gain(G[0] + G[1], H[0] + H[1])
    np.testing.assert_allclose(ref["gain"][0], [root, node1])
    # the program's stated values against themselves: every gap 0
    same = reference.compare_followed(ref, ref)
    assert same == {"leaf_count_diff": 0.0, "leaf_value_gap": 0.0, "split_gain_gap": 0.0,
                    "split_gain_median_gap": 0.0}
    # half of the rows left out shows in the counts
    half = reference.recompute(trees, leaf, y, reference.Params(
        {"objective": "binary", "learning_rate": 0.1}), keep=np.array([1.0, 0, 1, 0, 1]))
    assert reference.compare_followed(half, ref)["leaf_count_diff"] == 2.0


def test_auc_agrees_with_scikit_learn():
    from sklearn.metrics import roc_auc_score
    rng = np.random.default_rng(0)
    y = (rng.random(5000) > 0.5).astype(np.float32)
    p = np.round(rng.random(5000) * 0.5 + y * 0.2, 2)       # many ties
    assert reference.auc(y, p) == pytest.approx(roc_auc_score(y, p), abs=1e-12)


def test_round_bf16_keeps_eight_bits():
    x = np.array([1.0, 1.0 + 2.0**-8, 1.0 + 2.0**-7, -3.14159, 1e-3])
    r = reference.round_bf16(x)
    assert r[0] == 1.0 and r[1] == 1.0 and r[2] == 1.0 + 2.0**-7
    assert np.all(np.abs(r - x) <= np.abs(x) * 2.0**-8)


def test_judge_needs_a_limit_for_every_number():
    ok, rows = reference.judge({"a": 0.0, "b": 0.5}, {"a": 0, "b": 1.0})
    assert ok and rows == [("a", 0.0, 0.0), ("b", 0.5, 1.0)]
    assert not reference.judge({"a": 1.0}, {"a": 0})[0]
    assert not reference.judge({"a": float("nan")}, {"a": 1})[0]
    with pytest.raises(KeyError):
        reference.judge({"c": 0.0}, {"a": 0})
