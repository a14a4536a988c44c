"""The wave grower has ONE host-side owner (``learner/serial.py``
``WaveTreeLearner``): the serial, the data-parallel and the voting learner
hand their wave route to it, so the translation ``Config`` -> grower
arguments, the device layout of the bins and the call convention of ``grow``
exist once.

Run as a script, this file prints what the compiled grower of a checkout IS
for four set-ups (q8 / exact on one device, q8 ``tree_learner=data``, exact
``tree_learner=voting``), captured at the call ``learner.train`` makes:

    JAX_PLATFORMS=cpu python tests/test_wave_learner.py [ROOT]

Two checkouts that print the same lines compile the same programs (the
persistent compile cache of one serves the other).
"""

import hashlib
import os
import sys

if __name__ == "__main__":      # before jax: the suite's 8 virtual devices
    ROOT = os.path.abspath(next(
        (a for a in sys.argv[1:] if not a.startswith("--")),
        os.path.join(os.path.dirname(__file__), "..")))
    sys.path.insert(0, ROOT)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    jax.config.update("jax_num_cpu_devices", 8)

import numpy as np
import pytest

import lightgbm_tpu as lgb

F, N, CHIPS = 6, 6000, 4
WAVE = {"objective": "binary", "num_leaves": 15, "max_bin": 63,
        "min_data_in_leaf": 5, "tree_grow_mode": "wave",
        "tpu_histogram_impl": "pallas", "verbosity": -1}
Q8 = {"use_quantized_grad": True, "num_grad_quant_bins": 254,
      "quant_train_renew_leaf": True}
MESH = {"serial": {}, "data": {"tree_learner": "data", "num_devices": CHIPS},
        "voting": {"tree_learner": "voting", "num_devices": CHIPS}}
HASH_SETUPS = {"q8-serial": dict(WAVE, **Q8),
               "exact-serial": dict(WAVE),
               "q8-data": dict(WAVE, **Q8, **MESH["data"]),
               "exact-voting": dict(WAVE, **MESH["voting"])}

# The first two trees of the exact wave grower on ``_data()``, as the tree
# BEFORE the three wrappers became one class grew them (commit e1aa7ae, all
# three tree_learners alike: ``top_k`` covers the six features):
# (split_feature, threshold_bin) of every node.
GOLDEN = (((0, 1, 0, 1, 0, 0, 1, 1, 0, 0, 1, 1, 0, 0),
           (29, 46, 13, 21, 41, 21, 33, 4, 53, 36, 36, 53, 21, 44)),
          ((0, 1, 0, 1, 0, 0, 1, 0, 1, 0, 1, 0, 1, 1),
           (29, 43, 13, 16, 41, 36, 36, 50, 53, 24, 27, 21, 4, 60)))


def _data():
    rng = np.random.default_rng(3)
    X = rng.standard_normal((N, F)).astype(np.float32)
    y = (X[:, 0] + 0.5 * X[:, 1] + 0.3 * rng.standard_normal(N) > 0)
    return X, y.astype(np.float32)


def _booster(params):
    X, y = _data()
    return lgb.Booster(params=params,
                       train_set=lgb.Dataset(X, y, params=params))


def _trees(bst):
    return tuple((tuple(int(v) for v in t.split_feature[:t.num_leaves - 1]),
                  tuple(int(v) for v in t.threshold_bin[:t.num_leaves - 1]))
                 for t in bst._gbdt.models)


def grower_programs():
    """{set-up: (jaxpr hash, sha256 of the lowered StableHLO)} of the
    compiled grower, with the operands ``learner.train`` gives it."""
    from lightgbm_tpu.analysis import ir
    out = {}
    for name, params in HASH_SETUPS.items():
        bst = _booster(params)
        learner = bst._gbdt.learner
        compiled, calls = learner._grow, []
        learner._grow = lambda *a, **k: (calls.append((a, k)),
                                         compiled(*a, **k))[1]
        bst.update()
        (a, k), = calls
        text = compiled.lower(*a, **k).as_text()
        out[name] = (ir.stable_hash(ir.trace(compiled, *a, **k)),
                     hashlib.sha256(text.encode()).hexdigest()[:16])
    return out


@pytest.mark.parametrize("tree_learner", ["serial", "data", "voting"])
def test_one_wave_learner_behind_every_tree_learner(tree_learner):
    from lightgbm_tpu.learner.serial import WaveTreeLearner
    bst = _booster(dict(WAVE, **MESH[tree_learner]))
    for _ in range(2):
        bst.update()
    learner = bst._gbdt.learner
    # (a) the wave route is the one class: no subclass has a layout, a
    # call convention or a grower builder of its own
    assert isinstance(learner, WaveTreeLearner) and learner.wave
    for owned in ("train", "bind", "build_grow_fn"):
        assert getattr(type(learner), owned) is getattr(WaveTreeLearner, owned)
    assert (learner.mesh is None) == (tree_learner == "serial")
    # (b) the layout is made, and timed, in one place for all three
    assert "layout" in bst.train_record.snapshot()["setup_seconds"]
    # (c) and the trees are the ones the three wrappers grew
    assert _trees(bst) == GOLDEN


def test_the_translation_from_config_is_shared():
    """For one Config the three learners' grower arguments differ only in
    what selects the mesh route (``strategy``) and in the conditions the
    mesh wrappers always imposed (``pack4`` / ``pipeline`` are not handed
    on under a strategy; voting takes no interaction constraints, lazy
    CEGB penalties or forced splits)."""
    params = dict(WAVE, **Q8, max_bin=15, tpu_pallas_pipeline="dma",
                  interaction_constraints="[0,1,2],[2,3,4,5]")
    kw = {}
    for tree_learner, mesh in MESH.items():
        learner = _booster(dict(params, **mesh))._gbdt.learner
        kw[tree_learner] = dict(learner._grow_kwargs)
    assert kw["serial"]["pack4"] and kw["serial"]["pipeline"] == "dma"
    assert kw["serial"]["strategy"] is None
    for tree_learner in ("data", "voting"):
        got = kw[tree_learner]
        assert set(got) == set(kw["serial"])
        assert got["strategy"] is not None
        assert not got["pack4"] and got["pipeline"] is None
        differ = {k for k in got if got[k] != kw["serial"][k]}
        allowed = {"strategy", "pack4", "pipeline"}
        if tree_learner == "voting":
            allowed |= {"interaction_groups", "cegb_lazy", "forced_splits"}
            assert got["interaction_groups"] == ()
        else:
            assert got["interaction_groups"] == ((0, 1, 2), (2, 3, 4, 5))
        assert differ <= allowed, differ


if __name__ == "__main__":
    print(f"# {ROOT} ({os.path.dirname(lgb.__file__)})")
    if "--trees" in sys.argv:
        for tl, mesh in MESH.items():
            bst = _booster(dict(WAVE, **mesh))
            for _ in range(2):
                bst.update()
            print(f'    "{tl}": {_trees(bst)!r},')
    for name, (jaxpr, hlo) in grower_programs().items():
        print(f"{name}: jaxpr {jaxpr} stablehlo {hlo}")
