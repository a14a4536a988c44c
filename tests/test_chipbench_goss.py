"""The benchmark's ``boosting=goss`` cell at a tiny size, in the tier-1 suite:
q8, the draw through the one jitted sampler, the driver ``train_loop_goss``,
judged ``correct`` by ``chipbench/reference_goss.py`` (the sample's law and
the sampled trees' weighted sums); the control and the four planted faults
read ``correct`` false, each by the number that is there to catch it; a
program without ``last_sample()`` ends before any data is made; a window tree
that was not sampled ends the run.  The tests are
``chipbench/tests/test_run_goss.py``'s own, run here too so that the suite the
driver counts holds the deployment to its reference; but its manifest test,
which states how many cells the benchmark had when the goss cell came (four:
PR 34 added a fifth, and may not edit a file the benchmark has): the same
facts of the goss cell are held here without the count."""

from chipbench import manifest as mf, validate
from chipbench.tests import helpers, helpers_goss
from chipbench.tests.test_run_goss import (  # noqa: F401
    cpu_stands_in, test_a_planted_fault_is_not_correct,
    test_a_program_without_last_sample_ends_before_any_data_is_made,
    test_a_sound_goss_run_is_correct,
    test_a_traced_goss_run_reports_the_metrics_it_can_read,
    test_a_window_tree_that_was_not_sampled_ends_the_run,
    test_warm_up_trees_that_do_not_match_the_mix_end_the_run)


def test_the_manifest_with_the_goss_cell_passes(tmp_path):
    assert validate.validate(helpers.REPO) == []
    m = mf.load_manifest(helpers.REPO)
    assert [w["name"] for w in m["workloads"] if w["chips"] == 4] == ["criteo-q8-dp4.train"]
    cell = mf.find_named(m["workloads"], "criteo-q8-goss.train", "workload")
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("criteo-share-q8-goss", "train-steady-goss", 1)
    mine = {x["name"] for x in mf.metrics_for(m, cell["name"], "per_layer")}
    assert set(helpers_goss.GOSS_METRICS) <= mine
    # a floor over all N rows is not a sampled tree's floor, and one chip has no mesh
    assert not mine & {"hist_kernel_roofline", "tree_step_mfu", "mesh_tree_step_mfu",
                       "collective_bytes_per_pass"}
    for name in helpers_goss.GOSS_METRICS:
        assert mf.find_named(m["per_layer"], name, "metric")["workloads"] == [cell["name"]]
    cfg = mf.load_json(f"{helpers.REPO}/chipbench/configs/criteo-share-q8-goss.json")
    q8 = mf.load_json(f"{helpers.REPO}/chipbench/configs/criteo-share-q8.json")
    assert cfg["params"] == dict(q8["params"], boosting="goss", top_rate=0.2, other_rate=0.1)
    assert cfg["data"] == q8["data"] and cfg["reduced"] == q8["reduced"]
    assert set(cfg["limits"]) == set(q8["limits"]) | {
        "goss_top_violations", "goss_top_share_gap", "goss_rest_rate_gap", "goss_rest_bias"}
    assert validate.validate(helpers_goss.make_root(str(tmp_path))) == []
