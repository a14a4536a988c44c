"""The benchmark's integer + categorical cell at a tiny size, in the tier-1
suite: q8, rows routed by the row-update kernel's categorical slots, the
driver ``train_loop_cat``, judged ``correct`` by ``chipbench/reference_cat.py``
(bitsets and NaN directions walked on raw values, the categorical search
redone in float64); the control and the four planted faults read ``correct``
false, each by the number that is there to catch it; a program whose record
states no grower paths ends before any data is made.  With them the generator,
the reference on a hand-made tree and the row update's roofline.  The tests
are ``chipbench/tests``' own, run here too so that the suite the driver counts
holds the deployment to its reference; but its manifest test, which states
that the cat cell reports every metric ``criteo-q8.train`` does: PR 36 added
three that read what this cell's grower never runs (a ramp, an endgame, trees
of differing pass counts) and lists them without it, and may not edit a file
the benchmark has: the same facts are held here with that difference named."""

from chipbench import datagen_ctr, manifest as mf, validate
from chipbench.tests import helpers, helpers_cat

from chipbench.tests.test_datagen_ctr import (  # noqa: F401
    test_a_block_made_twice_is_equal_and_blocks_differ,
    test_ids_are_not_sorted_by_frequency_and_the_permutation_is_fixed,
    test_missing_shares_and_the_top_categorys_share_follow_the_tables,
    test_rank_cdf_folds_the_tail_into_the_last_kept_id,
    test_the_spec_refuses_tables_of_the_wrong_length)
from chipbench.tests.test_reference_cat import (  # noqa: F401
    test_recompute_counts_gains_search_and_the_law,
    test_the_model_text_is_parsed_in_full,
    test_the_planted_walks_differ_where_they_should,
    test_the_published_search_one_vs_rest_and_sorted_subset,
    test_the_walk_on_raw_values)
from chipbench.tests.test_roofline_row_update import (  # noqa: F401
    test_four_bytes_a_row_at_the_new_cells_shape,
    test_the_reader_divides_by_the_calls_of_one_device,
    test_wider_codes_and_leaf_ids_cost_their_bytes)
from chipbench.tests.test_run_cat import (  # noqa: F401
    cpu_stands_in, test_a_path_the_cell_does_not_describe_ends_the_run,
    test_a_planted_fault_is_not_correct,
    test_a_program_without_the_record_ends_before_any_data_is_made,
    test_a_sound_cat_run_is_correct,
    test_a_traced_cat_run_reports_the_metrics_it_can_read,
    test_the_probe_leaves_no_histogram_variant_to_a_timing_of_64_rows)


def test_the_manifest_with_five_cells_passes(tmp_path):
    assert validate.validate(helpers.REPO) == []
    m = mf.load_manifest(helpers.REPO)
    # five when this cell came (PR 34); tests/test_chipbench_efb.py holds the count now
    assert len(m["configs"]) >= 5 and len(m["workloads"]) >= 5
    assert [w["name"] for w in m["workloads"] if w["chips"] == 4] == ["criteo-q8-dp4.train"]
    cell = mf.find_named(m["workloads"], "criteo-cat-q8.train", "workload")
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("criteo-kaggle-cat-q8", "train-steady-blocks-cat", 1)
    mine = {x["name"] for x in mf.metrics_for(m, cell["name"], "per_layer")}
    q8 = {x["name"] for x in mf.metrics_for(m, "criteo-q8.train", "per_layer")}
    # every tree of this cell takes 14 passes, none the ramp's or the endgame's
    no_ramp_no_endgame = {"marginal_pass_ms", "endgame_rows_share", "ramp_sample_row_share"}
    assert mine == (q8 - no_ramp_no_endgame) | set(helpers_cat.CAT_METRICS)
    for name in helpers_cat.CAT_METRICS:
        # row_update_kernel_roofline holds for any fused routing: PR 38's cell reports it too
        assert mf.find_named(m["per_layer"], name, "metric")["workloads"][0] == cell["name"]
    cfg = mf.load_json(f"{helpers.REPO}/chipbench/configs/criteo-kaggle-cat-q8.json")
    assert cfg["reduced"] == ["num_trees"] and cfg["data"]["rows"] == 45_840_617
    assert cfg["data"]["rows"] == cfg["upstream"]["rows"]
    assert cfg["params"]["categorical_feature"] == list(range(13, 39))
    assert set(cfg["limits"]) == {
        "leaf_count_diff", "leaf_value_gap", "split_gain_gap", "split_gain_median_gap",
        "train_score_gap", "heldout_pred_gap", "cat_law_violations", "cat_search_gap"}
    spec = datagen_ctr.CtrSpec(cfg["data"])
    assert spec.blocks == 175 and max(spec.ids(j) for j in range(26)) == 65536
    assert validate.validate(helpers_cat.make_root(str(tmp_path))) == []
