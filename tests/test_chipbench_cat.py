"""The benchmark's integer + categorical cell at a tiny size, in the tier-1
suite: q8, rows routed by the row-update kernel's categorical slots, the
driver ``train_loop_cat``, judged ``correct`` by ``chipbench/reference_cat.py``
(bitsets and NaN directions walked on raw values, the categorical search
redone in float64); the control and the four planted faults read ``correct``
false, each by the number that is there to catch it; a program whose record
states no grower paths ends before any data is made.  With them the generator,
the reference on a hand-made tree and the row update's roofline.  The tests
are ``chipbench/tests``' own, run here too so that the suite the driver counts
holds the deployment to its reference."""

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
    test_the_probe_leaves_no_histogram_variant_to_a_timing_of_64_rows,
    test_the_manifest_with_five_cells_passes)
