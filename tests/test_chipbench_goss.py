"""The benchmark's ``boosting=goss`` cell at a tiny size, in the tier-1 suite:
q8, the draw through the one jitted sampler, the driver ``train_loop_goss``,
judged ``correct`` by ``chipbench/reference_goss.py`` (the sample's law and
the sampled trees' weighted sums); the control and the four planted faults
read ``correct`` false, each by the number that is there to catch it; a
program without ``last_sample()`` ends before any data is made; a window tree
that was not sampled ends the run.  The tests are
``chipbench/tests/test_run_goss.py``'s own, run here too so that the suite the
driver counts holds the deployment to its reference."""

from chipbench.tests.test_run_goss import (  # noqa: F401
    cpu_stands_in, test_a_planted_fault_is_not_correct,
    test_a_program_without_last_sample_ends_before_any_data_is_made,
    test_a_sound_goss_run_is_correct,
    test_a_traced_goss_run_reports_the_metrics_it_can_read,
    test_a_window_tree_that_was_not_sampled_ends_the_run,
    test_the_manifest_with_the_goss_cell_passes,
    test_warm_up_trees_that_do_not_match_the_mix_end_the_run)
