"""The benchmark's four-device cell at a tiny size, in the tier-1 suite (its 8
CPU devices, a mesh of 4): ``tree_learner=data``, q8, the ramp as it defaults,
rows fed in blocks through the driver ``train_loop_blocks``, judged ``correct``
by the plain reference; one shard's histograms left out of the merge reads
``correct`` false; a program without block input ends before any data is
made.  The tests are ``chipbench/tests/test_run_blocks_dp4.py``'s own, run
here too so that the suite the driver counts holds the deployment to its
reference."""

from chipbench.tests.test_run_blocks_dp4 import (  # noqa: F401
    cpu_stands_in, test_a_dropped_shard_is_not_correct,
    test_a_mesh_other_than_the_configurations_ends_the_run,
    test_a_program_without_block_input_ends_before_any_data_is_made,
    test_a_sound_run_on_the_mesh_is_correct,
    test_a_traced_run_reports_the_mesh_metrics_it_can_read)
