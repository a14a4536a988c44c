"""Distributed tree learners over a JAX device mesh.

TPU-native replacement for the reference's network layer + parallel learners
(reference: src/network/ socket/MPI collectives + src/treelearner/
parallel_tree_learner.h — see SURVEY.md §2.5's mapping note): the Bruck
allgather / recursive-halving reduce-scatter over TCP/MPI collapse into
``jax.lax`` collectives (psum / all_gather / reduce_scatter semantics) over
ICI/DCN inside ``shard_map``; ``jax.distributed.initialize`` replaces the
machine-list bootstrap.
"""

from __future__ import annotations

from .data_parallel import DataParallelTreeLearner
from .feature_parallel import FeatureParallelTreeLearner
from .voting_parallel import VotingParallelTreeLearner
from .mesh import get_mesh


def create_parallel_learner(config, num_features, max_bins, num_bins, is_cat,
                            has_nan, monotone=None, interaction_groups=(),
                            cegb_lazy=(), forced_splits=(),
                            feature_contri=(), acc_rows=0):
    """Factory (reference tree_learner.h:104 TreeLearner::CreateTreeLearner
    dispatching on tree_learner type)."""
    kind = config.tree_learner
    cls = {
        "data": DataParallelTreeLearner,
        "feature": FeatureParallelTreeLearner,
        "voting": VotingParallelTreeLearner,
    }.get(kind)
    if cls is None:
        raise ValueError(f"Unknown tree_learner: {kind}")
    import jax
    if get_mesh(int(config.num_devices)).devices.size == 1 and \
            jax.process_count() == 1:
        # a parallel learner over a 1-device mesh IS the serial learner
        # with collective overhead on top — the reference likewise runs
        # serial when num_machines == 1 (application.cpp).  Fall back so
        # single-chip runs of parallel configs get the fast wave path.
        from ..utils.log import log_info
        from ..learner.serial import SerialTreeLearner
        log_info(f"tree_learner={kind} on a single-device mesh: using "
                 "the serial learner (no collectives needed)")
        return SerialTreeLearner(
            config, num_features, max_bins, num_bins, is_cat, has_nan,
            monotone, forced_splits,
            interaction_groups=interaction_groups, cegb_lazy=cegb_lazy,
            feature_contri=feature_contri, acc_rows=acc_rows)
    # feature_contri stops here for every mesh learner: inherited, not
    # chosen (ROADMAP D2: honour or raise)
    if kind == "data":
        return cls(config, num_features, max_bins, num_bins, is_cat,
                   has_nan, monotone, interaction_groups=interaction_groups,
                   cegb_lazy=cegb_lazy, forced_splits=forced_splits,
                   acc_rows=acc_rows)
    if interaction_groups or cegb_lazy or forced_splits:
        from ..utils.log import log_warning
        log_warning("interaction_constraints / cegb_penalty_feature_lazy / "
                    "forcedsplits_filename are applied by the serial and "
                    "data-parallel learners only; this learner ignores them")
    return cls(config, num_features, max_bins, num_bins, is_cat, has_nan,
               monotone)
