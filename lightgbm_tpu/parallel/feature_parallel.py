"""Feature-parallel tree learner: the feature axis sharded over the mesh.

TPU-native re-implementation of the reference FeatureParallelTreeLearner
(reference: src/treelearner/feature_parallel_tree_learner.cpp — features
partitioned per machine :40-56, local best split on owned features, global
best via ``SyncUpGlobalBestSplit`` allreduce-max, parallel_tree_learner.h:
191-214, then all machines split identically).

The reference keeps FULL data on every machine and partitions only the
histogram/split work.  On a TPU mesh we go further and shard the binned
matrix itself column-wise (halving HBM per chip as the mesh grows): the
winning split's bin column — which only its owner holds — is broadcast with
one (N,)-int psum per split, the FP analog of the reference's tiny
per-split allreduce.

Cross-device argmax uses pmax on gain + pmin on the encoded feature index
for deterministic tie-breaking (the SplitInfo comparison ladder,
split_info.hpp:280)."""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..config import Config
from ..learner.serial import (CommStrategy, GrownTree, local_best_candidate,
                              make_grow_fn, hist_pool_fits, resolve_hist_impl,
                              split_params_from_config)
from ..analysis.contracts import collective_contract
from ..telemetry.train_record import note_collective
from .mesh import get_mesh, shard_masked_grower

__all__ = ["FeatureParallelTreeLearner", "FeatureParallelStrategy"]

BIG_FEAT = np.int32(2 ** 30)


def _per_split_budget(ctx):
    """Candidate-scan collectives trace once per scan SITE, not per
    executed split (the grower's while body traces once); scan sites are
    bounded by a small multiple of the static leaf budget."""
    return 8 * max(2, int(ctx.get("leaves", 2)))


# The FP learner's wire profile (SyncUpGlobalBestSplit + owner column
# broadcast): winner scalars/payloads per scan site plus one (N,)-sized
# column psum per committed split — never a histogram.
collective_contract("feature_parallel/best_gain", "pmax",
                    max_count=_per_split_budget, max_bytes_per_op=64)
collective_contract("feature_parallel/best_feature", "pmin",
                    max_count=_per_split_budget, max_bytes_per_op=64)
collective_contract("feature_parallel/winner_bcast", "psum",
                    max_count=_per_split_budget, max_bytes_per_op=256,
                    note="winner payload scalars/vectors (SplitInfo)")
collective_contract("feature_parallel/column_bcast", "psum",
                    max_count=_per_split_budget,
                    note="owner broadcast of the winning bin column; "
                         "O(N) by design, unbounded bytes")


class FeatureParallelStrategy(CommStrategy):
    def __init__(self, axis_name, f_local, num_bins_full, is_cat_full,
                 has_nan_full):
        super().__init__(num_bins_full, is_cat_full, has_nan_full)
        self.axis_name = axis_name
        self.f_local = f_local

    def _local_slices(self):
        r = jax.lax.axis_index(self.axis_name)
        start = r * self.f_local
        sl = lambda a: jax.lax.dynamic_slice(a, (start,), (self.f_local,))
        return sl(self.num_bins_full), sl(self.is_cat_full), \
            sl(self.has_nan_full), start

    def leaf_candidates(self, hist_local, leaf_sum, feature_mask, params,
                        bound=None, depth=None, parent_out=None):
        nb, ic, hn, start = self._local_slices()
        r = jax.lax.axis_index(self.axis_name)
        fm = jax.lax.dynamic_slice(feature_mask, (r * self.f_local,),
                                   (self.f_local,))
        mono = jax.lax.dynamic_slice(self.monotone_full,
                                     (r * self.f_local,), (self.f_local,)) \
            if self.monotone_full is not None else None
        g, f_loc, b, dl, ls, rs, member = local_best_candidate(
            hist_local, leaf_sum, nb, ic, hn, fm, params, mono, bound, depth, parent_out=parent_out)
        # global best with deterministic tie-break on the feature index
        # (reference SyncUpGlobalBestSplit allreduce-max)
        note_collective("feature_parallel/best_gain", "pmax", g)
        gmax = jax.lax.pmax(g, self.axis_name)
        f_glob = start.astype(jnp.int32) + f_loc
        cand = jnp.where(g >= gmax, f_glob, BIG_FEAT)
        note_collective("feature_parallel/best_feature", "pmin", cand)
        f_win = jax.lax.pmin(cand, self.axis_name)
        is_win = (f_glob == f_win) & (g >= gmax)

        def bcast(v):
            note_collective("feature_parallel/winner_bcast", "psum", v)
            return jax.lax.psum(
                jnp.where(is_win, v, jnp.zeros_like(v)), self.axis_name)

        return (gmax, f_win, bcast(b), bcast(dl.astype(jnp.int32)) > 0,
                bcast(ls), bcast(rs),
                bcast(member.astype(jnp.int32)) > 0)

    def pair_candidates(self, hist_l, hist_r, lsum, rsum, feature_mask,
                        params, bound_l, bound_r, depth, fm_l=None,
                        fm_r=None, po_l=None, po_r=None):
        # collectives are not vmap-batched: two sequential candidate calls
        return (self.leaf_candidates(
                    hist_l, lsum,
                    feature_mask if fm_l is None else fm_l, params,
                    bound_l, depth, po_l),
                self.leaf_candidates(
                    hist_r, rsum,
                    feature_mask if fm_r is None else fm_r, params,
                    bound_r, depth, po_r))

    def get_column(self, X_local, feat_global):
        r = jax.lax.axis_index(self.axis_name)
        owner = feat_global // self.f_local
        lidx = feat_global % self.f_local
        col = jnp.take(X_local, lidx, axis=1).astype(jnp.int32)
        col = jnp.where(r == owner, col, 0)
        note_collective("feature_parallel/column_bcast", "psum", col)
        return jax.lax.psum(col, self.axis_name)


class FeatureParallelTreeLearner:
    name = "feature"
    # what models/gbdt.py reads off any learner (learner/serial.py
    # WaveTreeLearner): rows stay whole on every device, train() takes
    # no cegb_penalty / node_key / quant_key
    rows_sharded = False
    supports_extras = False
    quantized = False

    def __init__(self, config: Config, num_features: int, max_bins: int,
                 num_bins: np.ndarray, is_cat: np.ndarray, has_nan: np.ndarray,
                 monotone: Optional[np.ndarray] = None):
        self.config = config
        if config.use_quantized_grad:
            from ..utils.log import log_warning
            log_warning("use_quantized_grad is only applied by the wave "
                        "grower (serial / tree_learner=data); training "
                        "with exact gradients")
        self.max_bins = int(max_bins)
        self.num_features = num_features
        self.mesh = get_mesh(int(config.num_devices))
        self.setup_seconds = {}   # nothing of its own to time
        self.ndev = self.mesh.devices.size
        self.axis = self.mesh.axis_names[0]
        # pad the feature axis to a multiple of the mesh (padded features are
        # trivial: 1 bin -> never splittable)
        self.f_pad = (-num_features) % self.ndev
        fp = num_features + self.f_pad
        self.f_local = fp // self.ndev
        self.num_bins = jnp.asarray(
            np.concatenate([num_bins, np.ones(self.f_pad, np.int32)]), jnp.int32)
        self.is_cat = jnp.asarray(
            np.concatenate([is_cat, np.zeros(self.f_pad, bool)]), jnp.bool_)
        self.has_nan = jnp.asarray(
            np.concatenate([has_nan, np.zeros(self.f_pad, bool)]), jnp.bool_)
        mono_np = monotone if monotone is not None else np.zeros(num_features)
        self.monotone = jnp.asarray(
            np.concatenate([mono_np, np.zeros(self.f_pad)]), jnp.int32)
        strategy = FeatureParallelStrategy(self.axis, self.f_local,
                                           self.num_bins, self.is_cat,
                                           self.has_nan)
        from ..learner.serial import resolve_monotone_method
        resolve_monotone_method(
            config, bool(config.monotone_constraints and
                         any(int(v) for v in config.monotone_constraints)),
            wave=False)
        # X is feature-sharded; rows + every descriptor replicated
        self._grow = shard_masked_grower(make_grow_fn(
            num_leaves=int(config.num_leaves), max_bins=self.max_bins,
            max_depth=int(config.max_depth),
            split_params=split_params_from_config(config, num_bins,
                                                  is_cat),
            hist_impl=resolve_hist_impl(config, parallel=True),
            rows_per_chunk=int(config.tpu_rows_per_chunk),
            use_hist_pool=hist_pool_fits(config, self.f_local, self.max_bins),
            strategy=strategy, jit=False), self.mesh, self.axis,
            by_features=True)

    def train(self, X_dev: jnp.ndarray, grad: jnp.ndarray, hess: jnp.ndarray,
              sample_mask: jnp.ndarray,
              feature_mask: Optional[jnp.ndarray] = None) -> GrownTree:
        if feature_mask is None:
            feature_mask = jnp.ones((self.num_features,), jnp.bool_)
        if self.f_pad:
            X_dev = jnp.pad(X_dev, ((0, 0), (0, self.f_pad)))
            feature_mask = jnp.pad(feature_mask, (0, self.f_pad))
        return self._grow(X_dev, grad, hess, sample_mask, self.num_bins,
                          self.is_cat, self.has_nan, self.monotone,
                          feature_mask)
