"""Voting-parallel tree learner (PV-Tree): data-parallel with top-k voting.

TPU-native re-implementation of the reference VotingParallelTreeLearner
(reference: src/treelearner/voting_parallel_tree_learner.cpp — local top-k
vote, Allgather of compact LightSplitInfo :322, ``GlobalVoting`` picks the
global top-2k features :151, ``CopyLocalHistogram`` into the reduce-scatter
layout :184, full scan only on aggregated features; local min_data /
min_hessian scaled by 1/num_machines :62-63; paper: Meng et al., "A
Communication-Efficient Parallel Algorithm for Decision Tree", NIPS 2016).

Rows are sharded like data-parallel, but instead of reducing the full
(F, B, 3) histogram, each shard votes its top-k features (``lax.top_k`` on
local gains), votes are combined with an ``all_gather`` of k feature ids per
shard, and only the winning 2k features' histogram slices are ``psum``'d —
the communication volume drops from F*B to 2k*B per leaf, the whole point of
the algorithm."""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..config import Config
from ..learner.serial import (CommStrategy, GrownTree, WaveTreeLearner,
                              local_best_candidate, make_grow_fn,
                              hist_pool_fits, resolve_hist_impl,
                              resolve_monotone_method,
                              split_params_from_config)
from ..ops.split import NEG_INF, best_split_per_feature
from ..analysis.contracts import collective_contract, memory_budget
from ..telemetry.train_record import note_collective
from .mesh import get_mesh, shard_masked_grower

__all__ = ["VotingParallelTreeLearner", "VotingStrategy",
           "WaveVotingStrategy", "QuantizedGradUnsupportedError",
           "modeled_pass_bytes", "voting_favored"]


class QuantizedGradUnsupportedError(ValueError):
    """use_quantized_grad requested on a grower that cannot honor it.

    The WAVE voting learner trains quantized for real (int32 voted
    slices psum exactly); only the masked sequential fallback cannot —
    and silently downgrading to exact gradients there would make two
    'identical' configs train different models."""


def _vote_budget(ctx):
    return 8 * max(2, int(ctx.get("leaves", 2)))


def _voted_hist_bytes(ctx):
    """The PV-Tree refinement (arXiv:1611.01276): only the voted top-2k
    features' histograms cross the wire — a (2k, B, 3) psum replacing
    the (F, B, 3) merge; 2k defaults to ctx['top_k']*2 but never exceeds
    the full feature space."""
    two_k = min(2 * int(ctx.get("top_k", 10)), int(ctx["features"]))
    return two_k * int(ctx["bins"]) * 3 * int(ctx.get("itemsize", 4))


collective_contract("voting_parallel/leaf_sum", "psum",
                    max_count=_vote_budget, max_bytes_per_op=256)
collective_contract("voting_parallel/vote_allgather", "all_gather",
                    max_count=_vote_budget,
                    max_bytes_per_op=lambda ctx: 8 * int(
                        ctx.get("top_k", 10)),
                    note="local top-k feature ids, O(k) ints")
collective_contract("voting_parallel/voted_hist_psum", "psum",
                    max_count=_vote_budget,
                    max_bytes_per_op=_voted_hist_bytes,
                    note="top-2k voted feature histograms only")


# ---------------------------------------------------------------------------
# Contracts for the WAVE voting learner's sites (WaveVotingStrategy below;
# the per-wave machinery lives in learner/wave.py _voting_candidates).
# Counts mirror the DP-wave merge budget: one vote + one voted psum per
# candidate-scan site (root / wave body / endgame, plus the spec-ramp
# provisional passes), because the voted merge IS the merge on this path.
# Cross-host (DCN) limits: on a host-major 1-D mesh a hierarchical
# collective moves (H-1)/H of the payload over DCN — declared explicitly
# so lint-trace at abstract W=64 bounds the pod bytes, not just the
# per-op payload (analysis/contracts.py max_dcn_bytes_per_op).
# ---------------------------------------------------------------------------

def _wave_vote_budget(ctx):
    from ..learner.wave import _wave_merge_budget
    return _wave_merge_budget(ctx)


def _wave_vote_ids_bytes(ctx):
    """all_gather operand: (k_leaves, top_k) int32 feature ids — O(W*k)
    ints, never a histogram."""
    from ..learner.wave import WAVE_SIZE
    w = int(ctx.get("wave_size", WAVE_SIZE))
    return (4 * max(2 * w, int(ctx.get("leaves", 2 * w))) *
            int(ctx.get("top_k", 10)))


def _wave_voted_batch_bytes(ctx):
    """The voted merge payload: (k_leaves, min(2k, F), B, 3) selected
    slices — the 2k/F refinement of the full (k_leaves, F, B, 3) psum."""
    from ..learner.wave import WAVE_SIZE
    w = int(ctx.get("wave_size", WAVE_SIZE))
    two_k = min(2 * int(ctx.get("top_k", 10)), int(ctx["features"]))
    return (max(2 * w, int(ctx.get("leaves", 2 * w))) * two_k *
            int(ctx["bins"]) * 3 * int(ctx.get("itemsize", 4)))


def _dcn(limit):
    """DCN ceiling: the modeled cross-host share — (H-1)/H on a
    host-major axis, analysis.contracts.dcn_fraction — of a payload."""
    def dcn_bytes(ctx):
        from ..analysis.contracts import dcn_fraction
        base = limit(ctx) if callable(limit) else limit
        return base * dcn_fraction(ctx)
    return dcn_bytes


def _wave_exchange_bytes(ctx):
    from ..learner.wave import _exchange_payload_bytes
    return _exchange_payload_bytes(ctx)


def _wave_full_batch_bytes(ctx):
    from ..learner.wave import _hist_batch_bytes
    return _hist_batch_bytes(ctx)


collective_contract(
    "voting_parallel/wave/vote_allgather", "all_gather",
    max_count=_wave_vote_budget, max_bytes_per_op=_wave_vote_ids_bytes,
    max_dcn_bytes_per_op=_dcn(_wave_vote_ids_bytes),
    note="local top-k feature-id vote per scan site, O(W*k) ints")
collective_contract(
    "voting_parallel/wave/voted_hist_psum", "psum",
    max_count=_wave_vote_budget, max_bytes_per_op=_wave_voted_batch_bytes,
    max_dcn_bytes_per_op=_dcn(_wave_voted_batch_bytes),
    note="voted top-2k feature slices only — the PV-Tree merge")
collective_contract(
    "voting_parallel/wave/hist_psum", "psum",
    max_count=_wave_vote_budget, max_bytes_per_op=_wave_full_batch_bytes,
    max_dcn_bytes_per_op=_dcn(_wave_full_batch_bytes),
    note="full-batch fallback merge for voting-gated shapes (cats/EFB)")
collective_contract(
    "voting_parallel/wave/scalar_sum", "psum",
    max_count=8, max_bytes_per_op=_wave_exchange_bytes,
    max_dcn_bytes_per_op=_dcn(_wave_exchange_bytes),
    note="leaf totals / root sums — small vectors only")
collective_contract(
    "voting_parallel/wave/quant_scale", "pmax",
    max_count=2, max_bytes_per_op=8, max_dcn_bytes_per_op=8,
    note="global gradient/hessian quantization scales (two scalars)")


# ---------------------------------------------------------------------------
# Memory budget for the voting-wave program (lint-mem enforced).  Voting
# trades WIRE bytes, not resident bytes: every device keeps FULL-F local
# kernel banks AND the full-F per-leaf pool (only the voted 2k slices
# are psum'd), so unlike the scatter path there is no post-merge F/k
# slicing — the pool and scan temporaries stay on all F features.
# ---------------------------------------------------------------------------

def voting_wave_hbm_bytes(ctx):
    """Per-device HBM curve of one voting-wave tree program: the DP
    local-bank term plus pool/scan temporaries on FULL F (the voted
    merge never slices the resident histograms)."""
    from ..learner.wave import Q_WAVE_SIZE, WAVE_SIZE
    from ..analysis.contracts import world_size
    k = world_size(ctx)
    f = int(ctx["features"])
    b = int(ctx["bins"])
    it = int(ctx.get("itemsize", 4))
    r = -(-int(ctx["rows"]) // k)
    wave = int(ctx.get("wave_size", WAVE_SIZE))
    kernel_ch = Q_WAVE_SIZE if ctx.get("quantized", True) else WAVE_SIZE
    local_banks = int(2.5 * max(2 * wave, kernel_ch) * f * b * 3 * it)
    pool = (int(ctx.get("leaves", 2)) + 6 * wave) * f * b * 3 * it
    rows = r * (f + 24)
    return local_banks + pool + rows + (1 << 20)


memory_budget(
    "voting_parallel/wave_full", ("voting",), voting_wave_hbm_bytes,
    note="2.5 local full-F kernel banks + full-F pool/scan (voting "
         "slices the wire, not the residents) + rows")


# ---------------------------------------------------------------------------
# Modeled bytes per histogram pass: the auto-selection rule and the
# multichip artifact both read this ONE model, so the CI snapshot and the
# learner pick cannot drift.
# ---------------------------------------------------------------------------

def modeled_pass_bytes(num_features: int, bins: int, top_k: int,
                       world: int, *, wave: int = 0, itemsize: int = 4,
                       devices_per_host: int = 8) -> dict:
    """Modeled per-pass histogram-merge bytes for the DP reduce-scatter
    path vs the voting path at world size ``world``, split per-host
    (ICI) vs cross-host (DCN) assuming a host-major 1-D axis with
    ``devices_per_host`` devices per host.

    Reduce-scatter moves the whole (W, F, B, 3) batch once around the
    ring (each shard receives its F/k block fully reduced); voting moves
    the O(k) vote ids plus the (W, 2k, B, 3) selected slices, allreduced
    (2x a reduce-scatter's volume for the slice payload)."""
    from ..learner.wave import WAVE_SIZE
    w = int(wave) or WAVE_SIZE
    hosts_ = max(1, int(world) // max(1, int(devices_per_host)))
    dcn = (hosts_ - 1) / hosts_ if hosts_ > 1 else 0.0
    two_k = min(2 * int(top_k), int(num_features))
    ch = 3 * int(itemsize) * int(bins) * w
    full = int(num_features) * ch          # (W, F, B, 3) batch bytes
    voted = two_k * ch                     # (W, 2k, B, 3) voted slices
    vote_ids = 4 * w * int(top_k) * int(world)   # gathered id payload
    rs_total = full                        # reduce-scatter: ~1x volume
    vote_total = 2 * voted + vote_ids      # allreduce: ~2x + the vote
    return {
        "world": int(world),
        "hosts": hosts_,
        "reduce_scatter": {
            "total": rs_total,
            "cross_host": int(rs_total * dcn),
            "per_host": int(rs_total * (1.0 - dcn)),
        },
        "voting": {
            "total": vote_total,
            "cross_host": int(vote_total * dcn),
            "per_host": int(vote_total * (1.0 - dcn)),
        },
        "voted_full_ratio": voted / full,
    }


#: world size at or above which ``tree_learner=auto`` considers voting
AUTO_VOTING_MIN_WORLD = 4


def voting_favored(num_features: int, bins: int, top_k: int,
                   world: int, **kw) -> bool:
    """The ``tree_learner=auto`` flip rule: voting wins when its modeled
    CROSS-HOST bytes per pass undercut the reduce-scatter path's (PV-Tree
    is a DCN optimisation — on a single host the scatter path's exact
    merge is strictly better)."""
    if int(world) < AUTO_VOTING_MIN_WORLD:
        return False
    m = modeled_pass_bytes(num_features, bins, top_k, world, **kw)
    if m["hosts"] > 1:
        return m["voting"]["cross_host"] < m["reduce_scatter"]["cross_host"]
    return m["voting"]["total"] < m["reduce_scatter"]["total"]


class WaveVotingStrategy(CommStrategy):
    """Row-sharded strategy for the WAVE grower with the PV-Tree voted
    merge (learner/wave.py use_voting): the per-leaf histogram pool stays
    shard-LOCAL and each candidate scan votes, all_gathers O(k) feature
    ids and psums only the voted top-2k feature slices — per-leaf wire
    volume drops from F*B to 2k*B, the communication-efficient recipe
    for DCN-bound pod meshes (arXiv:1611.01276).

    Voting-gated shapes (cats / EFB / lazy CEGB / forced splits) fall
    back to ``reduce_hist``'s full-batch psum, so every config still
    trains correctly.  ``spec_ok`` unlocks the speculative ramp: the
    provisional passes vote exactly like committed waves."""

    rows_sharded = True
    spec_ok = True
    hist_voting = True

    def __init__(self, axis_name: str, nshards: int = 1, top_k: int = 20,
                 local_params=None):
        self.axis_name = axis_name
        self.nshards = int(nshards)
        self.top_k = int(top_k)
        self.local_params = local_params
        self.monotone_full = None

    def reduce_sum(self, v):
        note_collective("voting_parallel/wave/scalar_sum", "psum", v)
        return jax.lax.psum(v, self.axis_name)

    def reduce_max(self, v):
        """Global quantization scales (shared with the DP wave path)."""
        note_collective("voting_parallel/wave/quant_scale", "pmax", v)
        return jax.lax.pmax(v, self.axis_name)

    def shard_key(self, key):
        """Independent stochastic-rounding streams per row shard."""
        return jax.random.fold_in(key, jax.lax.axis_index(self.axis_name))

    def reduce_hist(self, hist):
        # fallback full-batch merge for the voting-gated configs — and
        # the single collective those configs pay per wave
        note_collective("voting_parallel/wave/hist_psum", "psum", hist)
        return jax.lax.psum(hist, self.axis_name)

    def vote_allgather(self, top_ids):
        """(k_leaves, top_k) local winner ids -> (nshards, k_leaves,
        top_k): the ONLY full-world exchange the vote needs."""
        note_collective("voting_parallel/wave/vote_allgather",
                        "all_gather", top_ids)
        return jax.lax.all_gather(top_ids, self.axis_name)

    def reduce_hist_voted(self, sel):
        """Exact merge of the voted (k_leaves, 2k, B, 3) slices —
        int32 under quantized gradients, so the sum is order-free."""
        note_collective("voting_parallel/wave/voted_hist_psum", "psum",
                        sel)
        return jax.lax.psum(sel, self.axis_name)


class VotingStrategy(CommStrategy):
    rows_sharded = True
    def __init__(self, axis_name, top_k, num_features, ndev,
                 num_bins, is_cat, has_nan, local_params):
        super().__init__(num_bins, is_cat, has_nan)
        self.axis_name = axis_name
        self.top_k = top_k
        self.num_features = num_features
        self.ndev = ndev
        self.local_params = local_params  # 1/num_machines-scaled constraints

    def reduce_sum(self, v):
        note_collective("voting_parallel/leaf_sum", "psum", v)
        return jax.lax.psum(v, self.axis_name)

    # reduce_hist stays identity: the pool keeps shard-LOCAL histograms and
    # only voted features are aggregated below.

    def leaf_candidates(self, hist_local, leaf_sum, feature_mask, params,
                        bound=None, depth=None, parent_out=None):
        k = self.top_k
        # 1. local candidate gains with relaxed (1/num_machines) constraints
        #    (voting_parallel_tree_learner.cpp:62-63)
        local_sum = leaf_sum / self.ndev
        fs = best_split_per_feature(hist_local, local_sum, self.num_bins_full,
                                    self.is_cat_full, self.has_nan_full,
                                    self.local_params, self.monotone_full,
                                    bound, depth, parent_out=parent_out)
        gain = jnp.where(feature_mask, fs.gain, NEG_INF)
        # 2. local top-k vote -> allgather (LightSplitInfo allgather :322)
        _, top_ids = jax.lax.top_k(gain, k)
        note_collective("voting_parallel/vote_allgather", "all_gather",
                        top_ids)
        all_ids = jax.lax.all_gather(top_ids, self.axis_name)  # (ndev, k)
        # 3. global voting: feature vote counts, top-2k selected
        #    (GlobalVoting :151); ties break toward lower feature index via
        #    a small index-based epsilon
        votes = jnp.zeros((self.num_features,), jnp.float32).at[
            all_ids.reshape(-1)].add(1.0, mode="drop")
        anti_index = -jnp.arange(self.num_features, dtype=jnp.float32) * 1e-6
        _, selected = jax.lax.top_k(votes + anti_index, min(2 * k,
                                                           self.num_features))
        # 4. aggregate only the selected features' histograms (the 2k*B psum
        #    replacing the F*B reduce-scatter)
        sel_local = hist_local[selected]
        note_collective("voting_parallel/voted_hist_psum", "psum",
                        sel_local)
        hist_sel = jax.lax.psum(sel_local, self.axis_name)
        nb = self.num_bins_full[selected]
        ic = self.is_cat_full[selected]
        hn = self.has_nan_full[selected]
        fm = feature_mask[selected]
        mono = self.monotone_full[selected] \
            if self.monotone_full is not None else None
        g, f_loc, b, dl, ls, rs, member = local_best_candidate(
            hist_sel, leaf_sum, nb, ic, hn, fm, params, mono, bound, depth, parent_out=parent_out)
        return (g, selected[f_loc], b, dl, ls, rs, member)

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


class VotingParallelTreeLearner(WaveTreeLearner):
    """Two growers, like the DP learner: the WAVE grower with the voted
    merge (first-class: quantized gradients, exact endgame, spec ramp —
    ``WaveTreeLearner`` with a ``WaveVotingStrategy``, learner/wave.py
    use_voting) and the masked sequential grower with per-scan voting
    (VotingStrategy; off-TPU fallback).  The masked fallback cannot train
    quantized — that combo raises QuantizedGradUnsupportedError instead
    of silently training a different model."""

    name = "voting"
    rows_sharded = True  # models/gbdt.py places per-row arrays on the mesh

    def __init__(self, config: Config, num_features: int, max_bins: int,
                 num_bins: np.ndarray, is_cat: np.ndarray, has_nan: np.ndarray,
                 monotone: Optional[np.ndarray] = None):
        mesh = get_mesh(int(config.num_devices))
        ndev = mesh.devices.size
        self.top_k = max(1, min(int(config.top_k), num_features))
        sp = split_params_from_config(config, num_bins, is_cat)
        local_sp = sp._replace(
            min_data_in_leaf=max(1, sp.min_data_in_leaf // ndev),
            min_sum_hessian_in_leaf=sp.min_sum_hessian_in_leaf / ndev)
        mode = str(config.tree_grow_mode)
        impl_wave = resolve_hist_impl(config, parallel=True, wave=True,
                                      max_bins=int(max_bins))
        wave_able = (int(config.num_leaves) > 2 and
                     hist_pool_fits(config, num_features, int(max_bins)))
        wave = wave_able and (mode == "wave" or
                              (mode == "auto" and impl_wave == "pallas"))
        if not wave and config.use_quantized_grad and wave_able \
                and mode != "partition":
            # quantized voting is a wave-grower feature; ride it rather
            # than refuse when the config merely defaulted off-TPU
            wave = True
        if wave:
            # voting gates cats / lazy CEGB / forced splits off inside the
            # grower (full-batch psum fallback); the wave scan still runs
            # in full feature space
            super().__init__(
                config, num_features, max_bins, num_bins, is_cat, has_nan,
                monotone, hist_impl=impl_wave, mesh=mesh,
                strategy=WaveVotingStrategy(
                    mesh.axis_names[0], nshards=ndev, top_k=self.top_k,
                    local_params=local_sp))
            return
        self._describe(config, num_features, max_bins, num_bins, is_cat,
                       has_nan, monotone, mesh=mesh)
        self.supports_extras = False
        if config.use_quantized_grad:
            raise QuantizedGradUnsupportedError(
                "use_quantized_grad with tree_learner=voting requires the "
                "wave grower (tree_grow_mode=wave, or auto on TPU); the "
                "masked voting grower trains exact gradients only — "
                "drop use_quantized_grad or enable the wave grower")
        resolve_monotone_method(config, sp.use_monotone, wave=False)
        strategy = VotingStrategy(self.axis, self.top_k, num_features,
                                  self.ndev, self.num_bins, self.is_cat,
                                  self.has_nan, local_sp)
        self._grow = shard_masked_grower(make_grow_fn(
            num_leaves=int(config.num_leaves), max_bins=self.max_bins,
            max_depth=int(config.max_depth), split_params=sp,
            hist_impl=resolve_hist_impl(config, parallel=True),
            rows_per_chunk=int(config.tpu_rows_per_chunk),
            use_hist_pool=hist_pool_fits(config, num_features, self.max_bins),
            strategy=strategy, jit=False), mesh, self.axis)

    def _train_other(self, X_dev, grad, hess, sample_mask, feature_mask,
                     cegb_penalty, node_key) -> GrownTree:
        n = X_dev.shape[0]
        pad = (-n) % self.ndev
        if pad:
            X_dev = jnp.pad(X_dev, ((0, pad), (0, 0)))
            grad = jnp.pad(grad, (0, pad))
            hess = jnp.pad(hess, (0, pad))
            sample_mask = jnp.pad(sample_mask, (0, pad))
        grown = self._grow(X_dev, grad, hess, sample_mask, self.num_bins,
                           self.is_cat, self.has_nan, self.monotone,
                           feature_mask)
        if pad:
            grown = grown._replace(row_leaf=grown.row_leaf[:n])
        return grown
