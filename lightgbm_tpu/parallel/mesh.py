"""Device mesh helpers (replaces reference Network::Init bootstrap,
src/network/linkers_socket.cpp machine-list TCP handshake — on TPU the mesh
is declared, XLA routes collectives over ICI/DCN)."""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..learner.serial import GrownTree

__all__ = ["get_mesh", "shard_rows", "replicate", "tree_specs",
           "shard_wave_grower", "shard_masked_grower"]


def get_mesh(num_devices: int = 0, axis_name: str = "workers") -> Mesh:
    """1-D mesh over visible devices (the GBDT parallelism axis — the analog
    of the reference's num_machines rank space)."""
    devs = jax.devices()
    if num_devices and num_devices > 0:
        devs = devs[:num_devices]
    return Mesh(np.array(devs), (axis_name,))


def shard_rows(mesh: Mesh, arr, axis_name: str = "workers", dim: int = 0):
    """Place an array on the mesh with its row dimension ``dim`` sharded
    (the data-parallel layout; ``dim=1`` for feature-major bin matrices).
    A no-op for an array that already lives there, so the row-sharded
    learners call it on every per-row operand: whatever the boosting
    loop created on the mesh stays put, anything else is scattered here,
    once, instead of silently inside each grower call."""
    spec = P(*([None] * dim), axis_name)
    return jax.device_put(arr, NamedSharding(mesh, spec))


def replicate(mesh: Mesh, arr):
    return jax.device_put(arr, NamedSharding(mesh, P()))


def tree_specs(axis) -> GrownTree:
    """``shard_map`` out_specs of a grown tree: every field replicated
    but ``row_leaf``, which stays with its rows on ``axis`` (``None``
    where the rows are not sharded), and the three per-shard counters
    (``hist_rows_contracted``, ``pass_log``, ``ramp_sample``), one row a
    shard."""
    return GrownTree(
        split_feature=P(), threshold_bin=P(), nan_bin=P(),
        cat_member=P(), decision_type=P(), left_child=P(),
        right_child=P(), split_gain=P(), internal_value=P(),
        internal_weight=P(), internal_count=P(), leaf_value=P(),
        leaf_weight=P(), leaf_count=P(), num_leaves=P(),
        row_leaf=P(axis), hist_passes=P(), wave_passes=P(),
        endgame_passes=P(), ramp_committed=P(),
        hist_rows_contracted=P(axis), pass_log=P(axis),
        ramp_sample=P(axis))


def shard_wave_grower(grow, mesh, axis: str, *, n_keys: int = 0,
                      lazy: bool = False):
    """The wave grower as ONE program over row shards, for a ``grow``
    that takes, in this order: the feature-major bins ``(F, N)`` sharded
    along N; grad, hess and mask sharded; six replicated per-feature
    operands (num_bins, is_cat, has_nan, monotone and, in the caller's
    order, feature_mask and cegb_penalty); ``n_keys`` replicated PRNG
    keys; with ``lazy``, the lazy-CEGB bitmap ``(F, N/8)`` sharded like
    the bins.  It returns a ``GrownTree`` (and the bitmap)."""
    by_rows, by_cols = P(axis), P(None, axis)
    out = tree_specs(axis)
    return jax.jit(jax.shard_map(
        grow, mesh=mesh,
        in_specs=(by_cols,) + (by_rows,) * 3 + (P(),) * (6 + n_keys) +
        ((by_cols,) if lazy else ()),
        out_specs=(out, by_cols) if lazy else out, check_vma=False))


def shard_masked_grower(grow_t, mesh, axis: str, *, by_features=False):
    """The masked sequential grower (``make_grow_fn`` with a strategy,
    ``jit=False``) as one program over the mesh: the row-major bins and
    the per-row vectors sharded by rows or, ``by_features``, the bins by
    columns and the rows whole on every device.  The per-feature operands
    reach the grower as FULL replicated arrays (global feature indexing;
    a strategy slices per shard)."""
    def grow(X, g, h, m, nb, ic, hn, mono, fm):
        return grow_t(X, None, g, h, m, nb, ic, hn, mono, fm)
    rows = P() if by_features else P(axis)
    return jax.jit(jax.shard_map(
        grow, mesh=mesh,
        in_specs=(P(None, axis) if by_features else rows,) + (rows,) * 3 +
        (P(),) * 5,
        out_specs=tree_specs(None if by_features else axis),
        check_vma=False))
