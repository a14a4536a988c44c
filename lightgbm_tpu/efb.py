"""Exclusive Feature Bundling (EFB) — one of the two core LightGBM tricks.

TPU-native re-implementation of the reference's bundling
(reference: src/io/dataset.cpp:53 ``GetConflictCount``, :100 ``FindGroups``
greedy conflict-bounded grouping, :239 ``FastFeatureBundling``, bin offsets
per feature inside a group à la feature_group.h).

TPU-first design: the DEVICE matrix holds one uint8 column per BUNDLE
(width ≈ bundle count, the whole point for wide-sparse data), histograms
are built and pooled in bundle space (G, Bb, 3).  Tree structure, split
finding, and the model format stay entirely in original-feature space, so
EFB is invisible outside training.

How the wave grower (learner/wave.py) reads bundles:

* **The split scan** (:func:`make_scan_expand`) reads member features
  where they lie in bundle space, by STATIC slices — nothing is moved by
  index on the chip.  A two-bin member of a bundle (an indicator column:
  the one-hot case bundling exists for) has one split, "its code against
  the rest", so it is scanned as one lane of a (3, Fn) plane cut out of
  the bundle histogram in runs of consecutive codes
  (ops/split.py ``best_split_two_bin``); every other feature (a singleton
  bundle, verbatim; a member with more bins, its codes shifted around the
  default bin) is laid out as (Fw, B, 3) for the scan the unbundled data
  takes.  Each member's default (zero) bin is restored from the leaf
  totals, the reference's Dataset::FixHistogram trick (dataset.cpp:1239).
  :func:`make_expand_hist`, the per-leaf (F, B) gather this replaced,
  stays for the partitioned grower, for forced splits, and as the test
  oracle of the slices.
* **The row update**: the codes stay uint8 (a bundle holds at most 255
  codes, 255 stays the no-NaN sentinel) and rows are routed by the fused
  kernel (ops/histogram_pallas.py ``wave_row_update_pallas``).  A slot
  whose split feature lives in a bundle hands the kernel the SET of
  bundle codes that go left (:func:`bundle_left_sets`: threshold, default
  bin and NaN direction folded in from ``f_offset`` / ``f_nbins`` /
  ``f_default``), through the 256-bit left sets the categorical slots
  carry.

Bundle bin layout: bundle bin 0 = "every member feature at its default
bin"; member feature f with nb_f bins gets the range
[offset_f, offset_f + nb_f - 1) for its non-default bins (the default is
elided).  Singleton bundles keep their feature's bins verbatim.  Where two
members of a bundle are both away from their default in one row (a
CONFLICT: at most ``CONFLICT_RATE`` of the binning sample a bundle), the
member with the larger feature id keeps the row and the other reads as
its default there; ``bundle_sparse_csc`` / ``bundle_binned_matrix`` count
those rows exactly (``BundleInfo.conflict_rows``).
"""

from __future__ import annotations

import dataclasses
from typing import Callable, List, NamedTuple, Sequence

import numpy as np


MAX_BUNDLE_BINS = 256    # uint8 device columns
CONFLICT_RATE = 1e-4     # max conflicting rows per bundle, as fraction of N


@dataclasses.dataclass
class BundleInfo:
    """Static bundling descriptors over INNER (used) features."""
    n_bundles: int
    bundle_bins: int                 # Bb: max bins over bundles
    f_bundle: np.ndarray             # (F,) bundle id per feature
    f_offset: np.ndarray             # (F,) non-default bin offset in bundle
    f_default: np.ndarray            # (F,) the feature's default bin
    f_nbins: np.ndarray              # (F,) the feature's bin count
    f_single: np.ndarray             # (F,) bool: singleton bundle (verbatim)
    exp_map: np.ndarray              # (F, B) flat bundle-bin id or -1
    fix_mask: np.ndarray             # (F,) bool: restore default via totals
    # rows of the bundled matrix in which a conflict overwrote a member's
    # non-default value (counted exactly where the matrix is built), and
    # the overwritten entries themselves, (row, feature) in writing order:
    # the values the training saw as the feature's default
    conflict_rows: int = 0
    conflict_entries: tuple = (np.zeros(0, np.int64), np.zeros(0, np.int32))

    @property
    def needs_fix(self) -> bool:
        return bool(self.fix_mask.any())

    def layout(self) -> tuple:
        """The bundle layout as a hashable static value (a grower built
        from it is cached under it): ``(f_bundle, f_offset, f_default,
        f_nbins, f_single)`` as tuples."""
        return tuple(tuple(int(v) for v in a) for a in (
            self.f_bundle, self.f_offset, self.f_default, self.f_nbins,
            self.f_single))

    def record(self) -> dict:
        """``TrainRecord.snapshot()["efb"]``: the bundling as the data
        set was built."""
        return {"features": int(len(self.f_bundle)),
                "bundles": int(self.n_bundles),
                "bundle_bins": int(self.bundle_bins),
                "bundled_features": int(np.count_nonzero(
                    np.logical_not(self.f_single))),
                "conflict_rows": int(self.conflict_rows)}


def find_bundles(mappers: Sequence, nondefault: List[np.ndarray], n_rows: int,
                 sample_rows: int,
                 max_bundle_bins: int = MAX_BUNDLE_BINS,
                 conflict_rate: float = CONFLICT_RATE) -> List[List[int]]:
    """Greedy conflict-bounded grouping (dataset.cpp:100 FindGroups).

    nondefault[f] is a bool mask over the SAMPLED rows where feature f is
    away from its default bin.  Returns bundles as lists of feature ids.
    """
    max_conflict = max(0, int(conflict_rate * sample_rows))
    counts = np.array([int(m.sum()) for m in nondefault])
    order = np.argsort(-counts, kind="stable")

    bundles: List[List[int]] = []
    bundle_mask: List[np.ndarray] = []
    bundle_conflict: List[int] = []
    bundle_bins: List[int] = []
    for f in order:
        nb_extra = int(mappers[f].num_bin) - 1
        placed = False
        for bi in range(len(bundles)):
            if bundle_bins[bi] + nb_extra >= max_bundle_bins:
                continue
            conflict = int(np.count_nonzero(bundle_mask[bi] & nondefault[f]))
            if bundle_conflict[bi] + conflict <= max_conflict:
                bundles[bi].append(int(f))
                bundle_mask[bi] |= nondefault[f]
                bundle_conflict[bi] += conflict
                bundle_bins[bi] += nb_extra
                placed = True
                break
        if not placed:
            bundles.append([int(f)])
            bundle_mask.append(nondefault[f].copy())
            bundle_conflict.append(0)
            bundle_bins.append(1 + nb_extra)
    return bundles


def build_bundle_info(mappers: Sequence, bundles: List[List[int]],
                      max_feature_bins: int) -> BundleInfo:
    F = len(mappers)
    B = max_feature_bins
    f_bundle = np.zeros(F, np.int32)
    f_offset = np.zeros(F, np.int32)
    f_default = np.asarray([int(m.default_bin) for m in mappers], np.int32)
    f_nbins = np.asarray([int(m.num_bin) for m in mappers], np.int32)
    f_single = np.zeros(F, bool)
    bb = 1
    for g, feats in enumerate(bundles):
        if len(feats) == 1:
            f = feats[0]
            f_bundle[f] = g
            f_offset[f] = 0
            f_single[f] = True
            bb = max(bb, int(f_nbins[f]))
        else:
            off = 1
            for f in feats:
                f_bundle[f] = g
                f_offset[f] = off
                off += int(f_nbins[f]) - 1
            bb = max(bb, off)

    G = len(bundles)
    exp_map = np.full((F, B), -1, np.int64)
    fix_mask = np.zeros(F, bool)
    for f in range(F):
        g = int(f_bundle[f])
        nb = int(f_nbins[f])
        if f_single[f]:
            exp_map[f, :nb] = g * bb + np.arange(nb)
        else:
            fix_mask[f] = True
            d = int(f_default[f])
            o = int(f_offset[f])
            for b in range(nb):
                if b == d:
                    continue  # restored from leaf totals (FixHistogram)
                exp_map[f, b] = g * bb + o + b - (1 if b > d else 0)
    return BundleInfo(n_bundles=G, bundle_bins=bb, f_bundle=f_bundle,
                      f_offset=f_offset, f_default=f_default,
                      f_nbins=f_nbins, f_single=f_single,
                      exp_map=exp_map.astype(np.int32), fix_mask=fix_mask)


def bundle_binned_matrix(X_binned: np.ndarray, info: BundleInfo) -> np.ndarray:
    """Compress a per-feature binned matrix (N, F) into bundle columns
    (N, G) (dense-input path); sets ``info.conflict_rows``."""
    n = X_binned.shape[0]
    out = np.zeros((n, info.n_bundles), np.uint8)
    lost = _Conflicts(info)
    for f in range(X_binned.shape[1]):
        g = int(info.f_bundle[f])
        col = X_binned[:, f].astype(np.int32)
        if info.f_single[f]:
            out[:, g] = col.astype(np.uint8)
        else:
            d = int(info.f_default[f])
            o = int(info.f_offset[f])
            rows = np.flatnonzero(col != d)
            lost.note(g, rows, out[rows, g])
            vals = o + col[rows] - (col[rows] > d)
            out[rows, g] = vals.astype(np.uint8)
    lost.state(info)
    return out


class _Conflicts:
    """The entries that building a bundled matrix overwrites: a member's
    code written where another member of its bundle left one.  The feature
    that owned the old code reads as its default in that row from then on."""

    def __init__(self, info: BundleInfo):
        # which feature owns a code of a multi-member bundle's column
        self.owner = np.full((info.n_bundles, MAX_BUNDLE_BINS), -1, np.int32)
        for f in np.flatnonzero(np.logical_not(info.f_single)):
            o = int(info.f_offset[f])
            self.owner[info.f_bundle[f], o:o + int(info.f_nbins[f]) - 1] = f
        self.rows, self.feats = [], []

    def note(self, g: int, rows: np.ndarray, codes_there: np.ndarray) -> None:
        hit = codes_there != 0
        if hit.any():
            self.rows.append(rows[hit].astype(np.int64))
            self.feats.append(self.owner[g, codes_there[hit]])

    def state(self, info: BundleInfo) -> None:
        """``info.conflict_entries`` and ``info.conflict_rows``."""
        if self.rows:
            info.conflict_entries = (np.concatenate(self.rows),
                                     np.concatenate(self.feats))
        else:
            info.conflict_entries = (np.zeros(0, np.int64),
                                     np.zeros(0, np.int32))
        info.conflict_rows = int(len(np.unique(info.conflict_entries[0])))


def bundle_sparse_csc(csc, mappers: Sequence, info: BundleInfo) -> np.ndarray:
    """Build the bundled matrix straight from a scipy CSC matrix — the raw
    data is never densified (sparse-ingestion path; reference
    sparse_bin.hpp's role collapses into this one pass); sets
    ``info.conflict_rows``."""
    n = csc.shape[0]
    out = np.zeros((n, info.n_bundles), np.uint8)
    lost = _Conflicts(info)
    for f in range(len(mappers)):
        g = int(info.f_bundle[f])
        lo, hi = csc.indptr[f], csc.indptr[f + 1]
        rows = csc.indices[lo:hi]
        vals = np.asarray(csc.data[lo:hi], np.float64)
        bins = mappers[f].value_to_bin(vals).astype(np.int32)
        d = int(mappers[f].default_bin)
        if info.f_single[f]:
            if d:
                out[:, g] = np.uint8(d)  # implied zeros sit in bin(0.0)
            out[rows, g] = bins.astype(np.uint8)
        else:
            o = int(info.f_offset[f])
            nd = bins != d
            rows, bins = rows[nd], bins[nd]
            lost.note(g, rows, out[rows, g])
            out[rows, g] = (o + bins - (bins > d)).astype(np.uint8)
    lost.state(info)
    return out


# ---------------------------------------------------------------------------
# Device-side helpers shared by the growers (learner/partitioned.py and
# learner/wave.py).  ``efb_arrays`` is the jnp tuple built by
# SerialTreeLearner from BundleInfo: (exp_map, f_bundle, f_offset,
# f_default, f_nbins, f_single).
# ---------------------------------------------------------------------------


def make_expand_hist(efb_arrays, num_features: int, n_bundles: int,
                     bundle_bins: int):
    """Closure mapping a bundle-space (G, Bb, 3) histogram to per-feature
    (F, B, 3) space, restoring each feature's default bin from the leaf
    totals (Dataset::FixHistogram, reference src/io/dataset.cpp:1239).
    Identity when ``efb_arrays`` is empty (no bundling)."""
    import jax.numpy as jnp

    if not efb_arrays:
        return lambda hb, total: hb
    exp_map, f_bundle, f_off, f_def, f_nb, f_single = efb_arrays
    G, Bb, F = n_bundles, bundle_bins, num_features

    def expand(hb, total):
        flat = hb.reshape(G * Bb, 3)
        e = jnp.where((exp_map >= 0)[:, :, None],
                      flat[jnp.maximum(exp_map, 0)], 0.0)
        fix = total[None, :] - jnp.sum(e, axis=1)
        fixable = jnp.logical_not(f_single).astype(jnp.float32)
        e = e.at[jnp.arange(F), f_def].add(fix * fixable[:, None])
        return e

    return expand


class ScanExpand(NamedTuple):
    """What :func:`make_scan_expand` returns: ``expand(hb, total)`` and the
    two feature classes it lays out (global feature ids, int32):
    ``wide_ids`` ascending, ``narrow_ids`` in bundle-code order."""
    expand: Callable
    wide_ids: np.ndarray
    narrow_ids: np.ndarray


def make_scan_expand(layout: tuple, n_bundles: int, bundle_bins: int,
                     max_bins: int) -> ScanExpand:
    """The split scan's input out of a bundle-space histogram, by static
    slices and shifts (no gather): ``expand(hb (G, Bb, 3), total (3,)) ->
    (wide, narrow)``, element for element what :func:`make_expand_hist`
    gathers.

    * ``narrow`` (2, 3, Fn): the two-bin members of multi-member bundles
      (indicator columns), FEATURES ON THE LAST AXIS, in bundle-code
      order: ``narrow[b, c, i]`` is channel ``c`` of bin ``b`` of feature
      ``narrow_ids[i]``.  The non-default bin is the member's one code,
      cut out of its bundle's row in runs of consecutive codes; the
      default bin is the leaf total less it (Dataset::FixHistogram).
    * ``wide`` (Fw, B, 3): every other feature, ``wide_ids`` ascending, as
      the unbundled scan takes it.  A singleton bundle's row verbatim; a
      member with more bins as its codes ``[o, o + nb - 1)``, moved one
      place up from the default bin on, that bin restored from the total.

    Either is None where its class is empty.  ``layout``:
    :meth:`BundleInfo.layout`."""
    import jax
    import jax.numpy as jnp

    f_bundle, f_off, f_def, f_nb, f_single = (np.asarray(a) for a in layout)
    f_single = f_single.astype(bool)
    G, Bb, B = int(n_bundles), int(bundle_bins), int(max_bins)
    is_narrow = np.logical_not(f_single) & (f_nb == 2)
    wide_ids = np.flatnonzero(np.logical_not(is_narrow)).astype(np.int32)
    # bundle-code order: consecutive members' codes are consecutive
    narrow_ids = np.flatnonzero(is_narrow)
    narrow_ids = narrow_ids[np.lexsort(
        (f_off[narrow_ids], f_bundle[narrow_ids]))].astype(np.int32)
    runs = []            # (bundle, first code, members)
    for f in narrow_ids:
        g, o = int(f_bundle[f]), int(f_off[f])
        if runs and runs[-1][0] == g and runs[-1][1] + runs[-1][2] == o:
            runs[-1][2] += 1
        else:
            runs.append([g, o, 1])
    nd_is_bin1 = jnp.asarray(f_def[narrow_ids] == 0)          # (Fn,)
    # wide pieces: runs of singleton bundles with consecutive ids as one
    # slice of whole rows; a bundled member as one slice of its codes
    pieces = []          # ("rows", g0, count) | ("codes", g, o)
    for f in wide_ids:
        g = int(f_bundle[f])
        if not f_single[f]:
            pieces.append(["codes", g, int(f_off[f])])
        elif pieces and pieces[-1][0] == "rows" and \
                pieces[-1][1] + pieces[-1][2] == g:
            pieces[-1][2] += 1
        else:
            pieces.append(["rows", g, 1])
    w_single = jnp.asarray(f_single[wide_ids])
    w_nb = jnp.asarray(f_nb[wide_ids].astype(np.int32))
    w_def = jnp.asarray(f_def[wide_ids].astype(np.int32))
    any_codes = any(p[0] == "codes" for p in pieces)

    def expand(hb, total):
        narrow = wide = None
        if len(narrow_ids):
            planes = jnp.moveaxis(hb, -1, 0)                   # (3, G, Bb)
            nd = jnp.concatenate(
                [jax.lax.slice(planes, (0, g, o), (3, g + 1, o + k))[:, 0]
                 for g, o, k in runs], axis=1)                 # (3, Fn)
            fix = total[:, None] - nd
            narrow = jnp.stack([jnp.where(nd_is_bin1, fix, nd),
                                jnp.where(nd_is_bin1, nd, fix)])
        if len(wide_ids):
            if any_codes:   # a member's codes may run up to the row's end
                hp = jnp.pad(hb, ((0, 0), (0, B), (0, 0)))
            got = jnp.concatenate(
                [jax.lax.slice(hb, (g, 0, 0), (g + k, B, 3))
                 if kind == "rows" else
                 jax.lax.slice(hp, (g, k, 0), (g + 1, k + B, 3))
                 for kind, g, k in pieces], axis=0)            # (Fw, B, 3)
            bins = jnp.arange(B, dtype=jnp.int32)[None, :]
            # singleton: bins [0, nb) verbatim.  member: ``got`` holds its
            # non-default bins in order; those from the default on move up
            live = bins < jnp.where(w_single, w_nb, w_nb - 1)[:, None]
            got = jnp.where(live[:, :, None], got, 0.0)
            if any_codes:
                up = jnp.concatenate(
                    [jnp.zeros_like(got[:, :1]), got[:, :-1]], axis=1)
                d = w_def[:, None]
                moved = jnp.where((bins < d)[:, :, None], got,
                                  jnp.where((bins > d)[:, :, None], up, 0.0))
                fix = total[None, :] - jnp.sum(moved, axis=1)
                moved = jnp.where((bins == d)[:, :, None], fix[:, None, :],
                                  moved)
                got = jnp.where(w_single[:, None, None], got, moved)
            wide = got
        return wide, narrow

    return ScanExpand(expand, wide_ids, narrow_ids)


def bundle_left_sets(efb_arrays, feat, thr, nan_bin, default_left,
                     num_codes: int = MAX_BUNDLE_BINS):
    """For W splits in FEATURE space (``feat``, threshold bin ``thr``,
    ``nan_bin`` or -1, ``default_left``): ``(bundled (W,) bool, go_left
    (W, num_codes) bool)`` — whether the split feature is a member of a
    multi-member bundle, and for every code of its bundle column whether
    a row holding it goes left (a code of another member reads as this
    feature's default bin).  The fused row update takes the sets as it
    takes a categorical slot's (ops/histogram_pallas.py)."""
    import jax.numpy as jnp

    decode = make_bundle_decode(efb_arrays)
    codes = jnp.arange(num_codes, dtype=jnp.int32)[None, :]
    fb = decode(codes, feat[:, None])
    go = jnp.where(fb == nan_bin[:, None], default_left[:, None],
                   fb <= thr[:, None])
    return jnp.logical_not(efb_arrays[5][feat]), go


def make_bundle_decode(efb_arrays):
    """Closure mapping a BUNDLE-space bin column ``v`` (int32 values of
    feature ``feat``'s bundle column) to FEATURE-space bin codes —
    the inverse of the offset encoding in bundle_binned_matrix().
    Identity when ``efb_arrays`` is empty."""
    import jax.numpy as jnp

    if not efb_arrays:
        return lambda v, feat: v
    exp_map, f_bundle, f_off, f_def, f_nb, f_single = efb_arrays

    def decode(v, feat):
        u = v - f_off[feat]
        inr = (u >= 0) & (u < f_nb[feat] - 1)
        mapped = jnp.where(inr, u + (u >= f_def[feat]).astype(jnp.int32),
                           f_def[feat])
        return jnp.where(f_single[feat], v, mapped)

    return decode
