"""Dataset: binned feature matrix + metadata, resident on device.

TPU-native re-implementation of the reference data layer
(reference: include/LightGBM/dataset.h:282 ``Dataset``, dataset.h:41
``Metadata``, src/io/dataset_loader.cpp ``DatasetLoader``).

Key departures from the reference, driven by TPU/XLA:

* The reference stores per-feature-group ``Bin`` objects with dense/sparse/
  4-bit/multi-value layouts chosen per feature (src/io/dense_bin.hpp,
  sparse_bin.hpp).  On TPU the working set is ONE dense uint8/uint16 array of
  shape (rows, features) — static shape, MXU/VPU friendly, shardable over a
  mesh along the row axis (data parallel) or feature axis (feature parallel).
* Bin construction runs host-side on a row sample (numpy), mirroring
  ``DatasetLoader::ConstructBinMappersFromTextData``; the binned matrix is
  then device_put once.
* Validation datasets are aligned to the training dataset's bin mappers
  (reference dataset.h:304 alignment check / create_valid).
"""

from __future__ import annotations

import copy
from typing import Any, Dict, List, Optional, Sequence, Union

import numpy as np

from .binning import (BinMapper, bin_matrix, find_bin,
                      find_bin_from_summary)
from .config import Config
from .telemetry.trace import timed_span
from .utils.log import log_info

__all__ = ["Dataset", "Metadata", "DatasetCorruptError", "RowBlocks"]


class DatasetCorruptError(ValueError):
    """A binary dataset file could not be read or failed validation
    (truncated/garbage payload, missing fields, or a fingerprint that
    does not match the stored binned matrix) — the Dataset analog of
    ``ModelCorruptError``."""

    def __init__(self, source: str, detail: str) -> None:
        super().__init__(f"{source}: {detail}")
        self.source = source
        self.detail = detail

_ArrayLike = Union[np.ndarray, Sequence[float], "Any"]


class Metadata:
    """Labels / weights / query boundaries / init scores
    (reference dataset.h:41, src/io/metadata.cpp)."""

    def __init__(self) -> None:
        self.label: Optional[np.ndarray] = None
        self.weight: Optional[np.ndarray] = None
        self.group: Optional[np.ndarray] = None            # sizes per query
        self.query_boundaries: Optional[np.ndarray] = None  # cumulative offsets
        self.init_score: Optional[np.ndarray] = None
        self.position: Optional[np.ndarray] = None

    def set_label(self, label: _ArrayLike) -> None:
        self.label = np.asarray(label, dtype=np.float32).ravel()

    def set_weight(self, weight: Optional[_ArrayLike]) -> None:
        if weight is None:
            self.weight = None
        else:
            w = np.asarray(weight, dtype=np.float32).ravel()
            if (w < 0).any():
                raise ValueError("weights must be non-negative")
            self.weight = w

    def set_group(self, group: Optional[_ArrayLike]) -> None:
        if group is None:
            self.group = None
            self.query_boundaries = None
            return
        g = np.asarray(group, dtype=np.int64).ravel()
        self.group = g
        self.query_boundaries = np.concatenate([[0], np.cumsum(g)]).astype(np.int64)

    def set_init_score(self, init_score: Optional[_ArrayLike]) -> None:
        if init_score is None:
            self.init_score = None
        else:
            self.init_score = np.asarray(init_score, dtype=np.float64).ravel()

    @property
    def num_queries(self) -> int:
        return 0 if self.group is None else len(self.group)


class RowBlocks:
    """``data`` given as a list of 2-D numpy row blocks of equal width (the
    reference's "list of numpy arrays"): the rows of one matrix, never
    joined.  ``construct`` gathers its bin-finding sample from the blocks
    and bins them one by one, each from its own dtype, so no copy of the
    whole matrix exists at any time."""

    def __init__(self, blocks: Sequence[np.ndarray]) -> None:
        self.blocks = list(blocks)
        f = self.blocks[0].shape[1]
        if any(b.shape[1] != f for b in self.blocks):
            raise ValueError(
                "row blocks must have equal width; got "
                f"{sorted({b.shape[1] for b in self.blocks})}")
        self.offsets = np.concatenate(
            [[0], np.cumsum([b.shape[0] for b in self.blocks])]).astype(
            np.int64)
        self.shape = (int(self.offsets[-1]), int(f))

    def __iter__(self):
        """(global row offset, block) in row order."""
        return zip(self.offsets[:-1].tolist(), self.blocks)

    def take_rows(self, idx: np.ndarray) -> np.ndarray:
        """float64 (len(idx), F): the rows ``idx`` (global, sorted)."""
        from .ingest.sketch import sampled_rows
        return np.concatenate([sampled_rows(b, lo, idx) for lo, b in self])

    def columns(self, used: np.ndarray, dtype) -> np.ndarray:
        """(N, len(used)) of ``dtype``: the used columns, block by block."""
        out = np.empty((self.shape[0], len(used)), dtype)
        for lo, b in self:
            out[lo:lo + b.shape[0]] = b[:, used]
        return out


class Dataset:
    """User-facing dataset, lazily constructed (reference python-package
    basic.py ``Dataset`` + C++ ``Dataset``/``DatasetLoader``).

    Parameters mirror the reference Python API.  ``data`` may be a numpy
    array, a pandas DataFrame, a path to a CSV/TSV/LibSVM file, or a list
    of 2-D numpy row blocks of equal width.  Use the list for a matrix
    that does not fit the host twice: a whole array is copied to float64
    (three times at ``construct``'s peak), blocks are binned one by one
    from their own dtype into the same mappers and bin codes.
    """

    def __init__(self, data: Any, label: Optional[_ArrayLike] = None,
                 reference: Optional["Dataset"] = None,
                 weight: Optional[_ArrayLike] = None,
                 group: Optional[_ArrayLike] = None,
                 init_score: Optional[_ArrayLike] = None,
                 feature_name: Union[str, List[str]] = "auto",
                 categorical_feature: Union[str, List[Union[int, str]]] = "auto",
                 params: Optional[Dict[str, Any]] = None,
                 free_raw_data: bool = True) -> None:
        self.data = data
        self.params = dict(params or {})
        self.reference = reference
        self.feature_name = feature_name
        self.categorical_feature = categorical_feature
        self.free_raw_data = free_raw_data
        self.metadata = Metadata()
        self._label_arg = label
        self._weight_arg = weight
        self._group_arg = group
        self._init_score_arg = init_score
        # populated by construct()
        self.constructed = False
        self.bin_mappers: List[BinMapper] = []
        self.X_binned: Optional[np.ndarray] = None   # (N, F) uint8/uint16, host copy
        self.num_bins_per_feature: Optional[np.ndarray] = None
        self.used_feature_map: Optional[np.ndarray] = None  # inner -> real index
        self.num_total_features = 0
        self.efb = None  # BundleInfo when EFB-bundled (efb.py)
        self._device_cache: Dict[Any, Any] = {}
        # host seconds of construct()'s phases (all host-synchronous):
        # "to_float64", "bin_find", "bin_matrix"; GBDT._init_train copies
        # them into the run's TrainRecord
        self.setup_seconds: Dict[str, float] = {}

    # -- construction --------------------------------------------------------
    def construct(self, config: Optional[Config] = None) -> "Dataset":
        if self.constructed:
            return self
        cfg = config or Config(self.params)
        secs = self.setup_seconds
        with timed_span(secs, "to_float64", "dataset/construct/to_float64"):
            raw, feature_names = self._materialize_raw()
        sparse = hasattr(raw, "tocsc")
        if sparse:
            raw = raw.tocsc()
        blocks = isinstance(raw, RowBlocks)
        n, f = raw.shape
        self.num_total_features = f
        self.feature_names_ = feature_names
        self.efb = None

        cat_indices = self._resolve_categoricals(feature_names)

        if cfg.linear_tree and sparse:
            # linear leaves fit on RAW dense feature values
            # (linear_tree_learner.cpp reads raw columns); the reference
            # rejects this combination too
            raise ValueError("linear_tree requires dense input (the "
                             "per-leaf linear fits read raw feature "
                             "values); densify or disable linear_tree")

        # pre-partitioned multi-process ingest (reference pre_partition +
        # distributed bin finding, dataset_loader.cpp:1040-1130): each
        # process holds only ITS row range; bin-finding samples are
        # allgathered so every rank derives identical mappers, and
        # metadata is replicated (small next to the sharded features)
        from . import distributed as _dist
        dist_rows = (bool(cfg.pre_partition) and _dist.is_initialized()
                     and _dist.process_count() > 1
                     and self.reference is None)
        self.distributed_rows = dist_rows
        if dist_rows:
            if self._group_arg is not None:
                raise ValueError(
                    "pre_partition cannot shard query/group data (queries "
                    "must not straddle partitions); drop pre_partition or "
                    "the group argument")
        rng = np.random.RandomState(cfg.data_random_seed)
        if dist_rows:
            sample_cnt = min(n, max(1, int(cfg.bin_construct_sample_cnt) //
                                    _dist.process_count()))
        else:
            sample_cnt = min(n, int(cfg.bin_construct_sample_cnt))
        # one code path with the streamed sketch pass (the bit-identity
        # root); the shared generator keeps the sparse path's remaining
        # stream identical
        from .ingest.sketch import sample_row_indices
        with timed_span(secs, "bin_find", "dataset/construct/sample"):
            sample_idx = sample_row_indices(n, sample_cnt,
                                            cfg.data_random_seed, rng=rng)
            # the sampled rows of a block input, gathered once; a whole
            # matrix is indexed column by column below
            sample = raw.take_rows(sample_idx) if blocks else None
        dist_sketch = None
        dist_sparse_cols = None
        n_total = n
        if dist_rows:
            n_total = int(_dist.allgather_host(
                np.asarray([n], np.int32)).sum())
            if sparse:
                # per-column sampled NONZEROS gathered flat (one
                # variable-length collective), plus global nnz counts so
                # every rank derives identical zero fractions — the
                # sparse analog of the dense sample allgather below; the
                # raw shard itself never leaves this process
                vals_list, lens_loc, nnz_loc = [], [], []
                for j in range(f):
                    lo, hi = raw.indptr[j], raw.indptr[j + 1]
                    vals = np.asarray(raw.data[lo:hi], np.float64)
                    if len(vals) > sample_cnt:
                        vals = vals[np.sort(rng.choice(len(vals),
                                                       sample_cnt, False))]
                    vals_list.append(vals)
                    lens_loc.append(len(vals))
                    nnz_loc.append(hi - lo)
                flat_all = _dist.allgather_host(
                    np.concatenate(vals_list) if vals_list
                    else np.zeros(0, np.float64))
                lens_all = _dist.allgather_host(
                    np.asarray(lens_loc, np.int32)).reshape(-1, f)
                nnz_all = _dist.allgather_host(
                    np.asarray(nnz_loc, np.int32)).reshape(-1, f)
                nnz_glob = nnz_all.sum(axis=0)
                rank_off = np.concatenate(
                    [[0], np.cumsum(lens_all.sum(axis=1))])
                col_off = np.cumsum(
                    np.concatenate([np.zeros((len(lens_all), 1), np.int64),
                                    lens_all], axis=1), axis=1)
                dist_sparse_cols = []
                for j in range(f):
                    parts = [flat_all[rank_off[r] + col_off[r, j]:
                                      rank_off[r] + col_off[r, j + 1]]
                             for r in range(len(lens_all))]
                    vals = np.concatenate(parts) if parts else \
                        np.zeros(0, np.float64)
                    zfrac = 1.0 - nnz_glob[j] / max(n_total, 1)
                    nz = int(round(len(vals) * zfrac /
                                   max(1e-9, 1 - zfrac))) \
                        if zfrac < 1.0 else sample_cnt
                    nz = min(nz, sample_cnt * max(len(lens_all), 1))
                    dist_sparse_cols.append(
                        np.concatenate([vals, np.zeros(nz)]))
            else:
                # dense: per-rank per-feature SUMMARIES allgathered and
                # merged in rank order — the streamed sketch's wire form
                # (ingest/sketch.py), one code path with single-process
                # and streamed binning (both finalize through
                # binning.find_bin_from_summary), and never more bytes
                # than the raw sample-row gather it replaces
                from .ingest.sketch import BinningSketch
                dist_sketch = BinningSketch(f, cat_indices)
                dist_sketch.update(sample if blocks else
                                   np.asarray(raw[sample_idx], np.float64))
                dist_sketch.allgather_merge()

        if self.reference is not None:
            ref = self.reference
            if not ref.constructed:
                ref.construct(config)
            # align bins with the reference dataset (dataset.h:304)
            self.bin_mappers = ref.bin_mappers
            self.used_feature_map = ref.used_feature_map
            self.num_bins_per_feature = ref.num_bins_per_feature
            self.efb = ref.efb
        else:
            # sample rows for bin finding (dataset_loader.cpp:902
            # SampleTextDataFromFile — here rows are already in memory)
            forced_bins = self._load_forced_bins(cfg)
            self.bin_mappers = []
            with timed_span(secs, "bin_find", "dataset/construct/find_bins"):
                for j in range(f):
                    if dist_sketch is not None:
                        # distributed dense: finalize the merged summaries
                        # through the shared sketch machinery
                        summary = dist_sketch.summary(j)
                        filt = max(1, int(cfg.min_data_in_leaf *
                                          summary.total_cnt /
                                          max(1, n_total))) \
                            if cfg.feature_pre_filter else 0
                        self.bin_mappers.append(find_bin_from_summary(
                            summary, cfg.max_bin,
                            min_data_in_bin=cfg.min_data_in_bin,
                            use_missing=cfg.use_missing,
                            zero_as_missing=cfg.zero_as_missing,
                            forced_bounds=forced_bins.get(j),
                            pre_filter_cnt=filt))
                        continue
                    if dist_sparse_cols is not None:
                        col_sample = dist_sparse_cols[j]
                    elif sparse:
                        # sparse column: sampled nonzeros + proportional
                        # implied zeros (no densification)
                        lo, hi = raw.indptr[j], raw.indptr[j + 1]
                        vals = np.asarray(raw.data[lo:hi], np.float64)
                        if len(vals) > sample_cnt:
                            vals = vals[np.sort(rng.choice(len(vals),
                                                           sample_cnt, False))]
                        zfrac = 1.0 - (hi - lo) / max(n, 1)
                        nz = int(round(len(vals) * zfrac / max(1e-9, 1 - zfrac))) \
                            if zfrac < 1.0 else sample_cnt
                        nz = min(nz, sample_cnt)
                        col_sample = np.concatenate([vals, np.zeros(nz)])
                    elif blocks:
                        col_sample = sample[:, j]
                    else:
                        col_sample = raw[sample_idx, j]
                    # the reference's pre-filter threshold scales
                    # min_data_in_leaf by the sample fraction
                    # (dataset_loader.cpp filter_cnt)
                    # 0 disables the pre-filter (feature_pre_filter=false
                    # keeps even never-splittable features, like the reference)
                    filt = max(1, int(cfg.min_data_in_leaf * len(col_sample) /
                                      max(1, n_total))) \
                        if cfg.feature_pre_filter else 0
                    self.bin_mappers.append(find_bin(
                        col_sample, max_bin=cfg.max_bin,
                        min_data_in_bin=cfg.min_data_in_bin,
                        total_cnt=len(col_sample),
                        is_categorical=(j in cat_indices),
                        use_missing=cfg.use_missing,
                        zero_as_missing=cfg.zero_as_missing,
                        forced_bounds=forced_bins.get(j),
                        pre_filter_cnt=filt))
                self._finalize_used_features(f)

        used = self.used_feature_map
        mappers = [self.bin_mappers[j] for j in used]

        if self.efb is None:
            with timed_span(secs, "find_bundles",
                            "dataset/construct/find_bundles"):
                self.efb = self._maybe_bundle(cfg, raw, sparse, used,
                                              mappers, sample_idx, n, sample)
        if self.efb is not None:
            from .efb import bundle_binned_matrix, bundle_sparse_csc
            if self.reference is not None:
                # the conflict count is the training set's own
                import copy
                self.efb = copy.copy(self.efb)
            codes = None if sparse else self._bin_dense(raw, used, mappers)
            with timed_span(secs, "bundle_matrix",
                            "dataset/construct/bundle_matrix"):
                if sparse:
                    self.X_binned = bundle_sparse_csc(
                        raw if len(used) == f else raw[:, used].tocsc(),
                        mappers, self.efb)
                else:
                    self.X_binned = bundle_binned_matrix(codes, self.efb)
            # the bundled matrix IS this data set's bin matrix
            secs["bin_matrix"] = secs.get("bin_matrix", 0.0) + \
                secs["bundle_matrix"]
            log_info(f"EFB: bundled {len(used)} features into "
                     f"{self.efb.n_bundles} device columns "
                     f"({self.efb.bundle_bins} bundle bins)")
        elif sparse:
            # no beneficial bundling: densify the BINNED codes (uint8),
            # never the raw float64 values
            cols = []
            csc = raw[:, used].tocsc()
            for jj, m in enumerate(mappers):
                col = np.full(n, m.default_bin, np.uint8)
                lo, hi = csc.indptr[jj], csc.indptr[jj + 1]
                col[csc.indices[lo:hi]] = m.value_to_bin(
                    np.asarray(csc.data[lo:hi], np.float64)).astype(np.uint8)
                cols.append(col)
            self.X_binned = np.stack(cols, axis=1)
        else:
            self.X_binned = self._bin_dense(raw, used, mappers)
        if cfg.linear_tree and not sparse:
            # linear trees fit on RAW feature values (reference
            # linear_tree_learner.cpp raw_index); keep the used columns
            # (under pre_partition this is the LOCAL row shard — padded
            # in _finalize_distributed_rows and assembled row-sharded on
            # the mesh by the GBDT driver)
            self.raw_used = raw.columns(used, np.float32) if blocks else \
                raw[:, used].astype(np.float32)
        else:
            self.raw_used = None
        if self.distributed_rows:
            n = self._finalize_distributed_rows(n)
        self._set_metadata(n)
        self.constructed = True
        if self.free_raw_data:
            self.data = None
        return self

    def _bin_dense(self, raw, used, mappers) -> np.ndarray:
        """Bin codes of the used columns of a dense float64 matrix, or of
        row blocks: those are binned one by one, each converted from its
        own dtype, into one preallocated code matrix."""
        secs = self.setup_seconds
        if isinstance(raw, RowBlocks):
            out = None
            keep_all = len(used) == raw.shape[1]
            for lo, block in raw:
                with timed_span(secs, "bin_matrix",
                                "dataset/construct/select_columns"):
                    cols = np.asarray(block if keep_all else block[:, used],
                                      np.float64)
                with timed_span(secs, "bin_matrix",
                                "dataset/construct/bin_matrix"):
                    codes = bin_matrix(cols, mappers)
                    if out is None:
                        out = np.empty((raw.shape[0], len(used)), codes.dtype)
                    out[lo:lo + len(codes)] = codes
            return out
        with timed_span(secs, "bin_matrix", "dataset/construct/select_columns"):
            cols = raw[:, used]
        with timed_span(secs, "bin_matrix", "dataset/construct/bin_matrix"):
            return bin_matrix(cols, mappers)

    @staticmethod
    def _load_forced_bins(cfg) -> Dict[int, list]:
        """forcedbins_filename JSON -> {feature: [upper bounds]}
        (dataset_loader.cpp:641 GetForcedBins) — shared with the
        streamed construct (ingest/stream.py)."""
        forced_bins: Dict[int, list] = {}
        if getattr(cfg, "forcedbins_filename", ""):
            import json as _json
            with open(cfg.forcedbins_filename) as fh:
                for ent in _json.load(fh):
                    forced_bins[int(ent["feature"])] = \
                        list(ent["bin_upper_bound"])
        return forced_bins

    def _finalize_used_features(self, f: int) -> None:
        """Trivial-feature pre-filter (config.h feature_pre_filter) ->
        used_feature_map / num_bins_per_feature — shared with the
        streamed construct so the filter policy cannot drift between
        the in-core and streamed mapper sets."""
        used = [j for j, m in enumerate(self.bin_mappers)
                if not m.is_trivial]
        if len(used) == 0:
            raise ValueError("cannot construct Dataset: all features are "
                             "trivial (constant); nothing to split on")
        if len(used) < f:
            log_info(f"Dataset: filtered {f - len(used)} trivial features, "
                     f"{len(used)} remain")
        self.used_feature_map = np.asarray(used, dtype=np.int32)
        self.num_bins_per_feature = np.asarray(
            [self.bin_mappers[j].num_bin for j in used], dtype=np.int32)

    def _finalize_distributed_rows(self, n_local: int) -> int:
        """Pad the LOCAL binned shard to the mesh row quantum and
        replicate the (small) metadata across processes; the feature
        matrix itself never leaves this process (the point of
        pre_partition — Experiments.rst:228's 176 GB -> per-machine
        shards)."""
        from . import distributed as _dist
        import jax
        from .utils.backend import default_backend
        rb = 4096 if default_backend() == "tpu" else 1
        quantum = max(1, jax.local_device_count()) * rb
        lens = _dist.allgather_host(np.asarray([n_local], np.int64)).ravel()
        pad_to = int(-(-int(lens.max()) // quantum) * quantum)
        pad = pad_to - n_local
        if pad:
            self.X_binned = np.pad(self.X_binned, ((0, pad), (0, 0)))
            if self.raw_used is not None:
                self.raw_used = np.pad(self.raw_used, ((0, pad), (0, 0)))

        def padded(a, fill=0.0):
            a = np.asarray(a, np.float64).ravel()
            if len(a) != n_local:
                raise ValueError(f"metadata length {len(a)} != local rows "
                                 f"{n_local} under pre_partition")
            return np.concatenate([a, np.full(pad, fill, np.float64)])

        lab = np.zeros(n_local) if self._label_arg is None \
            else np.asarray(self._label_arg, np.float64).ravel()
        w = np.ones(n_local) if self._weight_arg is None \
            else np.asarray(self._weight_arg, np.float64).ravel()
        self._label_arg = _dist.allgather_host(padded(lab))
        # padded rows carry zero weight so objectives/metrics ignore them
        self._weight_arg = _dist.allgather_host(padded(w))
        if self._init_score_arg is not None:
            self._init_score_arg = _dist.allgather_host(
                padded(self._init_score_arg))
        self._dist_valid_local = np.concatenate(
            [np.ones(n_local, np.float32), np.zeros(pad, np.float32)])
        self._dist_pad_to = pad_to
        self._dist_global_rows = pad_to * _dist.process_count()
        log_info(f"pre_partition: rank {_dist.process_index()} holds "
                 f"{n_local} rows (padded {pad_to}); global "
                 f"{self._dist_global_rows} across "
                 f"{_dist.process_count()} processes")
        return self._dist_global_rows

    def _maybe_bundle(self, cfg, raw, sparse, used, mappers, sample_idx, n,
                      sample=None):
        """Decide + build EFB bundles (dataset.cpp:239 FastFeatureBundling);
        serial-learner training only, and only when it shrinks the device
        matrix."""
        from .efb import build_bundle_info, find_bundles
        if (not cfg.enable_bundle or cfg.tree_learner != "serial"
                or cfg.linear_tree or len(used) < 3):
            return None
        # non-default masks over the sampled rows; categorical features
        # stay singleton (their set-membership decisions read raw bins)
        nondefault = []
        cand = []
        from .efb import MAX_BUNDLE_BINS
        if sparse:
            # a row's place in the sample, -1 outside it: one lookup a
            # stored value (a set intersection a column sorted the sample
            # 4,228 times over)
            place = np.full(n, -1, np.int32)
            place[sample_idx] = np.arange(len(sample_idx), dtype=np.int32)
        for jj, m in enumerate(mappers):
            if m.is_categorical:
                continue
            if m.num_bin > MAX_BUNDLE_BINS:
                # a >256-bin feature (max_bin > 256) cannot ride a uint8
                # bundle column; it stays a standalone uint16 column
                continue
            j = int(used[jj])
            if sparse:
                lo, hi = raw.indptr[j], raw.indptr[j + 1]
                mask = np.zeros(len(sample_idx), bool)
                at = place[raw.indices[lo:hi]]
                mask[at[at >= 0]] = True
            else:
                col = mappers[jj].value_to_bin(
                    raw[sample_idx, j] if sample is None else sample[:, j])
                mask = col != mappers[jj].default_bin
            # only near-sparse features are worth bundling
            if mask.mean() <= 0.5:
                nondefault.append(mask)
                cand.append(jj)
        if len(cand) < 2:
            return None
        cand_mappers = [mappers[jj] for jj in cand]
        bundles_local = find_bundles(cand_mappers, nondefault, n,
                                     len(sample_idx))
        bundles = [[cand[i] for i in b] for b in bundles_local]
        in_bundle = {f for b in bundles for f in b}
        for jj in range(len(mappers)):
            if jj not in in_bundle:
                bundles.append([jj])
        if len(bundles) > 0.9 * len(mappers):
            return None  # not worth the indirection
        max_b = max(m.num_bin for m in mappers)
        return build_bundle_info(mappers, bundles, max_b)

    def _materialize_raw(self):
        data = self.data
        if data is None:
            raise ValueError("Dataset raw data was freed; pass free_raw_data=False "
                             "to reuse it")
        if isinstance(data, str):
            from .io_utils import load_data_file
            raw, names, label = load_data_file(data, self.params)
            if label is not None and self._label_arg is None:
                self._label_arg = label
            return raw, names
        try:  # pandas without a hard dependency
            import pandas as pd  # type: ignore
            if isinstance(data, pd.DataFrame):
                names = [str(c) for c in data.columns]
                raw = data.to_numpy(dtype=np.float64, na_value=np.nan)
                return raw, names
        except ImportError:
            pass
        if hasattr(data, "tocsc"):  # scipy sparse: handled without
            raw = data                # densification in construct()
            if self.feature_name != "auto" and self.feature_name is not None:
                return raw, list(self.feature_name)
            return raw, [f"Column_{i}" for i in range(raw.shape[1])]
        if isinstance(data, (list, tuple)) and len(data) > 0 and all(
                isinstance(b, np.ndarray) and b.ndim == 2 for b in data):
            raw = RowBlocks(data)
        else:
            raw = np.asarray(data, dtype=np.float64)
            if raw.ndim == 1:
                raw = raw.reshape(-1, 1)
        if self.feature_name != "auto" and self.feature_name is not None:
            names = list(self.feature_name)
        else:
            names = [f"Column_{i}" for i in range(raw.shape[1])]
        return raw, names

    def _resolve_categoricals(self, feature_names: List[str]) -> set:
        cats = self.categorical_feature
        if cats == "auto" or cats is None:
            from_params = self.params.get("categorical_feature", "")
            if isinstance(from_params, str) and from_params:
                cats = from_params.split(",")
            else:
                return set()
        out = set()
        for c in cats:
            if isinstance(c, str) and c in feature_names:
                out.add(feature_names.index(c))
            elif isinstance(c, str) and c.strip().isdigit():
                out.add(int(c))
            elif isinstance(c, (int, np.integer)):
                out.add(int(c))
        return out

    def _set_metadata(self, n: int) -> None:
        if self._label_arg is not None:
            self.metadata.set_label(self._label_arg)
            if len(self.metadata.label) != n:
                raise ValueError(f"label length {len(self.metadata.label)} != rows {n}")
        self.metadata.set_weight(self._weight_arg)
        self.metadata.set_group(self._group_arg)
        qb = self.metadata.query_boundaries
        if qb is not None and qb[-1] != n:
            # the boundaries are global row offsets: a block input's rows
            # arrive in order, so its queries may straddle its blocks
            raise ValueError(f"group sizes sum to {int(qb[-1])}; the data has "
                             f"{n} rows (reference metadata.cpp)")
        self.metadata.set_init_score(self._init_score_arg)

    # -- reference-API surface ----------------------------------------------
    def create_valid(self, data: Any, label: Optional[_ArrayLike] = None,
                     weight: Optional[_ArrayLike] = None,
                     group: Optional[_ArrayLike] = None,
                     init_score: Optional[_ArrayLike] = None,
                     params: Optional[Dict[str, Any]] = None) -> "Dataset":
        return Dataset(data, label=label, reference=self, weight=weight,
                       group=group, init_score=init_score,
                       params=params or self.params)

    def set_label(self, label: _ArrayLike) -> "Dataset":
        self._label_arg = label
        if self.constructed:
            self.metadata.set_label(label)
        return self

    def set_weight(self, weight: Optional[_ArrayLike]) -> "Dataset":
        self._weight_arg = weight
        if self.constructed:
            self.metadata.set_weight(weight)
        return self

    def set_group(self, group: Optional[_ArrayLike]) -> "Dataset":
        self._group_arg = group
        if self.constructed:
            self.metadata.set_group(group)
        return self

    def set_init_score(self, init_score: Optional[_ArrayLike]) -> "Dataset":
        self._init_score_arg = init_score
        if self.constructed:
            self.metadata.set_init_score(init_score)
        return self

    def get_label(self) -> Optional[np.ndarray]:
        return self.metadata.label if self.constructed else (
            None if self._label_arg is None else np.asarray(self._label_arg))

    def get_weight(self) -> Optional[np.ndarray]:
        return self.metadata.weight

    def get_group(self) -> Optional[np.ndarray]:
        return self.metadata.group

    def get_init_score(self) -> Optional[np.ndarray]:
        return self.metadata.init_score

    def num_data(self) -> int:
        self._check_constructed()
        if getattr(self, "distributed_rows", False):
            return int(self._dist_global_rows)
        return int(self.X_binned.shape[0])

    def num_feature(self) -> int:
        self._check_constructed()
        # inner FEATURE count — under EFB the device matrix is narrower
        # (bundle columns), but the feature surface stays per-feature
        return int(len(self.used_feature_map))

    def efb_conflicts(self) -> tuple:
        """``(rows int64, columns int32)``: the entries of the raw data
        that a bundle's conflict overwrote when this data set was built
        (efb.py): in row ``rows[i]``, column ``columns[i]`` (an index into
        the raw columns) held a non-default value and was trained on as
        its default, because another column of its bundle was set there
        too.  Empty without bundling.  Every other value was trained on
        as it is, so only these rows can reach another leaf at prediction
        than they reached in training."""
        self._check_constructed()
        if self.efb is None:
            return np.zeros(0, np.int64), np.zeros(0, np.int32)
        rows, feats = self.efb.conflict_entries
        return rows, np.asarray(self.used_feature_map, np.int32)[feats]

    def fingerprint(self) -> Dict[str, Any]:
        """Identity of the BINNED training matrix for checkpoint/resume
        validation: a resume against different rows or different binning
        cannot be bit-identical, so the bundle records (shape, a sha256
        over every used mapper's bin edges / category maps, a crc32 over
        the binned codes) and restore fails loudly on mismatch.

        Cached: the crc over X_binned is the only non-trivial cost and
        the binned matrix is immutable once constructed.  Under
        pre-partitioned multi-process ingest this fingerprints the LOCAL
        shard — resume must keep the same process count and sharding.
        """
        self._check_constructed()
        fp = self._device_cache.get("_fingerprint")
        if fp is not None:
            return fp
        import zlib
        crc = zlib.crc32(np.ascontiguousarray(self.X_binned).tobytes())
        fp = self._fingerprint_with_crc(crc)
        self._device_cache["_fingerprint"] = fp
        return fp

    def _fingerprint_with_crc(self, crc: int) -> Dict[str, Any]:
        """Fingerprint dict from a precomputed binned-codes crc — the
        mapper sha and field layout single-sourced here so the streamed
        subclass (which streams the crc over chunks) cannot drift from
        the in-core fingerprint it must equal bit for bit."""
        import hashlib
        h = hashlib.sha256()
        for j in self.used_feature_map:
            m = self.bin_mappers[j]
            h.update(f"{int(j)}:{m.num_bin}:{int(m.is_categorical)}:"
                     f"{m.missing_type.value}".encode())
            if m.bin_upper_bound is not None:
                h.update(np.ascontiguousarray(
                    m.bin_upper_bound, np.float64).tobytes())
            if m.cat_to_bin:
                h.update(repr(sorted(m.cat_to_bin.items())).encode())
        return {
            "num_data": int(self.num_data()),
            "binned_shape": [int(v) for v in self.X_binned.shape],
            "num_features": int(self.num_feature()),
            "binning_sha256": h.hexdigest(),
            "data_crc32": int(crc),
        }

    @property
    def feature_names(self) -> List[str]:
        self._check_constructed()
        return [self.feature_names_[j] for j in self.used_feature_map]

    def subset(self, used_indices: Sequence[int],
               params: Optional[Dict[str, Any]] = None) -> "Dataset":
        """Row subset sharing this dataset's bin mappers (reference
        Dataset::CopySubrow, used by cv/bagging)."""
        self._check_constructed()
        idx = np.asarray(used_indices, dtype=np.int64)
        sub = copy.copy(self)
        sub._device_cache = {}
        sub.X_binned = self.X_binned[idx]
        sub.metadata = Metadata()
        if self.metadata.label is not None:
            sub.metadata.set_label(self.metadata.label[idx])
        if self.metadata.weight is not None:
            sub.metadata.set_weight(self.metadata.weight[idx])
        if self.metadata.init_score is not None:
            sub.metadata.set_init_score(self.metadata.init_score[idx])
        if self.metadata.group is not None:
            # remap query boundaries: the subset must consist of whole
            # queries (reference Metadata partition re-indexing,
            # src/io/metadata.cpp:37)
            qb = self.metadata.query_boundaries
            qid = np.searchsorted(qb, idx, side="right") - 1
            sel_q, counts = np.unique(qid, return_counts=True)
            if not np.array_equal(counts, self.metadata.group[sel_q]):
                raise ValueError("subset() of ranking data must select whole "
                                 "queries (use query-aware folds)")
            if not np.all(np.diff(idx) > 0):
                raise ValueError("subset() of ranking data requires sorted, "
                                 "query-contiguous indices")
            sub.metadata.set_group(self.metadata.group[sel_q])
        return sub

    # -- binary serialization (reference Dataset::SaveBinaryFile /
    #    DatasetLoader::LoadFromBinFile) -------------------------------------
    def save_binary(self, filename: str) -> "Dataset":
        """Crash-safe binary save: the payload lands via
        ``io_utils.atomic_write_bytes`` (temp + fsync + rename — the same
        path Booster.save_model takes), and carries the dataset
        :meth:`fingerprint` so ``load_binary`` can validate the stored
        binned matrix against its recorded identity."""
        self._check_constructed()
        import pickle
        from .io_utils import atomic_write_bytes
        payload = {
            "format": "lightgbm_tpu.dataset.v1",
            "X_binned": np.asarray(self.X_binned),
            "bin_mappers": self.bin_mappers,
            "used_feature_map": self.used_feature_map,
            "num_bins_per_feature": self.num_bins_per_feature,
            "feature_names": self.feature_names_,
            "efb": self.efb,
            "label": self.metadata.label,
            "weight": self.metadata.weight,
            "group": self.metadata.group,
            "init_score": self.metadata.init_score,
            "fingerprint": self.fingerprint(),
        }
        atomic_write_bytes(filename, pickle.dumps(payload, protocol=4))
        return self

    _BINARY_REQUIRED = ("X_binned", "bin_mappers", "used_feature_map",
                        "num_bins_per_feature", "feature_names", "label",
                        "weight", "group", "init_score")

    @staticmethod
    def load_binary(filename: str, params: Optional[Dict[str, Any]] = None) -> "Dataset":
        """Load a :meth:`save_binary` file.  Truncated/garbage payloads
        raise a typed :class:`DatasetCorruptError` (never a raw pickle
        exception), and the stored :meth:`fingerprint` is recomputed and
        compared — a binned matrix that no longer matches its recorded
        identity fails loudly."""
        import pickle
        try:
            with open(filename, "rb") as fh:
                payload = pickle.load(fh)
        except OSError:
            raise
        except Exception as exc:
            raise DatasetCorruptError(
                str(filename), f"not a readable binary dataset "
                f"({type(exc).__name__}: {exc})") from exc
        if not isinstance(payload, dict) or \
                payload.get("format") != "lightgbm_tpu.dataset.v1":
            raise DatasetCorruptError(
                str(filename), "not a lightgbm_tpu binary dataset "
                "(missing/unknown format marker)")
        missing = [k for k in Dataset._BINARY_REQUIRED if k not in payload]
        if missing:
            raise DatasetCorruptError(
                str(filename),
                f"binary dataset is missing fields: {', '.join(missing)}")
        ds = Dataset(None, params=params)
        ds.X_binned = payload["X_binned"]
        ds.bin_mappers = payload["bin_mappers"]
        ds.used_feature_map = payload["used_feature_map"]
        ds.num_bins_per_feature = payload["num_bins_per_feature"]
        ds.feature_names_ = payload["feature_names"]
        ds.efb = payload.get("efb")
        ds.num_total_features = len(ds.feature_names_)
        if payload["label"] is not None:
            ds.metadata.set_label(payload["label"])
        ds.metadata.set_weight(payload["weight"])
        ds.metadata.set_group(payload["group"])
        ds.metadata.set_init_score(payload["init_score"])
        ds.constructed = True
        stored = payload.get("fingerprint")
        if stored:  # absent in pre-fingerprint files: accept
            try:
                got = ds.fingerprint()
            except Exception as exc:
                raise DatasetCorruptError(
                    str(filename), f"stored arrays are inconsistent "
                    f"({type(exc).__name__}: {exc})") from exc
            diffs = [k for k in stored if k in got and got[k] != stored[k]]
            if diffs:
                raise DatasetCorruptError(
                    str(filename),
                    "stored binned matrix does not match its recorded "
                    "fingerprint (" + ", ".join(
                        f"{k}: stored={stored[k]!r} got={got[k]!r}"
                        for k in diffs) + ")")
        return ds

    def _check_constructed(self) -> None:
        if not self.constructed:
            raise RuntimeError("Dataset not constructed yet; call construct() "
                               "(done automatically by train())")

    # -- device placement ----------------------------------------------------
    def device_bins(self, max_bin_global: int):
        """Return the binned matrix as a device array (cached)."""
        import jax.numpy as jnp
        key = ("bins", max_bin_global)
        if key not in self._device_cache:
            self._device_cache[key] = jnp.asarray(self.X_binned)
        return self._device_cache[key]

    def device_bins_packed4(self, row_block: int = 4096):
        """FEATURE-MAJOR nibble-packed device bins: two 4-bit bin codes
        per int8 lane (reference src/io/dense_bin.hpp 4-bit dense bins),
        rows padded to the Pallas kernel row block — the layout the
        packed histogram kernels stream (half the HBM bytes of the
        uint8 matrix).  Requires every used feature to fit 16 bins.
        Cached per row_block."""
        self._check_constructed()
        import numpy as _np
        import jax.numpy as jnp
        from .learner.serial import feature_major_bins
        from .ops.histogram_pallas import PACK4_MAX_BINS
        key = ("bins_packed4", row_block)
        if key not in self._device_cache:
            max_b = int(_np.max(self.num_bins_per_feature))
            if max_b > PACK4_MAX_BINS:
                raise ValueError(
                    f"device_bins_packed4 requires every feature to fit "
                    f"{PACK4_MAX_BINS} bins (max is {max_b}); set "
                    f"max_bin<={PACK4_MAX_BINS}")
            self._device_cache[key] = feature_major_bins(
                jnp.asarray(self.X_binned), row_block, pack4=True)
        return self._device_cache[key]
