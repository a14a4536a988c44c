"""StreamedDataset: out-of-core ingestion into a Dataset-compatible object.

The ingest subsystem's layer 1 (ROADMAP item 2; reference
``pipeline_reader.h`` streaming ingestion + sampled bin finding, PAPER.md
layers 0/3).  A :class:`StreamedDataset` wraps a
:class:`..ingest.source.ChunkSource` and constructs in two streaming
passes, never materializing the raw matrix:

1. **sketch pass** — the deterministic bin-construct row sample
   (``sketch.sample_row_indices`` — the same RNG draw the in-core
   ``Dataset.construct`` makes) is folded chunk-by-chunk into a
   :class:`..ingest.sketch.BinningSketch`; labels/weights accumulate into
   per-row host arrays.  Finalizing the sketch yields BinMappers
   **bit-identical** to an in-core construct of the same matrix (both run
   through ``binning.find_bin_from_summary``).
2. **bin + spill pass** — every chunk is quantized with the shared
   ``binning.bin_matrix`` fast path and appended to an on-disk
   ``np.memmap`` binned cache (1 B/value at max_bin<=256 — the XGBoost
   external-memory page file analog, arXiv:1806.11248), so later training
   passes stream binned codes from the OS page cache instead of re-parsing
   raw input.

Host working set: the sketch (bounded by ``bin_construct_sample_cnt``),
one raw chunk, and O(bytes-per-row) label/score state — a function of
``chunk_rows`` and features, never of total rows.  The full Dataset API
(fingerprint, device_bins, engine.train) works on top of the memmap: with
``tpu_ingest_mode=hbm`` (default) training uploads the binned matrix to
HBM and is bit-identical to in-core training on every learner path; with
``tpu_ingest_mode=chunked`` the wave grower accumulates histograms
chunk-by-chunk and HBM stays bounded by the declared chunk budget
(``ingest/chunk_pipeline`` MemoryBudget below, checked by ``lint-mem``).
"""

from __future__ import annotations

import os
import tempfile
import zlib
from typing import Any, Dict, Iterator, Optional, Tuple

import numpy as np

from ..analysis.contracts import memory_budget
from ..binning import bin_matrix
from ..config import Config
from ..dataset import Dataset
from ..telemetry.metrics import default_registry
from ..telemetry.trace import span
from ..utils.log import log_info
from .sketch import BinningSketch, sample_row_indices, sampled_rows
from .source import ChunkSource, DEFAULT_CHUNK_ROWS

__all__ = ["StreamedDataset", "ingest_chunk_hbm_bytes"]


# ---------------------------------------------------------------------------
# Memory budget for the chunked-ingest program family (lint-mem enforced).
# The whole point of the ingest path: the curve below is a function of
# (chunk_rows, features, bins, wave_size) ONLY — there is deliberately NO
# total-rows term, and tests/test_ingest.py asserts the curve is flat in
# ctx["rows"].  Terms: a double-buffered chunk ring (bin codes + f32
# grad/hess/mask + row_leaf + weight lanes, ~f+24 B/row), the wave
# histogram accumulator batch plus subtraction/scan temporaries (the same
# 6-layer working set the wave curve budgets), and the segment
# histogram's internally-chunked (rows, F, 3) update tensor (bounded at
# 64 MB by ops/histogram.py).
# ---------------------------------------------------------------------------

def ingest_chunk_hbm_bytes(ctx):
    from ..ops.histogram_pallas import LEAF_CHANNELS, Q_LEAF_CHANNELS
    c = int(ctx.get("chunk_rows", DEFAULT_CHUNK_ROWS))
    f = int(ctx["features"])
    b = int(ctx["bins"])
    it = int(ctx.get("itemsize", 4))
    wave = int(ctx.get("wave_size", LEAF_CHANNELS))
    kernel_ch = Q_LEAF_CHANNELS if ctx.get("quantized") else LEAF_CHANNELS
    leaves = int(ctx.get("leaves", 2))
    rows_term = 2 * c * (f + 24)
    hist = (leaves + 6 * max(2 * wave, kernel_ch)) * f * b * 3 * it
    return rows_term + hist + (64 << 20) + (1 << 20)


memory_budget(
    "ingest/chunk_pipeline", ("ingest",), ingest_chunk_hbm_bytes,
    note="double-buffered chunk ring + wave histogram working set; "
         "flat in total rows by construction")


class StreamedDataset(Dataset):
    """Dataset built from a :class:`ChunkSource` without ever holding the
    raw matrix.  ``spill_dir`` hosts the binned on-disk cache (a temp dir
    by default); ``chunk_rows`` is fixed by the source."""

    def __init__(self, source: ChunkSource,
                 params: Optional[Dict[str, Any]] = None,
                 categorical_feature: Any = "auto",
                 spill_dir: Optional[str] = None,
                 free_raw_data: bool = True) -> None:
        super().__init__(None, params=params,
                         categorical_feature=categorical_feature,
                         free_raw_data=free_raw_data)
        self.source = source
        self.chunk_rows = int(source.chunk_rows)
        self.spill_dir = spill_dir
        self._own_spill = spill_dir is None
        self._spill_path: Optional[str] = None
        self._spill_fd: Optional[int] = None
        self.is_streamed = True

    # -- construction (two streaming passes) --------------------------------
    def construct(self, config: Optional[Config] = None) -> "StreamedDataset":
        if self.constructed:
            return self
        cfg = config or Config(self.params)
        ref = self.reference
        if ref is not None and not ref.constructed:
            ref.construct(cfg)
        if cfg.linear_tree:
            raise ValueError("linear_tree needs raw feature values resident "
                             "in memory; StreamedDataset does not keep them")
        reg = default_registry()
        rows_ctr = reg.counter("ingest_rows_total",
                               "rows streamed through ingest")
        chunks_ctr = reg.counter("ingest_chunks_total",
                                 "chunks streamed through ingest")
        spill_ctr = reg.counter("ingest_spill_bytes_total",
                                "binned bytes spilled to the disk cache")
        src = self.source
        n = src.num_rows()
        f = src.num_features()
        self.num_total_features = f
        names = src.feature_names()
        self.feature_names_ = list(names) if names else \
            [f"Column_{i}" for i in range(f)]
        self.efb = None
        self.raw_used = None
        # pre-partitioned multi-host streaming (ISSUE 18 tentpole): each
        # process streams only ITS shard's ChunkSource; the per-rank
        # sketches ride the mergeable-summary wire format over the host
        # allgather (sketch.allgather_merge), so every rank derives
        # identical mappers while no host ever materializes — or even
        # streams — another host's rows
        from .. import distributed as _dist
        dist_rows = (bool(cfg.pre_partition) and _dist.is_initialized()
                     and _dist.process_count() > 1
                     and self.reference is None)
        self.distributed_rows = dist_rows
        if dist_rows and self._group_arg is not None:
            raise ValueError(
                "pre_partition cannot shard query/group data (queries "
                "must not straddle partitions); drop pre_partition or "
                "the group argument")
        cat_indices = self._resolve_categoricals(self.feature_names_)
        forced_bins = self._load_forced_bins(cfg)

        # ---- pass 1: sketch + metadata ------------------------------------
        if dist_rows:
            sample_cnt = max(1, int(cfg.bin_construct_sample_cnt) //
                             _dist.process_count())
        else:
            sample_cnt = int(cfg.bin_construct_sample_cnt)
        sample_idx = sample_row_indices(n, sample_cnt,
                                        cfg.data_random_seed)
        sketch = BinningSketch(f, cat_indices)
        label = None
        weight = None
        with span("ingest/sketch_pass"):
            for chunk in src.chunks():
                m = chunk.X.shape[0]
                if ref is None:
                    sketch.update(sampled_rows(chunk.X, chunk.offset,
                                               sample_idx))
                if chunk.label is not None:
                    if label is None:
                        label = np.empty(n, np.float64)
                    label[chunk.offset:chunk.offset + m] = chunk.label
                if chunk.weight is not None:
                    if weight is None:
                        weight = np.ones(n, np.float64)
                    weight[chunk.offset:chunk.offset + m] = chunk.weight
                rows_ctr.inc(m)
                chunks_ctr.inc()

        n_total = n
        if dist_rows:
            # merge every rank's summaries in rank order (the mergeable
            # sketch wire format over distributed.allgather_host) —
            # after this, all ranks hold IDENTICAL summaries and derive
            # identical mappers from their disjoint streamed shards
            n_total = int(_dist.allgather_host(
                np.asarray([n], np.float64)).sum())
            sketch.allgather_merge()

        def _filt(sample_total: int) -> int:
            if not cfg.feature_pre_filter:
                return 0
            return max(1, int(cfg.min_data_in_leaf * sample_total /
                              max(1, n_total)))

        if ref is not None:
            # align bins with the reference dataset (dataset.h:304 — the
            # in-core Dataset.construct reference path): a streamed valid
            # set bins against the TRAIN mappers so tree thresholds
            # transfer; no sketch finalize of its own
            if getattr(ref, "efb", None) is not None:
                raise ValueError(
                    "StreamedDataset cannot bin against an EFB-bundled "
                    "reference (the streamed binning pass has no bundle "
                    "step); construct the reference with "
                    "enable_bundle=false")
            self.bin_mappers = ref.bin_mappers
            self.used_feature_map = ref.used_feature_map
            self.num_bins_per_feature = ref.num_bins_per_feature
            self.efb = ref.efb
        else:
            self.bin_mappers = sketch.finalize(
                max_bin=cfg.max_bin, min_data_in_bin=cfg.min_data_in_bin,
                use_missing=cfg.use_missing,
                zero_as_missing=cfg.zero_as_missing,
                forced_bins=forced_bins, pre_filter_cnt_fn=_filt)
            self._finalize_used_features(f)   # shared trivial-filter policy
        used_arr = self.used_feature_map
        mappers = [self.bin_mappers[j] for j in used_arr]
        used = [int(j) for j in used_arr]

        # ---- pass 2: bin + spill ------------------------------------------
        max_bins = max(m_.num_bin for m_ in mappers)
        dtype = np.uint8 if max_bins <= 256 else np.uint16
        if self.spill_dir is None:
            self.spill_dir = tempfile.mkdtemp(prefix="lgbm_tpu_ingest_")
        os.makedirs(self.spill_dir, exist_ok=True)
        self._spill_path = os.path.join(self.spill_dir, "binned.dat")
        # sequential buffered FILE writes, not memmap stores: dirty pages
        # of a writable mapping stay in this process's RSS until
        # unmapped, which would make the "flat working set" claim false
        # at 10^8-row scale (scripts/ingest_smoke.py asserts the RSS
        # ceiling).  Sources must stream in offset order (they do).
        with span("ingest/bin_spill"), open(self._spill_path, "wb") as fh:
            expect = 0
            for chunk in src.chunks():
                if chunk.offset != expect:
                    raise ValueError(
                        f"chunk source must stream rows in order (got "
                        f"offset {chunk.offset}, expected {expect})")
                binned = bin_matrix(
                    np.asarray(chunk.X, np.float64)[:, used_arr], mappers)
                fh.write(np.ascontiguousarray(
                    binned.astype(dtype, copy=False)).tobytes())
                spill_ctr.inc(int(binned.size) * binned.dtype.itemsize)
                expect += chunk.X.shape[0]
        # the Dataset-API view: a read-only memmap (no page is resident
        # until touched; the hbm training route reads it once on upload)
        self.X_binned = np.memmap(self._spill_path, dtype=dtype, mode="r",
                                  shape=(n, len(used)))
        self._label_arg = label if self._label_arg is None else \
            self._label_arg
        self._weight_arg = weight if self._weight_arg is None else \
            self._weight_arg
        n_rows = n
        if dist_rows:
            # pad the LOCAL binned shard to the mesh quantum and
            # replicate the small metadata (shared Dataset machinery);
            # the feature shard itself never leaves this process — the
            # padded copy is the per-host upload staging buffer the DP
            # assembly (gbdt pre_partition route) hands to
            # jax.make_array_from_process_local_data
            n_rows = self._finalize_distributed_rows(n)
        self._set_metadata(n_rows)
        self.constructed = True
        log_info(f"StreamedDataset: {n} rows x {len(used)} features binned "
                 f"in {src.num_chunks()} chunks of {self.chunk_rows} "
                 f"(spill: {self._spill_path}, "
                 f"{os.path.getsize(self._spill_path) >> 20} MB)")
        return self

    # -- chunk access for the chunked trainer --------------------------------
    # (LOCAL rows: under pre_partition the spill cache holds only this
    # process's shard, while num_data() reports the global row count)
    def num_chunks(self) -> int:
        self._check_constructed()
        return -(-self._local_rows() // self.chunk_rows)

    def _local_rows(self) -> int:
        return int(self.X_binned.shape[0])

    def chunk_bounds(self, i: int) -> Tuple[int, int]:
        lo = i * self.chunk_rows
        return lo, min(lo + self.chunk_rows, self._local_rows())

    def binned_chunk(self, i: int) -> np.ndarray:
        """(m, F) binned codes of chunk ``i``, read with a positioned
        ``os.pread`` on a persistent fd (NOT through the memmap: a
        mapping's touched pages pile up in RSS for the run's lifetime,
        while ordinary reads recycle one chunk buffer — the difference
        between a flat and an O(rows) working set over a full training
        pass; the kept fd avoids an open/close pair per chunk per
        histogram pass)."""
        self._check_constructed()
        lo, hi = self.chunk_bounds(i)
        f = self.X_binned.shape[1]
        it = self.X_binned.dtype.itemsize
        if self._spill_fd is None:
            self._spill_fd = os.open(self._spill_path, os.O_RDONLY)
        nbytes = (hi - lo) * f * it
        buf = os.pread(self._spill_fd, nbytes, lo * f * it)
        if len(buf) != nbytes:
            raise IOError(f"short read from spill cache {self._spill_path}")
        return np.frombuffer(buf, dtype=self.X_binned.dtype).reshape(
            hi - lo, f)

    # -- spill lifecycle -----------------------------------------------------
    def close(self) -> None:
        """Release the spill cache.  Self-created temp spill dirs are
        deleted (a CV sweep constructing many StreamedDatasets must not
        accumulate orphaned binned caches in /tmp); caller-provided
        ``spill_dir``s are left in place for reuse."""
        if self._spill_fd is not None:
            try:
                os.close(self._spill_fd)
            except OSError:
                pass
            self._spill_fd = None
        self.X_binned = None
        if self._own_spill and self.spill_dir is not None:
            import shutil
            shutil.rmtree(self.spill_dir, ignore_errors=True)
            self.spill_dir = None
        self.constructed = False

    def __del__(self):  # best effort; close() is the reliable path
        try:
            if getattr(self, "_own_spill", False) and \
                    getattr(self, "spill_dir", None):
                self.close()
        except Exception:
            pass

    def binned_chunks(self) -> Iterator[Tuple[int, np.ndarray]]:
        for i in range(self.num_chunks()):
            yield self.chunk_bounds(i)[0], self.binned_chunk(i)

    # -- fingerprint: stream the crc instead of materializing ----------------
    def fingerprint(self) -> Dict[str, Any]:
        self._check_constructed()
        fp = self._device_cache.get("_fingerprint")
        if fp is not None:
            return fp
        # incremental crc over row blocks == one-shot crc over the full
        # buffer (zlib.crc32 chains); the mapper sha + field layout come
        # from the shared Dataset._fingerprint_with_crc, so this equals
        # the in-core fingerprint of the same binned matrix bit for bit
        crc = 0
        for _, block in self.binned_chunks():
            crc = zlib.crc32(np.ascontiguousarray(block).tobytes(), crc)
        fp = self._fingerprint_with_crc(crc)
        self._device_cache["_fingerprint"] = fp
        return fp
