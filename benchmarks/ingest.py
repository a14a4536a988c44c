"""Out-of-core ingest benchmark: the source x chunk_rows ladder, in
bench-matrix-v1 records.

Each rung streams a synthetic/mmap/CSV source through the full
StreamedDataset construct (sketch pass + bin/spill pass) and reports
rows/s plus effective host->spill GB/s; the chunked-training rungs
additionally measure host->HBM streamed GB/s per full histogram pass
(the bytes-per-pass budget PERF.md round 12 tracks).  At sizes that
also fit in core (<= INCORE_CAP rows) the in-core ``Dataset.construct``
is timed on identical data for a ``speedup_vs_incore`` column (usually
< 1 — streaming trades wall time for the O(rows) raw matrix it never
allocates; the point of the ladder is that streamed cost per row stays
FLAT as rows grow past what in-core can hold at all).

    JAX_PLATFORMS=cpu ROWS=1000000 python benchmarks/ingest.py \
        --json ingest.json

Env knobs: ROWS (default 1<<20), FEATURES (16), CHUNK_LADDER
("65536,262144"), SOURCES ("synthetic,mmap"), TRAIN_ROUNDS (2; 0 skips
the training rungs), INCORE_CAP (4<<20).
"""

import json
import os
import subprocess
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

ROWS = int(os.environ.get("ROWS", 1 << 20))
FEATURES = int(os.environ.get("FEATURES", 16))
CHUNK_LADDER = tuple(int(c) for c in
                     os.environ.get("CHUNK_LADDER", "65536,262144").split(","))
SOURCES = tuple(os.environ.get("SOURCES", "synthetic,mmap").split(","))
TRAIN_ROUNDS = int(os.environ.get("TRAIN_ROUNDS", 2))
INCORE_CAP = int(os.environ.get("INCORE_CAP", 4 << 20))

_PARAMS = {"objective": "binary", "verbosity": -1, "max_bin": 63,
           "num_leaves": 31, "enable_bundle": False,
           "use_quantized_grad": True, "stochastic_rounding": False,
           "tree_grow_mode": "wave", "tpu_exact_endgame": False,
           "tpu_speculative_ramp": False}


def _git_sha():
    try:
        return subprocess.run(
            ["git", "rev-parse", "HEAD"], capture_output=True, text=True,
            cwd=os.path.dirname(os.path.abspath(__file__)),
            timeout=10).stdout.strip() or None
    except Exception:
        return None


def _make_source(kind, rows, chunk_rows, workdir):
    from lightgbm_tpu.ingest import (CSVSource, NumpyMmapSource,
                                     SyntheticSource)
    if kind == "synthetic":
        return SyntheticSource(rows, FEATURES, chunk_rows=chunk_rows, seed=1)
    syn = SyntheticSource(rows, FEATURES, chunk_rows=max(CHUNK_LADDER),
                          seed=1)
    if kind == "mmap":
        xp = os.path.join(workdir, f"x_{rows}.npy")
        yp = os.path.join(workdir, f"y_{rows}.npy")
        if not os.path.exists(xp):
            X = np.lib.format.open_memmap(
                xp, mode="w+", dtype=np.float64, shape=(rows, FEATURES))
            Y = np.lib.format.open_memmap(
                yp, mode="w+", dtype=np.float64, shape=(rows,))
            for c in syn.chunks():
                X[c.offset:c.offset + len(c.X)] = c.X
                Y[c.offset:c.offset + len(c.X)] = c.label
            X.flush()
            Y.flush()
            del X, Y
        return NumpyMmapSource(xp, yp, chunk_rows=chunk_rows)
    if kind == "csv":
        path = os.path.join(workdir, f"d_{rows}.csv")
        if not os.path.exists(path):
            with open(path, "w") as fh:
                for c in syn.chunks():
                    for i in range(len(c.X)):
                        fh.write(f"{c.label[i]:g}," + ",".join(
                            f"{v:.9g}" for v in c.X[i]) + "\n")
        return CSVSource(path, chunk_rows=chunk_rows)
    raise ValueError(f"unknown source kind {kind}")


def main(argv):
    from lightgbm_tpu.utils.cache import configure_compile_cache
    configure_compile_cache()
    json_path = None
    if "--json" in argv:
        i = argv.index("--json")
        json_path = argv[i + 1]
        del argv[i:i + 2]

    import lightgbm_tpu as lgb
    from lightgbm_tpu.ingest import StreamedDataset, train_streamed
    from lightgbm_tpu.telemetry.metrics import default_registry
    from lightgbm_tpu.utils.backend import default_backend

    rows_out = []
    workdir = tempfile.mkdtemp(prefix="lgbm_ingest_bench_")
    incore_dt = None
    if ROWS <= INCORE_CAP:
        syn = _make_source("synthetic", ROWS, max(CHUNK_LADDER), workdir)
        X = np.concatenate([c.X for c in syn.chunks()])
        y = np.concatenate([c.label for c in syn.chunks()])
        t0 = time.perf_counter()
        lgb.Dataset(X, label=y, params=_PARAMS).construct()
        incore_dt = time.perf_counter() - t0
        rows_out.append({
            "name": "construct_incore",
            "config": {"source": "incore", "rows": ROWS,
                       "features": FEATURES, "chunk_rows": 0},
            "rows_per_sec": round(ROWS / incore_dt, 1),
            "raw_bytes_resident": ROWS * FEATURES * 8,
        })
        print(json.dumps(rows_out[-1]), flush=True)
        del X, y

    for kind in SOURCES:
        for chunk_rows in CHUNK_LADDER:
            if chunk_rows > ROWS:
                continue
            src = _make_source(kind, ROWS, chunk_rows, workdir)
            spill = os.path.join(workdir, f"spill_{kind}_{chunk_rows}")
            t0 = time.perf_counter()
            sd = StreamedDataset(src, params=_PARAMS,
                                 spill_dir=spill).construct()
            dt = time.perf_counter() - t0
            spill_bytes = os.path.getsize(
                os.path.join(spill, "binned.dat"))
            rec = {
                "name": f"construct_{kind}_c{chunk_rows}",
                "config": {"source": kind, "rows": ROWS,
                           "features": FEATURES, "chunk_rows": chunk_rows},
                "rows_per_sec": round(ROWS / dt, 1),
                "gbytes_per_sec": round(ROWS * FEATURES * 8 / dt / 1e9, 3),
                "spill_bytes": spill_bytes,
            }
            if incore_dt is not None:
                rec["speedup_vs_incore"] = round(incore_dt / dt, 3)
            rows_out.append(rec)
            print(json.dumps(rec), flush=True)

            if TRAIN_ROUNDS > 0 and kind == SOURCES[0]:
                reg = default_registry()
                ctr = reg.counter("ingest_train_h2d_bytes_total", "")
                b0 = ctr.value()
                t0 = time.perf_counter()
                bst = train_streamed(_PARAMS, sd,
                                     num_boost_round=TRAIN_ROUNDS)
                dt = time.perf_counter() - t0
                passes = sum(int(t.num_leaves) > 1
                             for t in bst._gbdt.models)
                h2d = ctr.value() - b0
                rec = {
                    "name": f"train_chunked_{kind}_c{chunk_rows}",
                    "config": {"source": kind, "rows": ROWS,
                               "features": FEATURES,
                               "chunk_rows": chunk_rows,
                               "rounds": TRAIN_ROUNDS},
                    "iters_per_sec": round(TRAIN_ROUNDS / dt, 4),
                    "h2d_gbytes_total": round(h2d / 1e9, 3),
                    "h2d_gbytes_per_sec": round(h2d / dt / 1e9, 3),
                    "trees": passes,
                }
                rows_out.append(rec)
                print(json.dumps(rec), flush=True)

    if json_path:
        record = {
            "schema": "bench-matrix-v1",
            "bench": "ingest",
            "git_sha": _git_sha(),
            "backend": default_backend(),
            "rows": rows_out,
        }
        with open(json_path, "w") as f:
            json.dump(record, f, indent=2)
        print(json.dumps({"written": json_path,
                          "rungs": len(rows_out)}), flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
