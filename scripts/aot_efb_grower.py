"""Compile the wave grower of ``allstate-onehot-efb-q8`` for a DESCRIBED TPU
v5e at the cell's real shape (no chip: nothing runs; what Mosaic or XLA:TPU
would refuse on the chip it refuses here) and print its compile time, its
kernels, its temporaries and the operations under ``lgbm.wave.efb_expand``
(none may be a gather).  The bundle layout is made up: 15 numeric singletons
and the 4,213 indicator columns in bundles of at most 254.

    python scripts/aot_efb_grower.py [--rows 12184290]
"""

import argparse
import json
import os
import re
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))
for k, v in (("JAX_PLATFORMS", "cpu"), ("TPU_LOG_DIR", "disabled"),
             ("TPU_ACCELERATOR_TYPE", "v5litepod-4"), ("TPU_WORKER_HOSTNAMES", "localhost"),
             ("TPU_SKIP_MDS_QUERY", "1")):
    os.environ.setdefault(k, v)

import jax
import jax.numpy as jnp
import numpy as np


class _Mapper:
    def __init__(self, num_bin, default_bin=0):
        self.num_bin, self.default_bin = num_bin, default_bin


def made_up_bundles(f: int, numeric: np.ndarray, seed: int = 0):
    """``BundleInfo`` of ``f`` features: the ``numeric`` ones 255-bin
    singletons, the rest two-bin members dealt at random into bundles of 254."""
    from lightgbm_tpu.efb import build_bundle_info
    mappers = [_Mapper(255 if numeric[j] else 2) for j in range(f)]
    ind = np.random.default_rng(seed).permutation(np.flatnonzero(~numeric))
    bundles = [sorted(int(j) for j in ind[lo:lo + 254]) for lo in range(0, len(ind), 254)]
    bundles += [[int(j)] for j in np.flatnonzero(numeric)]
    return build_bundle_info(mappers, bundles, 255)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=12_184_290)
    args = ap.parse_args()
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from chipbench import datagen_sparse
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.learner.serial import SerialTreeLearner
    from lightgbm_tpu.learner.wave import make_wave_grow_fn
    from lightgbm_tpu.ops.histogram_pallas import pad_rows, traced_kernels

    root = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
    cfg = json.load(open(os.path.join(root, "chipbench/configs/allstate-onehot-efb-q8.json")))
    # what a TPU resolves by itself has to be asked for where JAX runs on the CPU
    params = dict(cfg["params"], tree_grow_mode="wave", tpu_histogram_impl="pallas",
                  tpu_pallas_pipeline="dma", verbosity=-1)
    config = Config(params)
    spec = datagen_sparse.SparseSpec(cfg["data"])
    f, n = spec.features, pad_rows(args.rows)
    info = made_up_bundles(f, ~spec.is_indicator)
    g = info.n_bundles
    num_bins = info.f_nbins.astype(np.int32)
    learner = SerialTreeLearner(config, f, 255, num_bins, np.zeros(f, bool), np.zeros(f, bool),
                                efb=info)
    print("grower paths", learner.grower_paths, "bundles", g, flush=True)
    grow = make_wave_grow_fn(**learner._grow_kwargs, interpret=False)

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    sh = SingleDeviceSharding(topo.devices[0])
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=sh)
    jax.config.update("jax_enable_compilation_cache", False)
    efb_args = tuple(S(a.shape, a.dtype) for a in learner._efb_args)
    t = time.perf_counter()
    lowered = grow.lower(
        S((g, n), jnp.uint8), S((n,), jnp.float32), S((n,), jnp.float32), S((n,), jnp.float32),
        S((f,), jnp.int32), S((f,), jnp.bool_), S((f,), jnp.bool_), S((f,), jnp.int32),
        S((f,), jnp.float32), efb_args, S((f,), jnp.bool_), quant_key=S((2,), jnp.uint32))
    t_lower = time.perf_counter() - t
    t = time.perf_counter()
    compiled = lowered.compile()
    t_compile = time.perf_counter() - t
    mem = compiled.memory_analysis()
    print(f"rows {n}: trace+lower {t_lower:.1f}s compile {t_compile:.1f}s")
    print(f"temporaries {mem.temp_size_in_bytes / 1e9:.3f} GB, arguments "
          f"{mem.argument_size_in_bytes / 1e9:.3f} GB, output {mem.output_size_in_bytes / 1e9:.3f} GB")
    for name in sorted(traced_kernels()):
        print("kernel", name)
    ops = {}
    for line in compiled.as_text().splitlines():
        if "lgbm.wave.efb_expand" in line:
            m = re.search(r"= \S+ (\w[\w\-]*)\(", line)
            if m:
                ops[m.group(1)] = ops.get(m.group(1), 0) + 1
    print("operations under lgbm.wave.efb_expand:", ops)
    gathers = {k: v for k, v in ops.items() if "gather" in k or "scatter" in k}
    print("gathers or scatters among them:", gathers or "none")
    return 1 if gathers else 0


if __name__ == "__main__":
    sys.exit(main())
