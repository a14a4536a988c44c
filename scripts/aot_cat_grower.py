"""Compile the wave grower of ``criteo-kaggle-cat-q8`` for a DESCRIBED TPU v5e
at the cell's real shape (no chip: nothing runs; what Mosaic or XLA:TPU would
refuse on the chip it refuses here) and print its compile time, its kernels
and its temporaries.

    python scripts/aot_cat_grower.py [--rows 45840617] [--narrow]
"""

import argparse
import json
import os
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


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=45_840_617)
    ap.add_argument("--narrow", action="store_true", help="one int32 accumulation a pass")
    args = ap.parse_args()
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.learner.serial import SerialTreeLearner
    from lightgbm_tpu.learner.wave import make_wave_grow_fn
    from lightgbm_tpu.ops.histogram_pallas import pad_rows, traced_kernels
    from lightgbm_tpu.ops.quantize import hist_acc_rows

    root = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
    cfg = json.load(open(os.path.join(root, "chipbench/configs/criteo-kaggle-cat-q8.json")))
    # what a TPU resolves by itself has to be asked for where JAX runs on the CPU
    params = dict(cfg["params"], tree_grow_mode="wave", tpu_histogram_impl="pallas",
                  tpu_pallas_pipeline="dma", verbosity=-1)
    params.pop("categorical_feature")
    config = Config(params)
    f, n = 39, pad_rows(args.rows)
    is_cat = np.arange(f) >= 13
    card = cfg["data"]["cardinality"]
    num_bins = np.array([255] * 13 + [min(c, 254) + 1 for c in card], np.int32)
    has_nan = np.array([p > 0 for p in cfg["data"]["int_missing"]] + [False] * 26)
    acc = 0 if args.narrow else hist_acc_rows(n, 127, 127, 0.77)
    learner = SerialTreeLearner(config, f, 255, num_bins, is_cat, has_nan, acc_rows=acc)
    print("grower paths", learner.grower_paths, flush=True)
    grow = make_wave_grow_fn(**learner._grow_kwargs, interpret=False)

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    sh = SingleDeviceSharding(topo.devices[0])
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=sh)
    jax.config.update("jax_enable_compilation_cache", False)
    t = time.perf_counter()
    lowered = grow.lower(
        S((f, n), jnp.uint8), S((n,), jnp.float32), S((n,), jnp.float32), S((n,), jnp.float32),
        S((f,), jnp.int32), S((f,), jnp.bool_), S((f,), jnp.bool_), S((f,), jnp.int32),
        S((f,), jnp.float32), (), S((f,), jnp.bool_), quant_key=S((2,), jnp.uint32))
    t_lower = time.perf_counter() - t
    t = time.perf_counter()
    compiled = lowered.compile()
    t_compile = time.perf_counter() - t
    mem = compiled.memory_analysis()
    print(f"rows {n} acc_rows {acc}: trace+lower {t_lower:.1f}s compile {t_compile:.1f}s")
    print(f"temporaries {mem.temp_size_in_bytes / 1e9:.3f} GB, arguments "
          f"{mem.argument_size_in_bytes / 1e9:.3f} GB, output {mem.output_size_in_bytes / 1e9:.3f} GB")
    for name in sorted(traced_kernels()):
        print("kernel", name)
    return 0


if __name__ == "__main__":
    sys.exit(main())
