"""Compile the lambdarank gradient program of ``istella-letor-rank-q8`` for a
DESCRIBED TPU v5e at the cell's real shape (no chip: nothing runs; what
XLA:TPU would refuse on the chip it refuses here) and print its compile
time, its temporaries and its query layout (buckets, row slots, pair slots,
the pairs the reference's loop visits).  The queries are the configuration's
own (``chipbench/datagen_rank.py`` draws their lengths from ``weights_seed``);
the labels are made up.

    python scripts/aot_rank_lambdas.py
"""

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
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from chipbench import datagen_rank
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.dataset import Metadata
    from lightgbm_tpu.objective.rank import LambdarankNDCG

    root = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
    cfg = json.load(open(os.path.join(root, "chipbench/configs/istella-letor-rank-q8.json")))
    spec = datagen_rank.RankSpec(cfg["data"])
    md = Metadata()
    md.set_label(np.random.default_rng(0).integers(0, 5, spec.rows))
    md.set_group(spec.lengths)
    obj = LambdarankNDCG(Config(dict(cfg["params"], verbosity=-1)))
    t = time.perf_counter()
    obj.init(md, spec.rows)
    print(f"layout made on the host in {time.perf_counter() - t:.1f}s: {obj.record()}", flush=True)

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    sh = SingleDeviceSharding(topo.devices[0])
    S = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh)
    jax.config.update("jax_enable_compilation_cache", False)
    args = jax.tree_util.tree_map(S, obj._bucket_args)
    t = time.perf_counter()
    lowered = obj._grad_fn.lower(S(jnp.zeros(spec.rows, jnp.float32)), *args)
    t_lower = time.perf_counter() - t
    t = time.perf_counter()
    compiled = lowered.compile()
    t_compile = time.perf_counter() - t
    mem = compiled.memory_analysis()
    print(f"trace+lower {t_lower:.1f}s compile {t_compile:.1f}s")
    print(f"temporaries {mem.temp_size_in_bytes / 1e9:.3f} GB, arguments "
          f"{mem.argument_size_in_bytes / 1e9:.3f} GB, output {mem.output_size_in_bytes / 1e9:.3f} GB")
    return 0


if __name__ == "__main__":
    sys.exit(main())
