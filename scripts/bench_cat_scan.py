"""Time the split scan of ``criteo-kaggle-cat-q8`` alone, at the cell's shape:
84 children x 39 columns (13 count + 26 categorical) x 256 bins, vmapped as the
wave grower vmaps it.  On a TPU it prints the milliseconds a scan and, from a
trace of three scans, the device time of its largest operations; with ``--aot`` it compiles the scan for a DESCRIBED v5e
here (no chip, no time) and prints the compile time and which gathers, scatters
and sorts the compiled program holds.

    python scripts/bench_cat_scan.py [--aot] [--root OTHER_CHECKOUT ...]

Each ``--root`` is another checkout of this repository whose ``ops/split.py`` is
timed beside this one's, and whose results are held bit-equal to this one's.
"""

import argparse
import collections
import importlib.util
import json
import os
import re
import sys
import tempfile
import time

HERE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
sys.path.insert(0, HERE)

CHILDREN, BINS, SCANS = 84, 256, 20


def load_split(root):
    path = os.path.join(root, "lightgbm_tpu", "ops", "split.py")
    spec = importlib.util.spec_from_file_location("split_" + str(abs(hash(root))), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell():
    import numpy as np
    cfg = json.load(open(os.path.join(HERE, "chipbench/configs/criteo-kaggle-cat-q8.json")))
    p, card = cfg["params"], cfg["data"]["cardinality"]
    f = 39
    is_cat = np.arange(f) >= 13
    num_bins = np.array([255] * 13 + [min(c, 254) + 1 for c in card], np.int32)
    has_nan = np.array([q > 0 for q in cfg["data"]["int_missing"]] + [False] * 26)
    kw = dict(min_data_in_leaf=p["min_data_in_leaf"], cat_l2=p["cat_l2"], cat_smooth=p["cat_smooth"],
              max_cat_to_onehot=p["max_cat_to_onehot"], max_cat_threshold=p["max_cat_threshold"],
              min_data_per_group=p["min_data_per_group"], use_cat_subset=True, any_cat=True,
              cat_idx=tuple(range(13, f)))
    rng = np.random.RandomState(5)
    cnt = np.floor(rng.pareto(1.2, (CHILDREN, f, BINS)) * 40).astype(np.float32)
    cnt *= np.arange(BINS)[None, None, :] < num_bins[None, :, None]
    grad = (rng.randn(CHILDREN, f, BINS) * np.sqrt(cnt) * 0.3).astype(np.float32)
    hist = np.stack([grad, cnt * 0.2, cnt], -1).astype(np.float32)
    return kw, hist, hist[:, 0].sum(1), num_bins, is_cat, has_nan


def scan_fn(mod, kw, num_bins, is_cat, has_nan):
    import jax
    import jax.numpy as jnp
    sp = mod.SplitParams(**kw)
    nb, ic, hn = jnp.asarray(num_bins), jnp.asarray(is_cat), jnp.asarray(has_nan)
    return jax.jit(jax.vmap(lambda h, s: mod._best_split_impl(h, s, nb, ic, hn, sp)))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--aot", action="store_true")
    ap.add_argument("--root", action="append", default=[])
    args = ap.parse_args()
    if args.aot:
        for k, v in (("JAX_PLATFORMS", "cpu"), ("TPU_LOG_DIR", "disabled"),
                     ("TPU_ACCELERATOR_TYPE", "v5litepod-4"),
                     ("TPU_WORKER_HOSTNAMES", "localhost"), ("TPU_SKIP_MDS_QUERY", "1")):
            os.environ.setdefault(k, v)
    import jax
    import jax.numpy as jnp
    import numpy as np
    kw, hist, sums, num_bins, is_cat, has_nan = cell()
    roots = [HERE] + args.root
    fns = [scan_fn(load_split(r), kw, num_bins, is_cat, has_nan) for r in roots]

    if args.aot:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        sh = SingleDeviceSharding(topo.devices[0])
        jax.config.update("jax_enable_compilation_cache", False)
        for root, fn in zip(roots, fns):
            t = time.perf_counter()
            compiled = fn.lower(jax.ShapeDtypeStruct(hist.shape, jnp.float32, sharding=sh),
                                jax.ShapeDtypeStruct(sums.shape, jnp.float32, sharding=sh)).compile()
            text = compiled.as_text()
            ops = collections.Counter(re.findall(r"= \S+ (gather|scatter|sort|dynamic-slice)\(", text))
            print(f"{root}: lower+compile {time.perf_counter() - t:.1f}s {dict(ops)}", flush=True)
        return 0

    if jax.devices()[0].platform != "tpu":
        print("needs a TPU (or --aot)", file=sys.stderr)
        return 1
    from chipbench import scope_reduce as sr
    from chipbench import trace_reduce as tr
    hist_d, sums_d = jnp.asarray(hist), jnp.asarray(sums)
    outs = []
    for root, fn in zip(roots, fns):
        t = time.perf_counter()
        out = jax.block_until_ready(fn(hist_d, sums_d))
        t_first = time.perf_counter() - t
        outs.append(jax.tree.map(np.asarray, out))
        t = time.perf_counter()
        for _ in range(SCANS):
            out = fn(hist_d, sums_d)
        jax.block_until_ready(out)
        ms = (time.perf_counter() - t) / SCANS * 1e3
        print(f"{root}: first call {t_first:.1f}s, {ms:.3f} ms a scan "
              f"(host clock, {SCANS} scans back to back)", flush=True)
        trace_dir = tempfile.mkdtemp(prefix="cat_scan_trace_")
        with jax.profiler.trace(trace_dir):
            for _ in range(3):
                out = fn(hist_d, sums_d)
            jax.block_until_ready(out)
        with open(tr.find_xplane(trace_dir), "rb") as fh:
            events, _ = sr.read_scoped_events(fh.read())
        per = collections.Counter()
        for _, name, _, start, end in events:
            if not sr._is_container(name):
                per[name.split(" = ")[0].lstrip("%")] += end - start
        print(f"  device: {sum(per.values()) / 3e6:.3f} ms a scan; its largest operations:")
        for name, ns in per.most_common(10):
            print(f"    {ns / 3e6:8.3f} ms  {name[:80]}")
    for root, o in zip(roots[1:], outs[1:]):
        same = all(a.tobytes() == b.tobytes() for a, b in zip(outs[0], o))
        print(f"bit-equal to {root}: {same}")
        if not same:
            return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
