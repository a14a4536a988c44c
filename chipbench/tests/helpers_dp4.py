"""A tiny four-device cell for the tests, beside ``helpers.make_root``'s
one-device one: configuration ``tiny-dp4`` (``tree_learner=data`` over four CPU
devices, a ``mesh`` group), mix ``tiny-steady-blocks`` (driver
``train_loop_blocks``), cell ``tiny-dp4.train``; and the planted fault, one
shard's histograms left out of every merge."""

from __future__ import annotations

import json
import os

from chipbench.tests import helpers

CHIPS = 4
ROWS = 6000
MESH_METRICS = ("collective_device_ms_per_tree", "collective_bytes_per_pass",
                "mesh_hist_kernel_roofline", "mesh_tree_step_mfu")


def ask_for_devices() -> None:
    """Ask for CPU devices enough for the mesh while JAX still takes the
    answer (before its first backend).  No backend is started here."""
    import jax
    try:
        if jax.config.jax_num_cpu_devices < CHIPS:
            jax.config.update("jax_num_cpu_devices", CHIPS)
    except RuntimeError:
        pass          # a backend is up: what it has is what there is


def make_root(tmp: str) -> str:
    root = helpers.make_root(tmp, quantized=True)
    extra = os.path.join(root, "extrabench")
    cfg = helpers.tiny_config("tiny-dp4", True, rows=ROWS)
    cfg["params"].update(tree_learner="data", num_devices=CHIPS)
    cfg["mesh"] = {"chips": CHIPS, "rows_per_chip": ROWS // CHIPS}
    with open(os.path.join(extra, "configs", "tiny-dp4.json"), "w") as fh:
        json.dump(cfg, fh)
    with open(os.path.join(extra, "workloads", "tiny-steady-blocks.json"), "w") as fh:
        json.dump(dict(helpers.TINY_MIX, name="tiny-steady-blocks", driver="train_loop_blocks"), fh)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as fh:
        manifest = json.load(fh)
    manifest["configs"].append({"name": "tiny-dp4", "source": "test", "reduced": [], "why": "test",
                                "file": "extrabench/configs/tiny-dp4.json"})
    manifest["workloads"].append({"name": "tiny-dp4.train", "config": "tiny-dp4",
                                  "traffic": "tiny-steady-blocks", "chips": 1, "why": "test"})
    for m in manifest["per_layer"]:
        if "criteo-q8-dp4.train" in m["workloads"]:
            m["workloads"].append("tiny-dp4.train")
    with open(path, "w") as fh:
        json.dump(manifest, fh)
    return root


def drop_one_shard(monkeypatch, shard: int = 0) -> None:
    """The planted fault: shard ``shard``'s histograms never reach the merge
    (its rows are counted by nobody), in every histogram collective."""
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.parallel.data_parallel import WaveDPStrategy

    def without(real):
        def reduce(self, hist):
            mine = jax.lax.axis_index(self.axis_name) == shard
            return real(self, jnp.where(mine, jnp.zeros_like(hist), hist))
        return reduce

    for name in ("reduce_hist", "reduce_hist_scatter"):
        monkeypatch.setattr(WaveDPStrategy, name, without(getattr(WaveDPStrategy, name)))
