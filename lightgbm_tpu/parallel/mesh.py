"""Device mesh helpers (replaces reference Network::Init bootstrap,
src/network/linkers_socket.cpp machine-list TCP handshake — on TPU the mesh
is declared, XLA routes collectives over ICI/DCN)."""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["get_mesh", "shard_rows", "replicate"]


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
