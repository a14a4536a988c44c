"""Multi-host bootstrap: the TPU-native replacement for the reference's
machine-list network init.

The reference boots its socket mesh from ``machine_list_file`` + per-rank
TCP handshakes (reference: src/network/linkers_socket.cpp; CLI entry
application.cpp:168-178 ``Network::Init``; Python ``set_network``
basic.py:2178).  On TPU the equivalent is the JAX multi-process runtime:
every host process calls :func:`init` once, after which ``jax.devices()``
spans ALL hosts' chips and the parallel tree learners' ``shard_map``
collectives ride ICI within a slice and DCN across slices — no framework
transport code at all (SURVEY.md §2.5 TPU mapping).

Single-host multi-chip needs none of this: a local mesh over
``jax.local_devices()`` is built automatically from ``num_devices``.

Typical multi-host launch (one process per host, same program)::

    import lightgbm_tpu as lgb
    lgb.distributed.init(coordinator_address="10.0.0.1:1234",
                         num_processes=4, process_id=rank)
    bst = lgb.train({"tree_learner": "data", ...}, dset)
"""

from __future__ import annotations

from typing import Optional

from .utils.log import log_info, log_warning

_initialized = False


def init(coordinator_address: Optional[str] = None,
         num_processes: Optional[int] = None,
         process_id: Optional[int] = None,
         local_device_ids=None,
         cpu_collectives: str = "gloo") -> None:
    """Initialize the JAX multi-process runtime (replaces the reference's
    ``Network::Init`` rank-0 handshake, network.cpp:26-43).

    On managed TPU slices (GKE/TPU VM) all arguments are optional — JAX
    discovers the topology from the environment; pass them explicitly for
    manual clusters, mirroring machine_list_file + local_listen_port.

    After init, the parallel tree learners work UNCHANGED: their mesh
    spans all hosts' devices and every process runs the same SPMD driver
    with the full host-side data — the reference's default distributed
    mode without ``pre_partition`` (each machine loads all data,
    dataset_loader.cpp:181 ``LoadFromFile(rank, num_machines)``); device
    memory shards across hosts even though host memory does not.

    ``cpu_collectives`` selects the cross-process collective backend for
    CPU clusters (gloo; TPU meshes use ICI/DCN natively).
    """
    global _initialized
    if _initialized:
        log_warning("lightgbm_tpu.distributed.init called twice; ignoring")
        return
    import jax
    if cpu_collectives:
        # an invalid VALUE raises here, so a typo'd backend fails loudly
        # rather than hanging at the first cross-process collective
        jax.config.update("jax_cpu_collectives_implementation",
                          cpu_collectives)
    jax.distributed.initialize(coordinator_address=coordinator_address,
                               num_processes=num_processes,
                               process_id=process_id,
                               local_device_ids=local_device_ids)
    _initialized = True
    log_info(f"distributed runtime up: process {jax.process_index()}/"
             f"{jax.process_count()}, {len(jax.local_devices())} local / "
             f"{len(jax.devices())} global devices")


def shutdown() -> None:
    """Tear down the multi-process runtime (reference LGBM_NetworkFree)."""
    global _initialized
    if not _initialized:
        return
    import jax
    jax.distributed.shutdown()
    _initialized = False


def is_initialized() -> bool:
    return _initialized


def allgather_host(arr) -> "object":
    """Concatenate per-process host arrays along axis 0 in rank order.

    The host-side collective behind pre-partitioned ingest (the analog of
    the reference's BinMapper allgather, dataset_loader.cpp:1040-1130):
    bin-finding samples and metadata gathered once at Dataset.construct;
    variable per-rank lengths are handled by a max-pad + trim."""
    import numpy as np
    import jax
    from jax.experimental import multihost_utils
    arr = np.asarray(arr)
    if jax.process_count() == 1:
        return arr
    if arr.dtype == np.float64:
        # x64 is disabled in JAX by default, so a float64 array would be
        # silently rounded to float32 in transit; ship the raw bits as
        # uint32 pairs instead (bin boundaries and labels must survive
        # exactly for the serial/distributed parity contract)
        return allgather_host(arr.view(np.uint32)).view(np.float64)
    lens = np.asarray(multihost_utils.process_allgather(
        np.asarray([arr.shape[0]], np.int32))).ravel()
    m = int(lens.max())
    if m > arr.shape[0]:
        pad = np.zeros((m - arr.shape[0],) + arr.shape[1:], arr.dtype)
        arr = np.concatenate([arr, pad], axis=0)
    gathered = np.asarray(multihost_utils.process_allgather(arr))
    return np.concatenate(
        [gathered[r, :int(lens[r])] for r in range(len(lens))], axis=0)


def process_index() -> int:
    import jax
    return jax.process_index()


def process_count() -> int:
    import jax
    return jax.process_count()
