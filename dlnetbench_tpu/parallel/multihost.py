"""Multi-host (multi-process) runtime support — ICI x DCN meshes.

The reference goes multi-node by launching N MPI ranks and bootstrapping
vendor communicators over them (ncclUniqueId broadcast over MPI, reference
cpp/data_parallel/dp.cpp:183-189; oneCCL KVS handshake, :205-217).  The
TPU equivalent is JAX's multi-controller runtime: one process per host,
``jax.distributed.initialize`` as the bootstrap (the ncclUniqueId-handshake
analogue — coordinator address instead of an MPI broadcast), and a single
global mesh whose axes are laid onto two fabrics:

* **ICI** — the intra-slice torus; fast, carries the latency-sensitive
  axes (tp/ep/sp rings);
* **DCN** — the data-center network between slices; carries the
  bandwidth-tolerant axes (usually dp, sometimes pp).

``make_hybrid_mesh`` expresses exactly that split; collectives inside
``shard_map`` then ride the right fabric with no further code changes —
the same proxy schedules scale from one chip to a multi-slice pod.

Single-process (tests, one chip, virtual CPU mesh) everything degrades
gracefully: ``initialize`` is a no-op, DCN axes of size 1 collapse, and
``barrier`` returns immediately.
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh

_INITIALIZED = False


def initialize(coordinator_address: str | None = None,
               num_processes: int | None = None,
               process_id: int | None = None) -> None:
    """Bootstrap the multi-controller runtime (idempotent).

    On TPU pods all three arguments auto-detect from the environment; pass
    them explicitly for CPU/GPU multi-process tests.  Single-process runs
    (``num_processes`` in (None, 1) with no coordinator) skip
    initialization entirely.
    """
    global _INITIALIZED
    if _INITIALIZED:
        return
    if coordinator_address is None and num_processes in (None, 1) \
            and not _looks_like_tpu_pod():
        return  # plain single-process dev box: nothing to bootstrap
    # distributed init must precede backend init: jax raises if this
    # process has already touched a backend
    jax.distributed.initialize(coordinator_address=coordinator_address,
                               num_processes=num_processes,
                               process_id=process_id)
    _INITIALIZED = True


def _looks_like_tpu_pod() -> bool:
    """Heuristic: env markers that mean jax.distributed auto-detects
    everything and MUST be initialised for multi-host TPU to work.
    A single-worker TPU_WORKER_HOSTNAMES (e.g. 'localhost' on a one-chip
    box) is NOT a pod — only a multi-worker list counts."""
    import os
    return ("," in os.environ.get("TPU_WORKER_HOSTNAMES", "")
            or bool(os.environ.get("MEGASCALE_COORDINATOR_ADDRESS")))


def is_multihost() -> bool:
    return jax.process_count() > 1


def make_hybrid_mesh(dcn: dict[str, int], ici: dict[str, int],
                     devices=None) -> Mesh:
    """Mesh with ``dcn`` axes outermost (sharded across hosts/slices over
    the data-center network) and ``ici`` axes innermost (within a slice).

    >>> make_hybrid_mesh(dcn={"dp": 2}, ici={"pp": 2, "tp": 4})  # 2 slices

    Every DCN axis of size 1 is kept in the mesh (axis names stay stable
    for ``shard_map`` specs) but costs nothing.  On a single host the
    whole mesh degenerates to an ordinary ICI mesh.
    """
    names = tuple(dcn) + tuple(ici)
    shape = tuple(dcn.values()) + tuple(ici.values())
    devices = list(devices) if devices is not None else jax.devices()
    if any(n <= 0 for n in shape):
        raise ValueError(f"axis sizes must be positive: { {**dcn, **ici} }")
    if is_multihost() and any(n > 1 for n in dcn.values()):
        # per-axis factorization: DCN axes replicate across slices
        # (mesh_shape 1 there), ICI axes live within a slice.  Failures
        # here (wrong slice count, unknown topology) must surface — a
        # silently mis-laid mesh would measure the wrong fabric.
        from jax.experimental import mesh_utils
        grid = mesh_utils.create_hybrid_device_mesh(
            mesh_shape=(1,) * len(dcn) + tuple(ici.values()),
            dcn_mesh_shape=tuple(dcn.values()) + (1,) * len(ici),
            devices=devices)
    else:
        # single-host: same validated, ICI-friendly construction as every
        # other mesh maker (raises when too few devices; extra devices
        # beyond the mesh size are deliberately left unused)
        from dlnetbench_tpu.parallel.mesh import _device_grid
        grid = _device_grid(shape, devices)
    return Mesh(grid, names)


def barrier(name: str = "dlnb_barrier") -> None:
    """Global cross-host barrier — the MPI_Barrier analogue (reference
    dp.cpp:234).  No-op single-process."""
    if not is_multihost():
        return
    from jax.experimental import multihost_utils
    multihost_utils.sync_global_devices(name)


def host_metadata() -> list[dict]:
    """One record per process (hostname, process index, local device ids) —
    feeds the multi-host topology view.  Gathered over DCN when multihost;
    local-only otherwise."""
    import json
    import socket
    local = {"process": jax.process_index(),
             "hostname": socket.gethostname(),
             "local_device_ids": [d.id for d in jax.local_devices()]}
    if not is_multihost():
        return [local]
    from jax.experimental import multihost_utils
    payload = json.dumps(local).encode()
    # agree on a buffer size first so a long hostname / big device list on
    # one host can't crash it mid-collective while peers block
    lens = np.asarray(multihost_utils.process_allgather(
        np.array([len(payload)], np.int32)))
    buf = np.zeros(int(lens.max()), np.uint8)
    buf[:len(payload)] = np.frombuffer(payload, np.uint8)
    gathered = np.asarray(multihost_utils.process_allgather(buf))
    return [json.loads(bytes(row).rstrip(b"\x00").decode())
            for row in gathered.reshape(jax.process_count(), -1)]
