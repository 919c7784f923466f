"""Device-mesh construction — the TPU-native replacement for the
reference's communicator-color machinery.

The reference forms process groups by splitting MPI_COMM_WORLD with color
math over a 3D rank grid (reference cpp/hybrid_parallel/hybrid_3d.cpp:283-300)
and bootstrapping a vendor communicator per group.  On TPU the grouping is a
``jax.sharding.Mesh``: each parallelism dimension is a named mesh axis, a
"communicator" is just the axis name passed to a collective inside
``shard_map``, and the runtime lays the axes onto the ICI torus (innermost
axes get the fastest links).  ``Grid3D`` from the schedule algebra maps onto
axes in the same fastest-varying-last order, so coordinates agree with the
reference's ``tp_id = rank % tp`` convention (hybrid_3d.cpp:283-285).
"""
from __future__ import annotations

import math

import jax
import numpy as np
from jax.sharding import Mesh

from dlnetbench_tpu.core.schedule import Grid3D

AXIS_DP = "dp"
AXIS_PP = "pp"
AXIS_TP = "tp"   # also carries EP (expert) grouping in the MoE proxies
AXIS_SP = "sp"   # sequence/context parallelism
AXIS_FLAT = "x"  # single-axis meshes (dp / fsdp proxies)


# mesh reuse across sweep grid points (sweep.py in-process mode): a
# Mesh over the same devices/shape/axes is immutable, and rebuilding it
# per point would defeat jax-internal sharding caches keyed on mesh
# identity.  Keyed on device ids so distinct --devices subsets coexist,
# AND on the device objects' python identity: after a backend re-init
# jax hands out NEW device objects with the SAME ids, and a Mesh over
# the dead backend's devices must never be served from here.
_MESH_CACHE: dict = {}

# grids laid out in the order the devices were given because jax knows
# no topology-aware assignment for them (keyed like _MESH_CACHE's device
# part); ``describe_mesh`` stamps it into the record
_GIVEN_ORDER: set = set()


def _cached_mesh(shape: tuple[int, ...], axes: tuple[str, ...],
                 devices) -> Mesh:
    devices = list(devices) if devices is not None else jax.devices()
    key = (tuple(shape), tuple(axes),
           tuple((d.id, id(d)) for d in devices))
    mesh = _MESH_CACHE.get(key)
    if mesh is None:
        mesh = Mesh(_device_grid(tuple(shape), devices), tuple(axes))
        _MESH_CACHE[key] = mesh
    return mesh


def _device_grid(shape: tuple[int, ...], devices=None) -> np.ndarray:
    devices = list(devices) if devices is not None else jax.devices()
    need = math.prod(shape)
    if need > len(devices):
        raise ValueError(f"mesh shape {shape} needs {need} devices, "
                         f"have {len(devices)}")
    if need < len(devices):
        devices = devices[:need]
    # let JAX pick an ICI-friendly assignment when it knows the topology
    from jax.experimental import mesh_utils
    try:
        return mesh_utils.create_device_mesh(shape, devices=devices)
    except (AssertionError, NotImplementedError, ValueError):
        # a device set that is no cuboid of the torus (fault-shrink
        # survivors, ``--devices 0,3``) has no such assignment: keep the
        # order given, and let the record say so
        _GIVEN_ORDER.add(tuple(d.id for d in devices))
        return np.asarray(devices).reshape(shape)


def make_flat_mesh(world_size: int | None = None, devices=None,
                   axis: str = AXIS_FLAT) -> Mesh:
    """1D mesh over all (or the first ``world_size``) devices — the analogue
    of MPI_COMM_WORLD for the dp proxy (reference dp.cpp:224)."""
    devices = list(devices) if devices is not None else jax.devices()
    n = world_size if world_size is not None else len(devices)
    return _cached_mesh((n,), (axis,), devices)


def make_grid_mesh(dp: int = 1, pp: int = 1, tp: int = 1,
                   devices=None) -> Mesh:
    """3D mesh (dp, pp, tp) with tp fastest-varying — device at mesh
    coordinate (d, p, t) is rank ``(d*pp + p)*tp + t``, matching the
    reference grid layout (hybrid_3d.cpp:283-285) so the innermost (tp/ep)
    axis, which carries the most latency-sensitive traffic, sits on
    neighboring ICI links."""
    return _cached_mesh((dp, pp, tp), (AXIS_DP, AXIS_PP, AXIS_TP), devices)


def make_fsdp_mesh(num_replicas: int, sharding_factor: int,
                   devices=None) -> Mesh:
    """2D mesh (replica, shard) for the FSDP proxy — the analogue of the
    reference's two comm splits, intra-shard ``unit_comm`` and inter-replica
    ``allreduce_comm`` (reference fsdp.cpp:257-265)."""
    return _cached_mesh((num_replicas, sharding_factor),
                        (AXIS_DP, AXIS_TP), devices)


def make_sp_mesh(sp: int, dp: int = 1, devices=None) -> Mesh:
    """2D mesh (dp, sp) for the sequence-parallel proxies; sp innermost so
    the ring rides neighboring ICI links."""
    return _cached_mesh((dp, sp), (AXIS_DP, AXIS_SP), devices)


def mesh_from_grid(grid: Grid3D, devices=None) -> Mesh:
    return make_grid_mesh(dp=grid.dp, pp=grid.pp, tp=grid.tp, devices=devices)


def describe_mesh(mesh: Mesh) -> dict:
    """Topology description for the metrics header — the counterpart of the
    reference's ASCII SLURM-switch graph (reference
    cpp/netcommunicators.hpp:142-290), built from device coords instead of
    ``SLURM_TOPOLOGY_ADDR``."""
    devs = mesh.devices.flatten().tolist()
    info = {
        "axes": dict(zip(mesh.axis_names, mesh.devices.shape)),
        "num_devices": len(devs),
        "platform": devs[0].platform,
        "device_kind": devs[0].device_kind,
        "num_hosts": len({d.process_index for d in devs}),
        # explicit marker (not just platform: cpu): collectives on a
        # virtual host mesh move loopback/thread bytes, and bandwidth
        # numbers derived from them must never be read as fabric numbers
        "fabric": "virtual" if devs[0].platform == "cpu" else "real",
    }
    if tuple(d.id for d in devs) in _GIVEN_ORDER:
        # no topology-aware assignment existed for this device set:
        # neighbours on a mesh axis need not be neighbours on the torus
        info["device_order"] = "given"
    coords = []
    for d in devs:
        c = getattr(d, "coords", None)
        coords.append({"id": d.id, "process": d.process_index,
                       **({"coords": tuple(c)} if c is not None else {})})
    info["devices"] = coords
    return info
