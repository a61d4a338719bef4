"""Device mesh + collective shim: the Network layer, TPU-native.

Reference: src/network/network.cpp + linkers (UNVERIFIED — empty mount,
see SURVEY.md banner): the reference hand-implements Allreduce
(recursive-halving/doubling), Bruck AllGather and ReduceScatter over TCP
sockets / MPI, with rank discovery from a machine list.

TPU-native replacement (SURVEY.md §5 "Distributed communication backend"):
the ``jax.sharding.Mesh`` IS the machine list — rank discovery, topology
and transport all collapse into XLA collectives (psum / all_gather /
psum_scatter) over ICI (intra-slice) or DCN (multi-slice). This module
keeps learner code transport-agnostic: learners name a mesh axis and call
``lax`` collectives; tests run the same program on 8 fake CPU devices
(``--xla_force_host_platform_device_count=8``), the driver dry-runs it on
a virtual mesh, and real pods just change the device list.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


# the one spelling every shard_map call site of the package goes
# through (tests patch it here to see which programs are sharded)
shard_map = jax.shard_map

DATA_AXIS = "data"
FEATURE_AXIS = "feature"

__all__ = ["Mesh", "NamedSharding", "P", "shard_map", "DATA_AXIS",
           "FEATURE_AXIS", "create_data_mesh", "num_devices",
           "shard_rows", "replicate", "local_mesh_positions"]


def local_mesh_positions(mesh: Mesh):
    """(positions, devices) of THIS process's addressable devices in
    mesh-flat order — the rank ids a multi-process engine computes for
    locally (the streaming engine's shard layout; one device per
    process on CPU gangs, all of them single-process)."""
    me = jax.process_index()
    flat = list(mesh.devices.flat)
    pos = [i for i, d in enumerate(flat) if d.process_index == me]
    return pos, [flat[i] for i in pos]


def num_devices() -> int:
    return jax.device_count()


def create_data_mesh(n_devices: Optional[int] = None) -> Mesh:
    """1-D mesh over the data axis (rows sharded, features replicated)."""
    devs = jax.devices()
    if n_devices is not None:
        devs = devs[:n_devices]
    return Mesh(np.array(devs), (DATA_AXIS,))


def create_feature_mesh(n_devices: Optional[int] = None) -> Mesh:
    """1-D mesh over the feature axis (columns sharded, rows replicated)
    — the feature-parallel learner's layout."""
    devs = jax.devices()
    if n_devices is not None:
        devs = devs[:n_devices]
    return Mesh(np.array(devs), (FEATURE_AXIS,))


def create_2d_mesh(data: int, feature: int) -> Mesh:
    """2-D mesh for combined data x feature sharding (voting/feature
    learners at scale)."""
    devs = np.array(jax.devices()[:data * feature]).reshape(data, feature)
    return Mesh(devs, (DATA_AXIS, FEATURE_AXIS))


def put(mesh: Mesh, arr, spec: P):
    """Place ``arr`` with the given spec. Under a MULTI-HOST mesh the
    array is assembled from per-process local chunks
    (``jax.make_array_from_process_local_data``): for row-sharded specs
    each process contributes its OWN row shard (the reference's
    rank-aware ``pre_partition`` load, dataset_loader.cpp) and every
    process must hold the SAME padded shard shape; for replicated specs
    every process must pass identical data. Feature-sharded layouts
    (feature-parallel) have no process-local semantics here — the
    engine rejects that learner multi-host."""
    sharding = NamedSharding(mesh, spec)
    if jax.process_count() > 1:
        return jax.make_array_from_process_local_data(
            sharding, np.asarray(arr))
    return jax.device_put(arr, sharding)


def shard_rows(mesh: Mesh, arr, extra_dims: int = 1):
    """Place an array with its leading (row) axis sharded over DATA_AXIS."""
    spec = P(DATA_AXIS, *([None] * (extra_dims - 1))) if extra_dims > 1 \
        else P(DATA_AXIS)
    return put(mesh, arr, spec)


def replicate(mesh: Mesh, arr):
    return put(mesh, arr, P())
