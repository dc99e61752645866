"""Device-mesh helpers: the ICI fabric the shuffle layer rides on.

The reference moves shuffle data between executors over Arrow Flight
(gRPC/HTTP2) point-to-point streams (reference
ballista/core/src/client.rs:112-187, shuffle_reader.rs:267-318).  On a TPU
pod the equivalent transport is the ICI mesh: co-located "executors" are
devices in one `jax.sharding.Mesh`, and a stage's hash repartition becomes a
single `all_to_all` collective over HBM-resident buffers instead of M×N
file fetches.  Cross-host (DCN) falls back to the gRPC data plane.

Axis naming convention:
- ``"part"`` — partition parallelism (the reference's one axis of
  parallelism: one task per partition, SURVEY.md §2.5).  DP analog.
- future axes (e.g. ``"op"`` for intra-operator sharding of one giant join)
  compose with ``part`` in the same Mesh.
"""
from __future__ import annotations

import threading
from typing import Optional, Sequence

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..obs.tracing import TracedLock

PART_AXIS = "part"

# process-global serialization of COLLECTIVE program dispatch: two mesh
# programs interleaved from different task threads deadlock XLA's CPU
# collective rendezvous ("Expected 8 threads to join ... only 6 arrived"
# -> hard abort / hang; observed again as a 180s job timeout when two
# warm-cache hybrid-join tasks dispatched concurrently).  Collectives
# already use every local device, so serializing them costs nothing.
MESH_DISPATCH_LOCK = TracedLock("mesh_dispatch")


def make_mesh(n_devices: Optional[int] = None, axis: str = PART_AXIS,
              devices: Optional[Sequence] = None) -> Mesh:
    """A 1-D mesh over the first ``n_devices`` local devices.

    Multi-dim meshes (e.g. (hosts, chips)) are built by callers that know
    their slice topology; everything in this module only needs axis names.
    """
    if devices is None:
        devices = jax.devices()
    if n_devices is not None:
        if len(devices) < n_devices:
            raise ValueError(
                f"requested {n_devices} devices, only {len(devices)} available")
        devices = devices[:n_devices]
    return Mesh(np.asarray(devices), (axis,))


def row_sharding(mesh: Mesh, axis: str = PART_AXIS) -> NamedSharding:
    """Shard rows (axis 0) of every column across the mesh."""
    return NamedSharding(mesh, P(axis))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def mesh_axis_size(mesh: Mesh, axis: str = PART_AXIS) -> int:
    return mesh.shape[axis]
