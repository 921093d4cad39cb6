"""Device mesh + shard→device assignment.

The mesh axis ``"shards"`` is the TPU analog of the reference's hash
partitioning (cluster.go: partition = hash(index, shard) % 256 → nodes —
SURVEY.md §2 #13): a query's shard list is laid out as the leading axis of
a global array sharded over the mesh, so each chip's HBM holds its slice
of shards and XLA collectives do the reduce that the reference did over
HTTP.

Multi-host: ``initialize_distributed`` wires jax.distributed so the same
mesh spans hosts over DCN; the shard axis simply gets longer. Nothing in
the executor changes — that is the point of expressing the cluster as a
mesh instead of porting the reference's gossip/RPC.
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from pilosa_tpu.executor.batch import ShardBlock
from pilosa_tpu.shardwidth import next_pow2

SHARDS_AXIS = "shards"


def make_mesh(n_devices: int | None = None, devices=None) -> Mesh:
    """Mesh over the shard axis. It is 1-D: bitmap ops have no second
    model axis to map, so the topology is just the flattened device
    list."""
    if devices is None:
        devices = jax.devices()
        if n_devices is not None:
            devices = devices[:n_devices]
    return Mesh(np.asarray(devices), (SHARDS_AXIS,))


def shards_spec(mesh: Mesh) -> P:
    """PartitionSpec splitting a leading shard-slot axis over every mesh
    device."""
    return P(SHARDS_AXIS)


def shards_sharding(mesh: Mesh) -> NamedSharding:
    """Sharding for [n_shards_padded, ...] arrays: leading axis split over
    the mesh."""
    return NamedSharding(mesh, shards_spec(mesh))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def initialize_distributed(coordinator: str | None = None,
                           num_processes: int | None = None,
                           process_id: int | None = None) -> None:
    """Multi-host bring-up over DCN (replaces the reference's
    memberlist/gossip data-plane role; schema gossip stays HTTP —
    parallel.cluster)."""
    if coordinator is None:
        return  # single-host
    jax.distributed.initialize(
        coordinator_address=coordinator,
        num_processes=num_processes,
        process_id=process_id,
    )


class ShardAssignment(ShardBlock):
    """Maps a query's shard list onto mesh slots.

    Extends the local ShardBlock layout (executor/batch.py): rows ordered
    by the sorted shard list, padded to a multiple of the mesh size with
    empty slots; slot s lives on device s // (S_padded / n_devices).
    Replication (the reference's replicaN) is a host-side property of
    fragment *files* (parallel.cluster); device residency is single-copy
    since HBM is a cache, not the durable store.
    """

    def __init__(self, shards: list[int], mesh: Mesh):
        super().__init__(shards)
        self.n_devices = mesh.size
        n = max(len(self.shards), 1)
        # bucketed per-device slot count (see ShardBlock): compile count
        # stays O(log shards) as the index grows
        self.padded = self.n_devices * next_pow2(-(-n // self.n_devices))
        self.mesh = mesh
        self.local_slots = (0, self.padded)
        # Multi-host: this process feeds only the slot rows that live on
        # its addressable devices (jax.make_array_from_process_local_data
        # in DistExecutor._leaf_put assembles the global array). Writes
        # patch resident leaves per-PIECE: the addressable single-device
        # buffer holding the shard's slot is rewritten locally and the
        # global handle reassembled, no collective involved
        # (batch._patch_sharded; batch._make_probe states the
        # owner-applies-the-write correctness contract).
        if jax.process_count() > 1:
            per_dev = self.padded // self.n_devices
            flat = mesh.devices.ravel()
            mine = [i for i, d in enumerate(flat)
                    if d.process_index == jax.process_index()]
            if not mine:
                raise ValueError(
                    f"mesh contains no devices of process "
                    f"{jax.process_index()}; every process driving a "
                    f"multi-host DistExecutor must own mesh devices "
                    f"(don't slice jax.devices() down to one host)"
                )
            lo, hi = mine[0], mine[-1] + 1
            if mine != list(range(lo, hi)):
                raise ValueError(
                    "mesh devices of one process must be contiguous for "
                    "per-host shard feeding"
                )
            self.local_slots = (lo * per_dev, hi * per_dev)
            self.patchable = False

    @property
    def slot_of(self) -> dict[int, int]:
        return {s: i for i, s in enumerate(self.shards)}
