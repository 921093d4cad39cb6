"""Distributed execution: device mesh, sharded evaluation, cluster topology.

This package is the TPU-native replacement for the reference's cluster
data plane (cluster.go shard→node assignment + http/client.go remote
mapReduce + gossip — SURVEY.md §2 #13–17, §2.3–2.4):

- within a slice, shards are assigned to mesh positions and queries run as
  ONE compiled SPMD program via ``shard_map`` with ``psum``/all-gather
  reduces over ICI (pilosa_tpu.parallel.dist) — this replaces the
  reference's per-node HTTP scatter/gather;
- across slices/hosts, the same mesh extends over DCN via
  ``jax.distributed`` (pilosa_tpu.parallel.mesh.initialize_distributed);
- the host control plane (membership, replica placement, anti-entropy,
  resize) lives in pilosa_tpu.parallel.cluster.
"""

from pilosa_tpu.parallel.mesh import (
    SHARDS_AXIS,
    ShardAssignment,
    make_mesh,
)
from pilosa_tpu.parallel.dist import DistExecutor
