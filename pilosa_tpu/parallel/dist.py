"""Distributed executor: one SPMD program per query over the shard mesh.

Reference counterpart: executor.go's remote branch — one HTTP sub-query
per node carrying its shard list, partials reduced on the caller
(SURVEY.md §3.2 ⇄NET hops). Here the whole map+reduce is a single
``shard_map``-ped XLA program: each device evaluates the fused bitmap
kernel over its resident block of shards (vmapped over the block), and
``psum``/``pmax`` over the ``shards`` axis does the reduce on ICI. No
serialization, no scatter/gather, no per-node re-dispatch.

The dispatch path counts what each reduction moves from its static
shapes (ReduceStats, the ``dist_reduce_*`` series).

All mapping/result logic lives in the base Executor's batched path
(executor/batch.py) — this class only swaps the placement/program
hooks: shard blocks pad to the mesh, stacked leaves are device_put with
a NamedSharding over the shard axis, and the program builders (per-query
AND micro-batched — the mesh path keeps Executor.submit's pipelined
micro-batching) wrap the same per-shard bodies in shard_map with
collective reductions.
"""

from __future__ import annotations

import threading

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

from pilosa_tpu.executor import expr
from pilosa_tpu.executor.executor import Executor
from pilosa_tpu.executor import batch
from pilosa_tpu.parallel.mesh import (
    SHARDS_AXIS, ShardAssignment, make_mesh, replicated, shards_sharding,
    shards_spec,
)
from pilosa_tpu.storage import residency
from pilosa_tpu.utils.compile_cache import named_jit
from pilosa_tpu.utils.cost import current_cost

_DIST_JIT_CACHE: dict = {}


def _reduce_split(packed_local):
    """A per-device split-sum partial, summed over the mesh."""
    return lax.psum(packed_local, SHARDS_AXIS)


def _dist_body(structure, reduce_kind: str, leaf_ranks: tuple):
    """Uncompiled per-query SPMD evaluator body (runs inside shard_map):
    vmap over the local shard slots, then psum/pmax over the mesh.
    Shared by the per-query program (_dist_fn) and the micro-batched
    program (_dist_fn_batched), mirroring batch._local_body /
    batch.local_fn_batched."""
    n_leaves = len(leaf_ranks)
    count_sub = (batch.count_elementwise_sub(structure, leaf_ranks)
                 if reduce_kind == "count" else None)

    def body(*args):
        leaves = args[:n_leaves]
        scalars = args[n_leaves:]

        if count_sub is not None:
            # elementwise count: reduce the local block flat in wide
            # chunks (batch.count_flat), then reduce the packed channels
            return _reduce_split(batch.count_flat(count_sub, leaves, scalars))

        def per_shard(*ls):
            return expr._go(structure, ls, scalars)

        out = jax.vmap(per_shard)(*leaves)
        if reduce_kind == "count":
            return _reduce_split(batch.split_sum(out))
        if reduce_kind == "countrows":
            return _reduce_split(batch.split_sum(out, axis=0))
        if reduce_kind == "bsisum":
            plane_counts, n = out  # [S_loc, depth], [S_loc]
            return _reduce_split(
                jnp.concatenate(
                    [batch.split_sum(plane_counts, axis=0),
                     batch.split_sum(n)[:, None]], axis=1
                )
            )
        if reduce_kind in ("min", "max"):
            values, counts = out
            want_max = reduce_kind == "max"
            masked, valid = batch.minmax_mask(values, counts, want_max)
            if want_max:
                best = lax.pmax(jnp.max(masked), SHARDS_AXIS)
            else:
                best = lax.pmin(jnp.min(masked), SHARDS_AXIS)
            valid_g = lax.pmax(jnp.any(valid).astype(jnp.int32), SHARDS_AXIS)
            any_valid = valid_g > 0
            n = _reduce_split(
                batch.minmax_at_best(values, counts, valid, best)
            )
            return batch.minmax_finalize(best, n, any_valid)
        return out  # 'row': stays shard-sharded

    return body


def _dist_fn(mesh, structure, reduce_kind: str, leaf_ranks: tuple,
             n_scalars: int):
    """Build (or fetch) the compiled SPMD evaluator for a query shape.
    Packed results match batch.local_fn's contracts exactly."""
    key = (mesh, structure, reduce_kind, leaf_ranks, n_scalars)
    fn = _DIST_JIT_CACHE.get(key)
    if fn is not None:
        return fn

    spec = shards_spec(mesh)
    leaf_specs = tuple(spec for _ in leaf_ranks)
    scalar_specs = tuple(P() for _ in range(n_scalars))
    out_specs = spec if reduce_kind == "row" else P()

    fn = named_jit(
        f"dist_{reduce_kind}",
        shard_map(
            _dist_body(structure, reduce_kind, leaf_ranks),
            mesh=mesh,
            in_specs=leaf_specs + scalar_specs,
            out_specs=out_specs,
        )
    )
    _DIST_JIT_CACHE[key] = fn
    return fn


def _dist_fn_batched(mesh, structure, reduce_kind: str, leaf_ranks: tuple,
                     n_scalars: int, n_queries: int):
    """ONE SPMD program evaluating ``n_queries`` same-shape pipelined
    queries over the mesh (the mesh counterpart of
    batch.local_fn_batched): per query the shared per-shard body runs
    vmapped over the local slots and reduces over the mesh (_dist_body);
    results come back stacked [B, ...] and replicated. Only scalar
    reductions micro-batch (count/bsisum/min/max — Executor.submit never
    coalesces 'row'), so out_specs is always replicated. Args: B
    repetitions of the sharded leaves, then (when the shape has scalars)
    ONE replicated int32[B, n_scalars] array."""
    key = ("distB", mesh, structure, reduce_kind, leaf_ranks, n_scalars,
           n_queries)
    fn = _DIST_JIT_CACHE.get(key)
    if fn is not None:
        return fn

    n_leaves = len(leaf_ranks)
    body1 = _dist_body(structure, reduce_kind, leaf_ranks)
    in_specs = (
        tuple(shards_spec(mesh) for _ in range(n_leaves * n_queries))
        + ((P(),) if n_scalars else ())
    )

    fn = named_jit(
        f"dist_{reduce_kind}_b{n_queries}",
        shard_map(
            batch.batched_body(body1, n_leaves, n_scalars, n_queries),
            mesh=mesh,
            in_specs=in_specs,
            out_specs=P(),
        )
    )
    _DIST_JIT_CACHE[key] = fn
    return fn


def _dist_groupby_level_fn(mesh, filt_structure, n_filt: int, n_scalars: int,
                           n_gather: int, n_planes: int):
    """SPMD GroupBy level program (same per-shard body as the local
    builder, reduced over the mesh like every other split-sum lane)."""
    key = ("gbl", mesh, filt_structure, n_filt, n_scalars, n_gather, n_planes)
    fn = _DIST_JIT_CACHE.get(key)
    if fn is not None:
        return fn

    n_leaves = n_filt + n_gather + (1 if n_planes else 0)
    # the leaves, then ONE replicated int32 array
    # (batch.unpack_groupby_operand; every host argument of a mesh
    # program is a placement on every chip, so there is one)
    in_specs = tuple(shards_spec(mesh) for _ in range(n_leaves)) + (P(),)

    def body(*args):
        leaves = args[:n_leaves]
        idxs, scalars = batch.unpack_groupby_operand(
            args[n_leaves], n_gather, n_scalars)
        out = batch.groupby_level_body(
            leaves, idxs, scalars, filt_structure, n_filt, n_gather, n_planes
        )
        if not n_planes:
            return _reduce_split(out).ravel()
        return jnp.concatenate([_reduce_split(o).ravel() for o in out])

    # the kernel's partials vary over the mesh like its leaves, so the
    # program keeps its varying-axes check; Pallas' interpreter carries
    # the kernel's scratch through its grid loop without them, so the
    # check is off where the interpreter runs the body
    fn = named_jit(
        "dist_groupby_level",
        shard_map(body, mesh=mesh, in_specs=in_specs, out_specs=P(),
                  check_vma=not batch._pallas_interpret())
    )
    _DIST_JIT_CACHE[key] = fn
    return fn


def _dist_expand_fn(mesh, n_rows: int, n_pad: int):
    """The sparse miss's expansion on a mesh (residency.expand_rows_body
    under shard_map): the shares' packed lists arrive as one array split
    over the shard axis, every chip expands its own slot rows, and the
    leaf ``uint32[n_rows, 32768]`` comes back sharded as _leaf_put shards
    a dense one. No collective."""
    key = ("expand_rows", mesh, n_rows, n_pad)
    fn = _DIST_JIT_CACHE.get(key)
    if fn is None:
        spec, sharding = shards_spec(mesh), shards_sharding(mesh)
        rows = n_rows // mesh.size

        def body(packed):
            return residency.expand_rows_body(packed, rows, n_pad)

        fn = _DIST_JIT_CACHE[key] = named_jit(
            "dist_expand_rows",
            shard_map(body, mesh=mesh, in_specs=spec, out_specs=spec,
                      check_vma=False),
            in_shardings=sharding, out_shardings=sharding)
    return fn


class _MeshLeafPut:
    """DistExecutor._leaf_put in one process: a dense host leaf is split
    over the mesh's chips by its slot rows; a sparse row leaf
    (kernels.SparseRows in ``sparse`` = mesh.size shares) is placed as
    its packed lists, a share a chip, and expanded there."""

    def __init__(self, mesh):
        self.mesh = mesh
        self.sparse = mesh.size
        self.sharding = shards_sharding(mesh)

    def __call__(self, host):
        return jax.device_put(host, self.sharding)

    def expand(self, packed, n_rows: int, n_pad: int):
        # the program call places its one host argument itself, a share
        # on each chip
        return _dist_expand_fn(self.mesh, n_rows, n_pad)(packed)


class DistExecutor(Executor):
    """Executor whose shard map phase runs as one SPMD program on a mesh.

    Single-process: the mesh spans all local devices and behaves like the
    base executor with on-device reduction, whose bytes each dispatch
    records (global_reduce_stats, the cost plane's reduceBytes, and the
    dist_reduce_* series).

    Multi-host (exercised for real by tests/test_multihost.py, two
    jax.distributed processes on the CPU backend): the same mesh spans
    hosts, and the contract is SPMD — every process drives the same query
    sequence. Each process decodes and uploads ONLY the shard slots its
    devices own (ShardAssignment.local_slots narrows block.stack, and
    _leaf_put assembles the global array with
    jax.make_array_from_process_local_data), reductions cross hosts via
    psum inside the compiled program, and reduced results come back
    replicated. Writes scatter-patch resident sharded leaves per
    addressable PIECE (batch._patch_sharded): the single-device buffer
    holding the written shard's slot is rewritten locally — a
    single-device program, no collective — and the global handle
    reassembled from the per-device buffers, so multi-host writes don't
    pay a purge + full re-decode of the process's slots.
    Row-materializing results stay shard-sharded and are only
    read back single-process; in a deployed cluster they travel per-node
    through the HTTP layer (parallel/cluster_exec.py), as the reference's
    do."""

    def __init__(self, holder, mesh=None):
        super().__init__(holder)
        self.mesh = mesh if mesh is not None else make_mesh()
        # micro-batch argument budgeting counts per-DEVICE bytes: leaves
        # are sharded over the mesh, so each chip holds 1/size of them
        self.arg_shard_factor = self.mesh.size

    def _make_block(self, shard_list):
        return ShardAssignment(shard_list, self.mesh)

    def _leaf_put(self, block):
        if jax.process_count() == 1:
            return _MeshLeafPut(self.mesh)
        sharding = shards_sharding(self.mesh)
        # Multi-host: ``host`` holds only this process's slot rows
        # (ShardAssignment narrows block.local_slots, so block.stack
        # decoded just the addressable slice); assemble the global array
        # from per-process local data — no host ever materializes or
        # ships the full shard axis
        padded = block.padded

        def put(host):
            return jax.make_array_from_process_local_data(
                sharding, host, (padded,) + host.shape[1:]
            )

        return put

    _operand_stage = "device.replicate"

    def _operand_place(self, packed):
        # one placement on every chip of the mesh at once; made on the
        # default device it would be a host transfer and then a copy to
        # every other chip at the program call
        return jax.device_put(packed, replicated(self.mesh))

    def _program(self, structure, reduce_kind, leaf_ranks, n_scalars):
        return _dist_fn(self.mesh, structure, reduce_kind, leaf_ranks,
                        n_scalars)

    def _program_batched(self, structure, reduce_kind, leaf_ranks, n_scalars,
                         n_queries):
        return _dist_fn_batched(self.mesh, structure, reduce_kind, leaf_ranks,
                                n_scalars, n_queries)

    def _groupby_level_program(self, filt_structure, n_filt, n_scalars,
                               n_gather, n_planes):
        return _dist_groupby_level_fn(
            self.mesh, filt_structure, n_filt, n_scalars, n_gather, n_planes,
        )

    def _note_reduce(self, reduce_kind: str, out_shape: tuple) -> None:
        """Per-dispatch reduction bytes, from static shapes only (host
        side, nothing blocks on the device): a ring all-reduce of the
        packed int32 lanes over the whole mesh."""
        if reduce_kind == "row":
            return  # stays shard-sharded: nothing is reduced
        elems = 1
        for d in out_shape:
            elems *= int(d)
        dense = dense_reduce_bytes(self.mesh.size, elems)
        _STATS.note_reduce(dense, dense)
        cost = current_cost()
        if cost is not None:
            cost.note_reduce(dense, dense)


def dense_reduce_bytes(n_devices: int, out_elems: int) -> int:
    """Bytes on the wire of a ring all-reduce of ``out_elems`` int32
    lanes over ``n_devices``."""
    return 2 * (n_devices - 1) * out_elems * 4


class ReduceStats:
    """Process-wide dist_reduce_* counters (served on /metrics and
    /debug/vars). Lock kept tiny: a handful of integer adds per device
    dispatch, invisible next to the dispatch itself. ``actual_bytes`` is
    what the reduction moved and ``dense_bytes`` what a dense ring
    all-reduce moves: the one lane there is moves exactly that, and the
    benchmark's reduce_bytes_per_dispatch reads the former."""

    def __init__(self):
        self._lock = threading.Lock()
        self.reset()

    def reset(self) -> None:
        with getattr(self, "_lock", threading.Lock()):
            self.dispatches = 0
            self.dense_bytes = 0
            self.actual_bytes = 0

    def note_reduce(self, dense: int, actual: int) -> None:
        with self._lock:
            self.dispatches += 1
            self.dense_bytes += dense
            self.actual_bytes += actual

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "dispatches": self.dispatches,
                "dense_bytes": self.dense_bytes,
                "actual_bytes": self.actual_bytes,
            }


_STATS = ReduceStats()


def global_reduce_stats() -> ReduceStats:
    return _STATS
