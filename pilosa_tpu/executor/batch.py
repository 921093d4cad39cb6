"""Batched shard evaluation: one compiled program, one device sync per query.

The reference executor maps shards with a goroutine pool and reduces
partials on the host (executor.go mapReduce — SURVEY.md §3.2). A literal
translation — one device dispatch + one host readback per shard — is
hostile to TPU serving: a blocking device→host sync costs a full
host↔device round trip, so per-shard syncs put the query floor at
O(shards × RTT). Here the whole map+reduce phase is ONE XLA program over
stacked leaves ``uint32[n_shards, ...]`` (vmapped per shard, reduced on
device) and exactly ONE packed result array crosses back to the host.

Leaves are built once per (query leaf, shard set) and cached in device
HBM via the residency LRU (storage.residency), so steady-state queries
touch the host only for the final packed result. Writes are routed to
resident leaves as in-place device scatter patches (see the
cached-stacked-leaves section below) rather than evicting them.

``ShardBlock`` is the local (single-device) layout; parallel.mesh's
``ShardAssignment`` extends it with mesh padding, and parallel.dist swaps
the program builder for shard_map+psum versions of the same reductions.

Reduce kinds and their packed results (all int32 unless noted):
  'count'     → [2]: split-sum scalar (see below)
  'countrows' → [2, n_rows] split sums
  'bsisum'    → [2, depth + 1]: per-plane popcount split sums ++ [n]
  'min'/'max' → [3]: [offset-encoded extremum, count_lo, count_hi]
                (count==0 → empty)
  'row'       → uint32[n_shards_padded, words] (stays dense; the only
                multi-row readback)

Split sums: device accumulators are int32 (no x64), and a per-shard
popcount can reach 2^20, so a plain int32 sum wraps past ~2^11 full
shards. Every cross-shard sum is therefore carried in two int32 channels
— lo 15 bits and hi bits of each per-shard value summed separately —
and recombined on the host as ``hi·2^15 + lo``, exact to 2^15 shards
(32 billion columns) per query.
"""

from __future__ import annotations

import itertools
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from pilosa_tpu.executor import expr
from pilosa_tpu.roaring import kernels
from pilosa_tpu.shardwidth import WORDS_PER_SHARD, next_pow2
from pilosa_tpu.storage import residency
from pilosa_tpu.utils.compile_cache import named_jit, pallas_interpret
from pilosa_tpu.utils.cost import current_cost

INT32_MIN = -(1 << 31)
INT32_MAX = (1 << 31) - 1

# Block-layout key interning table (see ShardBlock.key). Tokens are
# monotonic — never reused even across overflow resets, so a stale
# resident entry can never alias a new layout's key.
_KEY_INTERN: dict[tuple, tuple] = {}
_KEY_INTERN_SEQ = itertools.count()

# Split-sum carry point: per-shard summands are ≤ 2^20, so the lo channel
# (15 bits) sums safely over 2^16 shards and the hi channel (≤ 2^5 per
# shard) even further.
SPLIT_SHIFT = 15
SPLIT_MASK = (1 << SPLIT_SHIFT) - 1


def split_sum(x, axis=None):
    """Sum int32 per-shard values in two overflow-safe int32 channels.
    Returns stacked [2, ...]: (lo-bit sums, hi-bit sums)."""
    lo = jnp.sum(x & SPLIT_MASK, axis=axis)
    hi = jnp.sum(x >> SPLIT_SHIFT, axis=axis)
    return jnp.stack([lo, hi])


def merge_split(packed: np.ndarray) -> np.ndarray:
    """Host-side recombination of split sums [2, ...] → int64 [...]."""
    packed = np.asarray(packed, np.int64)
    return (packed[1] << SPLIT_SHIFT) + packed[0]


class ShardBlock:
    """Orders a query's shard list as the leading axis of stacked leaves.

    The padded slot count buckets to the next power of two so a growing
    index recompiles each query shape O(log shards) times instead of on
    every new shard (XLA compiles are tens of seconds on TPU; the cost is
    ≤2x zero slots on stacked leaves, which reduce to nothing). The mesh
    form (parallel.mesh.ShardAssignment) additionally pads to a multiple
    of the device count so the leading axis shards evenly.
    """

    def __init__(self, shards: list[int]):
        self.shards = sorted(shards)
        self.padded = next_pow2(max(len(self.shards), 1))
        self.n_devices = 1
        self._key = None
        # single-process defaults; the multi-host ShardAssignment
        # (parallel/mesh.py) narrows local_slots to this process's rows
        # and clears patchable (write events then patch the addressable
        # single-device PIECE holding the slot — _patch_sharded — instead
        # of scattering into the whole array)
        self.local_slots = (0, self.padded)
        self.patchable = True

    def key(self) -> tuple:
        # Interned: leaf-cache keys embed the block key, and hashing a
        # 1k-shard tuple on every residency lookup is measurable on the
        # serving path. Equal layouts (shards, padding, device count,
        # local slot span) share one small token, so equal blocks built
        # at different times still hit the same cache entries; the full
        # tuple is hashed once per distinct layout.
        if self._key is None:
            full = (tuple(self.shards), self.padded, self.n_devices,
                    self.local_slots)
            if len(_KEY_INTERN) >= 4096:
                # runaway distinct layouts (pathological Options(
                # shards=) traffic): reset — orphaned residency entries
                # simply age out of the LRU; tokens stay monotonic so
                # none can alias
                _KEY_INTERN.clear()
            # setdefault: atomic under the GIL, so two threads racing the
            # same new layout agree on ONE token (a loser's token would
            # split the residency cache for that layout forever)
            self._key = _KEY_INTERN.setdefault(
                full, ("blk", next(_KEY_INTERN_SEQ))
            )
        return self._key

    @property
    def host_rows(self) -> int:
        """Rows this process materializes on host: padded single-process,
        the local slot span under multi-host feeding."""
        lo, hi = self.local_slots
        return hi - lo

    def stack(self, per_shard_fn, inner: tuple | None = None) -> np.ndarray:
        """Build the [host_rows, ...] host array for this process's slots
        (all of [0, padded) single-process): per_shard_fn(shard) → row
        block; empty slots are zeros. ``inner`` is the per-shard row
        shape; when omitted it is probed by decoding one shard (an
        all-padding process then pays a wasted decode — callers with a
        statically known shape should pass it)."""
        lo, hi = self.local_slots
        local = self.shards[lo:min(hi, len(self.shards))]
        first = per_shard_fn(local[0]) if local else None
        if first is not None:
            inner = first.shape
        elif inner is None:
            # all-padding process: still must feed correctly-shaped zeros
            inner = per_shard_fn(self.shards[0]).shape if self.shards else ()
        out = _staging_array((hi - lo,) + tuple(inner))
        out[len(local):] = 0
        for i, s in enumerate(local):
            out[i] = first if i == 0 else per_shard_fn(s)
        return out


# Host staging arrays of ShardBlock.stack and host_leaf, recycled. A
# residency miss decodes into one, hands it to device_put and drops it; whether glibc
# then keeps the freed 16 MiB of a row leaf or returns them to the
# kernel depends on what else its heap holds, so one and the same decode
# cost a server 14 ms a row in one process and 22 in the next (PR 34, on
# the chip's host: 4,096 page faults a row). An array goes out again only
# while nothing but the pool refers to it: JAX keeps a reference for as
# long as a transfer, or on the CPU a zero-copy alias, needs the memory.
# Stacks too large to pool (a GroupBy dimension's matrix) are built once
# and stay resident.
STAGING_POOL_BYTES = 128 << 20
_staging: dict[tuple, list] = {}
_staging_bytes = 0
_staging_lock = threading.Lock()


def _staging_array(shape: tuple) -> np.ndarray:
    """An uninitialised uint32 host array of ``shape``: a pooled one that
    nobody refers to any more, else a new one (pooled while the pool has
    room)."""
    global _staging_bytes
    with _staging_lock:
        pool = _staging.setdefault(shape, [])
        for buf in pool:
            if sys.getrefcount(buf) == 3:  # the pool, ``buf``, the call
                return buf
        buf = np.empty(shape, np.uint32)
        if _staging_bytes + buf.nbytes <= STAGING_POOL_BYTES:
            pool.append(buf)
            _staging_bytes += buf.nbytes
        return buf


# ------------------------------------------------------- host decode helpers


def host_row(idx, spec, shard: int) -> np.ndarray:
    """Dense uint32[words] for a _RowSpec leaf on one shard (host side)."""
    field = idx.field(spec.field)
    acc = None
    for vname in spec.views:
        view = field.view(vname) if field else None
        frag = view.fragment(shard) if view else None
        if frag is None:
            continue
        words = frag.row_words(spec.row)
        acc = words if acc is None else np.bitwise_or(acc, words)
    return acc if acc is not None else np.zeros(WORDS_PER_SHARD, np.uint32)


def host_leaf(idx, spec, block: ShardBlock, sparse: int = 0):
    """Dense uint32[host_rows, words] for a _RowSpec leaf (host side):
    ``block.stack`` of ``host_row`` byte for byte, decoded in one pass
    over the row's containers of every local shard and view
    (kernels.flatten_rows, kernels.dense_rows32) straight into a
    recycled staging array. A missing field, view or fragment, and a
    slot past the shards, read zeros. With ``sparse`` (the shares whoever
    places the leaf expands it in: 1 where the row cache places it
    itself, a share a chip where a one-process mesh does) a leaf whose
    containers are all sparse arrays comes back as its set bits instead,
    a kernels.SparseRows in a staging array of its bucket's shape, for
    the chips to expand: the same leaf, never laid out on the host."""
    lo, hi = block.local_slots
    local = block.shards[lo:min(hi, len(block.shards))]
    field = idx.field(spec.field)
    bitmaps = []
    for vname in spec.views:
        view = field.view(vname) if field else None
        if view is None:
            continue
        for slot, shard in enumerate(local):
            frag = view.fragment(shard)
            if frag is not None:
                bitmaps.append((slot, frag.bitmap))
    flat = kernels.flatten_rows(bitmaps, spec.row)
    cost = current_cost()
    if cost is not None:
        # one tally a leaf, the totals Fragment.row_words notes a shard
        cost.note_containers(*flat.kind_counts())
    if sparse:
        rows = kernels.sparse_rows32(flat, hi - lo, _staging_array, sparse)
        if rows is not None:
            return rows
    out = _staging_array((hi - lo, WORDS_PER_SHARD))
    kernels.dense_rows32(flat, out)
    return out


def host_planes(idx, spec, shard: int, depth: int) -> np.ndarray:
    """uint32[depth, words] BSI plane matrix for one shard (host side).
    A delete_field racing the decode reads zeros, not a dead object."""
    field = idx.field(spec.field)
    view = field.view(field.bsi_view_name()) if field is not None else None
    frag = view.fragment(shard) if view else None
    if frag is None:
        return np.zeros((depth, WORDS_PER_SHARD), np.uint32)
    return np.stack([frag.row_words(r) for r in range(depth)])


# ------------------------------------------------------ cached stacked leaves
#
# Leaves are keyed WITHOUT a write generation: a fragment mutation is
# routed (residency.apply_write) to exactly the dependent leaves, which
# are patched on device — a scatter of the affected shard slot — instead
# of being evicted. SURVEY.md §7.3 hard part #3: writes no longer force
# the next query to re-decode and re-upload its whole working set.


def _delta_patch(combine):
    """Body of the four delta programs: combine sparse word masks into
    one row of a leaf — row (slot,) of a [S, W] leaf, (slot, row) of a
    [S, R, W] matrix. ``args`` is _delta_args' one array: the row index,
    then n word indices, then their n masks. Padding repeats (word 0,
    mask 0), which .at[].max resolves correctly against any real mask
    for word 0."""

    def patch(arr, args):
        n_at = arr.ndim - 1
        n = (args.shape[0] - n_at) // 2
        at = tuple(args[i].astype(jnp.int32) for i in range(n_at))
        word_idx = args[n_at:n_at + n].astype(jnp.int32)
        delta = jnp.zeros((arr.shape[-1],), jnp.uint32).at[word_idx].max(
            args[n_at + n:])
        return arr.at[at].set(combine(arr[at], delta))

    return patch


def _andnot(words, delta):
    return words & ~delta


_or_delta = named_jit("or_delta", _delta_patch(jnp.bitwise_or))
_andnot_delta = named_jit("andnot_delta", _delta_patch(_andnot))
_or_delta_row = named_jit("or_delta_row", _delta_patch(jnp.bitwise_or))
_andnot_delta_row = named_jit("andnot_delta_row", _delta_patch(_andnot))


def _delta_args(at: tuple, positions) -> np.ndarray:
    """One patch's arguments as ONE host array, uint32[len(at) + 2n]:
    the row index ``at``, then the unique word indices of the in-shard
    ``positions`` and their OR-combined masks, each padded to the next
    power of two so delta scatters compile O(log n) distinct shapes.
    One array because every host argument of a program call is a
    transfer of its own, and the calling thread gives up the interpreter
    for each: measured on the chip beside one busy thread, a call with
    a python int and two arrays waits four times for it, a call with
    one array twice (PERF.md, PR 26)."""
    positions = np.asarray(positions, np.uint32)
    words = positions >> 5
    bits = np.uint32(1) << (positions & np.uint32(31))
    uw = np.unique(words)
    n = next_pow2(max(uw.size, 1))
    out = np.zeros(len(at) + 2 * n, np.uint32)
    out[: len(at)] = at
    out[len(at): len(at) + uw.size] = uw
    np.bitwise_or.at(out[len(at) + n:], np.searchsorted(uw, words), bits)
    return out


def _patch_sharded(arr, slot: int, make_patch):
    """Patch one global row of a multi-process sharded array WITHOUT a
    collective: rewrite only the addressable single-device piece holding
    ``slot`` (a single-device program on that piece's device) and
    reassemble the global handle from the per-device buffers — every
    other piece's buffer is reused as-is. Each process's handle only
    contributes its own addressable data to SPMD execution, so a
    process-local reassembly is all a local write needs (SURVEY.md §7.3
    hard part #3, multi-host case)."""
    pieces = list(arr.addressable_shards)
    datas = [p.data for p in pieces]
    for i, p in enumerate(pieces):
        sl = p.index[0]
        start = sl.start or 0
        stop = sl.stop if sl.stop is not None else arr.shape[0]
        if start <= slot < stop:
            datas[i] = make_patch(datas[i], slot - start)
            return jax.make_array_from_single_device_arrays(
                arr.shape, arr.sharding, datas
            )
    return arr  # slot not addressable here: nothing local to patch


def _make_probe(block: ShardBlock, match, row_pos_of, decode_row,
                delta_on_clear: bool):
    """Shared write-routing probe for every stacked-leaf kind.

    match(ev) → is this event for our leaf's (view, row) surface?
    row_pos_of(ev) → inner row axis position, or None for [S, W] leaves.
    decode_row(ev) → fresh host words for the affected (shard, row), the
    fallback when the exact delta can't be applied.
    delta_on_clear → clears may delta-patch (single-view leaves only: with
    multiple OR'd views a cleared bit may survive via another view).

    Non-patchable blocks (multi-host ShardAssignment): a whole-array
    scatter on a multi-process global array would be a collective every
    process must join, but a write event fires only on the process whose
    holder received the write — so the patch is applied per-piece
    (_patch_sharded): the addressable single-device buffer holding the
    shard's slot is rewritten locally and the global handle reassembled,
    with no host round trip and no purge-refeed of unrelated slots.
    Correctness contract: a shard's writes must be applied on (at least)
    the process owning that shard's slot — the cluster layer routes
    writes to fragment owners, which the slot layout mirrors; a process
    observing a foreign shard's write has nothing local to patch (its
    pieces don't contain that slot) and leaves its handle untouched.

    The probe itself runs under the row cache's lock and only looks
    things up; the closure it returns does the numpy (word masks, row
    decode) and the device work when the cache applies it, with the lock
    free (residency._patch_routed), possibly more than once.
    """
    slot_of = {s: i for i, s in enumerate(block.shards)}
    per_piece = not block.patchable
    slot_lo, slot_hi = block.local_slots

    def probe(ev):
        slot = slot_of.get(ev.shard)
        if slot is None or not match(ev):
            return None
        if per_piece and not (slot_lo <= slot < slot_hi):
            # foreign shard's write observed on this process: none of our
            # addressable pieces contain that slot — nothing local to do
            return None
        row_pos = row_pos_of(ev) if row_pos_of is not None else None
        if ev.positions is not None and (
                ev.added or (ev.added is False and delta_on_clear)):
            if row_pos is None:
                fn = _or_delta if ev.added else _andnot_delta
                at = ()
            else:
                fn = _or_delta_row if ev.added else _andnot_delta_row
                at = (row_pos,)
            args = {}  # built when first applied, kept for a retry

            def patch(arr_or_piece, r):
                if r not in args:
                    args[r] = _delta_args((r, *at), ev.positions)
                return fn(arr_or_piece, args[r])
        else:
            def patch(arr_or_piece, r):
                new = jnp.asarray(decode_row(ev))
                if row_pos is None:
                    return arr_or_piece.at[r].set(new)
                return arr_or_piece.at[r, row_pos].set(new)

        if per_piece:
            return lambda arr: _patch_sharded(arr, slot, patch)
        return lambda arr: patch(arr, slot)

    return probe


def leaf_key(idx, spec, block: ShardBlock) -> tuple:
    """Residency key for a compiled spec's stacked leaf (must stay in
    lockstep with stacked_leaf below — the executor's operand memo uses
    these keys to re-touch LRU positions on memo hits)."""
    from pilosa_tpu.executor.executor import (
        PQLError,
        _PlanesSpec,
        _RowSpec,
        _ZeroSpec,
    )

    # idx.scope (the holder-unique data-dir path) leads every key: two
    # Holders in one process (in-process clusters, embedded multi-server)
    # hold DIFFERENT replicas' data under identical index/field names, and
    # a shared-cache hit across them served one node a stale copy of
    # another's row (membership-churn property sweep). The zero leaf
    # stays unscoped: all-zero content is identical everywhere.
    if isinstance(spec, _RowSpec):
        return ("stack", idx.scope, idx.name, spec.field, spec.views,
                spec.row, block.key())
    if isinstance(spec, _PlanesSpec):
        return ("stackp", idx.scope, idx.name, spec.field, 2 + spec.depth,
                spec.pad_rows, block.key())
    if isinstance(spec, _ZeroSpec):
        return ("stackz", block.key())
    raise PQLError(f"unknown leaf spec {type(spec).__name__}")


def leaf_keys(idx, specs, block: ShardBlock) -> tuple:
    """Residency keys for a plan's leaves (operand-memo LRU re-touch)."""
    return tuple(leaf_key(idx, s, block) for s in specs)


def stacked_leaf(idx, spec, block: ShardBlock, device_put=None):
    """Device-resident stacked leaf for a compiled spec, via the residency
    LRU. ``device_put`` overrides placement (mesh sharding); one that
    can expand a sparse row leaf says in how many shares (``sparse``)."""
    from pilosa_tpu.executor.executor import (
        PQLError,
        _PlanesSpec,
        _RowSpec,
        _ZeroSpec,
    )

    cache = residency.global_row_cache()
    if isinstance(spec, _RowSpec):
        key = leaf_key(idx, spec, block)

        def decode():
            return host_leaf(idx, spec, block, sparse=1 if device_put is None
                             else getattr(device_put, "sparse", 0))

        def probe():  # factory: only built when the key isn't registered
            views = frozenset(spec.views)
            return _make_probe(
                block,
                match=lambda ev: ev.row == spec.row and ev.view in views,
                row_pos_of=None,
                decode_row=lambda ev: host_row(idx, spec, ev.shard),
                delta_on_clear=len(spec.views) == 1,
            )
    elif isinstance(spec, _PlanesSpec):
        from pilosa_tpu.storage.view import view_name_bsi

        # compile-time depth + name-derived view: a delete_field racing
        # the query resolves to zeros instead of a dead dereference
        depth = 2 + spec.depth
        bsi_view = view_name_bsi(spec.field)
        key = leaf_key(idx, spec, block)
        pad = ((0, spec.pad_rows), (0, 0))   # zero rows after the planes

        def decode():
            return block.stack(
                lambda shard: np.pad(host_planes(idx, spec, shard, depth),
                                     pad),
                inner=(depth + spec.pad_rows, WORDS_PER_SHARD),
            )

        def decode_row(ev):
            field = idx.field(spec.field)  # live schema: None post-delete
            view = field.view(bsi_view) if field is not None else None
            frag = view.fragment(ev.shard) if view else None
            if frag is None:
                return np.zeros(WORDS_PER_SHARD, np.uint32)
            return frag.row_words(ev.row)

        def probe():
            return _make_probe(
                block,
                match=lambda ev: ev.view == bsi_view and ev.row < depth,
                row_pos_of=lambda ev: ev.row,
                decode_row=decode_row,
                delta_on_clear=True,
            )
    elif isinstance(spec, _ZeroSpec):
        key = leaf_key(idx, spec, block)

        def decode():
            return np.zeros((block.host_rows, WORDS_PER_SHARD), np.uint32)

        return cache.get_row(key, decode, device_put=device_put)
    else:
        raise PQLError(f"unknown leaf spec {type(spec).__name__}")

    return cache.get_or_build(key, (idx.scope, idx.name, spec.field),
                               probe, decode,
                              device_put=device_put)


def stacked_matrix(idx, field_name: str, view, row_ids, block: ShardBlock,
                   device_put=None, pad_rows: int = 0):
    """Stacked row matrix ``uint32[padded, len(row_ids) + pad_rows,
    words]`` of one view (TopN phase-2 candidates, GroupBy dimensions),
    HBM-cached. ``pad_rows`` appends all-zero rows (shape bucketing for
    pipelined TopN) — zeros, NOT duplicates of a real row: a duplicate
    would break the write-patch routing, which maps each row id to ONE
    inner position."""
    cache = residency.global_row_cache()
    view_name = view.name if view is not None else None
    n_rows = len(row_ids) + pad_rows
    key = ("stackm", idx.scope, idx.name, field_name, view_name,
           tuple(row_ids), pad_rows, block.key())

    def live_view():
        # resolve by NAME at decode time, never through the captured
        # object: a delete_field racing the build must read the live
        # schema (None / the recreated field), not a dead view's bitmap
        field = idx.field(field_name)
        return field.view(view_name) if field and view_name else None

    def decode():
        v = live_view()

        def per_shard(shard):
            frag = v.fragment(shard) if v else None
            if frag is None:
                return np.zeros((n_rows, WORDS_PER_SHARD), np.uint32)
            rows = [frag.row_words(r) for r in row_ids]
            rows.extend(
                np.zeros(WORDS_PER_SHARD, np.uint32)
                for _ in range(pad_rows)
            )
            return np.stack(rows)

        return block.stack(per_shard, inner=(n_rows, WORDS_PER_SHARD))

    def decode_row(ev):
        v = live_view()
        frag = v.fragment(ev.shard) if v else None
        if frag is None:
            return np.zeros(WORDS_PER_SHARD, np.uint32)
        return frag.row_words(ev.row)

    def probe():
        row_pos_of = {r: i for i, r in enumerate(row_ids)}
        return _make_probe(
            block,
            match=lambda ev: ev.view == view_name and ev.row in row_pos_of,
            row_pos_of=lambda ev: row_pos_of[ev.row],
            decode_row=decode_row,
            delta_on_clear=True,
        )

    return cache.get_or_build(key, (idx.scope, idx.name, field_name),
                               probe, decode,
                              device_put=device_put)


# ------------------------------------------------------ local program builder

_LOCAL_JIT_CACHE: dict = {}


def minmax_mask(values, counts, want_max: bool):
    """Per-shard masking for the Min/Max merge: shards with no candidates
    (count 0 — including padded slots) are replaced by the opposite-extreme
    sentinel so they lose every comparison. Returns (masked, valid)."""
    valid = counts > 0
    sentinel = INT32_MIN if want_max else INT32_MAX
    return jnp.where(valid, values, sentinel), valid


def minmax_at_best(values, counts, valid, best):
    """Split-sum count of candidates holding the extremum (pre-reduction:
    the SPMD builder psums this across the mesh before packing)."""
    return split_sum(jnp.where(valid & (values == best), counts, 0))


def minmax_finalize(best, n, any_valid):
    """Pack [best, count_lo, count_hi] int32 (count 0 → empty result)."""
    best = jnp.where(any_valid, best, 0)
    return jnp.concatenate([best.astype(jnp.int32)[None], n])


def minmax_merge(values, counts, want_max: bool):
    """Device-side cross-shard Min/Max merge (single device: plain
    reductions; the SPMD builder composes the same helpers with pmax/psum)."""
    masked, valid = minmax_mask(values, counts, want_max)
    best = jnp.max(masked) if want_max else jnp.min(masked)
    n = minmax_at_best(values, counts, valid, best)
    return minmax_finalize(best, n, jnp.any(valid))


# Reduction row width for the elementwise-count fast path. Measured on
# v5e (2026-07, /tmp/shape_test): axis-1 popcount sums over 2^18-word
# rows run at flat-array speed, while the natural 32768-word shard rows
# are ~8% slower (too many short reduction rows). Must divide any
# stacked block size: S_padded·2^15 words with S_padded a power of two.
COUNT_CHUNK_WORDS = 1 << 18


def elementwise_words(node) -> bool:
    """True for a tree of and/or/xor/diff over word leaves
    (leaf/const0): bit position never matters, so it can be evaluated
    on any flat chunk or tile of its leaves. No shift (its bit motion
    is per shard), no BSI ops, and no ``flipall``: the stacked block
    pads its shard axis with zero slots, and an unmasked NOT turns those
    into all-ones words. The compiler never emits it (Not lowers to
    diff(exists, x), masked by construction), so excluding it costs
    nothing and removes the latent hazard for hand-built trees."""
    if node[0] in ("leaf", "const0"):
        return True
    if node[0] in ("and", "or", "xor", "diff"):
        return all(elementwise_words(c) for c in node[1:])
    return False


def count_elementwise_sub(structure, leaf_ranks: tuple):
    """For a ('count', sub) structure whose tree is purely elementwise
    over rank-1 word leaves (elementwise_words), return ``sub``; else
    None. Such counts need no per-shard vmap: the whole stacked block
    reduces as one flat array in wider chunks (COUNT_CHUNK_WORDS) — the
    per-shard row width of 2^15 words costs measurable reduction
    overhead on TPU."""
    if not structure or structure[0] != "count":
        return None
    if any(r != 1 for r in leaf_ranks):
        return None
    return structure[1] if elementwise_words(structure[1]) else None


def count_flat(sub, leaves, scalars):
    """Evaluate an elementwise count subtree over whole stacked leaves
    and reduce popcounts in COUNT_CHUNK_WORDS-wide rows. Exact for any
    block size: per-chunk sums ≤ 2^23 fit int32 and cross-chunk sums ride
    the same split channels as the per-shard path."""
    words = expr._go(sub, leaves, scalars)
    chunk = min(COUNT_CHUNK_WORDS, words.size)
    rows = words.reshape(-1, chunk)
    counts = jnp.sum(lax.population_count(rows).astype(jnp.int32), axis=-1)
    return split_sum(counts)


def _local_body(structure, reduce_kind: str, leaf_ranks: tuple):
    """Uncompiled single-query evaluator body: vmap over the stacked
    shard axis + on-device reduction. Shared by the per-query program
    (local_fn) and the micro-batched program (local_fn_batched)."""
    n_leaves = len(leaf_ranks)
    count_sub = (count_elementwise_sub(structure, leaf_ranks)
                 if reduce_kind == "count" else None)

    def body(*args):
        leaves = args[:n_leaves]
        scalars = args[n_leaves:]

        if count_sub is not None:
            return count_flat(count_sub, leaves, scalars)

        def per_shard(*ls):
            return expr._go(structure, ls, scalars)

        out = jax.vmap(per_shard)(*leaves)
        if reduce_kind == "count":
            return split_sum(out)
        if reduce_kind == "countrows":
            return split_sum(out, axis=0)
        if reduce_kind == "bsisum":
            plane_counts, n = out  # [S, depth], [S]
            return jnp.concatenate(
                [split_sum(plane_counts, axis=0),
                 split_sum(n)[:, None]], axis=1
            )
        if reduce_kind in ("min", "max"):
            values, counts = out
            return minmax_merge(values, counts, reduce_kind == "max")
        return out  # 'row': [padded, words]

    return body


def local_fn(structure, reduce_kind: str, leaf_ranks: tuple, n_scalars: int):
    """Build (or fetch) the single-device batched evaluator for a query
    shape: vmap over the stacked shard axis + on-device reduction."""
    key = ("local", structure, reduce_kind, leaf_ranks, n_scalars)
    fn = _LOCAL_JIT_CACHE.get(key)
    if fn is None:
        fn = named_jit(reduce_kind,
                       _local_body(structure, reduce_kind, leaf_ranks))
        _LOCAL_JIT_CACHE[key] = fn
    return fn


def batched_body(body1, n_leaves: int, n_scalars: int, n_queries: int):
    """Wrap a per-query evaluator body into the micro-batch calling
    convention shared by _flush_group_locked's dispatch, the local
    builder below, and the SPMD builder (parallel.dist._dist_fn_batched):
    args are B repetitions of the leaves, then (when the shape has
    scalars) ONE int32[B, n_scalars] array carrying every query's scalars
    in a single transfer; the per-query packed results come back stacked
    on axis 0."""

    def body(*args):
        if n_scalars:
            flat, scal = args[:-1], args[-1]
        else:
            flat, scal = args, None
        outs = []
        for i in range(n_queries):
            leaves_i = flat[i * n_leaves:(i + 1) * n_leaves]
            scalars_i = (
                tuple(scal[i, j] for j in range(n_scalars))
                if n_scalars else ()
            )
            outs.append(body1(*leaves_i, *scalars_i))
        return jnp.stack(outs)

    return body


def local_fn_batched(structure, reduce_kind: str, leaf_ranks: tuple,
                     n_scalars: int, n_queries: int):
    """ONE device program evaluating ``n_queries`` same-shape queries
    (Executor.submit micro-batching). Each program dispatch carries a
    fixed launch cost that can rival the device compute of a whole
    1B-column query; stacking a micro-batch of
    pipelined queries into one program amortizes it, and the single
    [B, ...] readback serves every query in the batch with one host
    round trip."""
    key = ("localB", structure, reduce_kind, leaf_ranks, n_scalars,
           n_queries)
    fn = _LOCAL_JIT_CACHE.get(key)
    if fn is not None:
        return fn

    body1 = _local_body(structure, reduce_kind, leaf_ranks)
    fn = named_jit(
        f"{reduce_kind}_b{n_queries}",
        batched_body(body1, len(leaf_ranks), n_scalars, n_queries))
    _LOCAL_JIT_CACHE[key] = fn
    return fn


# ----------------------------------------------------- GroupBy level kernel
#
# A level is computed word tile by word tile with every candidate's
# accumulators held on-chip: each operand row is read from HBM once a
# level and no [C, words] mask is ever written to it. The device's own
# layout of a stacked matrix u32[S, n, W] keeps the shard slots on the
# sublanes unless n is 1, 2 or a multiple of eight (XLA puts the small
# dimension outermost rather than pad it), so the kernel is handed the
# [n, S, W] view (a bitcast there) and a candidate's row for eight slots
# is one dense (8, tile) block picked by a dynamic index on the leading
# dimension. The caller keeps a dimension's and the planes' row count off
# those values with zero rows (groupby_pad_rows).

_LANES = 128
_SUBLANES = 8

# The one allowance everything a level program holds in VMEM is sized
# against, from static shapes: an eighth of it the accumulator block
# (one 128-lane int32 row of lane partials per candidate and counted
# quantity, resident for the whole program: this is what bounds the
# candidates a program, not anything in HBM), three eighths the operand
# tiles; the rest is the pipeline's second output buffer and the
# compiler's own.
GROUPBY_VMEM_BYTES = 32 << 20

# A candidate walks a tile eight vregs an operand row at a time (one
# load with one dynamic address), and the walk is unrolled to one basic
# block of up to 64 vregs, the register file, for the scheduler to
# overlap; a Sum's many quantities already fill an iteration.
_CHUNK_WORDS = 8 * _LANES
_CHUNK_UNROLL = 8


def groupby_chunk_groups(n_planes: int) -> int:
    """Max candidates per level program (n_planes: the aggregate's
    depth + 2, 0 without one): the accumulator block against its eighth
    of GROUPBY_VMEM_BYTES, a power of two (8192 count-only, 256 with a
    16-bit Sum). From static shapes only."""
    quantities = max(n_planes, 1)
    fit = GROUPBY_VMEM_BYTES // 8 // (quantities * _LANES * 4)
    return max(_SUBLANES, next_pow2(fit + 1) >> 1)


def groupby_pad_rows(n_rows: int) -> int:
    """Zero rows a GroupBy appends to a dimension or plane matrix of
    n_rows (stacked_matrix's and the planes spec's pad_rows) so that XLA
    keeps its shard slots on the sublanes and the kernel's [n, S, W]
    view is never a copy."""
    pad = 0
    while n_rows + pad <= 2 or (n_rows + pad) % _SUBLANES == 0:
        pad += 1
    return pad


def groupby_tile_plan(dim_rows: tuple, other_rows: int, slots: int,
                      words: int):
    """(word-tile width, which dimensions are paged) of a level program.

    A resident dimension has every row of its tile in VMEM (read from
    HBM once a level); filter leaves and planes (other_rows) always are.
    The tile is as wide as twice those rows (the pipeline's two buffers)
    allow for one block of shard slots. Where that would leave less than
    one chunk of the walk, the dimension with the most rows is paged
    instead: it stays in HBM and a candidate's row tile is copied in by
    its index where that differs from the candidate's before, else read
    again where it lies (two tiles a paged dimension, whatever its row
    count: the row in use and the next one arriving).
    """
    budget = 3 * GROUPBY_VMEM_BYTES // 8
    paged = [False] * len(dim_rows)
    while True:
        rows = other_rows + sum(1 if p else n
                                for n, p in zip(dim_rows, paged))
        fit = budget // (2 * max(rows, 1) * slots * 4)
        tw = min(words, next_pow2(fit + 1) >> 1)
        if tw >= min(_CHUNK_WORDS, words) or all(paged):
            return max(_LANES, tw), tuple(paged)
        resident = [n if not p else -1 for n, p in zip(dim_rows, paged)]
        paged[resident.index(max(resident))] = True


def groupby_level_plan(filt_structure, leaf_ndims, dim_rows: tuple,
                       n_planes: int, slots: int, words: int):
    """(filter folded first, word-tile width, which dimensions are paged)
    of a level program over a device's ``slots`` shard slots, from static
    shapes: what the body builds its kernel by, and what the executor
    counts a paged program by where it dispatches one. A filter of
    shifts, BSI comparisons or an empty tree is folded by XLA into one
    row a slot before the kernel; row leaves under set operations are
    combined a word at a time inside it."""
    folds = filt_structure is not None and not (
        leaf_ndims and all(n == 2 for n in leaf_ndims)
        and elementwise_words(filt_structure))
    n_filt = 1 if folds else len(leaf_ndims)
    tw, paged = groupby_tile_plan(dim_rows, n_filt + n_planes,
                                  min(_SUBLANES, slots), words)
    return folds, tw, paged


def groupby_paged_rows(cand: np.ndarray, paged: tuple, chunk: int) -> tuple:
    """(row tiles named, row tiles copied) a grid step by the programs
    of one level, ``chunk`` of the candidates [C, n_gather] each: every
    candidate names a row of every paged dimension, and the kernel
    copies one where a paged column's index differs from the
    candidate's before (a program's first candidate always copies).
    (0, 0) where nothing pages."""
    cols = [d for d, p in enumerate(paged) if p]
    copies = sum(np.count_nonzero(np.diff(cand[lo:lo + chunk, d])) + 1
                 for lo in range(0, len(cand), chunk) for d in cols)
    return len(cand) * len(cols), int(copies)


# off the TPU the same kernel body runs through Pallas' interpreter
_pallas_interpret = pallas_interpret


def groupby_level_body(leaves, idxs, scalars, filt_structure, n_filt: int,
                       n_gather: int, n_planes: int):
    """GroupBy level over whole stacked leaves (leading axis: this
    device's shard slots), shared by the local and SPMD builders.

    leaves: filt leaves ++ dim matrices [S, n_i, W] ++ (planes
    [S, n_planes + pad, W] if n_planes, which is the aggregate's
    depth + 2), W a multiple of 128; a matrix's rows past those the
    candidates and n_planes name are padding (groupby_pad_rows). idxs:
    int32[n_gather, C] candidate rows; a negative entry in idxs[0] marks
    padding, after the real candidates. Returns split sums over the
    slots: counts [2, C], and with an aggregate (counts, n_g [2, C],
    plane_counts [2, depth, C]) (expr 'bsisum' semantics per
    candidate)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    filt_leaves = list(leaves[:n_filt])
    dim_mats = leaves[n_filt:n_filt + n_gather]
    has_agg = n_planes > 0
    planes = leaves[n_filt + n_gather] if has_agg else None
    slots, _, words = dim_mats[0].shape
    c_pad = idxs.shape[1]

    folds, tw, paged = groupby_level_plan(
        filt_structure, [f.ndim for f in filt_leaves],
        tuple(m.shape[1] for m in dim_mats), n_planes, slots, words)
    if folds:
        # shifts, BSI comparisons and empty trees are evaluated once by
        # XLA into one row a slot; the kernel sees a single leaf
        with jax.named_scope("groupby_filter"):
            if n_filt:
                f = jax.vmap(lambda *ls: expr._go(filt_structure, ls,
                                                  scalars))(*filt_leaves)
            else:
                f = jnp.zeros((slots, words), jnp.uint32)
        filt_leaves, filt_structure = [f], ("leaf", 0)
    n_filt = len(filt_leaves)

    quantities = max(n_planes, 1)   # count, n_g, depth planes
    sb = min(_SUBLANES, slots)
    paged_dims = [d for d in range(n_gather) if paged[d]]
    cw = min(_CHUNK_WORDS, tw)
    unroll = min(tw // cw, max(1, _CHUNK_UNROLL // quantities))
    own_filter = filt_structure is not None and filt_structure[0] != "leaf"
    n_real = jnp.sum((idxs[0] >= 0).astype(jnp.int32))
    prefetch = jnp.concatenate(
        [jnp.maximum(idxs, 0).reshape(-1), n_real[None]])

    def kernel(idx_ref, *refs):
        filt_refs = refs[:n_filt]
        dim_refs = refs[n_filt:n_filt + n_gather]
        planes_ref = refs[n_filt + n_gather] if has_agg else None
        n_in = n_filt + n_gather + (1 if has_agg else 0)
        out_ref = refs[n_in]
        scratch = list(refs[n_in + 1:])
        f_scratch = scratch.pop(0) if own_filter else None
        page_refs = {d: (scratch[2 * k], scratch[2 * k + 1])
                     for k, d in enumerate(paged_dims)}

        def chunk(k):
            return pl.ds(pl.multiple_of(k * cw, cw), cw)

        f_ref = None
        if filt_structure is not None:
            if filt_structure[0] == "leaf":
                f_ref = filt_refs[filt_structure[1]]
            else:
                # the filter expression once a tile, not once a candidate
                f_ref = f_scratch

                def filter_chunk(k, _):
                    f_ref[:, chunk(k)] = expr._go(
                        filt_structure, [r[:, chunk(k)] for r in filt_refs],
                        ())
                    return 0

                lax.fori_loop(0, tw // cw, filter_chunk, 0)

        slot_block, word_tile = pl.program_id(0), pl.program_id(1)

        @pl.when((slot_block == 0) & (word_tile == 0))
        def _():
            out_ref[...] = jnp.zeros_like(out_ref)

        def page(d, c, half):
            """The copy of candidate c's row tile of paged dimension d
            into one half of its buffer."""
            buf, sem = page_refs[d]
            return pltpu.make_async_copy(
                dim_refs[d].at[idx_ref[d * c_pad + c],
                               pl.ds(slot_block * sb, sb),
                               pl.ds(word_tile * tw, tw)],
                buf.at[half], sem.at[half])

        def lanes(x):
            """Popcounts of an (sb, cw) chunk folded to (sb, 128): the
            columns meet pairwise, not in one chain of adds."""
            pc = lax.population_count(x).astype(jnp.int32)
            cols = [pc[:, k * _LANES:(k + 1) * _LANES]
                    for k in range(cw // _LANES)]
            while len(cols) > 1:
                cols = [a + b for a, b in zip(cols[::2], cols[1::2])]
            return cols[0]

        n_cand = idx_ref[n_gather * c_pad]

        def candidate(c, halves):
            """Candidate c's counts into its accumulator rows. halves: for
            each paged dimension, the half of its buffer that holds the
            row tile of the candidate before (0 where nothing pages)."""
            at = [idx_ref[d * c_pad + c] for d in range(n_gather)]
            half = {}
            for d, held in zip(paged_dims, halves if paged_dims else ()):
                # a row tile is copied where the candidate's row changes
                # (a pruned level's candidates are prefix-major: the
                # older dimensions repeat theirs); a new grid step is a
                # new tile
                fresh = (c == 0) | (
                    at[d] != idx_ref[d * c_pad + jnp.maximum(c - 1, 0)])
                half[d] = jnp.where(fresh, 1 - held, held)

                @pl.when(fresh)
                def _():
                    page(d, c, half[d]).wait()

                # the next candidate's row arrives while this one counts,
                # over the row before this one, which nobody reads again
                @pl.when((c + 1 < n_cand)
                         & (idx_ref[d * c_pad + c + 1] != at[d]))
                def _():
                    page(d, c + 1, 1 - half[d]).start()

            def row(d, k):
                if paged[d]:
                    return page_refs[d][0][half[d], :, chunk(k)]
                return dim_refs[d][at[d], :, chunk(k)]

            def counted(k):
                """The words whose bits a chunk adds to each quantity, one
                at a time: made all at once they would not fit the
                vector registers."""
                m = row(0, k)
                for d in range(1, n_gather):
                    m = m & row(d, k)
                if f_ref is not None:
                    m = m & f_ref[:, chunk(k)]
                yield m
                if has_agg:
                    gm = m & planes_ref[expr.PLANES_EXISTS, :, chunk(k)]
                    yield gm
                    for b in range(expr.PLANES_OFFSET, n_planes):
                        yield planes_ref[b, :, chunk(k)] & gm

            def chunks(i, acc):
                for u in range(unroll):
                    acc = tuple(a + lanes(x) for a, x in
                                zip(acc, counted(i * unroll + u)))
                return acc

            acc = lax.fori_loop(
                0, tw // (cw * unroll), chunks,
                tuple(jnp.zeros((sb, _LANES), jnp.int32)
                      for _ in range(quantities)))
            for q, a in enumerate(acc):
                out_row = pl.ds(q * c_pad + c, 1)
                out_ref[out_row, :] = out_ref[out_row, :] + jnp.sum(
                    a, axis=0, keepdims=True)
            return tuple(half[d] for d in paged_dims) if paged_dims else 0

        if paged_dims:
            @pl.when(n_cand > 0)
            def _():
                for d in paged_dims:
                    page(d, 0, 0).start()

        # the candidates after the last real one are padding; the first
        # one finds its rows in half 0
        lax.fori_loop(0, n_cand, candidate,
                      (jnp.int32(1),) * len(paged_dims) if paged_dims else 0)

    def by_row(n_rows):
        return pl.BlockSpec((n_rows, sb, tw), lambda i, j, *_: (0, i, j))

    in_specs = [pl.BlockSpec((sb, tw), lambda i, j, *_: (i, j))] * n_filt
    scratch_shapes = ([pltpu.VMEM((sb, tw), jnp.uint32)]
                      if own_filter else [])
    for m, p in zip(dim_mats, paged):
        in_specs.append(pl.BlockSpec(memory_space=pl.ANY) if p
                        else by_row(m.shape[1]))
        if p:
            scratch_shapes += [pltpu.VMEM((2, sb, tw), jnp.uint32),
                               pltpu.SemaphoreType.DMA((2,))]
    operands = filt_leaves + [jnp.swapaxes(m, 0, 1) for m in dim_mats]
    if has_agg:
        in_specs.append(by_row(n_planes))   # the pad rows are never read
        operands.append(jnp.swapaxes(planes, 0, 1))
    # the partials vary over whatever mesh axes the leaves do
    vma = jax.typeof(dim_mats[0]).vma
    with jax.named_scope("groupby_level"):
        partials = pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct((quantities * c_pad, _LANES),
                                           jnp.int32, vma=vma),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(slots // sb, words // tw),
                in_specs=in_specs,
                out_specs=pl.BlockSpec((quantities * c_pad, _LANES),
                                       lambda i, j, *_: (0, 0)),
                scratch_shapes=scratch_shapes,
            ),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary"),
                vmem_limit_bytes=GROUPBY_VMEM_BYTES,
            ),
            interpret=_pallas_interpret(),
            name="groupby_level",
        )(prefetch, *operands)
    # a lane partial is at most 2^13 a slot, so it cannot wrap before
    # 2^18 slots; the lanes are summed in the two split channels and the
    # carry moved up, which leaves both inside the bounds a sum of
    # per-slot splits has (SPLIT_MASK and SHARD_WIDTH >> SPLIT_SHIFT a slot)
    lo, hi = split_sum(partials.reshape(quantities, c_pad, _LANES), axis=-1)
    out = jnp.stack([lo & SPLIT_MASK, hi + (lo >> SPLIT_SHIFT)])
    if not has_agg:
        return out[:, 0]
    return out[:, 0], out[:, 1], out[:, expr.PLANES_OFFSET:]


def unpack_groupby_operand(packed, n_gather: int, n_scalars: int):
    """The one int32 operand a level program takes after its leaves
    (Executor._groupby_operand_put packs it): the candidate index arrays
    end to end, then the scalars. Returns (idxs [n_gather, C], scalars)."""
    c = (packed.shape[0] - n_scalars) // n_gather
    idxs = packed[:n_gather * c].reshape(n_gather, c)
    return idxs, tuple(packed[n_gather * c + i] for i in range(n_scalars))


def local_groupby_level_fn(filt_structure, n_filt: int, n_scalars: int,
                           n_gather: int, n_planes: int):
    """Single-device GroupBy level program (n_planes: the aggregate's
    depth + 2, 0 without one).

    Args: filt leaves ++ dim matrices [S, n_i, W] ++ (planes
    [S, n_planes + pad, W] if agg) ++ ONE int32 array
    (unpack_groupby_operand).
    Packed result (split sums, [2, ·] raveled): counts [2·C] without
    agg, else counts [2·C] ++ n_g [2·C] ++ plane_counts [2·depth·C].
    """
    key = ("localgbl", filt_structure, n_filt, n_scalars, n_gather, n_planes)
    fn = _LOCAL_JIT_CACHE.get(key)
    if fn is not None:
        return fn

    n_leaves = n_filt + n_gather + (1 if n_planes else 0)

    def body(*args):
        idxs, scalars = unpack_groupby_operand(
            args[n_leaves], n_gather, n_scalars)
        out = groupby_level_body(
            args[:n_leaves], idxs, scalars, filt_structure, n_filt,
            n_gather, n_planes)
        if not n_planes:
            return out.ravel()
        return jnp.concatenate([o.ravel() for o in out])

    fn = named_jit("groupby_level", body)
    _LOCAL_JIT_CACHE[key] = fn
    return fn
