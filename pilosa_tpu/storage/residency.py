"""Device residency manager: which fragment rows live in HBM.

The reference mmaps every fragment file and lets the OS page cache decide
residency (fragment.go + syswrap/ — SURVEY.md §2 #3, #26). HBM is orders of
magnitude smaller than a disk page cache, so residency is explicit here: a
byte-budgeted LRU of decoded dense rows (uint32[32768] each = 128 KiB) keyed
by (fragment id, row). The host roaring file remains the source of truth and
rows are re-decoded on demand (SURVEY.md §7.3 hard part #1).

Two tiers. Hot entries are dense, ready for the bitwise kernels. When the
dense tier overflows its budget share, sparse entries are *demoted* instead
of dropped: their nonzero 4 KiB blocks are gathered on device into a compact
``uint32[nb, 1024]`` array (one jitted gather — no host round trip; block
indices were computed from the host array at insert time, so demotion never
blocks on a device→host sync). A hit on a demoted entry scatters the blocks
back into a dense array (one jitted scatter) and promotes it. For bitmap
data at real-world densities this multiplies effective HBM residency by the
inverse block-occupancy, which matters because a re-upload over host↔device
is the slowest path in the system.

A third, HOST tier backs heat-driven residency tiering
(storage/tiering.py): cold entries demote to compact nonzero-block
copies in host RAM (own byte budget, ``residency-host-tier-bytes``) and
promote back to dense on access or when the ResidencyTierer's pass sees
their heat recover — so far more indexes than fit in HBM stay one paced
upload away from device residency.

Writes invalidate the affected row in every tier; queries call ``get_row``
and receive a device array ready for the bitwise kernels.

Derived entries (the batched executor's stacked query leaves,
executor/batch.py) register an *updater* instead: a write to one fragment
row becomes an in-place device scatter of the affected shard slot
(SURVEY.md §7.3 hard part #3 — no host round trip for pure bit-adds, one
128 KiB row re-upload otherwise), so a Set() no longer evicts unrelated
resident leaves. Compressed-tier copies of an affected leaf are
invalidated rather than patched (decompress+patch costs more than the
re-decode they were demoted to avoid).
"""

from __future__ import annotations

import math
import threading
import weakref
from collections import OrderedDict
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from pilosa_tpu.roaring import kernels
from pilosa_tpu.shardwidth import WORDS_PER_SHARD, next_pow2
from pilosa_tpu.utils.compile_cache import named_jit, pallas_interpret
from pilosa_tpu.utils.cost import current_cost
from pilosa_tpu.utils.tracing import stage, staged

ROW_BYTES = WORDS_PER_SHARD * 4  # 128 KiB per resident row

# Budget of a cache nobody sized: 4 GiB of ONE chip's HBM. A server
# derives its own from the chips it holds (default_budget_bytes); this
# is what that falls back to where the backend reports no memory limit
# (the CPU). Per chip: an entry is charged what it holds on the fullest
# chip (chip_bytes), so a leaf sharded over a four-chip mesh costs a
# quarter of its nbytes and the mesh as a whole holds four budgets.
# Tests override.
DEFAULT_BUDGET_BYTES = 4 << 30

# Default compressed host-tier budget (residency-host-tier-bytes knob):
# host RAM parking for cold demoted entries.
DEFAULT_HOST_BUDGET_BYTES = 1 << 30

# Compression granularity: 4 KiB device blocks. Row = 32 blocks. A tile
# of a sparse miss (kernels.SparseRows) is the same 1,024 words, so the
# tiles it lists are the leaf's block index.
COMPRESS_BLOCK_WORDS = kernels.SPARSE_TILE_WORDS

# Probe return sentinel: "this write affects the entry but it cannot be
# patched in place — drop it" (multi-host sharded leaves, where a device
# scatter would be a collective program a single host can't run alone).
PURGE = object()

# Demote-as-compressed only when it actually saves memory; denser entries
# are simply dropped (host re-decode is the fallback, as before).
COMPRESS_MAX_OCCUPANCY = 0.5


def default_budget_bytes(devices=None) -> int:
    """The budget of a server whose ``device-budget-bytes`` is unset:
    three quarters of the smallest ``memory_stats()["bytes_limit"]``
    over ``devices`` (the local ones by default; the budget is per chip,
    so on a mesh the smallest chip decides), DEFAULT_BUDGET_BYTES where
    a device gives no stats or no limit. The quarter left over is for
    program temporaries, the one upload in flight when the cache is full
    (_insert_dense inserts before it evicts) and XLA's workspace."""
    limits = []
    for d in jax.local_devices() if devices is None else devices:
        limit = (d.memory_stats() or {}).get("bytes_limit")
        if not limit:
            return DEFAULT_BUDGET_BYTES
        limits.append(int(limit))
    return min(limits) * 3 // 4 if limits else DEFAULT_BUDGET_BYTES


def chip_bytes(arr) -> int:
    """What ``arr`` holds on the fullest chip: the bytes of its largest
    addressable shard. A leaf that DistExecutor._leaf_put placed with a
    NamedSharding over the mesh is split evenly over the chips, so that
    is ``nbytes / mesh.size``; for a single-device array, a replicated
    one and a host array it is ``nbytes``. The dense tier's budget, its
    ``_bytes`` and every figure derived from them are in these bytes,
    the unit Executor.arg_shard_factor already reckons in."""
    sharding = getattr(arr, "sharding", None)
    if sharding is None or len(sharding.device_set) == 1:
        return int(arr.nbytes)
    return math.prod(sharding.shard_shape(arr.shape)) * arr.dtype.itemsize


def _gather_blocks(arr, idx, block_words: int):
    """Compact the nonzero blocks of a flattened array: uint32[nb, bw]."""
    return arr.reshape(-1, block_words)[idx]


def _scatter_blocks(blocks, idx, n_blocks: int, block_words: int):
    """Inverse of _gather_blocks. ``idx`` may contain duplicates (padding
    repeats a real index with its real data — identical writes are safe)."""
    out = jnp.zeros((n_blocks, block_words), jnp.uint32)
    return out.at[idx].set(blocks).reshape(-1)


_gather_blocks = named_jit("gather_blocks", _gather_blocks,
                           static_argnames=("block_words",))
_scatter_blocks = named_jit("scatter_blocks", _scatter_blocks,
                            static_argnames=("n_blocks", "block_words"))


# ------------------------------------------------------------ sparse misses
#
# A row leaf whose containers are all sparse arrays reaches the cache as
# its set bits (kernels.sparse_rows32: a tile table and the bits' numbers
# within the leaf, in tile order) and is made dense here, on the chip: the
# host neither fills nor scans nor ships the 16 MiB of a row that is
# 0.05 % set. What becomes resident is the dense leaf every program takes.

_LANES = 128
_SUBLANES = 8
_TILE_SUBLANES = COMPRESS_BLOCK_WORDS // _LANES  # a tile is one vreg
_TILE_BITS = COMPRESS_BLOCK_WORDS * 32
# Listed bits a copy into scalar memory (there are two buffers), and how
# many of them one step of the walk ORs into its tile: a power of two,
# because the step ORs its hits pairwise (on the chip 8 read 0.36 ms a
# 64 k-bit row, 16 0.29, 32 0.29 and 0.29 against 0.23 a 4.5 k-bit one).
EXPAND_CHUNK = 4096
EXPAND_UNROLL = 16
assert EXPAND_UNROLL & (EXPAND_UNROLL - 1) == 0


def expand_rows_body(packed, n_rows: int, n_pad: int):
    """One share of ``kernels.SparseRows.packed`` to its rows of the
    leaf, ``uint32[n_rows, 32768]``: the whole leaf on one chip
    (``jit_expand_rows``), a chip's slot rows under a mesh's
    ``shard_map`` (``jit_dist_expand_rows``, parallel/dist.py).

    One Pallas kernel, a grid step for eight slot rows. A step reads the
    bounds of its tiles from the tile table (scalar prefetch) and copies
    the listed bits between them from HBM into scalar memory a chunk at
    a time (the next chunk travels while this one is walked). The walk is
    ONE loop over the chunk, EXPAND_UNROLL listed bits a pass: a listed
    bit XOR the number of a lane's first bit is under 32, and is then the
    bit's place in the word, in the one lane whose word holds it; the
    pass ORs ``1 << place`` there into the vreg of the tile it stands in,
    stores that vreg, and moves to the next tile when it reached the
    tile's end, by selects and not by branches (a loop a tile inside a
    loop over tiles cost ~70 cycles a tile of loop overhead). A pass may
    read past its tile's end or, moved back from the chunk's end, before
    its start: entries read again change nothing under an OR, and entries
    of other tiles and padding match no lane of this one. The tiles of a
    row are 1,024 consecutive words each, the leaf wants its rows on the
    sublanes: strided reads of the scratch turn one into the other when
    the step ends."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    words = WORDS_PER_SHARD
    row_tiles = words // COMPRESS_BLOCK_WORDS
    sb = min(_SUBLANES, n_rows)
    step_tiles = sb * row_tiles
    t1 = kernels.sparse_starts_len(n_rows)
    ch, unroll = EXPAND_CHUNK, EXPAND_UNROLL
    packed = lax.bitcast_convert_type(packed, jnp.int32)

    def kernel(starts_ref, listed_ref, out_ref, acc_ref, buf, sem):
        t0 = pl.program_id(0) * step_tiles
        lo, hi = starts_ref[t0], starts_ref[t0 + step_tiles]
        c0 = lo // ch
        c1 = jnp.where(hi > lo, (hi + ch - 1) // ch, c0)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        # the first bit of each lane's word, within a tile
        lane_bit = 32 * (
            lax.broadcasted_iota(jnp.int32, (_TILE_SUBLANES, _LANES), 0)
            * _LANES
            + lax.broadcasted_iota(jnp.int32, (_TILE_SUBLANES, _LANES), 1))

        def copy(c, half):
            return pltpu.make_async_copy(
                listed_ref.at[pl.ds(pl.multiple_of(c * ch, ch), ch)],
                buf.at[pl.ds(pl.multiple_of(half * ch, ch), ch)],
                sem.at[half])

        def vreg_of(t):
            return pl.ds(pl.multiple_of(t * _TILE_SUBLANES, _TILE_SUBLANES),
                         _TILE_SUBLANES)

        @pl.when(c1 > c0)
        def _():
            copy(c0, lax.rem(c0, 2)).start()

        def chunk(c, t_first):
            half = lax.rem(c, 2)
            copy(c, half).wait()

            @pl.when(c + 1 < c1)
            def _():
                copy(c + 1, 1 - half).start()

            base = c * ch
            stop = jnp.minimum(hi, base + ch)

            def more(at):
                t, j, _ = at
                return (t < step_tiles) & (j < stop)

            def walk(at):
                t, j, acc = at
                tile_end = starts_ref[t0 + t + 1]
                k = half * ch + jnp.minimum(j - base, ch - unroll)
                first_bit = lane_bit + (t0 + t) * _TILE_BITS
                hits = []
                for u in range(unroll):
                    place = first_bit ^ buf[k + u]
                    hits.append(jnp.where(
                        place < 32, jnp.left_shift(1, place & 31), 0))
                while len(hits) > 1:  # pairwise, not one chain of ORs
                    hits = [a | b for a, b in zip(hits[::2], hits[1::2])]
                acc = acc | hits[0]
                acc_ref[vreg_of(t), :] = acc
                done = j + unroll >= jnp.minimum(tile_end, base + ch)
                t_next = jnp.where(done, t + 1, t)
                return (t_next, jnp.where(done, tile_end, j + unroll),
                        jnp.where(done, acc_ref[vreg_of(jnp.minimum(
                            t_next, step_tiles - 1)), :], acc))

            t_last, _, _ = lax.while_loop(more, walk, (
                t_first, jnp.maximum(starts_ref[t0 + t_first], base),
                acc_ref[vreg_of(t_first), :]))
            # the tile the chunk ended in may go on in the next one
            return jnp.maximum(t_last - 1, 0)

        lax.fori_loop(c0, c1, chunk, 0)

        # scratch row (r * row_tiles + k) * 8 + s holds the words from
        # k * 1024 + s * 128 of slot row r
        def relay(k, _):
            for s in range(_TILE_SUBLANES):
                ks = k * _TILE_SUBLANES + s
                rows = acc_ref[pl.ds(ks, sb,
                                     stride=row_tiles * _TILE_SUBLANES), :]
                out_ref[:, pl.ds(pl.multiple_of(ks * _LANES, _LANES),
                                 _LANES)] = pltpu.bitcast(rows, jnp.uint32)
            return 0

        lax.fori_loop(0, row_tiles, relay, 0)

    with jax.named_scope("expand_rows"):
        return pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct((n_rows, words), jnp.uint32),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(n_rows // sb,),
                in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
                out_specs=pl.BlockSpec((sb, words), lambda g, *_: (g, 0)),
                scratch_shapes=[
                    pltpu.VMEM((step_tiles * _TILE_SUBLANES, _LANES),
                               jnp.int32),
                    pltpu.SMEM((2 * ch,), jnp.int32),
                    pltpu.SemaphoreType.DMA((2,)),
                ],
            ),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
            interpret=pallas_interpret(),
            name="expand_rows",
        )(packed[:t1], packed[t1:])


_expand_rows = named_jit("expand_rows", expand_rows_body,
                         static_argnames=("n_rows", "n_pad"))
# (row count, shares, the device or mesh) whose every bucket's expansion
# has been compiled
_expansions_ready: set = set()


class WriteEvent:
    """One fragment-row mutation, as seen by dependent cache entries.

    positions: in-shard bit positions touched, or None when unknown (bulk
    row replace). added: True = bits only set, False = bits only cleared,
    None = mixed/unknown.
    """

    __slots__ = ("index", "field", "view", "shard", "row", "positions",
                 "added", "scope")

    def __init__(self, index, field, view, shard, row, positions=None,
                 added=None, scope=""):
        self.index = index
        self.field = field
        self.view = view
        self.shard = shard
        self.row = row
        self.positions = positions
        self.added = added
        self.scope = scope


class _DenseEntry:
    __slots__ = ("arr", "block_idx", "custom")

    def __init__(self, arr, block_idx, custom=False):
        self.arr = arr
        self.block_idx = block_idx  # np.int32[nb] or None = incompressible
        # custom placement (mesh-sharded device_put): pinned to its
        # sharding — never compressed, never tiered to host
        self.custom = custom


class _CompressedEntry:
    __slots__ = ("blocks", "idx", "shape", "n_blocks", "block_idx")

    def __init__(self, blocks, idx, shape, n_blocks, block_idx):
        self.blocks = blocks  # device uint32[nb_padded, bw]
        self.idx = idx  # device int32[nb_padded]
        self.shape = shape
        self.n_blocks = n_blocks
        self.block_idx = block_idx  # host copy, for re-demotion

    @property
    def nbytes(self) -> int:
        return self.blocks.nbytes + self.idx.nbytes


class _HostEntry:
    """Compressed HOST-tier copy (heat-driven residency tiering): the
    nonzero 4 KiB blocks in host RAM — or the full flat array when the
    entry is incompressible — one paced upload + scatter away from dense
    device residency. Cold fragments park here at roaring-like density
    (Chambi et al. 1402.6407), so 10-100x more indexes stay one promote
    away from HBM than HBM holds dense."""

    __slots__ = ("blocks", "idx", "shape", "n_blocks", "block_idx")

    def __init__(self, blocks, idx, shape, n_blocks, block_idx):
        self.blocks = blocks  # np.uint32[nb_padded, bw], or flat full array
        self.idx = idx  # np.int32[nb_padded], or None = full array
        self.shape = shape
        self.n_blocks = n_blocks
        self.block_idx = block_idx  # original nonzero-block index (or None)

    @property
    def nbytes(self) -> int:
        n = int(self.blocks.nbytes)
        if self.idx is not None:
            n += int(self.idx.nbytes)
        return n


class _ContendedLock:
    """The row cache's one lock, with its waiting measured: an
    uncontended acquire costs what it did; only when the non-blocking
    try fails is the blocking acquire timed, as stage
    ``residency.lock_wait`` — so the stage's seconds are the time threads
    spent queued behind the lock, and its count the contended
    acquisitions. ``inner`` is the re-entrant lock itself (the build
    condition waits on it)."""

    __slots__ = ("inner",)

    def __init__(self):
        self.inner = threading.RLock()

    def __enter__(self):
        if not self.inner.acquire(blocking=False):
            with stage("residency.lock_wait"):
                self.inner.acquire()

    def __exit__(self, *exc):
        self.inner.release()
        return False


class DeviceRowCache:
    """Byte-budgeted two-tier LRU of device-resident arrays (dense rows,
    BSI plane matrices, mesh-sharded shard stacks). ``budget_bytes`` is
    a budget PER CHIP: a dense entry is charged chip_bytes, what it
    holds on the fullest chip, so on one chip its nbytes and on a mesh
    its shard. The compressed tier lives on ``device`` alone (small
    single-device arrays, nbytes = chip bytes) and shares that budget;
    the host tier is host RAM under its own. Sparse entries compress on
    demotion instead of dropping."""

    def __init__(self, budget_bytes: int = DEFAULT_BUDGET_BYTES, device=None,
                 host_budget_bytes: int = DEFAULT_HOST_BUDGET_BYTES):
        self.budget_bytes = budget_bytes
        self.host_budget_bytes = int(host_budget_bytes)
        self.device = device
        self._rows: OrderedDict[tuple, _DenseEntry] = OrderedDict()
        self._compressed: OrderedDict[tuple, _CompressedEntry] = OrderedDict()
        # compressed HOST tier (heat-driven tiering): demoted entries in
        # host RAM, own byte budget + LRU, promoted back on access or by
        # the ResidencyTierer pass (storage/tiering.py)
        self._host: OrderedDict[tuple, _HostEntry] = OrderedDict()
        self._bytes = 0
        self._compressed_bytes = 0
        self._host_bytes = 0
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.compressions = 0
        self.decompressions = 0
        self.miss_bytes = 0  # bytes of the dense arrays misses placed
        self.miss_transfer_bytes = 0  # bytes misses handed to device_put
        self.sparse_misses = 0  # misses placed from their set bits
        self.host_hits = 0  # host-tier lookups served (inline promotes)
        self.tier_promotions = 0  # host -> dense (lookup or pass)
        self.tier_demotions = 0  # dense/compressed -> host
        self.updates = 0  # in-place scatter updates of derived entries
        # patches dispatched again because another writer swapped the
        # same leaf first (_patch_routed)
        self.patch_retries = 0
        self.write_events = 0  # fragment mutations routed through apply_write
        # Snapshot validity counter: bumped whenever an entry is removed
        # or a dense array replaced (write patch, invalidate, evict,
        # demote, clear). Holders of (key -> array) snapshots taken
        # OUTSIDE this cache (the executor's operand memo) may serve
        # them only while generation is unchanged; additions never bump
        # (they cannot stale an existing snapshot). Listeners are
        # weakly-held zero-arg callables invoked on every bump so
        # snapshot holders drop their array references EAGERLY — an
        # eviction must actually free HBM, not wait for the holder's
        # next lazy validity check.
        self.generation = 0
        self._gen_listeners: list = []
        # derived-entry dependency registry: a stacked leaf registers an
        # updater under a (index, field) tag; apply_write routes each
        # fragment mutation to exactly the tagged entries
        self._updaters: dict[tuple, tuple[tuple, Callable]] = {}
        self._tag_index: dict[tuple, set[tuple]] = {}
        # One lock, for dictionary bookkeeping. Neither a write's device
        # patch nor a miss's host decode runs under it: a writer
        # dispatches its patch on the array it saw and swaps the result
        # in only if the entry still holds that array (_patch_routed),
        # so two concurrent writes to different fragments of one field
        # can't lose each other's read-modify-write of the same leaf;
        # a miss decodes first and replays what it missed (get_or_build).
        self._lock = _ContendedLock()
        # in-flight builds: key -> buffered write events, replayed onto
        # the entry after its unlocked decode (see get_or_build); the
        # condition lets concurrent builders of one key wait for the first
        self._pending_builds: dict[tuple, list] = {}
        self._build_done = threading.Condition(self._lock.inner)

    def __len__(self) -> int:
        return len(self._rows) + len(self._compressed)

    @property
    def bytes_used(self) -> int:
        return self._bytes + self._compressed_bytes

    @property
    def compressed_bytes(self) -> int:
        return self._compressed_bytes

    @property
    def host_bytes(self) -> int:
        return self._host_bytes

    def touch(self, keys) -> None:
        """Refresh LRU positions without fetching (executor operand-memo
        hits: the leaves are served from the memo, but they must not
        look LRU-cold and become eviction's first victims)."""
        with self._lock:
            for key in keys:
                if key in self._rows:
                    self._rows.move_to_end(key)
                elif key in self._compressed:
                    self._compressed.move_to_end(key)

    def add_generation_listener(self, fn) -> None:
        """Register a bound method invoked (under the cache lock) on
        every generation bump; held via WeakMethod so registrants can be
        garbage-collected. Listeners must be lock-free and cheap (the
        executor's is a dict.clear)."""
        with self._lock:
            self._gen_listeners.append(weakref.WeakMethod(fn))

    def remove_generation_listener(self, fn) -> None:
        """Unregister ``fn`` (and drop dead refs). Re-homing callers
        (the executor when the global cache is swapped) must remove
        themselves from the OLD cache: a still-registered listener
        would keep wholesale-clearing state that now tracks the new
        cache, and a swap-back would stack duplicate registrations."""
        with self._lock:
            live = []
            for ref in self._gen_listeners:
                cb = ref()  # bind once: a second ref() could race GC
                if cb is not None and cb != fn:
                    live.append(ref)
            self._gen_listeners = live

    def _bump_generation(self) -> None:
        """Caller holds the lock. Bump + notify snapshot holders."""
        self.generation += 1
        if self._gen_listeners:
            live = []
            for ref in self._gen_listeners:
                cb = ref()
                if cb is not None:
                    cb()
                    live.append(ref)
            self._gen_listeners = live

    def _lookup_locked(self, key: tuple):
        """Dense hit or compressed→dense promotion; None on miss.
        Caller holds the lock."""
        entry = self._rows.get(key)
        if entry is not None:
            self.hits += 1
            self._rows.move_to_end(key)
            return entry.arr
        centry = self._compressed.pop(key, None)
        if centry is not None:
            self.hits += 1
            self.decompressions += 1
            self._compressed_bytes -= centry.nbytes
            flat = _scatter_blocks(
                centry.blocks, centry.idx, centry.n_blocks,
                COMPRESS_BLOCK_WORDS,
            )
            arr = flat.reshape(centry.shape)
            self._insert_dense(key, arr, centry.block_idx)
            return arr
        hentry = self._host.pop(key, None)
        if hentry is not None:
            # host-tier hit: upload + scatter + promote inline — the
            # access IS the heat (the tiering pass sweeps what queries
            # didn't touch). Updaters stayed registered across the
            # demotion, so the promoted entry keeps its write routing.
            self.hits += 1
            self.host_hits += 1
            self.tier_promotions += 1
            self._host_bytes -= hentry.nbytes
            arr = self._upload_host_entry(hentry)
            self._insert_dense(key, arr, hentry.block_idx)
            return arr
        return None

    def _place(self, host, device_put):
        """A miss's decode to ``(device array, block index)``: a dense
        host array is transferred (by ``device_put``, a custom placement
        that is never compressed, or to this cache's device), a
        kernels.SparseRows is transferred as it is and expanded there
        (by ``device_put.expand`` where the placement is a mesh's: only a
        placement that has one is ever handed the sparse form)."""
        block_idx = None
        if isinstance(host, kernels.SparseRows):
            if device_put is None:
                expand, where = self._expand, self.device
            else:
                expand, where = device_put.expand, device_put.mesh
            self._compile_expansions(host.n_rows, host.parts, expand, where)
            arr = expand(host.packed, host.n_rows, host.n_pad)
            if (device_put is None and host.tiles.size * COMPRESS_BLOCK_WORDS
                    <= COMPRESS_MAX_OCCUPANCY * arr.size):
                block_idx = host.tiles
            self.sparse_misses += 1
            sent = int(host.packed.nbytes)
        elif device_put is not None:
            arr = device_put(host)
            sent = int(arr.nbytes)
        else:
            arr = jax.device_put(host, self.device)
            block_idx = self._host_block_index(host)
            sent = int(arr.nbytes)
        self.miss_transfer_bytes += sent
        cost = current_cost()
        if cost is not None:  # host→device bytes for the active request
            cost.note_upload(sent)
        return arr, block_idx

    def _expand(self, packed: np.ndarray, n_rows: int, n_pad: int):
        # the program call transfers its one host argument itself: a
        # device_put of its own first costs the thread 0.16 ms more
        # (PERF.md, PR 38); only a cache bound to a device needs one
        if self.device is not None:
            packed = jax.device_put(packed, self.device)
        return _expand_rows(packed, n_rows=n_rows, n_pad=n_pad)

    @staticmethod
    def _compile_expansions(n_rows: int, parts: int, expand, where) -> None:
        """Before the first sparse leaf of ``n_rows`` in ``parts`` shares
        is expanded on ``where`` (a device, a mesh), run the expansion of
        every bucket such a leaf may come in once, on an empty list: the
        closed list of programs is compiled (or read from the persistent
        cache) at one known moment, and no later miss, however rare its
        bucket, compiles."""
        if (n_rows, parts, where) in _expansions_ready:
            return
        rows = n_rows // parts
        for n_pad in kernels.sparse_buckets(rows):
            expand(np.zeros(parts * kernels.sparse_packed_len(rows, n_pad),
                            np.uint32), n_rows, n_pad)
        _expansions_ready.add((n_rows, parts, where))

    def _put_locked(self, key, host, device_put):
        with stage("residency.upload"):
            arr, block_idx = self._place(host, device_put)
        self.miss_bytes += int(arr.nbytes)
        self._insert_dense(key, arr, block_idx,
                           custom=device_put is not None)
        return arr

    def get_row(self, key: tuple, decode: Callable[[], np.ndarray],
                device_put: Callable | None = None) -> jax.Array:
        """Return the device array for ``key``, decoding+uploading on miss.
        ``device_put`` overrides placement (e.g. a NamedSharding put);
        entries with custom placement are never compressed."""
        cost = current_cost()
        with self._lock:
            arr = self._lookup_locked(key)
            if arr is not None:
                if cost is not None:
                    cost.note_cache(True)
                return arr
            self.misses += 1
            if cost is not None:
                cost.note_cache(False)
            # decode under the lock: plain get_row keys are per-fragment
            # (invalidated by their writers), so staleness isn't possible,
            # and single-row decodes are cheap
            with stage("residency.miss"):
                with stage("residency.decode"):
                    host = decode()
                return self._put_locked(key, host, device_put)

    def get_or_build(self, key: tuple, tag: tuple | None,
                     probe: Callable | None,
                     decode: Callable[[], np.ndarray],
                     device_put: Callable | None = None) -> jax.Array:
        """get_row for derived (write-patched) entries.

        Event-buffered build: on a miss, the builder registers the
        probe (produced by the ``probe`` zero-arg factory) and claims the
        key BEFORE decoding, so writes landing during the unlocked host
        decode are buffered (apply_write) and replayed as patches after
        the upload — no write can be missed, the slow decode never holds
        the global lock (queries and writers to other keys proceed), and
        concurrent builders of the SAME key wait on the first instead of
        decoding twice. Delta patches are idempotent, so an event whose
        write the decode already saw replays harmlessly. A buffered
        event the probe cannot patch (PURGE — multi-host sharded leaves)
        forces one re-decode under the lock, which writers then
        serialize behind."""
        cost = current_cost()
        with self._lock:
            while True:
                arr = self._lookup_locked(key)
                if arr is not None:
                    if tag is not None:
                        self._register_locked(key, tag, probe)
                    if cost is not None:
                        cost.note_cache(True)
                    return arr
                if key not in self._pending_builds:
                    break
                self._build_done.wait()  # another thread is building key
            if cost is not None:
                cost.note_cache(False)
            buf: list = []
            self._pending_builds[key] = buf
            if tag is not None:
                # route this tag's writes into the buffer from now on
                self._updaters[key] = (tag, probe())
                self._tag_index.setdefault(tag, set()).add(key)
        return self._build_missing(key, tag, decode, device_put, buf)

    @staged("residency.miss")
    def _build_missing(self, key, tag, decode, device_put, buf):
        """The miss half of get_or_build: decode outside the lock,
        upload and replay the buffered writes under it."""
        try:
            with stage("residency.decode"):
                host = decode()  # slow host work, outside the lock
        except BaseException:
            with self._lock:
                self._pending_builds.pop(key, None)
                self._drop_updater(key)
                self._build_done.notify_all()
            raise
        with self._lock:
            try:
                self.misses += 1
                reg = self._updaters.get(key)
                if tag is not None and reg is None:
                    # invalidate_tag raced the build (field delete): the
                    # decode belongs to a dead field — serve it to this
                    # query but don't cache it
                    return self._place(host, device_put)[0]
                arr = self._put_locked(key, host, device_put)
                for ev in buf:  # replay writes that landed mid-decode
                    apply = reg[1](ev) if reg is not None else None
                    if apply is None:
                        continue
                    if apply is PURGE:
                        # can't patch: drop the first upload (and its
                        # byte accounting) and re-decode with writers
                        # held off
                        old = self._rows.pop(key, None)
                        if old is not None:
                            self._bytes -= chip_bytes(old.arr)
                        arr = self._put_locked(key, decode(), device_put)
                        break
                    entry = self._rows.get(key)
                    if entry is not None:
                        with stage("residency.patch"):
                            entry.arr = apply(entry.arr)
                        entry.block_idx = None
                        arr = entry.arr
                return arr
            finally:
                self._pending_builds.pop(key, None)
                self._build_done.notify_all()

    @staticmethod
    def _host_block_index(host: np.ndarray):
        """Nonzero-block indices, computed from the host array at insert
        time (free pass over data already in cache) so demotion later
        needs no device→host sync. None = incompressible."""
        if host.dtype != np.uint32 or host.size % COMPRESS_BLOCK_WORDS:
            return None
        mask = np.any(
            host.reshape(-1, COMPRESS_BLOCK_WORDS) != 0, axis=1
        )
        if mask.mean() > COMPRESS_MAX_OCCUPANCY:
            return None
        return np.flatnonzero(mask).astype(np.int32)

    def _insert_dense(self, key: tuple, arr, block_idx,
                      custom: bool = False) -> None:
        self._rows[key] = _DenseEntry(arr, block_idx, custom)
        self._bytes += chip_bytes(arr)
        self._evict()

    def invalidate(self, key: tuple) -> None:
        with self._lock:
            self._invalidate_locked(key)

    def _invalidate_locked(self, key: tuple) -> None:
        entry = self._rows.pop(key, None)
        if entry is not None:
            self._bytes -= chip_bytes(entry.arr)
        centry = self._compressed.pop(key, None)
        if centry is not None:
            self._compressed_bytes -= centry.nbytes
        # host copies invalidate like compressed ones: decompress+
        # patch costs more than the re-decode they were demoted to
        # avoid (a routed write's missing-dense branch lands here)
        hentry = self._host.pop(key, None)
        if hentry is not None:
            self._host_bytes -= hentry.nbytes
        if entry is not None or centry is not None or hentry is not None:
            self._bump_generation()
        self._drop_updater(key)

    def invalidate_fragment(self, frag_id: tuple) -> None:
        with self._lock:
            self._invalidate_prefix_locked(frag_id)

    def _invalidate_prefix_locked(self, prefix: tuple) -> None:
        """Every key of every tier that starts with ``prefix``: a scan
        of all three stores, so not for the per-write path."""
        n = len(prefix)
        for store in (self._rows, self._compressed, self._host):
            for k in [k for k in store if k[:n] == prefix]:
                self._invalidate_locked(k)

    # --------------------------------------------------- derived-entry updates

    def register_updater(self, key: tuple, tag: tuple,
                         probe: Callable) -> None:
        """Attach a write-routing probe to a resident derived entry.

        ``probe(event)`` returns None when the entry is unaffected by the
        write, else a function ``apply(arr) -> arr`` that patches the
        device array in place (scatter of the affected shard slot).
        Idempotent per key; dropped when the entry leaves both tiers.
        """
        with self._lock:
            self._register_locked(key, tag, lambda: probe)

    def _register_locked(self, key: tuple, tag: tuple, probe_factory) -> None:
        if key in self._rows or key in self._compressed:
            old = self._updaters.get(key)
            if old is not None and old[0] == tag:
                return  # already registered; probes are stateless closures
            if old is not None:
                self._tag_index[old[0]].discard(key)
            self._updaters[key] = (tag, probe_factory())
            self._tag_index.setdefault(tag, set()).add(key)

    def invalidate_tag(self, tag: tuple) -> None:
        """Drop every derived entry registered under a (index, field) tag
        (field close/delete: the durable files are no longer ours)."""
        with self._lock:
            for key in list(self._tag_index.get(tag, ())):
                self._invalidate_locked(key)

    def _drop_updater(self, key: tuple) -> None:
        reg = self._updaters.pop(key, None)
        if reg is not None:
            keys = self._tag_index.get(reg[0])
            if keys is not None:
                keys.discard(key)
                if not keys:
                    del self._tag_index[reg[0]]

    def apply_write(self, event: WriteEvent) -> None:
        """Route one fragment mutation to the derived entries that depend
        on it: dense entries are patched on device, compressed and host
        copies are invalidated, everything else is untouched (this
        replaces the old global write-generation purge, which evicted
        EVERY stacked leaf on any write). The lock is held to find the
        affected entries and again to swap the patched arrays in; the
        patches themselves are dispatched between the two, outside it
        (_patch_routed)."""
        with self._lock:
            todo = self._route_locked(event)
        self._patch_routed(event, todo)

    def row_written(self, frag_id: tuple, event: WriteEvent,
                    planes: bool = False) -> None:
        """A fragment's whole per-row write bookkeeping in one
        acquisition: drop the fragment's own row entry (and, for a
        fragment of a BSI view, ``planes``, its plane matrices — the
        only fragments that have any, so only they pay the key scan),
        then route the event as apply_write does."""
        with self._lock:
            self._invalidate_locked(frag_id + (event.row,))
            if planes:
                self._invalidate_prefix_locked(frag_id + ("__planes__",))
            todo = self._route_locked(event)
        self._patch_routed(event, todo)

    def _route_locked(self, event: WriteEvent) -> list:
        """The bookkeeping half of a routed write (caller holds the
        lock): buffer the event for keys that are mid-build, invalidate
        what cannot be patched (PURGE, no dense entry), and return
        ``(key, entry, apply, entry.arr)`` for each dense entry to
        patch. Only the probes' cheap part runs here (slot lookup and
        match); the closures they return do their numpy and device work
        when _patch_routed applies them."""
        self.write_events += 1
        tag = (event.scope, event.index, event.field)
        todo = []
        for key in list(self._tag_index.get(tag, ())):
            reg = self._updaters.get(key)
            if reg is None:
                continue
            pending = self._pending_builds.get(key)
            if pending is not None:
                # key is mid-build: its decode may or may not see this
                # write — buffer it for replay after the upload
                pending.append(event)
                continue
            apply = reg[1](event)
            if apply is None:
                continue  # unaffected (different row/view/shard)
            entry = None if apply is PURGE else self._rows.get(key)
            if entry is None:
                self._invalidate_locked(key)
            else:
                todo.append((key, entry, apply, entry.arr))
        return todo

    def _patch_routed(self, event: WriteEvent, todo: list) -> None:
        """Dispatch each patch on the array its entry held when the
        write was routed, with the lock free, then take the lock once
        to swap the results in. An entry whose array is no longer the
        one patched (another writer swapped the same leaf first) is
        patched again on the array it holds now: every patch is an
        idempotent delta or a set-to-current-truth of its own shard
        slot, so racing patches of one leaf commute. An entry that left
        the dense tier meanwhile (evicted, invalidated, demoted) is
        never put back: the key is invalidated like any copy that
        cannot be patched, or, if a build of it has started, the event
        joins that build's buffer. Device arrays are immutable, so a
        reader holding the old array keeps a consistent snapshot; the
        swap is done when this returns, so before the write is
        acknowledged."""
        while todo:
            try:
                patched = []
                for _key, _entry, apply, seen in todo:
                    with stage("residency.patch"):
                        patched.append(apply(seen))
            except BaseException:
                # the fragment already holds the write: a leaf that
                # could not be patched must not outlive it
                with self._lock:
                    for key, *_ in todo:
                        self._invalidate_locked(key)
                raise
            retry = []
            with self._lock:
                for (key, entry, apply, seen), new in zip(todo, patched):
                    if self._rows.get(key) is not entry:
                        pending = self._pending_builds.get(key)
                        if pending is not None:
                            pending.append(event)
                        else:
                            self._invalidate_locked(key)
                    elif entry.arr is not seen:
                        self.patch_retries += 1
                        retry.append((key, entry, apply, entry.arr))
                    else:
                        entry.arr = new
                        # occupancy may have changed; don't demote later
                        entry.block_idx = None
                        self.updates += 1
                        self._bump_generation()
            todo = retry

# ---------------------------------------------------- host tier (tiering)

    def demote_fragment_to_host(self, scope: str, index: str, field: str,
                                shard: int) -> tuple[int, int]:
        """Host-demote every per-fragment entry of one (scope, index,
        field, shard) — the ResidencyTierer's cold verdict. Returns
        (entries moved, device bytes freed). A reader between tiers
        re-decodes from the roaring file (the miss path): old-resident
        or new-resident, never absent — the scrub read-repair swap
        discipline."""
        with self._lock:
            return self._demote_matching_locked(
                lambda k: self._frag_match(k, scope, index, field, shard))

    def demote_field_stacks_to_host(self, scope: str, index: str,
                                    field: str) -> tuple[int, int]:
        """Host-demote the batched executor's stacked leaves of one
        field (a leaf spans a whole shard block, so stacks tier at
        field granularity — the tiering pass uses the field's MAX shard
        heat). Updaters stay registered: a write routed to a host-tier
        leaf invalidates it (apply_write's missing-dense branch),
        exactly like compressed-tier copies."""
        with self._lock:
            return self._demote_matching_locked(
                lambda k: self._stack_match(k, scope, index, field))

    @staticmethod
    def _frag_match(key: tuple, scope, index, field, shard) -> bool:
        # frag_id + (row,) / frag_id + ("__planes__", depth):
        # (scope, index, field, view, shard, ...) — never a stack key
        # (those lead with a "stack*" tag, not the holder scope)
        return (len(key) >= 6 and key[0] == scope and key[1] == index
                and key[2] == field and isinstance(key[4], int)
                and key[4] == shard
                and not (isinstance(key[0], str)
                         and key[0].startswith("stack")))

    @staticmethod
    def _stack_match(key: tuple, scope, index, field) -> bool:
        # ("stack"/"stackp", scope, index, field, ...); "stackm"
        # (mesh-sharded) and "stackz" (the shared zero leaf) never tier
        return (len(key) >= 4 and key[0] in ("stack", "stackp")
                and key[1] == scope and key[2] == index
                and key[3] == field)

    def _demote_matching_locked(self, match) -> tuple[int, int]:
        moved = 0
        freed = 0
        for key in [k for k, e in self._rows.items()
                    if not e.custom and match(k)]:
            entry = self._rows.pop(key)
            charge = chip_bytes(entry.arr)
            self._bytes -= charge
            freed += charge
            self._bump_generation()
            host = np.asarray(entry.arr).reshape(-1)
            block_idx = entry.block_idx
            if block_idx is None:
                # write-patched entries lost their block index;
                # recompute from the host copy (occupancy may have
                # changed either way)
                block_idx = self._host_block_index(
                    host.reshape(entry.arr.shape))
            self._host_insert_locked(key, host, entry.arr.shape,
                                     block_idx)
            moved += 1
        for key in [k for k in self._compressed if match(k)]:
            centry = self._compressed.pop(key)
            self._compressed_bytes -= centry.nbytes
            freed += centry.nbytes
            self._bump_generation()
            hentry = _HostEntry(
                np.asarray(centry.blocks), np.asarray(centry.idx),
                centry.shape, centry.n_blocks, centry.block_idx,
            )
            self._host[key] = hentry
            self._host_bytes += hentry.nbytes
            moved += 1
        if moved:
            self.tier_demotions += moved
            self._evict_host_locked()
        return moved, freed

    def _host_insert_locked(self, key: tuple, flat_host: np.ndarray,
                            shape, block_idx) -> None:
        if block_idx is not None and len(block_idx):
            nb = len(block_idx)
            nb_padded = next_pow2(nb)
            idx_host = np.full(nb_padded, block_idx[0], np.int32)
            idx_host[:nb] = block_idx
            blocks = flat_host.reshape(
                -1, COMPRESS_BLOCK_WORDS)[idx_host].copy()
            hentry = _HostEntry(
                blocks, idx_host, shape,
                flat_host.size // COMPRESS_BLOCK_WORDS, block_idx,
            )
        else:
            # incompressible (dense occupancy / odd shape) or all-zero:
            # park the full flat copy — host RAM is the cheap tier
            hentry = _HostEntry(flat_host.copy(), None, shape, 0,
                                block_idx)
        self._host[key] = hentry
        self._host_bytes += hentry.nbytes

    def _upload_host_entry(self, hentry: _HostEntry):
        """Host → device for one host-tier entry: upload the compact
        blocks and scatter them back to the dense shape (or upload the
        full array when incompressible). Billed to the active request
        as upload bytes, like any residency miss."""
        if hentry.idx is not None:
            blocks = jax.device_put(hentry.blocks, self.device)
            idx = jax.device_put(hentry.idx, self.device)
            flat = _scatter_blocks(blocks, idx, hentry.n_blocks,
                                   COMPRESS_BLOCK_WORDS)
            arr = flat.reshape(hentry.shape)
        else:
            arr = jax.device_put(
                hentry.blocks.reshape(hentry.shape), self.device)
        cost = current_cost()
        if cost is not None:
            cost.note_upload(int(arr.nbytes))
        return arr

    def promote_key(self, key: tuple) -> int:
        """Tiering-pass promotion of one host-tier entry back to dense
        residency; returns the host bytes freed, 0 when the key is no
        longer host-resident (a query's lookup promoted it first — the
        pacer sleeps OUTSIDE the lock, so this race is expected)."""
        with self._lock:
            hentry = self._host.pop(key, None)
            if hentry is None:
                return 0
            self._host_bytes -= hentry.nbytes
            self.tier_promotions += 1
            arr = self._upload_host_entry(hentry)
            self._insert_dense(key, arr, hentry.block_idx)
            return int(hentry.nbytes)

    def host_keys_of(self, scope: str, index: str, field: str,
                     shard: int) -> list:
        """(key, nbytes) of the host-tier entries of one fragment —
        the tiering pass promotes them outside the lock (paced)."""
        with self._lock:
            return [(k, e.nbytes) for k, e in self._host.items()
                    if self._frag_match(k, scope, index, field, shard)]

    def host_stack_keys_of(self, scope: str, index: str,
                           field: str) -> list:
        with self._lock:
            return [(k, e.nbytes) for k, e in self._host.items()
                    if self._stack_match(k, scope, index, field)]

    def _evict_host_locked(self) -> None:
        # LRU within the host tier's own budget; no generation bump
        # (snapshots only ever hold device arrays)
        while self._host_bytes > self.host_budget_bytes and self._host:
            key, hentry = self._host.popitem(last=False)
            self._host_bytes -= hentry.nbytes
            self.evictions += 1
            self._drop_updater(key)

    def tier_overlay(self) -> tuple[dict, dict]:
        """The tiering manager's world view and the
        ``/debug/heatmap?tier=true`` column source:
        ``(per_fragment, per_field_stacks)`` — bytes by tier keyed
        (scope, index, field, shard) for per-fragment row/plane entries
        and (scope, index, field) for the batched executor's stacked
        leaves (a leaf spans a whole shard block). Mesh-sharded and
        zero leaves are excluded (never tiered)."""
        with self._lock:
            stores = (("dense", self._rows,
                       lambda e: 0 if e.custom else chip_bytes(e.arr)),
                      ("compressed", self._compressed,
                       lambda e: e.nbytes),
                      ("host", self._host, lambda e: e.nbytes))
            per_frag: dict[tuple, dict] = {}
            per_stack: dict[tuple, dict] = {}
            for tier, store, size in stores:
                for key, entry in store.items():
                    nbytes = int(size(entry))
                    if nbytes == 0 and tier == "dense":
                        continue  # custom placement: not tierable
                    tag = key[0]
                    if isinstance(tag, str) and tag.startswith("stack"):
                        # the stack test runs FIRST (residency_overlay's
                        # order): a plane-stack key ("stackp", scope,
                        # index, field, 2+depth, pad, block) has an int
                        # at [4] and would otherwise masquerade as a
                        # fragment entry under a bogus key with heat 0 —
                        # demoted every pass no matter how hot the field
                        if tag not in ("stack", "stackp") or len(key) < 4:
                            continue  # stackm (mesh) / stackz: not tiered
                        out, okey = per_stack, (key[1], key[2], key[3])
                    elif len(key) >= 6 and isinstance(key[4], int):
                        out, okey = per_frag, (key[0], key[1], key[2],
                                               key[4])
                    else:
                        continue
                    slot = out.get(okey)
                    if slot is None:
                        slot = out[okey] = {"dense": 0, "compressed": 0,
                                            "host": 0}
                    slot[tier] += nbytes
        return per_frag, per_stack

    def residency_overlay(self) -> tuple[dict, dict]:
        """HBM residency bucketed for the heat map (/debug/heatmap):
        ``(per_fragment, per_field)`` — exact bytes per (scope, index,
        field, shard) for per-fragment row/plane entries, and (scope,
        index, field) totals for the batched executor's stacked leaves
        (one stacked array spans a whole shard block, so its bytes
        cannot honestly be attributed to a single shard). Scope leads
        (the holder tag, as in frag_id/leaf_key) so in-process
        multi-holder setups never conflate replicas. Key shapes are
        pinned by executor/batch.leaf_key and Fragment.frag_id."""
        with self._lock:
            items = [(k, chip_bytes(e.arr)) for k, e in self._rows.items()]
            items += [(k, e.nbytes) for k, e in self._compressed.items()]
        per_frag: dict[tuple, int] = {}
        per_field: dict[tuple, int] = {}
        for key, nbytes in items:
            tag = key[0]
            if isinstance(tag, str) and tag.startswith("stack"):
                # ("stack"/"stackp", scope, index, field, ...) and
                # ("stackm", scope, index, field, view, ...); "stackz"
                # (the shared zero leaf) belongs to nobody
                if len(key) >= 4 and tag != "stackz":
                    fkey = (key[1], key[2], key[3])
                    per_field[fkey] = per_field.get(fkey, 0) + int(nbytes)
                continue
            if len(key) >= 6 and isinstance(key[4], int):
                # frag_id + (row,) / frag_id + ("__planes__", depth):
                # (scope, index, field, view, shard, ...)
                fkey = (key[0], key[1], key[2], key[4])
                per_frag[fkey] = per_frag.get(fkey, 0) + int(nbytes)
        return per_frag, per_field

    # metrics() keys that are monotonic counters (get the Prometheus
    # _total suffix); the rest are point-in-time gauges
    _MONOTONIC_METRICS = frozenset({
        "residency_hits", "residency_misses", "residency_evictions",
        "residency_compressions", "residency_decompressions",
        "residency_miss_bytes", "residency_miss_transfer_bytes",
        "residency_sparse_misses",
        "residency_updates", "residency_patch_retries",
        "residency_write_events",
        "residency_host_hits", "residency_tier_promotions",
        "residency_tier_demotions",
    })

    def device_bytes(self) -> dict[str, int]:
        """Resident bytes of each chip, keyed by device id: every dense
        entry's shard on each device that holds a piece of it, and the
        compressed tier on its one device. An uneven mesh shows here;
        ``residency_bytes_used`` is the figure the budget is held to
        (the sum of the entries' charges, never less than the fullest
        chip's bytes here)."""
        out: dict[str, int] = {}
        with self._lock:
            arrays = [e.arr for e in self._rows.values()]
            for c in self._compressed.values():
                arrays += (c.blocks, c.idx)
        for arr in arrays:
            n = chip_bytes(arr)
            for d in arr.sharding.addressable_devices:
                out[str(d.id)] = out.get(str(d.id), 0) + n
        return out

    def metrics(self) -> dict:
        """Operational gauges/counters for /metrics and /debug/vars (the
        HBM LRU is the system's central capacity mechanism — reference
        analog: syswrap's mmap-count limits, SURVEY.md §2 #26).
        ``residency_bytes_used`` and ``residency_budget_bytes`` are per
        chip (chip_bytes); device_bytes() has each chip's own figure."""
        with self._lock:
            return {
                "residency_entries": len(self._rows) + len(self._compressed),
                "residency_entries_compressed": len(self._compressed),
                "residency_bytes_used": self.bytes_used,
                "residency_bytes_compressed": self._compressed_bytes,
                "residency_budget_bytes": self.budget_bytes,
                "residency_hits": self.hits,
                "residency_misses": self.misses,
                "residency_evictions": self.evictions,
                "residency_compressions": self.compressions,
                "residency_decompressions": self.decompressions,
                "residency_miss_bytes": self.miss_bytes,
                "residency_miss_transfer_bytes": self.miss_transfer_bytes,
                "residency_sparse_misses": self.sparse_misses,
                "residency_updates": self.updates,
                "residency_patch_retries": self.patch_retries,
                "residency_write_events": self.write_events,
                "residency_entries_host": len(self._host),
                "residency_bytes_host": self._host_bytes,
                "residency_host_budget_bytes": self.host_budget_bytes,
                "residency_host_hits": self.host_hits,
                "residency_tier_promotions": self.tier_promotions,
                "residency_tier_demotions": self.tier_demotions,
            }

    def prometheus_lines(self, prefix: str = "pilosa_tpu",
                         seen: set | None = None) -> str:
        """metrics() in Prometheus text form, following the stats
        registry's conventions (one render shared by every consumer):
        counters carry the _total suffix; values are ints emitted
        exactly (no %g truncation of byte gauges or large counters).
        Each family leads with # HELP/# TYPE so a stock Prometheus
        scrape ingests the block (docs/OBSERVABILITY.md); ``seen``
        shares the page-wide family-metadata dedupe. One renderer for
        the whole exposition page — stats.prometheus_block."""
        from pilosa_tpu.utils.stats import _meta_lines, prometheus_block

        seen = seen if seen is not None else set()
        text = prometheus_block(
            {
                (f"{name}_total" if name in self._MONOTONIC_METRICS
                 else name): v
                for name, v in self.metrics().items()
            },
            prefix, seen=seen,
        )
        family = f"{prefix}_residency_device_bytes"
        lines = _meta_lines(
            family, "gauge",
            "resident bytes on each chip (residency_bytes_used is the "
            "per-chip figure the budget is held to)", seen)
        lines += [f'{family}{{device="{d}"}} {n}'
                  for d, n in sorted(self.device_bytes().items())]
        return text + "\n".join(lines) + "\n"

    def clear(self) -> None:
        with self._lock:
            self._bump_generation()
            self._rows.clear()
            self._compressed.clear()
            self._host.clear()
            self._updaters.clear()
            self._tag_index.clear()
            self._bytes = 0
            self._compressed_bytes = 0
            self._host_bytes = 0

    def _evict(self) -> None:
        # Demotion only under real pressure: the dense tier may use the
        # whole budget while it fits (a fully-resident working set stays
        # fully resident, as in the single-tier cache). Over budget, LRU
        # dense entries demote (compressible — shrinks usage) or drop;
        # then LRU compressed entries drop.
        while self.bytes_used > self.budget_bytes and len(self._rows) > 1:
            key, entry = self._rows.popitem(last=False)
            self._bytes -= chip_bytes(entry.arr)
            self._bump_generation()
            if entry.block_idx is not None:
                self._demote(key, entry)  # key stays resident (compressed)
            else:
                self.evictions += 1
                self._drop_updater(key)
        while self.bytes_used > self.budget_bytes and self._compressed:
            key, centry = self._compressed.popitem(last=False)
            self._compressed_bytes -= centry.nbytes
            self._bump_generation()
            self.evictions += 1
            self._drop_updater(key)

    def _demote(self, key: tuple, entry: _DenseEntry) -> None:
        """Dense → compressed: gather nonzero blocks on device."""
        nb = len(entry.block_idx)
        nb_padded = next_pow2(nb)
        # pad by repeating a real index: scatter rewrites identical data
        idx_host = np.full(nb_padded, entry.block_idx[0] if nb else 0,
                           np.int32)
        idx_host[:nb] = entry.block_idx
        idx = jax.device_put(idx_host, self.device)
        flat = entry.arr.reshape(-1)
        blocks = _gather_blocks(flat, idx, COMPRESS_BLOCK_WORDS)
        centry = _CompressedEntry(
            blocks, idx, entry.arr.shape,
            flat.shape[0] // COMPRESS_BLOCK_WORDS, entry.block_idx,
        )
        self._compressed[key] = centry
        self._compressed_bytes += centry.nbytes
        self.compressions += 1


_global_cache: DeviceRowCache | None = None


def global_row_cache() -> DeviceRowCache:
    global _global_cache
    if _global_cache is None:
        _global_cache = DeviceRowCache()
    return _global_cache


def set_global_row_cache(cache: DeviceRowCache) -> None:
    global _global_cache
    _global_cache = cache
