"""Vectorized whole-fragment roaring kernels (host path).

Every host-side roaring consumer used to walk containers in
per-container Python/numpy loops: one ``lows()`` / ``dense_words32()``
/ ``tobytes()`` dispatch per 65536-bit container, so a populated
fragment (hundreds to thousands of containers) paid hundreds of numpy
dispatches where the actual bit work was microseconds. This module is
the batched replacement, after Lemire's vectorized popcount blueprint
(arXiv:1611.07612) and the roaring container design itself
(arXiv:1709.07821): concatenate the fragment's container payloads into
flat arrays with offset tables ONCE (:func:`flatten` — the single
sanctioned per-container metadata loop), then do id materialization,
dense decode, popcount (``np.bitwise_count``), AND/OR/XOR/ANDNOT,
digest feeding, and manifest diffing as single whole-fragment numpy
kernels — one dispatch per *fragment*, not per *container*.

Contract: every kernel is **byte-identical** to the per-container
reference path it replaces (tests/test_roaring_kernels.py pins this
property over randomized array/bitmap/run mixes). Set ops use a
galloping (searchsorted) intersect when the operand sizes are lopsided
and a linear merge otherwise; bitmap containers are only materialized
to ids where the kind combination forces it (bitmap×bitmap stays in
word space).

Consumers (enforced by scripts/check_hostpath_loops.py): fragment row
decode + block digests (storage/fragment.py), verified loads and the
scrubber (storage/integrity.py, parallel/scrub.py), the anti-entropy
sync manifest diffs (parallel/cluster.py, server block serving), and
the CDC bulk-sync path (cdc/tailer.py).
"""

from __future__ import annotations

import bisect
import struct
import threading

import numpy as np

from pilosa_tpu.roaring.bitmap import (
    ARRAY, BITMAP, RUN, BITMAP_N_WORDS, ContainerDirectory,
)
from pilosa_tpu.shardwidth import SHARD_WIDTH

_U16 = np.uint64(16)
_EMPTY_IDS = np.empty(0, np.uint64)
_EMPTY_IDS.setflags(write=False)


# ------------------------------------------------------------- statistics


class KernelStats:
    """Process-wide host-path kernel counters (``hostpath_*`` series on
    /metrics). Plain int adds, no lock: these feed dashboards, not
    correctness invariants, and the hot paths must not pay a lock."""

    __slots__ = ("kernel_calls", "containers_flattened", "ids_materialized",
                 "dense_decodes", "set_ops", "directory_windows",
                 "walked_windows")

    def __init__(self):
        self.kernel_calls = 0
        self.containers_flattened = 0
        self.ids_materialized = 0
        self.dense_decodes = 0
        self.set_ops = 0
        # fragment windows of row-leaf gathers (flatten_rows): read from
        # a ContainerDirectory, or walked container by container
        self.directory_windows = 0
        self.walked_windows = 0

    def metrics(self) -> dict:
        return {
            "hostpath_kernel_calls_total": self.kernel_calls,
            "hostpath_containers_flattened_total": self.containers_flattened,
            "hostpath_ids_materialized_total": self.ids_materialized,
            "hostpath_dense_decodes_total": self.dense_decodes,
            "hostpath_set_ops_total": self.set_ops,
            "hostpath_directory_windows_total": self.directory_windows,
            "hostpath_walked_windows_total": self.walked_windows,
        }


_STATS = KernelStats()


def global_kernel_stats() -> KernelStats:
    return _STATS


# --------------------------------------------------------------- flatten


class FlatFragment:
    """A fragment's containers concatenated into flat per-kind arrays.

    ``keys``/``kinds``/``cards`` are parallel per-container metadata in
    ascending key order; ``kind_row[i]`` is container *i*'s row within
    its kind's concatenation. Array payloads concatenate into
    ``arr_data`` with ``arr_off`` offsets; bitmap words stack into
    ``bmp_words`` (n, 1024) uint64; run intervals concatenate into
    ``run_data`` (R, 2) int64 with ``run_off`` run-count offsets.
    Containers are immutable once published (bitmap.py swaps whole
    containers atomically), so a flat view taken lock-free is a
    consistent snapshot of every container it captured.

    ``bmp_words`` is stacked when first read: a view built from
    containers keeps their word arrays by reference in ``bmp_parts``,
    and the consumers that never read the stack (metadata folds, the
    row-leaf decode, which copies each container's words straight to
    its place) never pay the copy.
    """

    __slots__ = ("keys", "kinds", "cards", "kind_row",
                 "arr_sel", "arr_data", "arr_off",
                 "bmp_sel", "bmp_parts", "_bmp_words",
                 "run_sel", "run_data", "run_off")

    @property
    def bmp_words(self) -> np.ndarray:
        w = self._bmp_words
        if w is None:
            w = self._bmp_words = (
                np.stack(self.bmp_parts) if self.bmp_parts
                else np.empty((0, BITMAP_N_WORDS), np.uint64))
        return w

    @bmp_words.setter
    def bmp_words(self, words: np.ndarray) -> None:
        self._bmp_words = words
        self.bmp_parts = None

    @property
    def n_containers(self) -> int:
        return int(self.keys.size)

    def total(self) -> int:
        return int(self.cards.sum()) if self.cards.size else 0

    def kind_counts(self) -> tuple[int, int, int]:
        """(array, bitmap, run) container counts — the PROFILE
        container-scan tally, one call per kernel invocation."""
        c = np.bincount(self.kinds, minlength=4)
        return int(c[ARRAY]), int(c[BITMAP]), int(c[RUN])


def _build_flat(keys: list, conts: list) -> FlatFragment:
    """Assemble a FlatFragment from parallel lists of keys (ascending)
    and their Containers. With its callers' gathers, THE one sanctioned
    per-container walk on the host path: references and metadata only —
    every bit touch happens in the batched kernels below."""
    f = FlatFragment()
    n = len(keys)
    f.keys = np.asarray(keys, np.int64)
    f.kinds = kinds = np.asarray([c.kind for c in conts], np.uint8)
    f.cards = np.asarray([c.n for c in conts], np.int64)
    f.kind_row = kind_row = np.empty(n, np.int64)
    parts = {}
    for kind in (ARRAY, BITMAP, RUN):
        sel = np.flatnonzero(kinds == kind)
        kind_row[sel] = np.arange(sel.size)
        parts[kind] = sel, ([c.data for c in conts] if sel.size == n
                            else [conts[i].data for i in sel.tolist()])
    f.arr_sel, arr_parts = parts[ARRAY]
    f.arr_data = (np.concatenate(arr_parts) if arr_parts
                  else np.empty(0, np.uint16))
    f.arr_off = np.concatenate(
        ([0], np.cumsum([p.size for p in arr_parts], dtype=np.int64)))
    f.bmp_sel, f.bmp_parts = parts[BITMAP]
    f._bmp_words = None
    f.run_sel, run_parts = parts[RUN]
    f.run_data = (np.concatenate(run_parts).astype(np.int64).reshape(-1, 2)
                  if run_parts else np.empty((0, 2), np.int64))
    f.run_off = np.concatenate(
        ([0], np.cumsum([p.shape[0] for p in run_parts], dtype=np.int64)))
    _STATS.containers_flattened += n
    return f


def _collect(window: list, containers: dict, shift: int,
             kept: list, conts: list) -> None:
    """Append the live containers of the keys in ``window`` (a slice of
    a bitmap's key list) and their keys plus ``shift``. Lock-free
    against concurrent writers under the same discipline as ``to_ids``:
    ``.get`` + skip, empty containers skipped (they contribute nothing
    and the per-container tally never counted them)."""
    for key in window:
        c = containers.get(key)
        if c is not None and c.n:
            kept.append(key + shift)
            conts.append(c)


def flatten(bitmap, lo_key: int | None = None,
            hi_key: int | None = None) -> FlatFragment:
    """Flatten a RoaringBitmap's containers with keys in
    [lo_key, hi_key] (inclusive; None = unbounded), lock-free
    (:func:`_collect`)."""
    keys = bitmap.keys
    lo_i = 0 if lo_key is None else bisect.bisect_left(keys, lo_key)
    hi_i = len(keys) if hi_key is None else bisect.bisect_right(keys, hi_key)
    kept, conts = [], []
    _collect(keys[lo_i:hi_i], bitmap._containers, 0, kept, conts)
    return _build_flat(kept, conts)


# A row of one shard spans 16 consecutive containers (2^20 columns of
# 2^16), so a stack of rows keys its containers ``slot * 16 + k``.
ROW_KEYS = SHARD_WIDTH >> 16


def _collect_row(bitmap, base_key: int, shift: int, kept: list,
                 conts: list) -> None:
    """:func:`_collect` over the row window that starts at ``base_key``,
    found by bisection."""
    keys = bitmap.keys
    lo_i = bisect.bisect_left(keys, base_key)
    hi_i = bisect.bisect_left(keys, base_key + ROW_KEYS, lo_i)
    _collect(keys[lo_i:hi_i], bitmap._containers, shift, kept, conts)


def flatten_rows(bitmaps, row: int) -> FlatFragment:
    """Flatten row ``row`` of many fragments into ONE view: ``bitmaps``
    is (slot, RoaringBitmap) pairs, and container ``row * 16 + k`` of a
    slot's bitmap takes the key ``slot * 16 + k``. Keys ascend; a slot
    named more than once (a leaf that ORs several views) repeats its
    keys, which :func:`dense_rows32` ORs. A leaf of array containers
    whose slots ascend is sliced from the bitmaps' directories
    (:func:`_row_windows`); any other is walked: each bitmap's window
    found by bisection, lock-free as :func:`flatten` is."""
    bitmaps = list(bitmaps)
    flat = _row_windows(bitmaps, row)
    if flat is not None:
        return flat
    _STATS.walked_windows += len(bitmaps)
    base_key = row * ROW_KEYS
    kept, conts = [], []
    last_slot, ascending = -1, True
    for slot, bitmap in bitmaps:
        ascending &= slot > last_slot
        last_slot = slot
        _collect_row(bitmap, base_key, slot * ROW_KEYS - base_key, kept,
                     conts)
    if not ascending:
        order = sorted(range(len(kept)), key=kept.__getitem__)
        kept = [kept[i] for i in order]
        conts = [conts[i] for i in order]
    return _build_flat(kept, conts)


def _flat_of_arrays(keys, cards, arr_off, arr_data) -> FlatFragment:
    """The FlatFragment of array containers alone."""
    f = FlatFragment()
    n = keys.size
    f.keys, f.cards, f.arr_off, f.arr_data = keys, cards, arr_off, arr_data
    f.kinds = np.full(n, ARRAY, np.uint8)
    f.kind_row = np.arange(n)
    f.arr_sel = np.arange(n)
    f.bmp_sel = f.run_sel = np.empty(0, np.int64)
    f.bmp_parts, f._bmp_words = [], None
    f.run_data = np.empty((0, 2), np.int64)
    f.run_off = np.zeros(1, np.int64)
    _STATS.containers_flattened += n
    return f


def _row_windows(bitmaps: list, row: int) -> FlatFragment | None:
    """:func:`flatten_rows` of a leaf whose containers are all arrays
    and whose slots ascend, element for element, or None for any other
    leaf (and for one no bitmap of which has a ContainerDirectory). A
    bitmap with a directory gives its row window as slices of the
    directory's arrays: two positions by ``searchsorted``, then the
    keys, the cardinalities and ONE contiguous stretch of the
    snapshot's payload (a row is 16 consecutive keys and a snapshot's
    payloads lie in key order), with no Container touched. A bitmap
    without one (written since its snapshot) is walked as ever. A leaf
    that comes again with the same directories, all of its bitmaps',
    is read from their stack (:class:`_DirectoryStack`): one
    ``searchsorted`` for every fragment's window. A reader that took a
    directory before a write dropped it holds the older snapshot whole,
    which the lock-free walk may return too."""
    dirs = [bitmap.directory for _, bitmap in bitmaps]
    n_walked = dirs.count(None)
    if n_walked == len(dirs):
        return None
    slots = [slot for slot, _ in bitmaps]
    if any(a >= b for a, b in zip(slots, slots[1:])):
        return None  # several views of a slot: keys repeat
    base_key = row * ROW_KEYS
    stack = _leaf_stack(dirs) if not n_walked else None
    if stack is not None:
        flat = stack.row_windows(slots, base_key)
    else:
        flat = _sliced_windows(bitmaps, dirs, base_key)
    if flat is not None:
        _STATS.walked_windows += n_walked
        _STATS.directory_windows += len(dirs) - n_walked
    return flat


def _sliced_windows(bitmaps: list, dirs: list,
                    base_key: int) -> FlatFragment | None:
    """:func:`_row_windows` a fragment at a time: ``dirs[i]`` is the
    directory of ``bitmaps[i]``'s bitmap or None."""
    probe = np.asarray((base_key, base_key + ROW_KEYS))
    # a piece a non-empty window: its keys, cardinalities and payload
    # starts (a slice a container), its payload, and what takes the keys
    # to the leaf's and the starts to the leaf's payload
    keys, cards, starts, data, shifts, firsts, lasts = ([], [], [], [], [],
                                                        [], [])
    for (slot, bitmap), d in zip(bitmaps, dirs):
        if d is None:
            kept, conts = [], []
            _collect_row(bitmap, base_key, 0, kept, conts)
            if not kept:
                continue
            if any(c.kind != ARRAY for c in conts):
                return None
            sizes = [c.data.size for c in conts]
            keys.append(np.asarray(kept, np.int64))
            cards.append(np.asarray([c.n for c in conts], np.int32))
            starts.append(np.cumsum([0, *sizes[:-1]]))
            data.append(np.concatenate([c.data for c in conts]))
            firsts.append(0)
            lasts.append(sum(sizes))
        else:
            lo, hi = d.keys.searchsorted(probe).tolist()
            if lo == hi:
                continue
            if not d.all_arrays and bool((d.kinds[lo:hi] != ARRAY).any()):
                return None
            first, last = int(d.starts[lo]), int(d.starts[hi])
            keys.append(d.keys[lo:hi])
            cards.append(d.cards[lo:hi])
            starts.append(d.starts[lo:hi])
            data.append(d.payload[first:last])
            firsts.append(first)
            lasts.append(last)
        shifts.append(slot * ROW_KEYS - base_key)
    if not keys:
        return _flat_of_arrays(np.empty(0, np.int64), np.empty(0, np.int64),
                               np.zeros(1, np.int64), np.empty(0, np.uint16))
    counts = [k.size for k in keys]
    firsts = np.asarray(firsts)
    lens = np.asarray(lasts) - firsts
    before = np.cumsum(lens) - lens  # the leaf's payload before a piece
    return _flat_of_arrays(
        np.concatenate(keys) + np.repeat(shifts, counts),
        np.concatenate(cards).astype(np.int64),
        np.append(np.concatenate(starts) - np.repeat(firsts - before, counts),
                  before[-1] + lens[-1]),
        np.concatenate(data))


# A stack shifts fragment i's container keys (under 2^48: a 64-bit id's
# high 48 bits) by i << 48, so a leaf may stack this many fragments.
_STACK_SHIFT = 48
_STACK_MAX_FRAGMENTS = 1 << 14
# Stacks and candidates kept, by the id of their first directory (which
# they keep alive). A stack repeats its directories' arrays, 25 bytes a
# container: the leaves that miss steadily are few.
_STACKS_KEPT = 4
_stacks: dict[int, "_DirectoryStack | list"] = {}
_stacks_lock = threading.Lock()


class _DirectoryStack:
    """The ContainerDirectories of a leaf's fragments end to end, for
    the leaf's row windows in a fixed number of numpy calls: fragment
    ``i``'s keys are shifted by ``i << 48``, so the stacked keys ascend
    and ONE ``searchsorted`` finds every fragment's window; keys,
    cardinalities and payload sizes are then gathered by one index, and
    only the payload is sliced a fragment (each from its own snapshot's
    bytes). Valid for exactly the directories it was made from
    (``dirs``, compared by identity): a write drops a fragment's
    directory and the leaf is then sliced a fragment at a time."""

    __slots__ = ("dirs", "bases", "keys", "kinds", "cards", "starts",
                 "sizes", "payloads", "all_arrays")

    def __init__(self, dirs: list):
        self.dirs = dirs
        self.bases = np.arange(len(dirs), dtype=np.int64) << _STACK_SHIFT
        self.keys = np.concatenate(
            [d.keys + b for d, b in zip(dirs, self.bases.tolist())])
        self.kinds = np.concatenate([d.kinds for d in dirs])
        self.cards = np.concatenate([d.cards for d in dirs])
        self.starts = np.concatenate([d.starts[:-1] for d in dirs])
        self.sizes = np.concatenate(
            [np.diff(d.starts) for d in dirs]).astype(np.int32)
        self.payloads = [d.payload for d in dirs]
        self.all_arrays = all(d.all_arrays for d in dirs)

    def row_windows(self, slots: list, base_key: int) -> FlatFragment | None:
        """:func:`_row_windows` of the row that starts at ``base_key``,
        ``slots[i]`` being the slot of fragment ``i``."""
        edges = self.keys.searchsorted(np.concatenate(
            (self.bases + base_key, self.bases + (base_key + ROW_KEYS))))
        lo, hi = edges[:len(slots)], edges[len(slots):]
        counts = hi - lo
        n = int(counts.sum())
        # the stacked position of every container of the windows
        at = np.arange(n) + np.repeat(lo - (np.cumsum(counts) - counts),
                                      counts)
        if not self.all_arrays and bool((self.kinds[at] != ARRAY).any()):
            return None
        sizes = self.sizes[at]
        held = np.flatnonzero(counts)
        firsts = self.starts[lo[held]]
        lasts = self.starts[hi[held] - 1] + self.sizes[hi[held] - 1]
        payloads = self.payloads
        data = [payloads[i][a:b] for i, a, b in zip(
            held.tolist(), firsts.tolist(), lasts.tolist())]
        return _flat_of_arrays(
            self.keys[at] - np.repeat(
                self.bases + base_key - np.asarray(slots) * ROW_KEYS, counts),
            self.cards[at].astype(np.int64),
            np.concatenate(([0], np.cumsum(sizes, dtype=np.int64))),
            np.concatenate(data) if data else np.empty(0, np.uint16))


def _leaf_stack(dirs: list) -> _DirectoryStack | None:
    """The stack of exactly ``dirs`` (none None), made when they come the
    second time: a leaf seen once is remembered and sliced a fragment at
    a time, so leaves that do not come again (or more of them than are
    kept) never pay for a stack."""
    key = id(dirs[0])
    seen = _stacks.get(key)
    if type(seen) is _DirectoryStack:
        if seen.dirs == dirs:
            return seen
        seen = None
    if len(dirs) > _STACK_MAX_FRAGMENTS:
        return None
    stack = _DirectoryStack(dirs) if seen == dirs else None
    with _stacks_lock:
        _stacks.pop(key, None)
        while len(_stacks) >= _STACKS_KEPT:
            del _stacks[next(iter(_stacks))]
        _stacks[key] = stack or dirs
    return stack


def _take(f: FlatFragment, idx: np.ndarray) -> FlatFragment:
    """Sub-flatten: the containers at positions ``idx`` (ascending), as
    a new FlatFragment — pure array gathers, no per-container work."""
    arr_pick = idx[f.kinds[idx] == ARRAY]
    bmp_pick = idx[f.kinds[idx] == BITMAP]
    run_pick = idx[f.kinds[idx] == RUN]
    out = FlatFragment()
    out.keys = f.keys[idx]
    out.kinds = f.kinds[idx]
    out.cards = f.cards[idx]
    kind_row = np.empty(idx.size, np.int64)
    kind_row[f.kinds[idx] == ARRAY] = np.arange(arr_pick.size)
    kind_row[f.kinds[idx] == BITMAP] = np.arange(bmp_pick.size)
    kind_row[f.kinds[idx] == RUN] = np.arange(run_pick.size)
    out.kind_row = kind_row
    rows = f.kind_row[arr_pick]
    starts, stops = f.arr_off[rows], f.arr_off[rows + 1]
    out.arr_sel = np.nonzero(out.kinds == ARRAY)[0]
    out.arr_data = _gather_ranges(f.arr_data, starts, stops)
    out.arr_off = np.concatenate(
        ([0], np.cumsum(stops - starts))).astype(np.int64)
    out.bmp_sel = np.nonzero(out.kinds == BITMAP)[0]
    out.bmp_words = f.bmp_words[f.kind_row[bmp_pick]]
    rrows = f.kind_row[run_pick]
    rstarts, rstops = f.run_off[rrows], f.run_off[rrows + 1]
    out.run_sel = np.nonzero(out.kinds == RUN)[0]
    out.run_data = _gather_ranges(f.run_data, rstarts, rstops)
    out.run_off = np.concatenate(
        ([0], np.cumsum(rstops - rstarts))).astype(np.int64)
    return out


def _gather_ranges(data: np.ndarray, starts: np.ndarray,
                   stops: np.ndarray) -> np.ndarray:
    """``data[s0:e0] ++ data[s1:e1] ++ …`` — O(1) slice views plus one
    ``np.concatenate``, never a per-element fancy-index gather (which
    costs an index array as large as the payload)."""
    parts = [data[a:b] for a, b in zip(starts.tolist(), stops.tolist())]
    if not parts:
        return data[:0].copy()
    return np.concatenate(parts) if len(parts) > 1 else parts[0].copy()


# ------------------------------------------------------ id materialization


def _bmp_lows(f: FlatFragment) -> tuple[np.ndarray, np.ndarray]:
    """All set bit positions across the stacked bitmap words: returns
    (global bit index int64 into the (nb×65536)-bit space, counts per
    bitmap container int64). ``flatnonzero`` over a bool view is ~2×
    the uint8 scan, and searchsorted against the 65536-aligned edges
    beats a ``bincount`` over the positions by orders of magnitude."""
    nb = f.bmp_words.shape[0]
    if nb == 0:
        return np.empty(0, np.int64), np.empty(0, np.int64)
    bits = np.unpackbits(
        np.ascontiguousarray(f.bmp_words).view(np.uint8), bitorder="little"
    )
    pos = np.flatnonzero(bits.view(bool))
    edges = np.searchsorted(pos, np.arange(nb + 1, dtype=np.int64) << 16)
    return pos, np.diff(edges)


def _bmp_ids(f: FlatFragment) -> tuple[np.ndarray, np.ndarray]:
    """Global ids of every bitmap container, as one sorted uint64
    stream, plus per-container counts. The container base is folded
    into the stream-local bit index — ``id = pos + ((key - slot) <<
    16)`` — so materialization is one repeat + one add, with no
    low-16-bit mask pass."""
    pos, counts = _bmp_lows(f)
    if pos.size == 0:
        return _EMPTY_IDS, counts
    adj = ((f.keys[f.bmp_sel] - np.arange(f.bmp_sel.size))
           << np.int64(16)).tolist()
    edges = np.concatenate(([0], np.cumsum(counts))).tolist()
    # in-place scalar add per container segment: no repeat() temp the
    # size of the id stream (large temps force mmap churn on busy heaps)
    for c, a in enumerate(adj):
        if a and edges[c] != edges[c + 1]:
            pos[edges[c]:edges[c + 1]] += a
    return pos.view(np.uint64), counts


def _run_ids(f: FlatFragment) -> tuple[np.ndarray, np.ndarray]:
    """Global ids of every run container, as one sorted uint64 stream,
    plus per-container counts. Container bases are folded into the
    (few) run starts *before* expansion, so the expensive per-id work
    is a single repeat + arange over the whole stream."""
    runs = f.run_data
    n_runs = runs.shape[0]
    if n_runs == 0:
        return _EMPTY_IDS, np.zeros(f.run_sel.size, np.int64)
    lengths = np.maximum(runs[:, 1] - runs[:, 0] + 1, 0)
    per_cont = np.add.reduceat(lengths, f.run_off[:-1])
    per_cont[f.run_off[:-1] == f.run_off[1:]] = 0
    total = int(lengths.sum())
    if total == 0:
        return _EMPTY_IDS, per_cont
    runs_per_cont = f.run_off[1:] - f.run_off[:-1]
    gstarts = runs[:, 0] + np.repeat(f.keys[f.run_sel] << np.int64(16),
                                     runs_per_cont)
    keep = lengths > 0
    if not keep.all():
        gstarts, lengths = gstarts[keep], lengths[keep]
    # ones + boundary deltas + one in-place cumsum: two passes over the
    # id stream instead of the four of repeat + arange + add
    gids = np.ones(total, np.int64)
    gids[0] = gstarts[0]
    bounds = np.cumsum(lengths)[:-1]
    if bounds.size:
        gids[bounds] = gstarts[1:] - (gstarts[:-1] + lengths[:-1] - 1)
    np.cumsum(gids, out=gids)
    return gids.view(np.uint64), per_cont


def fragment_ids(f: FlatFragment) -> np.ndarray:
    """Every id in the flat fragment, globally sorted uint64 — the
    whole-fragment ``to_ids`` kernel. Byte-identical to concatenating
    ``container.lows() + (key << 16)`` over sorted keys.

    Per-container output extents come from the PAYLOADS (array sizes,
    bitmap popcounts, run lengths), never the cached cardinalities —
    the reference path materializes whatever the payload holds, and a
    corrupt-but-decodable file can carry a lying cardinality field
    (the integrity fuzz flips every byte; both paths must agree).

    Each kind's stream is already globally sorted, so a single-kind
    fragment returns its stream directly; mixed fragments interleave
    the streams with ONE view per run of consecutive same-kind
    containers (kinds cluster by row, so segments number ~rows, not
    ~containers) into one ``np.concatenate`` — measures ~2× faster
    than a destination-index scatter, with no per-container work."""
    _STATS.kernel_calls += 1
    nc = int(f.keys.size)
    if nc == 0:
        return _EMPTY_IDS
    arr_ids = _EMPTY_IDS
    arr_counts = f.arr_off[1:] - f.arr_off[:-1]
    if f.arr_data.size:
        bases = f.keys[f.arr_sel].astype(np.uint64) << _U16
        arr_ids = np.repeat(bases, arr_counts) + f.arr_data
    bmp_ids, bmp_counts = _bmp_ids(f)
    run_ids, run_counts = _run_ids(f)
    total = arr_ids.size + bmp_ids.size + run_ids.size
    if total == 0:
        return _EMPTY_IDS
    _STATS.ids_materialized += total
    if f.arr_sel.size == nc:
        return arr_ids
    if f.bmp_sel.size == nc:
        return bmp_ids
    if f.run_sel.size == nc:
        return run_ids
    arr_off = f.arr_off.tolist()
    bmp_off = np.concatenate(([0], np.cumsum(bmp_counts))).tolist()
    run_off = np.concatenate(([0], np.cumsum(run_counts))).tolist()
    kinds, rows = f.kinds.tolist(), f.kind_row.tolist()
    seg = [0, *(np.flatnonzero(np.diff(f.kinds)) + 1).tolist(), nc]
    parts = []
    for j in range(len(seg) - 1):
        s = seg[j]
        k, r0, r1 = kinds[s], rows[s], rows[seg[j + 1] - 1] + 1
        if k == ARRAY:
            parts.append(arr_ids[arr_off[r0]:arr_off[r1]])
        elif k == BITMAP:
            parts.append(bmp_ids[bmp_off[r0]:bmp_off[r1]])
        else:
            parts.append(run_ids[run_off[r0]:run_off[r1]])
    return np.concatenate(parts)


def range_ids(f: FlatFragment, start: int, stop: int) -> np.ndarray:
    """Sorted ids in [start, stop) — kernel analog of
    ``RoaringBitmap.range_ids`` over an already key-bounded flat view
    (edge containers trimmed the same way: one vectorized mask)."""
    ids = fragment_ids(f)
    if ids.size == 0:
        return ids
    return ids[(ids >= np.uint64(start)) & (ids < np.uint64(stop))]


# ------------------------------------------------------------ dense decode


def _or_runs_into(words: np.ndarray, starts: np.ndarray,
                  ends: np.ndarray) -> None:
    """OR the inclusive bit ranges [starts[i], ends[i]] into a flat
    uint64 word array, O(runs + words) — never per-bit: head/tail
    partial words via masked ``bitwise_or.at``, interior full words via
    a cumsum coverage count."""
    ok = ends >= starts
    if not ok.all():
        starts, ends = starts[ok], ends[ok]
    if starts.size == 0:
        return
    ones = np.uint64(0xFFFFFFFFFFFFFFFF)
    ws, we = starts >> 6, ends >> 6
    head = ones << (starts & 63).astype(np.uint64)
    tail = ones >> (np.uint64(63) - (ends & 63).astype(np.uint64))
    same = ws == we
    np.bitwise_or.at(words, ws, np.where(same, head & tail, head))
    cross = ~same
    if cross.any():
        np.bitwise_or.at(words, we[cross], tail[cross])
        delta = np.zeros(words.size + 1, np.int64)
        np.add.at(delta, ws[cross] + 1, 1)
        np.add.at(delta, we[cross], -1)
        words[np.cumsum(delta[:-1]) > 0] = ones


def dense_words32(f: FlatFragment, base_key: int,
                  n_containers: int) -> np.ndarray:
    """Materialize ``n_containers`` consecutive containers starting at
    ``base_key`` as packed uint32 words — the whole-row residency-miss
    decode kernel (byte-identical to per-container
    ``Container.dense_words32`` scatters). Bitmap containers copy their
    words straight across (an all-bitmap window is one memcpy); run
    intervals fill whole words via :func:`_or_runs_into` without ever
    expanding to per-bit positions; array set bits go through
    ``np.bitwise_or.at`` word scatters while sparse (~11 ns/bit, no
    window-sized memset) and fall back to one bool write + one
    ``np.packbits`` once they pass ~1/128 of the window, where the
    linear pack wins."""
    _STATS.kernel_calls += 1
    _STATS.dense_decodes += 1
    slots = f.keys - base_key
    n_scatter = int(f.arr_data.size)
    if (n_scatter == 0 and f.run_data.shape[0] == 0
            and f.bmp_sel.size == n_containers):
        w = f.bmp_words
        if w.flags.owndata and w.flags.writeable and w.flags.c_contiguous:
            # flatten() stacked these words into a fresh buffer the
            # FlatFragment owns — hand it over instead of copying again
            return w.reshape(-1).view("<u4")
        return np.ascontiguousarray(w).reshape(-1).view("<u4").copy()
    run_gs = run_ge = None
    if f.run_data.shape[0]:
        runs_per_cont = f.run_off[1:] - f.run_off[:-1]
        rbase = np.repeat(slots[f.run_sel] << 16, runs_per_cont)
        run_gs = rbase + f.run_data[:, 0]
        run_ge = rbase + f.run_data[:, 1]
    if n_scatter >= n_containers << 9:  # window_bits / 128
        bits = np.zeros(n_containers << 16, bool)
        arr_counts = f.arr_off[1:] - f.arr_off[:-1]
        gpos = (np.repeat(slots[f.arr_sel] << 16, arr_counts)
                + f.arr_data.astype(np.int64))
        bits[gpos] = True
        out8 = np.packbits(bits, bitorder="little")
        out64 = out8.view("<u8").reshape(n_containers, BITMAP_N_WORDS)
        if f.bmp_words.shape[0]:
            out64[slots[f.bmp_sel]] = f.bmp_words
        if run_gs is not None:
            _or_runs_into(out64.reshape(-1), run_gs, run_ge)
        return out8.view("<u4").copy()
    out64 = np.zeros((n_containers, BITMAP_N_WORDS), np.uint64)
    if f.bmp_words.shape[0]:
        out64[slots[f.bmp_sel]] = f.bmp_words
    if n_scatter:
        arr_counts = f.arr_off[1:] - f.arr_off[:-1]
        gpos = (np.repeat(slots[f.arr_sel] << 16, arr_counts)
                + f.arr_data.astype(np.int64))
        np.bitwise_or.at(out64.reshape(-1), gpos >> 6,
                         np.uint64(1) << (gpos & 63).astype(np.uint64))
    if run_gs is not None:
        _or_runs_into(out64.reshape(-1), run_gs, run_ge)
    return out64.reshape(-1).view("<u4")


def _spans(breaks: np.ndarray, n: int):
    """(start, stop) of each stretch of ``n`` items, where ``breaks[i]``
    (truthy) says that item ``i + 1`` starts a new one; none for 0."""
    cuts = (np.flatnonzero(breaks) + 1).tolist()
    return zip([0, *cuts], [*cuts, n]) if n else ()


def dense_rows32(f: FlatFragment, out: np.ndarray) -> None:
    """Write the rows of a :func:`flatten_rows` view into ``out``,
    ``uint32[n_slots, 32768]`` with whatever it held before: the
    whole-leaf residency-miss decode, byte-identical to stacking
    :func:`dense_words32` of each slot's 16-container window (several
    views of a slot ORed). One pass, written in place: one zero fill
    (none where bitmap containers cover the leaf), each run of
    neighbouring bitmap containers copied to its place, every sparse
    array container's bits in one ``np.bitwise_or.at`` over the leaf.
    Only what a single scatter serves badly is done a row at a time, and
    only for the rows that have it: array bits past 1/128 of the row
    (one bool write + ``np.packbits``, as in :func:`dense_words32`) and
    run containers (:func:`_or_runs_into`, whose temporaries follow the
    words it is given). No temporary is larger than ``out``."""
    _STATS.kernel_calls += 1
    _STATS.dense_decodes += 1
    if not out.flags.c_contiguous:
        raise ValueError("dense_rows32 writes in place: contiguous rows")
    out64 = out.reshape(-1).view("<u8").reshape(-1, BITMAP_N_WORDS)
    keys = f.keys
    unique = bool((keys[1:] > keys[:-1]).all())
    bmp_keys = keys[f.bmp_sel]
    parts = f.bmp_parts if f.bmp_parts is not None else list(f.bmp_words)
    if parts and unique and bmp_keys.size == out64.shape[0]:
        np.concatenate(parts, out=out64.reshape(-1))
        return
    out.fill(0)
    arr_keys, arr_off, data = keys[f.arr_sel], f.arr_off, f.arr_data
    if data.size >= ROW_KEYS << 9:
        # a row whose array containers hold 1/128 of its bits or more is
        # packed from a bool image of that row alone, and its containers
        # leave the scatter
        rows_of = arr_keys // ROW_KEYS
        thick = (np.bincount(rows_of, weights=np.diff(arr_off))
                 >= ROW_KEYS << 9)[rows_of]
        if thick.any():
            for r in np.unique(rows_of[thick]).tolist():
                c0, c1 = np.searchsorted(rows_of, [r, r + 1]).tolist()
                bits = np.zeros(ROW_KEYS << 16, bool)
                bits[np.repeat((arr_keys[c0:c1] % ROW_KEYS) << 16,
                               np.diff(arr_off[c0:c1 + 1]))
                     + data[arr_off[c0]:arr_off[c1]]] = True
                out[r] = np.packbits(bits, bitorder="little").view("<u4")
            starts, stops = arr_off[:-1][~thick], arr_off[1:][~thick]
            arr_keys = arr_keys[~thick]
            data = _gather_ranges(data, starts, stops)
            arr_off = np.concatenate(([0], np.cumsum(stops - starts)))
    if unique:
        # each run of neighbouring keys is one copy into its place
        for a, b in _spans(np.diff(bmp_keys) != 1, len(parts)):
            k = int(bmp_keys[a])
            np.concatenate(parts[a:b], out=out64[k:k + b - a].reshape(-1))
    else:
        for k, words in zip(bmp_keys.tolist(), parts):
            np.bitwise_or(out64[k], words, out=out64[k])
    if data.size:
        word = np.repeat((arr_keys << 11).astype(np.uint32),
                         np.diff(arr_off))
        word += data >> 5
        np.bitwise_or.at(out.reshape(-1), word, np.left_shift(
            np.uint32(1), (data & 31).astype(np.uint32)))
    if f.run_data.shape[0]:
        run_keys = keys[f.run_sel]
        for a, b in _spans(np.diff(run_keys // ROW_KEYS), run_keys.size):
            r = int(run_keys[a]) // ROW_KEYS
            base = np.repeat((run_keys[a:b] % ROW_KEYS) << 16,
                             np.diff(f.run_off[a:b + 1]))
            runs = f.run_data[f.run_off[a]:f.run_off[b]]
            _or_runs_into(out64[r * ROW_KEYS:(r + 1) * ROW_KEYS].reshape(-1),
                          base + runs[:, 0], base + runs[:, 1])


# A tile of the sparse form: 1,024 words, which the expansion holds in one
# (8, 128) vreg and the residency's compressed tier calls a 4 KiB block.
SPARSE_TILE_WORDS = 1024
# The list of set bits is padded to a power of two from SPARSE_MIN_BITS up
# to one listed bit (4 bytes) for every eight words of the dense leaf, an
# eighth of its bytes: a closed list of shapes for the expansion to be
# compiled for.
SPARSE_MIN_BITS = 8192
_TILE_BIT_SHIFT = (SPARSE_TILE_WORDS * 32).bit_length() - 1
_LANES = 128
# padding of the list: a bit no leaf has, and an int32 on the chip
_NO_BIT = np.uint32(0x7FFFFFFF)


def sparse_buckets(n_rows: int) -> tuple[int, ...]:
    """The padded bit counts a sparse leaf of ``n_rows`` may have (none
    where a bit's number within the leaf would not fit beside _NO_BIT)."""
    if n_rows * SHARD_WIDTH >= _NO_BIT:
        return ()
    out, n = [], SPARSE_MIN_BITS
    while n * 8 <= n_rows * (SHARD_WIDTH >> 5):
        out.append(n)
        n *= 2
    return tuple(out)


def sparse_starts_len(n_rows: int) -> int:
    """Length of a sparse leaf's tile table: one start a tile and the
    end, rounded up to whole lanes."""
    n = n_rows * (SHARD_WIDTH >> 5) // SPARSE_TILE_WORDS + 1
    return -(-n // _LANES) * _LANES


def sparse_packed_len(n_rows: int, n_pad: int) -> int:
    """Length of ``SparseRows.packed`` for a list padded to ``n_pad``."""
    return sparse_starts_len(n_rows) + n_pad


class SparseRows:
    """A row leaf ``uint32[n_rows, 32768]`` as its set bits, for the
    device to expand (residency.expand_rows_body). The leaf is listed in
    ``parts`` equal shares of its slot rows, one a chip that holds a
    share (1 off a mesh), each a list of its own: ``packed`` is ONE host
    array, the shares' lists end to end, each
    uint32[sparse_packed_len(n_rows // parts, n_pad)]: the share's tile
    table (``starts[t]`` .. ``starts[t + 1]`` are the listed bits of its
    tile ``t``), then ``n_pad`` bits in tile order, each as its number
    within the share (``word * 32 + bit``: the flat word index and the
    mask in one integer); a padding entry is a bit no leaf has. Every
    share is padded to the same ``n_pad``, the bucket of the fullest.
    ``tiles`` are the leaf's tiles that hold a set bit, ascending."""

    __slots__ = ("packed", "n_rows", "n_pad", "tiles", "parts")

    def __init__(self, packed, n_rows: int, n_pad: int, tiles,
                 parts: int = 1):
        self.packed = packed
        self.n_rows = n_rows
        self.n_pad = n_pad
        self.tiles = tiles
        self.parts = parts


def sparse_rows32(f: FlatFragment, n_rows: int, staging,
                  parts: int = 1) -> SparseRows | None:
    """The rows of a :func:`flatten_rows` view as a :class:`SparseRows`
    of ``parts`` shares, or None where :func:`dense_rows32` has to write
    them: a bitmap or a run container in the view, a key named twice
    (several views of a slot, whose bits may repeat), array containers
    whose values leave tile order, or a share with more set bits than
    the largest bucket of its row count holds. ``staging(shape)`` gives
    the uint32 array written; nothing of the dense leaf's size is made
    or read."""
    rows = n_rows // parts
    buckets = sparse_buckets(rows)
    keys = f.keys
    if (f.bmp_sel.size or f.run_sel.size or not buckets
            or not bool((keys[1:] > keys[:-1]).all())):
        return None
    # key = slot * 16 + k: a share's containers lie together
    key_cuts = np.searchsorted(
        keys, np.arange(parts + 1) * (rows * ROW_KEYS)).tolist()
    cuts = f.arr_off[key_cuts].tolist()
    n = max(b - a for a, b in zip(cuts, cuts[1:]))
    if n > buckets[-1]:
        return None
    _STATS.kernel_calls += 1
    n_pad = next(b for b in buckets if n <= b)
    n_tiles = rows * (SHARD_WIDTH >> 5) // SPARSE_TILE_WORDS
    t1 = sparse_starts_len(rows)
    packed = staging((parts * (t1 + n_pad),))
    tiles = []
    for p in range(parts):
        part = packed[p * (t1 + n_pad):(p + 1) * (t1 + n_pad)]
        ka, kb = key_cuts[p], key_cuts[p + 1]
        m = cuts[p + 1] - cuts[p]
        bits = part[t1:t1 + m]
        # the key within the share is the bit's number within it >> 16
        np.add(np.repeat(((keys[ka:kb] - p * rows * ROW_KEYS) << 16
                          ).astype(np.uint32), np.diff(f.arr_off[ka:kb + 1])),
               f.arr_data[cuts[p]:cuts[p + 1]], out=bits)
        tile_of = bits >> np.uint32(_TILE_BIT_SHIFT)
        if not bool((tile_of[1:] >= tile_of[:-1]).all()):
            return None  # a container out of order: dense_rows32 ORs any
        part[t1 + m:] = _NO_BIT
        counts = np.bincount(tile_of, minlength=n_tiles)
        part[0] = 0
        part[1:n_tiles + 1] = np.cumsum(counts)
        part[n_tiles + 1:t1] = m
        tiles.append(np.flatnonzero(counts).astype(np.int32) + p * n_tiles)
    return SparseRows(packed, n_rows, n_pad,
                      tiles[0] if parts == 1 else np.concatenate(tiles),
                      parts)


# ---------------------------------------------------------------- popcount


def popcount(f: FlatFragment) -> int:
    """Whole-fragment population count from the raw payloads (one
    ``np.bitwise_count`` over the stacked bitmap words + array sizes +
    run lengths) — does not trust the cached cardinalities."""
    _STATS.kernel_calls += 1
    total = int(f.arr_data.size)
    if f.bmp_words.shape[0]:
        total += int(np.bitwise_count(f.bmp_words).sum(dtype=np.int64))
    if f.run_data.shape[0]:
        total += int((f.run_data[:, 1] - f.run_data[:, 0] + 1).sum())
    return total


# ----------------------------------------------------------------- set ops


def intersect_sorted(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Sorted-unique uint64 intersection. Galloping when lopsided: probe
    the small side into the big side with one ``searchsorted`` (log per
    probe — the vectorized analog of the galloping intersect in the
    roaring papers); linear merge (``np.intersect1d``) otherwise."""
    if a.size == 0 or b.size == 0:
        return _EMPTY_IDS
    small, big = (a, b) if a.size <= b.size else (b, a)
    if small.size << 5 < big.size:
        i = np.searchsorted(big, small)
        i_c = np.minimum(i, big.size - 1)
        return small[(i < big.size) & (big[i_c] == small)]
    return np.intersect1d(a, b, assume_unique=True)


def setdiff_sorted(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Sorted-unique a \\ b, galloping when b dwarfs a."""
    if a.size == 0:
        return _EMPTY_IDS
    if b.size == 0:
        return a
    if a.size << 5 < b.size:
        i = np.searchsorted(b, a)
        i_c = np.minimum(i, b.size - 1)
        return a[~((i < b.size) & (b[i_c] == a))]
    return np.setdiff1d(a, b, assume_unique=True)


def _ids_from_word_rows(keys: np.ndarray, words: np.ndarray) -> np.ndarray:
    """ids for (key, 1024-word-row) pairs: one unpack + one nonzero,
    container bases folded in per row (same trick as ``_bmp_ids``)."""
    nb = words.shape[0]
    if nb == 0:
        return _EMPTY_IDS
    bits = np.unpackbits(
        np.ascontiguousarray(words).view(np.uint8), bitorder="little")
    pos = np.flatnonzero(bits.view(bool))
    if pos.size == 0:
        return _EMPTY_IDS
    edges = np.searchsorted(pos, np.arange(nb + 1, dtype=np.int64) << 16)
    adj = (keys.astype(np.int64) - np.arange(nb)) << np.int64(16)
    return (pos + np.repeat(adj, np.diff(edges))).view(np.uint64)


def _as_flat(x) -> FlatFragment:
    return x if isinstance(x, FlatFragment) else flatten(x)


def _setop(a, b, word_op, id_op, keep_a_only: bool,
           keep_b_only: bool) -> np.ndarray:
    fa, fb = _as_flat(a), _as_flat(b)
    _STATS.kernel_calls += 1
    _STATS.set_ops += 1
    common, ia, ib = np.intersect1d(fa.keys, fb.keys, return_indices=True)
    parts = []
    if common.size:
        bb = (fa.kinds[ia] == BITMAP) & (fb.kinds[ib] == BITMAP)
        if bb.any():
            # bitmap×bitmap stays in word space — no materialization
            wa = fa.bmp_words[fa.kind_row[ia[bb]]]
            wb = fb.bmp_words[fb.kind_row[ib[bb]]]
            parts.append(_ids_from_word_rows(common[bb], word_op(wa, wb)))
        if (~bb).any():
            ids_a = fragment_ids(_take(fa, ia[~bb]))
            ids_b = fragment_ids(_take(fb, ib[~bb]))
            parts.append(id_op(ids_a, ids_b))
    if keep_a_only:
        only = np.setdiff1d(np.arange(fa.keys.size), ia)
        if only.size:
            parts.append(fragment_ids(_take(fa, only)))
    if keep_b_only:
        only = np.setdiff1d(np.arange(fb.keys.size), ib)
        if only.size:
            parts.append(fragment_ids(_take(fb, only)))
    parts = [p for p in parts if p.size]
    if not parts:
        return _EMPTY_IDS
    if len(parts) == 1:
        return parts[0]
    return np.sort(np.concatenate(parts))


def fragment_and(a, b) -> np.ndarray:
    """Sorted ids of a ∩ b (whole-fragment AND kernel)."""
    return _setop(a, b, np.bitwise_and, intersect_sorted, False, False)


def fragment_or(a, b) -> np.ndarray:
    """Sorted ids of a ∪ b."""
    return _setop(a, b, np.bitwise_or,
                  lambda x, y: np.union1d(x, y), True, True)


def fragment_xor(a, b) -> np.ndarray:
    """Sorted ids of a △ b."""
    return _setop(a, b, np.bitwise_xor,
                  lambda x, y: np.setxor1d(x, y, assume_unique=True),
                  True, True)


def fragment_andnot(a, b) -> np.ndarray:
    """Sorted ids of a \\ b."""
    return _setop(a, b, lambda x, y: x & ~y, setdiff_sorted, True, False)


def diff_ids(a, b) -> tuple[np.ndarray, np.ndarray]:
    """(only-in-a, only-in-b) sorted id arrays — the content diff the
    anti-entropy block compare speaks."""
    ids_a = fragment_ids(_as_flat(a))
    ids_b = fragment_ids(_as_flat(b))
    return setdiff_sorted(ids_a, ids_b), setdiff_sorted(ids_b, ids_a)


# -------------------------------------------------------- digests / diffs


def block_slices(ids: np.ndarray, blocks, block_rows: int = 100) -> dict:
    """Slice a sorted id array into the requested checksum blocks with
    ONE searchsorted over the block boundaries — replaces the
    per-block full-``to_ids``-and-mask walk (O(blocks × population))
    the sync block server used to pay. Returns {block: ids}."""
    _STATS.kernel_calls += 1
    wanted = np.asarray(sorted(set(int(b) for b in blocks)), np.int64)
    if wanted.size == 0:
        return {}
    width = np.uint64(block_rows) << np.uint64(20)
    los = wanted.astype(np.uint64) * width
    edges = np.searchsorted(ids, np.concatenate((los, los + width)))
    n = wanted.size
    return {int(wanted[i]): ids[edges[i]:edges[n + i]] for i in range(n)}


def diff_digests(local, peer) -> list[int]:
    """Blocks whose digests differ (peer-driven fetch list): every block
    the peer has that the local side lacks or disagrees on — the sync
    manifest diff, one place."""
    local = dict(local)
    return sorted(int(b) for b, checksum in dict(peer).items()
                  if local.get(b) != checksum)


# ------------------------------------------------- snapshot-bytes fast path

_HEADER = struct.Struct("<IHHIQ")
_SNAP_MAGIC = 0x50C4B175
_SNAP_VERSION = 1
_DESCR_DTYPE = np.dtype([("key", "<u8"), ("kind", "<u2"),
                         ("nm1", "<u2"), ("plen", "<u4")])


def _snapshot_descriptors(buf: memoryview):
    """The descriptor table of a roaring/format.py snapshot as ONE
    structured array, with ``deserialize``'s structural validation (and
    error text): returns (descrs, kinds uint8, offs int64), container
    ``i``'s payload being ``buf[offs[i]:offs[i + 1]]`` and the ops
    beginning at ``offs[-1]``. Payload lengths a kind forbids (a bitmap
    payload not exactly 1024 words) raise :class:`_IrregularSnapshot`."""
    if len(buf) < _HEADER.size:
        raise ValueError("roaring: truncated header")
    magic, version, _flags, n_containers, payload_bytes = _HEADER.unpack_from(
        buf, 0)
    if magic != _SNAP_MAGIC:
        raise ValueError(f"roaring: bad magic 0x{magic:08X}")
    if version != _SNAP_VERSION:
        raise ValueError(f"roaring: unsupported version {version}")
    descr_end = _HEADER.size + n_containers * _DESCR_DTYPE.itemsize
    if descr_end > len(buf):
        raise ValueError("roaring: truncated container payload")
    descrs = np.frombuffer(buf, dtype=_DESCR_DTYPE, count=n_containers,
                           offset=_HEADER.size)
    kinds = descrs["kind"].astype(np.uint8)
    plens = descrs["plen"].astype(np.int64)
    bad = (kinds < ARRAY) | (kinds > RUN)
    if bad.any():
        k = int(descrs["kind"][np.nonzero(bad)[0][0]])
        raise ValueError(f"roaring: unknown container kind {k}")
    offs = descr_end + np.concatenate(([0], np.cumsum(plens)))
    if int(offs[-1]) > len(buf):
        raise ValueError("roaring: truncated container payload")
    if int(offs[-1]) != descr_end + payload_bytes:
        raise ValueError("roaring: payload length mismatch")
    is_b = kinds == BITMAP
    if ((plens[kinds == ARRAY] & 1).any()
            or (plens[is_b] != BITMAP_N_WORDS * 8).any()
            or (plens[kinds == RUN] & 3).any()):
        raise _IrregularSnapshot()
    return descrs, kinds, offs


def directory_from_snapshot(buf) -> ContainerDirectory | None:
    """The :class:`ContainerDirectory` of snapshot bytes that
    ``deserialize`` accepted, or None for an irregular snapshot
    (descriptors out of key order, a key twice, a payload length its
    kind forbids): :func:`flat_from_snapshot`'s descriptor arithmetic
    and nothing a container: a canonical snapshot's payloads already lie
    in key order, so ``payload`` is a view of ``buf`` and no byte of a
    container is copied."""
    buf = memoryview(buf)
    try:
        descrs, kinds, offs = _snapshot_descriptors(buf)
    except _IrregularSnapshot:
        return None
    keys = descrs["key"].astype(np.int64)
    if not bool((keys[1:] > keys[:-1]).all()):
        return None
    # every regular payload is a whole number of uint16: starts count them
    first, end = int(offs[0]), int(offs[-1])
    payload = np.frombuffer(buf, "<u2", count=(end - first) >> 1, offset=first)
    return ContainerDirectory(keys, kinds,
                              descrs["nm1"].astype(np.int32) + 1,
                              (offs - first) >> 1, payload)


def flat_from_snapshot(buf) -> tuple[FlatFragment, int]:
    """Parse a roaring/format.py snapshot straight into a FlatFragment —
    no Container objects, no per-container ``np.frombuffer`` — with the
    same structural validation (and error text) as ``deserialize``.
    Returns (flat, offset-where-ops-begin). The scrub/verify fast path:
    digesting a fragment file becomes parse → :func:`fragment_ids` →
    ``block_digests`` with zero per-container dispatches.

    Falls back (ValueError) only on inputs ``deserialize`` also
    rejects; irregular-but-accepted payloads (bitmap payload not
    exactly 1024 words) raise :class:`_IrregularSnapshot` so the caller
    can retry through the reference decoder.
    """
    buf = memoryview(buf)
    descrs, kinds, offs = _snapshot_descriptors(buf)
    n_containers = int(kinds.size)
    keys = descrs["key"].astype(np.int64)
    order = np.argsort(keys, kind="stable")
    if np.unique(keys).size != keys.size:
        # duplicate keys: dict semantics (last wins) — rare, reference path
        raise _IrregularSnapshot()
    buf8 = np.frombuffer(buf, np.uint8)
    f = FlatFragment()
    f.keys = keys[order]
    f.kinds = kinds[order]
    kind_row = np.empty(n_containers, np.int64)
    kind_row[f.kinds == ARRAY] = np.arange(int((f.kinds == ARRAY).sum()))
    kind_row[f.kinds == BITMAP] = np.arange(int((f.kinds == BITMAP).sum()))
    kind_row[f.kinds == RUN] = np.arange(int((f.kinds == RUN).sum()))
    f.kind_row = kind_row
    starts, stops = offs[:-1][order], offs[1:][order]
    a_m, b_m, r_m = (f.kinds == ARRAY), (f.kinds == BITMAP), (f.kinds == RUN)
    f.arr_sel = np.nonzero(a_m)[0]
    f.arr_data = np.ascontiguousarray(
        _gather_ranges(buf8, starts[a_m], stops[a_m])).view("<u2")
    f.arr_off = np.concatenate(
        ([0], np.cumsum((stops[a_m] - starts[a_m]) >> 1))).astype(np.int64)
    f.bmp_sel = np.nonzero(b_m)[0]
    f.bmp_words = np.ascontiguousarray(
        _gather_ranges(buf8, starts[b_m], stops[b_m])
    ).view("<u8").reshape(-1, BITMAP_N_WORDS)
    f.run_sel = np.nonzero(r_m)[0]
    f.run_data = np.ascontiguousarray(
        _gather_ranges(buf8, starts[r_m], stops[r_m])
    ).view("<u2").astype(np.int64).reshape(-1, 2)
    f.run_off = np.concatenate(
        ([0], np.cumsum((stops[r_m] - starts[r_m]) >> 2))).astype(np.int64)
    # cards from the payloads themselves (the reference materializes the
    # full payload regardless of the descriptor cardinality field)
    cards = np.zeros(n_containers, np.int64)
    cards[a_m] = f.arr_off[1:] - f.arr_off[:-1]
    if f.bmp_words.shape[0]:
        cards[b_m] = np.bitwise_count(f.bmp_words).sum(axis=1,
                                                       dtype=np.int64)
    if f.run_data.shape[0]:
        rlens = f.run_data[:, 1] - f.run_data[:, 0] + 1
        per = np.add.reduceat(rlens, f.run_off[:-1])
        per[f.run_off[:-1] == f.run_off[1:]] = 0
        cards[r_m] = per
    f.cards = cards
    _STATS.containers_flattened += n_containers
    return f, int(offs[-1])


class _IrregularSnapshot(Exception):
    """Structurally valid but irregular snapshot (non-canonical payload
    sizes, duplicate keys): take the reference decode path."""


def snapshot_ids(buf) -> tuple[np.ndarray, int]:
    """Sorted ids of a snapshot's payload, straight from the bytes.
    Returns (ids, ops_at). Byte-identical to
    ``deserialize(buf)[0].to_ids()`` — irregular snapshots transparently
    fall back to the reference decoder."""
    try:
        flat, ops_at = flat_from_snapshot(buf)
    except _IrregularSnapshot:
        from pilosa_tpu.roaring.format import deserialize

        bitmap, ops_at = deserialize(buf)
        return bitmap.to_ids(), ops_at
    return fragment_ids(flat), ops_at
