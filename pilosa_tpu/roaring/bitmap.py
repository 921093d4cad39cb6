"""64-bit roaring bitmap on the host (numpy-vectorized).

Model follows the reference roaring engine (roaring/roaring.go): values are
uint64, containers are keyed by ``value >> 16`` and hold the low 16 bits in
one of three kinds — sorted uint16 **array**, 1024×uint64 **bitmap**, or
**run** list of inclusive [start, last] uint16 intervals. Unlike the
reference this implementation is vectorized numpy (no per-value loops) and
exists only for durability/interchange; set algebra at query time happens
on device via the fused expression compiler (pilosa_tpu.executor.expr).
"""

from __future__ import annotations

import bisect

import numpy as np

from pilosa_tpu import native

ARRAY = 1
BITMAP = 2
RUN = 3

# Above this cardinality an array container is worse than a bitmap
# (4096 * 2 bytes == 8 KiB == bitmap size), same threshold reasoning as the
# roaring papers (PAPERS.md: Chambi et al.).
ARRAY_MAX = 4096
BITMAP_N_WORDS = 1024  # uint64 words per container (65536 bits)


def _scatter_bits(words8: np.ndarray, lows: np.ndarray) -> None:
    """OR uint16 bit positions into a byte view of a bitmap container."""
    np.bitwise_or.at(
        words8,
        (lows >> np.uint16(3)).astype(np.int64),
        np.uint8(1) << (lows & np.uint16(7)).astype(np.uint8),
    )


class Container:
    __slots__ = ("kind", "data", "n")

    def __init__(self, kind: int, data: np.ndarray, n: int):
        self.kind = kind
        self.data = data
        self.n = n  # cardinality

    # --- constructors ---

    @staticmethod
    def from_lows(lows: np.ndarray) -> "Container":
        """Build the optimal container for sorted unique uint16 lows."""
        n = int(lows.size)
        if n == 0:
            return Container(ARRAY, np.empty(0, np.uint16), 0)
        n_runs = int(np.count_nonzero(np.diff(lows.astype(np.int32)) != 1)) + 1
        # cost in bytes: array 2n, run 4*n_runs, bitmap 8192
        if 4 * n_runs < min(2 * n, 8192):
            d = np.diff(lows.astype(np.int32))
            starts_idx = np.concatenate(([0], np.nonzero(d != 1)[0] + 1))
            ends_idx = np.concatenate((np.nonzero(d != 1)[0], [n - 1]))
            runs = np.stack([lows[starts_idx], lows[ends_idx]], axis=1)
            return Container(RUN, np.ascontiguousarray(runs, np.uint16), n)
        if n <= ARRAY_MAX:
            return Container(ARRAY, np.ascontiguousarray(lows, np.uint16), n)
        words = np.zeros(BITMAP_N_WORDS * 8, np.uint8)
        _scatter_bits(words, lows)
        return Container(BITMAP, words.view("<u8").copy(), n)

    # --- conversions ---

    def lows(self) -> np.ndarray:
        """Sorted unique uint16 values in this container."""
        if self.kind == ARRAY:
            return self.data
        if self.kind == BITMAP:
            bits = np.unpackbits(
                np.ascontiguousarray(self.data).view(np.uint8), bitorder="little"
            )
            return np.nonzero(bits)[0].astype(np.uint16)
        # RUN
        runs = self.data.astype(np.int64)
        if runs.size == 0:
            return np.empty(0, np.uint16)
        lengths = runs[:, 1] - runs[:, 0] + 1
        total = int(lengths.sum())
        out = np.repeat(runs[:, 0] - np.concatenate(([0], np.cumsum(lengths)[:-1])), lengths)
        return (out + np.arange(total)).astype(np.uint16)

    def contains_low(self, low: int) -> bool:
        """O(1)/O(log n) membership for one in-container value — no
        materialization (``lows()`` unpacks all 65536 bits; a bitmap
        container probe must not)."""
        if self.kind == ARRAY:
            i = int(np.searchsorted(self.data, low))
            return i < self.data.size and int(self.data[i]) == low
        if self.kind == BITMAP:
            return bool((int(self.data[low >> 6]) >> (low & 63)) & 1)
        runs = self.data
        if runs.size == 0:
            return False
        i = int(np.searchsorted(runs[:, 0], low, side="right")) - 1
        return i >= 0 and low <= int(runs[i, 1])

    def dense_words32(self) -> np.ndarray:
        """Container as 2048 uint32 words (65536 bits) — device format block.
        Host→device decode hot path: native fastbits when available."""
        if self.kind == BITMAP:
            return np.ascontiguousarray(self.data).view("<u4").copy()
        from pilosa_tpu import native

        if self.kind == RUN:
            fast = native.runs_to_words(self.data)
            if fast is not None:
                return fast
        else:
            fast = native.pack_positions(self.data.astype(np.uint64), 2048)
            if fast is not None:
                return fast
        lows = self.lows()
        words = np.zeros(2048 * 4, np.uint8)
        if lows.size:
            _scatter_bits(words, lows)
        return words.view("<u4").copy()


class ContainerDirectory:
    """A snapshot's containers as parallel arrays over the snapshot's own
    bytes: what a reader needs of every container without touching a
    ``Container``. ``keys`` ascend (int64); ``kinds`` and ``cards`` are
    each container's kind and cardinality; container ``i``'s payload is
    ``payload[starts[i]:starts[i + 1]]``, where ``payload`` is a
    read-only uint16 view of the snapshot bytes (every regular payload
    is a whole number of uint16) and ``starts`` has one entry more than
    ``keys``. ``all_arrays`` says that no container is a bitmap or a run.
    Immutable: made from the bytes of a canonical snapshot
    (kernels.directory_from_snapshot) while the bitmap equals them, and
    dropped, never edited, by the first mutation (RoaringBitmap._merge)."""

    __slots__ = ("keys", "kinds", "cards", "starts", "payload", "all_arrays")

    def __init__(self, keys, kinds, cards, starts, payload):
        self.keys = keys
        self.kinds = kinds
        self.cards = cards
        self.starts = starts
        self.payload = payload
        self.all_arrays = bool((kinds == ARRAY).all())


class RoaringBitmap:
    """Sorted map: container key (high 48 bits) → Container."""

    def __init__(self):
        self.keys: list[int] = []
        self._containers: dict[int, Container] = {}
        # ContainerDirectory of the snapshot this bitmap was read from or
        # written to, while it still equals it (storage/fragment.py sets
        # it, _merge drops it); None otherwise
        self.directory: ContainerDirectory | None = None

    # --- constructors ---

    @classmethod
    def from_ids(cls, ids) -> "RoaringBitmap":
        b = cls()
        ids = np.unique(np.asarray(ids, dtype=np.uint64))
        if ids.size == 0:
            return b
        hi = (ids >> np.uint64(16)).astype(np.int64)
        lows = (ids & np.uint64(0xFFFF)).astype(np.uint16)
        boundaries = np.concatenate(
            ([0], np.nonzero(np.diff(hi))[0] + 1, [ids.size])
        )
        for i in range(boundaries.size - 1):
            lo_i, hi_i = int(boundaries[i]), int(boundaries[i + 1])
            key = int(hi[lo_i])
            b._containers[key] = Container.from_lows(lows[lo_i:hi_i])
        b.keys = sorted(b._containers)
        return b

    @classmethod
    def from_dense_words(cls, words: np.ndarray, base: int = 0) -> "RoaringBitmap":
        """From packed uint32 words; bit i → id base + i (base must be
        65536-aligned)."""
        assert base % 65536 == 0
        bits = np.unpackbits(
            np.ascontiguousarray(words, np.uint32).view(np.uint8), bitorder="little"
        )
        ids = np.nonzero(bits)[0].astype(np.uint64) + np.uint64(base)
        return cls.from_ids(ids)

    # --- accessors ---

    def container(self, key: int) -> Container | None:
        return self._containers.get(key)

    def to_ids(self) -> np.ndarray:
        # whole-bitmap materialization rides the vectorized kernel layer:
        # one flatten (lock-free .get + skip, same race discipline as
        # dense_range_words32) then one batched kernel call — the
        # per-container lows() loop lives on only as the test reference
        # (tests/test_roaring_kernels.py pins byte-identity)
        from pilosa_tpu.roaring import kernels

        return kernels.fragment_ids(kernels.flatten(self))

    def count(self) -> int:
        return sum(c.n for c in self._containers.values())

    def count_range(self, start: int, stop: int) -> int:
        if stop <= start:
            return 0
        lo_key, hi_key = start >> 16, (stop - 1) >> 16
        # bisect the sorted key list: count_range is called per written
        # row (ranked-cache refresh), so an O(#containers) scan here turns
        # bulk imports quadratic
        keys = self.keys
        lo_i = bisect.bisect_left(keys, lo_key)
        hi_i = bisect.bisect_right(keys, hi_key)
        total = 0
        for key in keys[lo_i:hi_i]:
            c = self._containers.get(key)
            if c is None:  # lock-free reader racing a remove
                continue
            # fully-covered containers (incl. aligned boundaries — the
            # count_row case) contribute their cardinality without being
            # materialized; only genuinely partial ones unpack
            if key << 16 >= start and (key + 1) << 16 <= stop:
                total += c.n
            else:
                lows = c.lows().astype(np.int64) + (key << 16)
                total += int(((lows >= start) & (lows < stop)).sum())
        return total

    def dense_range_words32(self, start: int, stop: int) -> np.ndarray:
        """Materialize [start, stop) as packed uint32 words (both 65536-aligned).

        This is the host→device decode path: a fragment row (2^20 bits = 16
        containers) becomes uint32[32768] for device_put.
        """
        assert start % 65536 == 0 and stop % 65536 == 0 and stop > start
        n_containers = (stop - start) >> 16
        out = np.zeros((n_containers, 2048), np.uint32)
        base_key = start >> 16
        for i in range(n_containers):
            c = self._containers.get(base_key + i)
            if c is not None:
                out[i] = c.dense_words32()
        return out.reshape(-1)

    def range_ids(self, start: int, stop: int) -> np.ndarray:
        """Sorted ids in [start, stop) — walks only the containers
        overlapping the range. The whole-bitmap ``to_ids()`` is O(total
        population); per-row probes (import_bsi membership, row_columns)
        must not pay that on large fragments."""
        if stop <= start or not self.keys:
            return np.empty(0, np.uint64)
        from pilosa_tpu.roaring import kernels

        # key-bounded flatten + one batched kernel; partial edge
        # containers are trimmed by one vectorized mask inside
        flat = kernels.flatten(self, start >> 16, (stop - 1) >> 16)
        return kernels.range_ids(flat, start, stop)

    def contains_lows(self, key: int, lows: np.ndarray) -> np.ndarray:
        """Vectorized membership of uint16 lows in ONE container, probed
        in place (no decode): ARRAY by searchsorted, BITMAP by word bit
        test, RUN by interval search."""
        c = self._containers.get(key)
        if c is None or c.n == 0:
            return np.zeros(lows.size, bool)
        if c.kind == ARRAY:
            idx = np.searchsorted(c.data, lows)
            idx_c = np.minimum(idx, c.data.size - 1)
            return (idx < c.data.size) & (c.data[idx_c] == lows)
        if c.kind == BITMAP:
            w = c.data  # uint64 words
            word = w[(lows >> np.uint16(6)).astype(np.int64)]
            bit = (lows & np.uint16(63)).astype(np.uint64)
            return ((word >> bit) & np.uint64(1)).astype(bool)
        starts = c.data[:, 0]
        lasts = c.data[:, 1]
        i = np.searchsorted(starts, lows, side="right") - 1
        ok = i >= 0
        i_c = np.maximum(i, 0)
        return ok & (lows <= lasts[i_c])

    def row_member(self, row: int, positions: np.ndarray) -> np.ndarray:
        """Vectorized membership of in-shard positions in one row.
        Probes only the containers the positions land in — O(batch·log)
        per row, independent of the row's population (the import hot
        paths must not decode whole rows to clear a handful of bits)."""
        ids = (np.uint64(row) << np.uint64(20)) + positions
        his = (ids >> np.uint64(16)).astype(np.int64)
        lows = (ids & np.uint64(0xFFFF)).astype(np.uint16)
        out = np.zeros(positions.size, bool)
        for key in np.unique(his).tolist():
            m = his == key
            out[m] = self.contains_lows(int(key), lows[m])
        return out

    # --- mutation (op-log replay + write path) ---

    def add_ids(self, ids) -> int:
        """Set bits; returns number actually changed (reference Add)."""
        return self._merge(ids, remove=False)

    def remove_ids(self, ids) -> int:
        return self._merge(ids, remove=True)

    def _merge(self, ids, remove: bool) -> int:
        """Dispatch a mutation batch: whole-batch merge kernel
        (roaring/merge_kernels.py — single numpy dispatches across ALL
        touched containers, GIL released inside them) above the size
        threshold, the per-container loop below it (a point write must
        not pay batch bookkeeping). Both produce byte-identical
        containers — tests/test_merge_kernels.py pins the property, so
        the threshold is pure performance tuning. The one door every
        mutation passes (add_ids, remove_ids, op replay, WAL recovery),
        so the snapshot's ContainerDirectory dies here."""
        ids = np.atleast_1d(np.asarray(ids, dtype=np.uint64))
        if ids.size == 0:
            return 0
        # before any container is swapped: a reader that already holds the
        # directory reads the older snapshot whole, a later one walks
        self.directory = None
        from pilosa_tpu.roaring import merge_kernels

        if ids.size >= merge_kernels.KERNEL_MIN_IDS:
            return merge_kernels.merge_ids(self, ids, remove)
        merge_kernels.global_merge_stats().loop_fallbacks += 1
        return self._merge_loop(ids, remove)

    def _merge_loop(self, ids: np.ndarray, remove: bool) -> int:
        """The per-container merge loop: small-batch fast path AND the
        byte-identity reference for the whole-batch kernel (the same
        role the retired per-container read paths play in
        tests/test_roaring_kernels.py)."""
        ids = np.atleast_1d(np.asarray(ids, dtype=np.uint64))
        if ids.size == 0:
            return 0
        # bulk imports arrive pre-sorted ((row<<20)+sorted positions per
        # row); skip np.unique's unconditional O(n log n) sort for them
        # and dedupe sorted input with one vectorized compare
        if ids.size > 1:
            if not bool(np.all(ids[1:] >= ids[:-1])):
                ids = np.sort(ids)
            ids = ids[np.concatenate(([True], ids[1:] != ids[:-1]))]
        hi = (ids >> np.uint64(16)).astype(np.int64)
        lows = (ids & np.uint64(0xFFFF)).astype(np.uint16)
        boundaries = np.concatenate(
            ([0], np.nonzero(np.diff(hi))[0] + 1, [ids.size])
        )
        changed = 0
        dirty = False
        for i in range(boundaries.size - 1):
            lo_i, hi_i = int(boundaries[i]), int(boundaries[i + 1])
            key = int(hi[lo_i])
            batch = lows[lo_i:hi_i]
            c = self._containers.get(key)
            delta = None
            # fast paths: scatter straight into a 1024-word bitmap instead
            # of unpack + sort + rebuild — the bulk-import hot loop
            if c is not None and c.kind == BITMAP:
                delta = self._merge_bitmap_inplace(key, c, batch, remove)
            elif (not remove and c is not None and c.kind == ARRAY
                  and c.n + batch.size > ARRAY_MAX):
                # promote via a temporary (not yet installed) bitmap; the
                # merge helper swaps in the final consistent container
                words = np.zeros(BITMAP_N_WORDS * 8, np.uint8)
                _scatter_bits(words, c.data)
                tmp = Container(BITMAP, words.view("<u8"), c.n)
                delta = self._merge_bitmap_inplace(key, tmp, batch, remove)
            elif not remove and c is None and batch.size > ARRAY_MAX:
                self._containers[key] = Container.from_lows(batch)
                delta = int(batch.size)
            if delta is None:
                existing = c.lows() if c is not None else np.empty(0, np.uint16)
                # both sides are sorted unique (container invariant;
                # batch is a slice of the deduped sorted ids) — the
                # native two-pointer merge beats union1d's concat+sort
                if remove:
                    new = native.diff_sorted_u16(existing, batch)
                    if new is None:
                        new = np.setdiff1d(existing, batch,
                                           assume_unique=True)
                else:
                    new = native.union_sorted_u16(existing, batch)
                    if new is None:
                        new = np.union1d(existing, batch)
                delta = abs(int(new.size) - int(existing.size))
                if delta and new.size == 0:
                    self._containers.pop(key, None)
                elif delta:
                    self._containers[key] = Container.from_lows(new)
            if delta == 0:
                continue
            changed += delta
            dirty = True
        if dirty:
            self.keys = sorted(self._containers)
        return changed

    def _merge_bitmap_inplace(self, key: int, c: Container, batch, remove: bool) -> int:
        """Scatter a unique uint16 batch into a copy of a BITMAP container
        and swap the new container in atomically (readers and snapshots
        always see a self-consistent immutable container — no torn
        data/cardinality under the threaded server). Returns the
        cardinality delta (container removed when emptied)."""
        words8 = np.array(c.data.view(np.uint8))  # 8 KiB copy, writable
        if remove:
            idx = (batch >> np.uint16(3)).astype(np.int64)
            np.bitwise_and.at(
                words8, idx,
                np.uint8(0xFF) ^ (np.uint8(1) << (batch & np.uint16(7)).astype(np.uint8)),
            )
        else:
            _scatter_bits(words8, batch)
        new_n = int(np.bitwise_count(words8).sum(dtype=np.int64))
        delta = abs(new_n - c.n)
        if new_n == 0:
            self._containers.pop(key, None)
        elif delta == 0:
            pass  # unchanged: keep the existing container
        elif new_n <= ARRAY_MAX:
            # shrunk (or overlap-heavy add) below the bitmap break-even:
            # rebuild the optimal array/run form instead of keeping 8 KiB
            new_c = Container(BITMAP, words8.view("<u8"), new_n)
            self._containers[key] = Container.from_lows(new_c.lows())
        else:
            self._containers[key] = Container(BITMAP, words8.view("<u8"), new_n)
        return delta

    def __contains__(self, id_: int) -> bool:
        c = self._containers.get(int(id_) >> 16)
        if c is None:
            return False
        return c.contains_low(int(id_) & 0xFFFF)

    def __eq__(self, other) -> bool:
        if not isinstance(other, RoaringBitmap):
            return NotImplemented
        return self.keys == other.keys and all(
            np.array_equal(self._containers[k].lows(), other._containers[k].lows())
            for k in self.keys
        )
