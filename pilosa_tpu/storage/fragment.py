"""Fragment: one (index, field, view, shard) slice of the bitmap matrix.

Reference: fragment.go (SURVEY.md §2 #3, §3.2–3.3) — the hot storage unit.
Row ``r`` of the matrix occupies bit positions [r·2^20, (r+1)·2^20) of the
fragment bitmap. Durability model: a roaring snapshot file plus an
append-only op log, compacted once the op count crosses a threshold;
crash recovery = snapshot + replay (torn tails dropped). WHERE the op
log lives depends on the holder's durability mode (storage/wal.py):
``group`` routes records through the per-holder group-commit WAL (one
fsync per wave of writers, fragment files hold snapshots only);
``per-op``/``flush-only`` append to this fragment's own file as the
reference does.

TPU divergence (SURVEY.md §7.1): reads are served from dense bit-packed
rows decoded on demand and cached in device HBM (residency.DeviceRowCache),
so query kernels see uniform uint32[32768] vectors instead of container
trees. The roaring form never reaches the device.
"""

from __future__ import annotations

import os
import threading

import numpy as np

from pilosa_tpu.roaring import RoaringBitmap, OP_ADD, OP_REMOVE
from pilosa_tpu.roaring import kernels
from pilosa_tpu.roaring.format import (
    deserialize,
    encode_op,
    load_any,
    replay_ops,
    serialize,
)
from pilosa_tpu.shardwidth import (
    SHARD_WIDTH,
    SHARD_WIDTH_EXP,
    keep_last_unique,
)
from pilosa_tpu.serving import rescache
from pilosa_tpu.storage.cache import CACHE_TYPE_RANKED, DEFAULT_CACHE_SIZE, new_row_cache
from pilosa_tpu.storage import residency
from pilosa_tpu.storage.heat import global_heat
from pilosa_tpu.storage.integrity import (
    CHECKSUM_SUFFIX,
    CorruptFragmentError,
    DECODE_ERRORS,
    block_digests,
    load_verified,
    read_file,
    save_checksums,
)
from pilosa_tpu.storage.wal import MODE_PER_OP, fsync_dir, wal_fsync
from pilosa_tpu.testing import faults as _faults
from pilosa_tpu.utils.cost import current_cost

# Snapshot (compact) once this many op records have accumulated
# (reference fragment.go opN threshold; exact upstream value unverifiable —
# SURVEY.md Appendix B).
DEFAULT_SNAPSHOT_OP_THRESHOLD = 2048

# Anti-entropy checksum granularity: rows per block (reference
# fragment.go Blocks(), 100 rows per block — SURVEY.md §2 #3).
BLOCK_ROWS = 100


def _group_by_row(rows: np.ndarray, positions: np.ndarray):
    """Yield ``(row, positions_in_row)`` ascending by row, preserving
    each row's original position order — one stable sort instead of a
    per-row mask scan."""
    if rows.size == 0:
        return
    order = np.argsort(rows, kind="stable")
    sorted_rows = rows[order]
    sorted_pos = positions[order]
    uniq, starts = np.unique(sorted_rows, return_index=True)
    bounds = np.append(starts, sorted_rows.size)
    for i, r in enumerate(uniq.tolist()):
        yield int(r), sorted_pos[bounds[i]:bounds[i + 1]]


def _nobody() -> None:
    """``Fragment._on_change`` of a fragment no view owns."""


class Fragment:
    def __init__(
        self,
        path: str,
        index: str,
        field: str,
        view: str,
        shard: int,
        cache_type: str = CACHE_TYPE_RANKED,
        cache_size: int = DEFAULT_CACHE_SIZE,
        snapshot_threshold: int = DEFAULT_SNAPSHOT_OP_THRESHOLD,
        scope: str = "",
        wal=None,
        verify_on_load: bool = False,
        on_change=None,
    ):
        self.path = path
        self.index = index
        self.field = field
        self.view = view
        self.shard = shard
        self.scope = scope
        # Holder-level write-ahead log (storage/wal.py). None (direct
        # construction, unit tests) behaves exactly like the round-5
        # flush-only path; a holder-provided WAL switches _log_op to the
        # configured durability mode.
        self.wal = wal
        # Verified loads (storage/integrity.py): open() checks the
        # snapshot's block digests against the .checksums sidecar
        # written at snapshot time, so silent media rot surfaces as a
        # typed CorruptFragmentError instead of being decoded and
        # served. Hot paths pay nothing — the digests ride the blocks()
        # memo against the mutation counter.
        self.verify_on_load = verify_on_load
        self.wal_key = f"{index}/{field}/{view}/{shard}"
        # scope leads the id: residency keys and write-routing tags must
        # never collide across two Holders in one process (in-process
        # clusters, embedded multi-server) — same-named fragments on
        # different holders hold DIFFERENT replicas' data
        self.frag_id = (scope, index, field, view, shard)
        # view.view_name_bsi(field): plane matrices are only ever cached
        # for a BSI view's fragments (_PlanesSpec.resolve), so only
        # their writes look for any (_after_row_write)
        self._bsi_view = view == f"bsig_{field}"
        self.bitmap = RoaringBitmap()
        self.op_n = 0
        # monotonic content version: bumped on every mutation (see
        # _log_op); validates the row_counts memo
        self.mutations = 0
        # the owning view's ``touch`` (View.new_fragment), called when a
        # change to the bitmap or to row_cache is complete (_publish,
        # recalculate_cache); a fragment built alone has nobody to tell
        self._on_change = on_change or _nobody
        self._row_counts_memo: tuple | None = None
        self._blocks_memo: tuple | None = None
        self.snapshot_threshold = snapshot_threshold
        self.row_cache = new_row_cache(cache_type, cache_size)
        self._file = None
        self._open = False
        # One writer at a time per fragment (reference fragment.mu):
        # mutators, snapshot, and consistent-view readers (blocks,
        # serialize_snapshot) take this; row reads stay lock-free against
        # atomic container swaps.
        self.lock = threading.RLock()

    # ------------------------------------------------------------- lifecycle

    def open(self) -> "Fragment":
        os.makedirs(os.path.dirname(self.path), exist_ok=True)
        if os.path.exists(self.path):
            buf = read_file(self.path)  # disk-fault read seam
            if buf:
                # snapshot decode + (verify-on-load) sidecar digest
                # check BEFORE op replay: the sidecar describes exactly
                # the snapshot portion; trailing ops carry their own
                # CRCs. Any decode error or digest mismatch raises the
                # typed CorruptFragmentError — View.open quarantines
                # the file and moves on; direct callers see the error.
                self.bitmap, ops_at = load_verified(
                    buf, self.path, verify=self.verify_on_load
                )
                try:
                    self.op_n = replay_ops(self.bitmap, buf, ops_at)
                except DECODE_ERRORS as e:
                    raise CorruptFragmentError(
                        self.path, f"op replay failed: {e}", offset=ops_at,
                    ) from e
                if self.op_n == 0:
                    # the bitmap equals the bytes in hand: row-leaf
                    # misses read them through the container directory
                    # (kernels.flatten_rows) until the first write
                    self.bitmap.directory = kernels.directory_from_snapshot(
                        buf)
        else:
            with open(self.path, "wb") as f:
                f.write(serialize(self.bitmap))
        self.row_cache.load(self._cache_path())
        self._file = open(self.path, "ab")
        self._open = True
        if self.op_n > self.snapshot_threshold:
            self.snapshot()
        return self

    def close(self, discard: bool = False) -> None:
        """``discard=True`` is the delete-path close: the caller is
        about to unlink the files, so skip the snapshot / cache-save /
        op-tail-fsync work that would durably rewrite data the
        tombstone already covers (a resize cleanup over many shards
        would otherwise pay one full fsynced bitmap rewrite per
        fragment purely to delete it)."""
        with self.lock:
            if not self._open:
                return
            if not discard:
                if (self.wal is not None and self.wal.grouped
                        and self.op_n > 0):
                    # group mode keeps ops only in the WAL: a clean
                    # close must snapshot so the fragment file is
                    # self-contained (and the holder can truncate the
                    # WAL afterwards). A FAILED snapshot (full/dying
                    # disk) must not abort the close: the ops stay
                    # durable in their WAL segments — note_snapshot was
                    # never called, so segment GC keeps them and the
                    # next open's recover() replays them (the contract
                    # holder.close documents).
                    try:
                        self._snapshot_locked()
                    except OSError:
                        pass  # health already tripped by the snapshot
                try:
                    self.row_cache.save(self._cache_path())
                except OSError:
                    pass  # cache is derived data; recount rebuilds it
            elif self.wal is not None and self.wal.grouped:
                # delete path: a write in flight during the delete may
                # have appended AFTER the tombstone's seq — release the
                # key's segment pins or that op holds the WAL hostage
                self.wal.discard_key(self.wal_key)
            if self._file:
                if self.op_n > 0 and not discard:
                    # clean-close durability for the appended op tail
                    # (flush-only/per-op modes): one fsync per fragment,
                    # not one per op
                    try:
                        self._file.flush()
                        os.fsync(self._file.fileno())
                    except OSError:
                        pass
                self._file.close()
                self._file = None
            residency.global_row_cache().invalidate_fragment(self.frag_id)
            # delete/repair-swap closes change what this fragment will
            # answer next; clean closes are invalidated too (harmless —
            # the holder is going away or the file may change while shut)
            rescache.invalidate_write(self.scope, self.index, self.field,
                                      self.shard)
            self._open = False

    def _cache_path(self) -> str:
        return self.path + ".cache"

    # ----------------------------------------------------------------- reads

    def max_row_id(self) -> int:
        if not self.bitmap.keys:
            return 0
        return self.bitmap.keys[-1] >> 4  # key = bit >> 16; row = key >> 4

    def row_ids(self) -> list[int]:
        """Rows with at least one container present (superset of non-empty
        rows; exact after compaction since empty containers are dropped)."""
        return sorted({k >> 4 for k in self.bitmap.keys})

    def row_counts(self) -> tuple[np.ndarray, np.ndarray]:
        """Exact (row_ids, counts) for every non-empty row, in one pass
        over container metadata: a row spans 16 containers (key >> 4), and
        each container already knows its cardinality, so counting all rows
        is O(#containers) with no per-row scan and no bit materialization.

        This is the cold-path feed for TopN phase 1 and Rows()/GroupBy
        dimension discovery (reference fragment.top / executor Rows —
        SURVEY.md §3.4). The reference walks the ranked cache instead; at
        design scale (50k rows × 1k shards) a per-row count loop is
        millions of host calls, and a device pass would upload dense
        zeros — container metadata is strictly cheaper than either.

        Memoized against the fragment's mutation counter: GroupBy/Rows
        call this per fragment per query, and even the metadata pass is
        ~0.4 ms on a populated fragment — ~50 ms/query of host prelude
        at 64 shards x 2 dims. The version is snapshotted BEFORE the
        pass so a racing write can only force an extra recompute, never
        a stale hit. Callers must not mutate the returned arrays.
        """
        memo = self._row_counts_memo
        if memo is not None and memo[0] == self.mutations:
            return memo[1]
        version = self.mutations
        # flatten is the one sanctioned container walk (lock-free .get +
        # skip inside kernels.flatten); the row fold is pure vectorized
        # metadata math on the flat key/cardinality arrays
        flat = kernels.flatten(self.bitmap)
        if flat.n_containers == 0:
            out = (np.empty(0, np.int64), np.empty(0, np.int64))
        else:
            rows = flat.keys >> 4
            uniq, inv = np.unique(rows, return_inverse=True)
            counts = np.zeros(uniq.size, np.int64)
            np.add.at(counts, inv, flat.cards)
            out = (uniq, counts)
        for a in out:  # shared across callers: in-place edits would
            a.setflags(write=False)  # corrupt the memo silently
        self._row_counts_memo = (version, out)
        return out

    def row_words(self, row: int) -> np.ndarray:
        """Dense uint32[32768] for one row (host side): one flatten of
        the row's 16-container window, one batched decode kernel —
        byte-identical to the per-container ``dense_range_words32``
        walk it replaced (tests/test_roaring_kernels.py)."""
        base_key = (row << 20) >> 16
        flat = kernels.flatten(self.bitmap, base_key, base_key + 15)
        cost = current_cost()
        if cost is not None:
            # Container-taxonomy cost accounting (Chambi et al.
            # 1402.6407): ONE tally per kernel call, totals identical
            # to the retired per-container walk (the flat view holds
            # exactly the row's non-empty containers). Only residency
            # MISSES reach this path — steady-state hot queries pay
            # nothing here.
            cost.note_containers(*flat.kind_counts())
        return kernels.dense_words32(flat, base_key, 16)

    def device_row(self, row: int):
        """Device-resident dense row, decoded through the residency cache."""
        return residency.global_row_cache().get_row(
            self.frag_id + (row,), lambda: self.row_words(row)
        )

    def row_columns(self, row: int) -> np.ndarray:
        """Sorted in-shard column positions set in ``row``."""
        base = row << 20
        ids = self.bitmap.range_ids(base, base + SHARD_WIDTH)
        return (ids - np.uint64(base)).astype(np.uint64)

    def count_row(self, row: int) -> int:
        base = row << 20
        return self.bitmap.count_range(base, base + SHARD_WIDTH)

    def count(self) -> int:
        return self.bitmap.count()

    def contains(self, row: int, pos: int) -> bool:
        return (row << 20) + pos in self.bitmap

    def rows_containing(self, pos: int) -> list[int]:
        """All rows with bit ``pos`` set (Rows(column=)).

        One vectorized pass filters container metadata — for a fixed
        in-shard position only the (key & 15) == pos>>16 sub-container of
        each row can hold it — then an O(1)/O(log) membership probe per
        surviving container (Container.contains_low). No full-row decode,
        no per-row Python loop over all rows (reference executor.go Rows
        with a column filter walks rows too; at 50k rows that was the
        host-side cliff an earlier review flagged — container metadata is
        strictly cheaper than either a host walk or shipping a
        [rows, words] probe matrix to the device)."""
        keys = self.bitmap.keys
        if not keys:
            return []
        arr = np.fromiter(keys, np.int64, len(keys))
        cand = arr[(arr & 15) == (pos >> 16)]
        low = pos & 0xFFFF
        out = []
        for key in cand.tolist():
            c = self.bitmap.container(key)
            if c is not None and c.contains_low(low):
                out.append(key >> 4)
        return out

    # ---------------------------------------------------------------- writes

    def set_bit(self, row: int, pos: int) -> bool:
        self._check_pos(pos)
        with self.lock:
            changed = self.bitmap.add_ids([(row << 20) + pos]) > 0
            if changed:
                self._log_op(OP_ADD, [(row << 20) + pos])
                self._after_row_write(row, positions=[pos], added=True)
            return changed

    def clear_bit(self, row: int, pos: int) -> bool:
        self._check_pos(pos)
        with self.lock:
            changed = self.bitmap.remove_ids([(row << 20) + pos]) > 0
            if changed:
                self._log_op(OP_REMOVE, [(row << 20) + pos])
                self._after_row_write(row, positions=[pos], added=False)
            return changed

    def clear_row(self, row: int) -> int:
        """Remove every bit in a row (mutex fields, Store). Returns #cleared."""
        with self.lock:
            cols = self.row_columns(row)
            if cols.size == 0:
                return 0
            ids = cols + np.uint64(row << 20)
            removed = self.bitmap.remove_ids(ids)
            self._log_op(OP_REMOVE, ids)
            self._after_row_write(row, positions=cols, added=False)
            return removed

    def write_row_words(self, row: int, words: np.ndarray) -> None:
        """Replace a row wholesale from a dense word vector (Store(),
        anti-entropy block repair). Logged as clear+add."""
        from pilosa_tpu.ops.packing import unpack_bits

        with self.lock:
            old = self.row_columns(row) + np.uint64(row << 20)
            new = unpack_bits(words) + np.uint64(row << 20)
            if old.size:
                self.bitmap.remove_ids(old)
                self._log_op(OP_REMOVE, old)
            if new.size:
                self.bitmap.add_ids(new)
                self._log_op(OP_ADD, new)
            self._after_row_write(row)

    def bulk_import(self, rows, positions) -> int:
        """Batched import of (row, position) pairs (reference
        fragment.bulkImport — SURVEY.md §3.3). Returns #bits changed."""
        rows = np.asarray(rows, dtype=np.uint64)
        positions = np.asarray(positions, dtype=np.uint64)
        if rows.shape != positions.shape:
            raise ValueError("rows and positions must have identical shape")
        if positions.size and positions.max() >= SHARD_WIDTH:
            raise ValueError("position out of shard range")
        ids = (rows << np.uint64(20)) + positions
        with self.lock:
            changed = self.bitmap.add_ids(ids)
            if changed:
                self._log_op(OP_ADD, ids)
                self._after_rows_added(rows, positions)
            return changed

    def import_mutex(self, rows: np.ndarray, positions: np.ndarray) -> int:
        """Mutex-aware bulk import (reference fragment.bulkImportMutex —
        SURVEY.md §3.3): each imported column's previous row clears in
        the same locked pass, preserving the single-value invariant that
        plain ``bulk_import`` would silently break. Duplicate positions
        keep the LAST row (sequential set_bit semantics). Returns the
        number of columns whose bit was newly added (a moved column
        counts once; a no-op re-set counts zero — matching set_bit)."""
        rows = np.asarray(rows, np.uint64)
        positions = np.asarray(positions, np.uint64)
        if rows.shape != positions.shape:
            raise ValueError("rows and positions must have identical shape")
        if positions.size == 0:
            return 0
        if int(positions.max()) >= SHARD_WIDTH:
            raise ValueError("position out of shard range")
        keep = keep_last_unique(positions)
        rows, positions = rows[keep], positions[keep]
        from pilosa_tpu.roaring import merge_kernels

        with self.lock:
            # ONE batched probe yields every (current-row, column) pair
            # set among the batch columns — replacing the old
            # row_member scan over ALL fragment rows (O(rows x batch))
            cur_rows, cur_idx = merge_kernels.set_rows_for_positions(
                self.bitmap, positions)
            conflict = cur_rows.astype(np.uint64) != rows[cur_idx]
            target_set = np.zeros(positions.size, bool)
            target_set[cur_idx[~conflict]] = True

            add_parts: list = []
            rem_parts: list = []
            rows_added: list = []
            rows_removed: list = []
            for r, p in _group_by_row(cur_rows[conflict],
                                      positions[cur_idx[conflict]]):
                rem_parts.append((np.uint64(r) << np.uint64(20)) + p)
                rows_removed.append((r, p))
            add_m = ~target_set
            changed = int(add_m.sum())
            for r, p in _group_by_row(rows[add_m], positions[add_m]):
                add_parts.append((np.uint64(r) << np.uint64(20)) + p)
                rows_added.append((r, p))
            self._apply_batch_locked(add_parts, rem_parts,
                                     rows_added, rows_removed)
            return changed

    def _apply_batch_locked(self, add_parts, rem_parts,
                            rows_added, rows_removed) -> None:
        """Shared tail of the batched import paths (caller holds the
        fragment lock): one sorted add pass + one sorted remove pass,
        each logged as a single op record, then per-row residency/cache
        bookkeeping."""
        if add_parts:
            ids = np.sort(np.concatenate(add_parts))
            self.bitmap.add_ids(ids)
            self._log_op(OP_ADD, ids)
        if rem_parts:
            ids = np.sort(np.concatenate(rem_parts))
            self.bitmap.remove_ids(ids)
            self._log_op(OP_REMOVE, ids)
        feed = self._row_count_feed(len(rows_added) + len(rows_removed))
        for r, p in rows_added:
            self._after_row_write(int(r), positions=p, added=True,
                                  count_stat=False,
                                  row_count=feed(int(r)))
        for r, p in rows_removed:
            self._after_row_write(int(r), positions=p, added=False,
                                  count_stat=False,
                                  row_count=feed(int(r)))
        # the batch-amortized tail (same shape as _after_rows_added):
        # ONE stats bump, ONE result-cache write event, ONE heat record
        # for the whole batch — a bit_depth-32 BSI import must not take
        # the global result-cache lock 34x per shard
        n_rows = len(rows_added) + len(rows_removed)
        if n_rows:
            from pilosa_tpu.utils.stats import global_stats

            global_stats().count("fragment_row_writes", n_rows)
            self._publish()
            if current_cost() is not None:
                bits = sum(len(p) for _, p in rows_added)
                bits += sum(len(p) for _, p in rows_removed)
                global_heat().record_write(self.index, self.field,
                                           self.shard, n=float(bits),
                                           scope=self.scope)

    def import_bsi(self, positions: np.ndarray, stored: np.ndarray,
                   bit_depth: int, exists_row: int = 0,
                   offset_row: int = 2) -> int:
        """Batched BSI write (reference fragment.importValue — SURVEY.md
        §3.3): one lock + one add pass + one remove pass for a whole
        (position, stored-value) batch, in place of per-column
        ``set_value``'s per-bit fragment ops (1 + depth locked ops and
        op-log appends per column). ``positions`` must be duplicate-free
        (callers dedupe keep-last). Returns the number of COLUMNS whose
        existence or stored value changed — the same count a set_value
        loop would report."""
        positions = np.asarray(positions, np.uint64)
        stored = np.asarray(stored, np.uint64)
        if positions.size and int(positions.max()) >= SHARD_WIDTH:
            raise ValueError("position out of shard range")
        from pilosa_tpu.roaring import merge_kernels

        with self.lock:
            add_parts: list = []
            rem_parts: list = []
            rows_added: list = []
            rows_removed: list = []
            # exists row + every bit plane probed in ONE batched pass
            # (the old code ran a row_member scan per plane: 1+depth
            # full-keyspace probes per import)
            member = merge_kernels.member_matrix(
                self.bitmap,
                [exists_row] + [offset_row + i for i in range(bit_depth)],
                positions)
            exists_new = ~member[0]
            changed_cols = exists_new.copy()
            if exists_new.any():
                p = positions[exists_new]
                add_parts.append(
                    (np.uint64(exists_row) << np.uint64(20)) + p
                )
                rows_added.append((exists_row, p))
            for i in range(bit_depth):
                row = offset_row + i
                desired = ((stored >> np.uint64(i)) & np.uint64(1)) == 1
                cur = member[1 + i]
                add_m = desired & ~cur
                rem_m = ~desired & cur
                if add_m.any():
                    p = positions[add_m]
                    add_parts.append((np.uint64(row) << np.uint64(20)) + p)
                    rows_added.append((row, p))
                if rem_m.any():
                    p = positions[rem_m]
                    rem_parts.append((np.uint64(row) << np.uint64(20)) + p)
                    rows_removed.append((row, p))
                changed_cols |= add_m | rem_m
            if not changed_cols.any():
                return 0
            self._apply_batch_locked(add_parts, rem_parts,
                                     rows_added, rows_removed)
            return int(changed_cols.sum())

    def import_roaring(self, data: bytes) -> int:
        """Union a serialized roaring bitmap into this fragment (reference
        api.ImportRoaring fast path). Accepts either this framework's
        layout or the upstream pilosa layout (sniffed by cookie).
        Undecodable payloads (torn wire frames, corrupt import bodies)
        raise the typed CorruptFragmentError (a ValueError subclass, so
        existing 400 mappings hold)."""
        try:
            other, _ = load_any(data)
        except DECODE_ERRORS as e:
            raise CorruptFragmentError(
                self.path, f"import-roaring payload decode failed: {e}",
            ) from e
        return self.import_roaring_bitmap(other)

    def import_roaring_bitmap(self, other) -> int:
        """Union an already-parsed RoaringBitmap into this fragment."""
        return self.add_ids(other.to_ids())

    def add_ids_mutex(self, ids) -> int:
        """Anti-entropy repair into a SINGLE-VALUE field's fragment: add
        only bits for columns not already set in a different row locally.
        A pure union would resurrect rows a newer import cleared,
        breaking the mutex invariant on this replica; conflicting
        columns keep the LOCAL row (each replica stays self-consistent,
        and the divergence heals on the next write to the column, which
        clears other rows on every replica)."""
        ids = np.asarray(ids, np.uint64)
        if ids.size == 0:
            return 0
        # incoming duplicates for one column (a peer already holding a
        # double-set) collapse to one candidate row
        pos = ids & np.uint64(SHARD_WIDTH - 1)
        ids = ids[keep_last_unique(pos)]
        pos = ids & np.uint64(SHARD_WIDTH - 1)
        rows = ids >> np.uint64(SHARD_WIDTH_EXP)
        from pilosa_tpu.roaring import merge_kernels

        with self.lock:
            # one batched probe finds every locally-set (row, column)
            # pair among the incoming columns (was a row_member scan
            # over every fragment row)
            cur_rows, cur_idx = merge_kernels.set_rows_for_positions(
                self.bitmap, pos)
            keep = np.ones(ids.size, bool)
            conflict = cur_rows.astype(np.uint64) != rows[cur_idx]
            keep[cur_idx[conflict]] = False
            ids = ids[keep]
            return self.add_ids(ids) if ids.size else 0

    def add_ids_value(self, ids, exists_row: int = 0) -> int:
        """Anti-entropy repair into a BSI fragment: per COLUMN
        all-or-nothing. A column whose exists bit is set locally keeps
        its whole local value — unioning a peer's stale planes into a
        newer value would splice together a value no client ever wrote.
        Columns absent locally adopt the peer's planes wholesale."""
        ids = np.asarray(ids, np.uint64)
        if ids.size == 0:
            return 0
        pos = ids & np.uint64(SHARD_WIDTH - 1)
        with self.lock:
            local_exists = self.bitmap.row_member(exists_row, pos)
            ids = ids[~local_exists]
            return self.add_ids(ids) if ids.size else 0

    def add_ids(self, ids) -> int:
        """Union raw bit ids under the fragment lock (import-roaring,
        anti-entropy block repair). Returns #bits changed."""
        ids = np.asarray(ids, np.uint64)
        with self.lock:
            changed = self.bitmap.add_ids(ids)
            if changed:
                self._log_op(OP_ADD, ids)
                self._after_rows_added(
                    ids >> np.uint64(20), ids & np.uint64(SHARD_WIDTH - 1)
                )
            return changed

    # ------------------------------------------------------------ durability

    def _log_op(self, op: int, ids) -> None:
        self.mutations += 1
        if self._file is None:
            return
        wal = self.wal
        record = encode_op(op, ids)
        if wal is not None and wal.grouped:
            # group commit (storage/wal.py): the record rides the
            # holder WAL; ONE fsync per group of concurrent writers.
            # The ACK point (server/api.py) barriers on the WAL, so the
            # mutator itself never blocks on the disk — and never waits
            # while holding this fragment's lock.
            wal.append_op(self.wal_key, record, self)
        else:
            self._file.write(record)
            self._file.flush()
            if wal is not None and wal.mode == MODE_PER_OP:
                # true per-write durability (round 5 only flush()ed —
                # OS-buffer-deep; see docs/OPERATIONS.md)
                try:
                    _faults.disk_check("fsync", self.path)
                    wal_fsync(self._file.fileno())
                except OSError as e:
                    self._trip_health(f"per-op fsync of {self.path}: {e}")
                    raise
        self.op_n += 1
        if self.op_n > self.snapshot_threshold:
            self.snapshot()

    def apply_recovered(self, op: int, ids) -> None:
        """Apply one replayed WAL op (holder open, single-threaded at
        recovery; also the CDC follower's live tail-apply path): the
        bitmap mutation without logging — the caller snapshots and
        recounts caches once per touched fragment afterwards."""
        ids = np.atleast_1d(np.asarray(ids, np.uint64))
        with self.lock:
            if op == OP_ADD:
                self.bitmap.add_ids(ids)
            else:
                self.bitmap.remove_ids(ids)
            self.mutations += 1
        cache = residency.global_row_cache()
        cache.invalidate_fragment(self.frag_id)
        # route the write to dependent STACKED leaves too (positions
        # unknown -> conservative invalidation, not in-place patching):
        # a crash-recovery replay has none resident, but the CDC
        # follower applies these against a live serving cache
        for row in np.unique(ids >> np.uint64(20)).tolist():
            cache.apply_write(residency.WriteEvent(
                self.index, self.field, self.view, self.shard, row,
                scope=self.scope,
            ))
        # row_cache is as it was: the caller recounts it afterwards
        # (recalculate_cache publishes again)
        self._publish()

    def snapshot(self) -> None:
        """Compact: rewrite the file as a clean snapshot, dropping the log
        (reference fragment.snapshot — SURVEY.md §3.3)."""
        with self.lock:
            # the fragment stays open and, under the lock, still equals
            # the bytes serialized (not what a write fault made of them)
            self.bitmap.directory = kernels.directory_from_snapshot(
                self._snapshot_locked())

    def _snapshot_locked(self) -> bytes:
        """Returns the snapshot's bytes as serialized."""
        if self._file:
            self._file.close()
        tmp = self.path + ".snapshotting"
        snapshot = serialize(self.bitmap)
        try:
            payload = _faults.disk_filter_write(  # torn-write seam
                self.path, snapshot
            )
            with open(tmp, "wb") as f:
                f.write(payload)
                f.flush()
                _faults.disk_check("fsync", self.path)
                os.fsync(f.fileno())
            # the OLD sidecar must die BEFORE the new snapshot is
            # published: a crash between the rename and the new sidecar
            # landing would otherwise pair the new snapshot with stale
            # digests, and verify-on-load would quarantine a perfectly
            # healthy file (a MISSING sidecar only downgrades the next
            # open to an unverified load — safe)
            try:
                os.unlink(self.path + CHECKSUM_SUFFIX)
            except FileNotFoundError:
                pass
            os.replace(tmp, self.path)
        except OSError as e:
            # a failed snapshot (ENOSPC, EIO) flips the node to the
            # read-only storage_degraded mode instead of surfacing a
            # raw traceback through the write path; the old file is
            # intact (tmp-then-rename), so reads keep serving
            self._trip_health(f"snapshot of {self.path}: {e}")
            if self._open and self._file is not None and self._file.closed:
                try:
                    self._file = open(self.path, "ab")
                except OSError:
                    self._file = None
            raise
        # a crash between the rename and the directory entry reaching
        # disk can lose the whole snapshot: rename durability needs the
        # parent fsynced too
        fsync_dir(os.path.dirname(self.path))
        # checksum sidecar: the block digests of exactly these bytes,
        # for verify-on-load and the background scrubber. Best-effort —
        # a torn/missing sidecar downgrades to an unverified load, it
        # never condemns the healthy snapshot beside it.
        try:
            save_checksums(self.path + CHECKSUM_SUFFIX, self.blocks())
        except OSError as e:
            self._trip_health(f"checksum sidecar of {self.path}: {e}")
        if self.wal is not None:
            # every op of this fragment appended so far (the lock is
            # held, so the seq covers them all) is in the snapshot —
            # release them from WAL segment retention
            self.wal.note_snapshot(self.wal_key, self.wal.current_seq())
        self.op_n = 0
        if self._open:
            self._file = open(self.path, "ab")
        return snapshot

    def _row_count_feed(self, n_rows: int):
        """Row-count source for batch bookkeeping: above a few touched
        rows, ONE ``row_counts()`` metadata pass feeds every
        ``row_cache.add`` instead of a ``count_row`` probe per row.
        Callers invoke this AFTER the batch's mutations are applied (the
        memo keys on the mutation counter). Small batches return None
        per row — the point-write probe is cheaper than the full pass."""
        if n_rows <= 8:
            return lambda row: None
        r_ids, r_counts = self.row_counts()

        def feed(row: int):
            i = int(np.searchsorted(r_ids, row))
            if i < r_ids.size and int(r_ids[i]) == row:
                return int(r_counts[i])
            return 0  # the batch emptied this row

        return feed

    def _after_rows_added(self, rows: np.ndarray, positions: np.ndarray) -> None:
        """Per-row write bookkeeping for bulk adds: group positions by row
        with one sort instead of a per-row mask scan (which is O(n·rows)
        and turns large imports quadratic)."""
        groups = list(_group_by_row(rows, positions))
        feed = self._row_count_feed(len(groups))
        for row, p in groups:
            self._after_row_write(
                row, positions=p, added=True, count_stat=False,
                row_count=feed(row),
            )
        # one counter bump for the whole batch: parallel ingest workers
        # would otherwise serialize on the global stats lock per row
        from pilosa_tpu.utils.stats import global_stats

        global_stats().count("fragment_row_writes", len(groups))
        # ONE result-cache write event per batch (the per-row calls
        # above pass count_stat=False and skip theirs) — unconditional:
        # the cost kill switch gates accounting, never correctness
        self._publish()
        if current_cost() is not None:
            # one heat record per batch, weighted by written bits — same
            # lock-amortization reasoning as the counter above. Gated on
            # an ACTIVE request context (like the access side): bulk
            # imports record at the API layer, and background
            # anti-entropy repair (add_ids/write_row_words with neither)
            # must not rank merely-repaired shards hot
            global_heat().record_write(self.index, self.field, self.shard,
                                       n=float(rows.size),
                                       scope=self.scope)

    def _after_row_write(self, row: int, positions=None, added=None,
                         count_stat: bool = True,
                         row_count: int | None = None) -> None:
        """Invalidate this fragment's own device entries and route the
        write to dependent stacked leaves for in-place patching (instead
        of the old global generation purge — one Set() must not evict
        unrelated resident leaves), in one call of the row cache: the
        patched leaves are swapped in when it returns. Batch paths pass
        ``row_count`` from one shared ``row_counts()`` metadata pass;
        point writes leave it None and pay one ``count_row``."""
        residency.global_row_cache().row_written(
            self.frag_id,
            residency.WriteEvent(
                self.index, self.field, self.view, self.shard, row,
                positions=positions, added=added, scope=self.scope,
            ),
            planes=self._bsi_view,
        )
        if row_count is None:
            row_count = self.count_row(row)
        self.row_cache.add(row, row_count)
        if count_stat:
            # the WAL-visible write point: a cached result depending on
            # this (index, field, shard) must die BEFORE the write's
            # durability barrier releases its 200 — the in-memory
            # mutation above is already reader-visible, so an acked
            # write can never be masked by stale cached bytes.
            # Batch paths (count_stat=False, from _after_rows_added)
            # invalidate once per batch instead of once per row.
            self._publish()
            from pilosa_tpu.utils.stats import global_stats

            global_stats().count("fragment_row_writes", 1)
            if current_cost() is not None:
                # per-shard write heat (docs/OBSERVABILITY.md) for PQL
                # writes — an active request context only: bulk imports
                # record at the API layer, background repair records
                # nothing (see _after_rows_added)
                global_heat().record_write(self.index, self.field,
                                           self.shard, scope=self.scope)

    def _publish(self) -> None:
        """The last step of every change to the bitmap, after the ranked
        cache has it too (``_log_op`` moved ``mutations`` before
        ``row_cache.add`` ran, and ``top`` takes no lock, so that counter
        cannot vouch for the cache) and before the caller's
        acknowledgement: what is kept ABOVE the fragment turns over here,
        the cached results of its (index, field, shard) and the owning
        view's version (``View.touch``: the folds the plan stage reads)."""
        rescache.invalidate_write(self.scope, self.index, self.field,
                                  self.shard)
        self._on_change()

    def _check_pos(self, pos: int) -> None:
        if not 0 <= pos < SHARD_WIDTH:
            raise ValueError(f"position {pos} outside shard width {SHARD_WIDTH}")

    def _trip_health(self, reason: str) -> None:
        """Route a disk fault into the holder's StorageHealth latch
        (read-only degraded mode) via the WAL the storage tree already
        threads; direct-constructed fragments (wal=None) just raise."""
        health = getattr(self.wal, "health", None) if self.wal else None
        if health is not None:
            health.trip(reason)

    # ---------------------------------------------------- anti-entropy blocks

    def serialize_snapshot(self) -> bytes:
        """Consistent serialized snapshot of the live bitmap (resize /
        anti-entropy fragment-data fetch)."""
        with self.lock:
            return serialize(self.bitmap)

    def blocks(self) -> list[tuple[int, str]]:
        """Checksums of BLOCK_ROWS-row blocks for replica diffing
        (reference fragment.Blocks — SURVEY.md §3.5).

        Memoized against the mutation counter: the batched manifest route
        serves EVERY fragment's checksums per anti-entropy pass, and each
        recompute is a full to_ids materialization + hash walk. The
        version is snapshotted before the pass, so a racing write can
        only force an extra recompute, never a stale hit. Callers must
        not mutate the returned list."""
        memo = self._blocks_memo
        if memo is not None and memo[0] == self.mutations:
            return memo[1]
        version = self.mutations
        # flatten under the lock (metadata-only; containers are
        # immutable once published), materialize + digest outside it —
        # the id kernel no longer serializes writers
        with self.lock:
            flat = kernels.flatten(self.bitmap)
        ids = kernels.fragment_ids(flat)
        # one digest implementation (storage/integrity.py) shared by
        # the sync manifests, backup blob addressing, verify-on-load,
        # and the scrubber — every plane speaks the same checksums
        out = block_digests(ids, BLOCK_ROWS)
        self._blocks_memo = (version, out)
        return out

    def block_ids(self, block: int) -> np.ndarray:
        """All bit ids in one checksum block (for block repair)."""
        return self.blocks_ids([block])[block]

    def blocks_ids(self, blocks) -> dict[int, np.ndarray]:
        """Ids of MANY checksum blocks from one materialization: one
        flatten + one id kernel + one searchsorted slice per request —
        the sync block server used to pay a full ``to_ids`` PER block
        (O(blocks × population))."""
        with self.lock:
            flat = kernels.flatten(self.bitmap)
        ids = kernels.fragment_ids(flat)
        return kernels.block_slices(ids, blocks, BLOCK_ROWS)

    # -------------------------------------------------------------- TopN feed

    def recalculate_cache(self) -> None:
        """Rebuild the TopN row cache from exact container cardinalities
        and persist it (reference ``POST /recalculate-caches`` —
        fragment.RecalculateCache). Every write path maintains the cache
        incrementally; this is the authoritative recount for anything
        that drifted (a crash between bitmap flush and cache save, a
        hand-edited data dir)."""
        with self.lock:
            if not self._open:
                return  # racing index delete: nothing to repair, and
                        # save() would raise inside the removed dir
            fresh = new_row_cache(self.row_cache.kind,
                                  self.row_cache.max_size)
            rows, counts = self.row_counts()
            for r, c in zip(rows.tolist(), counts.tolist()):
                fresh.bulk_add(r, c)
            self.row_cache = fresh
            self._on_change()  # what top() answers may have changed
            self.row_cache.save(self._cache_path())

    def top(self, n: int = 10, row_ids=None):
        """Local TopN candidates: (row, count) pairs from the ranked cache,
        counts exact (recomputed) — phase 1 of the reference's two-phase
        TopN (SURVEY.md §3.4). Cold/none cache falls back to the exact
        O(#containers) metadata scan, not a per-row loop."""
        if row_ids is not None:
            pairs = [(r, self.count_row(r)) for r in row_ids]  # O(candidates)
        else:
            pairs = self.row_cache.top()
            if not pairs:
                rows, counts = self.row_counts()
                pairs = list(zip(rows.tolist(), counts.tolist()))
        pairs = [(r, c) for r, c in pairs if c > 0]
        pairs.sort(key=lambda rc: (-rc[1], rc[0]))
        return pairs[:n] if n else pairs


def build_index_manifest(idx) -> list[tuple[str, str, int, list]]:
    """Every (field, view, shard) → checksum-block list of one index, in
    deterministic order — the body of ``GET /internal/sync/manifest``.
    One response replaces the per-fragment ``fragment_blocks`` GET storm
    of the r5 anti-entropy pass (O(fragments) control RTTs → 1); the
    per-fragment blocks() memo keeps serving it cheap for unmutated
    fragments. Fragments with no data still appear (empty block list):
    the manifest doubles as the peer catalog for inventory walks."""
    out = []
    for fname, fld in sorted(idx.fields.items()):
        for vname, view in sorted(fld.views.items()):
            for shard in sorted(view.fragments):
                frag = view.fragment(shard)
                if frag is None:
                    continue
                out.append((fname, vname, shard, frag.blocks()))
    return out
