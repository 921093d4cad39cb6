"""Per-holder write-ahead log with group commit.

The reference (and rounds 1-5 here) made each acked write pay its own
op-log append+flush into the fragment's file — never an fsync, so "per
write durability" was OS-buffer-deep, and making it real would have put
one fsync on every ACK (the drag behind the mixed read+write
ceiling). This module is the classic
WAL trade instead: concurrent writers append op records into ONE
holder-level log, a commit thread issues ONE flush+fsync for the whole
group, and only then are all the waiting ACKs released — durability at
amortized cost (SURVEY.md §5.4; the same group-commit shape PR 3 used
for remote sub-queries, applied to the disk instead of the wire).

Three durability modes (``durability-mode`` ServerConfig knob):

- ``group`` (default): ops append to the WAL; fragment files hold only
  snapshots. An ACK barrier (server/api.py) releases once the record's
  group has been fsynced. Fragment snapshots (threshold compaction,
  checkpoint, clean close) make WAL segments garbage-collectable.
- ``per-op``: every op record fsyncs the fragment's own file before the
  mutator returns — true per-write durability, the honest version of
  what round 5 only claimed.
- ``flush-only``: the round-5 behavior, byte for byte — append+flush,
  no fsync anywhere on the write path. Survives SIGKILL (the OS buffer
  outlives the process) but not power loss. Kept for back-compat
  baselining.

Recovery: ``recover()`` replays surviving segments on holder open. Op
replay is a suffix re-application — each fragment's snapshot state is
some prefix of its op sequence, and re-applying ordered add/remove
records on top of a later state is idempotent (every bit ends at its
LAST op's value) — so replay needs no per-fragment positions, only two
invariants: a segment is deleted when every fragment with ops in it
has snapshotted at or past them, and segments are reclaimed
OLDEST-FIRST so the survivors are always a contiguous tail of the log
(out-of-order reclamation would leave a non-suffix op subset whose
replay resurrects stale bits). Replayed fragments are snapshotted
immediately and the segments dropped, so a restart in any mode starts
from self-contained fragment files.

WAL segment record layout (little-endian):
  magic uint16 = 0x574C ('WL'), rtype uint16 (1=op 2=tombstone),
  keylen uint16, bodylen uint32, crc32 uint32 (over key+body),
  key bytes (utf-8 "index/field/view/shard"; tombstone keys are either
  a "/"-terminated prefix for index/field deletes or an exact fragment
  key for shard deletes — see tombstone_matches),
  body bytes (for ops: one roaring/format.py encode_op record)
A torn tail (crash mid-append) is dropped, exactly like the fragment
op log's crash model.
"""

from __future__ import annotations

import logging
import os
import struct
import threading
import time
import weakref
import zlib

from pilosa_tpu.utils.tracing import (
    enter_thread_role,
    retire_thread_role,
    staged,
)

_LOG = logging.getLogger("pilosa_tpu.storage.wal")

MODE_GROUP = "group"
MODE_PER_OP = "per-op"
MODE_FLUSH_ONLY = "flush-only"
DURABILITY_MODES = (MODE_GROUP, MODE_PER_OP, MODE_FLUSH_ONLY)

# Group forming window / size bound (ServerConfig group-commit-max-ms /
# group-commit-max-ops): a record never waits longer than the window
# before its group's fsync starts, and a group never exceeds max-ops.
DEFAULT_GROUP_MAX_MS = 2.0
DEFAULT_GROUP_MAX_OPS = 256

# Rotate the active segment past this size; rotation checkpoints the
# fragments still pinning closed segments (snapshot, off the ACK path)
# so the WAL stays bounded by ~2 segments in steady state.
SEGMENT_MAX_BYTES = 16 << 20

WAL_MAGIC = 0x574C
REC_OP = 1
REC_TOMBSTONE = 2
_REC_HEADER = struct.Struct("<HHHII")

def wal_fsync(fd: int) -> None:
    """Op-log fsync: group WAL segments and per-op fragment files both
    route here (the one name a test replaces to count or fail them)."""
    os.fsync(fd)


def fsync_dir(path: str) -> None:
    """Best-effort directory fsync: after os.replace/create, the parent
    directory entry must also reach the platter or a power cut can lose
    the rename. Some filesystems (9p, certain network mounts) reject
    directory fsync — degrade silently rather than fail the write."""
    try:
        fd = os.open(path, os.O_RDONLY)
    except OSError:
        return
    try:
        os.fsync(fd)
    except OSError:
        pass
    finally:
        os.close(fd)


def encode_wal_record(rtype: int, key: str, body: bytes = b"") -> bytes:
    kb = key.encode()
    crc = zlib.crc32(kb + body)
    return _REC_HEADER.pack(WAL_MAGIC, rtype, len(kb), len(body), crc) + kb + body


def iter_wal_records(buf: bytes):
    """Yield (rtype, key, body) records; stops at a torn/corrupt tail
    (the crash model: the final group may be partially written)."""
    view = memoryview(buf)
    pos = 0
    while pos + _REC_HEADER.size <= len(view):
        magic, rtype, keylen, bodylen, crc = _REC_HEADER.unpack_from(view, pos)
        if magic != WAL_MAGIC:
            return
        end = pos + _REC_HEADER.size + keylen + bodylen
        if end > len(view):
            return  # torn write
        kb = bytes(view[pos + _REC_HEADER.size : pos + _REC_HEADER.size + keylen])
        body = bytes(view[pos + _REC_HEADER.size + keylen : end])
        if zlib.crc32(kb + body) != crc:
            return  # corrupt tail
        yield rtype, kb.decode(errors="replace"), body
        pos = end


def decode_op_body(body: bytes):
    """Parse one encode_op record back to (op, ids) — the WAL op body is
    exactly a fragment op-log record, checksum and all."""
    import numpy as np

    from pilosa_tpu.roaring.format import OP_MAGIC, _OP_HEADER

    if len(body) < _OP_HEADER.size:
        raise ValueError("wal: truncated op body")
    magic, op, id_count, crc = _OP_HEADER.unpack_from(body, 0)
    if magic != OP_MAGIC:
        raise ValueError("wal: bad op magic")
    raw = body[_OP_HEADER.size : _OP_HEADER.size + id_count * 8]
    if len(raw) != id_count * 8 or zlib.crc32(raw) != crc:
        raise ValueError("wal: corrupt op body")
    return op, np.frombuffer(raw, dtype="<u8")


def tombstone_matches(key: str, tomb: str) -> bool:
    """True when tombstone ``tomb`` deletes fragment ``key``.
    Index/field deletes write "/"-terminated prefixes ("idx/",
    "idx/fld/") and match everything under them; shard deletes write
    the exact fragment key and must match ONLY it — a bare startswith
    would make shard 1's tombstone swallow shards 10-19, 100-199, ..."""
    if tomb.endswith("/"):
        return key.startswith(tomb)
    return key == tomb


class TailGone(Exception):
    """The requested tail position is no longer served: either segment
    GC reclaimed it past the retention budget (``since < floor``) or the
    node restarted and its seq space reset (``since > durable``). The
    consumer must restart from a snapshot — invalidate everything it
    derived from the feed and resume from ``restart_from``."""

    def __init__(self, floor: int, durable: int):
        super().__init__(
            f"wal tail gone: floor={floor} durable={durable}")
        self.floor = floor
        self.restart_from = durable


class _Segment:
    __slots__ = ("path", "start_seq", "last_seq", "nbytes", "groups",
                 "end_seq")

    def __init__(self, path: str, start_seq: int):
        self.path = path
        self.start_seq = start_seq
        self.last_seq: dict[str, int] = {}  # op key -> last seq written
        self.nbytes = 0
        # CDC tail index: one (first_seq, byte_offset, byte_len, count)
        # entry per fsynced GROUP. Seqs within a group are consecutive
        # (append_op/tombstone each take exactly one seq and the batch
        # is a contiguous buffer slice), so the tail reader recovers
        # every record's seq from the group's first_seq alone. Offsets
        # cover durable bytes only — a group that failed its fsync is
        # never indexed, and the faulted segment is abandoned.
        self.groups: list[tuple[int, int, int, int]] = []
        self.end_seq = 0


class WriteAheadLog:
    """Holder-scoped op durability: group-commit segments in
    ``<data-dir>/.wal/`` plus the mode switch the fragment write path
    consults. One instance per Holder; fragments receive it down the
    storage tree and call ``append_op``/``note_snapshot``/``tombstone``;
    the API façade calls ``barrier()`` at every write ACK point."""

    def __init__(self, dir_path: str, mode: str = MODE_GROUP,
                 group_max_ms: float = DEFAULT_GROUP_MAX_MS,
                 group_max_ops: int = DEFAULT_GROUP_MAX_OPS,
                 fsync_fn=None):
        if mode not in DURABILITY_MODES:
            raise ValueError(
                f"invalid durability mode {mode!r} "
                f"(want one of {', '.join(DURABILITY_MODES)})"
            )
        self.dir = dir_path
        self.mode = mode
        self.group_max_ms = max(0.0, float(group_max_ms))
        self.group_max_ops = max(1, int(group_max_ops))
        self._fsync = fsync_fn or wal_fsync
        self._cond = threading.Condition()
        # (key, encoded record, seq, fragment) pending the next group
        self._buffer: list = []
        self._seq = 0
        self._durable_seq = 0
        self._group_open_t = 0.0
        self._last_group_size = 0
        self._error: BaseException | None = None
        # highest seq whose group's fsync FAILED: those records are
        # gone (torn tail of the poisoned segment), so a barrier for
        # them must raise forever — even after the disk recovers and
        # newer groups commit past them (clear_fault)
        self._failed_seq = 0
        self._closing = False
        # holder's StorageHealth latch (storage/integrity.py): a commit
        # fault trips the node read-only; its probe calls clear_fault()
        # when the disk answers again
        self.health = None
        self._thread: threading.Thread | None = None
        self._started = False
        # segment bookkeeping (commit/checkpoint threads + note_snapshot)
        self._seg_lock = threading.Lock()
        self._segments: list[_Segment] = []
        self._active: _Segment | None = None
        self._file = None
        self._snap_seq: dict[str, int] = {}
        self._tombstones: list[tuple[str, int]] = []
        self._dirty: dict[str, weakref.ref] = {}
        self._checkpointing = False
        # CDC cursor registry (storage for the /internal/wal/tail
        # plane): name -> highest seq the consumer has acknowledged.
        # Segment GC keeps covered segments the oldest cursor still
        # needs, up to cdc_retention_bytes; past the budget it reclaims
        # oldest-first anyway and advances _tail_floor so the laggard's
        # next read raises TailGone (restart-from-snapshot).
        self._cursors: dict[str, int] = {}
        self._tail_floor = 0
        self.cdc_retention_bytes = 64 << 20
        self.cdc_forced_reclaims = 0
        self.tail_reads = 0
        self.tail_bytes = 0
        self.cursors_dropped = 0
        # observability (metrics() exports zeros from scrape one)
        self.groups = 0
        self.fsyncs = 0
        self.appended_ops = 0
        self.wal_bytes = 0
        self.max_group_ops = 0
        self.checkpoints = 0
        self.recovered_ops = 0
        self.commit_recoveries = 0

    # ------------------------------------------------------------ lifecycle

    @property
    def grouped(self) -> bool:
        """True when ops should ride the WAL instead of fragment files."""
        return self.mode == MODE_GROUP and self._started

    def configure(self, mode: str | None = None,
                  group_max_ms: float | None = None,
                  group_max_ops: int | None = None) -> None:
        """Apply knobs before ``start()`` (Server.open wiring)."""
        if self._started:
            raise RuntimeError("wal already started")
        if mode is not None:
            if mode not in DURABILITY_MODES:
                raise ValueError(
                    f"invalid durability mode {mode!r} "
                    f"(want one of {', '.join(DURABILITY_MODES)})"
                )
            self.mode = mode
        if group_max_ms is not None:
            self.group_max_ms = max(0.0, float(group_max_ms))
        if group_max_ops is not None:
            self.group_max_ops = max(1, int(group_max_ops))

    def start(self) -> None:
        """Open the active segment and the commit thread (group mode
        only; the other modes need no WAL machinery)."""
        if self.mode != MODE_GROUP or self._started:
            return
        os.makedirs(self.dir, exist_ok=True)
        self._open_segment()
        self._started = True
        self._thread = threading.Thread(
            target=self._commit_loop, daemon=True, name="wal-commit"
        )
        self._thread.start()

    def _open_segment(self) -> None:
        with self._seg_lock:
            numbers = [int(os.path.basename(s.path).split(".")[0])
                       for s in self._segments]
            if os.path.isdir(self.dir):
                numbers += [
                    int(e.split(".")[0]) for e in os.listdir(self.dir)
                    if e.endswith(".log") and e.split(".")[0].isdigit()
                ]
            path = os.path.join(self.dir,
                                f"{max(numbers, default=0) + 1:08d}.log")
            if self._file is not None:
                self._file.close()
            self._file = open(path, "ab")
            seg = _Segment(path, self._seq + 1)
            self._segments.append(seg)
            self._active = seg
        fsync_dir(self.dir)

    def close(self) -> None:
        """Flush pending groups, stop the commit thread, and drop every
        segment whose ops are covered by durable snapshots (a clean
        close, where fragments snapshotted on their way down, leaves an
        empty WAL; a failed snapshot leaves its segment for recover())."""
        t = self._thread
        with self._cond:
            self._closing = True
            self._cond.notify_all()
        if t is not None:
            t.join(30)
            if t.is_alive():
                # the commit thread is still draining (or wedged in a
                # slow fsync): closing the segment file under it would
                # truncate the shutdown flush SILENTLY — its next write
                # hits a closed file. Leave the file to the thread,
                # keep every segment on disk for the next open's
                # recover(), and make the condition loud: future
                # barriers fail instead of acking volatile writes.
                with self._cond:
                    if self._error is None:
                        self._error = OSError(
                            "wal close timed out with commit backlog"
                        )
                    self._cond.notify_all()
                _LOG.error(
                    "wal: commit thread did not drain within 30s on "
                    "close; leaving segments in %s for recovery",
                    self.dir,
                )
                self._thread = None
                self._started = False
                return
        self._thread = None
        self._started = False
        with self._seg_lock:
            if self._file is not None:
                self._file.close()
                self._file = None
        self._gc_segments(include_active=True)

    # ------------------------------------------------------------ write path

    def append_op(self, key: str, record: bytes, frag=None) -> int:
        """Queue one op record for the next group; returns its sequence
        number (callers don't wait here — the ACK point's ``barrier()``
        does). Called under the fragment lock; the critical section is a
        list append."""
        with self._cond:
            if self._error is not None:
                raise OSError(f"wal commit failed: {self._error}")
            self._seq += 1
            seq = self._seq
            if not self._buffer:
                self._group_open_t = time.monotonic()
            self._buffer.append(
                (key, encode_wal_record(REC_OP, key, record), seq, frag,
                 REC_OP)
            )
            self._cond.notify_all()
        return seq

    def tombstone(self, prefix: str) -> None:
        """Record a delete: every fragment matched by ``prefix`` (a
        "/"-terminated index/field prefix, or one exact fragment key —
        tombstone_matches) is gone. Replay must not resurrect its ops
        into a later re-creation, and its pending ops stop pinning
        segments."""
        if not self.grouped:
            return
        with self._cond:
            self._seq += 1
            seq = self._seq
            if not self._buffer:
                self._group_open_t = time.monotonic()
            self._buffer.append(
                (prefix, encode_wal_record(REC_TOMBSTONE, prefix), seq, None,
                 REC_TOMBSTONE)
            )
            self._cond.notify_all()
        # _tombstones (consulted by _covered for segment GC) is updated
        # by the commit loop only once the record is DURABLE; callers
        # that need the delete on disk follow up with barrier()

    def note_snapshot(self, key: str, seq: int) -> None:
        """A fragment's snapshot (fsynced file + dir) now covers all its
        ops up to ``seq`` — they no longer pin WAL segments."""
        with self._seg_lock:
            if seq > self._snap_seq.get(key, -1):
                self._snap_seq[key] = seq

    def discard_key(self, key: str) -> None:
        """A deleted fragment's ops need no preserving: release their
        segment pins (coverage only — the durable tombstone still rules
        replay). Closes the delete race where an in-flight writer
        appends between the tombstone record and the fragment's close;
        that late op would otherwise pin its segment — and, with
        oldest-first reclamation, every newer one — until restart."""
        with self._cond:
            seq = self._seq
        with self._seg_lock:
            if seq > self._snap_seq.get(key, -1):
                self._snap_seq[key] = seq
            self._dirty.pop(key, None)

    def current_seq(self) -> int:
        with self._cond:
            return self._seq

    def durable_seq(self) -> int:
        with self._cond:
            return self._durable_seq

    # ------------------------------------------------------------- CDC tail

    def register_cursor(self, name: str, seq: int) -> None:
        """Register (or advance) a named tail cursor: the consumer has
        acknowledged everything up to ``seq``. Registration pins covered
        segments with records past ``seq`` against GC, within the
        retention budget. Cursors only move forward — a stale re-poll
        must not re-pin segments the registry already released."""
        with self._seg_lock:
            if seq >= self._cursors.get(name, -1):
                self._cursors[name] = seq

    def drop_cursor(self, name: str) -> None:
        with self._seg_lock:
            self._cursors.pop(name, None)

    def drop_cursors_for(self, node_id: str) -> int:
        """Drop every cursor a departed member registered here —
        names carry the owner as a ``:<node-id>`` suffix
        (``tailer:<id>``, ``follower:<id>``). A permanently departed
        node's cursor would otherwise pin WAL retention until
        force-reclaim (the cursor-leak satellite of the elastic
        plane). Returns the number dropped; counted in
        ``cdc_cursors_dropped_total``."""
        suffix = f":{node_id}"
        with self._seg_lock:
            names = [n for n in self._cursors if n.endswith(suffix)]
            for n in names:
                del self._cursors[n]
            self.cursors_dropped += len(names)
        return len(names)

    def cursors(self) -> dict[str, int]:
        with self._seg_lock:
            return dict(self._cursors)

    def tail_floor(self) -> int:
        with self._seg_lock:
            return self._tail_floor

    def read_tail(self, since: int, max_bytes: int = 1 << 20):
        """Read committed records after ``since`` in commit order.
        Returns ``(events, next_seq, durable_seq)`` where events is a
        list of ``(seq, rtype, key, body)`` and ``next_seq`` is the
        position to poll from next (== durable_seq when the read
        drained the feed; seqs of groups lost to storage faults are
        skipped over, never replayed). Raises TailGone when ``since``
        predates the retention floor or postdates the durable seq (the
        node restarted and its seq space reset)."""
        with self._cond:
            durable = self._durable_seq
        with self._seg_lock:
            if since < self._tail_floor or since > durable:
                raise TailGone(self._tail_floor, durable)
            plan: list[tuple[str, int, int, int, int]] = []
            planned_bytes = 0
            complete = True
            for seg in self._segments:
                for first, offset, nb, count in seg.groups:
                    if first + count - 1 <= since:
                        continue
                    if plan and planned_bytes + nb > max_bytes:
                        complete = False
                        break
                    plan.append((seg.path, offset, nb, first, count))
                    planned_bytes += nb
                if not complete:
                    break
        events: list[tuple[int, int, str, bytes]] = []
        try:
            for path, offset, nb, first, count in plan:
                with open(path, "rb") as f:
                    f.seek(offset)
                    buf = f.read(nb)
                seq = first
                for rtype, key, body in iter_wal_records(buf):
                    # cap at the durable snapshot: a group indexed
                    # between our durable read and the plan scan would
                    # otherwise emit seqs past next_seq
                    if since < seq <= durable:
                        events.append((seq, rtype, key, body))
                    seq += 1
        except FileNotFoundError:
            # GC raced the read and reclaimed a planned segment: the
            # consumer is behind the (just-advanced) floor
            with self._seg_lock:
                raise TailGone(self._tail_floor, durable) from None
        if complete:
            next_seq = durable
        else:
            next_seq = events[-1][0] if events else since
        self.tail_reads += 1
        self.tail_bytes += sum(nb for _, _, nb, _, _ in plan)
        return events, next_seq, durable

    def barrier(self, seq: int | None = None) -> None:
        """Block until every op appended so far (or up to ``seq``) is
        durable — the write ACK gate. No-op outside group mode (per-op
        fsyncs inline; flush-only promises nothing). Ops whose group's
        fsync FAILED raise forever: their bytes are a torn tail of a
        poisoned segment, and acking them after the disk recovers would
        be acking lost writes."""
        if not self.grouped:
            return
        with self._cond:
            target = self._seq if seq is None else seq
            # the lost-group check comes BEFORE the durable check: a
            # recovered WAL commits newer groups past the failed range,
            # and a late barrier for a lost seq must still raise — not
            # convert a lost write into a late ACK
            if 0 < target <= self._failed_seq:
                raise OSError(
                    "wal commit failed: this write's group was lost "
                    "to a storage fault"
                )
            while self._durable_seq < target:
                if self._error is not None:
                    raise OSError(f"wal commit failed: {self._error}")
                if self._closing and self._thread is None:
                    raise OSError("wal closed with ops pending")
                t = self._thread
                if t is not None and not t.is_alive():
                    # the commit thread died without recording an error
                    # (shouldn't happen — its whole body is guarded —
                    # but a hung barrier would wedge every write
                    # handler server-wide, so fail loudly instead)
                    raise OSError("wal commit thread died")
                self._cond.wait(1.0)

    def flush(self) -> None:
        self.barrier()

    def clear_fault(self) -> bool:
        """The disk answers again (StorageHealth probe succeeded): drop
        the recorded fault and resume committing buffered groups into a
        FRESH segment — the faulted segment's tail may be torn, and
        appending past a tear would bury good records behind it.
        Returns False (stay degraded) when the fresh segment itself
        cannot be opened."""
        with self._cond:
            if self._error is None:
                return True
        # open the fresh segment BEFORE clearing the error: the commit
        # loop only writes while _error is None, so clearing first
        # would let a woken group fsync into the faulted segment PAST
        # its torn tail — recover()'s sequential replay stops at the
        # tear and the acked group behind it would be unreachable
        if self._started:
            try:
                self._open_segment()
            except OSError:
                return False  # probe retries; _error stays set
        with self._cond:
            self._error = None
            self._cond.notify_all()
        self.commit_recoveries += 1
        return True

    # ---------------------------------------------------------- commit loop

    def _commit_loop(self) -> None:
        # any escape — fsync failure is handled inline below, but also
        # segment rotation (open/fsync-dir on a full disk), checkpoint
        # spawn, or a plain bug — must record an error and wake the
        # barrier waiters: a silently dead commit thread would wedge
        # every write ACK in the server forever
        enter_thread_role("wal_commit")
        try:
            self._run_commits()
        except BaseException as e:
            with self._cond:
                if self._error is None:
                    self._error = e
                self._cond.notify_all()
        finally:
            retire_thread_role()

    def _run_commits(self) -> None:
        while True:
            with self._cond:
                # with a fault recorded, hold off instead of burning a
                # retry loop against a sick disk: clear_fault() (driven
                # by the health probe) wakes this wait when the disk
                # answers again. The timeout exists ONLY in the faulted
                # state (belt-and-braces vs a missed notify); an idle
                # healthy node sleeps untimed like it always did.
                while ((not self._buffer or self._error is not None)
                       and not self._closing):
                    self._cond.wait(
                        0.5 if self._error is not None else None
                    )
                if self._closing and (not self._buffer
                                      or self._error is not None):
                    break  # shutdown (clean, or still-faulted: the
                    # surviving segments are recover()'s problem)
                # Self-latching forming window (the serving pipeline's
                # gather idiom): hold the group open up to max_ms only
                # when there is evidence of concurrency — this group
                # already has >1 record, or the previous group did. A
                # solo serial writer stays on the zero-wait path; a real
                # burst re-opens the window within one group.
                if (self.group_max_ms > 0 and not self._closing
                        and (len(self._buffer) > 1
                             or self._last_group_size > 1)):
                    deadline = self._group_open_t + self.group_max_ms / 1e3
                    while (len(self._buffer) < self.group_max_ops
                           and not self._closing):
                        left = deadline - time.monotonic()
                        if left <= 0:
                            break
                        self._cond.wait(left)
                batch = self._buffer[:self.group_max_ops]
                self._buffer = self._buffer[self.group_max_ops:]
                self._last_group_size = len(batch)
                if self._buffer:
                    self._group_open_t = time.monotonic()
            seg = self._commit_group(batch)
            if (seg is not None and seg.nbytes > SEGMENT_MAX_BYTES
                    and not self._closing):
                self._open_segment()
                self._spawn_checkpoint()

    @staged("wal.commit")
    def _commit_group(self, batch: list):
        """One group's write, flush, fsync and registration, up to the
        ``notify_all`` that releases its barriers: the commit thread's
        busy time (stage ``wal.commit``; the waits on ``_cond`` are
        outside it). Returns the segment it landed in, None when the
        group was lost to a disk fault."""
        end_seq = batch[-1][2]
        data = b"".join(rec for _, rec, _, _, _ in batch)
        try:
            with self._seg_lock:
                f, seg = self._file, self._active
                seg_path = seg.path
                f.write(data)
                f.flush()
            from pilosa_tpu.testing import faults as _faults

            _faults.disk_check("fsync", seg_path)
            self._fsync(f.fileno())
        except (OSError, ValueError) as e:
            # an fsync/write failure means this GROUP is lost (its
            # bytes are a torn tail): fail its barriers forever,
            # trip the holder into read-only storage_degraded mode,
            # and park the loop until the health probe's
            # clear_fault() says the disk answers again — instead
            # of dying and wedging the node until restart
            with self._cond:
                self._error = e
                self._failed_seq = max(self._failed_seq, end_seq)
                self._cond.notify_all()
            if self.health is not None:
                self.health.trip(f"wal commit fsync: {e}")
            return None
        with self._seg_lock:
            seg.groups.append(
                (batch[0][2], seg.nbytes, len(data), len(batch)))
            seg.end_seq = end_seq
            seg.nbytes += len(data)
            for key, _, seq, frag, rtype in batch:
                if rtype == REC_TOMBSTONE:
                    # register only NOW, post-fsync: _covered must
                    # never GC op segments on the strength of a
                    # tombstone a crash could still erase. And keep
                    # it out of last_seq — a tombstone is not an op
                    # and must not cover or pin anything as one.
                    self._tombstones.append((key, seq))
                    for k in list(self._dirty):
                        if tombstone_matches(k, key):
                            del self._dirty[k]
                    continue
                seg.last_seq[key] = seq
                if frag is not None:
                    self._dirty[key] = weakref.ref(frag)
        self.groups += 1
        self.fsyncs += 1
        self.appended_ops += len(batch)
        self.wal_bytes += len(data)
        self.max_group_ops = max(self.max_group_ops, len(batch))
        with self._cond:
            self._durable_seq = max(self._durable_seq, end_seq)
            self._cond.notify_all()
        return seg

    # ------------------------------------------------- checkpoint / segments

    def _covered(self, key: str, last_seq: int) -> bool:
        if self._snap_seq.get(key, -1) >= last_seq:
            return True
        return any(
            ts_seq >= last_seq and tombstone_matches(key, prefix)
            for prefix, ts_seq in self._tombstones
        )

    def _gc_segments(self, include_active: bool = False) -> None:
        """Reclaim covered segments OLDEST-FIRST, stopping at the first
        segment that must stay. In-order reclamation is load-bearing
        twice over: recover() replays every surviving record as a
        suffix re-application, so the survivors must be a contiguous
        tail of the log — deleting a newer covered segment while an
        older one lives would replay stale ops (an add whose later
        remove was reclaimed) on top of a snapshot that already folded
        them in — and it guarantees a tombstone's file outlives every
        older segment still holding ops it must kill on replay."""
        with self._seg_lock:
            keep = list(self._segments)
            min_cursor = (min(self._cursors.values())
                          if self._cursors else None)
            while keep:
                seg = keep[0]
                if not include_active and seg is self._active:
                    break
                if not all(
                    self._covered(k, s) for k, s in seg.last_seq.items()
                ):
                    break
                if (min_cursor is not None and seg.end_seq > min_cursor
                        and not include_active):
                    # a registered CDC cursor still needs this covered
                    # segment. Retain the contiguous covered prefix up
                    # to the retention budget; past it, reclaim
                    # oldest-first anyway and advance the tail floor so
                    # the laggard's next read answers TailGone instead
                    # of the WAL growing without bound.
                    pinned = 0
                    for s in keep:
                        if s is self._active or not all(
                            self._covered(k, q)
                            for k, q in s.last_seq.items()
                        ):
                            break
                        pinned += s.nbytes
                    if pinned <= self.cdc_retention_bytes:
                        break
                    self.cdc_forced_reclaims += 1
                try:
                    os.unlink(seg.path)
                except OSError:
                    break
                if seg.end_seq:
                    self._tail_floor = max(self._tail_floor, seg.end_seq)
                keep.pop(0)
            if len(keep) != len(self._segments):
                self._segments = keep
                fsync_dir(self.dir)
            # prune tombstones that predate every surviving segment:
            # they can never cover another surviving or future op, and
            # _covered scans this list for every key at every
            # checkpoint — unbounded growth under shard churn otherwise
            min_start = keep[0].start_seq if keep else self._seq + 1
            if self._tombstones:
                self._tombstones = [
                    (p, s) for p, s in self._tombstones if s >= min_start
                ]

    def _spawn_checkpoint(self) -> None:
        """Snapshot the fragments pinning closed segments, then GC —
        runs on its own thread so groups keep committing into the fresh
        segment while the checkpoint walks fragment locks."""
        with self._seg_lock:
            if self._checkpointing:
                return
            self._checkpointing = True
        threading.Thread(
            target=self._checkpoint, daemon=True, name="wal-checkpoint"
        ).start()

    def _checkpoint(self) -> None:
        try:
            with self._seg_lock:
                pinned: dict[str, int] = {}
                for seg in self._segments:
                    if seg is self._active:
                        continue
                    for key, seq in seg.last_seq.items():
                        if not self._covered(key, seq):
                            pinned[key] = max(pinned.get(key, 0), seq)
                frags = [(k, self._dirty.get(k)) for k in pinned]
            for key, ref in frags:
                frag = ref() if ref is not None else None
                if frag is None or not getattr(frag, "_open", False):
                    continue
                try:
                    frag.snapshot()  # calls back into note_snapshot
                except OSError:
                    pass  # segment stays pinned; retried next rotation
            self.checkpoints += 1
            self._gc_segments()
        finally:
            with self._seg_lock:
                self._checkpointing = False

    # -------------------------------------------------------------- recovery

    def recover(self, holder) -> int:
        """Replay surviving segments into the holder's fragments (open
        time, single-threaded, any mode — a group-mode crash must heal
        even if the restart is configured differently). Touched
        fragments are snapshotted and the segments deleted, so the
        post-open state is self-contained fragment files and an empty
        WAL regardless of mode history."""
        if not os.path.isdir(self.dir):
            return 0
        paths = sorted(
            os.path.join(self.dir, e) for e in os.listdir(self.dir)
            if e.endswith(".log")
        )
        if not paths:
            return 0
        records = []
        for p in paths:
            with open(p, "rb") as f:
                records.extend(iter_wal_records(f.read()))
        # tombstone pass: an op is dead if a LATER tombstone matches it
        tombs = [
            (i, key) for i, (rtype, key, _) in enumerate(records)
            if rtype == REC_TOMBSTONE
        ]
        # redo shard deletes: an exact-key tombstone whose fragment
        # files survived means the crash landed between the durable
        # tombstone and remove_fragment's unlinks — finish the delete
        # before replay. Safe for a same-key re-creation: oldest-first
        # segment GC means every post-tombstone op is still in the log
        # while its tombstone is, so replay rebuilds the new era in
        # full. (Index/field deletes need no redo: their directory is
        # renamed away atomically before the tombstone is written.)
        for _, tk in tombs:
            if tk.endswith("/"):
                continue
            parts = tk.split("/")
            if len(parts) != 4 or not parts[3].isdigit():
                continue
            idx = holder.index(parts[0])
            fld = idx.field(parts[1]) if idx is not None else None
            view = fld.views.get(parts[2]) if fld is not None else None
            if view is None:
                continue
            stale = view.discard(int(parts[3]))
            if stale is not None:
                stale.close(discard=True)
            frag_path = os.path.join(view.path, "fragments", parts[3])
            for p in (frag_path, frag_path + ".cache"):
                try:
                    os.unlink(p)
                except FileNotFoundError:
                    pass
            # the unlink must hit the platter BEFORE the segments (and
            # with them the tombstone) are durably erased below — a
            # power cut could otherwise revert the volatile unlink with
            # no tombstone left anywhere to redo it
            fsync_dir(os.path.dirname(frag_path))
        applied = 0
        touched: dict[str, object] = {}
        for i, (rtype, key, body) in enumerate(records):
            if rtype != REC_OP:
                continue
            if any(ti > i and tombstone_matches(key, tk) for ti, tk in tombs):
                continue
            frag = self._resolve_fragment(holder, key)
            if frag is None:
                continue  # index/field deleted out from under the log
            try:
                op, ids = decode_op_body(body)
            except ValueError:
                continue  # corrupt record: skip, keep replaying
            frag.apply_recovered(op, ids)
            touched[key] = frag
            applied += 1
        for frag in touched.values():
            frag.snapshot()           # durable, self-contained file
            frag.recalculate_cache()  # replay bypassed cache upkeep
        for p in paths:
            try:
                os.unlink(p)
            except OSError:
                pass
        fsync_dir(self.dir)
        self.recovered_ops += applied
        return applied

    @staticmethod
    def _resolve_fragment(holder, key: str):
        parts = key.split("/")
        if len(parts) != 4 or not parts[3].isdigit():
            return None
        index, field, view, shard = parts
        idx = holder.index(index)
        if idx is None:
            return None
        fld = idx.field(field)
        if fld is None:
            return None
        return fld.view(view, create=True).fragment(int(shard), create=True)

    # ---------------------------------------------------------------- stats

    def metrics(self) -> dict:
        with self._seg_lock:
            segments = len(self._segments)
            retained = sum(s.nbytes for s in self._segments)
            cursors = len(self._cursors)
            min_cursor = (min(self._cursors.values())
                          if self._cursors else 0)
            floor = self._tail_floor
        return {
            "cdc_cursors": cursors,
            "cdc_min_cursor_seq": min_cursor,
            "cdc_tail_floor": floor,
            "cdc_retained_bytes": retained,
            "cdc_forced_reclaims_total": self.cdc_forced_reclaims,
            "cdc_tail_reads_total": self.tail_reads,
            "cdc_tail_bytes_total": self.tail_bytes,
            "cdc_cursors_dropped_total": self.cursors_dropped,
            "groups_total": self.groups,
            "fsyncs_total": self.fsyncs,
            "appended_ops_total": self.appended_ops,
            "bytes_total": self.wal_bytes,
            "group_max_ops": self.max_group_ops,
            "checkpoints_total": self.checkpoints,
            "recovered_ops_total": self.recovered_ops,
            "commit_recoveries_total": self.commit_recoveries,
            "segments": segments,
        }
