"""Views: named fragment groups within a field.

Reference: view.go (SURVEY.md §2 #5) — ``"standard"`` for plain data, time
views ``standard_YYYY[MM[DD[HH]]]`` generated by a field's time quantum,
and ``bsig_<field>`` for BSI integer bit planes. ``views_by_time_range``
computes the minimal set of time views covering a [from, to) window using
the coarsest units the quantum provides (the reference's viewsByTimeRange).
"""

from __future__ import annotations

import datetime as dt
import os
import threading

import logging

from pilosa_tpu.storage.cache import CACHE_TYPE_RANKED, DEFAULT_CACHE_SIZE
from pilosa_tpu.storage.fragment import Fragment
from pilosa_tpu.storage.integrity import (
    CorruptFragmentError,
    global_integrity,
    quarantine_paths,
)
from pilosa_tpu.utils.tracing import note_plan_view_fold

_LOG = logging.getLogger("pilosa_tpu.storage.view")

VIEW_STANDARD = "standard"
_UNITS = "YMDH"


def view_name_bsi(field_name: str) -> str:
    return f"bsig_{field_name}"


def validate_quantum(q: str) -> str:
    if q == "":
        return q
    if not q or any(c not in _UNITS for c in q) or "".join(
        u for u in _UNITS if u in q
    ) != q:
        raise ValueError(f"invalid time quantum {q!r} (want a subsequence of YMDH)")
    return q


def _trunc(t: dt.datetime, unit: str) -> dt.datetime:
    if unit == "Y":
        return t.replace(month=1, day=1, hour=0, minute=0, second=0, microsecond=0)
    if unit == "M":
        return t.replace(day=1, hour=0, minute=0, second=0, microsecond=0)
    if unit == "D":
        return t.replace(hour=0, minute=0, second=0, microsecond=0)
    return t.replace(minute=0, second=0, microsecond=0)


def _advance(t: dt.datetime, unit: str) -> dt.datetime:
    if unit == "Y":
        return t.replace(year=t.year + 1)
    if unit == "M":
        return (t.replace(day=28) + dt.timedelta(days=4)).replace(day=1)
    if unit == "D":
        return t + dt.timedelta(days=1)
    return t + dt.timedelta(hours=1)


def _name(base: str, t: dt.datetime, unit: str) -> str:
    fmt = {"Y": "%Y", "M": "%Y%m", "D": "%Y%m%d", "H": "%Y%m%d%H"}[unit]
    return f"{base}_{t.strftime(fmt)}"


def views_for_time(base: str, quantum: str, t: dt.datetime) -> list[str]:
    """View names a timestamped write lands in — one per unit in the quantum
    (reference: a Set with a timestamp writes standard + each quantum view)."""
    return [_name(base, t, u) for u in quantum]


def views_by_time_range(
    base: str, quantum: str, t_from: dt.datetime, t_to: dt.datetime
) -> list[str]:
    """Minimal cover of [t_from, t_to) by quantum views, coarsest-first.

    Greedy: at each step emit the coarsest quantum unit whose aligned span
    fits inside the remaining window; fall back to the finest unit
    otherwise. Matches the reference's minimal-cover behavior for aligned
    windows; unaligned edges are covered at the finest granularity.
    """
    if not quantum:
        return []
    units = [u for u in _UNITS if u in quantum]  # coarse → fine
    finest = units[-1]
    out: list[str] = []
    t = _trunc(t_from, finest)
    while t < t_to:
        emitted = False
        for u in units:
            if _trunc(t, u) == t and _advance(t, u) <= t_to:
                out.append(_name(base, t, u))
                t = _advance(t, u)
                emitted = True
                break
        if not emitted:
            # remaining window smaller than the finest unit
            out.append(_name(base, t, finest))
            t = _advance(t, finest)
    return out


class View:
    def __init__(
        self,
        path: str,
        index: str,
        field: str,
        name: str,
        cache_type: str = CACHE_TYPE_RANKED,
        cache_size: int = DEFAULT_CACHE_SIZE,
        scope: str = "",
        wal=None,
        verify_on_load: bool = False,
    ):
        self.path = path  # .../views/<name>
        self.scope = scope
        self.wal = wal  # holder WAL, threaded down to fragments
        self.verify_on_load = verify_on_load
        self.index = index
        self.field = field
        self.name = name
        self.cache_type = cache_type
        self.cache_size = cache_size
        self.fragments: dict[int, Fragment] = {}
        # serializes first-write fragment creation: an unlocked
        # check-then-create under the threaded HTTP server hands two
        # concurrent writers DISTINCT Fragment objects for one path, and
        # the loser's acknowledged bits vanish (caught by the mixed
        # read/write serving load test, ~1-in-10 runs)
        self._create_lock = threading.Lock()
        # what the plan stage asks of every fragment, kept while nothing
        # changed (see touch, rows_fold, topn_fold)
        self.version = 0
        self._version_lock = threading.Lock()
        # kind -> (version, shard list, parameter, sorted ids): two entries
        self._folds: dict[str, tuple] = {}

    def new_fragment(self, shard: int) -> Fragment:
        """A fragment of this view, not opened and not yet in
        ``fragments`` (``adopt`` publishes it): its writes move this
        view's version."""
        return Fragment(
            os.path.join(self.path, "fragments", str(shard)),
            self.index,
            self.field,
            self.name,
            shard,
            cache_type=self.cache_type,
            cache_size=self.cache_size,
            scope=self.scope,
            wal=self.wal,
            verify_on_load=self.verify_on_load,
            on_change=self.touch,
        )

    def adopt(self, shard: int, frag: Fragment) -> None:
        """Publish ``frag`` as this view's fragment of ``shard`` (a first
        write's creation, the open, the scrubber's repaired copy)."""
        self.fragments[shard] = frag
        self.touch()

    def discard(self, shard: int) -> Fragment | None:
        """Take a fragment out of ``fragments`` (the caller closes it and
        deletes its files)."""
        frag = self.fragments.pop(shard, None)
        if frag is not None:
            self.touch()
        return frag

    def open(self) -> "View":
        frag_dir = os.path.join(self.path, "fragments")
        os.makedirs(frag_dir, exist_ok=True)
        for entry in sorted(os.listdir(frag_dir)):
            if not entry.isdigit():
                continue
            shard = int(entry)
            frag = self.new_fragment(shard)
            try:
                frag.open()
            except CorruptFragmentError as e:
                # rot on disk: quarantine the artifacts and start
                # WITHOUT the fragment — corrupt bytes are never
                # decoded into serving state, and anti-entropy / the
                # scrubber read-repairs the shard from a healthy
                # replica (docs/OPERATIONS.md integrity runbook)
                global_integrity().count("verify_failures")
                quarantine_paths(frag.path, reason=str(e))
                _LOG.error("startup quarantine of %s/%s/%s/%d: %s",
                           self.index, self.field, self.name, shard, e)
                continue
            self.adopt(shard, frag)
        return self

    def close(self, discard: bool = False) -> None:
        for frag in list(self.fragments.values()):
            frag.close(discard=discard)

    def fragment(self, shard: int, create: bool = False) -> Fragment | None:
        frag = self.fragments.get(shard)
        if frag is None and create:
            with self._create_lock:
                frag = self.fragments.get(shard)
                if frag is None:
                    frag = self.new_fragment(shard).open()
                    self.adopt(shard, frag)
        return frag

    def remove_fragment(self, shard: int, invalidate_derived: bool = True
                        ) -> None:
        """Close and delete one fragment (post-resize holder cleanup:
        this node no longer owns the shard). Removes the snapshot+op-log
        file and the row-cache sidecar; an in-flight reader holding the
        old Fragment object finishes against the closed in-memory bitmap.

        ``invalidate_derived=False`` lets a bulk caller (cleanup_unowned)
        batch the whole-field derived-entry invalidation to one pass per
        field instead of one per removed shard; such callers MUST call
        ``invalidate_derived_entries`` afterwards — deletion emits no
        WriteEvent, so stacked leaves holding this shard's slot would
        otherwise keep serving the removed data (and a later re-fetch
        only UNIONS on top of the stale base)."""
        self.remove_fragments([shard], invalidate_derived=invalidate_derived)

    def remove_fragments(self, shards, invalidate_derived: bool = True
                         ) -> int:
        """Bulk remove_fragment: ONE durable-tombstone barrier (one
        group-commit fsync) covers every removed shard — a post-resize
        cleanup dropping hundreds of shards would otherwise pay a
        serialized forming-window + journal fsync each. Returns
        #fragments removed.

        WAL replay must not resurrect these shards' ops into a later
        re-creation (post-resize re-own serves fresh data); the
        tombstones must be DURABLE before any file disappears — a crash
        in between would otherwise find durable ops, no tombstone, and
        a still-present field to recreate them in. The reverse window
        (durable tombstone, crash before the unlinks) is closed by
        recover()'s redo pass, which finishes the delete from the
        tombstone record."""
        frags = []
        with self._create_lock:
            for shard in shards:
                frag = self.discard(shard)
                if frag is not None:
                    frags.append(frag)
        if not frags:
            return 0
        if self.wal is not None:
            for frag in frags:
                self.wal.tombstone(frag.wal_key)
            self.wal.barrier()
        for frag in frags:
            frag.close(discard=True)
            from pilosa_tpu.storage.integrity import CHECKSUM_SUFFIX

            for p in (frag.path, frag.path + ".cache",
                      frag.path + CHECKSUM_SUFFIX):
                try:
                    os.unlink(p)
                except FileNotFoundError:
                    pass
        # the unlinks must reach the platter: the tombstone that
        # backstops them can be GC'd later, and a power cut undoing a
        # volatile unlink would then resurrect the shard with no
        # tombstone left for recover()'s redo pass to act on
        from pilosa_tpu.storage.wal import fsync_dir

        fsync_dir(os.path.join(self.path, "fragments"))
        if invalidate_derived:
            self.invalidate_derived_entries()
        return len(frags)

    def invalidate_derived_entries(self) -> None:
        """Drop every derived cache entry (stacked leaves) for this
        view's field; single-row entries go with each fragment close's
        invalidate_fragment."""
        from pilosa_tpu.storage import residency

        residency.global_row_cache().invalidate_tag(
            (self.scope, self.index, self.field)
        )

    def available_shards(self) -> list[int]:
        return sorted(self.fragments)

    # ------------------------------------------------- the plan stage's folds
    #
    # A request's plan asks every fragment of a view two things: which
    # rows are non-empty (Rows(), a GroupBy dimension) and which rows rank
    # (TopN's phase 1). Each fragment memoises its own answer; the fold
    # over the fragments is what grew with the shard count, in Python, on
    # the dispatcher thread, and nothing in it changes between two writes.
    # The view keeps the last fold of each kind under the version it was
    # made at.

    def touch(self) -> None:
        """Move the version. Called by whoever changed what a fragment of
        this view answers to either question, AFTER the bitmap and the
        ranked cache hold the change and before the write is acknowledged
        (``Fragment._publish`` at the end of every mutator, under the
        fragment's lock; ``recalculate_cache``; ``adopt``; ``discard``).

        Fragments of one view have locks of their own, so two writers get
        here at once, and ``version += 1`` is a read and a store: bare,
        writer A could read 5, B and C move it to 7 and A's late store
        put 6 back, the very value a reader took between B's and C's
        writes, whose walk missed C's: a stale fold under a current
        version. Under this lock the version only rises and no two
        touches leave the same value, so a fold made at snapshot ``s`` is
        served only while no touch has returned since ``s`` was read, and
        every write that had touched by then was in the bitmap and the
        ranked cache before the walk began."""
        with self._version_lock:
            self.version += 1

    def _fold(self, kind: str, shard_list: list[int], param, of_fragment,
              keep: bool) -> tuple[int, ...]:
        """Sorted union of ``of_fragment(frag)`` over ``shard_list``.
        ``keep``: the list is ``Index.available_shards``' own (one object
        while the index has the fragments it has, what
        ``Executor._shard_block`` keys on), so the fold is looked up and
        kept; a request that names its shards walks and leaves the
        entries alone. The version is read BEFORE the walk, as
        ``Fragment.row_counts`` reads ``mutations``: a write that lands
        during the walk has not touched yet, touches a higher version
        afterwards, and forces one more walk."""
        version = self.version
        if keep:
            entry = self._folds.get(kind)
            if (entry is not None and entry[0] == version
                    and entry[1] is shard_list and entry[2] == param):
                note_plan_view_fold(walked=False)
                return entry[3]
        ids: set[int] = set()
        for shard in shard_list:
            frag = self.fragment(shard)
            if frag is not None:
                ids.update(of_fragment(frag))
        out = tuple(sorted(ids))
        if keep:
            self._folds[kind] = (version, shard_list, param, out)
        note_plan_view_fold(walked=True)
        return out

    def rows_fold(self, shard_list: list[int], keep: bool) -> tuple[int, ...]:
        """Sorted ids of the rows with a bit in any of ``shard_list``'s
        fragments: one O(#containers) metadata pass per fragment (exact,
        no per-row count loop), memoised there by ``row_counts``."""
        return self._fold("rows", shard_list, None,
                          lambda frag: frag.row_counts()[0].tolist(), keep)

    def topn_fold(self, shard_list: list[int], overfetch: int,
                  keep: bool) -> tuple[int, ...]:
        """TopN's phase-1 candidates: the sorted union of every
        fragment's ``overfetch`` best-ranked rows. One entry: a second
        ``overfetch`` on the same view replaces it."""
        return self._fold("topn", shard_list, overfetch,
                          lambda frag: [r for r, _ in frag.top(overfetch)],
                          keep)
