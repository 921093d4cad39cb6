"""Background scrubber: paced disk verification + quarantine + repair.

Verified loads (storage/integrity.py) catch rot at OPEN; a long-lived
node can go months without reopening a fragment, so this pass walks the
owned fragments on a budget and re-derives each snapshot's block
digests from the BYTES ON DISK, comparing them against the checksum
sidecar written at snapshot time. The comparison is disk-vs-disk — the
live bitmap never enters the verdict, so a busy write path cannot mask
rot and a scrub cannot be fooled by a healthy in-memory copy of a
rotten file.

On confirmed corruption the fragment is handled by replica topology:

- **Replicas exist** (cluster, replica_n > 1): the fragment is
  QUARANTINED whole — dropped from the view (never served again),
  files renamed to ``.quarantine-*`` — and READ-REPAIRED from the
  healthy replicas over the existing ``sync/blocks`` delta wire
  (cluster._sync_fragment: one manifest RTT + one multi-block POST,
  conflict-aware merge rules intact), then snapshotted. Single-replica
  corruption heals with zero lost acked writes (every acked write also
  lives on the healthy replica) and zero corrupt bytes ever served.
- **No replicas**: the LIVE bitmap is the only other copy; the corrupt
  file is renamed aside and a fresh snapshot is written from memory
  (self-heal). If the live state itself was loaded from the corrupt
  file before verification existed, only a backup restore can help —
  the quarantine artifact is kept for that forensics.

Budget: ``scrub-interval`` seconds between passes (0 = disabled) and a
``scrub-max-bytes-per-sec`` token bucket (parallel/pacer.py RepairPacer
— the PR-4 shape), so a scrub storm cannot starve serving I/O.

A racing snapshot can swap file+sidecar mid-read and fake a mismatch:
every corruption verdict is re-derived under the fragment lock before
quarantine acts.
"""

from __future__ import annotations

import logging
import os
import threading
import time

import numpy as np

from pilosa_tpu.parallel.pacer import RepairPacer
from pilosa_tpu.roaring import kernels
from pilosa_tpu.storage.integrity import (
    CorruptFragmentError,
    global_integrity,
    quarantine_paths,
    verify_fragment_file,
)

_LOG = logging.getLogger("pilosa_tpu.parallel.scrub")


class Scrubber:
    """One holder's background integrity scrubber (Server.open wires it
    when ``scrub-interval`` > 0; ``POST /internal/scrub`` and the CLI
    ``check --host`` run single passes on demand)."""

    def __init__(self, holder, cluster=None, interval_s: float = 0.0,
                 max_bytes_per_sec: float = 0.0, stats=None, logger=None):
        self.holder = holder
        self.cluster = cluster
        self.interval_s = float(interval_s)
        self.pacer = RepairPacer(max_bytes_per_sec=max_bytes_per_sec,
                                 stats=stats)
        self.logger = logger or _LOG
        self._closed = threading.Event()
        self._thread: threading.Thread | None = None
        self._pass_lock = threading.Lock()
        # counters (api.integrity_metrics -> /metrics; zeros from
        # scrape one)
        self.passes = 0
        self.fragments_scanned = 0
        self.bytes_scanned = 0
        self.corruptions = 0
        self.repaired = 0
        self.self_healed = 0
        self.unrepaired = 0
        self.last_pass_s = 0.0

    # ------------------------------------------------------------ lifecycle

    def start(self) -> "Scrubber":
        if self.interval_s <= 0 or self._thread is not None:
            return self
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="storage-scrub")
        self._thread.start()
        return self

    def close(self) -> None:
        self._closed.set()

    def _loop(self) -> None:
        while not self._closed.wait(self.interval_s):
            try:
                self.scrub_pass()
            except Exception as e:  # noqa: BLE001 — the ticker must
                # outlive any one pass's surprise (a fragment deleted
                # mid-walk, a peer dying mid-repair)
                self.logger.warning("scrub pass failed: %s", e)

    # ----------------------------------------------------------------- pass

    def scrub_pass(self) -> dict:
        """Walk every owned fragment once; verify, quarantine, repair.
        Returns the pass record (also folded into the counters)."""
        with self._pass_lock:  # one pass at a time (ticker + on-demand)
            t0 = time.perf_counter()
            bytes_before = self.bytes_scanned
            out = {"scanned": 0, "bytes": 0, "corrupt": 0, "repaired": 0,
                   "self_healed": 0, "unrepaired": 0, "skipped": 0}
            # every LOCAL fragment is scanned — owned fragments because
            # this node serves them, stray (unowned, post-resize)
            # copies because cleanup_unowned defers their deletion
            # until an owner absorbs them, and absorbing rot would
            # replicate it; the heal policy differs by ownership below
            for iname, idx in list(self.holder.indexes.items()):
                for fname, field in list(idx.fields.items()):
                    for vname, view in list(field.views.items()):
                        for shard in sorted(view.fragments):
                            if self._closed.is_set():
                                break
                            frag = view.fragment(shard)
                            if frag is None:
                                continue
                            self._scrub_fragment(iname, idx, fname, view,
                                                 shard, frag, out)
            self.passes += 1
            self.last_pass_s = time.perf_counter() - t0
            out["bytes"] = self.bytes_scanned - bytes_before
            out["wall_s"] = round(self.last_pass_s, 3)
            return out

    def _verify_on_disk(self, frag, count: bool = True) -> None:
        """Disk-vs-disk verification of one fragment (the shared
        integrity.verify_fragment_file recipe, so the scrubber, the
        chaos oracle, and CLI check can never drift apart), paced and
        counted. ``count=False`` on the locked confirm re-read keeps
        the scanned/bytes counters one-per-fragment. Raises
        CorruptFragmentError."""
        try:
            # build_bitmap=False: the kernel parser digests the snapshot
            # bytes directly (roaring/kernels.py) — the scrubber never
            # needs the Container tree, only the verdict
            _bitmap, data, _ops_at = verify_fragment_file(
                frag.path, build_bitmap=False)
        except CorruptFragmentError:
            raise
        finally:
            # pace/count by what was actually read, even on corruption
            try:
                size = os.path.getsize(frag.path)
            except OSError:
                size = 0
            self.pacer.consume(size)
            if count:
                self.fragments_scanned += 1
                self.bytes_scanned += size

    def _scrub_fragment(self, iname, idx, fname, view, shard, frag,
                        out) -> None:
        try:
            self._verify_on_disk(frag)
        except OSError:
            out["skipped"] += 1  # deleted/rotated mid-walk: not rot
            return
        except CorruptFragmentError:
            pass  # confirm under the lock below
        else:
            out["scanned"] += 1
            return
        # Re-derive the verdict under the fragment lock: a snapshot
        # racing the unlocked read swaps file+sidecar and can fake a
        # mismatch; under the lock the pair is stable.
        with frag.lock:
            try:
                self._verify_on_disk(frag, count=False)
            except OSError:
                out["skipped"] += 1
                return
            except CorruptFragmentError as err:
                confirmed = err
            else:
                out["scanned"] += 1
                return
        out["scanned"] += 1
        out["corrupt"] += 1
        self.corruptions += 1
        global_integrity().count("verify_failures")
        self.logger.error("scrub: %s", confirmed)
        self._heal(iname, idx, fname, view, shard, frag, confirmed, out)

    # ----------------------------------------------------------------- heal

    def _repairable(self, iname: str, shard: int) -> bool:
        """Read-repair applies to fragments this node OWNS with other
        replicas holding copies. A stray (unowned) copy self-heals from
        its live bitmap instead: cleanup_unowned defers its deletion
        until an owner absorbs it, so its bits must survive locally —
        but re-fetching data this node does not own would be wrong."""
        if self.cluster is None:
            return False
        owners = self.cluster.shard_nodes(iname, shard)
        return (any(n.id == self.cluster.local.id for n in owners)
                and any(n.id != self.cluster.local.id for n in owners))

    def _fetch_replica_copy(self, iname, fname, vname, shard):
        """One healthy replica's COMPLETE fragment content over the
        sync wire (one manifest RTT + one multi-block sync/blocks POST
        per candidate; whole-fragment GET for legacy-wire peers), with
        every fetched block digest-verified against that replica's own
        manifest — the wire is not trusted either. Returns a
        RoaringBitmap or None when no replica could supply a verified
        copy."""
        from pilosa_tpu.roaring import RoaringBitmap
        from pilosa_tpu.storage.integrity import block_digests

        key = (fname, vname, shard)
        replicas = [n for n in self.cluster.shard_nodes(iname, shard)
                    if n.id != self.cluster.local.id]
        client = self.cluster.client
        for node in replicas:
            try:
                if client.supports_sync_manifest(node.uri):
                    entry = None
                    for f, v, s, blocks in client.sync_manifest(
                            node.uri, iname):
                        if (f, v, s) == key:
                            entry = list(blocks)
                            break
                    if entry is None:
                        continue  # replica lacks the fragment
                    wanted = [b for b, _ in entry]
                    bitmaps = client.sync_blocks(
                        node.uri, iname, [(fname, vname, shard, wanted)],
                    )
                    # one batched id kernel per block bitmap, one sort,
                    # one from_ids — not N add_ids merges + a re-walk
                    parts = [kernels.fragment_ids(kernels.flatten(bm))
                             for bm in bitmaps]
                    ids = (np.sort(np.concatenate(parts)) if parts
                           else np.empty(0, np.uint64))
                    if block_digests(ids) != [
                        (int(b), d) for b, d in entry
                    ]:
                        continue  # raced or torn transfer: next replica
                    return RoaringBitmap.from_ids(ids)
                # legacy-wire peer: whole-fragment GET, verified
                # against the peer's per-fragment block checksums (the
                # same no-trust bar as the manifest path — an
                # unverified transfer would launder a flipped bit into
                # a fragment every future scrub pronounces clean)
                blocks = client.fragment_blocks(node.uri, iname, fname,
                                                vname, shard)
                data = client.fragment_data(node.uri, iname, fname,
                                            vname, shard)
                if data:
                    from pilosa_tpu.roaring.format import load_any

                    copy, _ = load_any(data)
                    if block_digests(
                        kernels.fragment_ids(kernels.flatten(copy))
                    ) != [
                        (int(b), d) for b, d in blocks
                    ]:
                        continue  # raced or torn transfer: next replica
                    return copy
            except Exception:  # noqa: BLE001 — transport faults, torn
                # frames: the next replica may still supply a copy
                continue
        return None

    def _heal(self, iname, idx, fname, view, shard, frag, err, out) -> None:
        if self._repairable(iname, shard):
            # Read-repair, REPLACE not union: on-disk rot means the
            # local copy (disk AND whatever was loaded from it) is
            # untrustworthy, and union-merging suspect bits would
            # propagate a flipped-on bit cluster-wide through
            # anti-entropy. The replica copy is fetched FIRST, and the
            # swap (quarantine old artifacts, write the fresh fragment,
            # publish it in the view) is atomic from a reader's view —
            # queries see the old in-memory state or the repaired one,
            # never a missing fragment, so zero corrupt (or absent)
            # responses are served during the window.
            copy = self._fetch_replica_copy(iname, fname, view.name, shard)
            if copy is None:
                self.unrepaired += 1
                out["unrepaired"] += 1
                self.logger.error(
                    "scrub: no healthy replica copy of %s/%s/%s/%d; "
                    "leaving it in place until the next pass",
                    iname, fname, view.name, shard,
                )
                return
            try:
                with view._create_lock:
                    stale = view.fragments.get(shard)
                    if stale is None:
                        return  # concurrently deleted: deletion wins
                    stale.close(discard=True)
                    quarantine_paths(frag.path, reason=str(err))
                    fresh = view.new_fragment(shard).open()
                    fresh.import_roaring_bitmap(copy)
                    fresh.snapshot()  # durable + fresh sidecar
                    fresh.recalculate_cache()
                    view.adopt(shard, fresh)
            except OSError as e:
                self.unrepaired += 1
                out["unrepaired"] += 1
                self.logger.error(
                    "scrub: read-repair swap of %s/%s/%s/%d failed (%s)",
                    iname, fname, view.name, shard, e,
                )
                return
            global_integrity().count("read_repairs")
            self.repaired += 1
            out["repaired"] += 1
            self.logger.warning(
                "scrub: read-repaired %s/%s/%s/%d byte-identical from a "
                "healthy replica", iname, fname, view.name, shard,
            )
        else:
            # no replica to repair from (single-node, replica_n=1, or a
            # stray unowned copy): the live bitmap is the only other
            # copy — move the rotten file aside and rewrite the
            # snapshot from memory. (If the live state itself was
            # loaded from these bytes, restore from backup; the
            # quarantine artifact is kept for that call.)
            try:
                with frag.lock:
                    if frag._file is not None:
                        frag._file.close()
                        frag._file = None
                    quarantine_paths(frag.path, reason=str(err))
                    frag.snapshot()
            except OSError as e:  # a sick disk (ENOSPC mid-heal):
                # leave it for the next pass, after the probe clears
                self.unrepaired += 1
                out["unrepaired"] += 1
                self.logger.error(
                    "scrub: self-heal of %s/%s/%s/%d failed (%s)",
                    iname, fname, view.name, shard, e,
                )
                return
            global_integrity().count("self_heals")
            self.self_healed += 1
            out["self_healed"] += 1
            self.logger.warning(
                "scrub: re-snapshotted %s/%s/%s/%d from the live bitmap "
                "(no replica copy to read-repair from)",
                iname, fname, view.name, shard,
            )

    # -------------------------------------------------------------- metrics

    def metrics(self) -> dict:
        return {
            "scrub_passes_total": self.passes,
            "scrub_fragments_scanned_total": self.fragments_scanned,
            "scrub_bytes_total": self.bytes_scanned,
            "scrub_corruptions_detected_total": self.corruptions,
            "scrub_read_repairs_total": self.repaired,
            "scrub_self_heals_total": self.self_healed,
            "scrub_unrepaired_total": self.unrepaired,
            "scrub_last_pass_seconds": round(self.last_pass_s, 6),
            "scrub_paced_sleep_seconds": round(self.pacer.paced_sleep_s, 6),
        }
