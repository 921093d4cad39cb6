"""Cluster topology, membership, anti-entropy, and resize.

Reference: cluster.go + gossip/ + broadcast.go (SURVEY.md §2 #13–15,
§3.5). Semantics preserved:

- fixed 256 hash partitions; partition = hash(index, shard) % 256; each
  partition maps to ``replica_n`` nodes by walking a ring ordered by node
  id hash;
- a coordinator (lowest node id) owns schema/translation primacy and
  drives resize;
- schema deltas broadcast synchronously to every node (SendSync); node
  liveness via lightweight HTTP heartbeats instead of memberlist UDP
  gossip (the data plane that made gossip latency-critical in the
  reference is gone — intra-slice reduces ride ICI, and the control plane
  tolerates HTTP);
- anti-entropy: per replicated fragment, diff 100-row checksum blocks
  against peers and union-merge differing blocks; attr stores diff their
  own blocks the same way.

The TPU division of labor: this layer decides which *host* owns which
fragment files; inside a host, shards map onto the device mesh
(pilosa_tpu.parallel.mesh) and queries reduce over ICI, so cluster fan-out
only happens across hosts (DCN), exactly where the reference used HTTP.
"""

from __future__ import annotations

import hashlib
import os
import random
import threading
import time
import uuid

from pilosa_tpu.parallel.client import ClientError, InternalClient
from pilosa_tpu.roaring import kernels
from pilosa_tpu.testing import faults
from pilosa_tpu.utils.pool import concurrent_map

PARTITION_N = 256

STATE_STARTING = "STARTING"
STATE_NORMAL = "NORMAL"
STATE_RESIZING = "RESIZING"
STATE_DEGRADED = "DEGRADED"

# Consecutive failed heartbeats before the acting coordinator declares a
# node dead and re-replicates its shards (memberlist suspect→dead in the
# reference — SURVEY.md §2 #14, §5.3).
DEAD_HEARTBEATS = 3

# Control messages fenced by the cluster epoch: a copy stamped with an
# epoch older than the receiver's is rejected unapplied. These are the
# messages a partitioned ex-coordinator could otherwise use to un-gate
# queries, re-trigger resizes, or delete fragments with commands minted
# before the partition (docs/OPERATIONS.md failure model). Schema
# deltas and shard announcements stay unfenced — they are idempotent
# and monotonic, and fencing them would wedge mixed-epoch metadata.
FENCED_MESSAGES = frozenset(
    {"cluster-state", "resize-instruction", "resize-cleanup",
     "node-leave", "placement-update", "drain-update", "drain-leave"}
)

# Drain state machine (autopilot/elastic.py): the states a drain record
# moves through, gossiped cluster-wide so any failover coordinator can
# resume mid-drain. ACTIVE states block a second coordinated actuator
# (autopilot pass, another drain) from minting dueling resizes.
DRAIN_ACTIVE_STATES = frozenset({"pending", "moving", "handoff", "leaving"})


class RouteStats:
    """Process-wide write-routing counters (``routing_range_*`` series
    on /metrics — docs/OBSERVABILITY.md). Plain int adds, no lock:
    dashboards, not invariants."""

    __slots__ = ("range_slices", "range_fallbacks", "union_writes",
                 "wire_bytes")

    def __init__(self):
        self.range_slices = 0     # write slices narrowed to span owners
        self.range_fallbacks = 0  # eligible slices forced back to union
        self.union_writes = 0     # write sends routed by union fan-out
        self.wire_bytes = 0       # payload bytes shipped to remote owners

    def metrics(self) -> dict:
        return {
            "routing_range_slices_total": self.range_slices,
            "routing_range_fallback_total": self.range_fallbacks,
            "routing_range_union_writes_total": self.union_writes,
            "routing_range_wire_bytes_total": self.wire_bytes,
        }


_ROUTE_STATS = RouteStats()


def global_route_stats() -> RouteStats:
    return _ROUTE_STATS


class ClusterDegradedError(Exception):
    """This node cannot reach a majority of the member list (minority
    side of a partition): coordination and writes are refused, locally-
    owned reads still serve. Maps to HTTP 503 + Retry-After at the API
    edge (server/api.py)."""

    retry_after = 5.0


class Node:
    def __init__(self, id: str, uri: str):
        self.id = id
        self.uri = uri.rstrip("/")
        self.state = STATE_NORMAL

    def to_json(self) -> dict:
        return {"id": self.id, "uri": self.uri, "state": self.state}

    def __repr__(self):
        return f"Node({self.id}, {self.uri})"


def _hash64(data: str) -> int:
    return int.from_bytes(hashlib.blake2b(data.encode(), digest_size=8).digest(), "big")


class PlacementTable:
    """Epoch-stamped (index, shard) → owner-node-id override map — the
    autopilot's actuator surface, living BESIDE the hash ring rather
    than replacing it.

    The contract that makes mixed-version clusters safe: an EMPTY table
    leaves every ownership decision byte-identical to the pure hash
    walk, and an entry only applies while every listed owner is a live
    member — otherwise the shard falls back to hash placement, which is
    the view an override-unaware (older) node computes anyway. Entries
    are stamped with the cluster epoch the coordinator minted when it
    installed them; a stale copy (gossiped by a healed ex-coordinator)
    loses to any newer table. Persisted beside ``cluster.epoch`` with
    the same tmp+fsync+replace discipline; a corrupt file starts empty
    and the table is re-adopted from gossip (/status, placement-update
    messages) — same recovery posture as the epoch file."""

    def __init__(self, path: str | None = None, logger=None):
        self._lock = threading.Lock()
        self._overrides: dict[tuple[str, int], tuple[str, ...]] = {}
        # Sub-shard range splits (elastic plane): (index, shard) →
        # ((lo, hi, owner-ids), ...) column ranges, sorted by lo. A
        # split ALWAYS travels with a whole-shard override equal to the
        # union of its range owners, so an override-unaware (older)
        # peer — whose from_wire drops the separate "ranges" key —
        # computes the identical data placement from overrides alone;
        # ranges only refine which owner a range-aware reader PREFERS.
        # Empty ⇒ byte-identical to the plain override/hash behavior.
        self._ranges: dict[
            tuple[str, int], tuple[tuple[int, int, tuple[str, ...]], ...]
        ] = {}
        self.epoch = 0
        self._path = path
        self.logger = logger
        self.updates_applied = 0
        self.updates_rejected = 0
        self._load()

    def __len__(self) -> int:
        with self._lock:
            return len(self._overrides)

    def get(self, index: str, shard: int) -> tuple[str, ...] | None:
        with self._lock:
            return self._overrides.get((index, int(shard)))

    def snapshot(self) -> dict[tuple[str, int], tuple[str, ...]]:
        """Point-in-time copy, for callers that make several ownership
        decisions against ONE view (cleanup_unowned's frozen walk)."""
        with self._lock:
            return dict(self._overrides)

    def get_ranges(self, index: str, shard: int
                   ) -> tuple[tuple[int, int, tuple[str, ...]], ...] | None:
        with self._lock:
            return self._ranges.get((index, int(shard)))

    def ranges_snapshot(self) -> dict:
        with self._lock:
            return dict(self._ranges)

    @property
    def range_count(self) -> int:
        with self._lock:
            return sum(len(rs) for rs in self._ranges.values())

    @staticmethod
    def _clean_ranges(ranges) -> dict:
        cleaned: dict[
            tuple[str, int], tuple[tuple[int, int, tuple[str, ...]], ...]
        ] = {}
        for (index, shard), spans in (ranges or {}).items():
            rs = []
            for lo, hi, ids in spans or ():
                lo, hi = int(lo), int(hi)
                ids = tuple(str(i) for i in ids)
                if lo < hi and ids:
                    rs.append((lo, hi, ids))
            if rs:
                rs.sort(key=lambda r: r[0])
                cleaned[(str(index), int(shard))] = tuple(rs)
        return cleaned

    def replace(self, overrides: dict, epoch: int,
                ranges: dict | None = None) -> bool:
        """Install a whole new table stamped ``epoch``. Applies only
        when the stamp beats the current one (strictly newer — the
        coordinator mints a fresh epoch per change, so ties mean a
        duplicate delivery of the same table). ``ranges`` rides the
        same stamp: a table replaced without them (an older coordinator
        or a plain move plan) drops every split — correct, because the
        matching union overrides are gone too. Returns applied?"""
        cleaned: dict[tuple[str, int], tuple[str, ...]] = {}
        for (index, shard), ids in (overrides or {}).items():
            ids = tuple(str(i) for i in ids)
            if ids:
                cleaned[(str(index), int(shard))] = ids
        cleaned_ranges = self._clean_ranges(ranges)
        with self._lock:
            if int(epoch) <= self.epoch:
                self.updates_rejected += 1
                return False
            self._overrides = cleaned
            self._ranges = cleaned_ranges
            self.epoch = int(epoch)
            self.updates_applied += 1
            self._persist_locked()
        return True

    # ------------------------------------------------------------- wire

    @staticmethod
    def wire_entries(overrides: dict) -> list[dict]:
        return [
            {"index": index, "shard": shard, "nodes": list(ids)}
            for (index, shard), ids in sorted(overrides.items())
        ]

    @staticmethod
    def from_wire(entries) -> dict:
        out: dict[tuple[str, int], tuple[str, ...]] = {}
        for e in entries or []:
            try:
                key = (str(e["index"]), int(e["shard"]))
                ids = tuple(str(i) for i in e.get("nodes", []))
            except (KeyError, TypeError, ValueError):
                continue  # one malformed entry must not poison the rest
            if ids:
                out[key] = ids
        return out

    @staticmethod
    def wire_ranges(ranges: dict) -> list[dict]:
        return [
            {"index": index, "shard": shard,
             "spans": [{"lo": lo, "hi": hi, "nodes": list(ids)}
                       for lo, hi, ids in spans]}
            for (index, shard), spans in sorted(ranges.items())
        ]

    @staticmethod
    def ranges_from_wire(entries) -> dict:
        out: dict = {}
        for e in entries or []:
            try:
                key = (str(e["index"]), int(e["shard"]))
                spans = tuple(
                    (int(s["lo"]), int(s["hi"]),
                     tuple(str(i) for i in s.get("nodes", [])))
                    for s in e.get("spans", [])
                )
            except (KeyError, TypeError, ValueError):
                continue  # one malformed entry must not poison the rest
            spans = tuple(s for s in spans if s[0] < s[1] and s[2])
            if spans:
                out[key] = spans
        return out

    def to_json(self) -> dict:
        with self._lock:
            out = {
                "epoch": self.epoch,
                "overrides": self.wire_entries(self._overrides),
            }
            if self._ranges:
                # separate key: an override-unaware peer's from_wire
                # ignores it and still computes identical placement
                # from the union overrides above
                out["ranges"] = self.wire_ranges(self._ranges)
            return out

    # ------------------------------------------------------ persistence

    def _load(self) -> None:
        if self._path is None:
            return
        import json

        try:
            with open(self._path, "rb") as f:
                raw = f.read()
        except FileNotFoundError:
            return
        except OSError:
            return
        try:
            d = json.loads(raw)
            epoch = int(d.get("epoch", 0) or 0)
            overrides = self.from_wire(d.get("overrides", []))
            ranges = self.ranges_from_wire(d.get("ranges", []))
        except (ValueError, TypeError, AttributeError):
            # corrupt/torn file: start empty, re-adopt from gossip —
            # an override table is always reconstructible cluster state
            if self.logger is not None:
                self.logger.error(
                    "corrupt placement table %r: starting empty "
                    "(re-adopted from gossip)", self._path,
                )
            return
        self._overrides = overrides
        self._ranges = ranges
        self.epoch = epoch

    def _persist_locked(self) -> None:
        if self._path is None:
            return
        import json

        tmp = self._path + ".tmp"
        payload = {"epoch": self.epoch,
                   "overrides": self.wire_entries(self._overrides)}
        if self._ranges:
            payload["ranges"] = self.wire_ranges(self._ranges)
        try:
            with open(tmp, "w") as f:
                json.dump(payload, f)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, self._path)
        except OSError:  # table still applies in memory; gossip
            pass         # re-seeds it after a restart


class Cluster:
    """Shard→node assignment + membership + schema broadcast."""

    # How long the coordinator holds RESIZING waiting for peers'
    # resize-complete reports before releasing stragglers to anti-entropy
    # repair (tests shrink this).
    RESIZE_COMPLETE_TIMEOUT = 120.0

    def __init__(self, local: Node, peers: list[Node] | None = None,
                 replica_n: int = 1, holder=None, api=None,
                 insecure_tls: bool = False, pool_size: int = 8):
        self.local = local
        self.nodes: dict[str, Node] = {local.id: local}
        for p in peers or []:
            self.nodes[p.id] = p
        self.replica_n = replica_n
        self.holder = holder
        self.api = api  # set by Server after API construction
        self.client = InternalClient(insecure_tls=insecure_tls,
                                     pool_size=pool_size)
        self._state = STATE_NORMAL
        self._state_normal = threading.Event()
        self._state_normal.set()
        self._lock = threading.RLock()
        # bytes of the coordinator's translate log already applied locally;
        # resets on restart (re-apply is idempotent)
        self._translate_offset = 0
        # shards learned from peers' create-shard broadcasts (reference
        # CreateShardMessage): new remote shards become visible to queries
        # immediately instead of after a catalog-poll TTL
        self.known_shards: dict[str, set[int]] = {}
        self._announced_shards: dict[str, set[int]] = {}
        self._heartbeat_failures: dict[str, int] = {}
        self._resize_lock = threading.Lock()
        # async resize-job tracking (coordinator side): peers ack the
        # instruction immediately, fetch in a worker, and report
        # resize-complete; the coordinator holds RESIZING until every
        # pending peer reports (or the straggler timeout passes)
        self._resize_cv = threading.Condition()
        self._resize_job: str | None = None
        self._resize_pending: set[str] = set()
        self._resize_deadline = 0.0
        # Local fetch-job gate: while this node is pulling fragments it
        # does not yet have (self-join pull, resize-instruction worker),
        # it must stay RESIZING — a concurrently finishing resize path
        # (the coordinator's NORMAL broadcast, another local job's
        # completion) must not un-gate queries mid-fetch. The counter
        # tracks jobs in flight; _commanded_state remembers the last
        # externally commanded state so the final job restores it.
        self._gate_lock = threading.Lock()
        self._local_fetch_jobs = 0
        self._commanded_state = STATE_NORMAL
        self.logger = None  # set by Server; failures fall back to stderr
        # Anti-entropy pipeline width (ServerConfig sync-workers): owned
        # fragments diff/fetch/apply concurrently, so a pass tracks the
        # slowest peer's RTTs, not the sum over fragments — which also
        # shrinks the gated self-join window that rides sync_holder.
        self.sync_workers = 8
        # ---- partition tolerance (docs/OPERATIONS.md failure model) ----
        # Monotonic cluster epoch: minted by the acting coordinator (with
        # quorum) at each coordinated action, stamped on every fenced
        # control message, persisted as the highest epoch SEEN — so a
        # partitioned ex-coordinator healing back cannot act with
        # commands minted before the partition. Bare clusters (no holder
        # data dir) keep it in memory only.
        self._epoch_path = None
        data_dir = getattr(holder, "data_dir", None) if holder else None
        if data_dir:
            self._epoch_path = os.path.join(data_dir, "cluster.epoch")
        self.epoch = self._load_epoch()
        # Heat-weighted placement overrides (autopilot actuator): empty
        # table ⇒ byte-identical to the pure hash ring. Persisted beside
        # the epoch file; bare clusters keep it in memory only.
        self.placement = PlacementTable(
            path=(os.path.join(data_dir, "cluster.placement")
                  if data_dir else None),
        )
        # Ring memoization: _frozen_ring re-sorted (and re-blake2b'd
        # every node id) per shard per query fan-out. The generation
        # counter bumps at every membership mutation; the hash memo
        # never invalidates (a node id's hash is immutable), only
        # bounded. Belt-and-braces validation against a missed bump:
        # the cached ring must also match the live dict's identity and
        # size (membership changes always change one or the other,
        # except same-id object replacement — covered by the bump).
        self._ring_gen = 0
        self._ring_cache: tuple[int, int, int, list[Node]] | None = None
        self._ring_hash_memo: dict[str, int] = {}
        if getattr(self, "_epoch_file_corrupt", False):
            # rewrite the corrupt file NOW so the next restart reads a
            # clean value instead of re-diagnosing the same garbage
            self._persist_epoch_locked()
        # True while this node cannot reach a member-list majority: the
        # minority side of a partition serves locally-owned reads only
        # (writes shed 503, no resize, no cleanup, no death declaring).
        self.degraded = False
        # Tight dedicated timeout for liveness probes (heartbeat, quorum
        # checks, death corroboration): a hung peer's socket must not
        # stall the whole heartbeat loop and delay detection of OTHER
        # failures. ServerConfig heartbeat-timeout.
        self.heartbeat_timeout = 2.0
        # (epoch, action) every time THIS node acted as coordinator —
        # the chaos harness's ≤1-coordinator-per-epoch oracle reads it.
        # Bounded deques: on a long-lived server under churn these are
        # observability rings, not unbounded history (the harness
        # drains them between schedules, far below the caps).
        import collections as _collections

        self.acted_epochs = _collections.deque(maxlen=4096)
        # every cleanup_unowned decision (epoch, quorum, removed count)
        # — the no-deletion-without-quorum oracle reads it
        self.cleanup_log = _collections.deque(maxlen=1024)
        self._rejoin_lock = threading.Lock()
        self._left = False  # leave() called: never auto-rejoin
        # peers this node declared dead (id → uri): a node that ends up
        # SOLO probes them on heartbeat — if one answers, the "deaths"
        # were a partition and the sides reunite instead of serving as
        # split-brained 1-node clusters forever
        self._forgotten: dict[str, str] = {}
        # observability counters (api.cluster_metrics → /metrics)
        self.stale_epoch_rejects = 0
        self.heartbeat_probes = 0
        self.heartbeat_probe_failures = 0
        self.deaths_declared = 0
        self.deaths_vetoed = 0
        self.quorum_denials = 0
        self.rejoins = 0
        self.cleanups_deferred = 0
        # ---- elastic membership plane (autopilot/elastic.py) ----
        # The cluster-wide drain record: epoch-stamped at drain start,
        # rev-bumped per state change, gossiped via /status and
        # drain-update messages so a failover coordinator resumes the
        # state machine where the dead one left it. Empty = no drain
        # has ever run.
        self.drain_record: dict = {}
        # True on the drain TARGET while its groups move off (and after
        # it has left the ring): writes shed 503 with the "draining"
        # qos reason, reads keep serving the tail.
        self.draining = False
        # join-absorption counters: heat-ordered warm fetches and the
        # byte-verify outcomes of the gated self-join path
        self.warm_heat_ordered = 0
        self.warm_verified = 0
        self.warm_verify_failed = 0

    @property
    def state(self) -> str:
        return self._state

    @state.setter
    def state(self, value: str) -> None:
        self._state = value
        if value == STATE_NORMAL:
            self._state_normal.set()
        else:
            self._state_normal.clear()

    def wait_until_normal(self, timeout: float) -> bool:
        """Block until the cluster leaves RESIZING (queries are deferred
        during a resize, reference cluster state machine — SURVEY.md §2
        #13). Returns False on timeout."""
        return self._state_normal.wait(timeout)

    def _command_state(self, value: str) -> None:
        """Apply an externally commanded cluster state (coordinator
        broadcast, or the local coordinator path itself). A NORMAL
        command is deferred while local fetch jobs are in flight — the
        last job to finish restores it (_end_local_fetch)."""
        with self._gate_lock:
            self._commanded_state = value
            if value == STATE_NORMAL and self._local_fetch_jobs > 0:
                return
            self.state = value

    def _begin_local_fetch(self) -> None:
        with self._gate_lock:
            self._local_fetch_jobs += 1
            self.state = STATE_RESIZING

    def _end_local_fetch(self) -> None:
        with self._gate_lock:
            self._local_fetch_jobs -= 1
            if self._local_fetch_jobs <= 0:
                self.state = self._commanded_state

    # --------------------------------------------------- epoch / quorum

    def _load_epoch(self) -> int:
        """Read the persisted epoch high-water mark. A corrupt or torn
        ``cluster.epoch`` (binary garbage, a half-written tmp swap) is
        an OPERATIONAL event, not a crash: log it, start from 0, and
        re-persist a clean file — the real epoch is re-adopted from
        gossip on the first peer contact (adopt_epoch takes the max any
        peer reports), so fencing recovers to cluster truth without
        operator surgery."""
        self._epoch_file_corrupt = False
        if self._epoch_path is None:
            return 0
        try:
            with open(self._epoch_path, "rb") as f:
                raw = f.read(64).decode("ascii", errors="replace").strip()
        except FileNotFoundError:
            return 0
        except OSError as e:
            self._log_exception("cluster epoch read", e)
            return 0
        if not raw:
            return 0
        try:
            return int(raw)
        except ValueError:
            self._epoch_file_corrupt = True
            self._log_exception(
                "cluster epoch file",
                ValueError(
                    f"corrupt {self._epoch_path!r} (contents "
                    f"{raw[:32]!r}): re-adopting epoch from gossip"
                ),
            )
            return 0

    def _persist_epoch_locked(self) -> None:
        if self._epoch_path is None:
            return
        tmp = self._epoch_path + ".tmp"
        try:
            with open(tmp, "w") as f:
                f.write(str(self.epoch))
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, self._epoch_path)
        except OSError as e:  # epoch still advances in memory; fencing
            # degrades to per-process until the disk recovers
            self._log_exception("cluster epoch persist", e)

    def adopt_epoch(self, epoch: int) -> None:
        """Record a higher epoch seen on the wire (messages, peers'
        /status). The persisted high-water mark is what stops a
        RESTARTED ex-coordinator from reusing pre-partition epochs."""
        with self._lock:
            if epoch > self.epoch:
                self.epoch = int(epoch)
                self._persist_epoch_locked()

    def adopt_placement(self, d) -> bool:
        """Apply a placement table seen on the wire (placement-update
        message, a peer's /status, the join seed). Strictly-newer
        stamps win; anything malformed is ignored — the table is
        always reconstructible from the coordinator's next gossip."""
        if not isinstance(d, dict):
            return False
        try:
            epoch = int(d.get("epoch", 0) or 0)
        except (TypeError, ValueError):
            return False
        if epoch <= self.placement.epoch:
            return False  # cheap pre-check; replace() re-checks locked
        overrides = PlacementTable.from_wire(d.get("overrides", []))
        ranges = PlacementTable.ranges_from_wire(d.get("ranges", []))
        applied = self.placement.replace(overrides, epoch, ranges=ranges)
        if applied and self.logger is not None:
            self.logger.info(
                "%s adopted placement table epoch %d (%d overrides, "
                "%d split shards)",
                self.local.id, epoch, len(overrides), len(ranges),
            )
        return applied

    def apply_placement(self, overrides: dict,
                        ranges: dict | None = None) -> int:
        """Coordinator-side install of a new override table, the
        autopilot's single actuator: quorum-gated, epoch-minted (so the
        broadcast fences above every stale copy), persisted, and pushed
        to every peer. The caller then drives coordinate_resize() — new
        owners pull their fragments through the existing epoch-fenced
        machinery and the post-resize cleanup drops the old copies.
        ``ranges`` carries sub-shard splits (each split's union owners
        MUST also appear as a whole-shard override — the planner and
        drain both enforce it — so range-unaware peers compute the same
        data placement). Returns the minted epoch, or 0 when refused
        (not coordinator / no quorum)."""
        if not self.is_acting_coordinator:
            return 0
        if len(self.nodes) > 1 and not self.check_quorum():
            return 0
        epoch = self._bump_epoch()
        self._note_acted(epoch, "placement-update")
        self.placement.replace(overrides, epoch, ranges=ranges)
        message = {
            "type": "placement-update", "epoch": epoch,
            "overrides": PlacementTable.wire_entries(
                self.placement.snapshot()),
        }
        range_snapshot = self.placement.ranges_snapshot()
        if range_snapshot:
            message["ranges"] = PlacementTable.wire_ranges(range_snapshot)
        self._broadcast(message)
        return epoch

    # ------------------------------------------------------ drain record

    @property
    def drain_active(self) -> bool:
        """A drain is in flight somewhere in the cluster: one
        coordinated actuator at a time (autopilot skips, a second
        drain is refused)."""
        return self.drain_record.get("state") in DRAIN_ACTIVE_STATES

    def set_drain(self, record: dict) -> None:
        """Install + broadcast a drain record (coordinator side, or the
        failover coordinator taking the state machine over). The record
        is epoch-stamped once at drain start and rev-bumped per state
        change, so adopt_drain orders copies without re-minting."""
        with self._lock:
            self.drain_record = dict(record)
        self._apply_drain_side_effects()
        # wire epoch is the CURRENT cluster epoch, not the record's
        # minted-at-start epoch: the drain's own moving step bumps the
        # cluster epoch (apply_placement + resize), and a later state
        # advance stamped with the start epoch would be fenced as stale
        # by every peer. Fencing guards against stale SENDERS; record
        # ordering is (epoch, rev) inside adopt_drain.
        self._broadcast({
            "type": "drain-update",
            "epoch": self.epoch,
            "drain": dict(record),
        })

    def adopt_drain(self, d) -> bool:
        """Apply a drain record seen on the wire (drain-update message,
        a peer's /status, the join seed). Ordered by (epoch, rev) —
        strictly newer wins; malformed copies are ignored."""
        if not isinstance(d, dict) or not d:
            return False
        try:
            key = (int(d.get("epoch", 0) or 0), int(d.get("rev", 0) or 0))
        except (TypeError, ValueError):
            return False
        if key[0] <= 0:
            return False
        with self._lock:
            cur = self.drain_record
            cur_key = (int(cur.get("epoch", 0) or 0),
                       int(cur.get("rev", 0) or 0))
            if key <= cur_key:
                return False
            self.drain_record = dict(d)
        self._apply_drain_side_effects()
        return True

    def _apply_drain_side_effects(self) -> None:
        """Recompute the local ``draining`` latch from the current
        record: the TARGET sheds writes through every active state and
        stays shedding after "done" if it actually departed (_left) —
        a drained node is read-only until decommissioned. A target that
        never left (drain resolved via declare-dead, then the node
        healed and rejoined) un-sheds on the terminal state, because it
        is a full member again."""
        with self._lock:
            record = dict(self.drain_record)
        if record.get("target") != self.local.id:
            return
        state = record.get("state")
        was = self.draining
        self.draining = (state in DRAIN_ACTIVE_STATES
                         or (state == "done" and self._left))
        if was != self.draining and self.logger is not None:
            self.logger.info(
                "%s drain latch -> %s (drain state %s)",
                self.local.id, self.draining, state,
            )

    # ---------------------------------------------- departed-member CDC

    def drop_departed_cursors(self, node_id: str) -> int:
        """Drop WAL CDC cursors a permanently departed member
        registered on this node's WAL (``tailer:<id>``,
        ``follower:<id>``): a dead node's cursor would otherwise pin
        WAL retention until force-reclaim. Called on node-leave (drain
        handoff, graceful exit) and declare-dead; counted in the
        ``wal_cdc_cursors_dropped_total`` metric."""
        wal = getattr(self.holder, "wal", None) if self.holder else None
        if wal is None:
            return 0
        drop = getattr(wal, "drop_cursors_for", None)
        if drop is None:
            return 0
        dropped = drop(node_id)
        if dropped and self.logger is not None:
            self.logger.info(
                "dropped %d CDC cursor(s) for departed member %s",
                dropped, node_id,
            )
        return dropped

    # Epochs advance in strides, with each node minting into its own
    # hash slot: two coordinators acting CONCURRENTLY (possible in the
    # documented 2-member/asymmetric corner where both sides pass their
    # quorum check) mint provably DIFFERENT epochs, so "one authority
    # per epoch" holds by construction and the conflict resolves by
    # fencing — the higher epoch's commands win, the lower side's are
    # rejected everywhere (the Raft-term shape, without the election).
    EPOCH_STRIDE = 1024

    def _bump_epoch(self) -> int:
        """Mint the next epoch for a coordinated action (caller holds
        quorum — check_quorum adopted the cluster-wide max first, so
        the minted epoch exceeds anything any reachable peer has
        seen)."""
        with self._lock:
            slot = _hash64(self.local.id) % self.EPOCH_STRIDE
            self.epoch = ((self.epoch // self.EPOCH_STRIDE + 1)
                          * self.EPOCH_STRIDE + slot)
            self._persist_epoch_locked()
            return self.epoch

    def quorum_size(self) -> int:
        """Majority of the CURRENT member list (the list quorum-gated
        actions froze their decisions against)."""
        with self._lock:
            return len(self.nodes) // 2 + 1

    def check_quorum(self) -> bool:
        """Live quorum probe: concurrently /status every member with the
        tight heartbeat timeout; this node has quorum when itself plus
        the reachable peers form a member-list majority. Adopts any
        higher epoch a peer reports (so an action minted next fences
        above everything the majority has seen) and updates
        ``degraded``.

        Two-node special case: a majority of 2 is 2, so a lone survivor
        could never fail over — the reference has the same blind spot
        (memberlist cannot distinguish peer death from a cut link with
        n=2). A 2-node survivor is allowed to act; the tradeoff is
        documented in docs/OPERATIONS.md: run 3+ nodes for partition
        safety."""
        with self._lock:
            peers = [n for n in self.nodes.values()
                     if n.id != self.local.id]
            n = len(peers) + 1
        if not peers:
            self.degraded = False
            return True

        def probe(node):
            try:
                st = self.client.status(node.uri,
                                        timeout=self.heartbeat_timeout)
            except Exception:  # noqa: BLE001 — any transport symptom
                # (wrapped or raw) reads as unreachable for the vote
                return None
            return int(st.get("epoch", 0) or 0)

        epochs = [e for e in concurrent_map(probe, peers) if e is not None]
        top = max(epochs, default=0)
        if top > self.epoch:
            self.adopt_epoch(top)
        ok = (1 + len(epochs)) >= (n // 2 + 1) or n <= 2
        self.degraded = not ok
        if not ok:
            self.quorum_denials += 1
        return ok

    def _note_acted(self, epoch: int, action: str) -> None:
        self.acted_epochs.append((epoch, action))

    # Bounded jittered retry for control-message sends: one dropped
    # node-leave/state broadcast must not strand a peer in RESIZING
    # until the straggler timeout. Class attributes so tests and the
    # chaos harness can shrink the backoff.
    SEND_ATTEMPTS = 3
    SEND_BACKOFF_S = 0.05

    def _send_retry(self, uri: str, message: dict) -> dict:
        """send_message with bounded jittered-backoff retry on NODE
        faults (transport, 5xx). Deterministic 4xx never retries —
        every replay would answer the same. Raises the last ClientError
        when every attempt fails."""
        last: ClientError | None = None
        for attempt in range(max(1, self.SEND_ATTEMPTS)):
            try:
                return self.client.send_message(uri, message)
            except ClientError as e:
                if not e.is_node_fault:
                    raise
                last = e
                if attempt + 1 < self.SEND_ATTEMPTS:
                    time.sleep(self.SEND_BACKOFF_S * (2 ** attempt)
                               * (0.5 + random.random()))
        raise last

    def metrics(self) -> dict:
        """Partition-tolerance series for /metrics and /debug/vars
        (docs/OBSERVABILITY.md) — every key present from scrape one."""
        with self._lock:
            members = len(self.nodes)
            suspects = sum(1 for f in self._heartbeat_failures.values()
                           if f > 0)
        return {
            "cluster_epoch": self.epoch,
            "cluster_quorum": 0 if self.degraded else 1,
            "cluster_degraded": 1 if self.degraded else 0,
            "cluster_members": members,
            "cluster_suspects": suspects,
            "cluster_heartbeat_probes_total": self.heartbeat_probes,
            "cluster_heartbeat_failures_total":
                self.heartbeat_probe_failures,
            "cluster_deaths_declared_total": self.deaths_declared,
            "cluster_deaths_vetoed_total": self.deaths_vetoed,
            "cluster_stale_epoch_rejects_total": self.stale_epoch_rejects,
            "cluster_quorum_denials_total": self.quorum_denials,
            "cluster_rejoins_total": self.rejoins,
            "cluster_cleanup_deferred_total": self.cleanups_deferred,
            "cluster_placement_overrides": len(self.placement),
            "cluster_placement_epoch": self.placement.epoch,
            "cluster_placement_ranges": self.placement.range_count,
            "elastic_drain_active": 1 if self.drain_active else 0,
            "elastic_drain_epoch":
                int(self.drain_record.get("epoch", 0) or 0),
            "elastic_draining": 1 if self.draining else 0,
            "elastic_warm_heat_ordered_total": self.warm_heat_ordered,
            "elastic_warm_verified_total": self.warm_verified,
            "elastic_warm_verify_failed_total": self.warm_verify_failed,
        }

    # How long the coordinator waits for every member to drain to NORMAL
    # before the post-resize cleanup. A member still RESIZING runs its
    # own gated self-join fetch, which may be SOURCING from fragments the
    # cleanup would delete; on timeout the cleanup is skipped entirely
    # (safe: stale copies only mislead after a LATER ownership change,
    # and the next resize retries the cleanup). Runs under _resize_lock,
    # so the timeout also bounds how long a follow-on resize can be
    # delayed behind an undrainable peer.
    CLEANUP_DRAIN_TIMEOUT = 15.0

    def _broadcast_cleanup(self, epoch: int | None = None) -> None:
        """End-of-resize holder cleanup, coordinator-initiated: every
        member drops fragments for shards it no longer owns. Runs ONLY
        after (a) every receiver reported resize-complete AND (b) every
        member's /status shows NORMAL — a joiner's self-join inventory
        fetch is a separate background job that outlives the
        instruction-resize, and deleting its source fragments mid-fetch
        loses sole copies (exactly what happened when cleanup ran at
        resize-complete time in the join test). The message carries the
        membership the coordinator resized against: a receiver whose
        member view disagrees (missed join/leave broadcast) skips, so a
        stale ring can never compute wrong ownership and delete a sole
        surviving copy."""
        with self._lock:
            members = sorted(self.nodes)
            # Poll EVERY peer, including DEGRADED ones: a transient
            # failure (missed instruction ack, heartbeat blip) marks a
            # LIVE node DEGRADED while its gated self-join fetch is
            # still in flight — skipping it here would let cleanup
            # delete the sole source copy that fetch is about to pull
            # (fatal at replica_n=1). An actually-dead peer never
            # reports NORMAL, so the deadline below converts it into a
            # conservative cleanup skip; the timeout bounds how long a
            # follow-on resize can stall behind it.
            peers = [n for n in self.nodes.values()
                     if n.id != self.local.id]
        deadline = time.monotonic() + self.CLEANUP_DRAIN_TIMEOUT
        pending = {p.id: p for p in peers}
        while pending:
            with self._lock:
                if sorted(self.nodes) != members:
                    return  # membership changed mid-drain: the new
                            # event's own resize will clean up instead
            for pid, node in list(pending.items()):
                try:
                    st = self.client.status(node.uri)
                except Exception:  # noqa: BLE001 — a freshly-killed
                    # peer can surface raw socket errors the client
                    # doesn't wrap; any failure means "not confirmably
                    # NORMAL", retried until the deadline
                    continue
                if st.get("state") == STATE_NORMAL:
                    del pending[pid]
            if not pending:
                break
            if time.monotonic() >= deadline:
                if self.logger is not None:
                    self.logger.info(
                        "skipping post-resize cleanup: %s still draining",
                        sorted(pending),
                    )
                return
            time.sleep(0.1)
        try:
            self.cleanup_unowned(members, epoch=epoch)
        except Exception as e:  # noqa: BLE001 — must not wedge the resize
            self._log_exception("post-resize holder cleanup", e)
        message = {"type": "resize-cleanup", "members": members}
        if epoch is not None:
            # epoch-fenced: a receiver that has seen a newer epoch (a
            # later coordinator acted) must not delete by this resize's
            # now-stale view of ownership
            message["epoch"] = epoch
        self._broadcast(message)

    def cleanup_unowned(self, members: list[str] | None = None,
                        epoch: int | None = None) -> int:
        """Reference post-resize holder cleanup: delete fragments for
        shards this node no longer owns. Without this, a node that loses
        a shard during churn keeps an era-frozen copy; when a later
        resize returns ownership, the missing-only fetch skips the held
        fragment and the node serves stale data (set-field union repair
        cannot remove the stale-extra bits, and Store/ClearRow computed
        from the stale replica poison healthy ones — found by the
        seed-swept membership-churn property test). ``members`` is the
        coordinator's post-resize membership; mismatch with the local
        view means this node's ring is stale and deleting by it could
        destroy a sole copy — skip. Returns #fragments removed.

        The node RING is snapshotted under _lock at the same moment the
        membership is verified, and every per-shard ownership decision
        below walks that frozen snapshot (TOCTOU): a
        node-join/leave message landing mid-loop would otherwise swing
        shard_nodes() to the NEW ring before the new ring's resize has
        copied anything — at replica_n=1 deleting by the new ring
        destroys the sole copy the coming resize needs as its source.

        QUORUM-GATED (docs/OPERATIONS.md failure model): fragment
        deletion is the one irreversible control-plane action, and a
        minority-side node's ring is by definition a minority view of
        ownership — under an asymmetric partition the pre-gate code
        deleted sole surviving copies by it. No member-majority contact
        → no deletion, logged and counted. Every decision (epoch,
        quorum, removed) lands in ``cleanup_log`` — the chaos harness's
        no-deletion-without-quorum oracle reads it."""
        if self.holder is None:
            return 0
        entry = {
            "epoch": self.epoch if epoch is None else int(epoch),
            "quorum": True, "removed": 0, "skipped": None,
        }
        self.cleanup_log.append(entry)
        with self._lock:
            n_members = len(self.nodes)
        if n_members > 1 and not self.check_quorum():
            entry["quorum"] = False
            entry["skipped"] = "no quorum"
            if self.logger is not None:
                self.logger.info(
                    "skipping holder cleanup on %s: no member quorum",
                    self.local.id,
                )
            return 0
        faults.crash_point("cluster.pre-cleanup")
        with self._lock:
            local_members = sorted(self.nodes)
            ring = self._frozen_ring()
            # overrides freeze WITH the ring: a placement-update landing
            # mid-walk must not swing ownership under the deletions
            placement = self.placement.snapshot()
        if self.local.id not in local_members:
            entry["skipped"] = "departed"
            return 0  # departed (leave()): never self-wipe on exit
        if members is not None and sorted(members) != local_members:
            entry["skipped"] = "membership mismatch"
            if self.logger is not None:
                self.logger.info(
                    "skipping post-resize cleanup: membership %s != "
                    "coordinator's %s", local_members, sorted(members),
                )
            return 0
        removed = 0
        deferred = 0
        for index_name, idx in list(self.holder.indexes.items()):
            owned: dict[int, bool] = {}
            for field in list(idx.fields.values()):
                for view in list(field.views.values()):
                    unowned = []
                    for shard in list(view.fragments):
                        mine = owned.get(shard)
                        if mine is None:
                            mine = any(
                                n.id == self.local.id
                                for n in self._shard_nodes_on(
                                    ring, placement, index_name, shard,
                                )
                            )
                            owned[shard] = mine
                        if mine:
                            continue
                        frag = view.fragment(shard)
                        if (frag is not None and frag.count()
                                and not self._owner_covers(
                                    ring, placement, index_name,
                                    field.name, view.name, shard, frag)):
                            # this copy holds bits NO owner does — a
                            # write acked under an older ring, or
                            # divergence a partition left behind.
                            # Deleting it would lose acked data;
                            # keep it until an anti-entropy pass
                            # absorbs it into the owners (stray-copy
                            # absorption in _sync_fragment), and let
                            # the resize after that delete it.
                            deferred += 1
                            continue
                        unowned.append(shard)
                    # bulk removal: one durable-tombstone barrier per
                    # view, not one group-commit fsync per shard
                    view_removed = view.remove_fragments(
                        unowned, invalidate_derived=False
                    )
                    if view_removed:
                        # one derived-entry purge per field, not per shard
                        view.invalidate_derived_entries()
                        removed += view_removed
        entry["removed"] = removed
        entry["deferred"] = deferred
        if deferred:
            self.cleanups_deferred += deferred
        if (removed or deferred) and self.logger is not None:
            self.logger.info(
                "post-resize cleanup: removed %d non-owned fragments"
                " (%d deferred: owners have not absorbed their bits)",
                removed, deferred,
            )
        return removed

    def _owner_covers(self, ring, placement, index_name: str,
                      field_name: str, view_name: str, shard: int,
                      frag) -> bool:
        """True when some live owner of ``shard`` demonstrably holds a
        SUPERSET of this fragment's bits, so deleting the local copy
        cannot lose data. Checksum-equal blocks are covered outright;
        differing blocks are fetched and compared as sets — a strict
        subset (the era-frozen-copy case) still deletes, only bits the
        owner genuinely lacks defer the deletion. Uses the per-block
        legacy wire so mixed-version owners answer too; an unreachable
        owner simply fails to cover (the next pass retries)."""
        local_blocks = dict(frag.blocks())
        if not local_blocks:
            return True
        for node in self._shard_nodes_on(
                ring, placement, index_name, shard):
            if node.id == self.local.id:
                continue
            try:
                peer_blocks = dict(self.client.fragment_blocks(
                    node.uri, index_name, field_name, view_name, shard,
                ))
            except ClientError:
                continue
            covered = True
            for block, checksum in local_blocks.items():
                if peer_blocks.get(block) == checksum:
                    continue  # identical content
                try:
                    bm = self.client.fragment_block_bitmap(
                        node.uri, index_name, field_name, view_name,
                        shard, block,
                    )
                except ClientError:
                    covered = False
                    break
                # subset test as one galloping set-difference kernel
                # over the two sorted id arrays, not Python sets
                peer_ids = kernels.fragment_ids(kernels.flatten(bm))
                if kernels.setdiff_sorted(
                        frag.block_ids(block), peer_ids).size:
                    covered = False  # we hold bits this owner lacks
                    break
            if covered:
                return True
        return False

    def _log_exception(self, what: str, exc: BaseException) -> None:
        logger = self.logger
        if logger is not None:
            logger.error("%s failed on %s: %r", what, self.local.id, exc)
        else:  # no server wired (bare Cluster in tests/tools)
            import traceback

            traceback.print_exception(exc)

    def _drop_resize_pending(self, node_id: str) -> None:
        """A departed/dead node can't report resize-complete; don't gate
        the cluster on it for the full straggler timeout."""
        with self._resize_cv:
            if node_id in self._resize_pending:
                self._resize_pending.discard(node_id)
                self._resize_cv.notify_all()

    # ----------------------------------------------------------- membership

    @property
    def coordinator(self) -> Node:
        return self.sorted_nodes()[0]

    @property
    def is_coordinator(self) -> bool:
        return self.coordinator.id == self.local.id

    def sorted_nodes(self) -> list[Node]:
        return [self.nodes[i] for i in sorted(self.nodes)]

    def nodes_json(self) -> list[dict]:
        out = []
        for n in self.sorted_nodes():
            d = n.to_json()
            d["isCoordinator"] = n.id == self.coordinator.id
            out.append(d)
        return out

    # ----------------------------------------------------------- assignment

    def partition(self, index: str, shard: int) -> int:
        return _hash64(f"{index}:{shard}") % PARTITION_N

    def partition_nodes(self, partition: int) -> list[Node]:
        """replica_n nodes for a partition: walk the ring of nodes ordered
        by hash(node id), starting at the partition's point."""
        return self._partition_nodes_on(
            self._frozen_ring(), partition
        )

    def _note_membership_changed_locked(self) -> None:
        """Caller holds _lock and just mutated ``self.nodes``: the
        memoized ring is stale."""
        self._ring_gen += 1

    def _frozen_ring(self) -> list[Node]:
        """Hash-ordered snapshot of the current membership. Callers that
        make several ownership decisions against ONE membership view
        (cleanup_unowned) take this once under _lock and walk it, so a
        join/leave landing mid-walk cannot shift ownership under them.

        Memoized per ring generation (bumped on every membership
        mutation): the blake2b per node per call showed up per shard
        per query fan-out. Callers treat the returned list as frozen —
        never mutate it."""
        with self._lock:
            cached = self._ring_cache
            if (cached is not None and cached[0] == self._ring_gen
                    and cached[1] == id(self.nodes)
                    and cached[2] == len(self.nodes)):
                return cached[3]
            memo = self._ring_hash_memo
            if len(memo) > 4096:  # bound, not invalidate: id→hash is
                memo.clear()      # immutable, churn just grows the map

            def ring_key(n: Node) -> tuple[int, str]:
                h = memo.get(n.id)
                if h is None:
                    h = _hash64(n.id)
                    memo[n.id] = h
                return (h, n.id)

            ring = sorted(self.nodes.values(), key=ring_key)
            self._ring_cache = (self._ring_gen, id(self.nodes),
                                len(self.nodes), ring)
            return ring

    def _partition_nodes_on(self, ring: list[Node],
                            partition: int) -> list[Node]:
        if not ring:
            return []
        start = partition % len(ring)
        n = min(self.replica_n, len(ring))
        return [ring[(start + i) % len(ring)] for i in range(n)]

    def shard_nodes(self, index: str, shard: int) -> list[Node]:
        """Owners of one shard: the placement override when one applies
        (every listed owner a live member), else the pure hash walk.
        With an empty override table this is byte-identical to the
        pre-autopilot placement — the mixed-version safety contract.
        A range-split shard resolves through its union override (the
        planner installs both together), so data placement needs no
        range awareness here; ranges refine routing PREFERENCE only —
        read targets (range_read_nodes) and plain-set write slices
        (range_write_spans) — never membership of the data."""
        override = self.placement.get(index, shard)
        if override is not None:
            with self._lock:
                nodes = [self.nodes[i] for i in override
                         if i in self.nodes]
            if len(nodes) == len(override):
                return nodes
            # a listed owner left the membership: hash placement
            # resumes for this shard until the planner re-plans
        return self.partition_nodes(self.partition(index, shard))

    def range_read_nodes(self, index: str, shard: int,
                         column_offset: int) -> list[Node] | None:
        """Preferred readers for one column offset of a range-split
        shard, or None when the shard has no (fully live) split. Every
        range owner holds the WHOLE fragment (data placement is the
        union override), so this is a routing refinement — a caller
        that ignores it still reads correct bytes from any owner."""
        spans = self.placement.get_ranges(index, shard)
        if not spans:
            return None
        for lo, hi, ids in spans:
            if lo <= column_offset < hi:
                with self._lock:
                    nodes = [self.nodes[i] for i in ids if i in self.nodes]
                if len(nodes) == len(ids):
                    return nodes
                return None  # a range owner departed: union routing
        return None

    def range_write_spans(self, index: str, shard: int
                          ) -> list[tuple[int, int, list[Node] | None]] | None:
        """Write-routing view of a shard's sub-shard column ranges:
        ``[(lo, hi, owners-or-None), ...]`` covering the adopted spans,
        or None when the shard has no split (the union/hash path). A
        span whose owner list has a departed member yields ``None``
        owners — the caller must fall back to union fan-out for columns
        in that span (anti-entropy converges the refill; a narrowed send
        to a half-live span could strand the slice). Only PLAIN SET
        writes may use this: union repair converges a non-span owner
        that missed a set, but cannot undo a clear, a mutex row move, or
        a BSI value it never saw (see cluster_exec._route_all_replicas)
        — those keep full union fan-out."""
        spans = self.placement.get_ranges(index, shard)
        if not spans:
            return None
        out: list[tuple[int, int, list[Node] | None]] = []
        with self._lock:
            for lo, hi, ids in spans:
                nodes = [self.nodes[i] for i in ids if i in self.nodes]
                out.append((lo, hi,
                            nodes if len(nodes) == len(ids) else None))
        return out

    def _shard_nodes_on(self, ring: list[Node], placement: dict,
                        index: str, shard: int) -> list[Node]:
        """shard_nodes against a FROZEN (ring, placement) snapshot —
        the cleanup walk's TOCTOU discipline extended to overrides."""
        ids = placement.get((index, int(shard)))
        if ids:
            by_id = {n.id: n for n in ring}
            nodes = [by_id[i] for i in ids if i in by_id]
            if len(nodes) == len(ids):
                return nodes
        return self._partition_nodes_on(ring, self.partition(index, shard))

    def owns_shard(self, index: str, shard: int) -> bool:
        return any(n.id == self.local.id for n in self.shard_nodes(index, shard))

    def primary_for_shard(self, index: str, shard: int) -> Node:
        return self.shard_nodes(index, shard)[0]

    def local_shards(self, index: str, shards: list[int]) -> list[int]:
        return [s for s in shards if self.owns_shard(index, s)]

    def shard_nodes_json(self, index: str, shard: int) -> list[dict]:
        return [n.to_json() for n in self.shard_nodes(index, shard)]

    # ------------------------------------------------------------ broadcast

    def _broadcast(self, message: dict, mark_degraded: bool = False) -> None:
        """Deliver a message to every peer, tolerating per-node failures
        (the one broadcast loop — send_sync/leave/state/shard announcements
        all route here so error handling can't drift between them). Each
        send retries on node faults with jittered backoff (_send_retry):
        a single dropped state broadcast would otherwise strand a peer
        in RESIZING until the straggler timeout."""
        for node in self.sorted_nodes():
            if node.id == self.local.id:
                continue
            try:
                self._send_retry(node.uri, message)
            except ClientError:
                if mark_degraded:
                    node.state = STATE_DEGRADED

    def send_sync(self, message: dict) -> None:
        """Deliver a schema delta to every peer (reference SendSync)."""
        self._broadcast(message, mark_degraded=True)

    def handle_message(self, message: dict) -> dict:
        """Apply a cluster message received from a peer (reference
        broadcastHandler).

        Epoch fencing: a FENCED message stamped with an epoch older
        than this node's is rejected unapplied — the partitioned
        ex-coordinator's un-gate/resize/cleanup commands die here. A
        newer epoch is adopted first (the wire doubles as epoch
        gossip). Messages without an epoch (older wire, bare test
        constructions) pass unfenced, same mixed-version posture as
        every other wire change."""
        kind = message.get("type")
        msg_epoch = message.get("epoch")
        if msg_epoch is not None:
            msg_epoch = int(msg_epoch)
            if msg_epoch > self.epoch:
                self.adopt_epoch(msg_epoch)
            elif msg_epoch < self.epoch and kind in FENCED_MESSAGES:
                self.stale_epoch_rejects += 1
                if self.logger is not None:
                    self.logger.info(
                        "rejecting stale-epoch %s (%d < %d) on %s",
                        kind, msg_epoch, self.epoch, self.local.id,
                    )
                return {"error": f"stale epoch {msg_epoch} "
                                 f"(current {self.epoch})",
                        "epoch": self.epoch}
        if kind == "create-index":
            if self.holder.index(message["index"]) is None:
                self.holder.create_index(
                    message["index"],
                    keys=message.get("keys", False),
                    track_existence=message.get("trackExistence", True),
                )
        elif kind == "delete-index":
            if self.holder.index(message["index"]) is not None:
                self.holder.delete_index(message["index"])
            self.forget_index(message["index"])
        elif kind == "create-field":
            from pilosa_tpu.storage import FieldOptions

            idx = self.holder.index(message["index"])
            if idx is not None and idx.field(message["field"]) is None:
                idx.create_field(
                    message["field"], FieldOptions.from_dict(message.get("options", {}))
                )
        elif kind == "delete-field":
            idx = self.holder.index(message["index"])
            if idx is not None and idx.field(message["field"]) is not None:
                idx.delete_field(message["field"])
        elif kind == "resize-cleanup":
            try:
                self.cleanup_unowned(message.get("members"),
                                     epoch=msg_epoch)
            except Exception as e:  # noqa: BLE001
                self._log_exception("post-resize holder cleanup", e)
        elif kind == "suspect-probe":
            # death corroboration: the asking coordinator suspects a
            # node; answer with THIS node's own live view of it (tight
            # timeout — the answer must arrive inside the asker's
            # heartbeat pass)
            uri = message.get("uri")
            if not uri:
                with self._lock:
                    node = self.nodes.get(message.get("id"))
                uri = node.uri if node is not None else None
            if uri is None:
                return {"reachable": False, "known": False}
            try:
                self.client.status(uri, timeout=self.heartbeat_timeout)
            except Exception:  # noqa: BLE001 — unreachable however it
                # failed; this vote corroborates the suspicion
                return {"reachable": False}
            return {"reachable": True}
        elif kind == "recalculate-caches":
            # reference RecalculateCachesMessage: each receiver recounts
            # its own fragments' TopN caches (local-only apply — the
            # originator already broadcast to every peer)
            if self.api is not None:
                self.api.recalculate_caches(remote=True)
        elif kind == "forward-query":
            # a write forwarded verbatim (attr calls); apply locally
            if self.api is not None:
                self.api.query(
                    message["index"], message["pql"], remote=True
                )
        elif kind == "node-join":
            node = Node(message["id"], message["uri"])
            with self._lock:
                known = node.id in self.nodes
                self.nodes[node.id] = node
                self._forgotten.pop(node.id, None)
                self._note_membership_changed_locked()
                relay_to = ([n for n in self.nodes.values()
                             if n.id != node.id
                             and n.id != self.local.id]
                            if not known else [])
            if relay_to:
                # Join gossip (reference: memberlist broadcasts joins).
                # A joiner announces only to the members the seed's
                # /status listed at ITS join time, so two nodes joining
                # the same seed CONCURRENTLY each adopt [seed, self] and
                # announce to the seed alone — neither ever learns the
                # other, and each serves its own asymmetric ring (reads
                # through one routes around data the other holds). On
                # first learning of a node, relay the join both ways:
                # the new member to every known member, every known
                # member to the new one. A relay of an already-known
                # node is a no-op here (known ⇒ no further relay), so
                # the wave terminates after one generation per edge.
                def _relay_join():
                    for peer in relay_to:
                        try:
                            self._send_retry(peer.uri, {
                                "type": "node-join",
                                "id": node.id, "uri": node.uri,
                            })
                        except ClientError:
                            pass
                        try:
                            self._send_retry(node.uri, {
                                "type": "node-join",
                                "id": peer.id, "uri": peer.uri,
                            })
                        except ClientError:
                            pass

                # async: this handler runs on the serving thread of the
                # announce POST — the relay fan-out must not hold it
                threading.Thread(target=_relay_join, daemon=True,
                                 name="join-relay").start()
            # membership changed ownership: the acting coordinator computes
            # per-node fetch instructions (reference ResizeInstruction)
            if self.is_acting_coordinator:
                self._spawn_resize()
        elif kind == "node-leave":
            with self._lock:
                removed = self.nodes.pop(message["id"], None)
                self._note_membership_changed_locked()
                if removed is not None:
                    # remember the uri: if this node later ends up solo
                    # (everyone amputated during a partition) it probes
                    # forgotten peers to reunite instead of serving as
                    # a split-brained 1-node cluster (dead peers just
                    # fail the probe — tracking them is harmless)
                    self._forgotten[removed.id] = removed.uri
                self._heartbeat_failures.pop(message["id"], None)
            self._drop_resize_pending(message["id"])
            if removed is not None:
                # departed-member CDC: its cursors must not pin our WAL
                self.drop_departed_cursors(message["id"])
            if self.is_acting_coordinator:
                self._spawn_resize()
        elif kind == "create-shard":
            with self._lock:
                self.known_shards.setdefault(message["index"], set()).update(
                    int(s) for s in message.get("shards", [])
                )
        elif kind == "cluster-state":
            self._command_state(message.get("state", STATE_NORMAL))
        elif kind == "resize-instruction":
            job, reply_to = message.get("job"), message.get("reply_to")
            if job is None:
                # direct form (tests/tools): fetch inline
                self.fetch_fragments(message.get("sources", []))
            else:
                # ack now, fetch in a worker: the coordinator's delivery
                # must not block on the fetch (a large move would trip
                # the client timeout, spuriously DEGRADE a healthy-but-
                # busy node, and un-gate queries mid-move). Gate BEFORE
                # spawning: if the worker took the gate itself, a node
                # whose other fetch paths just drained would be briefly
                # observable as NORMAL while the instruction fragments
                # are still missing — wait_until_normal callers then
                # query short (caught ~1-in-15 under CI load).
                self._begin_local_fetch()
                try:
                    threading.Thread(
                        target=self._run_resize_job,
                        args=(message.get("sources", []), job, reply_to,
                              True),
                        daemon=True,
                    ).start()
                except BaseException:
                    self._end_local_fetch()
                    raise
        elif kind == "resize-complete":
            with self._resize_cv:
                if message.get("job") == self._resize_job:
                    if int(message.get("fetched", 0)) < 0:
                        # the CURRENT job's peer fetch raised: it acked
                        # but is missing fragments — mark it DEGRADED
                        # BEFORE the notify wakes the coordinator, so
                        # queries can't route to it in the window between
                        # un-gating and the mark (stale reports from
                        # superseded jobs are ignored; anti-entropy
                        # repairs and the next heartbeat restores it)
                        node = self.nodes.get(message.get("node"))
                        if node is not None:
                            node.state = STATE_DEGRADED
                    self._resize_pending.discard(message.get("node"))
                    self._resize_cv.notify_all()
        elif kind == "placement-update":
            # fenced above: a healed ex-coordinator's stale table was
            # already rejected; what reaches here is current-or-newer
            self.adopt_placement(message)
        elif kind == "drain-update":
            # fenced above; (epoch, rev) ordering inside adopt_drain
            # handles same-epoch state advances
            self.adopt_drain(message.get("drain"))
        elif kind == "drain-leave":
            # the drain coordinator finished moving this node's groups:
            # leave the ring. Async — the coordinator's send must not
            # block on our departure broadcast fan-out.
            if message.get("node") == self.local.id:
                threading.Thread(target=self.leave, daemon=True,
                                 name="drain-leave").start()
        elif kind == "resize-progress":
            with self._resize_cv:
                if message.get("job") == self._resize_job:
                    # still alive and moving: push the straggler deadline
                    self._resize_deadline = (
                        time.monotonic() + self.RESIZE_COMPLETE_TIMEOUT
                    )
        else:
            return {"error": f"unknown message type {kind!r}"}
        return {}

    def note_local_shards(self, index: str, shards) -> None:
        """Announce newly-created local shards to every peer (reference
        CreateShardMessage on max-shard bump — SURVEY.md §2 #15), so remote
        queries see them immediately rather than after the catalog-poll
        TTL. Fire-and-forget: the catalog poll remains the backstop."""
        with self._lock:
            seen = self._announced_shards.setdefault(index, set())
            new = sorted(set(int(s) for s in shards) - seen)
            if not new:
                return
            seen.update(new)
            # Self-knowledge too: the shard universe is monotonic
            # cluster metadata (reference maxShard only grows), NOT a
            # reflection of local holdings. Without this, a node whose
            # post-resize cleanup deleted its formerly-local fragments
            # lost those shards from its own fan-out universe whenever
            # the peer-poll cache predated the resize — a cluster-wide
            # Count quietly skipped them (mesh join test, ~1-in-10
            # under load).
            self.known_shards.setdefault(index, set()).update(new)
        if len(self.nodes) <= 1:
            return
        message = {"type": "create-shard", "index": index, "shards": new}
        threading.Thread(
            target=self._broadcast, args=(message,), daemon=True
        ).start()

    def get_known_shards(self, index: str) -> list[int]:
        """Snapshot of peer-announced shards (copied under the lock: the
        message handler mutates the set from HTTP threads)."""
        with self._lock:
            return sorted(self.known_shards.get(index, ()))

    def forget_index(self, index: str) -> None:
        """Drop shard bookkeeping for a deleted index: stale entries would
        fan queries out to phantom shards and suppress announcements for a
        recreated index of the same name."""
        with self._lock:
            self.known_shards.pop(index, None)
            self._announced_shards.pop(index, None)

    # ------------------------------------------------------------ heartbeat

    @property
    def is_acting_coordinator(self) -> bool:
        """First NON-DEAD node in id order: coordination must fail over
        when the coordinator itself is the node that died."""
        for n in self.sorted_nodes():
            if n.state != STATE_DEGRADED:
                return n.id == self.local.id
        return True

    def heartbeat(self) -> None:
        """Liveness probe of peers (memberlist's role — SURVEY.md §2 #14).
        Probes run CONCURRENTLY with the tight dedicated
        ``heartbeat_timeout`` — a hung peer's socket must not stall the
        whole loop and delay detection of OTHER failures. After
        DEAD_HEARTBEATS consecutive failures the acting coordinator moves
        the node suspect→dead — but only with member-majority quorum AND
        ≥2 corroborating observers (all-but-self in 2-node clusters), so
        a single-observer flap (one cut link) can no longer amputate a
        live node (reference: memberlist's peer-corroborated suspect
        protocol — SURVEY.md §5.3).

        Each pass also (a) tracks quorum → the ``degraded`` read-only
        flag, (b) adopts any higher epoch a peer reports, and (c)
        detects EVICTION — a reachable peer whose member list no longer
        contains this node means the majority declared us dead while we
        were partitioned; we rejoin through it instead of split-braining
        forever."""
        with self._lock:
            peers = [n for n in self.sorted_nodes()
                     if n.id != self.local.id]
        if not peers:
            self.degraded = False
            if self._forgotten and not self._left:
                # solo after declaring everyone dead: if any forgotten
                # peer answers, the "deaths" were a partition — reunite
                self._solo_reunion()
            return

        def probe(node):
            try:
                return node, self.client.status(
                    node.uri, timeout=self.heartbeat_timeout
                )
            except ClientError:
                return node, None

        results = concurrent_map(probe, peers)
        dead: list[Node] = []
        live: list[Node] = []
        rejoin_via: dict | None = None
        for node, st in results:
            self.heartbeat_probes += 1
            if st is not None:
                live.append(node)
                node.state = STATE_NORMAL
                self._heartbeat_failures.pop(node.id, None)
                peer_epoch = int(st.get("epoch", 0) or 0)
                if peer_epoch > self.epoch:
                    self.adopt_epoch(peer_epoch)
                # placement + drain record gossip with the heartbeat: a
                # node that missed the broadcast (partitioned,
                # restarting) converges on the next probe round
                self.adopt_placement(st.get("placement"))
                self.adopt_drain(st.get("drain"))
                peer_ids = {n.get("id") for n in st.get("nodes", [])}
                if (peer_ids and self.local.id not in peer_ids
                        and (peer_epoch >= self.epoch
                             or len(peer_ids) >= len(self.nodes))
                        and rejoin_via is None):
                    # evicted while partitioned: the peer's view is at
                    # least as authoritative as ours (newer epoch, or no
                    # smaller a cluster) — surrender and rejoin through
                    # it rather than serving a split-brained ring
                    rejoin_via = st
            else:
                self.heartbeat_probe_failures += 1
                node.state = STATE_DEGRADED
                fails = self._heartbeat_failures.get(node.id, 0) + 1
                self._heartbeat_failures[node.id] = fails
                if fails >= DEAD_HEARTBEATS:
                    dead.append(node)
        n = len(peers) + 1
        self.degraded = not ((1 + len(live)) >= (n // 2 + 1) or n <= 2)
        if rejoin_via is not None and not self._left:
            self._rejoin(rejoin_via)
            return
        if self._forgotten and not self._left:
            # peers we (or a coordinator) amputated that turn out to be
            # alive were partitioned, not dead: INVITE the fully-split
            # ones back (they add us, see our view on their next probe,
            # and rejoin through it) — without this, a side that never
            # probes the forgotten node leaves it serving as a
            # split-brained cluster forever
            self._probe_forgotten()
        if dead and self.is_acting_coordinator:
            if self.degraded:
                # wanted to declare deaths but holds no quorum: the
                # minority side of a partition observing exactly the
                # blast radius the gate exists to stop
                self.quorum_denials += 1
                return
            for node in dead:
                if self._death_corroborated(node, live):
                    self.declare_dead(node.id)
                else:
                    # suspect stays DEGRADED (unrouted) but keeps its
                    # membership: a one-link flap must not amputate it
                    self.deaths_vetoed += 1

    def _death_corroborated(self, suspect: Node, live_peers: list[Node]
                            ) -> bool:
        """suspect→dead needs ≥2 observers: this node's failed probes
        plus at least one live peer that ALSO cannot reach the suspect
        right now (suspect-probe message → the peer runs its own
        tight-timeout probe). With no other live peer — a 2-node
        cluster — all-but-self is just this node and the single
        observation stands (check_quorum documents the 2-node
        tradeoff); in larger clusters a coordinator that can reach no
        corroborator has no business declaring deaths (the quorum gate
        already vetoes that, belt and braces)."""
        others = [p for p in live_peers if p.id != suspect.id]
        if not others:
            return len(self.nodes) <= 2

        def ask(peer):
            try:
                out = self.client.send_message(peer.uri, {
                    "type": "suspect-probe", "id": suspect.id,
                    "uri": suspect.uri,
                })
            except ClientError:
                return False
            return out.get("reachable") is False

        return any(concurrent_map(ask, others))

    def declare_dead(self, node_id: str) -> bool:
        """Remove a dead node and re-replicate its shards: broadcast the
        departure (epoch-stamped), then send per-node resize
        instructions. QUORUM-GATED: a minority-side node must not
        amputate members it merely cannot see — under an asymmetric
        partition both sides would otherwise each declare the other
        dead and resize against disjoint rings. Returns False when
        vetoed (no quorum / unknown node)."""
        with self._lock:
            known = node_id in self.nodes
            n_members = len(self.nodes)
        if not known:
            return False
        if n_members > 2 and not self.check_quorum():
            if self.logger is not None:
                self.logger.info(
                    "refusing to declare %s dead: no member quorum on %s",
                    node_id, self.local.id,
                )
            return False
        faults.crash_point("cluster.pre-declare-dead")
        epoch = self._bump_epoch()
        with self._lock:
            node = self.nodes.pop(node_id, None)
            if node is None:
                return False
            self._note_membership_changed_locked()
            self._forgotten[node_id] = node.uri
            self._heartbeat_failures.pop(node_id, None)
        self.deaths_declared += 1
        self._note_acted(epoch, f"declare-dead:{node_id}")
        self._drop_resize_pending(node_id)
        # a declared-dead member's CDC cursors must not pin retention
        self.drop_departed_cursors(node_id)
        for node in self.sorted_nodes():
            if node.id == self.local.id:
                continue
            try:
                self._send_retry(node.uri, {
                    "type": "node-leave", "id": node_id, "epoch": epoch,
                })
            except ClientError:
                pass
        self.coordinate_resize()
        return True

    def _probe_forgotten(self) -> None:
        """Tight-timeout probes of declared-dead peers. A reachable one
        whose member list no longer names US gets a node-join invite:
        it adds us, its next heartbeat sees our (no-smaller, no-older)
        view lacking it, and it rejoins through us. One message; safe —
        a genuinely removed node either stays unreachable (probe fails)
        or deliberately left (its _left latch refuses auto-rejoin)."""
        def one(item):
            node_id, node_uri = item
            try:
                st = self.client.status(node_uri,
                                        timeout=self.heartbeat_timeout)
            except Exception:  # noqa: BLE001 — still gone
                return
            peer_ids = {n.get("id") for n in st.get("nodes", [])}
            if self.local.id in peer_ids:
                return  # it still knows us: its own probes reconcile
            try:
                self._send_retry(node_uri, {
                    "type": "node-join", "id": self.local.id,
                    "uri": self.local.uri,
                })
            except ClientError:
                pass

        concurrent_map(one, list(self._forgotten.items()))

    def _solo_reunion(self) -> None:
        """A 1-node 'cluster' probing the peers it declared dead: a
        reachable one means the declarations were really a partition.
        Merge memberships (only ADDING — there is nobody left to evict)
        and announce ourselves so both sides' coordinators reconcile;
        data differences heal through anti-entropy's stray-copy
        absorption. Without this, a symmetric 2-way amputation leaves
        two 1-node clusters serving forever."""
        for node_id, node_uri in list(self._forgotten.items()):
            try:
                st = self.client.status(node_uri,
                                        timeout=self.heartbeat_timeout)
            except Exception:  # noqa: BLE001 — still unreachable
                continue
            if self.logger is not None:
                self.logger.info(
                    "%s rediscovered %s after a partition; reuniting",
                    self.local.id, node_id,
                )
            self.rejoins += 1
            with self._lock:
                self.nodes[node_id] = Node(node_id, node_uri)
                for n in st.get("nodes", []):
                    if n.get("id") and n["id"] not in self.nodes:
                        self.nodes[n["id"]] = Node(n["id"], n["uri"])
                self._forgotten.clear()
                self._note_membership_changed_locked()
            self.adopt_epoch(int(st.get("epoch", 0) or 0))
            self.adopt_placement(st.get("placement"))
            self.adopt_drain(st.get("drain"))
            for node in self.sorted_nodes():
                if node.id == self.local.id:
                    continue
                try:
                    self._send_retry(node.uri, {
                        "type": "node-join", "id": self.local.id,
                        "uri": self.local.uri,
                    })
                except ClientError:
                    pass
            if self.is_acting_coordinator:
                self._spawn_resize()
            return

    def _rejoin(self, via_status: dict) -> None:
        """This node was evicted while partitioned (a reachable peer's
        member list no longer contains it): adopt the majority's
        membership + epoch, announce ourselves (the coordinator's
        node-join resize re-replicates toward us), and run the gated
        self-join fetch so the stale window is repaired before the
        query gate releases. Without this, a healed partition leaves
        the evicted side split-brained forever — each side serving its
        own ring."""
        if not self._rejoin_lock.acquire(blocking=False):
            return  # one rejoin at a time
        try:
            if self.logger is not None:
                self.logger.info(
                    "%s was evicted while partitioned; rejoining the "
                    "majority", self.local.id,
                )
            self.rejoins += 1
            with self._lock:
                replacement = {self.local.id: self.local}
                for n in via_status.get("nodes", []):
                    if n.get("id") and n["id"] != self.local.id:
                        replacement[n["id"]] = Node(n["id"], n["uri"])
                # members the adoption DROPS go to the forgotten
                # registry: if the majority's view is itself missing a
                # live node (cascading partitions), someone must still
                # probe-and-invite it back — a silently dropped member
                # is how split-brained 1-node clusters wedge forever
                dropped = {
                    node_id: node.uri
                    for node_id, node in self.nodes.items()
                    if node_id not in replacement
                }
                self.nodes = replacement
                self._heartbeat_failures.clear()
                self._forgotten = dropped
                self._note_membership_changed_locked()
            self.adopt_epoch(int(via_status.get("epoch", 0) or 0))
            self.adopt_placement(via_status.get("placement"))
            self.adopt_drain(via_status.get("drain"))
            self.degraded = False
            for node in self.sorted_nodes():
                if node.id == self.local.id:
                    continue
                try:
                    self._send_retry(node.uri, {
                        "type": "node-join", "id": self.local.id,
                        "uri": self.local.uri,
                    })
                except ClientError:
                    pass
            self.resize_fetch_async()
        finally:
            self._rejoin_lock.release()

    # ----------------------------------------------------------- join/resize

    def join(self, seed_uri: str) -> None:
        """Join an existing cluster via any seed node: announce ourselves,
        adopt the member list + schema, then fetch owned fragments
        (reference: memberlist join + coordinator ResizeInstructions —
        SURVEY.md §3.5)."""
        status = self.client.status(seed_uri)
        with self._lock:
            for n in status.get("nodes", []):
                self.nodes[n["id"]] = Node(n["id"], n["uri"])
            self._note_membership_changed_locked()
        # adopt the cluster's epoch before announcing: a node that
        # rejoins after an eviction must not carry a pre-partition epoch
        # into its first broadcasts
        self.adopt_epoch(int(status.get("epoch", 0) or 0))
        # the placement table rides the same status payload: a joiner
        # must compute the SAME ownership as the members from its first
        # resize-instruction onward; the drain record rides along so a
        # joiner can immediately act as a failover drain coordinator
        self.adopt_placement(status.get("placement"))
        self.adopt_drain(status.get("drain"))
        # Gate BEFORE announcing: the announce triggers the coordinator's
        # resize, whose post-resize cleanup waits for every member to
        # drain to NORMAL — this node must never be observable as NORMAL
        # in the window between its instruction-job finishing and its
        # self-join inventory fetch starting, or the cleanup could delete
        # the very fragments that fetch is about to pull.
        self._begin_local_fetch()
        try:
            # announce to everyone (including seed); retried — a missed
            # join announcement leaves a peer routing around this node
            # until the next catalog poll
            for node in self.sorted_nodes():
                if node.id == self.local.id:
                    continue
                try:
                    self._send_retry(
                        node.uri,
                        {"type": "node-join", "id": self.local.id,
                         "uri": self.local.uri},
                    )
                except ClientError:
                    pass
            # adopt schema from the seed
            schema = self.client.schema(seed_uri)
            for idx_schema in schema.get("indexes", []):
                self.handle_message(
                    {
                        "type": "create-index",
                        "index": idx_schema["name"],
                        **idx_schema.get("options", {}),
                    }
                )
                for f in idx_schema.get("fields", []):
                    self.handle_message(
                        {
                            "type": "create-field",
                            "index": idx_schema["name"],
                            "field": f["name"],
                            "options": f.get("options", {}),
                        }
                    )
            self.resize_fetch_async(pre_gated=True)
        except BaseException:
            self._end_local_fetch()
            raise

    def resize_fetch_async(self, pre_gated: bool = False) -> threading.Thread:
        """Self-join fetch as a background job — the async pattern the
        instruction-driven resize path uses (_run_resize_job): the joiner
        flips to RESIZING immediately (queries gate on wait_until_normal)
        and returns, so Server.open completes and the node answers
        /status and cluster messages while fragments stream in
        concurrently. Unlike the instruction path, no keepalives are
        sent: this is the pull-based fallback — no coordinator is
        awaiting a completion report, and progress is observable as
        state=RESIZING in /status. ``pre_gated``: the caller already
        holds the local-fetch gate (join() gates before announcing) and
        hands it to the fetch thread — exactly one begin per end."""
        if not pre_gated:
            self._begin_local_fetch()  # gate queries before returning
        t = threading.Thread(target=self._resize_fetch_gated, daemon=True,
                             name="self-join-fetch")
        try:
            t.start()
        except BaseException:
            # the thread never ran, so the gate would never drain and
            # the node would sit RESIZING forever. pre_gated: the
            # CALLER's exception handler releases its own begin — ending
            # here too would double-decrement and un-gate a later fetch
            if not pre_gated:
                self._end_local_fetch()
            raise
        return t

    def _peer_fragment_entries(self, index_name: str, peers=None):
        """(field, view, shard, source node) for every fragment any peer
        holds of one index — shared by resize fetches and the anti-entropy
        inventory walk. Peers are polled CONCURRENTLY (reference: one
        goroutine per node in cross-node walks — SURVEY.md §2 #12), so
        the walk costs the slowest peer's RTT, not the sum; an
        unreachable peer contributes nothing. ``peers`` restricts the
        walk (the fast-path sync only catalogs old-wire peers this way —
        manifests carry the catalog for everyone else)."""
        if peers is None:
            peers = [n for n in self.sorted_nodes()
                     if n.id != self.local.id]

        def one(node):
            try:
                catalog = self.client.fragment_catalog(node.uri, index_name)
            except ClientError:
                return []
            return [(e["field"], e["view"], e["shard"], node)
                    for e in catalog]

        return [e for chunk in concurrent_map(one, peers) for e in chunk]

    def _peer_entries_by_index(self) -> dict[str, list]:
        """One concurrent catalog walk per index, shared by the self-join
        inventory and the gated freshness sync (one walk, two consumers)."""
        return {
            name: self._peer_fragment_entries(name)
            for name in list(self.holder.indexes)
        }

    def _owned_missing_sources(self, peer_entries: dict | None = None) -> list[dict]:
        """Fetch-instruction list for every fragment this node owns but
        does not hold locally (the self-join inventory). One FETCH per
        fragment: with replicaN>1 the peer walk reports the same
        (field, view, shard) once per replica holding it, and fetching a
        full payload per replica would multiply join transfer — so extra
        replicas become ``fallbacks`` that fetch_fragments tries only if
        the first source errors. Fragments already present locally WITH
        DATA are left to anti-entropy's block diff instead of a redundant
        full fetch; an empty local fragment is re-fetched (it may be the
        placeholder of an earlier failed fetch, which must not mask the
        repair)."""
        if peer_entries is None:
            peer_entries = self._peer_entries_by_index()
        sources = []
        # key -> source dict, or None for a key already evaluated and
        # skipped (so replicaN>1 doesn't re-resolve/count per replica)
        by_key: dict[tuple, dict | None] = {}
        for index_name, idx in list(self.holder.indexes.items()):
            for fname, vname, shard, node in peer_entries.get(index_name, []):
                key = (index_name, fname, vname, shard)
                if key in by_key:
                    prior = by_key[key]
                    if prior is not None:
                        prior["fallbacks"].append(node.uri)
                    continue
                if not self.owns_shard(index_name, shard):
                    by_key[key] = None
                    continue
                field = idx.field(fname)
                view = field.view(vname) if field is not None else None
                frag = view.fragment(shard) if view is not None else None
                if frag is not None and frag.count() > 0:
                    by_key[key] = None  # already held locally with data
                    continue
                src = {
                    "index": index_name, "field": fname, "view": vname,
                    "shard": shard, "from": node.uri, "fallbacks": [],
                }
                by_key[key] = src
                sources.append(src)
        return sources

    def resize_fetch(self) -> None:
        """Synchronous form of the self-join fetch (tests/tools): run the
        background job and wait for it. Same error behavior as the async
        path — failures are logged and left to anti-entropy, not raised."""
        self.resize_fetch_async().join()

    def _resize_fetch_gated(self) -> None:
        """The fetch body, with the local-fetch gate already held;
        always releases it. A failure is logged loudly (the async join
        path has no caller to raise to) and leaves the gap to
        anti-entropy repair.

        Join absorption (elastic plane): the inventory fetch is ordered
        HOTTEST SHARD FIRST from the cluster heatmap — a joiner starts
        holding the shards that matter to the serving tail instead of a
        hash-random order — and every fetched fragment is byte-verified
        (block checksums vs its source) before it may skip the
        follow-on freshness diff. An unverified copy stays in the
        diff's work list, so the query gate never releases a fragment
        whose bytes were not either verified or block-diff repaired —
        reads for a shard serve only once its copy is byte-verified
        (the gate holds the whole node in RESIZING throughout)."""
        try:
            peer_entries = self._peer_entries_by_index()
            sources = self._owned_missing_sources(peer_entries)
            if len(sources) > 1:
                heat = self._cluster_shard_heat()
                if heat:
                    sources.sort(
                        key=lambda s: heat.get(
                            (s["index"], int(s["shard"])), 0.0),
                        reverse=True,
                    )
                    self.warm_heat_ordered += len(sources)
            self.fetch_fragments(sources)
            verified = self._verify_fetched(sources)
            # Freshness: fragments we ALREADY held may be stale from an
            # outage window (writes landed on replicas while this node
            # was away). Block-diff them against replicas before the
            # gate releases, so a rejoining node never serves the stale
            # window — the full fetch above covers only missing
            # fragments (the byte-verified ones skip here), a
            # checksum-block diff is far cheaper than re-downloading
            # every held payload, and the peer catalog walk is shared
            # with the inventory above.
            self.sync_holder(peer_entries=peer_entries, skip=verified)
        except Exception as e:  # noqa: BLE001 — must not die silently
            self._log_exception("self-join fragment fetch", e)
        finally:
            self._end_local_fetch()

    def _cluster_shard_heat(self) -> dict:
        """(index, shard) → heat merged from every reachable peer's
        heatmap — the join-absorption warm order. Best-effort: an
        unreachable peer (or a peer whose wire predates the heatmap
        route) contributes nothing, and an empty result leaves the
        fetch in catalog order."""
        peers = [n for n in self.sorted_nodes() if n.id != self.local.id]
        if not peers:
            return {}
        try:
            from pilosa_tpu.storage.heat import merge_shard_heat
        except Exception:  # noqa: BLE001 — heat plane absent
            return {}

        def one(node):
            try:
                return self.client.heatmap(
                    node.uri, timeout=self.heartbeat_timeout,
                ).get("shards", [])
            except Exception:  # noqa: BLE001 — old wire / unreachable
                return []

        try:
            return merge_shard_heat(concurrent_map(one, peers))
        except Exception:  # noqa: BLE001 — malformed rows must not
            return {}      # fail the join fetch

    def _verify_fetched(self, sources: list[dict]) -> set:
        """Byte-verify freshly fetched fragments against their primary
        source: a fragment whose 100-row block checksums match is
        warm-verified and may skip the follow-on freshness diff; a
        mismatch (the source advanced mid-fetch, a torn transfer, a
        fallback source supplied the bytes) or an unreachable source
        keeps the fragment IN the diff, which repairs it block-by-block
        before the gate releases."""
        verified: set = set()
        for src in sources:
            key = (src["index"], src["field"], src["view"], src["shard"])
            idx = self.holder.index(src["index"])
            field = idx.field(src["field"]) if idx else None
            view = field.view(src["view"]) if field is not None else None
            frag = (view.fragment(int(src["shard"]))
                    if view is not None else None)
            local_blocks = dict(frag.blocks()) if frag is not None else {}
            try:
                peer_blocks = dict(self.client.fragment_blocks(
                    src["from"], src["index"], src["field"], src["view"],
                    int(src["shard"]),
                ))
            except ClientError:
                self.warm_verify_failed += 1
                continue  # unverifiable: leave it to the freshness diff
            if local_blocks == peer_blocks:
                verified.add(key)
                self.warm_verified += 1
            else:
                self.warm_verify_failed += 1
        return verified

    def fetch_fragments(self, sources: list[dict]) -> int:
        """Execute the receiving half of resize instructions: fetch and
        union each listed fragment from its source node, with the HTTP
        fetches running concurrently. Fragment objects are resolved (and
        created) serially first — view.fragment(create=True) must not be
        raced for one (view, shard) — and the per-fragment union runs
        under each fragment's own lock.

        A joiner runs TWO overlapping fetch paths (its own inventory
        fetch and the coordinator's resize instruction), which can both
        transfer a fragment when their timing overlaps. That redundancy
        is DELIBERATE: the union is idempotent, and each path covers the
        other's failure modes (the instruction job can arrive before
        schema adoption and fetch nothing; the inventory can race a
        source's cleanup). An earlier claims registry that deduplicated
        them converted a failed instruction fetch into a permanent gap —
        the skipped inventory pass was the safety net.

        A fragment created here solely to receive the move is REMOVED
        again when every source failed to supply data and nothing else
        has written to it: an empty placeholder would otherwise (a)
        serve silently-empty reads for a shard whose data exists
        elsewhere and (b) mask the gap from the self-join inventory's
        "already held locally" check — the other half of the
        resize-source race (the receiver was left holding an empty
        fragment when its last usable source disappeared mid-move)."""
        work = []
        created: list[tuple] = []
        for src in sources:
            idx = self.holder.index(src["index"])
            field = idx.field(src["field"]) if idx else None
            if field is None:
                continue
            view = field.view(src["view"], create=True)
            existed = view.fragment(int(src["shard"])) is not None
            frag = view.fragment(int(src["shard"]), create=True)
            if not existed:
                created.append((view, int(src["shard"]), frag))
            work.append((src, frag))

        from pilosa_tpu.roaring.format import load_any
        from pilosa_tpu.utils.stats import global_stats

        probe_blocks = getattr(self.client, "fragment_blocks", None)

        def one(item):
            src, frag = item
            for source_uri in [src["from"], *src.get("fallbacks", [])]:
                # Block-checksum probe first: a
                # legitimately-empty fragment — advertised by the peer
                # catalog but holding no bits — would otherwise be
                # re-fetched as a full payload from EVERY replica on
                # every self-join/resize pass (the empty-payload check
                # below only fires after the download). The blocks list
                # is O(checksum rows), so an empty source costs one tiny
                # control response instead of a data-plane transfer.
                if probe_blocks is not None:
                    try:
                        if not probe_blocks(
                            source_uri, src["index"], src["field"],
                            src["view"], int(src["shard"]),
                        ):
                            global_stats().count(
                                "sync_empty_fetches_skipped", 1
                            )
                            continue  # source holds no data: next replica
                    except ClientError:
                        continue  # unreachable for the probe: data fetch
                                  # would fail the same way
                try:
                    data = self.client.fragment_data(
                        source_uri, src["index"], src["field"], src["view"],
                        int(src["shard"]),
                    )
                except ClientError:
                    continue  # replica fallback: try the next holder
                if not data:
                    continue  # source lacks the fragment; try a replica
                try:
                    bitmap, _ = load_any(data)
                except Exception:
                    # torn/corrupt payload (e.g. a snapshot mid-write on
                    # the source) must not abort the batch — a healthy
                    # replica may hold good data for this fragment
                    continue
                if bitmap.count() == 0:
                    # an EMPTY payload may be the placeholder of the
                    # source's own failed fetch — keep trying replicas
                    # rather than declaring the move done with no data
                    continue
                frag.import_roaring_bitmap(bitmap)
                return 1
            return 0  # no replica holds data (or all are unreachable)

        fetched = sum(concurrent_map(one, work))
        for view, shard, frag in created:
            # drop placeholders that never received data; a write that
            # landed concurrently bumped count() and keeps the fragment
            # (the identity check guards against a racing re-create)
            if frag.count() == 0 and view.fragment(shard) is frag:
                view.remove_fragments([shard])
        return fetched

    # Seconds between resize-progress keepalives while a fetch runs.
    RESIZE_PROGRESS_INTERVAL = 10.0

    def _run_resize_job(self, sources: list[dict], job: str,
                        reply_to: str | None,
                        pre_gated: bool = False) -> None:
        """Receiver worker for an async resize instruction: fetch, with a
        timer thread sending progress keepalives for as long as the fetch
        runs — wall-clock-based, not per-fragment, so one huge fragment
        cannot outlast the coordinator's quiet deadline silently — then
        report completion (reference resize-job pattern — nodes fetch
        asynchronously and report, SURVEY.md §3.5). ``pre_gated``: the
        message handler already holds the local-fetch gate (taken before
        spawning this worker) and hands it over — exactly one begin per
        the finally's end."""
        done = threading.Event()

        def keepalive() -> None:
            while not done.wait(self.RESIZE_PROGRESS_INTERVAL):
                try:
                    self.client.send_message(reply_to, {
                        "type": "resize-progress", "job": job,
                        "node": self.local.id,
                    })
                except ClientError:
                    pass

        if not pre_gated:
            self._begin_local_fetch()
        ka = None
        try:
            # keepalive start is INSIDE the gate's try: a thread-spawn
            # failure here must still release the handed-over gate, or
            # the node wedges RESIZING forever
            if reply_to:
                ka = threading.Thread(target=keepalive, daemon=True)
                ka.start()
            fetched = self.fetch_fragments(sources)
        except Exception as e:
            self._log_exception("resize-instruction fetch", e)
            fetched = -1  # report anyway: the coordinator must not wait
        finally:
            self._end_local_fetch()
            done.set()
        if ka is not None:
            ka.join(timeout=5)
        if reply_to:
            try:
                # retried: a single dropped completion report would hold
                # the cluster RESIZING for the full straggler timeout
                self._send_retry(reply_to, {
                    "type": "resize-complete", "job": job,
                    "node": self.local.id, "fetched": fetched,
                })
            except ClientError:
                pass  # coordinator's straggler timeout covers lost acks

    def _spawn_resize(self) -> None:
        threading.Thread(target=self.coordinate_resize, daemon=True,
                         name="coordinate-resize").start()

    def coordinate_resize(self) -> dict:
        """Coordinator-computed resize (reference ResizeInstruction —
        SURVEY.md §2 #13, §3.5): gather the cluster-wide fragment catalog,
        compute which fragments each owner is missing and a live source
        for each, gate queries cluster-wide (RESIZING), send every node
        its instruction list, then return the cluster to NORMAL.

        Runs are serialized: an overlapping run's NORMAL broadcast must
        not un-gate queries while another run is still moving fragments.
        """
        with self._resize_lock:
            return self._coordinate_resize_locked()

    def _coordinate_resize_locked(self) -> dict:
        if not self.is_acting_coordinator:
            return {}
        with self._lock:
            n_members = len(self.nodes)
        if n_members == 1:
            # a 1-node "cluster" has nothing to move, nobody to fence,
            # and — crucially — no business MINTING epochs: a node that
            # amputated its peers during a partition must not out-mint
            # the real majority, or the rejoin direction (lower epoch
            # surrenders) inverts and the majority would shatter itself
            self._command_state(STATE_NORMAL)
            return {}
        if not self.check_quorum():
            # minority side of a partition: degrade to serving locally-
            # owned reads instead of resizing against a minority view of
            # ownership — the pre-gate code's cleanup then deleted sole
            # surviving copies by that view (the data-loss scenario the
            # failure model in docs/OPERATIONS.md walks through)
            if self.logger is not None:
                self.logger.info(
                    "refusing to coordinate resize on %s: no member "
                    "quorum (cluster degraded)", self.local.id,
                )
            return {}
        # check_quorum adopted the reachable maximum, so this epoch
        # fences above every command the previous coordinator minted
        epoch = self._bump_epoch()
        self._note_acted(epoch, "resize")
        # fragment → holders (node ids), from local + peer catalogs
        holders: dict[tuple, list[Node]] = {}
        for index_name, idx in list(self.holder.indexes.items()):
            for field_name, field in list(idx.fields.items()):
                for view_name, view in list(field.views.items()):
                    for shard in list(view.fragments):
                        holders.setdefault(
                            (index_name, field_name, view_name, shard), []
                        ).append(self.local)
            for f, v, s, node in self._peer_fragment_entries(index_name):
                holders.setdefault((index_name, f, v, s), []).append(node)
        instructions: dict[str, list[dict]] = {}
        for (index_name, f, v, s), have in holders.items():
            have_ids = {n.id for n in have}
            live_sources = [n for n in have if n.state != STATE_DEGRADED]
            if not live_sources:
                continue
            owners = self.shard_nodes(index_name, s)
            owner_ids = {n.id for n in owners}
            for owner in owners:
                if owner.state == STATE_DEGRADED or owner.id in have_ids:
                    continue
                usable = [n for n in live_sources if n.id != owner.id]
                if not usable:
                    continue
                # extra live holders ride along as fallbacks, tried by
                # the receiver when the primary source errors mid-move
                # (fetch_fragments) — same contract as the self-join
                # inventory. OWNERS FIRST: a holder that remains an
                # owner keeps its copy, while a non-owner's copy is
                # deleted by this very resize's cleanup — a receiver
                # whose fetch races that cleanup loses its source (the
                # ~1-in-12 resize-source flake)
                usable.sort(key=lambda n: (n.id not in owner_ids, n.id))
                instructions.setdefault(owner.id, []).append({
                    "index": index_name, "field": f, "view": v, "shard": s,
                    "from": usable[0].uri,
                    "fallbacks": [n.uri for n in usable[1:]],
                })
        if not instructions:
            # A coordinator can die between broadcasting RESIZING and
            # NORMAL; if the failover coordinator then finds nothing to
            # move (e.g. replica_n == 1 left no live source) it must still
            # un-gate peers or every query fails with "cluster is
            # resizing" forever. Unconditional (not gated on local state):
            # the dying coordinator's RESIZING broadcast may have missed
            # THIS node while reaching others — idempotent and serialized
            # under _resize_lock, so always safe.
            self._broadcast_state(STATE_NORMAL, epoch)
            # a leave can complete with nothing to move (survivors
            # already hold everything) yet still change ownership —
            # non-owned leftovers must go now, not at the next resize
            self._broadcast_cleanup(epoch)
            return {}
        job = uuid.uuid4().hex
        with self._resize_cv:
            self._resize_job = job
            self._resize_pending = set()
            self._resize_deadline = (
                time.monotonic() + self.RESIZE_COMPLETE_TIMEOUT
            )
        self._broadcast_state(STATE_RESIZING, epoch)
        faults.crash_point("cluster.post-resizing-broadcast")
        try:
            local_sources = None
            for node_id, sources in instructions.items():
                if node_id == self.local.id:
                    local_sources = sources  # after the sends: peers
                    continue                 # fetch concurrently with us
                node = self.nodes.get(node_id)
                if node is None:
                    continue
                with self._resize_cv:
                    self._resize_pending.add(node_id)
                try:
                    self._send_retry(
                        node.uri,
                        {"type": "resize-instruction", "sources": sources,
                         "job": job, "reply_to": self.local.uri,
                         "epoch": epoch},
                    )
                except ClientError:
                    # failing the quick ack IS a health signal (unlike a
                    # long fetch, which no longer holds this request open)
                    node.state = STATE_DEGRADED
                    with self._resize_cv:
                        self._resize_pending.discard(node_id)
            if local_sources is not None:
                self.fetch_fragments(local_sources)
            # hold RESIZING (queries stay gated) until every peer reports
            # its fetch done. The deadline distinguishes dead from slow:
            # peers send resize-progress keepalives per fetched fragment,
            # each pushing the deadline out — a large move stays gated to
            # completion, while a silent straggler (died mid-fetch) is
            # released to anti-entropy repair after one quiet timeout.
            with self._resize_cv:
                while self._resize_pending:
                    remaining = self._resize_deadline - time.monotonic()
                    if remaining <= 0:
                        break
                    self._resize_cv.wait(remaining)
        finally:
            with self._resize_cv:
                self._resize_job = None
                self._resize_pending = set()
            self._broadcast_state(STATE_NORMAL, epoch)
            self._broadcast_cleanup(epoch)
        return instructions

    def _broadcast_state(self, state: str, epoch: int | None = None) -> None:
        # sent to EVERY node, including ones marked DEGRADED mid-resize: a
        # node that received RESIZING but is skipped for NORMAL would stay
        # gated forever (queries time out with "cluster is resizing");
        # epoch-stamped so a healed ex-coordinator's stale un-gate (or
        # re-gate) commands are rejected by everyone current
        self._command_state(state)
        message = {"type": "cluster-state", "state": state}
        if epoch is not None:
            message["epoch"] = epoch
        self._broadcast(message)

    def leave(self) -> None:
        """Graceful departure: announce node-leave so peers re-own our
        shards (they repair from replicas; with replica_n == 1 data must be
        drained beforehand — same caveat as the reference)."""
        self._left = True  # never auto-rejoin after a deliberate exit
        for node in self.sorted_nodes():
            if node.id == self.local.id:
                continue
            try:
                self._send_retry(
                    node.uri, {"type": "node-leave", "id": self.local.id}
                )
            except ClientError:
                pass

    # ----------------------------------------------------- translate tailing

    def sync_translate(self) -> int:
        """Replica side of key-translation replication: tail the
        coordinator's append log from our current offset (reference
        translate.go Reader — SURVEY.md §2 #9)."""
        if self.is_coordinator or self.holder.translate is None:
            return 0
        coord = self.coordinator
        try:
            data = self.client.translate_log(coord.uri, self._translate_offset)
        except ClientError:
            return 0
        if not data:
            return 0
        applied = self.holder.translate.apply_log(data)
        self._translate_offset += len(data)
        return applied

    # --------------------------------------------------------- anti-entropy

    def sync_holder(self, peer_entries: dict | None = None,
                    skip: set | None = None) -> dict:
        """One anti-entropy pass over every fragment this node replicates
        (reference HolderSyncer.SyncHolder — SURVEY.md §3.5). Returns
        repair counts for observability. ``peer_entries`` reuses an
        already-gathered catalog walk; ``skip`` excludes fragments just
        fetched in full (the gated self-join path uses both).

        Fast path (docs/OPERATIONS.md): per index, ONE batched manifest
        per peer replaces the per-fragment blocks GET storm (and the
        catalog walk — the manifest carries the peer's inventory), and
        the owned fragments then diff/fetch/apply as a bounded pipeline
        (``sync_workers`` wide), so the pass costs the slowest peer, not
        the sum over fragments. Differing blocks move as one multi-block
        delta POST per (fragment, peer). Peers whose wire predates the
        sync routes (404 once) fall back per-peer to the r5 per-fragment
        path; post-repair state is byte-identical either way, and the
        mutex/bool/BSI conflict-aware merge rules are unchanged.

        A sampled pass (trace-sample-rate) roots a ``sync.pass`` trace:
        per-peer manifest and delta spans nest under it and each peer's
        serving-side span lands in that peer's local /debug/traces under
        the propagated trace id (docs/OBSERVABILITY.md)."""
        from pilosa_tpu.utils.tracing import global_tracer

        with global_tracer().root_span("sync.pass"):
            return self._sync_holder_pass(peer_entries, skip)

    def _sync_holder_pass(self, peer_entries: dict | None = None,
                          skip: set | None = None) -> dict:
        from pilosa_tpu.utils.stats import global_stats

        t0 = time.perf_counter()
        repaired = {"fragments": 0, "bits": 0, "attr_blocks": 0}
        repaired["translate_ops"] = self.sync_translate()
        repaired["attr_blocks"] = self._sync_attrs()
        for index_name, idx in list(self.holder.indexes.items()):
            peers = [n for n in self.sorted_nodes()
                     if n.id != self.local.id]
            manifests = (self._peer_sync_manifests(index_name, peers)
                         if peers else {})
            # Inventory = local fragments ∪ peers' holdings: a replica
            # that never materialized an owned fragment must still
            # repair it (the reference syncer walks the schema ×
            # max-shard space, not just local files — SURVEY.md §3.5).
            # Manifests double as the peer catalog; only old-wire peers
            # still cost a catalog GET.
            inventory = set()
            for field_name, field in list(idx.fields.items()):
                for view_name, view in list(field.views.items()):
                    for shard in list(view.fragments):
                        inventory.add((field_name, view_name, shard))
            for m in manifests.values():
                if isinstance(m, dict):
                    inventory.update(m.keys())
            legacy_peers = [n for n in peers
                            if manifests.get(n.id) == "legacy"]
            if peer_entries is not None:
                inventory.update(
                    (f, v, s)
                    for f, v, s, _ in peer_entries.get(index_name, [])
                )
            elif legacy_peers:
                inventory.update(
                    (f, v, s) for f, v, s, _ in
                    self._peer_fragment_entries(index_name, legacy_peers)
                )
            work = []
            for key in sorted(inventory):
                field_name, view_name, shard = key
                if skip and (index_name, *key) in skip:
                    continue
                if not self.owns_shard(index_name, shard):
                    continue
                if idx.field(field_name) is None:
                    continue
                work.append(key)
            results = concurrent_map(
                lambda key: self._sync_fragment(index_name, idx, key,
                                                manifests),
                work, max_workers=max(1, self.sync_workers),
                return_exceptions=True,
            )
            for key, result in zip(work, results):
                if isinstance(result, Exception):
                    self._log_exception(
                        f"anti-entropy sync of {index_name}/{key}", result
                    )
                    continue
                repaired["fragments"] += result[0]
                repaired["bits"] += result[1]
        global_stats().timing("sync_pass", time.perf_counter() - t0)
        return repaired

    def _peer_sync_manifests(self, index_name: str, peers) -> dict:
        """Concurrently fetch one batched sync manifest per peer. Values:
        a ``{(field, view, shard): {block: checksum}}`` dict for peers
        that answered, the string ``"legacy"`` for peers without the
        route (repair falls back to per-fragment GETs against them), or
        None for peers unreachable this pass (skipped — their fragment
        GETs would fail identically, so nothing is lost but the RTTs)."""
        def one(node):
            if not self.client.supports_sync_manifest(node.uri):
                return node.id, "legacy"
            from pilosa_tpu.utils.tracing import global_tracer

            try:
                # sync.manifest span + X-Pilosa-Trace on the hop when a
                # sampled sync pass is active (sync_holder roots it);
                # the kwarg rides only when sampled so client doubles
                # predating it keep working on the untraced path
                with global_tracer().span("sync.manifest",
                                          node=node.id) as span:
                    kw = ({"trace": span.header_value()}
                          if span is not None else {})
                    entries = self.client.sync_manifest(
                        node.uri, index_name, **kw,
                    )
            except ClientError:
                if not self.client.supports_sync_manifest(node.uri):
                    return node.id, "legacy"  # 404/405: old wire
                return node.id, None  # transport fault: skip this pass
            except Exception as e:  # noqa: BLE001 — a malformed 200
                # (truncated body, undecodable protobuf) from ONE peer
                # must not abort the whole pass against every peer; the
                # per-fragment blast radius the old loop had is the bar
                self._log_exception(
                    f"sync manifest from {node.id}", e
                )
                return node.id, None
            return node.id, {
                (f, v, s): dict(blocks) for f, v, s, blocks in entries
            }

        return dict(concurrent_map(one, peers))

    def _sync_fragment(self, index_name: str, idx, key, manifests
                       ) -> tuple[int, int]:
        """Diff/fetch/apply one owned fragment against its replicas (one
        pipeline work item). Returns (blocks-with-adds, bits-added) —
        the same counting the serial pass reported."""
        field_name, view_name, shard = key
        field = idx.field(field_name)
        if field is None:
            return 0, 0
        replicas = [
            n for n in self.shard_nodes(index_name, shard)
            if n.id != self.local.id
        ]
        # Stray-copy absorption: a NON-owner whose manifest lists this
        # fragment still contributes — a write acked under an older
        # ring (or during a partition) may live only on a node that no
        # longer owns the shard, and cleanup_unowned refuses to delete
        # such a copy until an owner has demonstrably absorbed it.
        # Owners first (authoritative), strays after; the conflict-
        # aware merge rules below apply to both.
        replica_ids = {n.id for n in replicas} | {self.local.id}
        for node in self.sorted_nodes():
            if node.id in replica_ids:
                continue
            stray = manifests.get(node.id)
            if isinstance(stray, dict) and stray.get(key):
                replicas.append(node)
        view = field.view(view_name, create=True)
        # fragment created lazily at first merge so a sync pass that
        # repairs nothing leaves no empty fragment files
        frag = view.fragment(shard)
        local_blocks = dict(frag.blocks()) if frag is not None else {}
        blocks_repaired = 0
        bits = 0
        for node in replicas:
            manifest = manifests.get(node.id)
            if manifest is None:
                continue  # unreachable this pass
            if isinstance(manifest, dict):
                peer_blocks = manifest.get(key)
                if not peer_blocks:
                    continue  # peer holds no data for this fragment
            else:  # "legacy": old-wire peer, per-fragment blocks GET
                try:
                    peer_blocks = dict(self.client.fragment_blocks(
                        node.uri, index_name, field_name, view_name,
                        shard,
                    ))
                except ClientError:
                    continue
            # the ONE manifest-diff implementation (roaring/kernels.py),
            # shared with the CDC bulk sync and the scrub replica fetch
            wanted = kernels.diff_digests(local_blocks, peer_blocks)
            if not wanted:
                continue
            merged_any = False
            for block, bm in self._fetch_delta_blocks(
                    node, index_name, key, wanted):
                if bm is None or not bm.count():
                    continue
                if frag is None:
                    frag = view.fragment(shard, create=True)
                if field.options.type in ("mutex", "bool"):
                    # single-value fields: union repair would resurrect
                    # rows a newer import cleared; conflicting columns
                    # keep the local row
                    added = frag.add_ids_mutex(
                        kernels.fragment_ids(kernels.flatten(bm)))
                elif view_name == field.bsi_view_name():
                    # BSI planes: per-column all-or-nothing — unioning
                    # stale planes into a newer value would fabricate
                    # values
                    added = frag.add_ids_value(
                        kernels.fragment_ids(kernels.flatten(bm)))
                else:
                    added = frag.import_roaring_bitmap(bm)
                if added:
                    bits += added
                    blocks_repaired += 1
                    merged_any = True
            # Recompute the local checksum set ONLY when this peer
            # actually merged something: the serial pass re-hashed the
            # whole fragment after EVERY peer, so an N-replica cluster
            # with zero divergence still paid N full to_ids+hash walks
            # per fragment per pass.
            if merged_any:
                local_blocks = dict(frag.blocks())
        return blocks_repaired, bits

    def _fetch_delta_blocks(self, node, index_name: str, key, wanted):
        """[(block, RoaringBitmap)] for the wanted blocks of one fragment
        from one peer: ONE multi-block POST when the peer speaks
        /internal/sync/blocks, per-block GETs otherwise (old wire). A
        transport fault skips the peer for this fragment — the next pass
        retries."""
        from pilosa_tpu.utils.tracing import global_tracer

        field_name, view_name, shard = key
        if self.client.supports_sync_manifest(node.uri):
            try:
                with global_tracer().span(
                    "sync.blocks", node=node.id, blocks=len(wanted),
                ) as span:
                    kw = ({"trace": span.header_value()}
                          if span is not None else {})
                    bitmaps = self.client.sync_blocks(
                        node.uri, index_name,
                        [(field_name, view_name, shard, wanted)],
                        **kw,
                    )
                return list(zip(wanted, bitmaps))
            except ClientError:
                if self.client.supports_sync_manifest(node.uri):
                    return []  # transport fault: skip peer this pass
                # 404/405 was just recorded: old wire — fall through to
                # the per-block path below
            except Exception as e:  # noqa: BLE001 — torn frames or an
                # undecodable payload from this peer: skip it this pass
                # (the next pass retries) instead of failing the fragment
                self._log_exception(
                    f"sync delta blocks from {node.id}", e
                )
                return []
        out = []
        for block in wanted:
            try:
                out.append((block, self.client.fragment_block_bitmap(
                    node.uri, index_name, field_name, view_name, shard,
                    block,
                )))
            except ClientError:
                continue
        return out

    def _sync_attrs(self) -> int:
        """Diff + union attr-store blocks against every peer (reference
        attr-block sync — SURVEY.md §3.5). Attrs are replicated everywhere
        (they are tiny), matching the reference's attr stores living beside
        every fragment owner. Peers are walked CONCURRENTLY per store —
        this runs inside the gated self-join path, where serial per-peer
        RTTs would extend the query-blocking window; merge_block
        serializes on the store's own lock."""
        merged = 0
        peers = [n for n in self.sorted_nodes() if n.id != self.local.id]
        for index_name, idx in list(self.holder.indexes.items()):
            stores = [("", idx.column_attrs)]
            stores += [
                (fname, f.row_attrs)
                for fname, f in list(idx.fields.items())
                if f.row_attrs is not None
            ]
            for field_name, store in stores:
                if store is None:
                    continue
                local = dict(store.blocks())
                # one fetch per DISTINCT peer version of a block: attrs
                # replicate everywhere, so N-1 peers usually advertise
                # the same checksum for a stale local block — without
                # the claim set every peer would redundantly fetch and
                # merge it. Divergent versions (different checksums)
                # still all merge.
                claimed: set[tuple] = set()
                claim_lock = threading.Lock()

                def sync_peer(node, field_name=field_name, store=store,
                              local=local, claimed=claimed,
                              claim_lock=claim_lock):
                    n = 0
                    try:
                        peer = self.client._call(
                            "GET",
                            f"{node.uri}/internal/attrs/blocks"
                            f"?index={index_name}&field={field_name}",
                        )
                    except ClientError:
                        return 0
                    for entry in peer.get("blocks", []):
                        block, checksum = entry["block"], entry["checksum"]
                        if local.get(block) == checksum:
                            continue
                        with claim_lock:
                            if (block, checksum) in claimed:
                                continue
                            claimed.add((block, checksum))
                        try:
                            data = self.client._call(
                                "GET",
                                f"{node.uri}/internal/attrs/block/data"
                                f"?index={index_name}&field={field_name}"
                                f"&block={block}",
                            )
                        except ClientError:
                            continue
                        store.merge_block(data.get("attrs", {}))
                        n += 1
                    return n

                merged += sum(concurrent_map(sync_peer, peers))
        return merged
