"""Deterministic chaos harness: randomized partition/heal/kill/churn
schedules against a real in-process cluster under a mixed read+write
workload, gated on the four partition-safety oracles
(docs/OPERATIONS.md failure model):

1. **Zero lost acked writes** — every Set() a client saw acknowledged
   (HTTP 200, changed=true) is queryable cluster-wide after heal.
2. **No fragment deleted by a non-quorum node** — every
   ``cleanup_unowned`` decision is logged with its quorum verdict;
   any removal without quorum is an oracle failure.
3. **At most one coordinator acting per epoch** — every coordinated
   action (declare-dead, resize) records (epoch, node); two actors in
   one epoch means fencing failed.
4. **Byte-identical replicas after heal** — the PR-4 sync oracle: once
   converged, every owner of a fragment holds the same serialized
   bytes.

Schedules are seeded (``random.Random(seed)``) so a failing run
replays. Partitions are injected on the internal wire only
(testing/faults.py through the connection pool); the workload's edge
requests ride plain urllib, so the observer is never partitioned from
the nodes — a write acked through a reachable node counts even when
that node is about to be cut off.

Used by tests/test_partition.py: one quick schedule a variant in
tier-1, and the ``slow`` soak.
"""

from __future__ import annotations

import json
import os
import random
import threading
import time
import urllib.error
import urllib.request

from pilosa_tpu.shardwidth import SHARD_WIDTH
from pilosa_tpu.testing import faults

N_ROWS = 4
INDEX = "chaos"
FIELD = "f"


def _post(base: str, path: str, data: bytes,
          content_type: str = "application/json", timeout: float = 10.0):
    r = urllib.request.Request(f"{base}{path}", data=data, method="POST")
    r.add_header("Content-Type", content_type)
    with urllib.request.urlopen(r, timeout=timeout) as resp:
        return json.loads(resp.read() or b"{}")


class ChaosHarness:
    """One cluster + one seeded schedule of fault events under load."""

    def __init__(self, tmp_dir, n_nodes: int = 3, replica_n: int = 2,
                 seed: int = 0, n_events: int = 6,
                 event_gap_s: float = 0.3, writer_threads: int = 2,
                 reader_threads: int = 1, n_shards: int = 4,
                 with_storage_faults: bool = False,
                 with_autopilot: bool = False,
                 with_cdc: bool = False,
                 with_elastic: bool = False,
                 log=lambda msg: None):
        self.tmp_dir = str(tmp_dir)
        self.n_nodes = n_nodes
        self.replica_n = replica_n
        self.rng = random.Random(seed)
        self.n_events = n_events
        self.event_gap_s = event_gap_s
        self.writer_threads = writer_threads
        self.reader_threads = reader_threads
        self.n_shards = n_shards
        # storage-fault schedules (ISSUE 10): bit-flip a live replica's
        # fragment file on disk, ENOSPC one node's fsync path — gated
        # on the integrity oracle (every fragment's disk bytes verify
        # clean after heal, on top of the four partition oracles)
        self.with_storage_faults = with_storage_faults
        # autopilot-active schedules (ISSUE 15): every node runs the
        # placement-plane ticker on a hot interval, plus a forced-pass
        # event in the bag — the five oracles must hold while the
        # autopilot mints overrides and resizes UNDER the same faults
        self.with_autopilot = with_autopilot
        # CDC mirror schedules (ISSUE 16): an out-of-cluster follower
        # tails n0's WAL feed into its own holder for the whole
        # schedule — kills, restarts and partitions included — gated on
        # the byte-identical mirror oracle (everything n0 holds after
        # heal is byte-identical in the mirror once its cursor passes
        # n0's durable seq)
        self.with_cdc = with_cdc
        # elastic-drain schedules (ISSUE 17): the bag gains a graceful
        # drain of a random member, and kills/partitions then land MID-
        # DRAIN — all six oracles must hold while shard groups move off
        # the target, its CDC cursors hand off, and it leaves the ring;
        # the finale aborts whatever drain is still in flight, retires
        # nodes that departed, and restarts them as fresh joiners
        self.with_elastic = with_elastic
        self.drains_started = 0
        self.cdc_mirror = None
        self.cdc_mirror_holder = None
        self.autopilot_moves = 0
        self.disk_plane = None
        self.corruptions_injected = 0
        self.disk_fault_rules: list[int] = []
        self.log = log
        self.servers: dict[str, object] = {}   # name -> live Server
        self.downed: dict[str, int] = {}       # name -> port to rebind
        self.plane = None
        self._lock = threading.Lock()
        self._stop = threading.Event()
        # acked-write ledger: (row, col) the workload saw acknowledged
        self.acked: set[tuple[int, int]] = set()
        self.write_errors = 0
        self.writes_acked = 0
        self.events: list[str] = []
        # harvested across restarts (a closed Server's cluster object
        # would otherwise take its logs with it)
        self.all_acted: list[tuple[int, str, str]] = []  # (epoch, node, act)
        self.all_cleanups: list[dict] = []

    # ------------------------------------------------------------- lifecycle

    def _make_server(self, name: str, seeds: list[str], port: int = 0):
        from pilosa_tpu.server import Server, ServerConfig

        autopilot_cfg = dict(
            # hot enough that the ticker fires between events; the
            # tight 1.2 budget makes even mild skew actionable, so
            # schedules actually exercise placement moves under faults
            autopilot_enabled=True, autopilot_interval=0.5,
            autopilot_heat_budget=1.2, autopilot_min_dwell=1.0,
        ) if self.with_autopilot else {}
        server = Server(ServerConfig(
            data_dir=f"{self.tmp_dir}/{name}", port=port, name=name,
            replica_n=self.replica_n, seeds=seeds,
            anti_entropy_interval=0, heartbeat_interval=0,
            heartbeat_timeout=0.5, use_mesh=False, **autopilot_cfg,
        )).open()
        cluster = server.api.cluster
        # instance-attr overrides: fast backoffs + short drains so the
        # schedule's wall time is events, not timeouts
        cluster.SEND_BACKOFF_S = 0.01
        cluster.CLEANUP_DRAIN_TIMEOUT = 2.0
        cluster.RESIZE_COMPLETE_TIMEOUT = 10.0
        if self.with_storage_faults:
            # fast degraded-mode recovery so ENOSPC events heal within
            # the schedule's gaps, not its lifetime
            server.holder.health.PROBE_INTERVAL_S = 0.2
        return server

    def boot(self) -> "ChaosHarness":
        self.plane = faults.install()
        if self.with_storage_faults:
            self.disk_plane = faults.install_disk()
        for i in range(self.n_nodes):
            name = f"n{i}"
            seeds = ([self._uri(next(iter(self.servers.values())))]
                     if self.servers else [])
            self.servers[name] = self._make_server(name, seeds)
        for s in self.servers.values():
            s.api.cluster.wait_until_normal(30)
        base = self._uri(self.servers["n0"])
        _post(base, f"/index/{INDEX}", b"{}")
        _post(base, f"/index/{INDEX}/field/{FIELD}", b"{}")
        if self.with_cdc:
            self._start_cdc_mirror()
        return self

    def _start_cdc_mirror(self) -> None:
        """Boot the CDC mirror: a follower outside the cluster tailing
        n0's feed into its own holder. Its InternalClient carries no
        node identity (``fault_source`` stays ``""``), so the named
        partition rules the schedule installs never match it — like the
        urllib workload, the observer is not partitioned from the
        system under test. n0 kills reset the seq space mid-schedule;
        the follower answers the resulting FeedGone (unknown-cursor
        410) with a merge resync, which converges because the chaos
        workload is add-only and kills are graceful closes (the durable
        WAL state survives)."""
        import types

        from pilosa_tpu.cdc.tailer import CdcFollower
        from pilosa_tpu.parallel.client import InternalClient
        from pilosa_tpu.storage import Holder

        self.cdc_mirror_holder = Holder(
            f"{self.tmp_dir}/cdc_mirror").open()
        self.cdc_mirror = CdcFollower(
            types.SimpleNamespace(holder=self.cdc_mirror_holder),
            InternalClient(timeout=10.0),
            self._uri(self.servers["n0"]),
            poll_interval=0.05, cursor_name="chaos-mirror",
        )
        self.cdc_mirror.start()

    def close(self) -> None:
        self._stop.set()
        if self.cdc_mirror is not None:
            self.cdc_mirror.stop()
            self.cdc_mirror = None
        if self.cdc_mirror_holder is not None:
            try:
                self.cdc_mirror_holder.close()
            except Exception:  # noqa: BLE001 — teardown must finish
                pass
            self.cdc_mirror_holder = None
        with self._lock:
            servers = list(self.servers.values())
            self.servers = {}
        for s in servers:
            self._harvest(s)
            try:
                s.close()
            except Exception:  # noqa: BLE001 — teardown must finish
                pass
        faults.clear()
        faults.clear_disk()

    @staticmethod
    def _uri(server) -> str:
        return f"http://localhost:{server.port}"

    def _harvest(self, server) -> None:
        cluster = server.api.cluster
        name = cluster.local.id
        self.all_acted.extend(
            (epoch, name, action) for epoch, action in cluster.acted_epochs
        )
        self.all_cleanups.extend(cluster.cleanup_log)
        cluster.acted_epochs.clear()
        cluster.cleanup_log.clear()
        pilot = getattr(server.api, "autopilot", None)
        if pilot is not None:
            # zero after read: kills, oracle checks, and close() all
            # harvest the same server — a counter read twice would
            # double-count the schedule's move total
            self.autopilot_moves += pilot.moves_executed
            pilot.moves_executed = 0

    def _live(self) -> list:
        with self._lock:
            return list(self.servers.values())

    # -------------------------------------------------------------- workload

    def _writer(self, t: int) -> None:
        i = 0
        while not self._stop.is_set():
            servers = self._live()
            if not servers:
                time.sleep(0.05)
                continue
            server = self.rng.choice(servers)
            shard = i % self.n_shards
            pos = t * 100_000 + (i // self.n_shards)
            col = shard * SHARD_WIDTH + pos
            row = 1 + (i % N_ROWS)
            i += 1
            try:
                out = _post(self._uri(server), f"/index/{INDEX}/query",
                            f"Set({col}, {FIELD}={row})".encode(),
                            content_type="text/plain", timeout=5.0)
            except Exception:  # noqa: BLE001 — shed/refused/timeout:
                # unacked, so the ledger owes nothing for it
                self.write_errors += 1
                continue
            if out.get("results") == [True]:
                with self._lock:
                    self.acked.add((row, col))
                    self.writes_acked += 1
            time.sleep(0.01)

    def _reader(self) -> None:
        while not self._stop.is_set():
            servers = self._live()
            if servers:
                try:
                    _post(self._uri(self.rng.choice(servers)),
                          f"/index/{INDEX}/query",
                          f"Count(Row({FIELD}=1))".encode(),
                          content_type="text/plain", timeout=5.0)
                except Exception:  # noqa: BLE001 — reads may 503 on a
                    pass           # degraded minority; that IS the design
            time.sleep(0.02)

    # --------------------------------------------------------------- events

    def _heartbeat_round(self) -> None:
        for s in self._live():
            try:
                s.api.cluster.heartbeat()
                # chaos servers run heartbeat_interval=0 (the harness IS
                # the ticker), so drain resumption after a coordinator
                # kill rides this round exactly as the server tick would
                if s.api.elastic is not None:
                    s.api.elastic.maybe_resume()
            except Exception:  # noqa: BLE001 — a heartbeat pass racing
                pass           # a concurrent kill must not abort the run

    def _event_partition(self) -> str:
        self.plane.heal()
        names = sorted(self.servers) + sorted(self.downed)
        self.rng.shuffle(names)
        cut = self.rng.randrange(1, len(names))
        side_a, side_b = names[:cut], names[cut:]
        symmetric = self.rng.random() < 0.6
        for a in side_a:
            for b in side_b:
                self.plane.partition(a, b, bidirectional=symmetric)
        kind = "sym" if symmetric else "asym"
        return f"partition[{kind}] {side_a}|{side_b}"

    def _event_heal(self) -> str:
        self.plane.heal()
        return "heal"

    def _event_kill(self) -> str:
        with self._lock:
            if len(self.servers) < 3:
                return "kill-skipped"  # keep ≥2 alive for the workload
            name = self.rng.choice(sorted(self.servers))
            server = self.servers.pop(name)
        self._harvest(server)
        # remember the PORT: a restarted node comes back on its old
        # advertised address, like a real deployment — peers' member
        # lists and forgotten-peer registries hold URIs, and a node
        # that silently moves ports is undiscoverable by either
        self.downed[name] = server.port
        server.close()
        return f"kill {name}"

    def _event_corrupt(self) -> str:
        """Bit-flip one byte of a random live snapshotted fragment ON
        DISK — silent media rot. The live bitmap stays healthy (that is
        the point: replicas hold every acked write), and the scrub
        passes in converge must detect, quarantine, and read-repair it;
        the integrity oracle then proves the disk verifies clean."""
        candidates = []
        for server in self._live():
            for idx in server.holder.indexes.values():
                for field in idx.fields.values():
                    for view in field.views.values():
                        for frag in view.fragments.values():
                            # select by LIVE content: in group mode the
                            # file is a bare header until the snapshot
                            # below materializes it
                            if frag.count() > 0:
                                candidates.append((server, frag))
        if not candidates:
            return "corrupt-skipped"
        server, frag = self.rng.choice(candidates)
        # ensure file+sidecar describe real content, then flip a byte
        # of the snapshot payload (past the 20-byte header)
        try:
            frag.snapshot()
            size = os.path.getsize(frag.path)
            if size <= 20:
                return "corrupt-skipped"
            offset = self.rng.randrange(20, size)
            with open(frag.path, "r+b") as f:
                f.seek(offset)
                byte = f.read(1)
                f.seek(offset)
                f.write(bytes([byte[0] ^ (1 << self.rng.randrange(8))]))
        except OSError:
            return "corrupt-skipped"
        self.corruptions_injected += 1
        return (f"corrupt {server.config.name}:"
                f"{frag.index}/{frag.field}/{frag.view}/{frag.shard}"
                f"@{offset}")

    def _event_disk_full(self) -> str:
        """ENOSPC on one node's fsync path: its writes shed 503 and the
        node flips storage-degraded until the heal event (or finale)
        removes the rule and the probe clears the latch."""
        if self.disk_plane is None:
            return "disk-full-skipped"
        names = sorted(self.servers)
        if not names:
            return "disk-full-skipped"
        name = self.rng.choice(names)
        import errno as _errno

        rule = self.disk_plane.add(
            "fsync", path=f"{self.tmp_dir}/{name}/",
            errno_=_errno.ENOSPC,
        )
        self.disk_fault_rules.append(rule.id)
        return f"disk-full {name}"

    def _heal_disk(self) -> int:
        if self.disk_plane is None:
            return 0
        removed = 0
        for rule_id in self.disk_fault_rules:
            removed += bool(self.disk_plane.remove(rule_id))
        self.disk_fault_rules = []
        return removed

    def _event_restart(self) -> str:
        if not self.downed:
            return "restart-skipped"
        name = self.rng.choice(sorted(self.downed))
        port = self.downed.pop(name)
        live = self._live()
        seeds = [self._uri(live[0])] if live else []
        server = self._make_server(name, seeds, port=port)
        with self._lock:
            self.servers[name] = server
        return f"restart {name}"

    def _event_autopilot_pass(self) -> str:
        """Force a planner pass NOW on the acting coordinator — the
        0.5s tickers run too, but a bag event guarantees the schedule
        exercises plan/apply/resize at adversarial moments (right
        after a kill, inside a partition) instead of between them."""
        for s in self._live():
            if s.api.cluster.is_acting_coordinator:
                pilot = s.api.autopilot
                if pilot is None:
                    return "autopilot-skipped (pilot not wired)"
                try:
                    record = pilot.run_pass()
                except Exception as e:  # noqa: BLE001 — an event must
                    return f"autopilot-error {e!r}"  # not kill the run
                if record.get("acted"):
                    return (f"autopilot-pass {s.config.name} "
                            f"moves={len(record.get('moves', []))}")
                return (f"autopilot-pass {s.config.name} "
                        f"skip={record.get('reason')}")
        return "autopilot-skipped (no live coordinator)"

    def _event_drain(self) -> str:
        """Start a graceful drain of a random member through the acting
        coordinator — subsequent bag events (kills, partitions, more
        heartbeats) then land mid-drain, which is the point. Victims
        exclude the coordinator (it drives the move) and, under
        with_cdc, n0 (the mirror oracle compares against n0's holder).
        Refusals (drain already in flight, degraded, too few nodes) are
        the elastic plane's guardrails working; they log and move on."""
        live = self._live()
        if len(live) < 3:
            return "drain-skipped (<3 live)"
        coord = next((s for s in live
                      if s.api.cluster.is_acting_coordinator), None)
        if coord is None:
            return "drain-skipped (no live coordinator)"
        victims = sorted(
            s.config.name for s in live
            if s.config.name != coord.config.name
            and not (self.with_cdc and s.config.name == "n0")
        )
        if not victims:
            return "drain-skipped (no eligible victim)"
        victim = self.rng.choice(victims)
        try:
            coord.api.elastic.start_drain(victim)
        except Exception as e:  # noqa: BLE001 — guardrail refusals
            return f"drain-refused {e}"
        self.drains_started += 1
        return f"drain {victim} (via {coord.config.name})"

    def _settle_drains(self) -> None:
        """Finale, step one: no drain may still be mutating placement
        while the finale rebuilds full membership. Abort the active
        record on the acting coordinator, then wait out every worker
        thread (an abort is only observed at the worker's next state
        advance)."""
        for s in self._live():
            c = s.api.cluster
            if (c.is_acting_coordinator
                    and getattr(c, "drain_active", False)):
                try:
                    s.api.elastic.abort_drain()
                    self.log("  finale: drain-abort "
                             f"{c.drain_record.get('target')}")
                except Exception:  # noqa: BLE001 — already terminal
                    pass
                break
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            busy = [s for s in self._live()
                    if s.api.elastic is not None
                    and getattr(s.api.elastic, "_thread", None) is not None
                    and s.api.elastic._thread.is_alive()]
            if not busy:
                return
            time.sleep(0.1)

    def _retire_departed(self) -> None:
        """Finale, step two: a drained target LEFT the ring (its
        ``_left`` latch refuses auto-rejoin), but its server object is
        still running read-only. Retire it like a kill — harvest,
        remember the port, close — so the restart loop below brings it
        back as a fresh joiner and convergence reaches full membership."""
        with self._lock:
            departed = [name for name, s in self.servers.items()
                        if getattr(s.api.cluster, "_left", False)]
            retired = {name: self.servers.pop(name) for name in departed}
        for name, server in retired.items():
            self._harvest(server)
            self.downed[name] = server.port
            server.close()
            self.log(f"  finale: retire-departed {name}")

    def run_schedule(self) -> dict:
        """Workload on, randomized events, then heal + converge and
        check every oracle. Returns the schedule's record."""
        threads = [
            threading.Thread(target=self._writer, args=(t,), daemon=True)
            for t in range(self.writer_threads)
        ] + [
            threading.Thread(target=self._reader, daemon=True)
            for _ in range(self.reader_threads)
        ]
        for t in threads:
            t.start()
        choices = [
            (self._event_partition, 4), (self._event_heal, 2),
            (self._event_kill, 2), (self._event_restart, 2),
        ]
        if self.with_storage_faults:
            choices += [(self._event_corrupt, 3),
                        (self._event_disk_full, 2)]
        if self.with_autopilot:
            choices += [(self._event_autopilot_pass, 3)]
        if self.with_elastic:
            choices += [(self._event_drain, 3)]
        bag = [fn for fn, w in choices for _ in range(w)]
        t0 = time.monotonic()
        for _ in range(self.n_events):
            event = self.rng.choice(bag)()
            self.events.append(event)
            self.log(f"  event: {event}")
            # liveness passes between events: detection, death
            # declaring, degradation flips all ride heartbeats
            for _ in range(2):
                time.sleep(self.event_gap_s / 2)
                self._heartbeat_round()
        # end of schedule: stop faults, bring everything back, converge
        self._stop.set()
        for t in threads:
            t.join(timeout=10)
        self.plane.heal()
        self._heal_disk()
        if self.with_elastic:
            self._settle_drains()
            self._retire_departed()
        while self.downed:
            self.log(f"  finale: {self._event_restart()}")
        converged = self._converge(deadline_s=60)
        record = self._check_oracles()
        record.update({
            "events": list(self.events),
            "drains": self.drains_started,
            "converged": converged,
            "converge_diag": getattr(self, "converge_diag", None),
            "acked_writes": len(self.acked),
            "write_errors": self.write_errors,
            "wall_s": round(time.monotonic() - t0, 2),
        })
        return record

    # ----------------------------------------------------------- convergence

    def _converge(self, deadline_s: float = 90.0) -> bool:
        full = {f"n{i}" for i in range(self.n_nodes)}
        deadline = time.monotonic() + deadline_s
        while time.monotonic() < deadline:
            self._heartbeat_round()
            self._heartbeat_round()  # suspect→dead/rejoin need streaks
            servers = self._live()
            # drain any pending/background resizes through the acting
            # coordinator's serialized resize lock
            for s in servers:
                if s.api.cluster.is_acting_coordinator:
                    try:
                        s.api.cluster.coordinate_resize()
                    except Exception:  # noqa: BLE001
                        pass
                    break
            ok = all(
                set(s.api.cluster.nodes) == full
                and s.api.cluster.wait_until_normal(5)
                and not s.api.cluster.degraded
                for s in servers
            ) and len(servers) == self.n_nodes
            if ok:
                break
            time.sleep(0.2)
        else:
            # capture WHY for the record — unconverged runs are
            # otherwise undebuggable after the fact
            self.converge_diag = {
                s.config.name: {
                    "members": sorted(s.api.cluster.nodes),
                    "state": s.api.cluster.state,
                    "degraded": s.api.cluster.degraded,
                    "epoch": s.api.cluster.epoch,
                } for s in self._live()
            }
            return False
        # repair passes until quiescent (bounded): every node pulls the
        # blocks it is missing from its replicas. With storage faults
        # on, each round leads with a scrub pass — injected rot must be
        # detected/quarantined BEFORE sync (quarantine-then-sync is the
        # read-repair; syncing a corrupt-on-disk fragment first would
        # never surface it)
        for _ in range(4):
            repaired = 0
            if self.with_storage_faults:
                # any still-degraded node blocks its own repair writes:
                # wait out the probe first
                for s in self._live():
                    deadline2 = time.monotonic() + 5
                    while (s.holder.health.degraded
                           and time.monotonic() < deadline2):
                        time.sleep(0.1)
                for s in self._live():
                    try:
                        repaired += s.api.scrub_now()["corrupt"]
                    except Exception:  # noqa: BLE001
                        repaired += 1
            for s in self._live():
                try:
                    repaired += s.api.cluster.sync_holder()["bits"]
                except Exception:  # noqa: BLE001
                    repaired += 1  # retry next round
            if repaired == 0:
                break
        return True

    # -------------------------------------------------------------- oracles

    def _check_oracles(self) -> dict:
        for s in self._live():
            self._harvest(s)
        lost = self._oracle_lost_writes()
        non_quorum_deletions = [
            e for e in self.all_cleanups
            if e.get("removed") and not e.get("quorum")
        ]
        actors_by_epoch: dict[int, set[str]] = {}
        for epoch, name, _action in self.all_acted:
            actors_by_epoch.setdefault(epoch, set()).add(name)
        conflicts = {e: sorted(a) for e, a in actors_by_epoch.items()
                     if len(a) > 1}
        mismatches = self._oracle_replica_identity()
        cdc_mismatches = (self._oracle_cdc_mirror()
                          if self.with_cdc else [])
        dirty_disk = (self._oracle_disk_integrity()
                      if self.with_storage_faults else [])
        degraded_stuck = [
            s.config.name for s in self._live()
            if self.with_storage_faults and s.holder.health.degraded
        ]
        return {
            "lost_acked_writes": len(lost),
            "lost_sample": sorted(lost)[:5],
            "non_quorum_deletions": len(non_quorum_deletions),
            "coordinator_conflicts": conflicts,
            "replica_mismatches": mismatches,
            "corruptions_injected": self.corruptions_injected,
            "disk_integrity_failures": dirty_disk,
            "degraded_stuck": degraded_stuck,
            "autopilot_moves": self.autopilot_moves,
            "cdc_mirror_mismatches": cdc_mismatches,
            "cdc_resyncs": (self.cdc_mirror.resyncs_total
                            if self.cdc_mirror is not None else 0),
            "cdc_applied_ops": (self.cdc_mirror.applied_ops_total
                                if self.cdc_mirror is not None else 0),
            "epochs_acted": len(actors_by_epoch),
            "ok": (not lost and not non_quorum_deletions
                   and not conflicts and not mismatches
                   and not dirty_disk and not degraded_stuck
                   and not cdc_mismatches),
        }

    def _oracle_cdc_mirror(self) -> list:
        """The CDC mirror oracle (ISSUE 16): after heal + converge, the
        out-of-cluster follower tailing n0 holds a byte-identical copy
        of every non-empty fragment n0 holds. Sound because EVERY write
        into n0's fragments — client Sets and anti-entropy repair alike
        — rides ``add_ids`` into the WAL, so it reached the mirror in
        the bulk sync or through the feed; waiting for the mirror's
        cursor to pass n0's durable seq turns the comparison into a
        barrier instead of a race. Mirror-⊇-n0, not equality: ownership
        churn can leave the mirror holding tombstoned leftovers whose
        delete fell in a resync window, which is the documented merge-
        resync semantics, not divergence."""
        n0 = self.servers.get("n0")
        if n0 is None or self.cdc_mirror is None:
            return ["n0 or mirror not live at oracle time"]
        wal = n0.holder.wal
        wal.barrier()
        durable = wal.durable_seq()
        # compare-until-deadline, not wait-then-compare: right after an
        # n0 restart the mirror can still carry a cursor from the OLD
        # seq space (numerically past the fresh durable) with its
        # unknown-cursor 410 resync in flight — a single cursor check
        # would green-light a comparison against a mid-resync mirror.
        # Nothing writes n0 after convergence, so a passing comparison
        # is stable; a persistent mismatch still fails loudly.
        deadline = time.monotonic() + 30.0
        mismatches = ["mirror never caught up for a comparison"]
        while time.monotonic() < deadline:
            since = self.cdc_mirror._since
            if since is None or since < durable:
                time.sleep(0.1)
                continue
            mismatches = self._cdc_mirror_diff(n0)
            if not mismatches:
                return []
            time.sleep(0.2)
        return mismatches

    def _cdc_mirror_diff(self, n0) -> list:
        mirror = self.cdc_mirror_holder
        mismatches = []
        for iname, idx in n0.holder.indexes.items():
            for fname, field in idx.fields.items():
                for vname, view in field.views.items():
                    for shard, frag in list(view.fragments.items()):
                        if not frag.count():
                            continue
                        midx = mirror.index(iname)
                        mf = midx.field(fname) if midx else None
                        mv = mf.view(vname) if mf else None
                        mfrag = mv.fragment(shard) if mv else None
                        if (mfrag is None
                                or mfrag.serialize_snapshot()
                                != frag.serialize_snapshot()):
                            mismatches.append(
                                f"{iname}/{fname}/{vname}/{shard}")
        return mismatches

    def _oracle_disk_integrity(self) -> list:
        """The corruption oracle (ISSUE 10): after heal + scrub, every
        fragment's BYTES ON DISK decode cleanly and match their
        checksum sidecar — injected rot was detected, quarantined, and
        repaired (or rewritten), never left to be served or replicated.
        Returns the list of still-dirty fragment paths."""
        from pilosa_tpu.storage import integrity

        dirty = []
        for server in self._live():
            for idx in server.holder.indexes.values():
                for field in idx.fields.values():
                    for view in field.views.values():
                        for frag in list(view.fragments.values()):
                            try:
                                integrity.verify_fragment_file(frag.path)
                            except integrity.CorruptFragmentError as e:
                                dirty.append(str(e))
                            except OSError:
                                continue
        return dirty

    def _oracle_lost_writes(self) -> set:
        """Every acked (row, col) must be queryable cluster-wide."""
        with self._lock:
            acked = set(self.acked)
        if not acked:
            return set()
        servers = self._live()
        missing = set(acked)
        for attempt in range(3):
            got: set[tuple[int, int]] = set()
            probe = servers[attempt % len(servers)]
            for row in range(1, N_ROWS + 1):
                try:
                    out = _post(self._uri(probe), f"/index/{INDEX}/query",
                                f"Row({FIELD}={row})".encode(),
                                content_type="text/plain", timeout=30.0)
                except Exception:  # noqa: BLE001
                    continue
                got.update((row, c) for c in
                           out.get("results", [{}])[0].get("columns", []))
            missing = acked - got
            if not missing:
                return set()
            # not yet converged: another repair round, then re-ask
            for s in servers:
                try:
                    s.api.cluster.sync_holder()
                except Exception:  # noqa: BLE001
                    pass
        return missing

    def _oracle_replica_identity(self) -> list:
        """Post-heal, every owner of a fragment holds byte-identical
        data (the PR-4 sync oracle); an owner missing a fragment other
        owners hold non-empty is a mismatch too."""
        servers = self._live()
        keys: set[tuple[str, str, str, int]] = set()
        for s in servers:
            for iname, idx in s.holder.indexes.items():
                for fname, field in idx.fields.items():
                    for vname, view in field.views.items():
                        for shard in view.fragments:
                            keys.add((iname, fname, vname, shard))
        mismatches = []
        for iname, fname, vname, shard in sorted(keys):
            owners = [s for s in servers
                      if s.api.cluster.owns_shard(iname, shard)]
            payloads = {}
            for s in owners:
                idx = s.holder.index(iname)
                field = idx.field(fname) if idx else None
                view = field.view(vname) if field else None
                frag = view.fragment(shard) if view else None
                payloads[s.config.name] = (
                    frag.serialize_snapshot()
                    if frag is not None and frag.count() else b""
                )
            distinct = set(payloads.values())
            if len(distinct) > 1:
                mismatches.append({
                    "fragment": f"{iname}/{fname}/{vname}/{shard}",
                    "holders": {k: len(v) for k, v in payloads.items()},
                })
        return mismatches


class MpServingChaos:
    """Kill-a-worker schedule for the multi-process serving tier
    (ISSUE 11): one device-owner + N ``SO_REUSEPORT`` workers under a
    mixed read+write load; the schedule SIGKILLs random workers
    mid-burst. Two oracles gate it:

    1. **Zero lost acked writes** — every Set() a client saw 200-acked
       through ANY worker is queryable afterwards (the WAL ACK barrier
       crossed the ring; a worker death must not un-happen it).
    2. **Owner never wedges** — after every kill the owner still
       answers a probe query within a bounded deadline (dead workers'
       in-flight ring slots were reclaimed, nothing blocks the drain
       loops) and the worker fleet respawns back to N.
    """

    PROBE_DEADLINE_S = 10.0
    RESPAWN_DEADLINE_S = 30.0

    def __init__(self, tmp_dir, n_workers: int = 2, seed: int = 0,
                 n_kills: int = 3, kill_gap_s: float = 0.8,
                 writer_threads: int = 3, reader_threads: int = 2,
                 log=lambda msg: None):
        self.tmp_dir = str(tmp_dir)
        self.n_workers = n_workers
        self.rng = random.Random(seed)
        self.n_kills = n_kills
        self.kill_gap_s = kill_gap_s
        self.writer_threads = writer_threads
        self.reader_threads = reader_threads
        self.log = log
        self.server = None
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self.acked: set[tuple[int, int]] = set()
        self.write_errors = 0
        self.events: list[str] = []
        self.wedges: list[str] = []

    def boot(self) -> "MpServingChaos":
        import socket as _socket

        from pilosa_tpu.server import Server, ServerConfig

        if not hasattr(_socket, "SO_REUSEPORT"):
            raise RuntimeError("SO_REUSEPORT unavailable")
        self.server = Server(ServerConfig(
            data_dir=self.tmp_dir, port=0, name="mpchaos",
            serving_workers=self.n_workers, anti_entropy_interval=0,
            heartbeat_interval=0, use_mesh=False,
        )).open()
        if self.server._mpserve is None:
            raise RuntimeError("multi-process serving did not start")
        base = f"http://localhost:{self.server.port}"
        _post(base, f"/index/{INDEX}", b"{}")
        _post(base, f"/index/{INDEX}/field/{FIELD}", b"{}")
        return self

    def close(self) -> None:
        self._stop.set()
        if self.server is not None:
            self.server.close()

    # -------------------------------------------------------------- workload

    def _public(self) -> str:
        return f"http://localhost:{self.server.port}"

    def _owner(self) -> str:
        return f"http://127.0.0.1:{self.server._mpserve.owner_port}"

    def _writer(self, t: int) -> None:
        i = 0
        while not self._stop.is_set():
            shard = i % 2
            pos = t * 100_000 + (i // 2)
            col = shard * SHARD_WIDTH + pos
            row = 1 + (i % N_ROWS)
            i += 1
            try:
                out = _post(self._public(), f"/index/{INDEX}/query",
                            f"Set({col}, {FIELD}={row})".encode(),
                            content_type="text/plain", timeout=5.0)
            except Exception:  # noqa: BLE001 — a kill mid-request:
                self.write_errors += 1  # unacked, the ledger owes nothing
                continue
            if out.get("results") == [True]:
                with self._lock:
                    self.acked.add((row, col))
            time.sleep(0.005)

    def _reader(self) -> None:
        while not self._stop.is_set():
            try:
                _post(self._public(), f"/index/{INDEX}/query",
                      f"Count(Row({FIELD}=1))".encode(),
                      content_type="text/plain", timeout=5.0)
            except Exception:  # noqa: BLE001 — resets from dying
                pass           # workers are expected mid-kill
            time.sleep(0.01)

    # --------------------------------------------------------------- oracle

    def _probe_owner(self) -> bool:
        """Owner-never-wedges, half 1: a probe query through the
        owner's own listener answers within the deadline."""
        deadline = time.monotonic() + self.PROBE_DEADLINE_S
        while time.monotonic() < deadline:
            try:
                out = _post(self._owner(), f"/index/{INDEX}/query",
                            f"Count(Row({FIELD}=1))".encode(),
                            content_type="text/plain", timeout=5.0)
                if "results" in out:
                    return True
            except Exception:  # noqa: BLE001
                time.sleep(0.1)
        return False

    def _kill_one_worker(self) -> str:
        mp = self.server._mpserve
        pids = [w["pid"] for w in mp.workers_json()
                if w["alive"] and w["pid"]]
        if not pids:
            return "kill-skipped"
        pid = self.rng.choice(pids)
        try:
            os.kill(pid, 9)
        except ProcessLookupError:
            return "kill-raced"
        return f"kill-worker pid={pid}"

    def run_schedule(self) -> dict:
        mp = self.server._mpserve
        threads = [
            threading.Thread(target=self._writer, args=(t,), daemon=True)
            for t in range(self.writer_threads)
        ] + [
            threading.Thread(target=self._reader, daemon=True)
            for _ in range(self.reader_threads)
        ]
        for t in threads:
            t.start()
        t0 = time.monotonic()
        time.sleep(self.kill_gap_s)  # let the burst establish
        for _ in range(self.n_kills):
            event = self._kill_one_worker()
            self.events.append(event)
            self.log(f"  event: {event}")
            if not self._probe_owner():
                self.wedges.append(f"owner probe timed out after {event}")
            if not mp.wait_workers(self.n_workers,
                                   timeout=self.RESPAWN_DEADLINE_S):
                self.wedges.append(f"fleet never respawned after {event}")
            time.sleep(self.kill_gap_s)
        self._stop.set()
        for t in threads:
            t.join(timeout=10)
        # final owner-never-wedges check, then the acked-write oracle
        # against the owner's authoritative listener
        if not self._probe_owner():
            self.wedges.append("owner probe timed out at finale")
        with self._lock:
            acked = set(self.acked)
        missing = set(acked)
        for _ in range(3):
            got: set[tuple[int, int]] = set()
            for row in range(1, N_ROWS + 1):
                try:
                    out = _post(self._owner(), f"/index/{INDEX}/query",
                                f"Row({FIELD}={row})".encode(),
                                content_type="text/plain", timeout=30.0)
                except Exception:  # noqa: BLE001
                    continue
                got.update((row, c) for c in
                           out.get("results", [{}])[0].get("columns", []))
            missing = acked - got
            if not missing:
                break
            time.sleep(0.2)
        m = mp.metrics()
        return {
            "events": list(self.events),
            "acked_writes": len(acked),
            "write_errors": self.write_errors,
            "lost_acked_writes": len(missing),
            "lost_sample": sorted(missing)[:5],
            "owner_wedges": list(self.wedges),
            "respawns": m["serving_worker_respawns_total"],
            "dropped_inflight": sum(w["droppedInflight"]
                                    for w in mp.workers_json()),
            "wall_s": round(time.monotonic() - t0, 2),
            "ok": not missing and not self.wedges,
        }


def run_chaos(tmp_dir, n_schedules: int = 20, n_nodes: int = 3,
              replica_n: int = 2, seed: int = 0, n_events: int = 6,
              event_gap_s: float = 0.3, with_storage_faults: bool = False,
              with_autopilot: bool = False, with_cdc: bool = False,
              with_elastic: bool = False,
              log=lambda msg: None) -> dict:
    """Run ``n_schedules`` independent seeded schedules (fresh cluster
    each — a schedule's damage must not leak into the next) and fold
    the oracle verdicts. Any failing schedule reports its seed so the
    run replays deterministically. ``with_storage_faults`` adds
    bit-flip and disk-full events plus the disk-integrity oracle;
    ``with_autopilot`` runs the placement plane live (fast tickers +
    forced-pass events) so the same oracles gate autopilot-minted
    resizes; ``with_cdc`` runs an out-of-cluster CDC mirror tailing n0
    for the whole schedule, gated on the byte-identical mirror oracle;
    ``with_elastic`` adds graceful-drain events so kills and partitions
    land mid-drain, gated on all of the above."""
    records = []
    for i in range(n_schedules):
        schedule_seed = seed * 1000 + i
        log(f"chaos schedule {i + 1}/{n_schedules} (seed {schedule_seed})")
        harness = ChaosHarness(
            f"{tmp_dir}/sched{i}", n_nodes=n_nodes, replica_n=replica_n,
            seed=schedule_seed, n_events=n_events,
            event_gap_s=event_gap_s,
            with_storage_faults=with_storage_faults,
            with_autopilot=with_autopilot, with_cdc=with_cdc,
            with_elastic=with_elastic, log=log,
        )
        try:
            harness.boot()
            record = harness.run_schedule()
        finally:
            harness.close()
        record["seed"] = schedule_seed
        records.append(record)
        log(f"  -> ok={record['ok']} acked={record['acked_writes']} "
            f"wall={record['wall_s']}s")
    failed = [r for r in records if not r["ok"]]
    return {
        "schedules": n_schedules,
        "n_nodes": n_nodes,
        "replica_n": replica_n,
        "acked_writes_total": sum(r["acked_writes"] for r in records),
        "events_total": sum(len(r["events"]) for r in records),
        "lost_acked_writes": sum(r["lost_acked_writes"] for r in records),
        "non_quorum_deletions": sum(r["non_quorum_deletions"]
                                    for r in records),
        "coordinator_conflicts": [r["coordinator_conflicts"]
                                  for r in records
                                  if r["coordinator_conflicts"]],
        "replica_mismatches": sum(len(r["replica_mismatches"])
                                  for r in records),
        "corruptions_injected": sum(r.get("corruptions_injected", 0)
                                    for r in records),
        "disk_integrity_failures": sum(
            len(r.get("disk_integrity_failures", []))
            for r in records),
        "degraded_stuck": sum(len(r.get("degraded_stuck", []))
                              for r in records),
        "autopilot_moves_total": sum(r.get("autopilot_moves", 0)
                                     for r in records),
        "drains_total": sum(r.get("drains", 0) for r in records),
        "cdc_mirror_mismatches": sum(
            len(r.get("cdc_mirror_mismatches", [])) for r in records),
        "cdc_resyncs_total": sum(r.get("cdc_resyncs", 0)
                                 for r in records),
        "cdc_applied_ops_total": sum(r.get("cdc_applied_ops", 0)
                                     for r in records),
        "unconverged": sum(1 for r in records if not r["converged"]),
        "failed_seeds": [r["seed"] for r in failed],
        "failed_diags": [
            {"seed": r["seed"], "events": r["events"],
             "lost": r["lost_acked_writes"],
             "mismatches": len(r["replica_mismatches"]),
             "diag": r.get("converge_diag")}
            for r in failed
        ],
        "ok": not failed,
    }
