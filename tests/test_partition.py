"""Network-partition scenarios for the control plane.

Drives REAL in-process clusters through injected partitions
(testing/faults.py rules on the internal wire) and asserts the
partition-safety contract (docs/OPERATIONS.md failure model):

- quorum gating: a minority side degrades to serving locally-owned
  reads (writes shed 503) instead of declaring deaths, resizing, or
  deleting fragments by a minority view of ownership;
- corroborated death: suspect→dead needs ≥2 observers (all-but-self in
  2-node clusters) — a single cut link cannot amputate a live node;
- epoch fencing: a partitioned ex-coordinator healing back cannot
  un-gate queries, re-trigger resizes, or delete fragments with
  commands minted before the partition;
- rejoin: an evicted node that heals detects its eviction and rejoins
  instead of split-braining forever.

The test driver's own edge requests ride plain urllib (not the pooled
internal wire), so the observer is never partitioned from the nodes.
"""

import json
import socket
import time
import urllib.error
import urllib.request

import pytest

from cluster_helpers import make_cluster, req, settle, uri
from pilosa_tpu.parallel.cluster import (
    Cluster,
    DEAD_HEARTBEATS,
    Node,
)
from pilosa_tpu.shardwidth import SHARD_WIDTH
from pilosa_tpu.testing import faults


@pytest.fixture(autouse=True)
def _fast_and_clean(monkeypatch):
    """Fresh plane per test + shrunken backoffs/timeouts so partitioned
    broadcasts and cleanup drains don't serialize test wall time."""
    faults.clear()
    monkeypatch.setattr(Cluster, "SEND_BACKOFF_S", 0.01)
    monkeypatch.setattr(Cluster, "CLEANUP_DRAIN_TIMEOUT", 1.0)
    yield
    faults.clear()


def boot(tmp_path, n, replica_n=1, **kw):
    """Install the fault plane FIRST so each server self-registers its
    name→endpoint mapping at open, then boot the cluster and let its
    join resizes drain."""
    plane = faults.install()
    servers = make_cluster(tmp_path, n, replica_n=replica_n, **kw)
    settle(servers)
    return plane, servers


def seed(servers, n_shards=6):
    req("POST", f"{uri(servers[0])}/index/i", {})
    req("POST", f"{uri(servers[0])}/index/i/field/f", {})
    cols = [s * SHARD_WIDTH + 7 for s in range(n_shards)]
    req("POST", f"{uri(servers[0])}/index/i/field/f/import",
        {"rows": [1] * len(cols), "columns": cols})
    return cols


def names(servers):
    return [s.api.cluster.local.id for s in servers]


def heartbeat_rounds(servers, rounds):
    for _ in range(rounds):
        for s in servers:
            s.api.cluster.heartbeat()


def post_query(server, pql, expect_status=None):
    r = urllib.request.Request(
        f"{uri(server)}/index/i/query", data=pql, method="POST",
        headers={"Content-Type": "text/plain"},
    )
    try:
        with urllib.request.urlopen(r, timeout=30) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:
        body = json.loads(e.read() or b"{}")
        if expect_status is not None:
            assert e.code == expect_status, (e.code, body)
        return e.code, body


class TestMinorityDegradation:
    def test_symmetric_partition_minority_read_only(self, tmp_path):
        """3 nodes, coordinator partitioned off: the minority side
        degrades (writes 503, locally-owned reads OK, membership
        intact, no resize) while the majority side performs a
        corroborated declare-dead + resize and keeps serving."""
        plane, servers = boot(tmp_path, 3, replica_n=2)
        try:
            cols = seed(servers)
            n0, n1, n2 = servers
            epoch_before = n1.api.cluster.epoch
            acted_before = list(n0.api.cluster.acted_epochs)
            plane.partition("n0", "n1")
            plane.partition("n0", "n2")

            heartbeat_rounds(servers, DEAD_HEARTBEATS)

            # minority (n0): degraded, membership INTACT, never acted
            assert n0.api.cluster.degraded is True
            assert set(n0.api.cluster.nodes) == {"n0", "n1", "n2"}
            assert list(n0.api.cluster.acted_epochs) == acted_before
            st = req("GET", f"{uri(n0)}/status")
            assert st["clusterDegraded"] is True
            # writes shed 503 with Retry-After
            status, body = post_query(n0, b"Set(3, f=9)",
                                      expect_status=503)
            assert "degraded" in body["error"]
            # a locally-owned shard still reads
            local_shard = next(
                s for s in range(6)
                if n0.api.cluster.owns_shard("i", s)
            )
            status, body = post_query(
                n0, f"Options(Count(Row(f=1)), shards=[{local_shard}])"
                .encode())
            assert status == 200 and body["results"] == [1], body
            # a cluster-wide read needing unreachable owners → 503
            all_owned = all(n0.api.cluster.owns_shard("i", s)
                            for s in range(6))
            if not all_owned:
                status, body = post_query(n0, b"Count(Row(f=1))",
                                          expect_status=503)
                assert "degraded" in body["error"]

            # majority (n1/n2): declared n0 dead with corroboration,
            # epoch advanced, still serving full queries
            assert set(n1.api.cluster.nodes) == {"n1", "n2"}
            assert set(n2.api.cluster.nodes) == {"n1", "n2"}
            assert n1.api.cluster.epoch > epoch_before
            for s in (n1, n2):
                status, body = post_query(s, b"Count(Row(f=1))")
                assert status == 200 and body["results"] == [len(cols)]

            # heal: the evicted ex-coordinator detects the eviction and
            # rejoins instead of split-braining
            plane.heal()
            n0.api.cluster.heartbeat()
            assert n0.api.cluster.rejoins == 1
            settle(servers)  # the rejoin's relays and join resizes
            heartbeat_rounds(servers, 1)
            for s in servers:
                assert set(s.api.cluster.nodes) == {"n0", "n1", "n2"}, (
                    s.config.name)
                assert s.api.cluster.degraded is False
            status, body = post_query(n0, b"Count(Row(f=1))")
            assert status == 200 and body["results"] == [len(cols)]
        finally:
            for s in servers:
                s.close()

    def test_asymmetric_partition_no_minority_resize(self, tmp_path):
        """One-way partition (n0 cannot reach n1/n2, both can reach
        n0): pre-PR n0 declared both peers dead and ran a minority
        resize + cleanup; now its quorum probe rides the same dead
        outbound links, so it degrades read-only instead — and the
        majority, which still SEES n0 alive, never amputates it."""
        plane, servers = boot(tmp_path, 3, replica_n=1)
        try:
            seed(servers)
            n0, n1, n2 = servers
            acted_before = {s.config.name: len(s.api.cluster.acted_epochs)
                            for s in servers}
            plane.partition("n0", "n1", bidirectional=False)
            plane.partition("n0", "n2", bidirectional=False)

            heartbeat_rounds(servers, DEAD_HEARTBEATS + 1)

            # n0: suspects both peers but cannot act (no quorum) —
            # degraded read-only, zero coordinated actions
            assert n0.api.cluster.degraded is True
            assert set(n0.api.cluster.nodes) == {"n0", "n1", "n2"}
            assert (len(n0.api.cluster.acted_epochs)
                    == acted_before["n0"])
            assert n0.api.cluster.quorum_denials > 0
            post_query(n0, b"Set(9, f=9)", expect_status=503)
            # majority: n0 answers their probes, so nothing changed
            for s in (n1, n2):
                assert set(s.api.cluster.nodes) == {"n0", "n1", "n2"}
                assert s.api.cluster.degraded is False
            # no fragment was deleted anywhere without quorum
            for s in servers:
                for entry in s.api.cluster.cleanup_log:
                    assert not (entry["removed"] and not entry["quorum"])

            plane.heal()
            heartbeat_rounds(servers, 1)
            assert n0.api.cluster.degraded is False
            status, body = post_query(n0, b"Count(Row(f=1))")
            assert status == 200
        finally:
            for s in servers:
                s.close()

    def test_minority_pair_keeps_sole_copies(self, tmp_path):
        """5 nodes, replica_n=1, partition {n0,n1,n2} | {n3,n4}: pre-PR
        the minority pair elected its own coordinator, resized over a
        2-node ring, and the cleanup DELETED sole surviving copies by
        that minority view of ownership — permanent data loss. Now the
        pair lacks quorum: no resize, no deletion, and after heal +
        rejoin every acked bit is queryable cluster-wide again."""
        plane, servers = boot(tmp_path, 5, replica_n=1)
        try:
            cols = seed(servers, n_shards=10)
            minority = [s for s in servers
                        if s.config.name in ("n3", "n4")]
            majority = [s for s in servers
                        if s.config.name not in ("n3", "n4")]
            # fragments whose SOLE copy lives on the minority pair
            minority_frag_counts = {
                s.config.name: sum(
                    1 for sh in range(10)
                    if s.api.cluster.owns_shard("i", sh)
                ) for s in minority
            }
            for a in majority:
                for b in minority:
                    plane.partition(a.config.name, b.config.name)

            heartbeat_rounds(servers, DEAD_HEARTBEATS)

            # minority pair: degraded, membership intact, never resized
            for s in minority:
                assert s.api.cluster.degraded is True, s.config.name
                assert len(s.api.cluster.nodes) == 5, s.config.name
                assert not any(a for e, a in s.api.cluster.acted_epochs
                               if a.startswith("declare-dead"))
                # its sole copies SURVIVED (no minority-ring cleanup)
                held = sum(
                    1 for sh in range(10)
                    if (v := s.holder.index("i").field("f")
                        .view("standard")) and v.fragment(sh) is not None
                    and v.fragment(sh).count() > 0
                )
                assert held >= minority_frag_counts[s.config.name], (
                    s.config.name)
                for entry in s.api.cluster.cleanup_log:
                    assert not (entry["removed"] and not entry["quorum"])
            # majority: declared the pair dead (it holds 3/5 = quorum)
            for s in majority:
                assert set(s.api.cluster.nodes) == {"n0", "n1", "n2"}, (
                    s.config.name)

            # heal → the evicted pair rejoins → full coverage returns
            plane.heal()
            for s in minority:
                s.api.cluster.heartbeat()
                assert s.api.cluster.rejoins == 1, s.config.name
                assert s.api.cluster.wait_until_normal(30)
            majority[0].api.cluster.coordinate_resize()  # drain joins
            heartbeat_rounds(servers, 1)
            for s in servers:
                assert len(s.api.cluster.nodes) == 5, s.config.name
            status, body = post_query(servers[0], b"Count(Row(f=1))")
            assert status == 200 and body["results"] == [len(cols)]
        finally:
            for s in servers:
                s.close()


class TestCorroboratedDeath:
    def test_single_observer_flap_cannot_amputate(self, tmp_path):
        """Only the coordinator's link to n2 is cut: n1 still reaches
        n2, so the suspect-probe corroboration vetoes the death — the
        pre-PR single-observer detector amputated a live node here.
        Cutting n1's link too completes the corroboration and the
        (now genuinely unreachable) node is declared dead."""
        plane, servers = boot(tmp_path, 3, replica_n=2)
        try:
            seed(servers)
            n0, n1, n2 = servers
            plane.partition("n0", "n2", bidirectional=False)
            heartbeat_rounds([n0], DEAD_HEARTBEATS)
            assert set(n0.api.cluster.nodes) == {"n0", "n1", "n2"}
            assert n0.api.cluster.deaths_vetoed >= 1
            assert n0.api.cluster.deaths_declared == 0

            plane.partition("n1", "n2", bidirectional=False)
            n0.api.cluster.heartbeat()
            assert n0.api.cluster.deaths_declared == 1
            assert set(n0.api.cluster.nodes) == {"n0", "n1"}
            assert set(n1.api.cluster.nodes) == {"n0", "n1"}
        finally:
            for s in servers:
                s.close()

    def test_two_node_cluster_survivor_may_act(self, tmp_path):
        """2-node special case (documented tradeoff): all-but-self
        corroboration is vacuous and a majority of 2 is unreachable by
        definition, so the survivor is allowed to fail over alone —
        the reference has the same n=2 blind spot."""
        plane, servers = boot(tmp_path, 2, replica_n=2)
        try:
            seed(servers)
            n0, n1 = servers
            victim = n1
            victim.close()
            for _ in range(DEAD_HEARTBEATS):
                n0.api.cluster.heartbeat()
            assert set(n0.api.cluster.nodes) == {"n0"}
            assert n0.api.cluster.deaths_declared == 1
            assert n0.api.cluster.degraded is False
            status, body = post_query(n0, b"Count(Row(f=1))")
            assert status == 200
        finally:
            for s in servers:
                if s is not victim:
                    s.close()


class TestEpochFencing:
    def test_stale_epoch_messages_rejected(self, tmp_path):
        """Fenced control messages stamped with an epoch below the
        receiver's are rejected unapplied: state commands can't re-gate
        or un-gate, cleanup can't delete, instructions can't re-fetch."""
        plane, servers = boot(tmp_path, 2, replica_n=1)
        try:
            seed(servers)
            n0 = servers[0]
            cluster = n0.api.cluster
            cluster.adopt_epoch(cluster.epoch + 5)
            current = cluster.epoch
            rejects = cluster.stale_epoch_rejects

            out = cluster.handle_message(
                {"type": "cluster-state", "state": "RESIZING",
                 "epoch": current - 1})
            assert "stale epoch" in out.get("error", "")
            assert cluster.state == "NORMAL"  # not re-gated
            out = cluster.handle_message(
                {"type": "node-leave", "id": "n1", "epoch": current - 3})
            assert "stale epoch" in out.get("error", "")
            assert "n1" in cluster.nodes  # membership untouched
            out = cluster.handle_message(
                {"type": "resize-cleanup",
                 "members": sorted(cluster.nodes),
                 "epoch": current - 1})
            assert "stale epoch" in out.get("error", "")
            assert cluster.stale_epoch_rejects == rejects + 3

            # equal and newer epochs pass (and newer is adopted)
            out = cluster.handle_message(
                {"type": "cluster-state", "state": "NORMAL",
                 "epoch": current})
            assert "error" not in out
            cluster.handle_message(
                {"type": "cluster-state", "state": "NORMAL",
                 "epoch": current + 4})
            assert cluster.epoch == current + 4
        finally:
            for s in servers:
                s.close()

    def test_stale_cleanup_cannot_delete(self, tmp_path):
        """A resize-cleanup minted before the partition must not delete
        fragments after the epoch moved on — even when the membership
        list it carries matches."""
        import numpy as np

        plane, servers = boot(tmp_path, 2, replica_n=1)
        try:
            req("POST", f"{uri(servers[0])}/index/i", {})
            req("POST", f"{uri(servers[0])}/index/i/field/f", {})
            n0 = servers[0]
            cluster = n0.api.cluster
            # a fragment n0 does NOT own, planted on BOTH nodes (the
            # owner holds identical content, so only the epoch fence —
            # not the owner-coverage guard — stands between the stale
            # message and the deletion)
            shard = next(s for s in range(64)
                         if not cluster.owns_shard("i", s))
            for s in servers:
                f = s.holder.index("i").field("f")
                f.view("standard", create=True).fragment(
                    shard, create=True
                ).bulk_import(np.asarray([1], np.uint64),
                              np.asarray([2], np.uint64))
            members = sorted(cluster.nodes)
            stale = cluster.epoch
            cluster.adopt_epoch(stale + 2)  # a later coordinator acted

            out = cluster.handle_message(
                {"type": "resize-cleanup", "members": members,
                 "epoch": stale})
            assert "stale epoch" in out.get("error", "")
            v = n0.holder.index("i").field("f").view("standard")
            assert v.fragment(shard) is not None  # survived

            # the SAME message at the current epoch does delete
            out = cluster.handle_message(
                {"type": "resize-cleanup", "members": members,
                 "epoch": cluster.epoch})
            assert "error" not in out
            assert v.fragment(shard) is None
        finally:
            for s in servers:
                s.close()

    def test_cleanup_defers_until_owner_absorbed(self, tmp_path):
        """The owner-coverage guard: cleanup must NOT delete a
        non-owned copy holding bits no owner has (an acked write from
        an older ring) — it defers, an anti-entropy pass absorbs the
        stray copy into the owner, and only then does cleanup delete."""
        import numpy as np

        plane, servers = boot(tmp_path, 2, replica_n=1)
        try:
            req("POST", f"{uri(servers[0])}/index/i", {})
            req("POST", f"{uri(servers[0])}/index/i/field/f", {})
            n0 = servers[0]
            cluster = n0.api.cluster
            shard = next(s for s in range(64)
                         if not cluster.owns_shard("i", s))
            owner = next(s for s in servers
                         if s.api.cluster.owns_shard("i", shard))
            assert owner is not n0
            f = n0.holder.index("i").field("f")
            f.view("standard", create=True).fragment(
                shard, create=True
            ).bulk_import(np.asarray([3], np.uint64),
                          np.asarray([7], np.uint64))

            removed = cluster.cleanup_unowned(sorted(cluster.nodes))
            v = n0.holder.index("i").field("f").view("standard")
            assert removed == 0 and v.fragment(shard) is not None
            assert cluster.cleanup_log[-1]["deferred"] == 1

            # the owner's sync pass absorbs the stray copy...
            owner.api.cluster.sync_holder()
            of = (owner.holder.index("i").field("f")
                  .view("standard").fragment(shard))
            assert of is not None and of.contains(3, 7)
            # ...and the next cleanup deletes the now-covered copy
            removed = cluster.cleanup_unowned(sorted(cluster.nodes))
            assert removed == 1
            assert v.fragment(shard) is None
        finally:
            for s in servers:
                s.close()

    def test_healed_ex_coordinator_is_fenced_then_rejoins(self, tmp_path):
        """End to end: partition the coordinator away, let the majority
        declare it dead (epoch E+…), heal, and verify (a) the
        ex-coordinator's pre-partition-epoch commands bounce off every
        peer, (b) its own next coordinated action adopts the higher
        epoch first (no stale acting), (c) its heartbeat detects the
        eviction and rejoins."""
        plane, servers = boot(tmp_path, 3, replica_n=2)
        try:
            seed(servers)
            n0, n1, n2 = servers
            plane.partition("n0", "n1")
            plane.partition("n0", "n2")
            heartbeat_rounds(servers, DEAD_HEARTBEATS)
            assert set(n1.api.cluster.nodes) == {"n1", "n2"}
            stale_epoch = n0.api.cluster.epoch
            assert n1.api.cluster.epoch > stale_epoch

            plane.heal()
            # the ex-coordinator's stale commands (minted before the
            # partition) arrive AFTER the heal — all fenced
            for message in (
                {"type": "cluster-state", "state": "RESIZING",
                 "epoch": stale_epoch},
                {"type": "resize-cleanup",
                 "members": sorted(n1.api.cluster.nodes),
                 "epoch": stale_epoch},
            ):
                out = n1.api.cluster.handle_message(dict(message))
                assert "stale epoch" in out.get("error", ""), message
            assert n1.api.cluster.state == "NORMAL"

            # its next real action adopts the majority's epoch first:
            # check_quorum probes peers, adopts, then mints ABOVE it
            n0.api.cluster.coordinate_resize()
            assert n0.api.cluster.epoch > n1.api.cluster.epoch - 1

            n0.api.cluster.heartbeat()
            assert n0.api.cluster.rejoins == 1
            assert n0.api.cluster.wait_until_normal(30)
            heartbeat_rounds(servers, 1)
            for s in servers:
                assert set(s.api.cluster.nodes) == {"n0", "n1", "n2"}
        finally:
            for s in servers:
                s.close()

    def test_epoch_persists_across_restart(self, tmp_path):
        """The persisted high-water mark stops a RESTARTED node from
        reusing pre-crash epochs."""
        plane, servers = boot(tmp_path, 1)
        try:
            cluster = servers[0].api.cluster
            cluster.adopt_epoch(41)
            data_dir = servers[0].config.data_dir
            servers[0].close()
            from pilosa_tpu.server import Server, ServerConfig

            reborn = Server(ServerConfig(
                data_dir=data_dir, port=0, name="n0",
                anti_entropy_interval=0, heartbeat_interval=0,
                use_mesh=False,
            )).open()
            servers = [reborn]
            assert reborn.api.cluster.epoch == 41
        finally:
            for s in servers:
                s.close()


class TestHeartbeatIsolation:
    def test_hung_peer_does_not_stall_detection(self, tmp_path):
        """A peer whose socket accepts but never answers must cost one
        tight heartbeat-timeout, not the 30 s client default — and the
        OTHER peers' probes (concurrent) still land in the same pass."""
        import time

        plane, servers = boot(tmp_path, 2)
        try:
            n0 = servers[0]
            tarpit = socket.socket()
            tarpit.bind(("localhost", 0))
            tarpit.listen(8)
            port = tarpit.getsockname()[1]
            n0.api.cluster.nodes["zz-tarpit"] = Node(
                "zz-tarpit", f"http://localhost:{port}")
            n0.api.cluster.heartbeat_timeout = 0.4
            t0 = time.monotonic()
            n0.api.cluster.heartbeat()
            wall = time.monotonic() - t0
            assert wall < 5.0, f"heartbeat stalled {wall:.1f}s on tarpit"
            states = {n.id: n.state
                      for n in n0.api.cluster.nodes.values()}
            assert states["zz-tarpit"] == "DEGRADED"
            assert states["n1"] == "NORMAL"  # probed despite the tarpit
            tarpit.close()
        finally:
            for s in servers:
                s.close()


class TestControlSendRetry:
    def test_send_retry_rides_out_one_drop(self, tmp_path):
        """A single dropped control send succeeds on retry; a hard
        partition still fails after the bounded attempts."""
        plane, servers = boot(tmp_path, 2)
        try:
            cluster = servers[0].api.cluster
            peer_uri = servers[1].api.cluster.local.uri
            plane.add("drop", src="n0", dst="n1",
                      route="/internal/cluster/message", count=1)
            out = cluster._send_retry(
                peer_uri, {"type": "create-shard", "index": "x",
                           "shards": [1]})
            assert out == {}
            assert plane.dropped == 1
            from pilosa_tpu.parallel.client import ClientError

            plane.add("drop", src="n0", dst="n1")
            with pytest.raises(ClientError):
                cluster._send_retry(
                    peer_uri, {"type": "create-shard", "index": "x",
                               "shards": [2]})
        finally:
            for s in servers:
                s.close()

    def test_state_broadcast_survives_flaky_link(self, tmp_path):
        """End to end: the NORMAL broadcast's first attempt is dropped;
        without retry the peer would sit RESIZING until the straggler
        timeout — with it, the resize leaves everyone NORMAL."""
        plane, servers = boot(tmp_path, 2, replica_n=2)
        try:
            seed(servers, n_shards=2)
            coord = next(s for s in servers
                         if s.api.cluster.is_acting_coordinator)
            peer = next(s for s in servers if s is not coord)
            # drop exactly one message-delivery attempt per direction
            # pair during the resize
            plane.add("drop", src=coord.config.name,
                      dst=peer.config.name,
                      route="/internal/cluster/message", count=1)
            coord.api.cluster.coordinate_resize()
            assert peer.api.cluster.state == "NORMAL"
            assert coord.api.cluster.state == "NORMAL"
        finally:
            for s in servers:
                s.close()


class TestChaosHarness:
    @pytest.mark.parametrize("variant", [
        {},
        {"with_storage_faults": True},
        {"with_autopilot": True},
        {"with_cdc": True},
        {"with_elastic": True, "with_cdc": True, "n_nodes": 4},
    ], ids=["plain", "storage-faults", "autopilot", "cdc", "mid-drain"])
    def test_quick_chaos_schedule_passes_oracles(self, tmp_path, variant):
        """One seeded schedule end to end through the harness, a
        variant: randomized partition/kill/heal under load, then the
        four oracles (zero lost acked writes, no non-quorum deletion,
        ≤1 coordinator per epoch, byte-identical replicas). The
        variants add their events to the same bag and their oracle to
        the verdict: bit flips and a full disk (every file verifies
        after heal and scrub), the placement planner minting resizes,
        an out-of-cluster CDC mirror (byte-identical to n0 after heal),
        graceful drains that kills and partitions land in the middle
        of."""
        faults.clear()  # the harness installs its own plane
        from pilosa_tpu.testing.chaos import run_chaos

        out = run_chaos(tmp_path, n_schedules=1, n_events=5, seed=3,
                        **variant)
        assert out["ok"], out
        assert out["unconverged"] == 0
        assert out["acked_writes_total"] > 0

    @pytest.mark.slow
    def test_chaos_soak(self, tmp_path):
        """Long randomized soak (env-tunable): more schedules, more
        events, 5 nodes."""
        import os

        faults.clear()
        from pilosa_tpu.testing.chaos import run_chaos

        out = run_chaos(
            tmp_path,
            n_schedules=int(os.environ.get("PILOSA_TPU_CHAOS_SCHEDULES",
                                           "12")),
            n_nodes=int(os.environ.get("PILOSA_TPU_CHAOS_NODES", "5")),
            n_events=int(os.environ.get("PILOSA_TPU_CHAOS_EVENTS", "8")),
            seed=int(os.environ.get("PILOSA_TPU_CHAOS_SEED", "1")),
        )
        assert out["ok"], out
        assert out["unconverged"] == 0


class TestObservabilitySurface:
    def test_cluster_series_and_status(self, tmp_path):
        plane, servers = boot(tmp_path, 2)
        try:
            st = req("GET", f"{uri(servers[0])}/status")
            assert "epoch" in st and "clusterDegraded" in st
            metrics = req("GET", f"{uri(servers[0])}/metrics", raw=True)
            text = metrics.decode()
            for series in ("cluster_epoch", "cluster_quorum",
                           "cluster_degraded",
                           "cluster_heartbeat_probes_total",
                           "cluster_stale_epoch_rejects_total"):
                assert f"pilosa_tpu_{series}" in text, series
            snap = req("GET", f"{uri(servers[0])}/debug/vars")
            assert "cluster" in snap
            assert snap["cluster"]["cluster_members"] == 2
        finally:
            for s in servers:
                s.close()

    def test_degraded_write_shed_counts_on_qos_path(self, tmp_path):
        plane, servers = boot(tmp_path, 1)
        try:
            req("POST", f"{uri(servers[0])}/index/i", {})
            req("POST", f"{uri(servers[0])}/index/i/field/f", {})
            servers[0].api.cluster.degraded = True
            post_query(servers[0], b"Set(1, f=1)", expect_status=503)
            from pilosa_tpu.utils.stats import global_stats

            snap = global_stats().snapshot()
            tagged = [k for k in snap.get("counters", {})
                      if "qos_shed" in k and "cluster_degraded" in k]
            assert tagged, snap.get("counters")
        finally:
            servers[0].api.cluster.degraded = False
            for s in servers:
                s.close()
