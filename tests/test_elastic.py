"""Elastic membership plane: graceful drain, join absorption, and the
split planner — the ISSUE-17 state machine end to end.

The invariants pinned here:

- a drain moves every group the target owns, hands off its CDC
  cursors, and removes it from the ring — with the data still
  byte-queryable from the survivors (replica_n == 1, so a lost group
  would be VISIBLY lost);
- the target sheds writes from the first broadcast until it departs,
  and STAYS read-only after "done" (a drained node is decommissioned,
  not recycled);
- one coordinated actuator per epoch: the autopilot skips (with a
  /debug/autopilot-visible reason) while a drain is active, a second
  drain is refused, and every refusal carries its reason;
- the record is resumable: any acting coordinator can adopt an ACTIVE
  record and finish the machine (coordinator failover mid-drain);
- the wire regression that motivated epoch-stamping: drain messages
  carry the CURRENT cluster epoch, because the drain's own moving step
  bumps the epoch past the record's minted-at-start one — a record
  ordered by (epoch, rev) must still be adoptable afterwards."""

import time
import urllib.error

import pytest

from cluster_helpers import join_node, make_cluster, req, seed, uri
from test_autopilot import _bare_cluster

from pilosa_tpu.autopilot import ElasticError, ElasticManager, plan_splits
from pilosa_tpu.autopilot.planner import Autopilot
from pilosa_tpu.shardwidth import SHARD_WIDTH
from pilosa_tpu.storage.wal import WriteAheadLog

HALF = SHARD_WIDTH // 2


def _coordinator(servers):
    return next(s for s in servers if s.api.cluster.is_acting_coordinator)


def _wait(pred, timeout=30.0, interval=0.1):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(interval)
    return pred()


def _mint_active_record(c, target, state="moving"):
    """Install a drain record as the coordinator would: epoch minted
    once at start, then gossiped (set_drain stamps the wire with the
    CURRENT cluster epoch)."""
    epoch = c._bump_epoch()
    record = {"epoch": epoch, "rev": 1, "target": target,
              "state": state, "coordinator": c.local.id,
              "groups": 0, "moved": 0, "error": ""}
    c.set_drain(record)
    return record


class TestPlanSplits:
    OWN = {("i", 0): ("a",), ("i", 1): ("b",)}

    def owners_of(self, ix, s):
        return self.OWN.get((ix, s), ())

    def test_hot_shard_splits_across_nodes(self):
        splits, merges = plan_splits(
            {("i", 0): 100.0, ("i", 1): 2.0}, self.owners_of,
            ["a", "b"], {}, split_threshold=1.5)
        assert merges == []
        assert len(splits) == 1
        s = splits[0]
        assert (s["index"], s["shard"]) == ("i", 0)
        # spans tile [0, SHARD_WIDTH) contiguously, one owner each
        spans = s["spans"]
        assert spans[0][0] == 0 and spans[-1][1] == SHARD_WIDTH
        assert all(spans[i][1] == spans[i + 1][0]
                   for i in range(len(spans) - 1))
        # the current owner keeps the first range (no data movement for
        # it) and the union NEVER shrinks below the current owners
        assert spans[0][2] == ("a",)
        assert s["owners"][0] == "a" and set(s["owners"]) == {"a", "b"}

    def test_disabled_threshold_merges_everything(self):
        current = {("i", 0): ((0, HALF, ("a",)),
                              (HALF, SHARD_WIDTH, ("b",)))}
        assert plan_splits({("i", 0): 100.0}, self.owners_of,
                           ["a", "b"], current,
                           split_threshold=0.0) == ([], [("i", 0)])

    def test_single_node_cannot_split(self):
        assert plan_splits({("i", 0): 100.0}, self.owners_of,
                           ["a"], {}, split_threshold=1.5) == ([], [])

    def test_hysteresis_merge(self):
        current = {("i", 0): ((0, HALF, ("a",)),
                              (HALF, SHARD_WIDTH, ("b",)))}
        # heat collapsed to near-zero: merged back
        _, merges = plan_splits(
            {("i", 0): 0.1, ("i", 1): 100.0}, self.owners_of,
            ["a", "b"], current, split_threshold=1.5)
        assert merges == [("i", 0)]
        # heat below the cut but above half of it: left alone (no
        # re-split either — already-split shards are skipped)
        splits, merges = plan_splits(
            {("i", 0): 60.0, ("i", 1): 40.0}, self.owners_of,
            ["a", "b"], current, split_threshold=1.5)
        assert merges == []
        assert all((s["index"], s["shard"]) != ("i", 0) for s in splits)

    def test_split_ways_clamped_to_membership(self):
        splits, _ = plan_splits(
            {("i", 0): 100.0, ("i", 1): 2.0}, self.owners_of,
            ["a", "b", "c"], {}, split_threshold=1.5, split_ways=16)
        assert len(splits[0]["spans"]) == 3

    def test_replica_width_spans(self):
        # replica_n > 1 widens each span's owner tuple so a narrowed
        # plain-Set write still lands on replica_n nodes; the union
        # (and with it data placement) is unchanged, and replica_n=1
        # degenerates to the original single-owner spans byte-for-byte
        one, _ = plan_splits(
            {("i", 0): 100.0, ("i", 1): 2.0}, self.owners_of,
            ["a", "b", "c"], {}, split_threshold=1.5, replica_n=1)
        assert all(len(ids) == 1 for _lo, _hi, ids in one[0]["spans"])
        two, _ = plan_splits(
            {("i", 0): 100.0, ("i", 1): 2.0}, self.owners_of,
            ["a", "b", "c"], {}, split_threshold=1.5, replica_n=2)
        spans = two[0]["spans"]
        assert all(len(ids) == 2 for _lo, _hi, ids in spans)
        # same tiling and same lead owner per span as the replica_n=1
        # plan; the extra replica is the next node round-robin
        assert [(lo, hi) for lo, hi, _ in spans] \
            == [(lo, hi) for lo, hi, _ in one[0]["spans"]]
        assert [ids[0] for _lo, _hi, ids in spans] \
            == [ids[0] for _lo, _hi, ids in one[0]["spans"]]
        assert two[0]["owners"] == one[0]["owners"]
        # width clamps to the spread: replica_n beyond membership
        wide, _ = plan_splits(
            {("i", 0): 100.0, ("i", 1): 2.0}, self.owners_of,
            ["a", "b"], {}, split_threshold=1.5, replica_n=5)
        assert all(len(ids) == 2 for _lo, _hi, ids in wide[0]["spans"])


class TestDepartedCursors:
    def test_wal_drops_only_the_departed_members_cursors(self, tmp_path):
        wal = WriteAheadLog(str(tmp_path))
        wal.register_cursor("tailer:n9", 5)
        wal.register_cursor("follower:n9", 3)
        wal.register_cursor("tailer:n3", 7)
        assert wal.drop_cursors_for("n9") == 2
        assert wal.drop_cursors_for("n9") == 0  # idempotent
        assert wal.cursors() == {"tailer:n3": 7}
        assert wal.metrics()["cdc_cursors_dropped_total"] == 2


class TestDrainEndToEnd:
    def test_drain_moves_data_sheds_writes_and_leaves(self, tmp_path):
        servers = make_cluster(tmp_path, 3, replica_n=1)
        try:
            seed(servers[0])
            coord = _coordinator(servers)
            coord.api.elastic.LEAVE_TIMEOUT = 5.0
            victim = next(s for s in reversed(servers) if s is not coord)
            vname = victim.config.name
            before = req("POST", f"{uri(coord)}/index/i/query",
                         b"Count(Row(f=1))")["results"][0]
            assert before == 24
            # a cursor the victim registered on the coordinator's WAL:
            # the handoff step must release the retention it pins
            wal = coord.api.holder.wal
            if wal is not None:
                wal.register_cursor(f"tailer:{vname}", 0)

            out = req("POST", f"{uri(coord)}/cluster/drain/{vname}", b"")
            assert out["state"] == "pending" and out["target"] == vname

            c = coord.api.cluster
            assert _wait(lambda: c.drain_record.get("state") == "done",
                         timeout=45), c.drain_record
            assert c.drain_record.get("error") == ""

            # the target left the ring — deliberately (never rejoins)
            assert _wait(lambda: vname not in c.nodes, timeout=10)
            assert victim.api.cluster._left
            assert sorted(c.nodes) == sorted(
                s.config.name for s in servers if s is not victim)

            # data intact on the survivors, at replica_n == 1
            assert _wait(lambda: c.state == "NORMAL", timeout=30)
            got = req("POST", f"{uri(coord)}/index/i/query",
                      b"Count(Row(f=1))")["results"][0]
            assert got == before
            # no survivor's placement names the departed node
            for s in servers:
                if s is victim:
                    continue
                for ids in s.api.cluster.placement.snapshot().values():
                    assert vname not in ids

            # a drained node is read-only FOREVER: done + _left
            assert victim.api.cluster.draining
            with pytest.raises(urllib.error.HTTPError) as err:
                req("POST", f"{uri(victim)}/index/i/query",
                    b"Set(1, f=1)")
            assert err.value.code == 503

            m = coord.api.elastic.metrics()
            assert m["elastic_drains_started_total"] == 1
            assert m["elastic_drains_completed_total"] == 1
            assert m["elastic_drain_active"] == 0
            if wal is not None:
                assert m["elastic_cursor_handoffs_total"] >= 1
                assert f"tailer:{vname}" not in wal.cursors()

            # the inspectors surface the machine on every node
            status = req("GET", f"{uri(coord)}/cluster/drain")
            assert status["drain"]["state"] == "done"
            assert status["active"] is False
            insp = req("GET", f"{uri(coord)}/debug/elastic")
            assert insp["enabled"] is True
            assert insp["metrics"]["elastic_drains_completed_total"] == 1
        finally:
            for s in servers:
                s.close()


class TestRefusals:
    def test_refusal_reasons(self, tmp_path):
        servers = make_cluster(tmp_path, 3, replica_n=1)
        try:
            coord = _coordinator(servers)
            other = next(s for s in servers if s is not coord)

            with pytest.raises(ElasticError, match="acting coordinator"):
                other.api.elastic.start_drain(coord.config.name)
            with pytest.raises(ElasticError) as err:
                coord.api.elastic.start_drain("no-such-node")
            assert err.value.status == 404
            with pytest.raises(ElasticError,
                               match="refusing to drain the acting"):
                coord.api.elastic.start_drain(coord.config.name)

            # the HTTP edge maps ElasticError to its carried status
            with pytest.raises(urllib.error.HTTPError) as herr:
                req("POST", f"{uri(coord)}/cluster/drain/no-such-node",
                    b"")
            assert herr.value.code == 404
            with pytest.raises(urllib.error.HTTPError) as herr:
                req("DELETE", f"{uri(coord)}/cluster/drain")
            assert herr.value.code == 409  # no drain in flight
        finally:
            for s in servers:
                s.close()

    def test_drain_and_autopilot_mutually_exclude(self, tmp_path):
        """One coordinated actuator per epoch: with a drain record
        ACTIVE the autopilot pass skips (reason on /debug/autopilot)
        and a second drain is refused; after the abort both resume."""
        servers = make_cluster(tmp_path, 3, replica_n=1,
                               autopilot_enabled=True,
                               autopilot_interval=3600)
        try:
            coord = _coordinator(servers)
            c = coord.api.cluster
            target = next(s.config.name for s in servers if s is not coord)
            _mint_active_record(c, target)
            assert c.drain_active

            rec = coord.api.autopilot.run_pass()
            assert rec == {"acted": False, "reason": "drain-in-flight"}
            out = req("GET", f"{uri(coord)}/debug/autopilot")
            assert out["skips"].get("drain-in-flight", 0) >= 1

            with pytest.raises(ElasticError, match="already in flight"):
                coord.api.elastic.start_drain(target)

            # the record gossiped: the TARGET is shedding writes now,
            # before any data moved
            victim = next(s for s in servers
                          if s.config.name == target)
            assert _wait(lambda: victim.api.cluster.draining, timeout=5)

            aborted = coord.api.elastic.abort_drain()
            assert aborted["state"] == "aborted"
            assert not c.drain_active
            assert _wait(lambda: not victim.api.cluster.draining,
                         timeout=5)
            with pytest.raises(ElasticError, match="no drain in flight"):
                coord.api.elastic.abort_drain()
        finally:
            for s in servers:
                s.close()


class TestSplitEndToEnd:
    def test_hot_shard_is_split_adopted_read_and_written_through(
            self, tmp_path):
        """One index, one shard, every byte of its heat on one owner:
        placement moves cannot help. With ``autopilot-split-threshold``
        armed a planner pass mints a sub-shard split across two nodes,
        every peer adopts the range table, reads stay exact, reads
        entering through a non-owner rotate over the span owners, a
        plain Set through it narrows to its column's span owner while a
        Clear keeps the union fan-out, and every acknowledged write is
        readable on every node after one anti-entropy pass. (Before
        that pass a read of the split shard goes to ONE span owner and
        sees only the narrowed writes that owner took: ROADMAP D11.)"""
        from pilosa_tpu.parallel.cluster import global_route_stats

        servers = make_cluster(tmp_path, 3, replica_n=1,
                               autopilot_enabled=True,
                               autopilot_interval=3600,
                               autopilot_split_threshold=1.5,
                               autopilot_split_ways=2)
        try:
            for s in servers:
                assert s.api.cluster.wait_until_normal(30)
            entry = uri(servers[0])
            req("POST", f"{entry}/index/hot", {})
            req("POST", f"{entry}/index/hot/field/f", {})
            # columns on both halves of the shard, so that both spans
            # of a 2-way split hold some
            cols = [k * (SHARD_WIDTH // 16) + 3 for k in range(16)]
            req("POST", f"{entry}/index/hot/field/f/import",
                {"rows": [1] * len(cols), "columns": cols})

            def count(server, row):
                return req("POST", f"{uri(server)}/index/hot/query",
                           f"Count(Row(f={row}))".encode())["results"][0]

            for _ in range(30):  # all of the cluster's heat on hot/0
                assert count(servers[0], 1) == len(cols)
            coord = _coordinator(servers)
            c = coord.api.cluster
            assert _wait(lambda: bool(
                coord.api.autopilot.run_pass().get("splits")), timeout=20)
            spans = c.placement.get_ranges("hot", 0)
            assert len(spans) == 2
            owners = sorted({i for _lo, _hi, ids in spans for i in ids})
            assert len(owners) == 2
            assert _wait(lambda: all(
                s.api.cluster.placement.range_count >= len(spans)
                for s in servers), timeout=10)
            assert coord.api.autopilot_metrics()[
                "autopilot_splits_total"] == 1

            # reads stay exact through every entry, and one entering
            # through the node that owns no span reaches both owners
            for s in servers:
                assert count(s, 1) == len(cols)
            by_name = {s.config.name: s for s in servers}
            (outsider,) = [s for s in servers
                           if s.config.name not in owners]

            def served(name):
                return req("GET", f"{uri(by_name[name])}/debug/vars")[
                    "serving_fastlane"]["http_requests_total"]

            before = {n: served(n) for n in owners}
            for _ in range(8):
                assert count(outsider, 1) == len(cols)
            # successive reads rotate over the span owners: each served
            # some (and one of the two /debug/vars requests around them)
            delta = {n: served(n) - before[n] for n in owners}
            assert all(d >= 2 for d in delta.values()), delta

            rs = global_route_stats()
            sliced, union = rs.range_slices, rs.union_writes
            for col in cols:
                out = req("POST", f"{uri(outsider)}/index/hot/query",
                          f"Set({col + 1}, f=2)".encode())
                assert out["results"] == [True]
            assert rs.range_slices - sliced >= len(cols)
            assert rs.union_writes == union
            for col in cols[:4]:
                req("POST", f"{uri(outsider)}/index/hot/query",
                    f"Clear({col}, f=1)".encode())
            assert rs.union_writes - union >= 4
            # each narrowed write is held by its span's owner, and by
            # every union owner after a repair pass
            assert sum(count(by_name[n], 2) for n in owners) >= len(cols)
            for s in servers:
                s.api.cluster.sync_holder()
            for s in servers:
                assert count(s, 2) == len(cols)
                assert count(s, 1) == len(cols) - 4
        finally:
            for s in servers:
                s.close()


class TestResume:
    def test_departed_target_record_is_stamped_done(self):
        c = _bare_cluster(["n0"])
        em = ElasticManager(c)
        epoch = c._bump_epoch()
        c.drain_record = {"epoch": epoch, "rev": 2, "target": "gone",
                          "state": "moving", "coordinator": "n9",
                          "groups": 1, "moved": 0, "error": ""}
        assert em.maybe_resume() is True
        assert c.drain_record["state"] == "done"
        assert em.drains_completed == 1
        assert em.maybe_resume() is False  # terminal: nothing to do

    def test_inactive_record_is_ignored(self):
        c = _bare_cluster(["n0"])
        em = ElasticManager(c)
        assert em.maybe_resume() is False
        c.drain_record = {"epoch": 1024, "rev": 9, "target": "n0",
                          "state": "aborted"}
        assert em.maybe_resume() is False

    def test_failover_coordinator_finishes_a_leaving_drain(self,
                                                           tmp_path):
        """The resumability contract: a record parked in "leaving"
        (its coordinator died right after the handoff step) is adopted
        by the acting coordinator's maybe_resume — the heartbeat-tick
        hook — and driven to done, with the target actually leaving."""
        servers = make_cluster(tmp_path, 3, replica_n=1)
        try:
            coord = _coordinator(servers)
            c = coord.api.cluster
            coord.api.elastic.LEAVE_TIMEOUT = 5.0
            victim = next(s for s in reversed(servers) if s is not coord)
            vname = victim.config.name
            # the record claims a DEAD coordinator minted it mid-drain
            epoch = c._bump_epoch()
            c.set_drain({"epoch": epoch, "rev": 4, "target": vname,
                         "state": "leaving", "coordinator": "departed",
                         "groups": 0, "moved": 0, "error": ""})

            assert coord.api.elastic.maybe_resume() is True
            assert coord.api.elastic.drains_resumed == 1
            assert _wait(lambda: c.drain_record.get("state") == "done",
                         timeout=20), c.drain_record
            assert _wait(lambda: vname not in c.nodes, timeout=10)
            assert victim.api.cluster._left
        finally:
            for s in servers:
                s.close()


class TestWireEpochRegression:
    def test_drain_update_survives_the_moving_steps_epoch_bump(self):
        """The bug the stamp fixed: the drain's own moving step mints
        newer cluster epochs (placement + resize), so a drain-update
        stamped with the record's start epoch would be FENCED as stale
        by every peer. The wire must carry the CURRENT epoch; the
        record's (epoch, rev) pair orders copies inside adopt_drain."""
        c = _bare_cluster(["n0", "n1"])
        record = {"epoch": 1024, "rev": 1, "target": "n1",
                  "state": "pending", "coordinator": "n0",
                  "groups": 0, "moved": 0, "error": ""}
        c.handle_message({"type": "drain-update", "epoch": 1024,
                          "drain": dict(record)})
        assert c.drain_record["state"] == "pending"

        # the moving step bumped the cluster epoch well past 1024
        c.handle_message({"type": "cluster-state", "state": "NORMAL",
                          "epoch": 9216})
        assert c.epoch == 9216

        # a state advance of the SAME drain, correctly stamped with the
        # current epoch, must be adopted via its higher rev
        record["rev"], record["state"] = 4, "handoff"
        c.handle_message({"type": "drain-update", "epoch": 9216,
                          "drain": dict(record)})
        assert c.drain_record["state"] == "handoff"

        # while a genuinely STALE SENDER (the healed ex-coordinator
        # replaying the old wire epoch) is fenced unapplied
        rejects = c.stale_epoch_rejects
        stale = dict(record, rev=9, state="aborted")
        c.handle_message({"type": "drain-update", "epoch": 1024,
                          "drain": stale})
        assert c.drain_record["state"] == "handoff"
        assert c.stale_epoch_rejects == rejects + 1

    def test_drain_leave_targets_only_the_named_node(self):
        c = _bare_cluster(["n0", "n1"])
        c.handle_message({"type": "drain-leave", "node": "n1",
                          "epoch": c.epoch})
        time.sleep(0.2)
        assert not c._left  # addressed to n1, we are n0
        c.handle_message({"type": "drain-leave", "node": "n0",
                          "epoch": c.epoch})
        assert _wait(lambda: c._left, timeout=5)


class TestJoinAbsorption:
    def test_joiner_byte_verifies_its_warmed_copy(self, tmp_path):
        """Join warm-up: the inventory fetch byte-verifies each fetched
        fragment against its source (warm_verified counts) before the
        freshness diff may skip it — and with cluster heat present the
        fetch order is hottest-first (warm_heat_ordered counts)."""
        servers = make_cluster(tmp_path, 2, replica_n=1)
        late = None
        try:
            seed(servers[0])
            for _ in range(12):  # heat so the joiner has a warm order
                req("POST", f"{uri(servers[0])}/index/i/query",
                    b"Count(Row(f=1))")
            late = join_node(tmp_path, servers[0], replica_n=1)
            assert late.api.cluster.wait_until_normal(30)
            c = late.api.cluster
            assert _wait(
                lambda: c.warm_verified + c.warm_verify_failed > 0,
                timeout=20)
            # verified copies serve reads; failures would have been
            # left to the freshness diff (still correct, just slower)
            assert c.warm_verified > 0
            got = req("POST", f"{uri(late)}/index/i/query",
                      b"Count(Row(f=1))")["results"][0]
            assert got == 24
            metrics = c.metrics()
            assert metrics["elastic_warm_verified_total"] == \
                c.warm_verified
        finally:
            if late is not None:
                late.close()
            for s in servers:
                s.close()
