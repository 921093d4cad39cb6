"""In-process multi-node cluster tests.

The analog of the reference's key fixture test.MustRunCluster (SURVEY.md
§4): boots n real Servers in ONE process, each with its own temp data dir
and real HTTP listener on an ephemeral localhost port. No mocks — remote
mapReduce, schema broadcast, routed writes, replication, and anti-entropy
all run over loopback HTTP.
"""

import json
import urllib.request

import numpy as np
import pytest

from pilosa_tpu.server import Server, ServerConfig
from pilosa_tpu.shardwidth import SHARD_WIDTH


# one make_cluster for every cluster suite (node names/dirs/ticker-off
# semantics identical; keeping a private copy here meant every new
# ServerConfig knob needed a synchronized two-file edit)
from cluster_helpers import make_cluster  # noqa: E402


def req(method, url, body=None, content_type="application/json"):
    data = body if isinstance(body, (bytes, type(None))) else json.dumps(body).encode()
    r = urllib.request.Request(url, data=data, method=method)
    if data is not None:
        r.add_header("Content-Type", content_type)
    with urllib.request.urlopen(r) as resp:
        return json.loads(resp.read() or b"{}")


@pytest.fixture
def cluster3(tmp_path):
    servers = make_cluster(tmp_path, 3)
    yield servers
    for s in servers:
        s.close()


def uri(s: Server) -> str:
    return f"http://localhost:{s.port}"


def _resize_pair(tmp_path, servers):
    """Shared resize-test fixture: schema on node 0, one fragment on the
    acting coordinator so the PEER is the owner that must fetch it.
    Returns (coord, peer)."""
    req("POST", f"{uri(servers[0])}/index/i", {})
    req("POST", f"{uri(servers[0])}/index/i/field/f", {})
    coord = next(s for s in servers if s.api.cluster.is_acting_coordinator)
    peer = next(s for s in servers if s is not coord)
    fc = coord.holder.index("i").field("f")
    fragc = fc.view("standard", create=True).fragment(3, create=True)
    fragc.bulk_import(np.asarray([2], np.uint64), np.asarray([5], np.uint64))
    return coord, peer


class TestMembership:
    def test_all_nodes_see_each_other(self, cluster3):
        for s in cluster3:
            st = req("GET", f"{uri(s)}/status")
            assert {n["id"] for n in st["nodes"]} == {"n0", "n1", "n2"}
            assert st["state"] == "NORMAL"
        coords = {
            next(n["id"] for n in req("GET", f"{uri(s)}/status")["nodes"]
                 if n["isCoordinator"])
            for s in cluster3
        }
        assert len(coords) == 1  # everyone agrees on the coordinator

    def test_concurrent_joins_relay_membership(self, cluster3):
        """Two joiners racing through ONE seed each adopt the seed's
        /status member list as of THEIR join and announce only to those
        nodes — so neither ever learns the other, and each serves its
        own asymmetric ring (reads through one route around data the
        other holds: indistinguishable from lost acked writes at the
        edge). The node-join handler must gossip a first-seen join both
        ways; this pins that relay."""
        import time

        n0, n1, n2 = (s.api.cluster for s in cluster3)
        uris = {c.local.id: c.local.uri for c in (n0, n1, n2)}
        # hand-craft the race end-state: n1 joined first (seed+n1 know
        # each other), n2 fetched the seed's status BEFORE n1's announce
        # landed (knows the seed only), n2's own announce still in flight
        for c, drop in ((n0, "n2"), (n1, "n2"), (n2, "n1")):
            with c._lock:
                c.nodes.pop(drop, None)
                c._note_membership_changed_locked()
        # ... and now n2's announce arrives at the seed
        n0.handle_message(
            {"type": "node-join", "id": "n2", "uri": uris["n2"]})
        want = {"n0", "n1", "n2"}
        deadline = time.time() + 10
        while time.time() < deadline:
            if all(set(c.nodes) == want for c in (n0, n1, n2)):
                break
            time.sleep(0.05)
        assert set(n1.nodes) == want  # the relay told the earlier joiner
        assert set(n2.nodes) == want  # ...and the new joiner about it
        assert set(n0.nodes) == want

    def test_schema_broadcast(self, cluster3):
        req("POST", f"{uri(cluster3[1])}/index/repos", {})
        req("POST", f"{uri(cluster3[1])}/index/repos/field/stargazer", {})
        for s in cluster3:
            schema = req("GET", f"{uri(s)}/schema")
            assert schema["indexes"][0]["name"] == "repos"
            assert schema["indexes"][0]["fields"][0]["name"] == "stargazer"

    def test_recalculate_caches_broadcasts(self, cluster3):
        """POST /recalculate-caches to ONE node repairs drifted TopN
        caches on EVERY node (reference api.RecalculateCaches: SendSync
        then local recount)."""
        req("POST", f"{uri(cluster3[0])}/index/i", {})
        req("POST", f"{uri(cluster3[0])}/index/i/field/f", {})
        for shard in range(6):  # bits spread across all three nodes
            cols = [shard * SHARD_WIDTH + c for c in range(4)]
            req("POST", f"{uri(cluster3[shard % 3])}/index/i/field/f/import",
                {"rows": [1] * len(cols), "columns": cols})
        # drift every node's caches for its local fragments of field f
        drifted = []
        for s in cluster3:
            for view in s.holder.indexes["i"].fields["f"].views.values():
                for frag in view.fragments.values():
                    frag.row_cache.bulk_add(1, 12345)
                    frag.row_cache.bulk_add(77, 9)  # phantom
                    drifted.append(frag)
        assert drifted
        r = urllib.request.Request(
            f"{uri(cluster3[2])}/recalculate-caches", data=b"",
            method="POST",
        )
        with urllib.request.urlopen(r) as resp:
            assert resp.status == 204
        # 204 = queued: every node recounts in a background worker so
        # message delivery/heartbeats never stall on the scan; join
        # each node's worker before asserting
        for s in cluster3:
            t = s.api._recalc_thread
            if t is not None:
                t.join(timeout=30)
        for frag in drifted:
            assert frag.row_cache.get(77) is None, frag.frag_id
            c = frag.row_cache.get(1)
            assert c is None or c != 12345, frag.frag_id


class TestDistributedQueries:
    def seed_data(self, cluster3):
        """Write bits spanning 6 shards through different nodes."""
        req("POST", f"{uri(cluster3[0])}/index/i", {})
        req("POST", f"{uri(cluster3[0])}/index/i/field/f", {})
        oracle = {}
        for shard in range(6):
            cols = [shard * SHARD_WIDTH + c for c in range(10 * (shard + 1))]
            node = cluster3[shard % 3]
            body = {"rows": [1] * len(cols), "columns": cols}
            req("POST", f"{uri(node)}/index/i/field/f/import", body)
            oracle[shard] = cols
        return oracle

    def test_writes_route_and_queries_fan_out(self, cluster3):
        oracle = self.seed_data(cluster3)
        total = sum(len(v) for v in oracle.values())
        for s in cluster3:  # every node sees the global count
            out = req("POST", f"{uri(s)}/index/i/query", b"Count(Row(f=1))")
            assert out["results"] == [total]

    def test_row_union_across_nodes(self, cluster3):
        oracle = self.seed_data(cluster3)
        out = req("POST", f"{uri(cluster3[2])}/index/i/query", b"Row(f=1)")
        expect = sorted(c for cols in oracle.values() for c in cols)
        assert out["results"][0]["columns"] == expect

    def test_set_via_any_node(self, cluster3):
        req("POST", f"{uri(cluster3[0])}/index/i", {})
        req("POST", f"{uri(cluster3[0])}/index/i/field/f", {})
        # single-bit Sets through node 2, columns across many shards
        for shard in range(5):
            col = shard * SHARD_WIDTH + 7
            out = req("POST", f"{uri(cluster3[2])}/index/i/query",
                      f"Set({col}, f=9)".encode())
            assert out["results"] == [True]
        out = req("POST", f"{uri(cluster3[0])}/index/i/query", b"Count(Row(f=9))")
        assert out["results"] == [5]

    def test_topn_two_phase_across_nodes(self, cluster3):
        req("POST", f"{uri(cluster3[0])}/index/i", {})
        req("POST", f"{uri(cluster3[0])}/index/i/field/f", {})
        # row r gets 10*r bits spread over shards owned by different nodes
        for row, n_bits in [(1, 10), (2, 40), (3, 25)]:
            cols = [
                (i % 6) * SHARD_WIDTH + (row * 1000 + i) for i in range(n_bits)
            ]
            req("POST", f"{uri(cluster3[0])}/index/i/field/f/import",
                {"rows": [row] * len(cols), "columns": cols})
        out = req("POST", f"{uri(cluster3[1])}/index/i/query", b"TopN(f, n=2)")
        assert out["results"][0] == [
            {"id": 2, "count": 40}, {"id": 3, "count": 25},
        ]

    def test_topn_threshold_applies_after_cross_node_merge(self, cluster3):
        """threshold= filters GLOBAL counts: rows whose per-node partial
        counts all sit below the floor but whose merged count qualifies
        must survive (the mapped sub-queries carry no threshold)."""
        req("POST", f"{uri(cluster3[0])}/index/i", {})
        req("POST", f"{uri(cluster3[0])}/index/i/field/f", {})
        # row 2: 5 bits in each of 6 shards (owned by different nodes)
        # → every per-node partial ≤ 10, global = 30
        for row, per_shard in [(1, 1), (2, 5)]:
            cols = [
                s * SHARD_WIDTH + row * 100 + i
                for s in range(6) for i in range(per_shard)
            ]
            req("POST", f"{uri(cluster3[0])}/index/i/field/f/import",
                {"rows": [row] * len(cols), "columns": cols})
        out = req("POST", f"{uri(cluster3[1])}/index/i/query",
                  b"TopN(f, n=10, threshold=20)")
        assert out["results"][0] == [{"id": 2, "count": 30}]

    def test_groupby_having_applies_after_cross_node_merge(self, cluster3):
        """having=Condition(count > N) filters MERGED group counts; a
        per-node filter would wrongly drop groups whose partials are
        individually under the floor."""
        req("POST", f"{uri(cluster3[0])}/index/i", {})
        req("POST", f"{uri(cluster3[0])}/index/i/field/a", {})
        for shard in range(6):
            base = shard * SHARD_WIDTH
            # row 1: 2 bits/shard (global 12); row 2: 1 bit/shard (global 6)
            req("POST", f"{uri(cluster3[0])}/index/i/field/a/import",
                {"rows": [1, 1, 2], "columns": [base, base + 1, base + 2]})
        out = req("POST", f"{uri(cluster3[1])}/index/i/query",
                  b"GroupBy(Rows(a), having=Condition(count > 8))")
        assert out["results"][0] == [
            {"group": [{"field": "a", "rowID": 1}], "count": 12}
        ]

    def test_options_shards_no_double_count_with_replication(self, tmp_path):
        """Options(shards=) on a replicated cluster: a remote sub-query
        must evaluate only its ASSIGNED slice of the user's shard set —
        overriding the assignment with the full user set makes every
        replica evaluate shards it holds as a SECONDARY too, double-
        counting them in the merge (3 nodes, replicaN=2: remote groups
        overlap through replication)."""
        servers = make_cluster(tmp_path, 3, replica_n=2)
        try:
            req("POST", f"{uri(servers[0])}/index/i", {})
            req("POST", f"{uri(servers[0])}/index/i/field/f", {})
            n_shards = 8
            cols = [s * SHARD_WIDTH + 1 for s in range(n_shards)]
            req("POST", f"{uri(servers[0])}/index/i/field/f/import",
                {"rows": [1] * len(cols), "columns": cols})
            all_shards = list(range(n_shards))
            pql = f"Options(Count(Row(f=1)), shards={all_shards})".encode()
            for s in servers:  # every coordinator sees the exact count
                out = req("POST", f"{uri(s)}/index/i/query", pql)
                assert out["results"] == [n_shards], (s.config.name, out)
            out = req("POST", f"{uri(servers[0])}/index/i/query",
                      b"Options(Count(Row(f=1)), shards=[0, 3, 5])")
            assert out["results"] == [3], out
            # a request-level ?shards= restriction INTERSECTS the
            # Options(shards=) set (never widened), same as single-node
            out = req("POST",
                      f"{uri(servers[0])}/index/i/query?shards=0,1",
                      f"Options(Count(Row(f=1)), shards={all_shards})"
                      .encode())
            assert out["results"] == [2], out
        finally:
            for s in servers:
                s.close()

    def test_includes_column_across_nodes(self, cluster3):
        """IncludesColumn routes to the column's shard owner; it honors
        Options(shards=) restrictions and keyed columns cluster-wide."""
        req("POST", f"{uri(cluster3[0])}/index/i", {})
        req("POST", f"{uri(cluster3[0])}/index/i/field/f", {})
        cols = [s * SHARD_WIDTH + 3 for s in range(6)]
        req("POST", f"{uri(cluster3[0])}/index/i/field/f/import",
            {"rows": [1] * len(cols), "columns": cols})
        target = 4 * SHARD_WIDTH + 3  # shard 4, wherever it lives
        for s in cluster3:  # answer identical from every coordinator
            out = req("POST", f"{uri(s)}/index/i/query",
                      f"IncludesColumn(Row(f=1), column={target})".encode())
            assert out["results"] == [True], s.config.name
        out = req("POST", f"{uri(cluster3[1])}/index/i/query",
                  f"Options(IncludesColumn(Row(f=1), column={target}), "
                  f"shards=[0, 1])".encode())
        assert out["results"] == [False]
        out = req("POST", f"{uri(cluster3[1])}/index/i/query",
                  f"IncludesColumn(Row(f=1), column={target + 1})".encode())
        assert out["results"] == [False]

    def test_bsi_sum_across_nodes(self, cluster3):
        req("POST", f"{uri(cluster3[0])}/index/i", {})
        req("POST", f"{uri(cluster3[0])}/index/i/field/v",
            {"options": {"type": "int", "min": 0, "max": 1000}})
        cols = [s * SHARD_WIDTH for s in range(6)]
        vals = [10, 20, 30, 40, 50, 60]
        req("POST", f"{uri(cluster3[1])}/index/i/field/v/import-value",
            {"columns": cols, "values": vals})
        out = req("POST", f"{uri(cluster3[2])}/index/i/query", b'Sum(field="v")')
        assert out["results"][0] == {"value": 210, "count": 6}
        out = req("POST", f"{uri(cluster3[0])}/index/i/query", b"Count(Range(v > 25))")
        assert out["results"] == [4]

    def test_groupby_across_nodes(self, cluster3):
        req("POST", f"{uri(cluster3[0])}/index/i", {})
        req("POST", f"{uri(cluster3[0])}/index/i/field/a", {})
        req("POST", f"{uri(cluster3[0])}/index/i/field/b", {})
        for shard in range(4):
            base = shard * SHARD_WIDTH
            req("POST", f"{uri(cluster3[0])}/index/i/field/a/import",
                {"rows": [1] * 6, "columns": [base + c for c in range(6)]})
            req("POST", f"{uri(cluster3[0])}/index/i/field/b/import",
                {"rows": [7] * 3, "columns": [base + c for c in range(0, 6, 2)]})
        out = req("POST", f"{uri(cluster3[1])}/index/i/query",
                  b"GroupBy(Rows(a), Rows(b))")
        assert out["results"][0] == [
            {"group": [{"field": "a", "rowID": 1}, {"field": "b", "rowID": 7}],
             "count": 12}
        ]


    def test_groupby_aggregate_sum_across_nodes(self, cluster3):
        req("POST", f"{uri(cluster3[0])}/index/i", {})
        req("POST", f"{uri(cluster3[0])}/index/i/field/a", {})
        req("POST", f"{uri(cluster3[0])}/index/i/field/amt",
            {"options": {"type": "int", "min": 0, "max": 1000}})
        cols, vals = [], []
        for shard in range(4):
            base = shard * SHARD_WIDTH
            req("POST", f"{uri(cluster3[0])}/index/i/field/a/import",
                {"rows": [1, 1], "columns": [base, base + 1]})
            cols += [base, base + 1]
            vals += [10 * (shard + 1), 1]
        req("POST", f"{uri(cluster3[1])}/index/i/field/amt/import-value",
            {"columns": cols, "values": vals})
        out = req("POST", f"{uri(cluster3[2])}/index/i/query",
                  b'GroupBy(Rows(a), aggregate=Sum(field="amt"))')
        (g,) = out["results"][0]
        assert g["group"] == [{"field": "a", "rowID": 1}]
        assert g["count"] == 8
        assert g["sum"] == sum(vals)


class TestReplication:
    def test_replica_writes_land_on_two_nodes(self, tmp_path):
        servers = make_cluster(tmp_path, 3, replica_n=2)
        try:
            req("POST", f"{uri(servers[0])}/index/i", {})
            req("POST", f"{uri(servers[0])}/index/i/field/f", {})
            cols = [s * SHARD_WIDTH + 1 for s in range(4)]
            req("POST", f"{uri(servers[0])}/index/i/field/f/import",
                {"rows": [1] * len(cols), "columns": cols})
            # each shard's fragment must exist on exactly replica_n holders
            for shard in range(4):
                holders_with = sum(
                    1 for s in servers
                    if (f := s.holder.index("i").field("f").view("standard"))
                    and f.fragment(shard) is not None
                    and f.fragment(shard).contains(1, 1)
                )
                assert holders_with == 2, f"shard {shard}"
            # queries still see each shard once
            out = req("POST", f"{uri(servers[1])}/index/i/query", b"Count(Row(f=1))")
            assert out["results"] == [4]
        finally:
            for s in servers:
                s.close()

    def test_anti_entropy_repairs_diverged_replica(self, tmp_path):
        servers = make_cluster(tmp_path, 2, replica_n=2)
        try:
            req("POST", f"{uri(servers[0])}/index/i", {})
            req("POST", f"{uri(servers[0])}/index/i/field/f", {})
            req("POST", f"{uri(servers[0])}/index/i/query", b"Set(1, f=1)")
            # diverge: write a bit directly into node0's holder only
            frag = (servers[0].holder.index("i").field("f")
                    .view("standard").fragment(0, create=True))
            frag.set_bit(1, 999)
            frag1 = (servers[1].holder.index("i").field("f")
                     .view("standard").fragment(0))
            assert not frag1.contains(1, 999)
            # node1 pulls the missing bits during its sync pass
            repaired = servers[1].api.cluster.sync_holder()
            assert repaired["bits"] >= 1
            assert frag1.contains(1, 999)
        finally:
            for s in servers:
                s.close()


class TestJoinResize:
    def test_new_node_fetches_owned_fragments(self, tmp_path):
        servers = make_cluster(tmp_path, 1)
        try:
            req("POST", f"{uri(servers[0])}/index/i", {})
            req("POST", f"{uri(servers[0])}/index/i/field/f", {})
            cols = [s * SHARD_WIDTH + 3 for s in range(16)]
            req("POST", f"{uri(servers[0])}/index/i/field/f/import",
                {"rows": [1] * len(cols), "columns": cols})
            # join a second node
            servers += make_cluster(tmp_path / "late", 0)  # no-op, keep shape
            cfg = ServerConfig(
                data_dir=str(tmp_path / "node_late"), port=0, name="n9",
                seeds=[uri(servers[0])], anti_entropy_interval=0,
                heartbeat_interval=0, use_mesh=False,
            )
            late = Server(cfg).open()
            servers.append(late)
            # the join-time fetch runs as a background job; wait for it
            assert late.api.cluster.wait_until_normal(30)
            # membership propagated
            st = req("GET", f"{uri(servers[0])}/status")
            assert {n["id"] for n in st["nodes"]} == {"n0", "n9"}
            # schema adopted
            assert late.holder.index("i") is not None
            # the late node now owns some shards and must have their data
            owned = [s for s in range(16)
                     if late.api.cluster.owns_shard("i", s)]
            assert owned, "hash ring should give the new node some shards"
            view = late.holder.index("i").field("f").view("standard")
            for shard in owned:
                frag = view.fragment(shard) if view else None
                assert frag is not None and frag.contains(1, 3), f"shard {shard}"
            # cluster-wide queries remain correct from either node
            out = req("POST", f"{uri(late)}/index/i/query", b"Count(Row(f=1))")
            assert out["results"] == [16]
        finally:
            for s in servers:
                s.close()


    def test_self_join_fetch_falls_back_to_replica_and_dedupes(
        self, tmp_path, monkeypatch
    ):
        """replicaN=2 self-join: the inventory lists each owned fragment
        ONCE (not once per replica), and when the chosen source errors on
        the data fetch the replica fallback supplies the fragment instead
        of silently losing it until anti-entropy."""
        from pilosa_tpu.parallel.client import ClientError, InternalClient

        servers = make_cluster(tmp_path, 2, replica_n=2)
        try:
            req("POST", f"{uri(servers[0])}/index/i", {})
            req("POST", f"{uri(servers[0])}/index/i/field/f", {})
            cols = [s * SHARD_WIDTH + 3 for s in range(8)]
            req("POST", f"{uri(servers[0])}/index/i/field/f/import",
                {"rows": [1] * len(cols), "columns": cols})
            # with replicaN=2 and two nodes, BOTH peers hold every
            # fragment; break one peer's data endpoint for everyone and
            # record every fetch attempt
            broken_uri = uri(servers[1])
            real_fd = InternalClient.fragment_data
            fetched: list[tuple] = []

            def flaky_fragment_data(client, node_uri, index, field, view,
                                    shard, *a, **k):
                fetched.append((node_uri, field, view, shard))
                if node_uri == broken_uri:
                    raise ClientError(f"injected failure for {node_uri}")
                return real_fd(client, node_uri, index, field, view, shard,
                               *a, **k)

            monkeypatch.setattr(
                InternalClient, "fragment_data", flaky_fragment_data
            )
            cfg = ServerConfig(
                data_dir=str(tmp_path / "node_late"), port=0, name="n9",
                seeds=[uri(servers[0])], anti_entropy_interval=0,
                heartbeat_interval=0, use_mesh=False, replica_n=2,
            )
            late = Server(cfg).open()
            servers.append(late)
            assert late.api.cluster.wait_until_normal(30)
            # every owned shard's data landed despite the broken peer
            owned = [s for s in range(8)
                     if late.api.cluster.owns_shard("i", s)]
            assert owned
            view = late.holder.index("i").field("f").view("standard")
            for shard in owned:
                frag = view.fragment(shard)
                assert frag is not None and frag.contains(1, 3), f"shard {shard}"
            # dedup: the inventory lists each fragment once (NOT once per
            # replica), so no key is fetched more than twice — twice only
            # when the joiner's inventory fetch and the coordinator's
            # instruction job overlap, a DELIBERATE redundancy (each path
            # covers the other's failure modes; the union is idempotent)
            ok = [f for f in fetched if f[0] != broken_uri]
            assert ok
            from collections import Counter
            worst = Counter(ok).most_common(1)[0]
            assert worst[1] <= 2, worst
        finally:
            for s in servers:
                s.close()


class TestFailureHandling:
    def test_query_survives_replica_node_death(self, tmp_path):
        """replicaN=2: killing one node must not lose query coverage —
        routing falls back to the surviving replica (reference: memberlist
        dead event -> DEGRADED, reads served from remaining owners)."""
        servers = make_cluster(tmp_path, 3, replica_n=2)
        try:
            req("POST", f"{uri(servers[0])}/index/i", {})
            req("POST", f"{uri(servers[0])}/index/i/field/f", {})
            cols = [s * SHARD_WIDTH + 7 for s in range(6)]
            req("POST", f"{uri(servers[0])}/index/i/field/f/import",
                {"rows": [1] * len(cols), "columns": cols})
            out = req("POST", f"{uri(servers[0])}/index/i/query", b"Count(Row(f=1))")
            assert out["results"] == [6]

            victim = servers.pop(2)
            victim.close()
            # survivors notice on their next heartbeat pass
            for s in servers:
                s.api.cluster.heartbeat()
                states = {n.id: n.state for n in s.api.cluster.nodes.values()}
                assert states["n2"] == "DEGRADED", states

            for s in servers:
                out = req("POST", f"{uri(s)}/index/i/query", b"Count(Row(f=1))")
                assert out["results"] == [6]
                out = req("POST", f"{uri(s)}/index/i/query", b"Row(f=1)")
                assert out["results"][0]["columns"] == cols
        finally:
            for s in servers:
                s.close()

    def test_node_restart_recovers_data_and_membership(self, tmp_path):
        """Kill + restart on the same data dir: fragments reload from the
        roaring files + op logs (checkpoint/resume == holder.Open,
        SURVEY.md §5.4) and the node rejoins the cluster."""
        servers = make_cluster(tmp_path, 2)
        try:
            req("POST", f"{uri(servers[0])}/index/i", {})
            req("POST", f"{uri(servers[0])}/index/i/field/f", {})
            cols = [s * SHARD_WIDTH + 1 for s in range(4)]
            req("POST", f"{uri(servers[0])}/index/i/field/f/import",
                {"rows": [1] * len(cols), "columns": cols})
            # unsnapshotted single-bit writes must also survive (op log)
            req("POST", f"{uri(servers[0])}/index/i/query", b"Set(123, f=9)")

            victim = servers.pop(1)
            victim_dir = victim.config.data_dir
            victim.close()
            servers[0].api.cluster.heartbeat()

            reborn = Server(ServerConfig(
                data_dir=victim_dir, port=0, name="n1",
                seeds=[uri(servers[0])], anti_entropy_interval=0,
                heartbeat_interval=0, use_mesh=False,
            )).open()
            servers.append(reborn)
            assert reborn.api.cluster.wait_until_normal(30)
            servers[0].api.cluster.heartbeat()
            st = req("GET", f"{uri(servers[0])}/status")
            assert {n["id"]: n["state"] for n in st["nodes"]} == {
                "n0": "NORMAL", "n1": "NORMAL"}

            for s in servers:
                out = req("POST", f"{uri(s)}/index/i/query", b"Count(Row(f=1))")
                assert out["results"] == [4]
                out = req("POST", f"{uri(s)}/index/i/query", b"Row(f=9)")
                assert out["results"][0]["columns"] == [123]
        finally:
            for s in servers:
                s.close()

    def test_rejoining_node_catches_up_before_serving(self, tmp_path):
        """replicaN=2: writes that landed on the surviving replica during
        a node's outage must be visible the moment the restarted node
        reaches NORMAL — the self-join gate block-diffs held (stale)
        fragments before releasing, not just fetching missing ones."""
        servers = make_cluster(tmp_path, 2, replica_n=2)
        try:
            req("POST", f"{uri(servers[0])}/index/i", {})
            req("POST", f"{uri(servers[0])}/index/i/field/f", {})
            cols = [s * SHARD_WIDTH + 1 for s in range(4)]
            req("POST", f"{uri(servers[0])}/index/i/field/f/import",
                {"rows": [1] * len(cols), "columns": cols})

            victim = servers.pop(1)
            victim_dir = victim.config.data_dir
            victim.close()
            from pilosa_tpu.parallel.cluster import DEAD_HEARTBEATS

            for _ in range(DEAD_HEARTBEATS):
                servers[0].api.cluster.heartbeat()
            # outage-window writes: same row, new columns — the victim's
            # on-disk fragments are now non-empty AND stale
            stale_cols = [s * SHARD_WIDTH + 2 for s in range(4)]
            req("POST", f"{uri(servers[0])}/index/i/field/f/import",
                {"rows": [1] * len(stale_cols), "columns": stale_cols})

            reborn = Server(ServerConfig(
                data_dir=victim_dir, port=0, name="n1",
                seeds=[uri(servers[0])], anti_entropy_interval=0,
                heartbeat_interval=0, use_mesh=False, replica_n=2,
            )).open()
            servers.append(reborn)
            assert reborn.api.cluster.wait_until_normal(30)
            # the reborn node's LOCAL fragments carry the outage writes
            # (no cross-node query help: ask its holder directly)
            view = reborn.holder.index("i").field("f").view("standard")
            for shard in range(4):
                if not reborn.api.cluster.owns_shard("i", shard):
                    continue
                frag = view.fragment(shard)
                assert frag is not None and frag.contains(1, 2), (
                    f"shard {shard} missing outage-window write"
                )
        finally:
            for s in servers:
                s.close()


class TestResizeAndReReplication:
    def test_heartbeat_death_triggers_auto_rereplication(self, tmp_path):
        """Kill a node; after DEAD_HEARTBEATS failed probes the acting
        coordinator removes it and drives coordinator-computed resize
        instructions until every shard is back at full replica count
        (no manual join or anti-entropy pass needed)."""
        from pilosa_tpu.parallel.cluster import DEAD_HEARTBEATS

        servers = make_cluster(tmp_path, 3, replica_n=2)
        try:
            req("POST", f"{uri(servers[0])}/index/i", {})
            req("POST", f"{uri(servers[0])}/index/i/field/f", {})
            cols = [s * SHARD_WIDTH + 11 for s in range(8)]
            req("POST", f"{uri(servers[0])}/index/i/field/f/import",
                {"rows": [1] * len(cols), "columns": cols})

            victim = servers.pop(2)
            victim.close()
            for _ in range(DEAD_HEARTBEATS):
                for s in servers:
                    s.api.cluster.heartbeat()

            # membership converged: the dead node is gone everywhere
            for s in servers:
                assert set(s.api.cluster.nodes) == {"n0", "n1"}, (
                    s.api.cluster.nodes)
                assert s.api.cluster.state == "NORMAL"

            # full replication restored: every shard lives on BOTH
            # survivors with the right bits
            for shard in range(8):
                for s in servers:
                    frag = (s.holder.index("i").field("f")
                            .view("standard").fragment(shard))
                    assert frag is not None, (shard, s.config.name)
                    assert frag.count_row(1) == 1, (shard, s.config.name)

            for s in servers:
                out = req("POST", f"{uri(s)}/index/i/query", b"Count(Row(f=1))")
                assert out["results"] == [8]
        finally:
            for s in servers:
                s.close()

    def test_coordinator_resize_instructions(self, tmp_path):
        """coordinate_resize computes per-node fetch instructions for
        owners missing fragments (reference ResizeInstruction)."""
        import numpy as np

        servers = make_cluster(tmp_path, 2, replica_n=2)
        try:
            req("POST", f"{uri(servers[0])}/index/i", {})
            req("POST", f"{uri(servers[0])}/index/i/field/f", {})
            # node1 holds a fragment node0 (also an owner) lacks
            f1 = servers[1].holder.index("i").field("f")
            frag1 = f1.view("standard", create=True).fragment(3, create=True)
            frag1.bulk_import(np.asarray([2, 2], np.uint64),
                              np.asarray([5, 9], np.uint64))

            coord = next(s for s in servers
                         if s.api.cluster.is_acting_coordinator)
            instructions = coord.api.cluster.coordinate_resize()
            assert instructions  # something was computed
            f0 = servers[0].holder.index("i").field("f")
            frag0 = f0.view("standard").fragment(3)
            assert frag0 is not None and frag0.count() == 2
            for s in servers:
                assert s.api.cluster.state == "NORMAL"
        finally:
            for s in servers:
                s.close()

    def test_resize_instruction_uses_fallback_source(self, tmp_path):
        """Coordinator instructions carry extra live holders as
        fallbacks; a receiver whose primary source errors mid-move pulls
        the fragment from a fallback instead of losing it (same contract
        as the self-join inventory)."""
        import numpy as np

        from pilosa_tpu.parallel.client import ClientError, InternalClient

        servers = make_cluster(tmp_path, 3, replica_n=2)
        try:
            req("POST", f"{uri(servers[0])}/index/i", {})
            req("POST", f"{uri(servers[0])}/index/i/field/f", {})
            coord = next(s for s in servers
                         if s.api.cluster.is_acting_coordinator)
            # Drain the join-triggered background resizes (and their
            # synchronous cleanup broadcasts) BEFORE planting: the
            # ~1-in-12 flake was the pending join-resize's cleanup
            # legitimately deleting the planted non-owned copy mid-test,
            # leaving the receiver's fetch with only the broken source.
            # coordinate_resize serializes on the resize lock, so this
            # call returns only after every earlier resize (and its
            # cleanup) finished.
            coord.api.cluster.coordinate_resize()
            peers = [s for s in servers if s is not coord]
            # BOTH peers hold shard 3's fragment; the coordinator (an
            # owner for some shard under replicaN=2) may need to fetch it
            for p in peers:
                fp = p.holder.index("i").field("f")
                fp.view("standard", create=True).fragment(
                    3, create=True
                ).bulk_import(np.asarray([2, 2], np.uint64),
                              np.asarray([5, 9], np.uint64))

            owners = coord.api.cluster.shard_nodes("i", 3)

            def has_frag(s):
                v = s.holder.index("i").field("f").view("standard")
                return v is not None and v.fragment(3) is not None

            receivers = [s for s in servers
                         if any(n.id == s.api.cluster.local.id
                                for n in owners) and not has_frag(s)]
            if not receivers:
                pytest.skip("ring gave shard 3 to its holders only")
            # break the FIRST peer's data endpoint for everyone
            broken_uri = uri(peers[0])
            real_fd = InternalClient.fragment_data

            def flaky(client, node_uri, *a, **k):
                if node_uri == broken_uri:
                    raise ClientError("injected")
                return real_fd(client, node_uri, *a, **k)

            InternalClient.fragment_data = flaky
            try:
                coord.api.cluster.coordinate_resize()
            finally:
                InternalClient.fragment_data = real_fd
            for r in receivers:
                frag = (r.holder.index("i").field("f")
                        .view("standard").fragment(3))
                assert frag is not None and frag.count() == 2, (
                    r.config.name)
        finally:
            for s in servers:
                s.close()

    def test_failed_resize_fetch_leaves_no_empty_placeholder(self, tmp_path):
        """When EVERY source for an instructed move fails, the receiver
        must not keep the eagerly-created empty fragment: an empty
        placeholder serves silently-empty reads for a shard whose data
        exists elsewhere and masks the gap from the self-join
        inventory's already-held check (the resize-source race's second
        half; regression proven to fail pre-fix)."""
        import numpy as np

        from pilosa_tpu.parallel.client import ClientError, InternalClient

        servers = make_cluster(tmp_path, 2, replica_n=2)
        try:
            req("POST", f"{uri(servers[0])}/index/i", {})
            req("POST", f"{uri(servers[0])}/index/i/field/f", {})
            coord = next(s for s in servers
                         if s.api.cluster.is_acting_coordinator)
            coord.api.cluster.coordinate_resize()  # drain join resizes
            peer = next(s for s in servers if s is not coord)
            fp = peer.holder.index("i").field("f")
            fp.view("standard", create=True).fragment(
                3, create=True
            ).bulk_import(np.asarray([2, 2], np.uint64),
                          np.asarray([5, 9], np.uint64))

            def broken(*a, **k):
                raise ClientError("injected: source unreachable")

            real_fd = InternalClient.fragment_data
            real_fb = InternalClient.fragment_blocks
            InternalClient.fragment_data = broken
            InternalClient.fragment_blocks = broken
            try:
                coord.api.cluster.coordinate_resize()
            finally:
                InternalClient.fragment_data = real_fd
                InternalClient.fragment_blocks = real_fb
            v = coord.holder.index("i").field("f").view("standard")
            frag = v.fragment(3) if v is not None else None
            assert frag is None, (
                f"receiver kept an empty placeholder (count="
                f"{frag.count()}) after every source failed"
            )
            # the source's copy is untouched and a later healthy resize
            # still completes the move
            coord.api.cluster.coordinate_resize()
            v = coord.holder.index("i").field("f").view("standard")
            frag = v.fragment(3) if v is not None else None
            assert frag is not None and frag.count() == 2
        finally:
            for s in servers:
                s.close()

    def test_resize_sources_prefer_surviving_owners(self, tmp_path):
        """Instruction sources list holders that REMAIN owners first: a
        non-owner's copy is deleted by this very resize's cleanup, so a
        receiver whose fetch races that cleanup loses a non-owner
        primary source — the root of the ~1-in-12 resize-source flake
        (regression proven to fail pre-fix)."""
        import numpy as np

        servers = make_cluster(tmp_path, 3, replica_n=2)
        try:
            req("POST", f"{uri(servers[0])}/index/i", {})
            req("POST", f"{uri(servers[0])}/index/i/field/f", {})
            coord = next(s for s in servers
                         if s.api.cluster.is_acting_coordinator)
            coord.api.cluster.coordinate_resize()  # drain join resizes
            cluster = coord.api.cluster
            # a shard the COORDINATOR does not own: its two owners are
            # the peers, and the coordinator's planted copy is the
            # non-owner source that must NOT be the primary
            shard = next(
                s for s in range(64)
                if cluster.local.id not in
                {n.id for n in cluster.shard_nodes("i", s)}
            )
            owners = cluster.shard_nodes("i", shard)
            by_id = {s.api.cluster.local.id: s for s in servers}
            src_owner = by_id[owners[0].id]
            receiver = by_id[owners[1].id]
            for holder_server in (coord, src_owner):
                f = holder_server.holder.index("i").field("f")
                f.view("standard", create=True).fragment(
                    shard, create=True
                ).bulk_import(np.asarray([2], np.uint64),
                              np.asarray([5], np.uint64))
            instructions = cluster.coordinate_resize()
            entries = [e for e in instructions.get(
                receiver.api.cluster.local.id, []) if e["shard"] == shard]
            assert entries, instructions
            # pre-fix the holders-walk order made the coordinator (a
            # non-owner, swept by cleanup) the primary source
            assert entries[0]["from"] == src_owner.api.cluster.local.uri, \
                entries
            assert coord.api.cluster.local.uri in entries[0]["fallbacks"]
        finally:
            for s in servers:
                s.close()

    def test_queries_deferred_while_resizing(self, tmp_path):
        import threading
        import time as _time

        servers = make_cluster(tmp_path, 1)
        try:
            req("POST", f"{uri(servers[0])}/index/i", {})
            req("POST", f"{uri(servers[0])}/index/i/field/f", {})
            req("POST", f"{uri(servers[0])}/index/i/query", b"Set(1, f=1)")
            cluster = servers[0].api.cluster
            cluster.state = "RESIZING"
            results = []

            def run():
                out = req("POST", f"{uri(servers[0])}/index/i/query",
                          b"Count(Row(f=1))")
                results.append(out)

            t = threading.Thread(target=run)
            t.start()
            _time.sleep(0.3)
            assert not results  # gated while RESIZING
            cluster.state = "NORMAL"
            t.join(timeout=10)
            assert results and results[0]["results"] == [1]
        finally:
            for s in servers:
                s.close()

    def test_resize_wait_timeout_errors(self, tmp_path, monkeypatch):
        from pilosa_tpu.parallel import cluster_exec

        monkeypatch.setattr(cluster_exec, "_RESIZE_WAIT", 0.2)
        servers = make_cluster(tmp_path, 1)
        try:
            req("POST", f"{uri(servers[0])}/index/i", {})
            req("POST", f"{uri(servers[0])}/index/i/field/f", {})
            servers[0].api.cluster.state = "RESIZING"
            r = urllib.request.Request(
                f"{uri(servers[0])}/index/i/query",
                data=b"Count(Row(f=1))", method="POST")
            with pytest.raises(urllib.error.HTTPError) as e:
                urllib.request.urlopen(r, timeout=10)
            assert "resizing" in e.value.read().decode()
        finally:
            for s in servers:
                s.close()


    def test_node_leave_mid_resize_releases_pending(self, tmp_path, monkeypatch):
        """A peer that leaves (or is declared dead) after acking a resize
        instruction is dropped from the pending set immediately — the
        cluster must not stay gated for the full straggler timeout."""
        import threading
        import time as _time

        from pilosa_tpu.parallel.cluster import Cluster

        monkeypatch.setattr(Cluster, "RESIZE_COMPLETE_TIMEOUT", 30.0)
        servers = make_cluster(tmp_path, 2, replica_n=2)
        try:
            coord, peer = _resize_pair(tmp_path, servers)
            # peer acks the instruction but never fetches nor reports
            peer.api.cluster._run_resize_job = lambda *a, **k: None

            done = threading.Event()
            t = threading.Thread(
                target=lambda: (coord.api.cluster.coordinate_resize(),
                                done.set()),
                daemon=True,
            )
            t.start()
            # wait until the peer is actually pending — a fixed sleep
            # could fire the node-leave before the instruction is sent,
            # passing without exercising the pending-drop path
            deadline = _time.monotonic() + 10
            while not coord.api.cluster._resize_pending:
                assert _time.monotonic() < deadline, "peer never pending"
                _time.sleep(0.01)
            coord.api.cluster.handle_message(
                {"type": "node-leave", "id": peer.api.cluster.local.id}
            )
            assert done.wait(10), "coordinator still gated on departed node"
            assert coord.api.cluster.state == "NORMAL"
        finally:
            for s in servers:
                s.close()


class TestEagerShardVisibility:
    def test_new_remote_shard_visible_without_poll(self, tmp_path):
        """A shard created on one node is broadcast (CreateShardMessage)
        and visible to other nodes' queries immediately — no TTL window."""
        import time as _time

        servers = make_cluster(tmp_path, 2)
        try:
            req("POST", f"{uri(servers[0])}/index/i", {})
            req("POST", f"{uri(servers[0])}/index/i/field/f", {})
            # warm both nodes' shard caches with the empty state
            for s in servers:
                req("POST", f"{uri(s)}/index/i/query", b"Count(Row(f=1))")

            # find a shard owned by node1 alone, import via node1 directly
            c1 = servers[1].api.cluster
            shard = next(s for s in range(64)
                         if c1.shard_nodes("i", s)[0].id == c1.local.id)
            col = shard * SHARD_WIDTH + 3
            req("POST", f"{uri(servers[1])}/index/i/field/f/import",
                {"rows": [1], "columns": [col]})

            # the broadcast is async; wait for receipt (bounded)
            deadline = _time.monotonic() + 5
            while _time.monotonic() < deadline:
                if shard in servers[0].api.cluster.known_shards.get("i", set()):
                    break
                _time.sleep(0.02)
            assert shard in servers[0].api.cluster.known_shards.get("i", set())

            # node0 sees the new shard through its still-warm cache window
            out = req("POST", f"{uri(servers[0])}/index/i/query", b"Row(f=1)")
            assert out["results"][0]["columns"] == [col]
        finally:
            for s in servers:
                s.close()


class TestClusterRaces:
    def test_known_shards_read_during_create_shard_broadcasts(self, tmp_path):
        """_all_shards used to iterate the raw
        known_shards set while handle_message('create-shard') resized it
        from HTTP threads — set.update over a set being resized raises
        RuntimeError mid-query. Hammer both sides concurrently."""
        import threading

        servers = make_cluster(tmp_path, 1)
        try:
            req("POST", f"{uri(servers[0])}/index/i", {})
            req("POST", f"{uri(servers[0])}/index/i/field/f", {})
            cluster = servers[0].api.cluster
            execu = servers[0].api.executor
            errors = []
            stop = threading.Event()

            def mutate():
                shard = 0
                while not stop.is_set():
                    shard += 1
                    try:
                        cluster.handle_message({
                            "type": "create-shard", "index": "i",
                            "shards": [shard],
                        })
                    except Exception as e:  # pragma: no cover
                        errors.append(e)

            def read():
                while not stop.is_set():
                    try:
                        execu._all_shards("i")
                    except Exception as e:
                        errors.append(e)

            threads = [threading.Thread(target=mutate) for _ in range(2)]
            threads += [threading.Thread(target=read) for _ in range(2)]
            for t in threads:
                t.start()
            import time as _time
            _time.sleep(1.0)
            stop.set()
            for t in threads:
                t.join(timeout=5)
            assert not errors, errors
        finally:
            for s in servers:
                s.close()

    def test_failover_coordinator_ungates_stuck_resizing(self, tmp_path):
        """Coordinator dies between broadcasting
        RESIZING and NORMAL; the failover coordinator finds nothing to
        move (replica_n=1 left no live source) and must STILL broadcast
        NORMAL or peers stay gated forever."""
        servers = make_cluster(tmp_path, 2)
        try:
            # let the join-time background fetch settle first: while a
            # local fetch job is in flight, _command_state correctly
            # DEFERS a NORMAL command (the job's completion restores it),
            # so injecting the scenario early makes the final assert race
            # the join job rather than test the failover path
            for s in servers:
                assert s.api.cluster.wait_until_normal(30)
            coord = next(s for s in servers
                         if s.api.cluster.is_acting_coordinator)
            # simulate the dead coordinator's last act reaching only the
            # peers: the failover coordinator itself stays NORMAL (its
            # RESIZING delivery hit a transient error), peers are gated
            for s in servers:
                if s is not coord:
                    s.api.cluster.state = "RESIZING"
            instructions = coord.api.cluster.coordinate_resize()
            assert instructions == {}  # nothing to move...
            for s in servers:            # ...but everyone un-gated
                assert s.api.cluster.state == "NORMAL", s.config.name
        finally:
            for s in servers:
                s.close()

    def test_async_resize_slow_fetch_gates_queries_no_degrade(self, tmp_path):
        """A fetch slower than instruction delivery must not DEGRADE the
        fetching node or un-gate queries mid-move: peers ack immediately,
        fetch in a worker, and the coordinator holds RESIZING until the
        resize-complete report (reference resize-job pattern)."""
        import threading
        import time as _time

        servers = make_cluster(tmp_path, 2, replica_n=2)
        try:
            req("POST", f"{uri(servers[0])}/index/i", {})
            req("POST", f"{uri(servers[0])}/index/i/field/f", {})
            coord = next(s for s in servers
                         if s.api.cluster.is_acting_coordinator)
            peer = next(s for s in servers if s is not coord)
            # the fragment lives on the coordinator; the PEER is the owner
            # that must fetch it, exercising the remote async job path
            fc = coord.holder.index("i").field("f")
            fragc = fc.view("standard", create=True).fragment(3, create=True)
            fragc.bulk_import(np.asarray([2, 2], np.uint64),
                              np.asarray([5, 9], np.uint64))
            peer_cluster = peer.api.cluster

            fetch_started = threading.Event()
            release_fetch = threading.Event()
            real_fetch = type(peer_cluster).fetch_fragments
            states_during_fetch = []

            def slow_fetch(self, sources):
                fetch_started.set()
                assert release_fetch.wait(30)
                return real_fetch(self, sources)

            peer_cluster.fetch_fragments = slow_fetch.__get__(peer_cluster)
            t = threading.Thread(
                target=coord.api.cluster.coordinate_resize, daemon=True
            )
            t.start()
            assert fetch_started.wait(30)
            # mid-move: everyone still gated, nobody DEGRADED
            _time.sleep(0.2)
            states_during_fetch = [
                coord.api.cluster.state,
                next(n.state for n in coord.api.cluster.nodes.values()
                     if n.id == peer_cluster.local.id),
            ]
            release_fetch.set()
            t.join(timeout=30)
            assert not t.is_alive()
            assert states_during_fetch == ["RESIZING", "NORMAL"]
            for s in servers:
                assert s.api.cluster.state == "NORMAL"
            frag0 = (peer.holder.index("i").field("f")
                     .view("standard").fragment(3))
            assert frag0 is not None and frag0.count() == 2
        finally:
            for s in servers:
                s.close()

    def test_async_resize_straggler_timeout_ungates(self, tmp_path, monkeypatch):
        """A peer that never reports completion (died mid-fetch) must not
        gate the cluster forever: the coordinator's straggler timeout
        releases it to anti-entropy repair."""
        from pilosa_tpu.parallel.cluster import Cluster

        monkeypatch.setattr(Cluster, "RESIZE_COMPLETE_TIMEOUT", 0.5)
        servers = make_cluster(tmp_path, 2, replica_n=2)
        try:
            coord, peer = _resize_pair(tmp_path, servers)
            # peer swallows the instruction: fetch never runs, no report.
            # The message handler gates BEFORE spawning the job and hands
            # the gate to the worker — the swallow must still release it
            # or the peer wedges RESIZING for an unrelated reason.
            pc = peer.api.cluster
            pc.fetch_fragments = lambda sources: 0
            pc._run_resize_job = (
                lambda sources, job, reply_to, pre_gated=False:
                pc._end_local_fetch() if pre_gated else None
            )

            coord.api.cluster.coordinate_resize()
            for s in servers:
                assert s.api.cluster.state == "NORMAL"
        finally:
            for s in servers:
                s.close()

    def test_async_resize_progress_keepalive_outlives_timeout(self, tmp_path, monkeypatch):
        """A move longer than the straggler timeout stays gated to
        completion as long as the peer sends progress keepalives — the
        timeout distinguishes dead from slow, not big from small."""
        import threading
        import time as _time

        from pilosa_tpu.parallel.cluster import Cluster

        monkeypatch.setattr(Cluster, "RESIZE_COMPLETE_TIMEOUT", 0.6)
        monkeypatch.setattr(Cluster, "RESIZE_PROGRESS_INTERVAL", 0.2)
        servers = make_cluster(tmp_path, 2, replica_n=2)
        try:
            coord, peer = _resize_pair(tmp_path, servers)
            peer_cluster = peer.api.cluster
            real_fetch = type(peer_cluster).fetch_fragments
            fetch_done = threading.Event()

            def long_fetch(self, sources):
                # 1.5s of "fetching", far past the 0.6s quiet timeout;
                # the worker's timer thread keeps sending progress
                _time.sleep(1.5)
                out = real_fetch(self, sources)
                fetch_done.set()
                return out

            peer_cluster.fetch_fragments = long_fetch.__get__(peer_cluster)
            coord.api.cluster.coordinate_resize()
            # returned only AFTER the slow move finished (not released by
            # the quiet timeout): the fetch completed and data landed
            assert fetch_done.is_set()
            frag = (peer.holder.index("i").field("f")
                    .view("standard").fragment(3))
            assert frag is not None and frag.count() == 1
            for s in servers:
                assert s.api.cluster.state == "NORMAL"
        finally:
            for s in servers:
                s.close()


class TestBinaryInternalWire:
    def test_routed_bulk_import_transfers_bitmap_bytes(self, tmp_path):
        """A routed set-bit import ships per-shard roaring bodies: the
        bytes on the wire are O(bitmap bytes), not JSON int lists
        (reference: every internal hop is protobuf — SURVEY.md §2 #16-17)."""
        # the edge batch below is deliberately huge (2^18 rows); lift the
        # max-writes-per-request gate that edge imports now enforce
        servers = make_cluster(tmp_path, 2, max_writes_per_request=0)
        try:
            req("POST", f"{uri(servers[0])}/index/i", {})
            req("POST", f"{uri(servers[0])}/index/i/field/f", {})
            sent = []
            for s in servers:
                client = s.api.cluster.client
                real_call = client._call

                def spy(method, url, body=None, _real=real_call, **kw):
                    if body is not None:
                        sent.append((url, len(body)))
                    return _real(method, url, body, **kw)

                client._call = spy
            # 2^17 contiguous bits in each of two shards via ONE node:
            # at least one shard's slice routes to the other node
            n = 1 << 17
            cols = list(range(n)) + [SHARD_WIDTH + c for c in range(n)]
            body = {"rows": [1] * len(cols), "columns": cols}
            req("POST", f"{uri(servers[0])}/index/i/field/f/import", body)
            out = req("POST", f"{uri(servers[0])}/index/i/query",
                      b"Count(Row(f=1))")
            assert out["results"] == [2 * n]
            routed = [(u, sz) for u, sz in sent if "import-roaring" in u]
            assert routed, sent
            total = sum(sz for _, sz in routed)
            # run-encoded roaring: a few hundred bytes for 131k contiguous
            # bits; JSON int lists would be ~1.3 MB. Bound generously.
            assert total < 16 * 1024, (total, routed)
        finally:
            for s in servers:
                s.close()

    def test_remote_row_results_negotiate_protobuf(self, tmp_path):
        """Remote Row() partials come back as protobuf (varint-packed
        columns), decoded to the same shapes the JSON path yields."""
        import pytest as _pytest

        from pilosa_tpu import wire

        if not wire.available():
            _pytest.skip("protoc/protobuf runtime unavailable")
        servers = make_cluster(tmp_path, 2)
        try:
            req("POST", f"{uri(servers[0])}/index/i", {})
            req("POST", f"{uri(servers[0])}/index/i/field/f", {})
            cols = [s * SHARD_WIDTH + c for s in range(4) for c in range(50)]
            req("POST", f"{uri(servers[0])}/index/i/field/f/import",
                {"rows": [1] * len(cols), "columns": cols})
            seen_accept = []
            for s in servers:
                client = s.api.cluster.client
                real_call = client._call

                def spy(method, url, body=None, _real=real_call, **kw):
                    if "/query" in url:
                        seen_accept.append(kw.get("accept"))
                    return _real(method, url, body, **kw)

                client._call = spy
            # query via BOTH nodes: whatever the shard ownership split,
            # at least one of the two must fan out remotely
            for s in servers:
                out = req("POST", f"{uri(s)}/index/i/query", b"Row(f=1)")
                assert out["results"][0]["columns"] == sorted(cols)
            assert "application/x-protobuf" in seen_accept
        finally:
            for s in servers:
                s.close()


class TestConcurrentFanout:
    def test_remote_map_cost_is_max_not_sum(self, tmp_path):
        """Cross-node fan-out runs one concurrent sub-query per node
        (reference mapReduce): with two remote nodes each answering in
        ~delay seconds, the query's wall time is ~max(delays), not the
        sum."""
        import time

        servers = make_cluster(tmp_path, 3)
        try:
            req("POST", f"{uri(servers[0])}/index/i", {})
            req("POST", f"{uri(servers[0])}/index/i/field/f", {})
            s0 = servers[0]
            cluster = s0.api.cluster
            shard_for = {}
            for shard in range(64):
                owner = cluster.shard_nodes("i", shard)[0].id
                shard_for.setdefault(owner, shard)
                if len(shard_for) == 3:
                    break
            assert {"n0", "n1", "n2"} <= set(shard_for)
            for node_id, shard in shard_for.items():
                col = shard * SHARD_WIDTH + 1
                req("POST", f"{uri(s0)}/index/i/query",
                    f"Set({col}, f=1)".encode(), content_type="text/plain")
            out = req("POST", f"{uri(s0)}/index/i/query",
                      b"Count(Row(f=1))", content_type="text/plain")
            assert out["results"][0] == 3

            client = s0.api.executor.cluster.client
            orig = client.query_node
            # generous delay: the threshold below leaves ~delay*0.8 of
            # budget for real HTTP/query overhead on a loaded CI machine
            delay = 1.0

            def slow(node_uri, *a, **k):
                time.sleep(delay)
                return orig(node_uri, *a, **k)

            client.query_node = slow
            try:
                t0 = time.monotonic()
                out = req("POST", f"{uri(s0)}/index/i/query",
                          b"Count(Row(f=1))", content_type="text/plain")
                wall = time.monotonic() - t0
            finally:
                client.query_node = orig
            assert out["results"][0] == 3
            # serial fan-out would cost >= 2*delay of pure sleep
            assert wall < 2 * delay * 0.9, f"fan-out not concurrent: {wall:.3f}s"
        finally:
            for s in servers:
                s.close()


class TestAsyncSelfJoin:
    def test_joiner_with_slow_peer_serves_status_and_gates_queries(self, tmp_path):
        """Self-join fetch runs as a background job: while
        a slow peer drags the fragment fetch out, Server.open has already
        returned, the joiner answers /status as RESIZING, and queries
        gate on wait_until_normal — then complete correctly once the
        fetch finishes."""
        import threading
        import time

        from pilosa_tpu.parallel.client import InternalClient

        servers = make_cluster(tmp_path, 1)
        late = None
        orig = InternalClient.fragment_data
        started = threading.Event()
        release = threading.Event()

        def slow_fragment_data(self, *a, **k):
            started.set()
            release.wait(30)
            return orig(self, *a, **k)

        try:
            req("POST", f"{uri(servers[0])}/index/i", {})
            req("POST", f"{uri(servers[0])}/index/i/field/f", {})
            cols = [s * SHARD_WIDTH + 3 for s in range(16)]
            req("POST", f"{uri(servers[0])}/index/i/field/f/import",
                {"rows": [1] * len(cols), "columns": cols})

            InternalClient.fragment_data = slow_fragment_data
            t0 = time.monotonic()
            late = Server(ServerConfig(
                data_dir=str(tmp_path / "late"), port=0, name="n9",
                seeds=[uri(servers[0])], anti_entropy_interval=0,
                heartbeat_interval=0, use_mesh=False,
            )).open()
            open_wall = time.monotonic() - t0
            assert started.wait(10), "join fetch never started"
            # open() returned while the fetch is still blocked
            assert release.is_set() is False
            assert open_wall < 10
            # /status answers mid-fetch and reports the gate
            st = req("GET", f"{uri(late)}/status")
            assert st["state"] == "RESIZING"

            # a query against the joiner gates (does not error, does not
            # return early with partial data)
            result = {}

            def query():
                out = req("POST", f"{uri(late)}/index/i/query",
                          b"Count(Row(f=1))")
                result["count"] = out["results"][0]

            qt = threading.Thread(target=query, daemon=True)
            qt.start()
            qt.join(timeout=0.8)
            assert qt.is_alive(), "query should gate while RESIZING"

            release.set()
            qt.join(timeout=30)
            assert not qt.is_alive()
            assert result["count"] == 16
            assert late.api.cluster.wait_until_normal(10)
            assert req("GET", f"{uri(late)}/status")["state"] == "NORMAL"
        finally:
            InternalClient.fragment_data = orig
            release.set()
            for s in servers + ([late] if late else []):
                s.close()

    def test_normal_command_deferred_while_local_fetch_in_flight(self):
        """A coordinator's NORMAL broadcast arriving while this node is
        still pulling fragments must not un-gate queries mid-fetch; the
        last local fetch job restores the commanded state."""
        from pilosa_tpu.parallel.cluster import Cluster, Node

        c = Cluster(Node("n0", "http://localhost:1"))
        c._begin_local_fetch()
        assert c.state == "RESIZING"
        c.handle_message({"type": "cluster-state", "state": "NORMAL"})
        assert c.state == "RESIZING"  # deferred, not stomped
        c._end_local_fetch()
        assert c.state == "NORMAL"  # restored on last job exit

        # and a RESIZING command outlives the local fetch
        c._begin_local_fetch()
        c.handle_message({"type": "cluster-state", "state": "RESIZING"})
        c._end_local_fetch()
        assert c.state == "RESIZING"
        c.handle_message({"type": "cluster-state", "state": "NORMAL"})
        assert c.state == "NORMAL"


class TestFragmentNodesRoute:
    def test_fragment_nodes_lists_owners(self, tmp_path):
        servers = make_cluster(tmp_path, 3, replica_n=2)
        try:
            req("POST", f"{uri(servers[0])}/index/i", {})
            out = req("GET",
                      f"{uri(servers[0])}/internal/fragment/nodes"
                      f"?index=i&shard=5")
            ids = {n["id"] for n in out}
            assert len(ids) == 2  # replicaN owners
            want = {n.id for n in
                    servers[0].api.cluster.shard_nodes("i", 5)}
            assert ids == want
        finally:
            for s in servers:
                s.close()


class TestMutexImportRouting:
    def test_clustered_mutex_import_preserves_single_value(self, tmp_path):
        """Routed mutex imports must NOT ride the roaring union route:
        the receiver would keep a column's previous row set while the
        sender's replica cleared it — replica divergence plus a broken
        single-value invariant on the remote owner."""
        servers = make_cluster(tmp_path, 3, replica_n=2)
        try:
            req("POST", f"{uri(servers[0])}/index/i", {})
            req("POST", f"{uri(servers[0])}/index/i/field/m",
                {"options": {"type": "mutex"}})
            cols = [s * SHARD_WIDTH + 5 for s in range(6)]
            req("POST", f"{uri(servers[0])}/index/i/field/m/import",
                {"rows": [1] * len(cols), "columns": cols})
            # re-import the same columns under a DIFFERENT row via a
            # different node: every replica must move them, not union
            req("POST", f"{uri(servers[1])}/index/i/field/m/import",
                {"rows": [2] * len(cols), "columns": cols})
            for s in servers:
                url = f"{uri(s)}/index/i/query"
                out = req("POST", url, b"Count(Row(m=1))")
                assert out == {"results": [0]}, s.config.name
                out = req("POST", url, b"Row(m=2)")
                assert out["results"][0]["columns"] == cols, s.config.name
            # and the fragments themselves agree on every replica
            for s in servers:
                f = s.holder.index("i").field("m")
                view = f.view("standard")
                if view is None:
                    continue
                for shard in range(6):
                    frag = view.fragment(shard)
                    if frag is None:
                        continue
                    assert not frag.contains(1, 5), (s.config.name, shard)
        finally:
            for s in servers:
                s.close()
