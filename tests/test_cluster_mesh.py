"""Cluster-of-meshes topology: HTTP cluster across nodes, each node
wrapping a DistExecutor over the (virtual) device mesh.

This is the production shape wired at server.py _wire_cluster — ICI
collectives inside a node, HTTP/DCN between nodes (SURVEY.md §2.4) — and
before round 5 it was never exercised by CI: every cluster test passed
use_mesh=False. Covers query fan-out, a mid-flight resize, anti-entropy
repair feeding the mesh executor, and pipelined submit on the topology.
"""

import functools

from cluster_helpers import join_node, make_cluster, req, seed, uri

make_mesh_cluster = functools.partial(
    make_cluster, use_mesh=True, prefix="mnode"
)


class TestMeshClusterFanout:
    def test_every_node_wraps_a_mesh_and_agrees(self, tmp_path):
        """Each node's local executor is a DistExecutor; cross-node
        queries from every node produce the oracle answers."""
        from pilosa_tpu.parallel.dist import DistExecutor

        servers = make_mesh_cluster(tmp_path, 3)
        try:
            for s in servers:
                assert isinstance(s.api.executor.local, DistExecutor)
                assert s.api.executor.local.mesh.size == 8
            seed(servers[0])
            for s in servers:
                url = f"{uri(s)}/index/i/query"
                assert req("POST", url, b"Count(Row(f=1))") == {"results": [24]}
                assert req(
                    "POST", url, b"Count(Intersect(Row(f=1), Row(f=2)))"
                ) == {"results": [12]}
                out = req("POST", url, b"TopN(f, n=2)")
                assert out["results"][0] == [
                    {"id": 1, "count": 24}, {"id": 2, "count": 12},
                ]
                out = req("POST", url, b'Sum(Row(f=1), field="v")')
                assert out["results"][0] == {
                    "value": sum((s + 1) * 7 for s in range(6)), "count": 6,
                }
                out = req(
                    "POST", url,
                    b"GroupBy(Rows(f), having=Condition(count > 12))",
                )
                assert out["results"][0] == [
                    {"group": [{"field": "f", "rowID": 1}], "count": 24}
                ]
        finally:
            for s in servers:
                s.close()

    def test_pipelined_submit_on_mesh_cluster(self, tmp_path):
        """ClusterExecutor.submit over mesh-backed nodes: a whole stream
        submitted before any resolve, results equal eager execute."""
        servers = make_mesh_cluster(tmp_path, 2)
        try:
            seed(servers[0])
            ex = servers[1].api.executor
            queries = [
                "Count(Row(f=1))", "Union(Row(f=1), Row(f=2))",
                'Max(field="v")', "TopN(f, n=2)", "Rows(f)",
                "Count(Not(Row(f=2)))",
            ]
            want = [ex.execute("i", q)[0] for q in queries]
            defs = [ex.submit("i", q)[0] for q in queries]
            got = [d.result() for d in defs]
            from pilosa_tpu.executor.result import result_to_json

            for q, g, w in zip(queries, got, want):
                assert result_to_json(g) == result_to_json(w), q
        finally:
            for s in servers:
                s.close()


class TestMeshClusterResize:
    def test_join_resize_with_mesh_nodes(self, tmp_path):
        """A third mesh-backed node joins a live 2-node mesh cluster;
        after the resize it owns shards, holds their data, and serves
        correct cluster-wide queries."""
        servers = make_mesh_cluster(tmp_path, 2)
        try:
            seed(servers[0], n_shards=8)
            # prime both nodes' shard-universe poll caches BEFORE the
            # join: the post-cleanup re-check below must prove a node
            # still covers its formerly-local shards from its own
            # metadata when the poll cache predates the resize
            for s in servers:
                s.api.executor._all_shards("i")
            late = join_node(tmp_path, servers[0], use_mesh=True,
                             name="m9", prefix="mlate")
            servers.append(late)
            assert late.api.cluster.wait_until_normal(30)
            owned = [s for s in range(8)
                     if late.api.cluster.owns_shard("i", s)]
            assert owned, "ring should assign the new mesh node shards"
            view = late.holder.index("i").field("f").view("standard")
            for shard in owned:
                frag = view.fragment(shard)
                assert frag is not None and frag.contains(1, 100), shard
            for s in servers:
                out = req("POST", f"{uri(s)}/index/i/query",
                          b"Count(Row(f=1))")
                assert out == {"results": [32]}, s.api.cluster.local.id
            # Deterministic post-cleanup coverage (the async cleanup may
            # or may not have landed by the queries above): prime every
            # node's shard-universe poll cache, force the cleanup
            # everywhere, and re-check — a node whose formerly-local
            # fragments were just deleted must still fan out over the
            # full universe from its own metadata (regression: it lost
            # them whenever the poll cache predated the resize).
            members = sorted(servers[0].api.cluster.nodes)
            for s in servers:
                s.api.cluster.cleanup_unowned(members)
            for s in servers:
                out = req("POST", f"{uri(s)}/index/i/query",
                          b"Count(Row(f=1))")
                assert out == {"results": [32]}, (
                    "post-cleanup", s.api.cluster.local.id)
        finally:
            for s in servers:
                s.close()


class TestMeshClusterAntiEntropy:
    def test_repair_invalidates_mesh_residency(self, tmp_path):
        """Anti-entropy repair writes bits into a replica's fragments;
        a mesh executor that had already CACHED the repaired fragment's
        words on-device must serve the post-repair truth, not the stale
        resident copy."""
        servers = make_mesh_cluster(tmp_path, 2, replica_n=2)
        try:
            req("POST", f"{uri(servers[0])}/index/i", {})
            req("POST", f"{uri(servers[0])}/index/i/field/f", {})
            req("POST", f"{uri(servers[0])}/index/i/query", b"Set(1, f=1)")
            # warm BOTH nodes' mesh residency with the pre-divergence row
            for s in servers:
                out = req("POST", f"{uri(s)}/index/i/query",
                          b"Count(Row(f=1))")
                assert out == {"results": [1]}
            # diverge node0 directly, then let node1 pull the delta
            frag0 = (servers[0].holder.index("i").field("f")
                     .view("standard").fragment(0, create=True))
            frag0.set_bit(1, 999)
            repaired = servers[1].api.cluster.sync_holder()
            assert repaired["bits"] >= 1
            frag1 = (servers[1].holder.index("i").field("f")
                     .view("standard").fragment(0))
            assert frag1.contains(1, 999)
            # node1's mesh executor must see the repaired bit (query
            # routes shard 0 to a local mesh evaluation on either node)
            out = req("POST", f"{uri(servers[1])}/index/i/query",
                      b"Count(Row(f=1))")
            assert out == {"results": [2]}
            out = req("POST", f"{uri(servers[1])}/index/i/query",
                      b"Row(f=1)")
            assert out["results"][0]["columns"] == [1, 999]
        finally:
            for s in servers:
                s.close()
