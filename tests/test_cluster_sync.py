"""Cluster maintenance tests: attr sync, translate tailing, node-leave
resize, statsd emission."""

import socket

import pytest

from pilosa_tpu.shardwidth import SHARD_WIDTH
from tests.test_cluster import make_cluster, req, uri


def test_attr_sync_between_nodes(tmp_path):
    servers = make_cluster(tmp_path, 2)
    try:
        req("POST", f"{uri(servers[0])}/index/i", {})
        req("POST", f"{uri(servers[0])}/index/i/field/f", {})
        # attrs written directly on node0's stores only (diverged state)
        servers[0].holder.index("i").field("f").row_attrs.set_attrs(3, {"a": 1})
        servers[0].holder.index("i").column_attrs.set_attrs(9, {"b": 2})
        repaired = servers[1].api.cluster.sync_holder()
        assert repaired["attr_blocks"] >= 2
        assert servers[1].holder.index("i").field("f").row_attrs.attrs(3) == {"a": 1}
        assert servers[1].holder.index("i").column_attrs.attrs(9) == {"b": 2}
    finally:
        for s in servers:
            s.close()


def test_translate_tailing(tmp_path):
    servers = make_cluster(tmp_path, 2)
    try:
        # keyed writes translate on the coordinator; the replica's local
        # store learns the assignments by tailing the log
        req("POST", f"{uri(servers[0])}/index/users",
            {"options": {"keys": True}})
        req("POST", f"{uri(servers[0])}/index/users/field/likes",
            {"options": {"keys": True}})
        coord_id = servers[0].api.cluster.coordinator.id
        coord = next(s for s in servers if s.api.cluster.local.id == coord_id)
        replica = next(s for s in servers if s is not coord)
        req("POST", f"{uri(coord)}/index/users/query",
            b'Set("alice", likes="pizza")')
        replica.api.cluster.sync_translate()
        from pilosa_tpu.storage.translate import column_namespace, row_namespace

        # replica's local store mirrors the coordinator's assignments
        # (either tailed now or mirrored during the routed write)
        assert replica.holder.translate.translate(
            column_namespace("users"), ["alice"]
        ) == [0]
        assert replica.holder.translate.translate(
            row_namespace("users", "likes"), ["pizza"]
        ) == [0]
        # keyed reads work from the replica
        out = req("POST", f"{uri(replica)}/index/users/query",
                  b'Row(likes="pizza")')
        assert out["results"][0]["keys"] == ["alice"]
    finally:
        for s in servers:
            s.close()


def test_node_leave_triggers_reown(tmp_path):
    servers = make_cluster(tmp_path, 3, replica_n=2)
    try:
        req("POST", f"{uri(servers[0])}/index/i", {})
        req("POST", f"{uri(servers[0])}/index/i/field/f", {})
        cols = [s * SHARD_WIDTH + 2 for s in range(8)]
        req("POST", f"{uri(servers[0])}/index/i/field/f/import",
            {"rows": [1] * len(cols), "columns": cols})
        # node 2 leaves gracefully
        leaver = servers[2]
        leaver.api.cluster.leave()
        for s in servers[:2]:
            assert "n2" not in {
                n["id"] for n in req("GET", f"{uri(s)}/status")["nodes"]
            }
        leaver.close()
        # all data still queryable from the survivors
        out = req("POST", f"{uri(servers[0])}/index/i/query", b"Count(Row(f=1))")
        assert out["results"] == [8]
        out = req("POST", f"{uri(servers[1])}/index/i/query", b"Count(Row(f=1))")
        assert out["results"] == [8]
    finally:
        for s in servers[:2]:
            s.close()


def test_statsd_datagrams():
    from pilosa_tpu.utils.stats import StatsdStatsClient

    sink = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    sink.bind(("127.0.0.1", 0))
    sink.settimeout(2)
    port = sink.getsockname()[1]
    client = StatsdStatsClient("127.0.0.1", port)
    client.count("queries", 1, {"call": "Count"})
    client.gauge("resident_rows", 42)
    client.timing("query", 0.005)
    got = {sink.recv(1024).decode() for _ in range(3)}
    assert "pilosa_tpu.queries:1|c|#call:Count" in got
    assert "pilosa_tpu.resident_rows:42|g" in got
    assert any(g.startswith("pilosa_tpu.query:5") and g.endswith("|ms") for g in got)
    # in-memory registry still fed
    assert "queries" in client.prometheus_text()
    sink.close()


def test_block_repair_is_binary_and_compact(tmp_path):
    """Anti-entropy block repair moves roaring bytes, not JSON int lists:
    a dense 100-row block transfers ~O(bitmap bytes)."""
    import numpy as np

    servers = make_cluster(tmp_path, 2, replica_n=2)
    try:
        req("POST", f"{uri(servers[0])}/index/i", {})
        req("POST", f"{uri(servers[0])}/index/i/field/f", {})
        # diverged dense state written directly on node0's storage only:
        # 20 rows at 50% container density in checksum block 0
        f0 = servers[0].holder.index("i").field("f")
        frag0 = f0.view("standard", create=True).fragment(0, create=True)
        rng = np.random.default_rng(5)
        per_row = 30000
        rows = np.repeat(np.arange(20, dtype=np.uint64), per_row)
        poss = np.concatenate([
            rng.choice(65536, per_row, replace=False).astype(np.uint64)
            for _ in range(20)
        ])
        frag0.bulk_import(rows, poss)
        n_bits = frag0.count()
        assert n_bits == 20 * per_row

        # the other node must own shard 0 too (replica_n=2 in make_cluster)
        from pilosa_tpu.parallel.client import InternalClient

        client = InternalClient()
        raw = client._call(
            "GET",
            f"{uri(servers[0])}/internal/fragment/block/data"
            "?index=i&field=f&view=standard&shard=0&block=0",
            raw=True,
        )
        # dense data: roaring bitmap containers ~= bits/8 bytes; the old
        # JSON int lists were ~20 bytes per bit
        assert len(raw) < 0.5 * n_bits  # < 0.5 byte/bit on the wire

        repaired = servers[1].api.cluster.sync_holder()
        assert repaired["bits"] == n_bits
        f1 = servers[1].holder.index("i").field("f")
        frag1 = f1.view("standard").fragment(0)
        assert frag1.count() == n_bits
        assert frag1.blocks() == frag0.blocks()
    finally:
        for s in servers:
            s.close()
