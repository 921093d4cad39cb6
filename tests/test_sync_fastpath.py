"""Anti-entropy & resize data-plane fast path (docs/OPERATIONS.md):

- batched sync manifests (one RTT diffs a whole index against a peer)
  and multi-block deltas, byte-identical to the per-fragment r5 path;
- the RTT-count oracle (N fragments diffed in ≤ 2 fragment-sync RTTs
  per peer);
- compression negotiation + identity fallback on fragment/delta bodies;
- token-bucket pacer bounds (rate, inflight, the paced-sleep counter);
- conflict-aware merge rules (mutex/BSI) preserved through the new path;
- mixed-version cluster: one node forced JSON-only AND old-wire under a
  randomized workload;
- a ≥30-min mixed read+write+churn+repair soak with flat-RSS /
  flat-residency oracles behind the ``slow`` marker.
"""

import os
import threading
import time

import numpy as np
import pytest

from cluster_helpers import make_cluster, req, uri
from pilosa_tpu.parallel.pacer import RepairPacer
from pilosa_tpu.shardwidth import SHARD_WIDTH
from pilosa_tpu.wire.serializer import (
    decode_block_frames,
    encode_block_frames,
)


def _diverge(server, field="f", shards=(0,), rows=3, bits=200, seed=5,
             index="i"):
    """Write extra bits straight into one node's storage (replication
    bypassed) — the seeded divergence anti-entropy must heal."""
    rng = np.random.default_rng(seed)
    fld = server.holder.index(index).field(field)
    total = 0
    for shard in shards:
        frag = fld.view("standard", create=True).fragment(
            shard, create=True
        )
        r = np.repeat(np.arange(rows, dtype=np.uint64), bits)
        p = np.concatenate([
            rng.choice(SHARD_WIDTH, bits, replace=False).astype(np.uint64)
            for _ in range(rows)
        ])
        before = frag.count()
        frag.bulk_import(r, p)
        total += frag.count() - before
    return total


def _seed_schema(node0, with_index=True):
    if with_index:
        req("POST", f"{uri(node0)}/index/i",
            {"options": {"trackExistence": False}})
    req("POST", f"{uri(node0)}/index/i/field/f", {})


# ---------------------------------------------------------------- framing


def test_block_frame_roundtrip():
    payloads = [b"", b"x", b"roaring" * 100, bytes(range(256))]
    data = encode_block_frames(payloads)
    assert decode_block_frames(data) == payloads
    assert decode_block_frames(b"") == []


def test_block_frame_truncation_raises():
    data = encode_block_frames([b"abcdef", b"ghi"])
    with pytest.raises(ValueError):
        decode_block_frames(data[:-1])  # torn payload
    with pytest.raises(ValueError):
        decode_block_frames(data + b"\x00\x00")  # torn header


# ------------------------------------------------------- manifest + deltas


def test_manifest_matches_per_fragment_blocks(tmp_path):
    """The batched manifest is exactly the union of the per-fragment
    blocks GETs it replaces (same checksums, same inventory)."""
    servers = make_cluster(tmp_path, 2, replica_n=2)
    try:
        _seed_schema(servers[0])
        _diverge(servers[0], shards=(0, 2, 5), seed=7)
        client = servers[1].api.cluster.client
        manifest = dict(
            ((f, v, s), blocks)
            for f, v, s, blocks in client.sync_manifest(uri(servers[0]), "i")
        )
        f0 = servers[0].holder.index("i").field("f")
        for shard in (0, 2, 5):
            per_fragment = client.fragment_blocks(
                uri(servers[0]), "i", "f", "standard", shard
            )
            assert manifest[("f", "standard", shard)] == per_fragment
            frag = f0.view("standard").fragment(shard)
            assert per_fragment == frag.blocks()
    finally:
        for s in servers:
            s.close()


def test_sync_blocks_multi_fragment_delta(tmp_path):
    """One POST returns every wanted block across several fragments, in
    flattened request order, as parsed bitmaps matching block_ids."""
    servers = make_cluster(tmp_path, 2, replica_n=2)
    try:
        _seed_schema(servers[0])
        _diverge(servers[0], shards=(0, 1), rows=250, bits=20, seed=9)
        f0 = servers[0].holder.index("i").field("f")
        client = servers[1].api.cluster.client
        # rows 0..249 span checksum blocks 0-2 (100 rows per block)
        want = [("f", "standard", 0, [0, 1, 2]),
                ("f", "standard", 1, [0, 2])]
        bitmaps = client.sync_blocks(uri(servers[0]), "i", want)
        assert len(bitmaps) == 5
        i = 0
        for field, view, shard, blocks in want:
            frag = f0.view(view).fragment(shard)
            for block in blocks:
                assert (bitmaps[i].to_ids().tolist()
                        == frag.block_ids(block).tolist()), (shard, block)
                i += 1
    finally:
        for s in servers:
            s.close()


def _legacy_mode(server, peer_uris):
    """Force the r5 per-fragment path against the given peers (the
    old-wire fallback): no manifest/delta routes, serial pass."""
    server.api.cluster.sync_workers = 1
    for peer in peer_uris:
        server.api.cluster.client._no_manifest_peers.add(peer)


def test_fastpath_byte_identical_to_legacy(tmp_path):
    """The correctness bar of the tentpole: the same seeded divergence
    repaired via the manifest/delta fast path and via the per-fragment
    legacy path produces byte-identical fragments."""
    snaps = {}
    for mode in ("fast", "legacy"):
        servers = make_cluster(tmp_path, 2, replica_n=2, prefix=mode)
        try:
            _seed_schema(servers[0])
            cols = [s * SHARD_WIDTH + 7 * c for s in range(4)
                    for c in range(30)]
            req("POST", f"{uri(servers[0])}/index/i/field/f/import",
                {"rows": [1] * len(cols), "columns": cols})
            added = _diverge(servers[0], shards=(0, 1, 3), rows=120,
                             bits=50, seed=11)
            if mode == "legacy":
                _legacy_mode(servers[1], [uri(servers[0])])
            repaired = servers[1].api.cluster.sync_holder()
            assert repaired["bits"] == added, mode
            f1 = servers[1].holder.index("i").field("f")
            f0 = servers[0].holder.index("i").field("f")
            snaps[mode] = [
                f1.view("standard").fragment(s).serialize_snapshot()
                for s in range(4)
            ]
            for s in range(4):
                assert (f1.view("standard").fragment(s).blocks()
                        == f0.view("standard").fragment(s).blocks()), s
        finally:
            for s in servers:
                s.close()
    assert snaps["fast"] == snaps["legacy"]


def test_rtt_count_oracle(tmp_path):
    """N fragments diff (and repair) in ≤ 2 fragment-sync RTTs per peer:
    one manifest GET + at most one multi-block delta POST — against the
    legacy path's 1 catalog + N blocks GETs + K block-data GETs."""
    n_shards = 12
    servers = make_cluster(tmp_path, 2, replica_n=2)
    try:
        _seed_schema(servers[0])
        cols = [s * SHARD_WIDTH + 3 * c
                for s in range(n_shards) for c in range(20)]
        req("POST", f"{uri(servers[0])}/index/i/field/f/import",
            {"rows": [1] * len(cols), "columns": cols})
        _diverge(servers[0], shards=(0, 4, 9), seed=13)

        sync_urls = []
        pool = servers[1].api.cluster.client.pool
        real = pool.request

        def counting(method, url, body=None, headers=None, timeout=None):
            if "/internal/sync/" in url or "/internal/fragment" in url:
                sync_urls.append(url)
            return real(method, url, body=body, headers=headers,
                        timeout=timeout)

        pool.request = counting
        try:
            repaired = servers[1].api.cluster.sync_holder()
        finally:
            pool.request = real
        assert repaired["bits"] > 0
        # one manifest + one delta POST per divergent fragment, and the
        # DIFF of all 12 fragments costs exactly the manifest: ≤ 2
        # fragment-sync RTTs per (divergence-free peer would be 1)
        manifests = [u for u in sync_urls if "/sync/manifest" in u]
        deltas = [u for u in sync_urls if "/sync/blocks" in u]
        legacy_style = [u for u in sync_urls if "/internal/fragment" in u]
        assert len(manifests) == 1
        assert 1 <= len(deltas) <= 3  # one per divergent fragment
        assert not legacy_style  # the per-fragment path never fired
    finally:
        for s in servers:
            s.close()


def test_no_divergence_pass_is_one_rtt_and_skips_recompute(tmp_path):
    """Zero divergence: the whole index diffs in ONE manifest RTT, and
    no fragment recomputes its checksum set after a peer that repaired
    nothing (the r5 pass re-hashed after every peer)."""
    servers = make_cluster(tmp_path, 2, replica_n=2)
    try:
        _seed_schema(servers[0])
        cols = [s * SHARD_WIDTH + c for s in range(6) for c in range(40)]
        req("POST", f"{uri(servers[0])}/index/i/field/f/import",
            {"rows": [1] * len(cols), "columns": cols})
        # settle both replicas, then instrument node1's fragments
        servers[1].api.cluster.sync_holder()
        f1 = servers[1].holder.index("i").field("f")
        calls = {"blocks": 0}
        frags = [f1.view("standard").fragment(s) for s in range(6)]
        originals = [f.blocks for f in frags]

        def wrap(frag, orig):
            def counted():
                calls["blocks"] += 1
                return orig()
            return counted

        for frag, orig in zip(frags, originals):
            frag.blocks = wrap(frag, orig)
        sync_urls = []
        pool = servers[1].api.cluster.client.pool
        real = pool.request

        def counting(method, url, body=None, headers=None, timeout=None):
            if "/internal/sync/" in url or "/internal/fragment" in url:
                sync_urls.append(url)
            return real(method, url, body=body, headers=headers,
                        timeout=timeout)

        pool.request = counting
        try:
            repaired = servers[1].api.cluster.sync_holder()
        finally:
            pool.request = real
            for frag, orig in zip(frags, originals):
                frag.blocks = orig
        assert repaired["bits"] == 0
        assert len(sync_urls) == 1 and "/sync/manifest" in sync_urls[0]
        # exactly one local checksum walk per fragment, zero post-peer
        # recomputes (and the walk itself is served by the memo)
        assert calls["blocks"] == len(frags)
    finally:
        for s in servers:
            s.close()


def test_blocks_memo_invalidates_on_write(tmp_path):
    """fragment.blocks() memoizes against the mutation counter: same
    object until a write, fresh (and correct) after."""
    from pilosa_tpu.storage import Holder

    holder = Holder(str(tmp_path / "m")).open()
    try:
        frag = (holder.create_index("i").create_field("f")
                .view("standard", create=True).fragment(0, create=True))
        frag.bulk_import(np.array([1, 1], np.uint64),
                         np.array([5, 9], np.uint64))
        first = frag.blocks()
        assert frag.blocks() is first  # memo hit
        frag.set_bit(1, 700)
        second = frag.blocks()
        assert second is not first
        assert second != first
    finally:
        holder.close()


def test_unknown_index_answers_empty_not_404(tmp_path):
    """A peer lagging on a schema broadcast answers an EMPTY manifest /
    empty delta payloads for an index it doesn't know — NOT a 404, which
    the client would misread as 'route missing' and permanently demote
    the peer to the per-fragment legacy path."""
    servers = make_cluster(tmp_path, 2, replica_n=2)
    try:
        client = servers[1].api.cluster.client
        assert client.sync_manifest(uri(servers[0]), "nope") == []
        assert client.supports_sync_manifest(uri(servers[0]))
        bitmaps = client.sync_blocks(
            uri(servers[0]), "nope", [("f", "standard", 0, [0, 1])]
        )
        assert [bm.count() for bm in bitmaps] == [0, 0]
        assert client.supports_sync_manifest(uri(servers[0]))
    finally:
        for s in servers:
            s.close()


def test_malformed_manifest_does_not_abort_pass(tmp_path):
    """One peer answering a malformed 200 manifest is skipped for the
    pass (logged), not allowed to abort repair against every peer."""
    servers = make_cluster(tmp_path, 2, replica_n=2)
    try:
        _seed_schema(servers[0])
        added = _diverge(servers[0], shards=(0,), seed=31)
        client = servers[1].api.cluster.client
        real = client.sync_manifest
        calls = {"n": 0}

        def flaky(uri_, index):
            calls["n"] += 1
            if calls["n"] == 1:
                raise ValueError("truncated body")  # not a ClientError
            return real(uri_, index)

        client.sync_manifest = flaky
        try:
            first = servers[1].api.cluster.sync_holder()
            second = servers[1].api.cluster.sync_holder()
        finally:
            client.sync_manifest = real
        assert first["bits"] == 0  # peer skipped, pass completed
        assert second["bits"] == added  # next pass heals
    finally:
        for s in servers:
            s.close()


# ------------------------------------------------------------- compression


def test_compression_negotiation_and_fallback(tmp_path):
    """Fragment payloads ride zlib Content-Encoding when (and only when)
    the client advertises it; bytes decode identically either way, and a
    plain client (no Accept-Encoding) gets identity bytes."""
    import urllib.request
    import zlib

    servers = make_cluster(tmp_path, 2, replica_n=2)
    try:
        _seed_schema(servers[0])
        _diverge(servers[0], shards=(0,), rows=40, bits=4000, seed=3)
        frag = (servers[0].holder.index("i").field("f")
                .view("standard").fragment(0))
        plain = frag.serialize_snapshot()
        client = servers[1].api.cluster.client
        url = (f"{uri(servers[0])}/internal/fragment/data"
               "?index=i&field=f&view=standard&shard=0")

        client.compress_repair = True
        resp = client._call("GET", url, headers=client._repair_headers(),
                            want_response=True)
        assert resp.headers.get("Content-Encoding") == "deflate"
        assert len(resp.data) < len(plain)
        assert zlib.decompress(resp.data) == plain
        # the public helper does the decode
        assert client.fragment_data(
            uri(servers[0]), "i", "f", "standard", 0) == plain

        client.compress_repair = False  # knob off: identity on the wire
        resp = client._call("GET", url, headers=client._repair_headers(),
                            want_response=True)
        assert resp.headers.get("Content-Encoding") is None
        assert resp.data == plain

        # a plain stdlib client (no Accept-Encoding) gets identity bytes
        with urllib.request.urlopen(url, timeout=30) as r:
            assert r.read() == plain

        # delta payloads negotiate the same way
        client.compress_repair = True
        bitmaps = client.sync_blocks(
            uri(servers[0]), "i", [("f", "standard", 0, [0])]
        )
        assert bitmaps[0].to_ids().tolist() == frag.block_ids(0).tolist()
    finally:
        for s in servers:
            s.close()


def test_json_only_peer_still_syncs(tmp_path):
    """Protobuf-less negotiation (the 406 fallback class): a peer forced
    JSON-only for manifests/deltas repairs identically."""
    servers = make_cluster(tmp_path, 2, replica_n=2)
    try:
        _seed_schema(servers[0])
        added = _diverge(servers[0], shards=(0, 2), seed=21)
        servers[1].api.cluster.client._json_only_peers.add(uri(servers[0]))
        repaired = servers[1].api.cluster.sync_holder()
        assert repaired["bits"] == added
        f0 = servers[0].holder.index("i").field("f")
        f1 = servers[1].holder.index("i").field("f")
        for s in (0, 2):
            assert (f1.view("standard").fragment(s).blocks()
                    == f0.view("standard").fragment(s).blocks())
    finally:
        for s in servers:
            s.close()


# ------------------------------------------------------------------- pacer


def test_pacer_rate_bounds_throughput():
    from pilosa_tpu.utils.stats import StatsClient

    stats = StatsClient()
    pacer = RepairPacer(max_bytes_per_sec=2_000_000, stats=stats)
    t0 = time.perf_counter()
    total = 0
    for _ in range(40):
        pacer.consume(65536)
        total += 65536
    elapsed = time.perf_counter() - t0
    # ~2.6 MB at 2 MB/s with a 1 s burst allowance: the post-burst
    # deficit (~0.3 s) must have been slept off
    expected_min = (total - pacer.burst) / pacer.rate
    assert expected_min > 0
    assert elapsed >= expected_min * 0.9
    assert pacer.paced_sleep_s > 0
    snap = stats.snapshot()["counters"]
    assert snap.get("repair_paced_sleep_ms", 0) > 0


def test_pacer_unpaced_is_free():
    pacer = RepairPacer()  # both knobs 0
    t0 = time.perf_counter()
    for _ in range(1000):
        pacer.consume(1 << 20)
    assert time.perf_counter() - t0 < 0.5
    assert pacer.paced_sleep_s == 0


def test_pacer_inflight_bound():
    pacer = RepairPacer(max_inflight=2)
    active = {"now": 0, "max": 0}
    lock = threading.Lock()

    def transfer():
        with pacer.slot():
            with lock:
                active["now"] += 1
                active["max"] = max(active["max"], active["now"])
            time.sleep(0.05)
            with lock:
                active["now"] -= 1

    threads = [threading.Thread(target=transfer) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(10)
    assert active["max"] <= 2


# ------------------------------------------------------------- merge rules


def test_merge_rules_preserved_mutex_and_bsi(tmp_path):
    """The conflict-aware repair semantics ride the fast path unchanged:
    mutex columns keep the LOCAL row; BSI columns are all-or-nothing."""
    servers = make_cluster(tmp_path, 2, replica_n=2)
    try:
        base = uri(servers[0])
        req("POST", f"{base}/index/i",
            {"options": {"trackExistence": False}})
        req("POST", f"{base}/index/i/field/m", {"options": {"type": "mutex"}})
        req("POST", f"{base}/index/i/field/v",
            {"options": {"type": "int", "min": 0, "max": 1000}})
        # replicated baseline: col 10 -> row 1, col 20 BSI 7 (both nodes)
        req("POST", f"{base}/index/i/query", b"Set(10, m=1)")
        req("POST", f"{base}/index/i/query", b"Set(20, v=7)")
        # node0-only divergence: col 10 moved to row 2 (mutex clears row
        # 1 locally); col 20 -> 999; col 30 fresh on node0 only
        f0m = servers[0].holder.index("i").field("m")
        f0m.set_bit(2, 10)
        f0v = servers[0].holder.index("i").field("v")
        f0v.set_value(20, 999)
        f0m.set_bit(0, 30)
        repaired = servers[1].api.cluster.sync_holder()
        assert repaired["bits"] >= 1
        f1m = servers[1].holder.index("i").field("m")
        f1v = servers[1].holder.index("i").field("v")
        frag1m = f1m.view("standard").fragment(0)
        # mutex: local row 1 wins over the peer's row 2; fresh col adopts
        assert frag1m.row_columns(1).tolist() == [10]
        assert 10 not in frag1m.row_columns(2).tolist()
        assert frag1m.row_columns(0).tolist() == [30]
        # BSI: locally existing value keeps ALL its planes
        assert f1v.value(20) == (7, True)
    finally:
        for s in servers:
            s.close()


# ------------------------------------------------------------------- knobs


def test_config_knobs_roundtrip_and_wiring(tmp_path):
    from pilosa_tpu.server import Server, ServerConfig

    cfg = ServerConfig.from_dict({
        "sync-workers": 3,
        "repair-max-bytes-per-sec": 12345,
        "repair-max-inflight": 2,
        "repair-compression": False,
    })
    assert cfg.sync_workers == 3
    assert cfg.repair_max_bytes_per_sec == 12345
    assert cfg.repair_max_inflight == 2
    assert cfg.repair_compression is False
    d = cfg.to_dict()
    assert d["sync-workers"] == 3
    assert d["repair-max-bytes-per-sec"] == 12345
    assert d["repair-max-inflight"] == 2
    assert d["repair-compression"] is False

    server = Server(ServerConfig(
        data_dir=str(tmp_path / "k"), port=0, name="k",
        anti_entropy_interval=0, heartbeat_interval=0, use_mesh=False,
        sync_workers=3, repair_max_bytes_per_sec=12345,
        repair_max_inflight=2, repair_compression=False,
    )).open()
    try:
        cluster = server.api.cluster
        assert cluster.sync_workers == 3
        assert cluster.client.pacer.rate == 12345
        assert cluster.client.pacer.max_inflight == 2
        assert cluster.client.compress_repair is False
    finally:
        server.close()


def test_sync_metrics_exported(tmp_path):
    """sync_manifest_* / sync_delta_blocks_* counters and the pass timer
    land on /metrics and /debug/vars after a repair."""
    servers = make_cluster(tmp_path, 2, replica_n=2)
    try:
        _seed_schema(servers[0])
        _diverge(servers[0], shards=(0,), seed=2)
        servers[1].api.cluster.sync_holder()
        metrics = req("GET", f"{uri(servers[1])}/metrics", raw=True).decode()
        assert "sync_manifest_fetches_total" in metrics
        assert "sync_delta_blocks_requests_total" in metrics
        assert "sync_delta_blocks_bytes_total" in metrics
        assert "sync_pass_seconds_count" in metrics
        dvars = req("GET", f"{uri(servers[1])}/debug/vars")
        assert dvars["counters"].get("sync_manifest_fetches", 0) >= 1
        assert "sync_pass" in dvars["distributions"]
        served = req("GET", f"{uri(servers[0])}/metrics",
                     raw=True).decode()
        assert "sync_manifest_served_total" in served
        assert "sync_delta_blocks_served_total" in served
    finally:
        for s in servers:
            s.close()


# ----------------------------------------------------------- mixed version


def _force_old_wire(servers, victim):
    """Make ``victim`` look like an old-wire, JSON-only node to every
    peer (and make its own client JSON-only): manifests/deltas 404-class
    fallback + protobuf 406 fallback, in both directions."""
    vuri = uri(victim)
    for s in servers:
        if s is victim:
            for other in servers:
                if other is not victim:
                    victim.api.cluster.client._json_only_peers.add(
                        uri(other))
                    victim.api.cluster.client._no_manifest_peers.add(
                        uri(other))
        else:
            s.api.cluster.client._json_only_peers.add(vuri)
            s.api.cluster.client._no_manifest_peers.add(vuri)


def test_mixed_version_cluster_randomized(tmp_path):
    """A 3-node cluster with one node forced JSON-only AND old-wire (no
    manifest/delta routes) under the randomized property workload —
    manifest/delta negotiation and the r4
    proto renumbering cannot corrupt a mixed deployment. Every node must
    answer the full oracle after writes routed through ALL nodes and
    repair passes run from every node."""
    from test_property import (
        INT_MAX,
        INT_MIN,
        MUTEX_ROWS,
        ROWS,
        Oracle,
        random_workload,
    )

    rng = np.random.default_rng(42)
    servers = make_cluster(tmp_path, 3, replica_n=2, prefix="mixed")
    try:
        victim = servers[1]
        _force_old_wire(servers, victim)
        base = uri(servers[0])
        req("POST", f"{base}/index/i", {"options": {"trackExistence": True}})
        req("POST", f"{base}/index/i/field/f", {})
        req("POST", f"{base}/index/i/field/v",
            {"options": {"type": "int", "min": INT_MIN, "max": INT_MAX}})
        req("POST", f"{base}/index/i/field/m", {"options": {"type": "mutex"}})
        req("POST", f"{base}/index/i/field/b", {"options": {"type": "bool"}})
        req("POST", f"{base}/index/i/field/t",
            {"options": {"type": "time", "timeQuantum": "YMDH"}})

        class HttpEx:
            def execute(self, index, pql):
                s = servers[int(rng.integers(0, len(servers)))]
                return req(
                    "POST", f"{uri(s)}/index/{index}/query", pql.encode()
                )["results"]

        oracle = Oracle()
        random_workload(rng, HttpEx(), "i", oracle, n_ops=80)
        # repair from every node (victim uses the per-fragment path, the
        # others use manifests against each other and fall back for it)
        for s in servers:
            s.api.cluster.sync_holder()
        for s in servers:
            url = f"{uri(s)}/index/i/query"
            for row in ROWS:
                out = req("POST", url, f"Count(Row(f={row}))".encode())
                assert out["results"] == [len(oracle.sets[row])], (
                    s.config.name, row)
            out = req("POST", url, b"Row(f=1)")
            assert out["results"][0]["columns"] == sorted(oracle.sets[1])
            for row in MUTEX_ROWS:
                out = req("POST", url, f"Count(Row(m={row}))".encode())
                assert out["results"] == [len(oracle.mutex_row(row))]
            if oracle.values:
                out = req("POST", url, b'Sum(field="v")')
                assert out["results"][0] == {
                    "value": sum(oracle.values.values()),
                    "count": len(oracle.values),
                }, s.config.name
        # the old-wire fallback actually engaged: peers marked the victim
        for s in servers:
            if s is not victim:
                assert uri(victim) in (
                    s.api.cluster.client._no_manifest_peers)
    finally:
        for s in servers:
            s.close()


# -------------------------------------------------------------------- soak


def _rss_kb() -> int:
    with open("/proc/self/status") as f:
        for line in f:
            if line.startswith("VmRSS:"):
                return int(line.split()[1])
    return 0


@pytest.mark.slow
def test_maintenance_soak_flat_rss_and_residency(tmp_path):
    """≥30-min (env-tunable) mixed read+write+churn+repair soak:
    a replicated cluster serves queries and writes
    while a third node joins and leaves repeatedly and anti-entropy
    passes run throughout. Oracles: zero errors, exact counts at every
    checkpoint, flat RSS (the median of the last quarter within 25% + a
    32 MiB allowance of the first quarter's), and flat device-residency
    bytes."""
    from cluster_helpers import join_node
    from pilosa_tpu.storage.residency import global_row_cache

    duration = float(os.environ.get("PILOSA_SOAK_SECONDS", "1800"))
    servers = make_cluster(tmp_path, 2, replica_n=2, prefix="soak")
    third = None
    errors: list = []
    rss_samples: list[int] = []
    res_samples: list[int] = []
    try:
        base = uri(servers[0])
        req("POST", f"{base}/index/i", {"options": {"trackExistence": False}})
        req("POST", f"{base}/index/i/field/f", {})
        rng = np.random.default_rng(99)
        written: set[int] = set()
        deadline = time.monotonic() + duration
        round_no = 0
        while time.monotonic() < deadline:
            round_no += 1
            live = servers + ([third] if third is not None else [])
            try:
                # writes through a random live node
                cols = sorted(
                    int(c) for c in rng.integers(0, 4 * SHARD_WIDTH, 40)
                )
                target = live[int(rng.integers(0, len(live)))]
                req("POST", f"{uri(target)}/index/i/field/f/import",
                    {"rows": [1] * len(cols), "columns": cols})
                written.update(cols)
                # reads from every node must agree with the model
                for s in live:
                    out = req("POST", f"{uri(s)}/index/i/query",
                              b"Count(Row(f=1))")
                    if out["results"] != [len(written)]:
                        errors.append(
                            f"round {round_no}: {s.config.name} counted "
                            f"{out['results']} want {len(written)}"
                        )
                # divergence + repair: extra ROW-0 bits on node0 only
                # (row 1 stays the exact import-driven model), on a
                # shard node0 OWNS — anti-entropy syncs among a shard's
                # replicas, so divergence parked on a non-owner is
                # invisible to repair by design. Every live node runs a
                # pass; all must then AGREE on the divergent row.
                owned = [s for s in range(4)
                         if servers[0].api.cluster.owns_shard("i", s)]
                _diverge(
                    servers[0],
                    shards=(owned[int(rng.integers(0, len(owned)))],),
                    rows=1, bits=30, seed=round_no,
                )
                for s in live:
                    s.api.cluster.sync_holder()
                row0 = {
                    s.config.name: req(
                        "POST", f"{uri(s)}/index/i/query",
                        b"Count(Row(f=0))",
                    )["results"]
                    for s in live
                }
                if len(set(map(str, row0.values()))) != 1:
                    errors.append(
                        f"round {round_no}: post-repair divergence "
                        f"{row0}"
                    )
                # membership churn every few rounds
                if round_no % 5 == 0:
                    if third is None:
                        third = join_node(
                            tmp_path, servers[0], replica_n=2,
                            name="soak2", prefix=f"soak2-{round_no}",
                        )
                        if not third.api.cluster.wait_until_normal(60):
                            errors.append(f"round {round_no}: join stuck")
                    else:
                        third.api.cluster.leave()
                        third.close()
                        third = None
                        if not servers[0].api.cluster.wait_until_normal(60):
                            errors.append(f"round {round_no}: leave stuck")
            except Exception as e:  # noqa: BLE001 — soak oracle
                errors.append(f"round {round_no}: {e!r}")
                break
            rss_samples.append(_rss_kb())
            res_samples.append(
                int(global_row_cache().metrics().get(
                    "residency_bytes_used", 0))
            )
        assert not errors, errors[:5]
        assert round_no >= 4, "soak too short to judge slopes"
        q = max(1, len(rss_samples) // 4)
        first_rss = float(np.median(rss_samples[:q]))
        last_rss = float(np.median(rss_samples[-q:]))
        assert last_rss <= first_rss * 1.25 + 32 * 1024, (
            f"RSS slope: {first_rss} kB -> {last_rss} kB"
        )
        first_res = float(np.median(res_samples[:q]) or 0)
        last_res = float(np.median(res_samples[-q:]) or 0)
        assert last_res <= max(first_res * 1.5, first_res + (64 << 20)), (
            f"residency slope: {first_res} -> {last_res} bytes"
        )
    finally:
        if third is not None:
            third.close()
        for s in servers:
            s.close()
