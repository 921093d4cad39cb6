"""Query cost plane (ISSUE 8): PQL PROFILE, per-tenant usage
accounting, per-shard heat telemetry, and SLO burn-rate monitoring.

Covers the tentpole end to end: single-node and 3-node stitched
profiles (with the span-tree reconciliation oracle), the tenant ledger
+ /debug/tenants top-K view, the heat map's skewed-workload ranking and
decay, the SLO engine's burst-flip behavior, knob roundtrips, and the
/metrics exposition of the new families.
"""

import json
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from tests.cluster_helpers import make_cluster, req, seed, uri

from pilosa_tpu.qos.slo import SLOEngine, SLOObjective
from pilosa_tpu.server import Server, ServerConfig
from pilosa_tpu.storage.heat import HeatMap, global_heat
from pilosa_tpu.utils.cost import (
    CostLedger,
    cost_enabled,
    current_cost,
    set_cost_enabled,
)


@pytest.fixture(autouse=True)
def _fresh_cost_plane():
    """Cost plane on + empty global heat for every test (the heat map
    is process-global like the tracer)."""
    set_cost_enabled(True)
    global_heat().clear()
    yield
    set_cost_enabled(True)
    global_heat().clear()


@pytest.fixture()
def server(tmp_path):
    s = Server(ServerConfig(
        data_dir=str(tmp_path / "d"), port=0, anti_entropy_interval=0,
        heartbeat_interval=0,
    )).open()
    yield s
    s.close()


def _seed_one(s: Server, index="i", n_shards=2):
    from pilosa_tpu.shardwidth import SHARD_WIDTH
    from pilosa_tpu.storage.view import VIEW_STANDARD

    idx = s.holder.create_index(index)
    f = idx.create_field("f")
    for shard in range(n_shards):
        frag = f.view(VIEW_STANDARD, create=True).fragment(
            shard, create=True)
        frag.bulk_import(
            np.array([1, 1, 1, 2, 2], np.uint64),
            np.array([10, 11, 12, 10, 11], np.uint64),
        )
    s.api.cluster.note_local_shards(index, list(range(n_shards)))


def _post(s, path, body=b""):
    return req("POST", f"{uri(s)}{path}", body=body)


# ------------------------------------------------------------- PROFILE


def test_profile_single_node_structure(server):
    _seed_one(server)
    out = _post(server, "/index/i/query?profile=true",
                b"Count(Intersect(Row(f=1), Row(f=2)))")
    assert out["results"] == [4]
    prof = out["profile"]
    assert prof["node"] == server.api.cluster.local.id
    assert prof["index"] == "i"
    (call,) = prof["calls"]
    assert call["name"] == "Count"
    # AST children mirror the parsed tree
    (inter,) = call["children"]
    assert inter["name"] == "Intersect"
    assert [c["name"] for c in inter["children"]] == ["Row", "Row"]
    # measured counters: fresh server → residency misses decode roaring
    # containers; the per-leaf records carry field + container kinds
    assert call["deviceMs"] > 0
    assert call["dispatches"] >= 1
    assert call["shards"] == 2
    totals = prof["totals"]
    assert totals["rowCacheMisses"] > 0
    assert totals["bytesMoved"] > 0
    containers = totals["containers"]
    assert containers["array"] + containers["bitmap"] + containers["run"] > 0
    leaves = call["leaves"]
    assert {l["field"] for l in leaves} == {"f"}
    assert sorted(l["row"] for l in leaves) == [1, 2]


def test_profile_repeat_hits_caches(server):
    _seed_one(server)
    q = b"Count(Row(f=1))"
    _post(server, "/index/i/query?profile=true", q)
    out = _post(server, "/index/i/query?profile=true", q)
    (call,) = out["profile"]["calls"]
    # identical PQL → parse memo → plan-cache hit; warm leaves → either
    # the operand memo or the residency cache answers (no re-decode)
    assert call["planCacheHit"] is True
    assert out["profile"]["totals"]["containers"] == {
        "array": 0, "bitmap": 0, "run": 0}
    assert (call["operandMemoHit"]
            or out["profile"]["totals"]["rowCacheHits"] > 0)


def test_profile_rows_materialized(server):
    _seed_one(server)
    out = _post(server, "/index/i/query?profile=true", b"Row(f=1)")
    (call,) = out["profile"]["calls"]
    assert call["rowsMaterialized"] == 6  # 3 cols x 2 shards
    assert sorted(out["results"][0]["columns"])[:3] == [10, 11, 12]


def test_profile_absent_without_param(server):
    _seed_one(server)
    out = _post(server, "/index/i/query", b"Count(Row(f=1))")
    assert "profile" not in out


def test_profile_error_requests_carry_no_profile(server):
    _seed_one(server)
    with pytest.raises(urllib.error.HTTPError) as ei:
        _post(server, "/index/i/query?profile=true", b"Count(Row(nope=1))")
    assert ei.value.code == 400


def test_profile_wall_reconciles_with_span_tree(server):
    """Acceptance oracle: a profiled AND traced request's per-call wall
    total must reconcile with the span tree's executor.Execute duration
    (both envelopes wrap the same resolve loop). Uses a fresh query
    shape so compile time puts the durations at ms scale where the
    +/-10%% comparison is meaningful."""
    from pilosa_tpu.utils.tracing import global_tracer

    _seed_one(server)
    tracer = global_tracer()
    tracer.sample_rate = 1.0
    tracer.clear()
    try:
        out = _post(server, "/index/i/query?profile=true",
                    b"Count(Xor(Row(f=1), Row(f=2)))")
        prof_wall = sum(c["wallMs"] for c in out["profile"]["calls"])

        def find(node, name):
            if node["name"] == name:
                return node
            for c in node.get("children", []):
                hit = find(c, name)
                if hit is not None:
                    return hit
            return None

        execs = [find(t, "executor.Execute") for t in tracer.recent()]
        execs = [e for e in execs if e is not None]
        assert execs, "traced request produced no executor.Execute span"
        span_ms = execs[-1]["durationMs"]
        assert span_ms > 1.0  # compile puts this at ms scale
        assert prof_wall == pytest.approx(span_ms, rel=0.10)
    finally:
        tracer.sample_rate = 0.0
        tracer.clear()


# --------------------------------------------------------- 3-node PROFILE


def test_profile_three_node_stitched(tmp_path):
    servers = make_cluster(tmp_path, 3)
    try:
        seed(servers[0], n_shards=6)
        time.sleep(0.2)
        out = req(
            "POST",
            f"{uri(servers[0])}/index/i/query?profile=true",
            body=b"Count(Row(f=1))",
        )
        total = out["results"][0]
        prof = out["profile"]
        # one stitched tree: the coordinator's calls plus one grafted
        # per-node profile per remote leg, each a full profile whose
        # calls ran REMOTELY (rpc legs profile on their own node)
        remote_nodes = {r["node"] for r in prof["remote"]}
        assert len(remote_nodes) == 2
        assert prof["node"] not in remote_nodes
        for leg in prof["remote"]:
            sub = leg["profile"]
            assert sub["calls"], "remote leg returned an empty profile"
            assert sub["calls"][0]["name"] == "Count"
            assert sub["node"] in remote_nodes
        # per-stage reconciliation: shard coverage across the
        # coordinator + grafted legs equals the query's shard set
        local_shards = prof["totals"]["shards"]
        leg_shards = sum(leg["shards"] for leg in prof["remote"])
        assert local_shards + leg_shards == 6
        assert total == 4 * 6  # seed: row 1 holds 4 cols per shard
    finally:
        for s in servers:
            s.close()


def test_profile_three_node_trace_and_profile_agree(tmp_path):
    """Run ONE request with both planes on: the span tree's remote
    children and the profile's grafted legs must name the same peers."""
    from pilosa_tpu.utils.tracing import global_tracer

    servers = make_cluster(tmp_path, 3)
    tracer = global_tracer()
    try:
        seed(servers[0], n_shards=6)
        time.sleep(0.2)
        tracer.sample_rate = 1.0
        tracer.clear()
        out = req(
            "POST",
            f"{uri(servers[0])}/index/i/query?profile=true",
            body=b"Count(Row(f=2))",
        )
        prof_nodes = {r["node"] for r in out["profile"]["remote"]}

        span_nodes = set()

        def walk(node):
            if node["name"] == "rpc.query":
                span_nodes.add(node["tags"].get("node"))
            for c in node.get("children", []):
                walk(c)

        for t in tracer.recent():
            walk(t)
        assert prof_nodes
        assert prof_nodes == span_nodes
    finally:
        tracer.sample_rate = 0.0
        tracer.clear()
        for s in servers:
            s.close()


# ------------------------------------------------------------- ledger


def test_tenant_ledger_and_debug_endpoint(server):
    _seed_one(server)
    for tenant, n in (("acme", 6), ("beta", 2)):
        for _ in range(n):
            r = urllib.request.Request(
                f"{uri(server)}/index/i/query",
                data=b"Count(Row(f=1))", method="POST",
                headers={"X-Pilosa-Tenant": tenant},
            )
            urllib.request.urlopen(r, timeout=30).read()
    out = req("GET", f"{uri(server)}/debug/tenants?k=1&by=queries")
    by_tenant = {r["tenant"]: r for r in out["tenants"]}
    assert by_tenant["acme"]["queries"] == 6
    assert by_tenant["beta"]["queries"] == 2
    assert by_tenant["acme"]["egress_bytes"] > 0
    assert by_tenant["acme"]["device_ms"] >= 0
    # top-K offender view honors k and the requested column
    assert len(out["top"]) == 1
    assert out["top"][0]["tenant"] == "acme"
    assert out["totals"]["queries_total"] == 8


def test_tenant_ledger_counts_ingest(server):
    _seed_one(server)
    r = urllib.request.Request(
        f"{uri(server)}/index/i/field/f/import",
        data=json.dumps({"rows": [5, 5, 5], "columns": [1, 2, 3]}).encode(),
        method="POST",
        headers={"Content-Type": "application/json",
                 "X-Pilosa-Tenant": "loader"},
    )
    urllib.request.urlopen(r, timeout=30).read()
    out = req("GET", f"{uri(server)}/debug/tenants")
    row = next(r for r in out["tenants"] if r["tenant"] == "loader")
    assert row["ingest_rows"] == 3


def test_ledger_unknown_sort_column_400(server):
    with pytest.raises(urllib.error.HTTPError) as ei:
        req("GET", f"{uri(server)}/debug/tenants?by=bogus")
    assert ei.value.code == 400


def test_ledger_overflow_bucket():
    led = CostLedger(max_pairs=3)
    for i in range(10):
        led.add_ingest(f"t{i}", "i", 1)
    snap = led.snapshot()
    assert len(snap) == 4  # 3 real pairs + the one overflow bucket
    other = next(r for r in snap if r["tenant"] == "__other__")
    assert other["ingest_rows"] == 7  # everything past the cap
    assert led.metrics()["ingest_rows_total"] == 10  # totals stay exact


def test_cost_kill_switch(server):
    _seed_one(server)
    set_cost_enabled(False)
    try:
        assert current_cost() is None
        out = _post(server, "/index/i/query", b"Count(Row(f=1))")
        assert out["results"] == [6]
        assert server.api.cost.snapshot() == []
        assert global_heat().metrics()["accesses_total"] == 0
    finally:
        set_cost_enabled(True)
    assert cost_enabled()


# ------------------------------------------------------------- heat map


def test_heatmap_ranks_skewed_two_index_workload(server):
    _seed_one(server, index="hot", n_shards=2)
    _seed_one(server, index="cold", n_shards=2)
    for _ in range(9):
        _post(server, "/index/hot/query", b"Count(Row(f=1))")
    _post(server, "/index/cold/query", b"Count(Row(f=1))")
    out = req("GET", f"{uri(server)}/debug/heatmap?k=50")
    rows = [r for r in out["shards"] if r["field"] == "f"]
    hottest = rows[0]
    assert hottest["index"] == "hot"
    by_index = {}
    for r in rows:
        by_index.setdefault(r["index"], 0)
        by_index[r["index"]] += r["access"]
    assert by_index["hot"] > by_index["cold"] * 3
    # residency overlay: the queried leaves are device-resident
    assert any(r["resident"] for r in rows)
    assert out["halfLifeS"] == 300.0


def test_heatmap_counts_writes(server):
    _seed_one(server)
    _post(server, "/index/i/query", b"Set(7, f=9)")
    out = req("GET", f"{uri(server)}/debug/heatmap")
    row = next(r for r in out["shards"]
               if r["index"] == "i" and r["field"] == "f")
    assert row["writes"] >= 1


def test_heat_ignores_background_writes(server):
    """Fragment writes OUTSIDE a request cost context (anti-entropy
    repair, direct maintenance) must not skew the promote/demote
    signal; edge imports record at the API layer instead."""
    _seed_one(server)  # direct frag.bulk_import — no ctx, no API route
    rows = [r for r in global_heat().hottest(20)
            if r["index"] == "i" and r["field"] == "f"]
    assert all(r["writes"] == 0 for r in rows)
    # an edge HTTP import DOES record write heat (API-layer hook)
    _post(server, "/index/i/field/f/import",
          json.dumps({"rows": [3, 3], "columns": [1, 2]}).encode())
    row = next(r for r in global_heat().hottest(20)
               if r["index"] == "i" and r["field"] == "f"
               and r["shard"] == 0)
    assert row["writes"] >= 2


def test_debug_k_must_be_positive(server):
    for path in ("/debug/tenants?k=-1", "/debug/heatmap?k=-3"):
        with pytest.raises(urllib.error.HTTPError) as ei:
            req("GET", f"{uri(server)}{path}")
        assert ei.value.code == 400


def test_heatmap_k_zero_is_full_table(server):
    """``?k=0`` = the FULL heat table — the exact request
    ``client.heatmap`` (the autopilot coordinator's peer heat gather)
    sends. Rejecting or capping it makes every peer read cold and the
    planner skip 'in-budget' forever, silently."""
    from pilosa_tpu.parallel.client import InternalClient

    _seed_one(server, index="hot2", n_shards=2)
    _seed_one(server, index="cold2", n_shards=2)
    for _ in range(3):
        _post(server, "/index/hot2/query", b"Count(Row(f=1))")
    full = req("GET", f"{uri(server)}/debug/heatmap?k=0")
    capped = req("GET", f"{uri(server)}/debug/heatmap?k=1")
    assert len(capped["shards"]) == 1
    assert len(full["shards"]) > 1
    # and over the planner's actual wire path
    wired = InternalClient().heatmap(uri(server))
    assert {(r["index"], r["field"], r["shard"]) for r in wired["shards"]} \
        == {(r["index"], r["field"], r["shard"]) for r in full["shards"]}


def test_roaring_import_bills_submitted_bits(server):
    """Re-importing an identical roaring payload must bill the same
    ingest_rows as the first import (rows SUBMITTED, like the
    row/value routes) — not zero because nothing changed."""
    from pilosa_tpu.roaring import RoaringBitmap
    from pilosa_tpu.roaring.format import serialize

    _seed_one(server)
    data = serialize(RoaringBitmap.from_ids(
        np.array([9 << 20 | 5, 9 << 20 | 6, 9 << 20 | 7], np.uint64)))
    for _ in range(2):  # second import changes ZERO bits
        r = urllib.request.Request(
            f"{uri(server)}/index/i/field/f/import-roaring/0",
            data=data, method="POST",
            headers={"X-Pilosa-Tenant": "loader"},
        )
        urllib.request.urlopen(r, timeout=30).read()
    out = req("GET", f"{uri(server)}/debug/tenants")
    row = next(r for r in out["tenants"] if r["tenant"] == "loader")
    assert row["ingest_rows"] == 6


def test_heat_decay_half_life():
    heat = HeatMap(half_life_s=0.05)
    heat.record_access("i", "f", [0], n=8.0)
    time.sleep(0.1)  # two half-lives
    (row,) = heat.hottest(1)
    assert row["access"] == pytest.approx(2.0, rel=0.5)


def test_heat_prune_bounds_table():
    heat = HeatMap()
    for shard in range(300):
        heat.record_access("i", "f", [shard])
    heat._maybe_prune(max_entries=100)
    assert heat.metrics()["tracked_shards"] <= 100


# ------------------------------------------------------------------ SLO


def test_slo_objective_parsing():
    o = SLOObjective.parse("reads:latency:100ms:0.99")
    assert o.kind == "latency" and o.threshold_s == pytest.approx(0.1)
    o2 = SLOObjective.parse("avail:errors:0.999")
    assert o2.kind == "errors" and o2.target == 0.999
    for bad in ("nope", "x:latency:0.99", "x:errors:2.0",
                "x:latency:abcms:0.9", "x:weird:0.9"):
        with pytest.raises(ValueError):
            SLOObjective.parse(bad)


def test_slo_burst_flips_burn_rate_within_window():
    eng = SLOEngine.from_config(
        ["reads:latency:50ms:0.99"], ["2s", "10s"])
    for _ in range(100):
        eng.record(0.001)  # healthy traffic
    rows = eng.burn_rates()
    assert rows[0]["windows"]["2s"]["burnRate"] == 0.0
    assert rows[0]["breach"] is False
    # injected latency burst: evaluation is lazy, so the very next
    # scrape inside the window sees it burning
    for _ in range(10):
        eng.record(0.2)
    rows = eng.burn_rates()
    assert rows[0]["windows"]["2s"]["burnRate"] > 1.0
    assert rows[0]["breach"] is True


def test_slo_error_objective_and_multiwindow_and():
    eng = SLOEngine.from_config(["avail:errors:0.9"], ["1s", "3600s"])
    for _ in range(50):
        eng.record(0.001, error=False)
    time.sleep(1.1)  # healthy history ages OUT of the fast window only
    for _ in range(5):
        eng.record(0.001, error=True)
    rows = eng.burn_rates()
    w = rows[0]["windows"]
    assert w["1s"]["burnRate"] > 1.0          # all-bad fast window
    assert w["3600s"]["burnRate"] < 1.0        # diluted slow window
    assert rows[0]["breach"] is False          # multi-window AND holds


def test_slo_http_surface(tmp_path):
    s = Server(ServerConfig(
        data_dir=str(tmp_path / "d"), port=0, anti_entropy_interval=0,
        heartbeat_interval=0,
        slo_objectives=["reads:latency:1us:0.99"],
        slo_windows=["2s", "5s"],
    )).open()
    try:
        _seed_one(s)
        _post(s, "/index/i/query", b"Count(Row(f=1))")  # always > 1us
        out = req("GET", f"{uri(s)}/debug/slo")
        assert out["windows"] == [2, 5]
        (obj,) = out["objectives"]
        assert obj["name"] == "reads"
        assert obj["windows"]["2s"]["bad"] >= 1
        assert obj["breach"] is True
        metrics = req("GET", f"{uri(s)}/metrics", raw=True).decode()
        assert ('pilosa_tpu_slo_breach{objective="reads"} 1'
                in metrics)
        assert 'pilosa_tpu_slo_burn_rate{objective="reads",window="2s"}' \
            in metrics
    finally:
        s.close()


def test_slo_durations_match_sibling_knob_grammar():
    """SLO specs live in the same TOML as every other knob — compound
    Go-style durations must parse (review finding: a narrower grammar
    rejected '1m30s' that heat-half-life accepts)."""
    eng = SLOEngine.from_config(["r:latency:1m30s:0.99"], ["1m30s", "2h"])
    assert eng.objectives[0].threshold_s == pytest.approx(90.0)
    assert eng.windows_s == (90.0, 7200.0)
    assert SLOObjective.parse("r:latency:0.25:0.9").threshold_s == 0.25


def test_ledger_metrics_rank_per_family():
    """The ingest-heavy tenant must appear in tenant_ingest_rows_total
    even when the series cap drops it from the device-ms ranking."""
    led = CostLedger()
    for i in range(4):
        led.record_query(f"q{i}", "i", None, 0.5)  # wall_ms heavy
    led.add_ingest("loader", "i", 10_000)
    text = led.prometheus_lines("p", max_series=2)
    ingest_lines = [l for l in text.splitlines()
                    if l.startswith("p_tenant_ingest_rows_total{")]
    assert any('tenant="loader"' in l and l.endswith(" 10000")
               for l in ingest_lines), text


def test_profile_param_rejected_on_protobuf_accept(server):
    """?profile=true with a protobuf Accept must 400 (the profile rides
    only the JSON envelope) instead of silently paying the overhead and
    dropping the tree."""
    _seed_one(server)
    r = urllib.request.Request(
        f"{uri(server)}/index/i/query?profile=true",
        data=b"Count(Row(f=1))", method="POST",
        headers={"Accept": "application/x-protobuf"},
    )
    with pytest.raises(urllib.error.HTTPError) as ei:
        urllib.request.urlopen(r, timeout=30)
    assert ei.value.code == 400


def test_slo_invalid_objective_fails_config():
    with pytest.raises(ValueError):
        ServerConfig(slo_objectives=["bogus"])
    with pytest.raises(ValueError):
        ServerConfig(slo_objectives=["x:latency:10ms:1.5"])


# ------------------------------------------------------- knobs / metrics


def test_slow_query_ring_knob(tmp_path):
    cfg = ServerConfig(
        data_dir=str(tmp_path / "d"), port=0, anti_entropy_interval=0,
        heartbeat_interval=0, slow_query_ring=3, long_query_time=1e-9,
        heat_half_life=7.0,
    )
    # TOML/env roundtrip
    rt = ServerConfig.from_dict(cfg.to_dict())
    assert rt.slow_query_ring == 3
    assert rt.heat_half_life == 7.0
    s = Server(cfg).open()
    try:
        assert s.api.long_queries.maxlen == 3
        assert global_heat().half_life_s == 7.0
        _seed_one(s)
        for i in range(5):
            _post(s, "/index/i/query", b"Count(Row(f=1))")
        out = req("GET", f"{uri(s)}/debug/queries/slow")
        assert len(out["queries"]) == 3  # ring capped at the knob
        assert out["total"] == 5
    finally:
        s.close()
    with pytest.raises(ValueError):
        ServerConfig(slow_query_ring=0)
    with pytest.raises(ValueError):
        ServerConfig(heat_half_life=0)


def test_metrics_families_have_metadata(server):
    _seed_one(server)
    _post(server, "/index/i/query?profile=true", b"Count(Row(f=1))")
    text = req("GET", f"{uri(server)}/metrics", raw=True).decode()
    typed = {line.split(" ")[2] for line in text.splitlines()
             if line.startswith("# TYPE ")}
    for family in ("pilosa_tpu_tenant_queries_total",
                   "pilosa_tpu_tenant_device_ms_total",
                   "pilosa_tpu_tenant_egress_bytes_total",
                   "pilosa_tpu_heat_accesses_total",
                   "pilosa_tpu_heat_shard",
                   "pilosa_tpu_slo_events_total",
                   "pilosa_tpu_slo_breach",
                   "pilosa_tpu_slo_burn_rate"):
        assert family in typed, family
    # every tagged sample's family is declared (no TYPE orphans in the
    # new blocks)
    for line in text.splitlines():
        if line.startswith(("pilosa_tpu_tenant_", "pilosa_tpu_heat_",
                            "pilosa_tpu_slo_")) and "{" in line:
            family = line.split("{", 1)[0]
            assert family in typed, line


def test_tenant_label_escaping_keeps_metrics_parseable(server):
    """A client-controlled tenant header with quotes/backslashes must
    not corrupt the exposition page (review finding: one request could
    take ALL of the node's metrics dark for every scraper)."""
    _seed_one(server)
    r = urllib.request.Request(
        f"{uri(server)}/index/i/query", data=b"Count(Row(f=1))",
        method="POST",
        headers={"X-Pilosa-Tenant": 'evil"} 1 back\\slash'},
    )
    urllib.request.urlopen(r, timeout=30).read()
    text = req("GET", f"{uri(server)}/metrics", raw=True).decode()
    assert 'tenant="evil\\"} 1 back\\\\slash"' in text
    # every sample line still parses: name{labels} value
    import re

    sample = re.compile(
        r'^[a-zA-Z_:][a-zA-Z0-9_:]*(\{([a-zA-Z_][a-zA-Z0-9_]*='
        r'"(\\.|[^"\\])*",?)*\})? [^ ]+$'
    )
    for line in text.splitlines():
        if line and not line.startswith("#"):
            assert sample.match(line), line


def test_heat_write_only_workload_bounded():
    """record_write alone must trigger pruning too (review finding: a
    bulk-ingest phase with no reads grew the table without bound)."""
    heat = HeatMap()
    for shard in range(300):
        heat.record_write("i", "f", shard)
    heat._maybe_prune(max_entries=100)
    assert heat.metrics()["tracked_shards"] <= 100


def test_heat_scope_separates_holders():
    """Two holders in one process (in-process clusters) must not merge
    their heat under identical index/field names."""
    heat = HeatMap()
    heat.record_access("i", "f", [0], n=5.0, scope="/data/a")
    heat.record_access("i", "f", [0], n=1.0, scope="/data/b")
    rows = heat.hottest(10)
    assert len(rows) == 2
    assert rows[0]["scope"] == "/data/a" and rows[0]["access"] == 5.0
    assert rows[1]["scope"] == "/data/b" and rows[1]["access"] == 1.0


def test_profile_disabled_plane_is_marked(server):
    """?profile=true with the kill switch off must say so, not return a
    plausible-looking all-zero tree."""
    _seed_one(server)
    set_cost_enabled(False)
    try:
        out = _post(server, "/index/i/query?profile=true",
                    b"Count(Row(f=1))")
        assert out["results"] == [6]
        assert out["profile"] == {
            "disabled": True,
            "reason": "cost plane is disabled on this node"}
    finally:
        set_cost_enabled(True)


def test_debug_vars_includes_cost_plane(server):
    _seed_one(server)
    _post(server, "/index/i/query", b"Count(Row(f=1))")
    snap = req("GET", f"{uri(server)}/debug/vars")
    assert snap["tenants"]["queries_total"] == 1
    assert "tracked_shards" in snap["heat"]
    assert snap["slo"]["objectives"] == 0
