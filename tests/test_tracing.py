"""Distributed tracing, in-flight inspector, and metrics-plane suite
(ISSUE 7 / docs/OBSERVABILITY.md).

Oracles:
- context propagation: every span of a request is reachable from its
  request root (pool fan-outs, the serving pipeline's wave handoff, and
  the micro-batcher included) — none orphaned;
- cross-node stitching: a 3-node cluster query yields ONE tree on the
  coordinator containing remote child spans from both peers with intact
  parent/trace ids;
- sampling statistics and the zero-overhead off path (no spans retained,
  no context mutation, shared no-op handle);
- the slow-query ring captures full span trees;
- /debug/queries shows then clears an in-flight query;
- /metrics is stock-Prometheus parseable with HELP/TYPE per family and
  cumulative histogram series beside the windowed summaries.
"""

import json
import re
import threading
import time
import urllib.request

import pytest

from cluster_helpers import make_cluster, req, seed, uri
from pilosa_tpu.utils.tracing import (
    TRACE_HEADER,
    Tracer,
    current_span,
    global_query_tracker,
    global_tracer,
    parse_trace_header,
)


@pytest.fixture(autouse=True)
def _reset_tracing():
    """Every test starts with sampling off and empty rings, and leaves
    the process-global tracer/tracker the way tier-1 expects them."""
    tracer = global_tracer()
    tracker = global_query_tracker()
    tracer.sample_rate = 0.0
    tracer.clear()
    tracker.enabled = True
    yield
    tracer.sample_rate = 0.0
    tracer.clear()
    tracker.enabled = True


def _walk(tree, out=None):
    out = out if out is not None else []
    out.append(tree)
    for child in tree.get("children", []):
        _walk(child, out)
    return out


def _assert_tree_consistent(tree):
    """Every span shares the root's traceId and each child's parentId is
    its parent's spanId — the 'reachable from root, none orphaned'
    oracle."""
    trace_id = tree["traceId"]

    def rec(node):
        assert node["traceId"] == trace_id, node
        for child in node.get("children", []):
            assert child["parentId"] == node["spanId"], (node, child)
            rec(child)

    rec(tree)


# --------------------------------------------------------------- unit level


class TestTracerCore:
    def test_span_tree_and_ids(self):
        t = Tracer(sample_rate=1.0)
        with t.root_span("root", a=1) as root:
            with t.span("child") as child:
                assert current_span() is child
                assert child.trace_id == root.trace_id
                assert child.parent_id == root.span_id
            assert current_span() is root
        assert current_span() is None
        assert len(t.finished) == 1
        _assert_tree_consistent(t.recent()[0])

    def test_off_is_noop_no_allocation_no_context(self):
        t = Tracer(sample_rate=0.0)
        before = current_span()
        h1 = t.span("x")
        h2 = t.request_root("y")
        # zero-allocation: the shared no-op handle, same object every time
        assert h1 is h2 is t.span("z")
        with h1 as s:
            assert s is None
            assert current_span() is before is None
        assert len(t.finished) == 0
        assert t.spans_started == 0

    def test_unsampled_request_suppresses_inner_roots(self):
        t = Tracer(sample_rate=0.5)
        # force the negative decision deterministically
        import random

        random.seed(0)
        for _ in range(200):
            with t.request_root("http.query") as root:
                if root is None:
                    # inner span sites must NOT root their own trace
                    with t.span("executor.Execute") as inner:
                        assert inner is None
        # every finished tree is rooted at the request root
        assert all(s.name == "http.query" for s in t.finished)

    def test_sampling_rate_statistics(self):
        t = Tracer(sample_rate=0.25)
        n = 2000
        hits = 0
        for _ in range(n):
            with t.request_root("r") as root:
                if root is not None:
                    hits += 1
        # mean 500, sd ~19.4 — 5 sigma bounds
        assert 400 < hits < 600, hits
        assert t.sampled_traces == hits

    def test_header_roundtrip_and_remote_root(self):
        t = Tracer(sample_rate=1.0)
        with t.root_span("root") as root:
            header = root.header_value()
        assert parse_trace_header(header) == (root.trace_id, root.span_id)
        assert parse_trace_header(None) is None
        assert parse_trace_header("garbage") is None
        with t.remote_root(header, "rpc.query", node="n1") as remote:
            assert remote.trace_id == root.trace_id
            assert remote.parent_id == root.span_id
        # malformed header: suppressed, not sampled locally
        with t.remote_root("bad", "rpc.query") as none_span:
            assert none_span is None
            with t.span("inner") as inner:
                assert inner is None

    def test_context_propagates_through_pool(self):
        from pilosa_tpu.utils.pool import concurrent_map, spawn

        t = Tracer(sample_rate=1.0)
        with t.root_span("root") as root:
            names = concurrent_map(
                lambda i: (current_span() or root).trace_id, range(8)
            )
            assert all(tid == root.trace_id for tid in names)

            def thunk():
                with t.span("spawned") as s:
                    return s.trace_id

            assert spawn(thunk)() == root.trace_id
        tree = t.recent()[0]
        assert "spawned" in [c["name"] for c in tree["children"]]
        _assert_tree_consistent(tree)


# ------------------------------------------------------------- single node


@pytest.fixture()
def server(tmp_path):
    from pilosa_tpu.server import Server, ServerConfig

    s = Server(ServerConfig(
        data_dir=str(tmp_path / "node"), port=0, name="t",
        anti_entropy_interval=0, heartbeat_interval=0,
    )).open()
    yield s
    s.close()


def _seed_single(s):
    base = uri(s)
    req("POST", f"{base}/index/i", {})
    req("POST", f"{base}/index/i/field/f", {})
    req("POST", f"{base}/index/i/field/f/import",
        {"rows": [1, 1, 2], "columns": [1, 2, 2]})


class TestSingleNode:
    def test_pipeline_span_tree_reachable_from_http_root(self, server):
        _seed_single(server)
        global_tracer().sample_rate = 1.0
        for _ in range(3):
            req("POST", f"{uri(server)}/index/i/query",
                b"Count(Row(f=1))")
        traces = req("GET", f"{uri(server)}/debug/traces")
        assert traces["enabled"] and traces["sampleRate"] == 1.0
        query_trees = [t for t in traces["traces"]
                       if t["name"] == "http.query"]
        assert len(query_trees) == 3
        for tree in query_trees:
            _assert_tree_consistent(tree)
            names = [n["name"] for n in _walk(tree)]
            # the per-stage attribution the acceptance criterion names
            assert "qos.admit" in names
            assert "pipeline.wave" in names
            assert "executor.Execute" in names
            assert "executeCount" in names
            assert "device.dispatch" in names

    def test_no_spans_when_off_and_inflight_always_on(self, server):
        _seed_single(server)
        req("POST", f"{uri(server)}/index/i/query", b"Count(Row(f=1))")
        traces = req("GET", f"{uri(server)}/debug/traces")
        assert traces["traces"] == []
        assert traces["sampleRate"] == 0.0
        # the inspector tracked it even with tracing off
        q = req("GET", f"{uri(server)}/debug/queries")
        assert q["trackedTotal"] >= 1 and q["queries"] == []

    def test_write_gets_wal_barrier_span(self, server):
        _seed_single(server)
        global_tracer().sample_rate = 1.0
        req("POST", f"{uri(server)}/index/i/query", b"Set(5, f=3)")
        trees = req("GET", f"{uri(server)}/debug/traces")["traces"]
        names = [n["name"] for t in trees for n in _walk(t)]
        assert "wal.barrier" in names

    def test_inflight_query_shows_stage_then_clears(self, server):
        _seed_single(server)
        gate = threading.Event()
        release = threading.Event()
        admission = server.api.qos.admission
        real_admit = admission.admit

        def slow_admit(tenant="default"):
            gate.set()
            release.wait(10)
            return real_admit(tenant)

        admission.admit = slow_admit
        try:
            worker = threading.Thread(
                target=lambda: req("POST", f"{uri(server)}/index/i/query",
                                   b"Count(Row(f=1))"),
                daemon=True,
            )
            worker.start()
            assert gate.wait(10)
            q = req("GET", f"{uri(server)}/debug/queries")
            assert len(q["queries"]) == 1
            entry = q["queries"][0]
            assert entry["pql"] == "Count(Row(f=1))"
            assert entry["index"] == "i"
            assert entry["stage"] == "qos.admit"  # the stage site's name
            assert entry["ageSeconds"] >= 0
            release.set()
            worker.join(30)
            deadline = time.time() + 10
            while time.time() < deadline:
                if not req("GET",
                           f"{uri(server)}/debug/queries")["queries"]:
                    break
                time.sleep(0.05)
            assert not req("GET",
                           f"{uri(server)}/debug/queries")["queries"]
        finally:
            release.set()
            admission.admit = real_admit

    def test_slow_query_ring_captures_span_tree(self, server):
        _seed_single(server)
        global_tracer().sample_rate = 1.0
        server.api.long_query_time = 1e-9  # everything is "slow"
        req("POST", f"{uri(server)}/index/i/query", b"Count(Row(f=1))")
        out = req("GET", f"{uri(server)}/debug/queries/slow")
        assert out["threshold"] == pytest.approx(1e-9)
        assert out["total"] >= 1
        entry = out["queries"][-1]
        assert entry["pql"] == "Count(Row(f=1))"
        assert "trace" in entry and "traceId" in entry
        names = [n["name"] for n in _walk(entry["trace"])]
        assert "executor.Execute" in names
        _assert_tree_consistent(entry["trace"])
        # the legacy alias keeps answering
        legacy = req("GET", f"{uri(server)}/debug/long-queries")
        assert legacy["queries"]
        # counter exported on /metrics from this node's API counter
        metrics = req("GET", f"{uri(server)}/metrics", raw=True).decode()
        m = re.search(r"^pilosa_tpu_slow_queries_total (\d+)", metrics,
                      re.M)
        assert m and int(m.group(1)) >= 1

    def test_trace_device_capture(self, server):
        out = req("POST",
                  f"{uri(server)}/debug/trace-device?secs=0.2", b"")
        assert out["seconds"] >= 0.2
        import os

        assert os.path.isdir(out["logDir"])
        # the profiler wrote something under the log dir
        found = any(files for _, _, files in os.walk(out["logDir"]))
        assert found, f"empty trace dir {out['logDir']}"

    def test_trace_device_rejects_bad_secs(self, server):
        for bad in ("0", "-1", "61", "nan", "x"):
            with pytest.raises(urllib.error.HTTPError) as err:
                req("POST",
                    f"{uri(server)}/debug/trace-device?secs={bad}", b"")
            assert err.value.code == 400


# ------------------------------------------------------------ metrics plane


def _parse_prometheus(text):
    """Minimal exposition-format checker: returns (families: dict
    name->type, samples: list of (name, value)). Raises AssertionError
    on any malformed line."""
    families = {}
    samples = []
    sample_re = re.compile(
        r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{[^{}]*\})? "
        r"([-+]?(?:[0-9.]+(?:[eE][-+]?[0-9]+)?|[Ii]nf|NaN))$"
    )
    for line in text.splitlines():
        if not line.strip():
            continue
        if line.startswith("# HELP "):
            assert len(line.split(None, 3)) == 4, line
            continue
        if line.startswith("# TYPE "):
            parts = line.split()
            assert parts[3] in ("counter", "gauge", "summary",
                                "histogram"), line
            families[parts[2]] = parts[3]
            continue
        assert not line.startswith("#"), line
        m = sample_re.match(line)
        assert m, f"malformed sample line: {line!r}"
        samples.append((m.group(1), m.group(3)))
    return families, samples


def _family_of(name, families):
    """Map a sample name to its declared family (strip summary/histogram
    child suffixes)."""
    if name in families:
        return name
    for suffix in ("_bucket", "_sum", "_count", "_total"):
        if name.endswith(suffix) and name[: -len(suffix)] in families:
            return name[: -len(suffix)]
    return None


class TestMetricsPlane:
    def test_metrics_prometheus_compliant(self, server):
        _seed_single(server)
        global_tracer().sample_rate = 1.0
        req("POST", f"{uri(server)}/index/i/query", b"Count(Row(f=1))")
        text = req("GET", f"{uri(server)}/metrics", raw=True).decode()
        families, samples = _parse_prometheus(text)
        assert samples, "empty /metrics"
        # every series belongs to a declared family (HELP/TYPE present)
        orphans = [n for n, _ in samples
                   if _family_of(n, families) is None]
        assert not orphans, f"series without TYPE metadata: {orphans[:5]}"
        # no family declared twice
        type_lines = [l for l in text.splitlines()
                      if l.startswith("# TYPE ")]
        assert len(type_lines) == len(set(type_lines))
        # observability series present from scrape one
        for needle in ("pilosa_tpu_slow_queries_total",
                       "pilosa_tpu_tracing_sampled_traces_total",
                       "pilosa_tpu_inflight_queries"):
            assert needle in {n for n, _ in samples}, needle

    def test_timer_histogram_export(self):
        from pilosa_tpu.utils.stats import StatsClient

        s = StatsClient()
        for v in (0.0004, 0.003, 0.003, 0.2, 9.0, 99.0):
            s.timing("query", v)
        text = s.prometheus_text()
        families, samples = _parse_prometheus(text)
        assert families["pilosa_tpu_query_seconds"] == "summary"
        assert families["pilosa_tpu_query_hist_seconds"] == "histogram"
        by_name = {}
        for n, v in samples:
            by_name.setdefault(n, []).append(v)
        buckets = {}
        for line in text.splitlines():
            m = re.match(
                r'pilosa_tpu_query_hist_seconds_bucket\{le="([^"]+)"\} '
                r"(\d+)", line)
            if m:
                buckets[m.group(1)] = int(m.group(2))
        # cumulative: le=0.001 has 1, le=0.005 has 3, le=10 has 5,
        # +Inf has all 6 (99.0 lands only in +Inf)
        assert buckets["0.001"] == 1
        assert buckets["0.005"] == 3
        assert buckets["10"] == 5
        assert buckets["+Inf"] == 6
        assert by_name["pilosa_tpu_query_hist_seconds_count"] == ["6"]

    def test_debug_vars_observability_block(self, server):
        snap = req("GET", f"{uri(server)}/debug/vars")
        obs = snap["observability"]
        for key in ("slow_queries_total", "tracing_sample_rate",
                    "inflight_queries", "queries_tracked_total"):
            assert key in obs, key


# -------------------------------------------------------------- three nodes


class TestClusterStitching:
    def test_remote_span_tree_stitched_on_coordinator(self, tmp_path):
        servers = make_cluster(tmp_path, 3, trace_sample_rate=1.0)
        try:
            seed(servers[0], n_shards=9)
            out = req("POST", f"{uri(servers[0])}/index/i/query",
                      b"Count(Row(f=1))")
            assert out == {"results": [36]}
            trees = req("GET",
                        f"{uri(servers[0])}/debug/traces")["traces"]
            tree = next(t for t in reversed(trees)
                        if t["name"] == "http.query")
            _assert_tree_consistent(tree)
            spans = _walk(tree)
            remote_legs = [s for s in spans
                           if s["name"] == "remote.query"]
            leg_nodes = {s["tags"]["node"] for s in remote_legs}
            assert leg_nodes == {"n1", "n2"}, leg_nodes
            # each leg carries the PEER's returned subtree, parented to
            # the leg's span id, with per-stage times from the peer
            for leg in remote_legs:
                sub = [c for c in leg["children"]
                       if c["name"] == "rpc.query"]
                assert sub, leg
                assert sub[0]["parentId"] == leg["spanId"]
                assert sub[0]["traceId"] == tree["traceId"]
                peer_names = [n["name"] for n in _walk(sub[0])]
                assert "executor.Execute" in peer_names
            # coordinator stages present too
            names = [s["name"] for s in spans]
            for stage in ("qos.admit", "pipeline.wave",
                          "executor.Execute", "device.dispatch"):
                assert stage in names, stage
        finally:
            for s in servers:
                s.close()

    def test_batched_wave_keeps_per_item_traces(self, tmp_path):
        """Concurrent sampled queries ride the wave batcher's shared
        POST; every request must still get its own stitched tree."""
        servers = make_cluster(tmp_path, 2, trace_sample_rate=1.0)
        try:
            seed(servers[0], n_shards=6)
            n = 8
            results = [None] * n
            gate = threading.Event()

            def worker(k):
                gate.wait(10)
                # distinct PQL strings (leading spaces) defeat the
                # pipeline's identical-query dedupe — a deduped follower
                # legitimately has NO remote leg of its own, which is
                # exactly what this test must not conflate with a lost
                # trace context
                results[k] = req(
                    "POST", f"{uri(servers[0])}/index/i/query",
                    b" " * k + b"Count(Row(f=1))")

            threads = [threading.Thread(target=worker, args=(k,))
                       for k in range(n)]
            for t in threads:
                t.start()
            gate.set()
            for t in threads:
                t.join(60)
            assert all(r == {"results": [24]} for r in results), results
            trees = [t for t in
                     req("GET",
                         f"{uri(servers[0])}/debug/traces")["traces"]
                     if t["name"] == "http.query"]
            assert len(trees) == n
            stitched = 0
            for tree in trees:
                _assert_tree_consistent(tree)
                for s in _walk(tree):
                    if s["name"] == "rpc.query":
                        stitched += 1
            # every request that crossed the wire got its subtree back
            # (local-only routings are possible for some, but with 6
            # shards on 2 nodes every query has a remote leg)
            assert stitched >= n
            batcher = servers[0].api.executor.wave_batcher.metrics()
            assert (batcher["remote_batched_queries_total"]
                    + batcher["remote_batch_solo_total"]) >= n
        finally:
            for s in servers:
                s.close()

    def test_sync_pass_traces_and_remote_sync_spans(self, tmp_path):
        servers = make_cluster(tmp_path, 2, replica_n=2,
                               trace_sample_rate=1.0)
        try:
            seed(servers[0], n_shards=4)
            global_tracer().clear()
            servers[0].run_anti_entropy()
            trees = global_tracer().recent()
            sync_trees = [t for t in trees if t["name"] == "sync.pass"]
            assert sync_trees
            names = [n["name"] for t in sync_trees for n in _walk(t)]
            assert "sync.manifest" in names
        finally:
            for s in servers:
                s.close()


# -------------------------------------------------------------- config knob


class TestConfigKnobs:
    def test_sample_rate_roundtrip(self):
        from pilosa_tpu.server import ServerConfig

        cfg = ServerConfig.from_dict({"trace-sample-rate": "0.25",
                                      "trace-log-dir": "/tmp/tr"})
        assert cfg.trace_sample_rate == 0.25
        assert cfg.trace_log_dir == "/tmp/tr"
        d = cfg.to_dict()
        assert d["trace-sample-rate"] == 0.25
        assert d["trace-log-dir"] == "/tmp/tr"
        assert ServerConfig.from_dict(d).trace_sample_rate == 0.25

    def test_sample_rate_validation(self):
        from pilosa_tpu.server import ServerConfig

        with pytest.raises(ValueError):
            ServerConfig(trace_sample_rate=1.5)
        with pytest.raises(ValueError):
            ServerConfig(trace_sample_rate=-0.1)

    def test_sample_rate_one_means_always_on(self, tmp_path):
        # the legacy `tracing` boolean is gone (ROADMAP D3): rate 1.0 is
        # how a config says always-on
        from pilosa_tpu.server import Server, ServerConfig

        assert "tracing" not in ServerConfig().to_dict()
        s = Server(ServerConfig(
            data_dir=str(tmp_path / "n"), port=0, trace_sample_rate=1.0,
            anti_entropy_interval=0, heartbeat_interval=0,
        )).open()
        try:
            assert global_tracer().sample_rate == 1.0
        finally:
            s.close()

    def test_generate_config_documents_knobs(self, capsys):
        from pilosa_tpu.cli import main

        assert main(["generate-config"]) == 0
        out = capsys.readouterr().out
        assert "trace-sample-rate" in out
        assert "long-query-time" in out


class TestObsSmoke:
    def test_obs_smoke(self, server):
        """The `make obs-smoke` contract in one test: traced query →
        /debug/traces renders the tree, /debug/queries empty after the
        run, /metrics Prometheus-parseable."""
        _seed_single(server)
        global_tracer().sample_rate = 1.0
        hdr_resp = req("POST", f"{uri(server)}/index/i/query",
                       b"Count(Row(f=1))")
        assert hdr_resp == {"results": [2]}
        traces = req("GET", f"{uri(server)}/debug/traces")
        assert traces["traces"], "no span tree on /debug/traces"
        assert not req("GET", f"{uri(server)}/debug/queries")["queries"]
        _parse_prometheus(
            req("GET", f"{uri(server)}/metrics", raw=True).decode()
        )

    def test_remote_trace_header_returns_subtree(self, server):
        """An internal hop carrying X-Pilosa-Trace gets the span subtree
        in its response envelope even with local sampling OFF — the
        coordinator made the decision."""
        _seed_single(server)
        r = urllib.request.Request(
            f"{uri(server)}/index/i/query?remote=true&shards=0",
            data=b"Count(Row(f=1))", method="POST",
        )
        r.add_header(TRACE_HEADER, "aabbccddeeff0011:112233445566")
        with urllib.request.urlopen(r, timeout=30) as resp:
            out = json.loads(resp.read())
        assert "trace" in out, out
        sub = out["trace"]
        assert sub["traceId"] == "aabbccddeeff0011"
        assert sub["parentId"] == "112233445566"
        assert sub["name"] == "rpc.query"
        _assert_tree_consistent(sub)
