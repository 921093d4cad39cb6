"""HTTP integration tests: real listeners on ephemeral localhost ports
(reference http/handler_test.go httptest style — SURVEY.md §4)."""

import json
import urllib.error
import urllib.request

import pytest

from pilosa_tpu.server.api import API
from pilosa_tpu.server.http import serve_in_thread
from pilosa_tpu.storage import Holder


@pytest.fixture
def node_api(tmp_path):
    holder = Holder(str(tmp_path / "data")).open()
    api = API(holder)
    server, port, _ = serve_in_thread(api)
    yield f"http://localhost:{port}", api
    server.shutdown()
    server.server_close()
    holder.close()


@pytest.fixture
def node(node_api):
    return node_api[0]


def req(method, url, body=None, content_type="application/json", raw=False):
    data = body if isinstance(body, (bytes, type(None))) else json.dumps(body).encode()
    r = urllib.request.Request(url, data=data, method=method)
    if data is not None:
        r.add_header("Content-Type", content_type)
    with urllib.request.urlopen(r) as resp:
        payload = resp.read()
        return payload if raw else json.loads(payload or b"{}")


def test_full_lifecycle(node):
    # create index + fields
    req("POST", f"{node}/index/repos", {})
    req("POST", f"{node}/index/repos/field/stargazer", {})
    req("POST", f"{node}/index/repos/field/fare",
        {"options": {"type": "int", "min": 0, "max": 1000}})

    # schema surfaces both
    schema = req("GET", f"{node}/schema")
    names = {f["name"] for f in schema["indexes"][0]["fields"]}
    assert names == {"stargazer", "fare"}

    # writes via PQL query endpoint
    out = req("POST", f"{node}/index/repos/query",
              b"Set(10, stargazer=1) Set(20, stargazer=1)")
    assert out["results"] == [True, True]

    # read back
    out = req("POST", f"{node}/index/repos/query", b"Row(stargazer=1)")
    assert out["results"][0]["columns"] == [10, 20]

    # count fused
    out = req("POST", f"{node}/index/repos/query", b"Count(Row(stargazer=1))")
    assert out["results"] == [2]

    # BSI via import-value + Range/Sum
    req("POST", f"{node}/index/repos/field/fare/import-value",
        {"columns": [10, 20, 30], "values": [5, 10, 400]})
    out = req("POST", f"{node}/index/repos/query", b"Count(Range(fare > 6))")
    assert out["results"] == [2]
    out = req("POST", f"{node}/index/repos/query", b'Sum(field="fare")')
    assert out["results"][0] == {"value": 415, "count": 3}


def test_import_endpoint_and_export(node):
    req("POST", f"{node}/index/i", {})
    req("POST", f"{node}/index/i/field/f", {})
    out = req("POST", f"{node}/index/i/field/f/import",
              {"rows": [1, 1, 2], "columns": [5, 9, 5]})
    assert out["changed"] == 3
    csv = req("GET", f"{node}/export?index=i&field=f", raw=True).decode()
    assert csv.splitlines() == ["1,5", "1,9", "2,5"]


def test_import_roaring_endpoint(node):
    from pilosa_tpu.roaring import RoaringBitmap, serialize

    req("POST", f"{node}/index/i", {})
    req("POST", f"{node}/index/i/field/f", {})
    # row 2, positions {1, 4} → fragment bits 2*2^20 + {1,4}
    bm = RoaringBitmap.from_ids([(2 << 20) + 1, (2 << 20) + 4])
    out = req("POST", f"{node}/index/i/field/f/import-roaring/0",
              serialize(bm), content_type="application/octet-stream")
    assert out["changed"] == 2
    out = req("POST", f"{node}/index/i/query", b"Row(f=2)")
    assert out["results"][0]["columns"] == [1, 4]


def test_topn_groupby_over_http(node):
    req("POST", f"{node}/index/i", {})
    req("POST", f"{node}/index/i/field/f", {})
    rows, cols = [], []
    for row, n in [(1, 3), (2, 8), (3, 5)]:
        rows += [row] * n
        cols += list(range(n))
    req("POST", f"{node}/index/i/field/f/import", {"rows": rows, "columns": cols})
    out = req("POST", f"{node}/index/i/query", b"TopN(f, n=2)")
    assert out["results"][0] == [{"id": 2, "count": 8}, {"id": 3, "count": 5}]
    out = req("POST", f"{node}/index/i/query", b"GroupBy(Rows(f), limit=2)")
    assert out["results"][0] == [
        {"group": [{"field": "f", "rowID": 1}], "count": 3},
        {"group": [{"field": "f", "rowID": 2}], "count": 8},
    ]


def test_recalculate_caches_repairs_drift(node_api):
    """POST /recalculate-caches (reference parity): an authoritative
    recount rebuilds a drifted TopN row cache from container
    cardinalities and persists it; returns 204."""
    node, api = node_api
    req("POST", f"{node}/index/i", {})
    req("POST", f"{node}/index/i/field/f", {})
    rows, cols = [], []
    for row, n in [(1, 3), (2, 8), (3, 5)]:
        rows += [row] * n
        cols += list(range(n))
    req("POST", f"{node}/index/i/field/f/import", {"rows": rows, "columns": cols})

    # simulate drift: clobber the cache with wrong counts (as a crash
    # between bitmap flush and cache save, or a hand-edited dir, would).
    # Phase-2 TopN recounts exactly, so at this scale queries hide the
    # drift — the endpoint's contract is that the CACHE returns to the
    # authoritative counts and persists them.
    frag = api.holder.indexes["i"].fields["f"].views["standard"].fragments[0]
    frag.row_cache.bulk_add(1, 999)
    frag.row_cache.bulk_add(2, 1)
    frag.row_cache.bulk_add(7, 42)  # phantom row: must vanish

    r = urllib.request.Request(f"{node}/recalculate-caches", data=b"{}",
                               method="POST")  # non-empty body: must drain
    with urllib.request.urlopen(r) as resp:
        assert resp.status == 204
        assert resp.headers.get("Content-Length") is None  # RFC 7230 204
    # 204 means QUEUED: the recount runs in a background worker so the
    # cluster message-delivery path can't stall on it — join
    # the worker before asserting on the repaired cache
    t = api._recalc_thread
    if t is not None:
        t.join(timeout=30)
    cache = api.holder.indexes["i"].fields["f"].views["standard"] \
        .fragments[0].row_cache
    assert cache.get(1) == 3 and cache.get(2) == 8 and cache.get(3) == 5
    assert cache.get(7) is None
    # recount persisted: a reloaded cache sees the repaired counts
    fresh = type(cache)(cache.max_size)
    fresh.load(frag._cache_path())
    assert fresh.get(1) == 3 and fresh.get(7) is None
    out = req("POST", f"{node}/index/i/query", b"TopN(f, n=2)")
    assert out["results"][0] == [{"id": 2, "count": 8}, {"id": 3, "count": 5}]


def test_status_info_version_metrics(node):
    st = req("GET", f"{node}/status")
    assert st["state"] == "NORMAL" and st["nodes"]
    info = req("GET", f"{node}/info")
    assert info["shardWidth"] == 1 << 20
    v = req("GET", f"{node}/version")
    assert v["version"]
    # metrics endpoint serves prometheus text incl. residency gauges:
    # counters carry _total, values are exact ints (no %g truncation)
    text = req("GET", f"{node}/metrics", raw=True).decode()
    assert "pilosa_tpu_residency_bytes_used" in text
    assert "pilosa_tpu_residency_hits_total" in text
    # run one pipelined read, then the wave-coalescing counters must
    # be exported for operators (and exist as 0 even before it)
    req("POST", f"{node}/index/i", {})
    req("POST", f"{node}/index/i/field/f", {})
    req("POST", f"{node}/index/i/query", b"Set(1, f=1)")
    req("POST", f"{node}/index/i/query", b"Count(Row(f=1))")
    text = req("GET", f"{node}/metrics", raw=True).decode()
    assert "pilosa_tpu_serving_waves_total" in text
    # host-path kernel counters present from scrape one (PR 18) — and
    # the query above decoded at least one row through the kernels
    assert "pilosa_tpu_hostpath_kernel_calls_total" in text
    kline = [l for l in text.splitlines()
             if l.startswith("pilosa_tpu_hostpath_kernel_calls_total")]
    assert int(kline[0].split()[1]) > 0
    (budget_line,) = [l for l in text.splitlines()
                      if l.startswith("pilosa_tpu_residency_budget_bytes")]
    dv = req("GET", f"{node}/debug/vars")
    # exact int emission (no %g scientific-notation truncation)
    assert budget_line.split()[1] == str(dv["residency"]["residency_budget_bytes"])


def test_error_statuses(node):
    # query on missing index → 400 with error body
    with pytest.raises(urllib.error.HTTPError) as e:
        req("POST", f"{node}/index/nope/query", b"Row(f=1)")
    assert e.value.code == 400
    # delete missing index → 404
    with pytest.raises(urllib.error.HTTPError) as e:
        req("DELETE", f"{node}/index/nope")
    assert e.value.code == 404
    # duplicate create → 409
    req("POST", f"{node}/index/i", {})
    with pytest.raises(urllib.error.HTTPError) as e:
        req("POST", f"{node}/index/i", {})
    assert e.value.code == 409
    # bad PQL → 400 with parse error message
    with pytest.raises(urllib.error.HTTPError) as e:
        req("POST", f"{node}/index/i/query", b"Bogus(")
    assert e.value.code == 400
    assert "error" in json.loads(e.value.read())
    # unknown route → 404
    with pytest.raises(urllib.error.HTTPError) as e:
        req("GET", f"{node}/definitely/not/a/route")
    assert e.value.code == 404


def test_delete_field_and_index(node):
    req("POST", f"{node}/index/i", {})
    req("POST", f"{node}/index/i/field/f", {})
    req("POST", f"{node}/index/i/query", b"Set(1, f=1)")
    req("DELETE", f"{node}/index/i/field/f")
    schema = req("GET", f"{node}/schema")
    assert schema["indexes"][0]["fields"] == []
    req("DELETE", f"{node}/index/i")
    assert req("GET", f"{node}/schema") == {"indexes": []}


def test_internal_fragment_blocks_and_data(node):
    req("POST", f"{node}/index/i", {})
    req("POST", f"{node}/index/i/field/f", {})
    req("POST", f"{node}/index/i/query", b"Set(1, f=1) Set(5, f=101)")
    out = req("GET", f"{node}/internal/fragment/blocks?index=i&field=f&view=standard&shard=0")
    assert {b["block"] for b in out["blocks"]} == {0, 1}
    raw = req("GET", f"{node}/internal/fragment/data?index=i&field=f&view=standard&shard=0", raw=True)
    from pilosa_tpu.roaring.format import load

    bm, _ = load(raw)
    assert bm.count() == 2


def test_shards_max(node):
    req("POST", f"{node}/index/i", {})
    req("POST", f"{node}/index/i/field/f", {})
    req("POST", f"{node}/index/i/query", b"Set(1, f=1)")
    out = req("GET", f"{node}/internal/shards/max")
    assert out["standard"]["i"] == 0


def test_long_query_log(node_api):
    node, api = node_api
    api.long_query_time = 0.0000001  # everything is "long"
    req("POST", f"{node}/index/i", {})
    req("POST", f"{node}/index/i/field/f", {})
    req("POST", f"{node}/index/i/query", b"Set(1, f=1)")
    out = req("GET", f"{node}/debug/long-queries")
    assert out["threshold"] == api.long_query_time
    assert any(q["pql"] == "Set(1, f=1)" for q in out["queries"])
    # threshold off -> nothing more recorded
    api.long_query_time = 0.0
    api.long_queries.clear()
    req("POST", f"{node}/index/i/query", b"Count(Row(f=1))")
    assert req("GET", f"{node}/debug/long-queries")["queries"] == []


@pytest.mark.skipif(__import__("shutil").which("openssl") is None,
                    reason="openssl binary not available")
def test_tls_server(tmp_path):
    import subprocess

    from pilosa_tpu.parallel.client import InternalClient
    from pilosa_tpu.server.server import Server, ServerConfig

    cert = tmp_path / "node.crt"
    key = tmp_path / "node.key"
    subprocess.run(
        ["openssl", "req", "-x509", "-newkey", "rsa:2048", "-nodes",
         "-keyout", str(key), "-out", str(cert), "-days", "1",
         "-subj", "/CN=localhost"],
        check=True, capture_output=True,
    )
    cfg = ServerConfig(
        data_dir=str(tmp_path / "data"), port=0, use_mesh=False,
        anti_entropy_interval=0, heartbeat_interval=0,
        tls_certificate=str(cert), tls_key=str(key), tls_skip_verify=True,
    )
    server = Server(cfg).open()
    try:
        uri = f"https://localhost:{server.port}"
        assert server.api.cluster.local.uri.startswith("https://")
        # the server's own internal client got skip-verify from its config
        assert server.api.cluster.client._ssl_context is not None
        client = InternalClient(insecure_tls=True)
        client._call("POST", f"{uri}/index/i", json.dumps({}).encode())
        client._call("POST", f"{uri}/index/i/field/f", json.dumps({}).encode())
        out = client.query_node(uri, "i", "Set(3, f=1) Count(Row(f=1))",
                                shards=[0], remote=False)
        assert out["results"] == [True, 1]
        # plain http against the TLS socket must fail (URLError or a
        # straight connection reset depending on handshake timing)
        with pytest.raises(OSError):
            urllib.request.urlopen(f"http://localhost:{server.port}/schema", timeout=5)
    finally:
        server.close()


def test_parse_duration():
    from pilosa_tpu.server.server import _parse_duration

    assert _parse_duration(1.5) == 1.5
    assert _parse_duration("30s") == 30.0
    assert _parse_duration("1m30s") == 90.0
    assert _parse_duration("500ms") == 0.5
    assert _parse_duration("2h") == 7200.0
    assert _parse_duration("") == 0.0
    assert _parse_duration("0.25") == 0.25


def test_parse_duration_rejects_malformed():
    from pilosa_tpu.server.server import _parse_duration

    for bad in ("1m30", "abc", "10x", "s30"):
        with pytest.raises(ValueError):
            _parse_duration(bad)


def test_parse_duration_rejects_double_dot():
    from pilosa_tpu.server.server import _parse_duration

    for bad in ("1.2.3s", "..5s", "1..s"):
        with pytest.raises(ValueError):
            _parse_duration(bad)
    assert _parse_duration(".5s") == 0.5


def test_config_to_dict_round_trips_new_keys():
    from pilosa_tpu.server.server import ServerConfig

    cfg = ServerConfig(long_query_time=1.5, tls_certificate="/c", tls_key="/k",
                       tls_skip_verify=True)
    d = cfg.to_dict()
    assert d["long-query-time"] == 1.5
    assert d["tls-certificate"] == "/c" and d["tls-key"] == "/k"
    assert d["tls-skip-verify"] is True
    back = ServerConfig.from_dict(d)
    assert back.long_query_time == 1.5 and back.tls_enabled


def test_insecure_tls_is_per_client():
    # One skip-verify client must not disable verification for others in
    # the same process (scope the SSL context to the instance).
    from pilosa_tpu.parallel.client import InternalClient

    insecure = InternalClient(insecure_tls=True)
    secure = InternalClient()
    assert insecure._ssl_context is not None
    assert insecure._ssl_context.verify_mode == __import__("ssl").CERT_NONE
    assert secure._ssl_context is None


def test_max_writes_per_request(node_api):
    node, api = node_api
    req("POST", f"{node}/index/i", {})
    req("POST", f"{node}/index/i/field/f", {})
    api.max_writes_per_request = 3
    ok = " ".join(f"Set({c}, f=1)" for c in range(3))
    assert req("POST", f"{node}/index/i/query", ok.encode())["results"] == [True] * 3
    too_many = " ".join(f"Set({c}, f=1)" for c in range(10, 14))
    with pytest.raises(urllib.error.HTTPError) as e:
        req("POST", f"{node}/index/i/query", too_many.encode())
    assert e.value.code == 400
    assert "max-writes-per-request" in json.loads(e.value.read())["error"]
    # reads are unaffected
    assert req("POST", f"{node}/index/i/query", b"Count(Row(f=1))")["results"] == [3]


def test_import_roaring_edge_respects_max_writes(node_api):
    """max-writes-per-request covers the roaring route's EDGE bodies too
    (413, like /import) — the cheapest encoding must not bypass the
    admission limit; routed internal slices (?remote=true) are exempt."""
    from pilosa_tpu.roaring import RoaringBitmap, serialize

    node, api = node_api
    req("POST", f"{node}/index/i", {})
    req("POST", f"{node}/index/i/field/f", {})
    api.max_writes_per_request = 3
    body = serialize(RoaringBitmap.from_ids([1, 2, 3, 4, 5]))
    with pytest.raises(urllib.error.HTTPError) as e:
        req("POST", f"{node}/index/i/field/f/import-roaring/0", body,
            content_type="application/octet-stream")
    assert e.value.code == 413
    out = req("POST",
              f"{node}/index/i/field/f/import-roaring/0?remote=true",
              body, content_type="application/octet-stream")
    assert out["changed"] == 5


def test_bind_failure_raises_oserror_not_attributeerror():
    """TCPServer.__init__ calls server_close on a bind failure; the
    connection registry must already exist so the REAL error (port in
    use) surfaces."""
    import socket

    from pilosa_tpu.server.http import make_http_server

    srv = socket.create_server(("localhost", 0))
    busy_port = srv.getsockname()[1]
    try:
        with pytest.raises(OSError):
            make_http_server(None, "localhost", busy_port)
    finally:
        srv.close()


def test_import_roaring_malformed_upstream_blob_is_400(node):
    req("POST", f"{node}/index/i", {})
    req("POST", f"{node}/index/i/field/f", {})
    # pilosa cookie (12348) but truncated body: clean 400, not a 500
    with pytest.raises(urllib.error.HTTPError) as e:
        req("POST", f"{node}/index/i/field/f/import-roaring/0",
            b"\x3c\x30\x00\x00\x01", content_type="application/octet-stream")
    assert e.value.code == 400


def test_request_level_query_options(node):
    """URL params columnAttrs / excludeColumns / excludeRowAttrs apply to
    row results of the whole request (reference handler query args;
    SURVEY-MED spelling — names mirror the PQL Options() args)."""
    req("POST", f"{node}/index/i", {})
    req("POST", f"{node}/index/i/field/f", {})
    req("POST", f"{node}/index/i/query",
        b'Set(1, f=1) Set(2, f=1) SetColumnAttrs(1, city="nyc") '
        b'SetRowAttrs(f, 1, team="blue")')
    base = req("POST", f"{node}/index/i/query", b"Row(f=1)")["results"][0]
    assert base["columns"] == [1, 2] and base["attrs"] == {"team": "blue"}

    out = req("POST", f"{node}/index/i/query?columnAttrs=true",
              b"Row(f=1)")["results"][0]
    assert out["columnAttrs"] == [{"id": 1, "attrs": {"city": "nyc"}}]

    out = req("POST", f"{node}/index/i/query?excludeRowAttrs=true",
              b"Row(f=1)")["results"][0]
    assert out["attrs"] == {} and out["columns"] == [1, 2]

    out = req("POST",
              f"{node}/index/i/query?excludeColumns=true&columnAttrs=true",
              b"Row(f=1)")["results"][0]
    assert out["columns"] == [] and out["attrs"] == {"team": "blue"}
    assert out["columnAttrs"] == [{"id": 1, "attrs": {"city": "nyc"}}]


def test_fragment_nodes_route(node):
    """GET /internal/fragment/nodes reports shard ownership (reference
    clients route imports/queries with it)."""
    req("POST", f"{node}/index/i", {})
    out = req("GET", f"{node}/internal/fragment/nodes?index=i&shard=3")
    assert isinstance(out, list) and out and "uri" in out[0]


def test_import_with_timestamps_lands_in_time_views(node):
    """Timestamped bulk import writes the standard view AND each quantum
    view (batched per view, not per bit); Row(from=, to=) sees them."""
    req("POST", f"{node}/index/t", {})
    req("POST", f"{node}/index/t/field/ev",
        {"options": {"type": "time", "timeQuantum": "YMD"}})
    out = req("POST", f"{node}/index/t/field/ev/import", {
        "rows": [1, 1, 1, 2],
        "columns": [10, 11, 12, 10],
        "timestamps": ["2019-01-15T00:00", "2019-03-02T00:00",
                       None, "2019-01-15T00:00"],
    })
    assert out["changed"] == 4
    out = req("POST", f"{node}/index/t/query", b"Row(ev=1)")
    assert out["results"][0]["columns"] == [10, 11, 12]
    out = req("POST", f"{node}/index/t/query",
              b"Row(ev=1, from='2019-01-01T00:00', to='2019-02-01T00:00')")
    assert out["results"][0]["columns"] == [10]
    out = req("POST", f"{node}/index/t/query",
              b"Row(ev=1, from='2019-01-01T00:00', to='2019-12-31T00:00')")
    assert out["results"][0]["columns"] == [10, 11]
    # the un-timestamped bit exists only in standard
    out = req("POST", f"{node}/index/t/query",
              b"Row(ev=2, from='2019-01-01T00:00', to='2019-02-01T00:00')")
    assert out["results"][0]["columns"] == [10]
