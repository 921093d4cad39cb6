"""Serving fast-lane tests (ISSUE 4): keep-alive connection pooling
lifecycle, pre-serialized responses, pipeline dedupe, and cluster-wide
wave batching. `make serving-smoke` gates on this file: the
connection-count oracle proves keep-alive reuse, and the batch route
must return byte-identical results vs per-query dispatch."""

import json
import socket
import threading
import time
import urllib.request

import numpy as np
import pytest

from pilosa_tpu.parallel.client import ClientError, InternalClient
from pilosa_tpu.parallel.connpool import ConnectionPool
from pilosa_tpu.server.api import API
from pilosa_tpu.server.http import serve_in_thread
from pilosa_tpu.storage import Holder


@pytest.fixture
def node_api(tmp_path):
    holder = Holder(str(tmp_path / "data")).open()
    api = API(holder)
    server, port, _ = serve_in_thread(api)
    yield f"http://localhost:{port}", api, server
    server.shutdown()
    server.server_close()
    holder.close()


def _post_query(client, node, pql):
    """Edge query with NO shards/remote params — the dedupe-eligible
    request shape (api.query_raw only keys plain edge reads)."""
    return client._call("POST", f"{node}/index/i/query", pql.encode(),
                        content_type="text/plain")


def _seed(node, api, rows=4, per_row=16):
    client = InternalClient()
    client._call("POST", f"{node}/index/i", b"{}")
    client._call("POST", f"{node}/index/i/field/f", b"{}")
    body = {"rows": [], "columns": []}
    for r in range(1, rows + 1):
        body["rows"] += [r] * per_row
        body["columns"] += [r * 3 + 7 * c for c in range(per_row)]
    client._call("POST", f"{node}/index/i/field/f/import",
                 json.dumps(body).encode())
    return client


# ------------------------------------------------------------ pool lifecycle


class TestConnectionPool:
    def test_reuse_across_requests_connection_oracle(self, node_api):
        """N sequential requests through one client ride ONE server
        connection — the keep-alive oracle."""
        node, api, server = node_api
        client = _seed(node, api)
        base_conns = server.connections_opened
        for _ in range(20):
            out = client.query_node(node, "i", "Count(Row(f=1))",
                                    shards=[0], remote=False)
            assert out == {"results": [16]}
        with server.metrics_lock:
            new_conns = server.connections_opened - base_conns
        assert new_conns == 0  # the seeding connection is still serving
        m = client.pool.metrics()
        assert m["pool_connections_created_total"] == 1
        assert m["pool_connections_reused_total"] >= 20

    def test_chunked_request_body_rejected_411_and_connection_closed(
            self, node_api):
        """Chunked bodies can't be drained by the Content-Length logic;
        the server must 411 and close rather than let chunk framing
        poison the next request on the connection."""
        import http.client as hc

        node, api, server = node_api
        host, port = node.replace("http://", "").split(":")
        conn = hc.HTTPConnection(host, int(port), timeout=10)
        conn.putrequest("POST", "/index/i/query")
        conn.putheader("Transfer-Encoding", "chunked")
        conn.endheaders()
        conn.send(b"5\r\nCount\r\n0\r\n\r\n")
        resp = conn.getresponse()
        assert resp.status == 411
        assert "chunked" in json.loads(resp.read())["error"]
        assert resp.will_close
        conn.close()

    def test_keepalive_survives_error_responses_and_unread_bodies(
            self, node_api):
        """Error paths must drain unread bodies: a 404 route with a
        body, then a 400 PQL error, then a good query — all on the same
        pooled connection, with no desync."""
        node, api, server = node_api
        client = _seed(node, api)
        with pytest.raises(ClientError) as e:
            client._call("POST", f"{node}/no/such/route", b"x" * 4096)
        assert e.value.status == 404
        with pytest.raises(ClientError) as e:
            client.query_node(node, "i", "Bogus(", shards=[0], remote=False)
        assert e.value.status == 400
        out = client.query_node(node, "i", "Count(Row(f=2))",
                                shards=[0], remote=False)
        assert out == {"results": [16]}
        assert client.pool.metrics()["pool_connections_created_total"] == 1

    def test_half_closed_idle_socket_detected_and_replaced(self):
        """A server that closes idle keep-alive connections (FIN while
        pooled) must not produce request failures: checkout detects the
        readable/EOF socket, discards it, and reconnects."""
        done = threading.Event()
        response = (b"HTTP/1.1 200 OK\r\nContent-Type: application/json\r\n"
                    b"Content-Length: 2\r\n\r\n{}")
        srv = socket.create_server(("localhost", 0))
        port = srv.getsockname()[1]

        def serve():
            # serve exactly one request per connection, then close the
            # socket WITHOUT Connection: close (the keep-alive lie)
            for _ in range(2):
                conn, _ = srv.accept()
                conn.recv(65536)
                conn.sendall(response)
                conn.close()
            done.set()

        t = threading.Thread(target=serve, daemon=True)
        t.start()
        pool = ConnectionPool(timeout=5)
        try:
            assert pool.request("GET", f"http://localhost:{port}/x").data \
                == b"{}"
            time.sleep(0.1)  # let the FIN land on the pooled socket
            assert pool.request("GET", f"http://localhost:{port}/x").data \
                == b"{}"
            assert done.wait(5)
            m = pool.metrics()
            assert m["pool_connections_created_total"] == 2
            assert m["pool_connections_discarded_total"] >= 1
        finally:
            pool.close()
            srv.close()

    def test_stale_reuse_race_retries_on_fresh_connection(self):
        """The keep-alive race: the server closes the pooled connection
        only AFTER our request bytes land (no FIN visible at checkout).
        The pool must retry exactly once on a fresh connection."""
        response = (b"HTTP/1.1 200 OK\r\nContent-Type: application/json\r\n"
                    b"Content-Length: 2\r\n\r\n{}")
        srv = socket.create_server(("localhost", 0))
        port = srv.getsockname()[1]
        accepted = []

        def serve():
            # conn 1: answer request A, then close upon receiving B's
            # bytes (mid-request close -> RemoteDisconnected on reuse);
            # conn 2: answer the retried B
            conn, _ = srv.accept()
            accepted.append(1)
            conn.recv(65536)
            conn.sendall(response)
            conn.recv(65536)  # request B arrives on the reused conn
            conn.close()      # ...and dies without a response
            conn2, _ = srv.accept()
            accepted.append(2)
            conn2.recv(65536)
            conn2.sendall(response)
            conn2.close()

        threading.Thread(target=serve, daemon=True).start()
        pool = ConnectionPool(timeout=5)
        try:
            assert pool.request("GET", f"http://localhost:{port}/a").status \
                == 200
            assert pool.request("GET", f"http://localhost:{port}/b").status \
                == 200
            assert accepted == [1, 2]
            assert pool.metrics()["pool_connections_discarded_total"] >= 1
        finally:
            pool.close()
            srv.close()

    def test_dead_node_fails_fast_and_pools_nothing(self):
        """Connect refused on a fresh connection propagates (no retry
        loop), maps to a node-fault ClientError, and leaves nothing
        pooled for the dead peer."""
        srv = socket.create_server(("localhost", 0))
        port = srv.getsockname()[1]
        srv.close()  # nothing listens here any more
        client = InternalClient(timeout=2)
        with pytest.raises(ClientError) as e:
            client.status(f"http://localhost:{port}")
        assert e.value.status is None and e.value.is_node_fault
        assert client.pool.metrics()["pool_idle_connections"] == 0

    def test_concurrent_requests_use_distinct_connections(self, node_api):
        """Exclusive checkout: two in-flight requests (the shape of a
        hedge leg racing its primary — qos/hedge.py) can never share a
        socket; the second request opens connection #2."""
        node, api, server = node_api
        client = _seed(node, api)
        n = 4
        gate = threading.Event()
        errors = []

        def worker():
            gate.wait(5)
            try:
                # slow-ish request: enough work to overlap the others
                client.query_node(node, "i", "Row(f=1)", shards=[0],
                                  remote=False)
            except Exception as e:  # surfaced below
                errors.append(e)

        threads = [threading.Thread(target=worker) for _ in range(n)]
        for t in threads:
            t.start()
        gate.set()
        for t in threads:
            t.join(30)
        assert not errors
        m = client.pool.metrics()
        # the seed connection plus however many overlaps actually
        # happened; at least one overlap is effectively guaranteed with
        # 4 simultaneous requests
        assert 2 <= m["pool_connections_created_total"] <= n + 1
        assert m["pool_idle_connections"] == \
            m["pool_connections_created_total"] \
            - m["pool_connections_discarded_total"]

    def test_pool_bound_caps_idle_connections(self, node_api):
        node, api, server = node_api
        client = InternalClient(pool_size=2)
        gate = threading.Event()

        def worker():
            gate.wait(5)
            client.status(node)

        threads = [threading.Thread(target=worker) for _ in range(6)]
        for t in threads:
            t.start()
        gate.set()
        for t in threads:
            t.join(30)
        assert client.pool.metrics()["pool_idle_connections"] <= 2


# ----------------------------------------------------- responses + dedupe


class TestFastLaneResponses:
    def test_pre_serialized_bytes_match_legacy_json(self, node_api):
        """Every hot shape's pre-serialized bytes must parse to exactly
        the dict the legacy result_to_json envelope produced."""
        from pilosa_tpu.executor.result import (
            Pair,
            RowResult,
            ValCount,
            result_to_json,
            results_json_bytes,
        )
        from pilosa_tpu.ops.packing import pack_bits

        row = RowResult({0: pack_bits(np.array([1, 5, 9], np.uint64),
                                      1 << 20)})
        results = [7, True, False, None, ValCount(41, 3),
                   [Pair(2, 8), Pair(3, 5, key="k")], row,
                   ["a", "b"], [1, 2, 3]]
        data = results_json_bytes(results)
        assert json.loads(data) == {
            "results": [result_to_json(r) for r in results]
        }
        # RowResult encoding memoizes on the object (identity-keyed
        # encoded-bytes cache)
        assert row._json_bytes is not None
        again = results_json_bytes(results)
        assert again == data

    def test_identical_wave_dedupe_shares_results(self, node_api):
        """Identical concurrent queries collapse to one submit; every
        client still gets the (byte-identical) correct answer."""
        node, api, server = node_api
        client = _seed(node, api)
        serial = _post_query(client, node, "Count(Row(f=1))")

        # hold the dispatcher inside submit for the first (plug) query
        # so the identical burst piles into the NEXT wave deterministically
        real_executor = api.executor
        plug_seen = threading.Event()

        class SlowFirst:
            def __getattr__(self, name):
                return getattr(real_executor, name)

            def submit(self, index, query, **kwargs):
                if not plug_seen.is_set():
                    plug_seen.set()
                    time.sleep(0.8)
                return real_executor.submit(index, query, **kwargs)

        api.executor = SlowFirst()
        try:
            results = [None] * 9
            errors = []

            def worker(k):
                try:
                    results[k] = _post_query(client, node,
                                             "Count(Row(f=1))")
                except Exception as e:
                    errors.append(e)

            plug = threading.Thread(
                target=worker, args=(0,))
            plug.start()
            assert plug_seen.wait(10)
            time.sleep(0.1)  # burst lands while the dispatcher sleeps
            threads = [threading.Thread(target=worker, args=(k,))
                       for k in range(1, 9)]
            for t in threads:
                t.start()
            for t in [plug, *threads]:
                t.join(30)
        finally:
            api.executor = real_executor
        assert not errors
        assert all(r == serial for r in results)
        assert api._pipeline.deduped >= 7

    def test_deduped_error_reaches_every_request(self, node_api):
        """A shared submit that errors must fail EVERY deduped request
        with the same 400, not hang or poison followers."""
        node, api, server = node_api
        client = _seed(node, api)
        outcomes = []
        gate = threading.Event()

        def worker():
            gate.wait(5)
            try:
                _post_query(client, node, "Count(Row(ghost=1))")
                outcomes.append("ok")
            except ClientError as e:
                outcomes.append(e.status)

        threads = [threading.Thread(target=worker) for _ in range(6)]
        for t in threads:
            t.start()
        gate.set()
        for t in threads:
            t.join(30)
        assert outcomes == [400] * 6


# --------------------------------------------------------- batch route


class TestQueryBatchRoute:
    def test_batch_route_byte_identical_to_per_query(self, node_api):
        """The serving-smoke gate: each item of a batched response must
        be byte-for-byte the response the per-query route produces."""
        node, api, server = node_api
        client = _seed(node, api)
        items = [("i", "Count(Row(f=1))", [0]),
                 ("i", "Row(f=2)", [0]),
                 ("i", "TopN(f, n=2)", [0])]
        raw = client._call(
            "POST", f"{node}/internal/query-batch",
            json.dumps({"queries": [
                {"index": i, "query": q, "shards": s} for i, q, s in items
            ]}).encode(), raw=True)
        solo = [client._call(
            "POST", f"{node}/index/{i}/query?shards=0&remote=true",
            q.encode(), content_type="text/plain", raw=True)
            for i, q, _ in items]
        assert raw == b'{"responses":[' + b",".join(solo) + b"]}"

    def test_batch_items_are_isolated(self, node_api):
        """One bad item (missing index, write call, parse error) answers
        its own error; batchmates still succeed."""
        node, api, server = node_api
        client = _seed(node, api)
        out = client.query_batch(node, [
            ("i", "Count(Row(f=1))", [0]),
            ("nope", "Count(Row(f=1))", [0]),
            ("i", "Set(1, f=1)", [0]),
            ("i", "Bogus(", [0]),
            ("i", "Count(Row(f=3))", [0]),
        ])
        assert out[0] == {"results": [16]}
        assert out[1]["status"] == 404
        assert out[2]["status"] == 400 and "write" in out[2]["error"]
        assert out[3]["status"] == 400
        assert out[4] == {"results": [16]}

    def test_client_remembers_no_batch_peer(self, node_api):
        node, api, server = node_api
        client = _seed(node, api)
        assert client.supports_batch(node)
        # an old-wire peer answers 404 to the route and is remembered
        resp = (b"HTTP/1.1 404 Not Found\r\n"
                b"Content-Type: application/json\r\n"
                b"Content-Length: 22\r\n\r\n"
                b'{"error": "not found"}')
        srv = socket.create_server(("localhost", 0))
        port = srv.getsockname()[1]

        def serve():
            conn, _ = srv.accept()
            conn.recv(65536)
            conn.sendall(resp)
            conn.close()

        threading.Thread(target=serve, daemon=True).start()
        old_peer = f"http://localhost:{port}"
        try:
            with pytest.raises(ClientError) as e:
                client.query_batch(old_peer,
                                   [("i", "Count(Row(f=1))", [0])])
            assert e.value.status == 404
            assert not client.supports_batch(old_peer)
        finally:
            srv.close()


class TestWaveBatcher:
    class FakeClient:
        """Counting client: query_batch answers per item; optionally
        fails whole batches or lacks the route."""

        def __init__(self, fail=None, no_route=False, delay=0.0):
            self.batch_calls = []
            self.solo_calls = []
            self.fail = fail
            self.no_route = no_route
            self.delay = delay
            self._no_batch = set()

        def supports_batch(self, uri):
            return uri not in self._no_batch

        def query_node(self, uri, index, pql, shards, remote=True,
                       **kw):
            self.solo_calls.append((uri, pql, tuple(shards)))
            if self.delay:
                time.sleep(self.delay)
            return {"results": [f"solo:{pql}"]}

        def query_batch(self, uri, items):
            self.batch_calls.append((uri, list(items)))
            if self.no_route:
                self._no_batch.add(uri)
                raise ClientError("no route", status=404)
            if self.fail is not None:
                raise self.fail
            if self.delay:
                time.sleep(self.delay)
            return [{"results": [f"batch:{pql}"]} for _, pql, _ in items]

    class Node:
        def __init__(self, id="n1"):
            self.id = id
            self.uri = f"http://{id}"

    def _batcher(self, client):
        from pilosa_tpu.parallel.wavebatch import RemoteWaveBatcher

        return RemoteWaveBatcher(client)

    def test_group_commit_batches_concurrent_queries(self):
        client = self.FakeClient(delay=0.2)
        batcher = self._batcher(client)
        node = self.Node()
        results = [None] * 9
        gate = threading.Event()

        def worker(k):
            if k > 0:
                gate.wait(5)
            results[k] = batcher.query(node, "i", f"Count(Row(f={k}))",
                                       [k])

        leader = threading.Thread(target=worker, args=(0,))
        leader.start()
        time.sleep(0.05)  # leader's flush is in flight (solo, delayed)
        gate.set()
        rest = [threading.Thread(target=worker, args=(k,))
                for k in range(1, 9)]
        for t in rest:
            t.start()
        for t in [leader, *rest]:
            t.join(30)
        # the stragglers arriving during the leader's round trip must
        # have shipped as (at most a couple of) multi-query batches
        assert results[0] == {"results": ["solo:Count(Row(f=0))"]}
        for k in range(1, 9):
            assert results[k] == {"results": [f"batch:Count(Row(f={k}))"]}
        assert client.batch_calls  # a real batch formed
        assert batcher.metrics()["remote_batched_queries_total"] == 8

    def test_batch_transport_failure_fails_each_member_like_direct(self):
        """The leader's solo flush succeeds; two stragglers batch while
        it is in flight, the batch transport fails, and EACH straggler
        gets its own node-fault ClientError (replica fallback shape)."""
        client = self.FakeClient(fail=ClientError("boom"))
        batcher = self._batcher(client)
        node = self.Node()
        errors = {}
        gate = threading.Event()
        release = threading.Event()
        orig_solo = client.query_node

        def gated_solo(uri, index, pql, shards, remote=True, **kw):
            gate.set()
            release.wait(5)
            return orig_solo(uri, index, pql, shards, remote=remote, **kw)

        client.query_node = gated_solo

        def worker(k):
            try:
                batcher.query(node, "i", f"Q{k}", [k])
            except ClientError as e:
                errors[k] = e

        t0 = threading.Thread(target=worker, args=(0,))
        t0.start()
        assert gate.wait(5)  # leader's solo flush in flight
        t1 = threading.Thread(target=worker, args=(1,))
        t2 = threading.Thread(target=worker, args=(2,))
        t1.start()
        t2.start()
        time.sleep(0.1)
        release.set()
        for t in (t0, t1, t2):
            t.join(10)
        assert 0 not in errors  # the solo leader succeeded
        assert set(errors) == {1, 2}
        assert all(e.is_node_fault for e in errors.values())
        assert errors[1] is not errors[2]  # per-caller exception objects

    def test_malformed_batch_item_fails_only_its_slot_and_lane_survives(self):
        """A peer answering 200 with a malformed item (null) must fail
        THAT slot with a ClientError; well-formed batchmates resolve,
        nothing hangs, and the node's lane keeps working afterwards."""
        client = self.FakeClient()
        real_batch = client.query_batch

        def mangled(uri, items):
            out = real_batch(uri, items)
            out[0] = None  # malformed first item
            return out

        client.query_batch = mangled
        batcher = self._batcher(client)
        node = self.Node()
        gate = threading.Event()
        release = threading.Event()
        orig_solo = client.query_node

        def gated_solo(uri, index, pql, shards, remote=True, **kw):
            gate.set()
            release.wait(5)
            return orig_solo(uri, index, pql, shards, remote=remote, **kw)

        client.query_node = gated_solo
        outcomes = {}

        def worker(k):
            try:
                outcomes[k] = batcher.query(node, "i", f"Q{k}", [k])
            except ClientError as e:
                outcomes[k] = ("err", str(e))

        t0 = threading.Thread(target=worker, args=(0,))
        t0.start()
        assert gate.wait(5)
        t1 = threading.Thread(target=worker, args=(1,))
        t2 = threading.Thread(target=worker, args=(2,))
        t1.start()
        t2.start()
        time.sleep(0.1)
        release.set()
        for t in (t0, t1, t2):
            t.join(10)
        assert outcomes[0] == {"results": ["solo:Q0"]}
        assert outcomes[1][0] == "err" and "malformed" in outcomes[1][1]
        assert outcomes[2] == {"results": ["batch:Q2"]}
        # the lane is NOT wedged: a fresh query flushes normally
        client.query_node = orig_solo
        client.query_batch = real_batch
        assert batcher.query(node, "i", "Q9", [9]) == \
            {"results": ["solo:Q9"]}

    def test_no_route_peer_replays_individually_then_goes_direct(self):
        client = self.FakeClient(no_route=True, delay=0)
        batcher = self._batcher(client)
        node = self.Node()
        gate = threading.Event()
        release = threading.Event()
        orig_solo = client.query_node

        def gated_solo(uri, index, pql, shards, remote=True, **kw):
            if pql == "Q0":
                gate.set()
                release.wait(5)
            return orig_solo(uri, index, pql, shards, remote=remote, **kw)

        client.query_node = gated_solo
        results = {}

        def worker(k):
            results[k] = batcher.query(node, "i", f"Q{k}", [k])

        t0 = threading.Thread(target=worker, args=(0,))
        t0.start()
        assert gate.wait(5)
        t1 = threading.Thread(target=worker, args=(1,))
        t2 = threading.Thread(target=worker, args=(2,))
        t1.start()
        t2.start()
        time.sleep(0.1)
        release.set()
        for t in (t0, t1, t2):
            t.join(10)
        # first flush was solo (leader); the follow-up batch hit the 404
        # and replayed per-query; afterwards the peer is known no-batch
        assert results == {k: {"results": [f"solo:Q{k}"]} for k in range(3)}
        assert len(client.batch_calls) == 1
        assert batcher.metrics()["remote_batch_fallbacks_total"] >= 2


# ------------------------------------------------- cluster sync fast path


class TestEmptyFragmentProbe:
    def test_fetch_skips_payload_when_all_replicas_empty(self, tmp_path):
        """A legitimately-empty fragment is probed via the
        cheap block-checksum list, never re-fetched as a full payload."""
        from pilosa_tpu.parallel.cluster import Cluster, Node

        holder = Holder(str(tmp_path / "d")).open()
        holder.create_index("i").create_field("f")

        calls = {"blocks": 0, "data": 0}

        class FakeClient:
            def fragment_blocks(self, uri, index, field, view, shard):
                calls["blocks"] += 1
                return []  # empty on every replica

            def fragment_data(self, uri, index, field, view, shard):
                calls["data"] += 1
                return b""

        cluster = Cluster(Node("n0", "http://n0"), holder=holder)
        cluster.client = FakeClient()
        fetched = cluster.fetch_fragments([
            {"index": "i", "field": "f", "view": "standard", "shard": 0,
             "from": "http://n1", "fallbacks": ["http://n2"]},
        ])
        assert fetched == 0
        assert calls["blocks"] == 2  # probed both replicas
        assert calls["data"] == 0    # no full payload was transferred
        holder.close()

    def test_fetch_still_pulls_data_after_nonempty_probe(self, tmp_path):
        from pilosa_tpu.parallel.cluster import Cluster, Node
        from pilosa_tpu.roaring import RoaringBitmap
        from pilosa_tpu.roaring.format import serialize

        holder = Holder(str(tmp_path / "d")).open()
        holder.create_index("i").create_field("f")
        payload = serialize(RoaringBitmap.from_ids([1, 5, (1 << 20) - 1]))

        class FakeClient:
            def fragment_blocks(self, uri, index, field, view, shard):
                return [(0, "abc")]

            def fragment_data(self, uri, index, field, view, shard):
                return payload

        cluster = Cluster(Node("n0", "http://n0"), holder=holder)
        cluster.client = FakeClient()
        fetched = cluster.fetch_fragments([
            {"index": "i", "field": "f", "view": "standard", "shard": 0,
             "from": "http://n1"},
        ])
        assert fetched == 1
        frag = holder.index("i").field("f").view("standard").fragment(0)
        assert frag.count() == 3
        holder.close()


# --------------------------------------------------------------- config


def test_fastlane_config_knobs_round_trip():
    from pilosa_tpu.server.server import ServerConfig

    cfg = ServerConfig(client_pool_size=3, remote_batch=False)
    d = cfg.to_dict()
    assert d["client-pool-size"] == 3 and d["remote-batch"] is False
    back = ServerConfig.from_dict(d)
    assert back.client_pool_size == 3 and back.remote_batch is False
    # env-var style strings parse too
    assert ServerConfig.from_dict({"remote-batch": "false"}).remote_batch \
        is False


def test_generate_config_documents_fastlane_knobs(capsys):
    from pilosa_tpu import cli

    cli.main(["generate-config"])
    out = capsys.readouterr().out
    assert "client-pool-size" in out and "remote-batch" in out


def test_metrics_export_serving_fastlane_series(node_api):
    node, api, server = node_api
    text = urllib.request.urlopen(f"{node}/metrics").read().decode()
    for series in ("serving_pool_connections_created_total",
                   "serving_remote_batches_total",
                   "serving_deduped_requests_total",
                   "serving_http_connections_total",
                   "serving_http_requests_total"):
        assert f"pilosa_tpu_{series}" in text, series
    dv = json.loads(
        urllib.request.urlopen(f"{node}/debug/vars").read())
    assert "remote_batches_total" in dv["serving_fastlane"]
    assert dv["serving_fastlane"]["http_connections_total"] >= 1
