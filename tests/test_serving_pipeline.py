"""Serving-path pipelining: ClusterExecutor.submit + the coalescing
HTTP query pipeline (server/pipeline.py).

The reference serves N concurrent queries with ~linear throughput via
per-request mapReduce goroutines (SURVEY.md §2 #12, §3.2). On a TPU
backend the equivalent property is DISPATCH sharing: concurrent requests
must coalesce into micro-batched device programs instead of each paying
the host→device latency floor. These tests pin (a) result equivalence
between the pipelined and eager paths, over HTTP and in-process, and
(b) the coalescing itself, by counting batched-program builds.
"""

import threading
import urllib.request

import pytest

from cluster_helpers import make_cluster, req, seed, uri
from pilosa_tpu.server.pipeline import QueryPipeline
from pilosa_tpu.shardwidth import SHARD_WIDTH

READ_QUERIES = [
    "Count(Row(f=1))",
    "Row(f=2)",
    "Union(Row(f=1), Row(f=2))",
    "Count(Intersect(Row(f=1), Row(f=2)))",
    'Sum(Row(f=1), field="v")',
    'Min(field="v")',
    'Max(field="v")',
    "TopN(f, n=3)",
    "TopN(f, n=10, threshold=15)",
    "Rows(f)",
    "Rows(f, limit=1)",
    "GroupBy(Rows(f))",
    "GroupBy(Rows(f), having=Condition(count > 8))",
    "Options(Count(Row(f=1)), shards=[0, 2])",
    "Count(Not(Row(f=1)))",
]


class TestClusterSubmit:
    """ClusterExecutor.submit: pipelined results == eager execute, with
    real remote fan-out (3 nodes, shards spread across them)."""

    def test_submit_matches_execute_across_nodes(self, tmp_path):
        servers = make_cluster(tmp_path, 3)
        try:
            seed(servers[0])
            ex = servers[1].api.executor  # a non-coordinator node
            want = [ex.execute("i", q)[0] for q in READ_QUERIES]
            # submit the WHOLE stream first, then resolve — the remote
            # fan-outs and local enqueues of all queries overlap
            defs = [ex.submit("i", q)[0] for q in READ_QUERIES]
            got = [d.result() for d in defs]
            from pilosa_tpu.executor.result import result_to_json

            for q, g, w in zip(READ_QUERIES, got, want):
                assert result_to_json(g) == result_to_json(w), q
        finally:
            for s in servers:
                s.close()

    def test_submit_remote_flag_stays_local(self, tmp_path):
        """remote=True sub-queries must evaluate strictly locally (no
        re-fan-out), same as execute(remote=True)."""
        servers = make_cluster(tmp_path, 2)
        try:
            seed(servers[0])
            for s in servers:
                local_shards = sorted(
                    s.holder.index("i").available_shards()
                )
                want = s.api.executor.execute(
                    "i", "Count(Row(f=1))", shards=local_shards, remote=True
                )
                got = [
                    d.result() for d in s.api.executor.submit(
                        "i", "Count(Row(f=1))", shards=local_shards,
                        remote=True,
                    )
                ]
                assert got == want
        finally:
            for s in servers:
                s.close()


class TestHTTPServing:
    """Concurrent HTTP clients against one server: results must equal
    serial execution and the wave pipeline must coalesce dispatches."""

    N_THREADS = 24

    def _concurrent(self, url, queries):
        results = [None] * len(queries)
        errors = []
        gate = threading.Event()

        def worker(k, q):
            gate.wait(10)
            try:
                results[k] = req("POST", url, q.encode())
            except Exception as e:  # pragma: no cover - surfaced below
                errors.append((q, e))

        threads = [
            threading.Thread(target=worker, args=(k, q))
            for k, q in enumerate(queries)
        ]
        for t in threads:
            t.start()
        gate.set()
        for t in threads:
            t.join(60)
        assert not errors, errors[:3]
        return results

    def test_concurrent_load_matches_serial_mesh_on(self, tmp_path):
        """The load test: mesh-backed single-node server, N
        concurrent clients, per-query results identical to serial."""
        servers = make_cluster(tmp_path, 1, use_mesh=True)
        try:
            seed(servers[0])
            url = f"{uri(servers[0])}/index/i/query"
            queries = [
                READ_QUERIES[k % len(READ_QUERIES)]
                for k in range(self.N_THREADS)
            ]
            serial = [req("POST", url, q.encode()) for q in queries]
            concurrent = self._concurrent(url, queries)
            assert concurrent == serial
            pipe = servers[0].api._pipeline
            assert pipe is not None and pipe.waves >= 1
        finally:
            servers[0].close()

    def test_wave_coalesces_same_shape_counts(self, tmp_path):
        """Deterministic dispatch accounting: hold the wave gate until
        every request is queued, then count batched-program builds — 32
        same-shape Counts must share micro-batched dispatches instead of
        paying 32."""
        servers = make_cluster(tmp_path, 1, use_mesh=True)
        try:
            seed(servers[0])
            api = servers[0].api
            n = 32

            class Gated(QueryPipeline):
                def __init__(self, api, expected):
                    super().__init__(api)
                    self.expected = expected
                    self.arrived = 0
                    self.alock = threading.Lock()
                    self.gate = threading.Event()

                def run(self, index, query, kwargs, key=None):
                    with self.alock:
                        self.arrived += 1
                        if self.arrived >= self.expected:
                            self.gate.set()
                    self.gate.wait(30)
                    # key deliberately NOT forwarded: this test counts
                    # device dispatches across DISTINCT submits, so the
                    # identical-query dedupe (covered by its own tests)
                    # must stay out of the way
                    return super().run(index, query, kwargs)

            dist = api.executor.local
            url = f"{uri(servers[0])}/index/i/query"
            queries = [
                f"Count(Intersect(Row(f={1 + (k % 2)}), Row(f=2)))"
                for k in range(n)
            ]
            serial_want = req("POST", url, queries[0].encode())
            api._pipeline = Gated(api, n)

            builds = []
            orig = dist._program_batched

            def counting(structure, rk, lr, ns, nq):
                builds.append(nq)
                return orig(structure, rk, lr, ns, nq)

            dist._program_batched = counting
            out = self._concurrent(url, queries)
            dist._program_batched = orig
            for k, q in enumerate(queries):
                if q == queries[0]:
                    assert out[k] == serial_want
            # all queries went through batched programs, in far fewer
            # dispatches than queries (ideally 1-4 waves); batch sizes
            # pad to powers of two (at most 2x the real rows)
            assert n <= sum(builds) <= 2 * n, builds
            assert len(builds) <= n // 2, builds
            assert all(b & (b - 1) == 0 for b in builds), builds
        finally:
            servers[0].close()

    def test_gather_window_coalesces_under_pressure(self):
        """_gather unit behavior: under pressure (small inter-arrival
        gap) the dispatcher holds the wave open and absorbs stragglers;
        with sparse traffic it returns immediately with no window wait.
        Generous timings so a loaded CI box cannot flake the assertion
        in the strict direction (stretched sleeps only ADD stragglers
        to the window)."""
        import time as _time

        pipe = QueryPipeline(api=None)
        pipe.GATHER_WINDOW_S = 0.25
        pipe._recent_gap = 0.0  # pressure: arrivals back-to-back
        for i in range(3):
            pipe._q.put(i)  # already queued: greedy drain picks up

        def feeder():
            for i in range(5):
                _time.sleep(0.01)
                pipe._q.put(100 + i)

        t = threading.Thread(target=feeder)
        t.start()
        wave = [pipe._q.get()]
        pipe._gather(wave)
        t.join()
        # 1 + 2 drained + stragglers caught inside the 250 ms window;
        # floor not equality: a stretched CI scheduler can push late
        # feeder puts past the deadline, never add extras
        assert 4 <= len(wave) <= 8, len(wave)

        pipe._recent_gap = 1.0  # sparse: no pressure
        pipe._q.put(1)
        wave = [pipe._q.get()]
        t0 = _time.monotonic()
        pipe._gather(wave)
        assert _time.monotonic() - t0 < 0.05  # zero-wait fast path
        assert len(wave) == 1

        # already-queued items are free: the greedy drain is unbounded
        # (a mixed-shape backlog must reach one submit), while the
        # WINDOW phase stops waiting at the cap
        pipe._recent_gap = 0.0
        n = pipe.GATHER_CAP + 5
        for i in range(n):
            pipe._q.put(i)
        wave = [pipe._q.get()]
        t0 = _time.monotonic()
        pipe._gather(wave)
        assert len(wave) == n, len(wave)  # all n drained, none left
        # and the full wave means the window never opened (no 2 ms wait
        # beyond at most one timed get)
        assert _time.monotonic() - t0 < 0.1

    def test_mixed_reads_and_writes_concurrent(self, tmp_path):
        """Writes take the eager routed path, reads the pipeline —
        interleaved concurrent traffic must neither deadlock nor lose
        writes."""
        servers = make_cluster(tmp_path, 1, use_mesh=False)
        try:
            seed(servers[0])
            url = f"{uri(servers[0])}/index/i/query"
            ops = []
            for k in range(16):
                if k % 4 == 0:
                    ops.append(f"Set({7 * SHARD_WIDTH + k}, f=9)")
                else:
                    ops.append("Count(Row(f=1))")
            out = self._concurrent(url, ops)
            for k, op in enumerate(ops):
                if op.startswith("Set"):
                    assert out[k] == {"results": [True]}
            final = req("POST", url, b"Count(Row(f=9))")
            assert final == {"results": [4]}
        finally:
            servers[0].close()

    def test_read_falls_back_to_surviving_replica(self, tmp_path):
        """A replica that fails its sub-query is marked DEGRADED and its
        shards are retried on surviving replicas — a single-replica
        fault must not 500 a read when live replicas hold the data."""
        servers = make_cluster(tmp_path, 3, replica_n=2)
        try:
            n_shards = 16
            seed(servers[0], n_shards=n_shards)
            url = f"{uri(servers[0])}/index/i/query"
            assert req("POST", url, b"Count(Row(f=1))") == {
                "results": [4 * n_shards]
            }
            # pick the victim DETERMINISTICALLY: a node that node 0's
            # router would actually target first for some shard it does
            # not replicate itself (ring assignment is deterministic)
            cluster0 = servers[0].api.cluster
            routed_first = set()
            for s in range(n_shards):
                ns = cluster0.shard_nodes("i", s)
                if not any(n.id == "n0" for n in ns):
                    routed_first.add(ns[0].id)
            assert routed_first, "every shard is local to n0?"
            victim = next(s for s in servers[1:]
                          if s.api.cluster.local.id in routed_first)
            victim._http.shutdown()
            victim._http.server_close()
            for q, want in [
                (b"Count(Row(f=1))", [4 * n_shards]),
                (b"TopN(f, n=2)",
                 [[{"id": 1, "count": 4 * n_shards},
                   {"id": 2, "count": 2 * n_shards}]]),
            ]:
                assert req("POST", url, q) == {"results": want}, q
            states = {
                n.id: n.state
                for n in servers[0].api.cluster.sorted_nodes()
            }
            assert states[victim.api.cluster.local.id] == "DEGRADED", states
        finally:
            for s in servers:
                try:
                    s.close()
                except Exception:
                    pass

    def test_rowwide_write_tolerates_dead_replica(self, tmp_path):
        """Store/ClearRow skip an unreachable replica (DEGRADED) instead
        of 500ing after the live replicas already applied the write."""
        servers = make_cluster(tmp_path, 3, replica_n=2)
        try:
            seed(servers[0], n_shards=8)
            victim = servers[2]
            victim._http.shutdown()
            victim._http.server_close()
            url = f"{uri(servers[0])}/index/i/query"
            assert req("POST", url, b"Store(Row(f=1), f=9)") == {
                "results": [True]
            }
            assert req("POST", url, b"ClearRow(f=2)") == {"results": [True]}
            assert req("POST", url, b"Count(Row(f=9))") == {"results": [32]}
            assert req("POST", url, b"Count(Row(f=2))") == {"results": [0]}
        finally:
            for s in servers:
                try:
                    s.close()
                except Exception:
                    pass

    def test_4xx_from_replica_is_not_a_node_fault(self, tmp_path,
                                                  monkeypatch):
        """A deterministic query rejection (HTTP 4xx) from a remote
        replica must propagate to the client — every replica would
        answer identically, so retrying siblings and DEGRADING the
        healthy node would poison routing for one bad query."""
        from pilosa_tpu.parallel.client import ClientError, InternalClient

        servers = make_cluster(tmp_path, 3, replica_n=1)
        try:
            seed(servers[0], n_shards=8)
            real = InternalClient.query_node
            calls = {"n": 0}

            def reject(client, uri, index, pql, shards, remote=True):
                if "Count" in pql:
                    calls["n"] += 1
                    raise ClientError("injected 400", status=400)
                return real(client, uri, index, pql, shards, remote=remote)

            monkeypatch.setattr(InternalClient, "query_node", reject)
            url = f"{uri(servers[0])}/index/i/query"
            with pytest.raises(urllib.error.HTTPError) as ei:
                req("POST", url, b"Count(Row(f=1))")
            # surfaces as a CLIENT error (400), not 'internal' 500
            assert ei.value.code == 400, ei.value.code
            # only first-choice replicas were tried — 2 remote groups
            # from node 0 (nodes n1 and n2), no sibling retries
            assert 1 <= calls["n"] <= 2, calls
            states = {n.id: n.state
                      for n in servers[0].api.cluster.sorted_nodes()}
            assert all(s == "NORMAL" for s in states.values()), states
        finally:
            for s in servers:
                s.close()

    def test_404_schema_lag_retries_sibling_without_degrading(
        self, tmp_path, monkeypatch
    ):
        """A 404 from a replica is ambiguous (could be schema lag, not a
        bad query): the read must retry the shard's sibling replica and
        succeed, and the lagging node must NOT be marked DEGRADED."""
        from pilosa_tpu.parallel.client import ClientError, InternalClient

        servers = make_cluster(tmp_path, 3, replica_n=2)
        try:
            n_shards = 16
            seed(servers[0], n_shards=n_shards)
            cluster0 = servers[0].api.cluster
            routed_first = set()
            for s in range(n_shards):
                ns = cluster0.shard_nodes("i", s)
                if not any(n.id == "n0" for n in ns):
                    routed_first.add(ns[0].id)
            victim = next(s for s in servers[1:]
                          if s.api.cluster.local.id in routed_first)
            victim_port = victim.port
            real = InternalClient.query_node

            def lag(client, node_uri, index, pql, shards, remote=True):
                if str(victim_port) in node_uri and "Count" in pql:
                    raise ClientError("index 'i' not found", status=404)
                return real(client, node_uri, index, pql, shards,
                            remote=remote)

            monkeypatch.setattr(InternalClient, "query_node", lag)
            url = f"{uri(servers[0])}/index/i/query"
            assert req("POST", url, b"Count(Row(f=1))") == {
                "results": [4 * n_shards]
            }
            states = {n.id: n.state
                      for n in servers[0].api.cluster.sorted_nodes()}
            assert all(s == "NORMAL" for s in states.values()), states
        finally:
            for s in servers:
                s.close()

    def test_concurrent_first_writes_create_one_fragment(self, tmp_path):
        """Concurrent FIRST writes into brand-new shards/views must all
        land in one Fragment per path: the old unlocked check-then-create
        handed racing writer threads distinct Fragment objects for the
        same file and silently dropped the losers' acknowledged bits
        (reproduced ~1-in-10 under the mixed-traffic load test)."""
        servers = make_cluster(tmp_path, 1, use_mesh=False)
        try:
            req("POST", f"{uri(servers[0])}/index/i", {})
            req("POST", f"{uri(servers[0])}/index/i/field/g", {})
            url = f"{uri(servers[0])}/index/i/query"
            for round_ in range(6):  # fresh shards each round
                base = (50 + round_) * SHARD_WIDTH
                ops = [f"Set({base + k}, g={round_})" for k in range(12)]
                out = self._concurrent(url, ops)
                assert all(r == {"results": [True]} for r in out), out
                final = req("POST", url, f"Count(Row(g={round_}))".encode())
                assert final == {"results": [12]}, (round_, final)
        finally:
            servers[0].close()

    def test_bad_query_in_wave_does_not_poison_wavemates(self, tmp_path):
        """One request erroring at submit time (unknown field) must fail
        ALONE; the other requests coalesced into the same wave still
        resolve correctly."""
        servers = make_cluster(tmp_path, 1, use_mesh=False)
        try:
            seed(servers[0])
            url = f"{uri(servers[0])}/index/i/query"
            queries = (["Count(Row(f=1))"] * 6
                       + ["Count(Row(nosuch=1))"]
                       + ["Count(Row(f=2))"] * 5)
            results = [None] * len(queries)
            gate = threading.Event()

            def worker(k, q):
                gate.wait(10)
                try:
                    results[k] = req("POST", url, q.encode())
                except urllib.error.HTTPError as e:
                    results[k] = ("http-error", e.code)

            threads = [threading.Thread(target=worker, args=(k, q))
                       for k, q in enumerate(queries)]
            for t in threads:
                t.start()
            gate.set()
            for t in threads:
                t.join(60)
            for q, r in zip(queries, results):
                if "nosuch" in q:
                    assert r == ("http-error", 400), r
                elif "f=1" in q:
                    assert r == {"results": [24]}, (q, r)
                else:
                    assert r == {"results": [12]}, (q, r)
        finally:
            servers[0].close()

    def test_error_propagates_through_pipeline(self, tmp_path):
        servers = make_cluster(tmp_path, 1, use_mesh=False)
        try:
            seed(servers[0])
            url = f"{uri(servers[0])}/index/i/query"
            with pytest.raises(urllib.error.HTTPError) as ei:
                req("POST", url, b"Count(Row(nosuch=1))")
            assert ei.value.code == 400
            # the pipeline survives the error and keeps serving
            out = req("POST", url, b"Count(Row(f=1))")
            assert out == {"results": [24]}
        finally:
            servers[0].close()
