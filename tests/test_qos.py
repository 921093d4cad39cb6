"""Serving-QoS fault paths (pilosa_tpu/qos): admission shedding,
deadline propagation, hedged replica reads, circuit breaking.

Fault injection follows the repo idiom (test_serving_pipeline,
test_cluster): in-process servers with monkeypatched seams — a stalled
replica is that node's ``API.query_raw`` sleeping, a burst is real
concurrent HTTP clients against a blocked executor. The acceptance
shapes from ISSUE 1: a 5 s-stall replica at replica_n=2 answers a
500 ms-deadline query via hedge in < 500 ms; a burst beyond the
admission limit yields 429s (not queue growth); shed/hedge/deadline
series are visible in GET /metrics.
"""

import threading
import time
import urllib.error
import urllib.request

import pytest

from cluster_helpers import make_cluster, req, seed, uri
from pilosa_tpu.qos import (
    AdmissionController,
    AdmissionError,
    CircuitBreaker,
    Deadline,
    DeadlineExceeded,
    HedgePolicy,
)
from pilosa_tpu.shardwidth import SHARD_WIDTH


def _close_all(servers):
    for s in servers:
        s.close()


def _stall(server, seconds):
    """Make one node's query handling (local AND remote sub-queries)
    sleep: the slow-replica fault."""
    orig = server.api.query_raw

    def stalled(*args, **kwargs):
        time.sleep(seconds)
        return orig(*args, **kwargs)

    server.api.query_raw = stalled
    return orig


def _remote_shard(servers, index="i"):
    """A (shard, primary, replicas) triple whose owners exclude node 0,
    so a query from node 0 must take the remote fan-out."""
    cluster = servers[0].api.cluster
    for shard in range(64):
        owners = cluster.shard_nodes(index, shard)
        if all(n.id != cluster.local.id for n in owners):
            return shard, owners
    raise AssertionError("no shard routed fully remote from node 0")


# ---------------------------------------------------------------- unit: QoS


class TestDeadline:
    def test_after_and_expiry(self):
        d = Deadline.after(0.05)
        assert not d.expired
        assert 0 < d.remaining() <= 0.05
        d.check()  # not expired: no raise
        time.sleep(0.06)
        assert d.expired
        with pytest.raises(DeadlineExceeded):
            d.check("unit")

    def test_wire_budget_roundtrip(self):
        d = Deadline.after(0.5)
        ms = d.to_millis()
        assert 0 < ms <= 500
        d2 = Deadline.from_millis(ms)
        # re-anchored budget is within a scheduling hiccup of the original
        assert abs(d2.remaining() - d.remaining()) < 0.1

    def test_to_millis_floor(self):
        # an expired deadline still serializes to >= 1ms: expiry is
        # raised locally by check(), never encoded as a 0 budget
        assert Deadline.after(-1).to_millis() == 1


class TestAdmission:
    def test_global_limit_sheds_and_releases(self):
        gate = AdmissionController(max_inflight=2, retry_after=3.0)
        s1 = gate.admit("a")
        s2 = gate.admit("b")
        with pytest.raises(AdmissionError) as ei:
            gate.admit("c")
        assert ei.value.retry_after == 3.0
        assert gate.metrics() == {"admitted_total": 2, "shed_total": 1,
                                  "inflight": 2}
        s1.release()
        s1.release()  # idempotent: double release must not free 2 tokens
        gate.admit("c").release()
        s2.release()
        assert gate.inflight == 0

    def test_tenant_quota_isolates_hot_tenant(self):
        gate = AdmissionController(max_inflight=4, tenant_max=2)
        gate.admit("hot")
        gate.admit("hot")
        with pytest.raises(AdmissionError):  # hot tenant at its quota
            gate.admit("hot")
        # other tenants still admitted: the node has global headroom
        gate.admit("cold")
        gate.admit("cold2")

    def test_unlimited_gate_tracks_inflight(self):
        gate = AdmissionController()  # 0 = off
        slots = [gate.admit("t") for _ in range(100)]
        assert gate.inflight == 100
        for s in slots:
            s.release()
        assert gate.inflight == 0 and gate.shed == 0


class TestHedgePolicy:
    def test_delay_tracks_p95_after_warmup(self):
        pol = HedgePolicy(initial_delay=0.25)
        assert pol.delay() == 0.25  # cold: configured initial delay
        for _ in range(19):
            pol.record(0.010)
        assert pol.delay() == 0.25  # still under MIN_SAMPLES
        pol.record(0.010)
        assert abs(pol.delay() - 0.010) < 1e-9  # warmed: p95 of samples

    def test_budget_enforced_as_fraction_of_primaries(self):
        pol = HedgePolicy(budget_fraction=0.05)
        pol.note_primary()
        assert pol.try_hedge()  # the +1 seat: first slow read may hedge
        assert not pol.try_hedge()  # budget gone at 1 primary
        for _ in range(20):  # 21 primaries: 0.05*21+1 ≈ 2 hedge seats
            pol.note_primary()
        assert pol.try_hedge()
        assert not pol.try_hedge()
        m = pol.metrics()
        assert m["hedges_total"] == 2
        assert m["hedge_budget_denied_total"] == 2

    def test_zero_budget_never_hedges(self):
        pol = HedgePolicy(budget_fraction=0.0)
        for _ in range(100):
            pol.note_primary()
        assert not pol.try_hedge()


class TestCircuitBreaker:
    def test_open_half_open_close(self):
        br = CircuitBreaker(threshold=3, cooldown=0.05)
        for _ in range(2):
            br.record_failure()
        assert br.allow()  # under threshold: still closed
        br.record_failure()
        assert br.state == "open"
        assert not br.allow()  # open: fail fast
        time.sleep(0.06)
        assert br.allow()  # cooldown passed: the half-open probe
        assert not br.allow()  # exactly ONE probe, not a thundering herd
        br.record_success()
        assert br.state == "closed" and br.allow()

    def test_half_open_failure_reopens(self):
        br = CircuitBreaker(threshold=1, cooldown=0.05)
        br.record_failure()
        assert br.state == "open"
        time.sleep(0.06)
        assert br.allow()  # probe
        br.record_failure()  # probe failed
        assert br.state == "open" and not br.allow()
        assert br.opened_total == 2

    def test_stale_success_does_not_close_open_breaker(self):
        """A success from a read sent BEFORE the node flapped must not
        cancel the cooldown: only the half-open probe may close an open
        breaker, or traffic resumes to a still-sick node."""
        br = CircuitBreaker(threshold=1, cooldown=60)
        br.record_failure()
        assert br.state == "open"
        br.record_success()  # pre-flap in-flight read finally landed
        assert br.state == "open" and not br.allow()

    def test_inconclusive_probe_releases_seat(self):
        """A probe whose request dies without a node verdict (deadline
        expiry, deterministic 4xx) must release the half-open seat —
        otherwise allow() returns False forever and the node is locked
        out until restart."""
        br = CircuitBreaker(threshold=1, cooldown=0.05)
        br.record_failure()
        time.sleep(0.06)
        assert br.allow()  # the half-open probe
        br.record_inconclusive()  # e.g. the REQUEST's deadline expired
        assert br.state == "half-open"
        assert br.allow()  # seat released: the next request may probe
        br.record_success()
        assert br.state == "closed"


class TestBreakerClassification:
    def _exec(self):
        from pilosa_tpu.parallel.cluster_exec import ClusterExecutor
        from pilosa_tpu.qos import ServingQos

        ex = object.__new__(ClusterExecutor)  # classification needs only qos
        ex.qos = ServingQos()
        return ex

    def test_deadline_expiry_is_not_a_node_fault(self):
        """A transport timeout caused by the REQUEST's own capped TIGHT
        budget must not count against the node (deadline.py invariant):
        tight-deadline traffic would otherwise open a healthy node's
        breaker and fail generous-deadline queries behind it."""
        from pilosa_tpu.parallel.client import ClientError

        ex = self._exec()
        br = CircuitBreaker(threshold=1)
        expired = Deadline.after(-1)
        ex._record_breaker_outcome(
            br, ClientError("read timed out"), expired, elapsed=0.05)
        assert br.state == "closed"
        # a 4xx is deterministic — every replica would repeat it
        ex._record_breaker_outcome(
            br, ClientError("bad query", status=400), Deadline.after(10),
            elapsed=0.05)
        assert br.state == "closed"
        # the same transport fault with a LIVE budget is real evidence
        ex._record_breaker_outcome(
            br, ClientError("read timed out"), Deadline.after(10),
            elapsed=0.05)
        assert br.state == "open"

    def test_stalled_node_trips_breaker_even_at_expiry(self):
        """The converse guard: transport timeouts are budget-capped, so
        a truly stalled node always faults exactly at expiry — after it
        was given a fair chance (≥ 1 s and several× the hedge delay),
        the fault must count or its breaker would never open."""
        from pilosa_tpu.parallel.client import ClientError

        ex = self._exec()
        br = CircuitBreaker(threshold=1)
        ex._record_breaker_outcome(
            br, ClientError("read timed out"), Deadline.after(-0.001),
            elapsed=2.0)
        assert br.state == "open"


# --------------------------------------------------- integration: admission


class TestAdmissionOverHTTP:
    def test_burst_beyond_limit_yields_429_with_retry_after(self, tmp_path):
        """Acceptance: a burst beyond the admission limit sheds with 429
        + Retry-After while admitted requests complete — the queue does
        not grow. The executor is gated on an Event so 'in flight' is
        deterministic, not a race against service time."""
        from pilosa_tpu.server import Server, ServerConfig

        server = Server(ServerConfig(
            data_dir=str(tmp_path / "n0"), port=0, name="n0",
            anti_entropy_interval=0, heartbeat_interval=0, use_mesh=False,
            qos_max_inflight=2,
        )).open()
        try:
            base = uri(server)
            req("POST", f"{base}/index/i", {})
            req("POST", f"{base}/index/i/field/f", {})
            gate = threading.Event()
            entered = threading.Semaphore(0)
            real_exec = server.api.executor.execute

            def blocked_execute(*a, **k):
                entered.release()
                assert gate.wait(30)
                return real_exec(*a, **k)

            server.api.executor.execute = blocked_execute
            results: list = []

            def client():
                try:
                    # writes take the eager path (request thread blocks
                    # inside the gated executor = admitted and in flight)
                    results.append(
                        ("ok", req("POST", f"{base}/index/i/query",
                                   b"Set(1, f=1)"))
                    )
                except urllib.error.HTTPError as e:
                    results.append(
                        ("http", e.code, e.headers.get("Retry-After"))
                    )

            first = [threading.Thread(target=client) for _ in range(2)]
            for t in first:
                t.start()
            # both tokens taken (clients are INSIDE the executor) before
            # the burst fires, so every burst request must shed
            assert entered.acquire(timeout=10)
            assert entered.acquire(timeout=10)
            burst = [threading.Thread(target=client) for _ in range(6)]
            for t in burst:
                t.start()
            for t in burst:
                t.join(timeout=30)
            shed = [r for r in results if r[0] == "http"]
            assert len(shed) == 6, results
            assert all(code == 429 for _, code, _ in shed)
            assert all(ra is not None and int(ra) >= 1 for *_, ra in shed)
            gate.set()
            for t in first:
                t.join(timeout=30)
            assert sum(1 for r in results if r[0] == "ok") == 2
            # shed/admit decisions are exported on /metrics
            text = req("GET", f"{base}/metrics", raw=True).decode()
            assert "pilosa_tpu_qos_shed_total 6" in text
            assert "pilosa_tpu_qos_admitted_total 2" in text
        finally:
            gate.set()
            server.close()

    def test_tenant_header_drives_quota(self, tmp_path):
        """Per-tenant quotas key off X-Pilosa-Tenant: one tenant at its
        quota sheds while another sails through the same node."""
        from pilosa_tpu.server import Server, ServerConfig

        server = Server(ServerConfig(
            data_dir=str(tmp_path / "n0"), port=0, name="n0",
            anti_entropy_interval=0, heartbeat_interval=0, use_mesh=False,
            qos_max_inflight=8, qos_tenant_inflight=1,
        )).open()
        try:
            base = uri(server)
            req("POST", f"{base}/index/i", {})
            req("POST", f"{base}/index/i/field/f", {})
            gate = threading.Event()
            entered = threading.Semaphore(0)
            real_exec = server.api.executor.execute

            def blocked_execute(*a, **k):
                entered.release()
                assert gate.wait(30)
                return real_exec(*a, **k)

            server.api.executor.execute = blocked_execute

            def query(tenant):
                r = urllib.request.Request(
                    f"{base}/index/i/query", data=b"Set(1, f=1)",
                    method="POST", headers={"X-Pilosa-Tenant": tenant},
                )
                with urllib.request.urlopen(r, timeout=30) as resp:
                    return resp.status

            codes = {}
            t = threading.Thread(
                target=lambda: codes.__setitem__("first", query("alpha"))
            )
            t.start()
            assert entered.acquire(timeout=10)  # alpha is at quota 1
            with pytest.raises(urllib.error.HTTPError) as ei:
                query("alpha")
            assert ei.value.code == 429
            t2 = threading.Thread(
                target=lambda: codes.__setitem__("beta", query("beta"))
            )
            t2.start()
            assert entered.acquire(timeout=10)  # beta admitted regardless
            gate.set()
            t.join(timeout=30)
            t2.join(timeout=30)
            assert codes == {"first": 200, "beta": 200}
        finally:
            gate.set()
            server.close()


# ---------------------------------------------- integration: deadline/hedge


class TestDeadlineAndHedging:
    def test_stalled_replica_hedged_within_deadline(self, tmp_path):
        """THE acceptance shape: replica_n=2, the primary owner of a
        remote shard stalls 5 s, and a 500 ms-deadline query still
        answers correctly in < 500 ms because the hedge fires at the
        (lowered) hedge delay and the sibling replica wins the race."""
        servers = make_cluster(tmp_path, 3, replica_n=2)
        try:
            req("POST", f"{uri(servers[0])}/index/i", {})
            req("POST", f"{uri(servers[0])}/index/i/field/f", {})
            shard, owners = _remote_shard(servers)
            cols = [shard * SHARD_WIDTH + c for c in (1, 2, 3)]
            req("POST", f"{uri(servers[0])}/index/i/field/f/import",
                {"rows": [1] * len(cols), "columns": cols})
            # warm the exact query first (device-program compile, plan
            # caches, wire negotiation): the timed window below must
            # measure the HEDGE, not a cold first-compile. Hedging is
            # held off during warm-up — a slow cold compile must not
            # hedge and spend the single bootstrap budget seat
            # (0.05 * primaries + 1) the timed rescue below needs
            servers[0].api.qos.hedge.initial_delay = 30.0
            warm = req("POST", f"{uri(servers[0])}/index/i/query",
                       b"Count(Row(f=1))")
            assert warm["results"][0] == 3
            # the PRIMARY (first live owner = where node 0 routes) stalls
            by_id = {s.api.cluster.local.id: s for s in servers}
            _stall(by_id[owners[0].id], 5.0)
            # hedge fast (cold-start delay, no p95 history yet)
            servers[0].api.qos.hedge.initial_delay = 0.03

            r = urllib.request.Request(
                f"{uri(servers[0])}/index/i/query",
                data=b"Count(Row(f=1))", method="POST",
                headers={"X-Pilosa-Deadline-Ms": "500"},
            )
            t0 = time.monotonic()
            with urllib.request.urlopen(r, timeout=30) as resp:
                import json

                out = json.loads(resp.read())
            elapsed = time.monotonic() - t0
            assert out["results"][0] == 3
            assert elapsed < 0.5, f"hedge too slow: {elapsed:.3f}s"
            m = servers[0].api.qos.metrics()
            assert m["hedges_total"] >= 1
            assert m["hedge_wins_total"] >= 1
            # and the counters are scrapeable
            text = req("GET", f"{uri(servers[0])}/metrics",
                       raw=True).decode()
            assert "pilosa_tpu_qos_hedges_total" in text
            assert "pilosa_tpu_qos_deadline_expired_total" in text
        finally:
            _close_all(servers)

    def test_deadline_bounds_dead_sole_replica(self, tmp_path):
        """replica_n=1 with the sole owner stalled: no replica can save
        the read, so the deadline must bound it — 504 in ~budget, not
        the 30 s client timeout."""
        servers = make_cluster(tmp_path, 2, replica_n=1)
        try:
            req("POST", f"{uri(servers[0])}/index/i", {})
            req("POST", f"{uri(servers[0])}/index/i/field/f", {})
            shard, owners = _remote_shard(servers)
            cols = [shard * SHARD_WIDTH + 5]
            req("POST", f"{uri(servers[0])}/index/i/field/f/import",
                {"rows": [1], "columns": cols})
            by_id = {s.api.cluster.local.id: s for s in servers}
            _stall(by_id[owners[0].id], 10.0)

            r = urllib.request.Request(
                f"{uri(servers[0])}/index/i/query",
                data=b"Count(Row(f=1))", method="POST",
                headers={"X-Pilosa-Deadline-Ms": "400"},
            )
            t0 = time.monotonic()
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(r, timeout=30)
            elapsed = time.monotonic() - t0
            assert ei.value.code == 504, ei.value.code
            assert elapsed < 5.0, f"deadline not bounded: {elapsed:.3f}s"
            assert servers[0].api.qos.metrics()["deadline_expired_total"] >= 1
        finally:
            _close_all(servers)

    def test_deadline_budget_propagates_to_remote_hop(self, tmp_path):
        """The remote sub-query re-anchors the root's REMAINING budget:
        the replica sees a Deadline, and its remaining time never
        exceeds what the root had left."""
        servers = make_cluster(tmp_path, 2, replica_n=1)
        try:
            req("POST", f"{uri(servers[0])}/index/i", {})
            req("POST", f"{uri(servers[0])}/index/i/field/f", {})
            shard, owners = _remote_shard(servers)
            cols = [shard * SHARD_WIDTH + 9]
            req("POST", f"{uri(servers[0])}/index/i/field/f/import",
                {"rows": [1], "columns": cols})
            by_id = {s.api.cluster.local.id: s for s in servers}
            remote_srv = by_id[owners[0].id]
            seen = {}
            orig = remote_srv.api.query_raw

            def capture(*args, **kwargs):
                if kwargs.get("remote"):
                    seen["deadline"] = kwargs.get("deadline")
                return orig(*args, **kwargs)

            remote_srv.api.query_raw = capture
            r = urllib.request.Request(
                f"{uri(servers[0])}/index/i/query",
                data=b"Count(Row(f=1))", method="POST",
                headers={"X-Pilosa-Deadline-Ms": "60000"},
            )
            with urllib.request.urlopen(r, timeout=30) as resp:
                assert resp.status == 200
            assert seen.get("deadline") is not None
            assert 0 < seen["deadline"].remaining() <= 60.0
        finally:
            _close_all(servers)

    def test_server_default_deadline_only_on_edge_requests(self, tmp_path):
        """qos-default-deadline applies to EDGE queries only: a remote
        sub-query's budget belongs to its root — a locally-minted default
        would let one peer's tighter config fail healthy nodes."""
        from pilosa_tpu.server import Server, ServerConfig

        server = Server(ServerConfig(
            data_dir=str(tmp_path / "n0"), port=0, name="n0",
            anti_entropy_interval=0, heartbeat_interval=0, use_mesh=False,
            qos_default_deadline=2.0,
        )).open()
        try:
            base = uri(server)
            req("POST", f"{base}/index/i", {})
            req("POST", f"{base}/index/i/field/f", {})
            req("POST", f"{base}/index/i/query", b"Set(1, f=1)")
            seen = {}
            orig = server.api.query_raw

            def capture(*args, **kwargs):
                key = "remote" if kwargs.get("remote") else "edge"
                seen[key] = kwargs.get("deadline")
                return orig(*args, **kwargs)

            server.api.query_raw = capture
            req("POST", f"{base}/index/i/query?remote=true&shards=0",
                b"Count(Row(f=1))")
            assert seen["remote"] is None
            req("POST", f"{base}/index/i/query", b"Count(Row(f=1))")
            assert seen["edge"] is not None
            assert 0 < seen["edge"].remaining() <= 2.0
        finally:
            server.close()

    def test_expired_deadline_rejected_before_dispatch(self, tmp_path):
        """A request whose budget is already gone when it reaches the
        executor is 504d without occupying a dispatch slot; an invalid
        header is a clean 400."""
        servers = make_cluster(tmp_path, 1, replica_n=1)
        try:
            base = uri(servers[0])
            req("POST", f"{base}/index/i", {})
            req("POST", f"{base}/index/i/field/f", {})
            req("POST", f"{base}/index/i/query", b"Set(1, f=1)")
            # stall ADMISSION-side: deadline expires between edge and
            # executor (simulated by an absurdly small budget + a slow
            # pre-execute hook)
            real_exec = servers[0].api.executor
            orig_submit = real_exec.submit

            def slow_submit(*a, **k):
                time.sleep(0.05)
                return orig_submit(*a, **k)

            real_exec.submit = slow_submit
            r = urllib.request.Request(
                f"{base}/index/i/query", data=b"Count(Row(f=1))",
                method="POST", headers={"X-Pilosa-Deadline-Ms": "1"},
            )
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(r, timeout=30)
            assert ei.value.code == 504
            r = urllib.request.Request(
                f"{base}/index/i/query", data=b"Count(Row(f=1))",
                method="POST", headers={"X-Pilosa-Deadline-Ms": "nope"},
            )
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(r, timeout=30)
            assert ei.value.code == 400
        finally:
            _close_all(servers)

    def test_hedge_budget_caps_extra_load(self, tmp_path):
        """With hedging disabled (budget fraction 0 takes the inline
        no-race fast path), a slow primary is NOT hedged: the read
        completes via the primary at its own pace — budget enforcement
        caps the extra load by degrading to reference behavior, never by
        failing reads."""
        servers = make_cluster(tmp_path, 3, replica_n=2)
        try:
            req("POST", f"{uri(servers[0])}/index/i", {})
            req("POST", f"{uri(servers[0])}/index/i/field/f", {})
            shard, owners = _remote_shard(servers)
            cols = [shard * SHARD_WIDTH + c for c in (1, 2)]
            req("POST", f"{uri(servers[0])}/index/i/field/f/import",
                {"rows": [1] * len(cols), "columns": cols})
            by_id = {s.api.cluster.local.id: s for s in servers}
            _stall(by_id[owners[0].id], 0.5)
            qos = servers[0].api.qos
            qos.hedge.budget_fraction = 0.0  # budget exhausted
            qos.hedge.initial_delay = 0.03

            t0 = time.monotonic()
            out = req("POST", f"{uri(servers[0])}/index/i/query",
                      b"Count(Row(f=1))")
            elapsed = time.monotonic() - t0
            assert out["results"][0] == 2
            # no hedge fired: the answer had to wait out the stall
            assert elapsed >= 0.4, elapsed
            assert qos.metrics()["hedges_total"] == 0
        finally:
            _close_all(servers)


# ------------------------------------------- integration: circuit breaking


class TestCircuitBreakerIntegration:
    def test_breaker_opens_on_dead_node_and_recovers(self, tmp_path):
        """Repeated transport faults to one node open its breaker —
        subsequent reads skip the dead node's transport timeout and go
        straight to the sibling replica — and the half-open probe closes
        it again once the node heals."""
        servers = make_cluster(tmp_path, 3, replica_n=2)
        try:
            req("POST", f"{uri(servers[0])}/index/i", {})
            req("POST", f"{uri(servers[0])}/index/i/field/f", {})
            shard, owners = _remote_shard(servers)
            cols = [shard * SHARD_WIDTH + 7]
            req("POST", f"{uri(servers[0])}/index/i/field/f/import",
                {"rows": [1], "columns": cols})
            qos = servers[0].api.qos
            qos.hedge.budget_fraction = 0.0  # isolate the breaker path
            cluster = servers[0].api.cluster
            client = cluster.client
            dead_id = owners[0].id
            dead_uri = owners[0].uri
            real = type(client).query_node
            refused = {"n": 0}

            def flaky(self_, uri_, *a, **k):
                from pilosa_tpu.parallel.client import ClientError

                if uri_ == dead_uri and refused["n"] < 100:
                    refused["n"] += 1
                    raise ClientError(f"connect refused {uri_}")
                return real(self_, uri_, *a, **k)

            client.query_node = flaky.__get__(client)

            breaker = qos.breaker(dead_id)
            breaker.threshold = 2
            breaker.cooldown = 0.1

            def count():
                out = req("POST", f"{uri(servers[0])}/index/i/query",
                          b"Count(Row(f=1))")
                return out["results"][0]

            # each failed read records a breaker failure and survives
            # via replica fallback; node is re-marked NORMAL between
            # queries (heartbeat's job) so routing retries the primary
            for _ in range(2):
                assert count() == 1
                cluster.nodes[dead_id].state = "NORMAL"
            assert breaker.state == "open"
            faults_so_far = refused["n"]
            # circuit open: the next read never touches the dead node —
            # and the synthetic circuit-open error must not override the
            # heartbeat's NORMAL view (no contact was made)
            assert count() == 1
            assert refused["n"] == faults_so_far
            assert cluster.nodes[dead_id].state == "NORMAL"
            assert servers[0].api.qos.metrics()["breaker_open"] >= 1
            # heal the node and wait out the cooldown: the half-open
            # probe closes the breaker
            refused["n"] = 1000  # flaky() now passes through
            cluster.nodes[dead_id].state = "NORMAL"
            time.sleep(0.12)
            assert count() == 1
            assert breaker.state == "closed"
        finally:
            _close_all(servers)


# ------------------------------------------------------- pipeline satellite


class TestGatherLatch:
    def test_single_fast_client_does_not_latch_window(self):
        """A lone closed-loop client with sub-window service
        time keeps _recent_gap under the pressure threshold forever; the
        latch breaker must keep it on the zero-wait path (its waves are
        size 1, so the window buys nothing)."""
        from pilosa_tpu.server.pipeline import QueryPipeline

        pipe = QueryPipeline(api=None)
        pipe.GATHER_WINDOW_S = 0.2  # would be very visible if latched
        pipe._recent_gap = 0.001  # looks like pressure
        pipe._last_wave_size = 1  # ...but the last wave was a loner
        pipe._q.put(0)
        wave = [pipe._q.get()]
        t0 = time.monotonic()
        pipe._gather(wave)
        assert time.monotonic() - t0 < 0.05  # no 200 ms window paid
        assert len(wave) == 1

    def test_burst_reopens_window_within_one_wave(self):
        """The latch breaker must not lock OUT a real burst: a wave that
        greedy-drains >1 requests re-opens the window immediately."""
        from pilosa_tpu.server.pipeline import QueryPipeline

        pipe = QueryPipeline(api=None)
        pipe.GATHER_WINDOW_S = 0.2
        pipe._recent_gap = 0.001
        pipe._last_wave_size = 1  # closed by a quiet period
        for i in range(3):  # burst backlog
            pipe._q.put(i)

        def feeder():
            time.sleep(0.02)
            pipe._q.put(99)

        t = threading.Thread(target=feeder)
        t.start()
        pipe._q.put(-1)
        wave = [pipe._q.get()]
        pipe._gather(wave)
        t.join()
        # 1 + 3 drained + the straggler caught inside the window
        assert len(wave) == 5, wave
        assert pipe._last_wave_size == 5


# ------------------------------------------------------- cluster satellite


class TestCleanupRingSnapshot:
    def test_cleanup_ownership_frozen_against_midloop_join(self, tmp_path):
        """TOCTOU: a node-join landing while cleanup_unowned
        walks fragments must not swing ownership to the NEW ring — with
        one node and replica_n=1 every fragment is owned locally, and a
        join injected mid-walk must not delete any of them."""
        from pilosa_tpu.parallel.cluster import Cluster, Node
        from pilosa_tpu.storage import FieldOptions, Holder

        holder = Holder(str(tmp_path / "h"))
        holder.open()
        try:
            idx = holder.create_index("i")
            fld = idx.create_field("f", FieldOptions())
            for shard in range(8):
                fld.view("standard", create=True).fragment(
                    shard, create=True
                )
            cluster = Cluster(Node("a", "http://localhost:1"),
                              replica_n=1, holder=holder)
            real_partition = cluster.partition
            injected = {"done": False}

            def racing_partition(index, shard):
                if not injected["done"]:
                    injected["done"] = True
                    # the join lands mid-walk (as a concurrent
                    # node-join message would)
                    cluster.nodes["b"] = Node("b", "http://localhost:2")
                return real_partition(index, shard)

            cluster.partition = racing_partition
            removed = cluster.cleanup_unowned(members=["a"])
            assert removed == 0
            assert sorted(fld.view("standard").fragments) == list(range(8))
            # sanity: the LIVE ring does assign some shards to b now, so
            # the old code would have deleted sole copies here
            cluster.partition = real_partition
            live_owned = [
                s for s in range(8)
                if any(n.id == "a"
                       for n in cluster.shard_nodes("i", s))
            ]
            assert len(live_owned) < 8
        finally:
            holder.close()


# ----------------------------------------------------------- slow stress


@pytest.mark.slow
class TestQosStress:
    def test_sustained_burst_sheds_without_queue_growth(self, tmp_path):
        """Sustained overload (real service-time sleeps): shed count
        grows, in-flight stays bounded at the limit, and the node keeps
        answering /metrics throughout."""
        from pilosa_tpu.server import Server, ServerConfig

        server = Server(ServerConfig(
            data_dir=str(tmp_path / "n0"), port=0, name="n0",
            anti_entropy_interval=0, heartbeat_interval=0, use_mesh=False,
            qos_max_inflight=4,
        )).open()
        try:
            base = uri(server)
            req("POST", f"{base}/index/i", {})
            req("POST", f"{base}/index/i/field/f", {})
            real_exec = server.api.executor.execute

            def slow_execute(*a, **k):
                time.sleep(0.2)
                return real_exec(*a, **k)

            server.api.executor.execute = slow_execute
            codes: list = []
            lock = threading.Lock()

            def client():
                for _ in range(4):
                    try:
                        req("POST", f"{base}/index/i/query", b"Set(1, f=1)")
                        code = 200
                    except urllib.error.HTTPError as e:
                        code = e.code
                    with lock:
                        codes.append(code)

            threads = [threading.Thread(target=client) for _ in range(16)]
            for t in threads:
                t.start()
            deadline = time.monotonic() + 30
            while any(t.is_alive() for t in threads):
                assert server.api.qos.admission.inflight <= 4
                req("GET", f"{base}/metrics", raw=True)  # stays live
                if time.monotonic() > deadline:
                    raise AssertionError("stress burst wedged")
                time.sleep(0.05)
            for t in threads:
                t.join()
            assert codes.count(200) >= 4  # admitted work completed
            assert codes.count(429) >= 1  # and the excess was shed
            assert server.api.qos.admission.inflight == 0
        finally:
            server.close()
