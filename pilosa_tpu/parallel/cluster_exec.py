"""Cluster-aware executor: local mesh map + cross-node HTTP reduce.

Reference: the remote branch of executor.mapReduce (SURVEY.md §3.2) —
shards owned elsewhere are batched into ONE sub-query per node
(``Remote=true`` + explicit shard list) and partial results are reduced on
the requesting node: rows union, counts add, TopN pair-merge with an
exact second pass, ValCount merge, group-merge.

Local shards evaluate through the wrapped executor (DistExecutor when a
mesh is available), so inside a host the reduce is an ICI psum and only
the cross-host hop uses HTTP/DCN — the reference's topology with its
data plane swapped out.
"""

from __future__ import annotations

import threading
import time

import numpy as np

from pilosa_tpu.executor import Executor
from pilosa_tpu.executor.executor import (
    Deferred,
    PQLError,
    TOPN_CANDIDATE_FACTOR,
    apply_options_result,
    having_predicate,
    options_child,
    options_restrict_shards,
)
from pilosa_tpu.executor.result import GroupCount, Pair, RowResult, ValCount
from pilosa_tpu.ops.packing import pack_bits
from pilosa_tpu.parallel.client import ClientError
from pilosa_tpu.parallel.cluster import (
    Cluster,
    ClusterDegradedError,
    Node,
    global_route_stats,
)
from pilosa_tpu.qos.deadline import DeadlineExceeded
from pilosa_tpu.storage.field import TYPE_BOOL, TYPE_INT, TYPE_MUTEX
from pilosa_tpu.pql import Call, parse
from pilosa_tpu.pql.ast import Query
from pilosa_tpu.shardwidth import SHARD_WIDTH, shard_of
from pilosa_tpu.utils.pool import concurrent_map, run_concurrently, spawn

_WRITE_BROADCAST = {"SetRowAttrs", "SetColumnAttrs"}
_SHARDS_TTL = 3.0

# How long a query waits for a resize to finish before erroring
# (reference: queries are deferred while the cluster is RESIZING).
_RESIZE_WAIT = 30.0


class ClusterExecutor:
    """Wraps a local executor with shard routing across cluster nodes."""

    accepts_remote = True

    def __init__(self, local_executor: Executor, cluster: Cluster,
                 qos=None, remote_batch: bool = True):
        self.local = local_executor
        self.holder = local_executor.holder
        self.cluster = cluster
        # serving-QoS bundle (qos.ServingQos): hedge policy + per-node
        # circuit breakers for the remote read fan-out; None disables
        # both (bare constructions in tests/tools)
        self.qos = qos
        # cluster-wide wave batching (parallel/wavebatch.py): deadline-
        # free primary reads bound for the same node group-commit onto
        # one /internal/query-batch request. ``remote-batch = false``
        # (ServerConfig) restores per-query dispatch.
        self.remote_batch = remote_batch
        self._wave_batcher = None
        # read rotation over a range-split shard's span owners (elastic
        # plane): bumped per routed read; a lost increment under the
        # benign unlocked race just repeats a pick
        self._range_rr = 0
        self._shards_cache: dict[str, tuple[float, list[int]]] = {}
        self._lock = threading.Lock()
        # key translation goes through the coordinator (reference:
        # translation primary); reverse lookups backfill from its log
        local_executor.key_resolver = self._resolve_key_via_coordinator
        local_executor.key_backfill = cluster.sync_translate

    def _resolve_key_via_coordinator(self, namespace: str, key: str, create: bool):
        coord = self.cluster.coordinator
        if coord.id == self.cluster.local.id:
            if create:
                return self.holder.translate.translate_one(namespace, key, create=True)
            return None
        ids = self.cluster.client.translate_keys(coord.uri, namespace, [key], create)
        id_ = ids[0] if ids else None
        if id_ is not None:
            self.cluster.sync_translate()  # mirror the assignment locally
        return id_

    # ------------------------------------------------------------ top level

    def execute(self, index_name: str, query, shards=None,
                remote: bool = False, deadline=None):
        if remote:
            # sub-query from a peer: evaluate strictly locally on the given
            # shards, no re-fan-out (reference Remote=true)
            return self.local.execute(index_name, query, shards=shards,
                                      deadline=deadline)
        if not self.cluster.wait_until_normal(
            _RESIZE_WAIT if deadline is None
            else min(_RESIZE_WAIT, max(deadline.remaining(), 0))
        ):
            if deadline is not None:
                deadline.check("resize wait")
            raise PQLError("cluster is resizing; query deferred past timeout")
        if isinstance(query, str):
            query = parse(query)
        elif isinstance(query, Call):
            query = Query([query])
        idx = self.holder.index(index_name)
        if idx is None:
            raise PQLError(f"index {index_name!r} not found")
        return [self._execute_call(idx, call, shards, deadline=deadline)
                for call in query.calls]

    def submit(self, index_name: str, query, shards=None,
               remote: bool = False, deadline=None):
        """Pipelined cluster execution: one ``Deferred`` per call.

        The cluster analog of ``Executor.submit`` (the reference serves
        concurrent queries through per-request mapReduce goroutines —
        SURVEY.md §2 #12/§3.2; on a TPU backend the scarce resource is
        DISPATCHES, so the stream must coalesce instead of merely
        interleave). Per call: local shards enqueue through the wrapped
        executor's pipelined ``submit`` — so a stream of cluster queries
        micro-batches on-device exactly like a single-node stream — while
        the remote fan-out STARTS on a background thread at submit time
        (``spawn``); ``result()`` joins both and runs the cross-node
        reduce. When every routed shard is local (single-node cluster,
        full replication) the call delegates wholesale to the wrapped
        executor and pays zero cluster overhead. Writes and point reads
        (IncludesColumn) keep their eager routed semantics.
        """
        if remote:
            # peer sub-query: strictly local, still pipelined
            return self.local.submit(index_name, query, shards=shards,
                                     deadline=deadline)
        if isinstance(query, str):
            query = parse(query)
        elif isinstance(query, Call):
            query = Query([query])
        idx = self.holder.index(index_name)
        if idx is None:
            raise PQLError(f"index {index_name!r} not found")
        if not self.cluster.wait_until_normal(0):
            # Cluster is RESIZING: the deferral wait must burn on the
            # CALLER's thread at result() — concurrent requests then wait
            # in parallel, and a serving pipeline's dispatcher (which
            # calls submit, never result) stays unblocked.
            def deferred(call):
                def finalize():
                    wait = _RESIZE_WAIT
                    if deadline is not None:
                        wait = min(wait, max(deadline.remaining(), 0))
                    if not self.cluster.wait_until_normal(wait):
                        if deadline is not None:
                            deadline.check("resize wait")
                        raise PQLError(
                            "cluster is resizing; query deferred past timeout"
                        )
                    return self._execute_call(idx, call, shards,
                                              deadline=deadline)

                return Deferred(finalize)

            return [deferred(call) for call in query.calls]
        return [self._submit_call(idx, call, shards, deadline=deadline)
                for call in query.calls]

    def _submit_call(self, idx, call: Call, shards=None,
                     deadline=None) -> Deferred:
        if deadline is not None:
            deadline.check("cluster submit")
        name = call.name
        if name == "Options":
            inner = self._submit_call(
                idx, options_child(call),
                options_restrict_shards(call, shards), deadline=deadline,
            )
            return Deferred(
                lambda: apply_options_result(idx, call, inner.result())
            )
        if name == "IncludesColumn":
            # a READ with a possible remote hop: start it on a background
            # thread NOW so a slow shard owner cannot convoy a serving
            # pipeline's dispatcher; result() joins
            return Deferred(spawn(
                lambda: self._execute_includes(idx, call, shards,
                                               deadline=deadline)
            ))
        if name in ("Set", "Clear", "Store", "ClearRow") or name in _WRITE_BROADCAST:
            # writes keep eager in-order semantics at submit time
            return Deferred(value=self._execute_call(idx, call, shards))
        shard_list = shards if shards is not None else self._all_shards(idx.name)
        local, groups = self._route(idx.name, shard_list)
        if not groups:
            if shards is None and local == idx.available_shards():
                # the whole of this node's index and no shard named: say
                # so. The local executor then works from
                # Index.available_shards' one list object, which its
                # block memo and the views' folds are kept by; a list
                # made here is new to them every request
                local = None
            return self.local.submit(idx.name, call, shards=local,
                                     deadline=deadline)[0]
        if name == "TopN":
            return self._submit_topn(idx, call, local, groups,
                                     deadline=deadline)
        having = None
        if name == "GroupBy":
            having = having_predicate(
                call, has_agg=isinstance(call.arg("aggregate"), Call)
            )
        mapped = call
        if name in ("Rows", "GroupBy") and (
            call.arg("limit") or having is not None
        ):
            mapped = Call(
                name,
                {k: v for k, v in call.args.items()
                 if k not in ("limit", "having")},
                call.children,
            )
        # remote fan-out departs on a background thread FIRST (calls
        # whose local submit is eager — Rows — would otherwise serialize
        # ahead of it), then the local program enqueues on the device
        # stream; nothing blocks until result()
        remote_join = spawn(lambda: self._map_remote(idx.name, mapped, groups,
                                                     deadline=deadline))
        local_def = self.local.submit(idx.name, mapped, shards=local,
                                      deadline=deadline)[0]

        def finalize():
            local_res = local_def.result()
            partials = remote_join()
            return self._reduce(idx, call, local_res, partials, having=having)

        return Deferred(finalize)

    # -------------------------------------------------------- shard routing

    def _all_shards(self, index_name: str) -> list[int]:
        """Cluster-wide shard list: local shards ∪ peers' create-shard
        broadcasts (reference CreateShardMessage — new remote shards are
        visible immediately) ∪ a TTL-cached catalog poll as the backstop
        for missed broadcasts (e.g. this node restarted)."""
        with self._lock:
            hit = self._shards_cache.get(index_name)
            polled = hit[1] if hit and time.monotonic() - hit[0] < _SHARDS_TTL else None
        if polled is None:
            peers = [n for n in self.cluster.sorted_nodes()
                     if n.id != self.cluster.local.id]

            def poll(node):
                try:
                    out = self.cluster.client._call(
                        "GET",
                        f"{node.uri}/internal/shards/list?index={index_name}",
                    )
                    return out.get("shards", [])
                except ClientError:
                    return []

            polled = {s for chunk in concurrent_map(poll, peers)
                      for s in chunk}
            with self._lock:
                self._shards_cache[index_name] = (time.monotonic(), polled)
        shards = set(self.holder.index(index_name).available_shards())
        shards.update(polled)
        shards.update(self.cluster.get_known_shards(index_name))
        return sorted(shards)

    def _route(self, index_name: str, shards: list[int]):
        """Group shards by executing node (primary live replica; self
        preferred when we are any replica)."""
        if self.cluster.nodes.keys() == {self.cluster.local.id}:
            # a cluster of this node alone: shard_nodes can name no other
            # owner, so there is nothing to ask it a shard, a request
            return list(shards), []
        local: list[int] = []
        remote: dict[str, tuple[Node, list[int]]] = {}
        for shard in shards:
            nodes = self.cluster.shard_nodes(index_name, shard)
            if any(n.id == self.cluster.local.id for n in nodes):
                local.append(shard)
                continue
            target = self._range_read_target(index_name, shard)
            if target is None:
                live = [n for n in nodes if n.state == "NORMAL"] or nodes
                target = live[0]
            remote.setdefault(target.id, (target, []))[1].append(shard)
        return local, list(remote.values())

    def _range_read_target(self, index_name: str, shard: int):
        """Read-preference refinement for a range-split shard (elastic
        plane): successive reads rotate across the split's span owners
        — every one holds the WHOLE fragment through the union
        override, so any pick reads correct bytes, and the rotation is
        what spreads a hot single shard's read QPS after the planner
        splits it. None for an unsplit shard (or a departed span
        owner): the caller falls back to plain owner routing."""
        spans = self.cluster.placement.get_ranges(index_name, shard)
        if not spans:
            return None
        self._range_rr += 1
        lo = spans[self._range_rr % len(spans)][0]
        nodes = self.cluster.range_read_nodes(index_name, shard, lo)
        if not nodes:
            return None
        live = [n for n in nodes if n.state == "NORMAL"]
        return live[0] if live else None

    def _route_all_replicas(self, index_name: str, shards: list[int]):
        """Group shards by EVERY replica that holds them. Row-wide writes
        (Store/ClearRow) must reach all owners like point writes do —
        routing them to one executing replica per shard (the read path's
        _route) leaves the other replicas' copies of the row stale, and
        replicas diverge until (or past: union repair cannot remove
        cleared bits) the next anti-entropy pass. Found by the
        randomized cluster property test (replica_n=2)."""
        local: list[int] = []
        remote: dict[str, tuple[Node, list[int]]] = {}
        for shard in shards:
            for n in self.cluster.shard_nodes(index_name, shard):
                if n.id == self.cluster.local.id:
                    local.append(shard)
                else:
                    remote.setdefault(n.id, (n, []))[1].append(shard)
        return local, list(remote.values())

    def _map_remote(self, index_name: str, call: Call, groups, _depth=0,
                    deadline=None):
        """One CONCURRENT sub-query per remote node (reference mapReduce:
        one goroutine per remote node — SURVEY.md §2 #12); returns a flat
        list of raw JSON partials (shard coverage exact; group order
        immaterial to every reducer).

        Replica fallback: a node that fails its sub-query is marked
        DEGRADED and its shards are re-routed to surviving NORMAL
        replicas (recursing once per hop, bounded); the query only fails
        when some shard has no live replica left. Reads therefore
        tolerate single-replica faults the way the reference's
        mapReduce retry loop does.

        With a QoS bundle wired, each sub-query additionally rides the
        hedged-read path (_query_group): circuit-broken nodes are skipped
        without paying a transport timeout, and a primary slower than the
        p95-tracked hedge delay races a budgeted duplicate at the next
        replica. DeadlineExceeded propagates — an expired budget is a
        property of the REQUEST, so replica retries must not chase it."""
        from pilosa_tpu.utils.tracing import current_query

        pql = call.to_pql()
        # in-flight inspector (GET /debug/queries): count this fan-out's
        # shards as outstanding, decrementing as each node's group
        # settles — plain attribute writes on the request's record
        inflight = current_query() if _depth == 0 else None
        if inflight is not None:
            inflight.shards_outstanding = (
                (inflight.shards_outstanding or 0)
                + sum(len(g[1]) for g in groups)
            )

        def one(group):
            node, shard_group = group
            try:
                return self._query_group(index_name, call, pql, node,
                                         shard_group, _depth, deadline)
            except ClientError as e:
                if deadline is not None and deadline.expired:
                    # the budget died with this hop: report the deadline,
                    # not the transport symptom — no retry can answer an
                    # expired request, so replica fallback must not run
                    raise DeadlineExceeded(
                        f"deadline exceeded during remote read "
                        f"({node.id}: {e})"
                    ) from e
                # Transport/5xx: the NODE is sick — degrade it and retry
                # siblings. 404: ambiguous — 'index/field not found' can
                # mean a schema-lagging replica, so retry siblings but do
                # NOT degrade a healthy node. Other 4xx: deterministic
                # query errors every replica would repeat — surface as
                # PQLError (HTTP 400), never 'internal'.
                if e.is_node_fault:
                    # a circuit-open error is synthetic — no contact was
                    # made, so it reroutes but must not override the
                    # heartbeat's view of the node
                    if not getattr(e, "circuit_open", False):
                        node.state = "DEGRADED"
                elif e.status != 404:
                    raise PQLError(str(e)) from e

                def give_up():
                    if (e.is_node_fault
                            and getattr(self.cluster, "degraded", False)):
                        # minority side of a partition: name the real
                        # condition (503 + Retry-After at the edge)
                        # instead of surfacing one peer's transport
                        # symptom — locally-owned reads still serve
                        raise ClusterDegradedError(
                            "cluster degraded (no member quorum): shards "
                            "owned by unreachable peers cannot be "
                            "served; only locally-owned reads are "
                            "available"
                        ) from e
                    if e.is_node_fault:
                        raise e
                    raise PQLError(str(e)) from e

                if _depth >= 2:
                    give_up()
                retry, orphans = self._reroute_groups(
                    index_name, shard_group, node.id
                )
                if orphans:
                    give_up()  # some shard has no live replica left
                return self._map_remote(
                    index_name, call, retry, _depth + 1, deadline=deadline,
                )

        def one_tracked(group):
            try:
                return one(group)
            finally:
                if inflight is not None:
                    inflight.shards_outstanding = max(
                        0, (inflight.shards_outstanding or 0)
                        - len(group[1]),
                    )

        return [p for chunk in concurrent_map(one_tracked, groups)
                for p in chunk]

    # ------------------------------------------------------- hedged reads

    def _reroute_groups(self, index_name: str, shards, exclude_id: str):
        """Next-live-replica routing shared by the failure fallback and
        the hedge path: bucket each shard onto its first NORMAL replica
        that is not ``exclude_id``. Returns ``(groups, orphans)`` —
        orphans are shards with no live alternate; the caller decides
        whether that aborts the query (fallback) or merely disables
        hedging. One implementation so a future replica-selection change
        cannot make the two paths route differently."""
        groups: dict[str, tuple[Node, list[int]]] = {}
        orphans: list[int] = []
        for shard in shards:
            alts = [
                n for n in self.cluster.shard_nodes(index_name, shard)
                if n.id != exclude_id and n.state == "NORMAL"
            ]
            if not alts:
                orphans.append(shard)
            else:
                groups.setdefault(alts[0].id, (alts[0], []))[1].append(shard)
        return list(groups.values()), orphans

    def _record_breaker_outcome(self, breaker, exc, deadline,
                                elapsed: float) -> None:
        """Classify a failed primary read for the circuit breaker.

        A transport/5xx fault with the request's budget still live is
        plain evidence against the node. At budget expiry it is
        ambiguous — transport timeouts are capped at the remaining
        budget (client.py hop_kwargs), so a TIGHT deadline makes a
        healthy node look faulty (deadline.py's invariant: a request
        property must not open breakers) while a truly stalled node
        always faults exactly at expiry and would otherwise never trip
        its breaker. Discriminate by how long the node was given: a
        fault after several multiples of the tracked hedge delay (and
        at least 1 s) counts even at expiry. A 4xx is a deterministic
        query error every replica would repeat — never node evidence.
        Inconclusive outcomes release a half-open probe seat without
        moving state. (See _map_remote for the inspector's
        shards-outstanding accounting.)"""
        if isinstance(exc, ClientError) and exc.is_node_fault:
            fair_chance = max(1.0, 4 * self.qos.hedge.delay())
            if (deadline is None or not deadline.expired
                    or elapsed >= fair_chance):
                breaker.record_failure()
                return
        breaker.record_inconclusive()

    def _alternate_groups(self, index_name: str, primary, shard_group):
        """Hedge targets for one sub-query. All-or-nothing — a partial
        hedge would return a partial result that cannot stand in for the
        primary's, so any shard without a live alternate disables hedging
        for the whole group."""
        groups, orphans = self._reroute_groups(index_name, shard_group,
                                               primary.id)
        return [] if orphans else groups

    @property
    def wave_batcher(self):
        """Lazy per-executor batcher (observability handle for /metrics)."""
        batcher = self._wave_batcher
        if batcher is None:
            with self._lock:
                if self._wave_batcher is None:
                    from pilosa_tpu.parallel.wavebatch import (
                        RemoteWaveBatcher,
                    )

                    self._wave_batcher = RemoteWaveBatcher(
                        self.cluster.client)
                batcher = self._wave_batcher
        return batcher

    def _remote_query(self, node, index_name: str, pql: str, shard_group,
                      deadline, _depth) -> dict:
        """One remote sub-query, through the wave batcher when eligible.
        Eligibility: batching enabled, deadline-free, and a depth-0
        primary leg — deadline-capped hops keep their per-hop transport
        cap, and hedge/fallback legs (depth ≥ 1) must not queue behind
        the very primary they are racing.

        Tracing: when this request is sampled, the leg gets a
        ``remote.query`` span, the hop carries ``X-Pilosa-Trace``, and
        the peer's returned span subtree is grafted under the leg — the
        coordinator's /debug/traces then shows one tree spanning the
        cluster (docs/OBSERVABILITY.md).

        PROFILE: when the request carries a cost profile (utils/cost.py)
        the hop asks the peer for ITS per-AST-node profile and grafts the
        returned subtree under this request's profile, exactly like the
        span graft — so a cluster query answers one stitched per-node
        profile tree. Profiled legs bypass the wave batcher (per-item
        profiles don't ride the batch wire, and a debugging request must
        not perturb its batchmates' group-commit)."""
        from pilosa_tpu.utils.cost import current_cost
        from pilosa_tpu.utils.tracing import global_tracer

        cost = current_cost()
        profile = cost.profile if cost is not None else None
        with global_tracer().span(
            "remote.query", node=node.id, shards=len(shard_group),
            depth=_depth,
        ) as span:
            trace = span.header_value() if span is not None else None
            if (self.remote_batch and deadline is None and _depth == 0
                    and profile is None):
                out = self.wave_batcher.query(node, index_name, pql,
                                              shard_group, trace=trace)
            else:
                # kwargs only when set: test doubles (and older client
                # shims) that predate the trace/deadline/profile
                # keywords keep working on the plain common path
                kw = {}
                if deadline is not None:
                    kw["deadline"] = deadline
                if trace is not None:
                    kw["trace"] = trace
                if profile is not None:
                    kw["profile"] = True
                out = self.cluster.client.query_node(
                    node.uri, index_name, pql, shard_group, remote=True,
                    **kw,
                )
            if isinstance(out, dict):
                if span is not None:
                    subtree = out.pop("trace", None)
                    if subtree is not None:
                        span.add_remote(subtree)
                if profile is not None:
                    sub = out.pop("profile", None)
                    if sub is not None:
                        profile.add_remote(node.id, len(shard_group), sub)
            return out

    def _query_group(self, index_name: str, call: Call, pql: str, node,
                     shard_group, _depth, deadline):
        """One node's sub-query with QoS: circuit breaker, then a hedged
        race against the next replica when the primary outlives the
        hedge delay. Returns a flat partial list; raises ClientError on
        failure so the caller's replica-fallback path stays authoritative
        for DEGRADED marking and rerouting."""
        qos = self.qos
        if qos is None:
            out = self._remote_query(node, index_name, pql, shard_group,
                                     deadline, _depth)
            return [out["results"][0]]
        breaker = qos.breaker(node.id)
        if not breaker.allow():
            # open circuit: don't pay this node's transport timeout —
            # fail fast into the caller's replica fallback. The error is
            # SYNTHETIC (no contact was made), so it must reroute like a
            # node fault without being treated as fresh evidence: the
            # circuit_open marker stops one() from re-marking a
            # heartbeat-recovered node DEGRADED off stale breaker state
            err = ClientError(f"circuit open for node {node.id}")
            err.circuit_open = True
            raise err
        # only EDGE fan-out legs (depth 0) count toward the hedge-budget
        # denominator and the p95 tracker: hedge legs and fallback
        # retries re-enter this function at depth >= 1, and counting them
        # as primaries would inflate the denominator the ≤budget-fraction
        # invariant divides by (and skew the delay toward retry latency)
        is_edge_leg = _depth == 0
        if is_edge_leg:
            qos.hedge.note_primary()
        t0 = time.monotonic()
        if (self.cluster.replica_n <= 1 or _depth >= 2
                or qos.hedge.budget_fraction <= 0):
            # no race partner is possible (unreplicated, depth-capped, or
            # hedging disabled via qos-hedge-budget=0): call inline — the
            # thread + condvar handshake below would be pure overhead
            try:
                out = self._remote_query(node, index_name, pql, shard_group,
                                         deadline, _depth)
            except BaseException as e:
                self._record_breaker_outcome(breaker, e, deadline,
                                             time.monotonic() - t0)
                raise
            if is_edge_leg:
                qos.hedge.record(time.monotonic() - t0)
            breaker.record_success()
            return [out["results"][0]]

        import contextvars

        cv = threading.Condition()
        state: dict = {}

        def finish(key, value):
            with cv:
                state.setdefault(key, value)
                cv.notify_all()

        def run_primary():
            try:
                out = self._remote_query(node, index_name, pql, shard_group,
                                         deadline, _depth)
            except BaseException as e:
                self._record_breaker_outcome(breaker, e, deadline,
                                             time.monotonic() - t0)
                finish("primary_err", e)
            else:
                if is_edge_leg:
                    qos.hedge.record(time.monotonic() - t0)
                breaker.record_success()
                finish("result", ("primary", [out["results"][0]]))

        # hedge-race legs run on bare threads: capture this context so
        # their remote.query spans land in the request's trace instead
        # of being orphaned (utils/tracing.py)
        primary_ctx = contextvars.copy_context()
        threading.Thread(target=lambda: primary_ctx.run(run_primary),
                         daemon=True,
                         name=f"qos-primary-{node.id}").start()
        delay = qos.hedge.delay()
        if deadline is not None:
            delay = min(delay, max(deadline.remaining(), 0))
        with cv:
            cv.wait_for(lambda: state, timeout=delay)
            pending = not state
        hedged = False
        if pending and not (deadline is not None and deadline.expired):
            # alternates are computed only now, on the slow path: the
            # ~95% of reads the primary answers within the delay never
            # pay the per-shard ring walks
            alt_groups = self._alternate_groups(index_name, node,
                                                shard_group)
            with cv:
                # the primary may have settled during the ring walk —
                # don't spend budget on a hedge that cannot win
                pending = not state
            if pending and alt_groups and qos.hedge.try_hedge():
                hedged = True

                def run_hedge():
                    from pilosa_tpu.utils.tracing import global_tracer

                    try:
                        with global_tracer().span("qos.hedge",
                                                  primary=node.id):
                            partials = self._map_remote(
                                index_name, call, alt_groups, _depth + 1,
                                deadline=deadline,
                            )
                    except BaseException as e:
                        finish("hedge_err", e)
                    else:
                        finish("result", ("hedge", partials))

                hedge_ctx = contextvars.copy_context()
                threading.Thread(target=lambda: hedge_ctx.run(run_hedge),
                                 daemon=True,
                                 name=f"qos-hedge-{node.id}").start()

        def settled():
            return ("result" in state
                    or ("primary_err" in state
                        and (not hedged or "hedge_err" in state)))

        with cv:
            if deadline is None:
                cv.wait_for(settled)
            else:
                # wake at settle OR budget expiry — no fixed-rate polling
                while not cv.wait_for(settled,
                                      timeout=max(deadline.remaining(),
                                                  1e-3)):
                    if deadline.expired:
                        break
        with cv:
            final = dict(state)
        if "result" in final:
            source, partials = final["result"]
            if source == "hedge":
                qos.hedge.note_win()
            return partials
        if "primary_err" in final:
            # both legs failed (or no hedge fired): surface the PRIMARY
            # error so the caller's fallback semantics (DEGRADED marking,
            # bounded reroute, 4xx propagation) are unchanged
            raise final["primary_err"]
        # neither leg settled: the only path here is the expired-budget
        # break above, so the check always raises DeadlineExceeded
        deadline.check("hedged read")
        raise AssertionError("hedged-read settle loop exited unexpectedly")

    def _map_remote_tolerant(self, index_name: str, call: Call, groups):
        """Row-wide write fan-out (Store/ClearRow): every replica is
        already a direct target, so there is nothing to fall back to — a
        replica unreachable at write time is marked DEGRADED and skipped
        (exactly like point writes in _execute_routed_write), the live
        replicas' write stands. Failing the whole request after some
        replicas already applied it would leave the SAME divergence plus
        a client told to retry. Deterministic (4xx) errors DO propagate —
        every replica would reject identically, so nothing was applied
        anywhere and the client must see the error.

        Divergence window: identical to a missed point write — the
        skipped replica is repaired when heartbeat death detection
        re-owns its shards or a join/re-fetch replaces its fragments;
        until then anti-entropy's union repair can resurface bits a
        ClearRow removed (documented in docs/PQL.md note 5)."""
        pql = call.to_pql()

        def one(group):
            node, shard_group = group
            try:
                out = self.cluster.client.query_node(
                    node.uri, index_name, pql, shard_group, remote=True
                )
                return out["results"][0]
            except ClientError as e:
                if e.is_node_fault:
                    node.state = "DEGRADED"
                    return False
                if e.status == 404:
                    # schema-lagging replica: skip it (no health signal);
                    # schema sync + anti-entropy catch it up
                    return False
                raise PQLError(str(e)) from e

        return concurrent_map(one, groups)

    # ----------------------------------------------------------- dispatch

    def _execute_call(self, idx, call: Call, shards=None, deadline=None):
        name = call.name
        if name in ("Set", "Clear"):
            return self._execute_routed_write(idx, call)
        if name in _WRITE_BROADCAST:
            res = self.local._execute_call(idx, call)
            self.cluster.send_sync(
                {"type": "forward-query", "index": idx.name, "pql": call.to_pql()}
            )
            return res
        if name in ("Store", "ClearRow"):
            # row-wide writes execute on EVERY replica of every shard,
            # concurrently (local evaluation overlaps the remote fan-out)
            shard_list = shards if shards is not None else self._all_shards(idx.name)
            local, groups = self._route_all_replicas(idx.name, shard_list)
            result, outs = run_concurrently(
                lambda: (self.local._execute_call(idx, call, local)
                         if local else False),
                lambda: self._map_remote_tolerant(idx.name, call, groups),
            )
            for out in outs:
                result = result or out
            return result

        # Reads (Options, TopN, IncludesColumn, and the generic
        # map→reduce family) share ONE orchestration: the pipelined
        # _submit_call, resolved immediately. submit's enqueue/spawn
        # overlap gives eager execution the same max(local, slowest peer)
        # wall time run_concurrently did, and the two paths cannot drift.
        return self._submit_call(idx, call, shards, deadline=deadline).result()

    # --------------------------------------------------------------- writes

    def _execute_routed_write(self, idx, call: Call):
        col = call.arg("_col")
        if isinstance(col, str):
            # keyed writes translate on the coordinator (via the resolver
            # hook); after translation the call routes by numeric column
            col = self.local._translate_col(idx, col, create=call.name == "Set")
            if col is None:
                return False
            call = Call(call.name, {**call.args, "_col": col}, call.children)
        if col is None:
            raise PQLError(f"{call.name} requires a column")
        shard = shard_of(int(col))
        owners = self.cluster.shard_nodes(idx.name, shard)
        owners = self._narrow_write_owners(idx, call, shard, int(col),
                                           owners)
        route_stats = global_route_stats()
        result = False
        pql = call.to_pql()
        for node in owners:
            if node.id == self.cluster.local.id:
                result = bool(self.local._execute_call(idx, call)) or result
                if result and call.name == "Set":
                    self.cluster.note_local_shards(idx.name, [shard])
            else:
                try:
                    route_stats.wire_bytes += len(pql)
                    out = self.cluster.client.query_node(
                        node.uri, idx.name, pql, [shard], remote=True
                    )
                    result = bool(out["results"][0]) or result
                except ClientError as e:
                    if e.is_node_fault:
                        node.state = "DEGRADED"
                    elif e.status != 404:  # 404 = schema lag: skip quietly
                        raise PQLError(str(e)) from e
        return result

    def _narrow_write_owners(self, idx, call: Call, shard: int, col: int,
                             owners):
        """Range-aware write routing for point writes: a plain ``Set``
        into a range-split shard goes only to its column span's owners
        (every other union owner converges through anti-entropy's union
        repair). Everything else — ``Clear`` (union repair cannot remove
        a bit a narrowed send skipped), mutex/bool (row moves), int
        (value overwrite), timestamped sets (extra view rows) — keeps
        the full union fan-out, as does a span whose owner departed."""
        route_stats = global_route_stats()
        if call.name != "Set" or call.arg("timestamp") is not None:
            route_stats.union_writes += 1
            return owners
        try:
            fname, _ = self.local._row_field_and_value(call)
            field = idx.field(fname)
        except PQLError:
            field = None
        if field is None or field.options.type in (TYPE_BOOL, TYPE_INT,
                                                   TYPE_MUTEX):
            route_stats.union_writes += 1
            return owners
        spans = self.cluster.range_write_spans(idx.name, shard)
        if spans:
            off = col - shard * SHARD_WIDTH
            for rlo, rhi, span_nodes in spans:
                if rlo <= off < rhi:
                    if span_nodes is not None:
                        route_stats.range_slices += 1
                        return span_nodes
                    route_stats.range_fallbacks += 1
                    return owners
        route_stats.union_writes += 1
        return owners

    # --------------------------------------------------------------- reduce

    def _reduce(self, idx, call: Call, local_res, partials, having=None):
        name = call.name
        if name == "Count":
            return int(local_res) + sum(int(p) for p in partials)
        if name in ("Sum",):
            total, count = local_res.value, local_res.count
            for p in partials:
                total += p["value"]
                count += p["count"]
            return ValCount(total, count)
        if name in ("Min", "Max"):
            want_max = name == "Max"
            best, count = (local_res.value, local_res.count) if local_res.count else (None, 0)
            for p in partials:
                if p["count"] == 0:
                    continue
                v = p["value"]
                if best is None or (v > best if want_max else v < best):
                    best, count = v, p["count"]
                elif v == best:
                    count += p["count"]
            return ValCount(best or 0, count)
        if name == "Rows":
            merged = set(local_res)
            for p in partials:
                merged.update(p)
            out = sorted(merged)
            limit = call.arg("limit", 0)
            return out[: int(limit)] if limit else out
        if name == "GroupBy":
            # Normalize each element to rowKey for keyed dim fields before
            # merging: a node whose translate replica lags emits rowID for
            # a row others report by key, which must not split the group.
            keyed: dict[str, bool] = {}

            def normalize(group) -> list[dict]:
                out = []
                for e in group:
                    fname = e["field"]
                    if fname not in keyed:
                        f = idx.field(fname)
                        keyed[fname] = bool(f and f.options.keys)
                    if keyed[fname] and "rowKey" not in e:
                        f = idx.field(fname)
                        (key,) = self.local._row_keys(idx, f, [e["rowID"]])
                        if key is not None:
                            e = {"field": fname, "rowKey": key}
                    out.append(e)
                return out

            # Merge key per element: rowKey when the dim field is keyed,
            # rowID otherwise.
            def gkey(group: list[dict]) -> tuple:
                return tuple(
                    e.get("rowKey", e.get("rowID")) for e in group
                )

            counts: dict[tuple, int] = {}
            sums: dict[tuple, int] = {}
            fields: dict[tuple, list] = {}
            for g in local_res:
                group = normalize(g.group)
                key = gkey(group)
                counts[key] = counts.get(key, 0) + g.count
                if g.sum is not None:
                    sums[key] = sums.get(key, 0) + g.sum
                fields[key] = group
            for p in partials:
                for g in p:
                    group = normalize(g["group"])
                    key = gkey(group)
                    counts[key] = counts.get(key, 0) + g["count"]
                    if g.get("sum") is not None:
                        sums[key] = sums.get(key, 0) + g["sum"]
                    fields[key] = group
            # Type-aware ordering: numeric rowIDs sort numerically (matching
            # the single-node executor), rowKeys lexicographically after.
            def order(kv):
                return tuple(
                    (1, e) if isinstance(e, str) else (0, int(e))
                    for e in kv[0]
                )

            if having is not None:
                counts = {
                    k: c for k, c in counts.items() if having(c, sums.get(k))
                }
            out = [
                GroupCount(fields[k], c, sum=sums.get(k))
                for k, c in sorted(counts.items(), key=order)
            ]
            limit = call.arg("limit", 0)
            return out[: int(limit)] if limit else out
        # bitmap calls → RowResult union
        if isinstance(local_res, RowResult):
            merged = local_res
            for p in partials:
                merged = merged.merge(_row_from_json(p))
            if idx.keys:
                merged.keys = sorted(
                    set(merged.keys or [])
                    | {k for p in partials for k in p.get("keys", [])}
                )
            return merged
        return local_res

    # ----------------------------------------------------------------- TopN

    def _submit_topn(self, idx, call: Call, local, groups,
                     deadline=None) -> Deferred:
        """Two-phase distributed TopN, pipelined: phase 1 (overfetched
        candidates) enqueues locally and departs remotely at SUBMIT time;
        phase 2 (exact recount of the merged candidate set) must wait for
        phase-1 readbacks, so it runs inside result()."""
        n = call.arg("n", 10)
        # threshold= filters on GLOBAL counts, so it is stripped from
        # every mapped sub-query (a per-node floor would drop candidates
        # whose cross-node sum qualifies) and applied after the merge
        mapped_args = {k: v for k, v in call.args.items() if k != "threshold"}
        explicit_ids = call.arg("ids")
        local1 = remote1 = None
        if explicit_ids is None:
            overfetch = max(n * TOPN_CANDIDATE_FACTOR, n + 10)
            phase1 = Call("TopN", {**mapped_args, "n": overfetch}, call.children)
            remote1 = spawn(lambda: self._map_remote(idx.name, phase1, groups,
                                                     deadline=deadline))
            local1 = self.local.submit(idx.name, phase1, shards=local,
                                       deadline=deadline)[0]

        def finalize():
            if explicit_ids is None:
                candidates = {p.id for p in local1.result()}
                for p in remote1():
                    candidates.update(pair["id"] for pair in p)
                if not candidates:
                    return []
                ids = sorted(candidates)
            else:
                ids = sorted(int(i) for i in explicit_ids)
            # phase 2: exact recount of the merged candidate set everywhere
            phase2 = Call("TopN", {**mapped_args, "ids": ids, "n": 0},
                          call.children)
            totals: dict[int, int] = {}
            local2, remote2 = run_concurrently(
                lambda: self.local._execute_call(idx, phase2, local),
                lambda: self._map_remote(idx.name, phase2, groups,
                                         deadline=deadline),
            )
            for p in local2:
                totals[p.id] = totals.get(p.id, 0) + p.count
            for partial in remote2:
                for pair in partial:
                    totals[pair["id"]] = totals.get(pair["id"], 0) + pair["count"]
            floor = max(1, int(call.arg("threshold", 0) or 0))
            order = sorted((-c, r) for r, c in totals.items() if c >= floor)
            pairs = [Pair(r, -negc) for negc, r in order[: n or len(order)]]
            field = idx.field(call.arg("_field") or call.arg("field"))
            return self.local._finish_pairs(idx, field, pairs)

        return Deferred(finalize)

    def _execute_includes(self, idx, call: Call, shards=None, deadline=None):
        target = self.local.includes_target(idx, call, shards)
        if target is None:
            return False
        col, shard = target
        # forward the NUMERIC column (a lagging translate replica on the
        # target could otherwise fail to resolve the key)
        call = Call(call.name, {**call.args, "column": int(col)},
                    call.children)
        if self.cluster.owns_shard(idx.name, shard):
            return self.local._execute_call(idx, call)
        node = self.cluster.primary_for_shard(idx.name, shard)
        out = self.cluster.client.query_node(
            node.uri, idx.name, call.to_pql(), [shard], remote=True,
            **({"deadline": deadline} if deadline is not None else {}),
        )
        return out["results"][0]


def _row_from_json(p: dict) -> RowResult:
    """Rebuild a RowResult from a peer's JSON columns."""
    cols = np.asarray(p.get("columns", []), np.uint64)
    segments: dict[int, np.ndarray] = {}
    if cols.size:
        shards = (cols >> np.uint64(20)).astype(np.int64)
        for shard in np.unique(shards).tolist():
            pos = cols[shards == shard] & np.uint64(SHARD_WIDTH - 1)
            segments[int(shard)] = pack_bits(pos, SHARD_WIDTH)
    return RowResult(segments, attrs=p.get("attrs") or {})
