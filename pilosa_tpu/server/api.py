"""Transport-neutral API façade.

Reference: api.go (SURVEY.md §2 #18) — validates, resolves index/field,
calls executor/holder; used by both the HTTP handler and the CLI so
in-process imports skip the network entirely.
"""

from __future__ import annotations

import collections
import datetime as dt
import threading

import numpy as np

from pilosa_tpu import __version__
from pilosa_tpu.executor import Executor
from pilosa_tpu.executor.result import result_to_json
from pilosa_tpu.shardwidth import SHARD_WIDTH, SHARD_WIDTH_EXP, shard_groups
from pilosa_tpu.storage import FieldOptions, Holder
from pilosa_tpu.storage.wal import MODE_FLUSH_ONLY
from pilosa_tpu.storage.field import (
    TYPE_BOOL,
    TYPE_INT,
    TYPE_MUTEX,
    TYPE_TIME,
)
from pilosa_tpu.storage.view import VIEW_STANDARD
from pilosa_tpu.utils.cost import (
    QueryProfile,
    activate_cost,
    deactivate_cost,
    new_cost_context,
)
from pilosa_tpu.utils.tracing import stage


class ApiError(Exception):
    def __init__(self, message: str, status: int = 400):
        super().__init__(message)
        self.status = status


class ImportRoutingError(ApiError):
    """A routed import failed on one or more owners AFTER other owners'
    batches (fanned out concurrently) already landed. Partial application
    is explicit: ``failed_nodes`` names the owners whose batch did not
    apply, ``node_errors`` maps each to its error text, and ``applied``
    counts the bits/values the healthy owners acknowledged — the caller
    can retry idempotently (imports are set-unions / last-write-wins) or
    surface exactly what is missing."""

    def __init__(self, node_errors: dict[str, str], applied: int,
                 status: int = 502):
        detail = "; ".join(f"{n}: {m}" for n, m in sorted(node_errors.items()))
        super().__init__(
            f"import failed on node(s) {', '.join(sorted(node_errors))} "
            f"({applied} changes applied on healthy owners): {detail}",
            status,
        )
        self.failed_nodes = sorted(node_errors)
        self.node_errors = dict(node_errors)
        self.applied = applied


# Default width of the bounded worker pool applying independent local
# shard groups of one import batch (fragments carry their own locks, so
# groups are lock-disjoint). Overridden by the ``ingest-workers``
# ServerConfig knob. Default 1 (serial). Re-measured after the
# write-path merge kernels (roaring/merge_kernels.py) replaced the
# per-container merge loops: serial apply itself got ~3.3x faster
# (8 shard groups x 60k bits, tmpfs: 5.0-5.4 M rows/s at 1 worker vs
# 1.57 before), 2 workers lands within noise of serial and 4 workers
# loses ~15% to pool overhead on a saturated box. The per-group work is
# now one big numpy kernel call (which releases the GIL) plus a thin
# Python envelope, so modest overlap is possible where spare cores
# exist — but not enough, measured, to move the default. Raise the knob
# where fragment writes pay real disk latency (fsync'd disks, network
# filesystems) so groups overlap I/O stalls — see docs/INGEST.md.
INGEST_WORKERS_DEFAULT = 1


class API:
    def __init__(self, holder: Holder, cluster=None, stats=None):
        self.holder = holder
        self.executor = Executor(holder)
        self.cluster = cluster  # pilosa_tpu.parallel.cluster (M4+); may be None
        self.stats = stats
        self.started_at = dt.datetime.now(dt.timezone.utc)
        # long-query log (reference long-query-time server knob): queries
        # slower than the threshold are logged and kept in a ring buffer.
        self.long_query_time: float = 0.0  # seconds; 0 = off
        # deque(maxlen): append is atomic and bounded, so concurrent HTTP
        # handler threads can't interleave an append/trim pair
        self.long_queries: collections.deque[dict] = collections.deque(maxlen=100)
        # exported from scrape one (/metrics); lock: += from concurrent
        # handler threads would lose increments (same hazard the deque
        # comment above documents)
        self.slow_queries_total = 0
        self._slow_lock = threading.Lock()
        # live JAX profiler capture (POST /debug/trace-device): one at a
        # time; empty dir string = default under the data dir
        self.trace_log_dir: str = ""
        self._device_trace_lock = threading.Lock()
        self.logger = None
        # reference max-writes-per-request server knob: reject queries
        # carrying more write calls than this (0 = unlimited)
        self.max_writes_per_request: int = 5000
        # Parallel ingest (docs/INGEST.md): local shard groups of one
        # import apply on a bounded pool (ingest-workers knob), and
        # routed batches fan out to owner nodes concurrently.
        self.ingest_workers: int = INGEST_WORKERS_DEFAULT
        # Coalescing serving pipeline (server/pipeline.py): read-only
        # requests ride Executor.submit through a wave-forming queue so
        # concurrent HTTP clients share micro-batched dispatches.
        self._pipeline = None  # created lazily on first pipelined query
        self._pipeline_lock = threading.Lock()
        # Serving QoS (pilosa_tpu.qos): admission gate + hedge policy +
        # breakers. Default bundle has the gate OFF (0 = unlimited) and
        # stock hedge knobs; Server.open swaps in the configured one.
        from pilosa_tpu.qos import ServingQos

        self.qos = ServingQos()
        # server default request deadline in seconds (0 = none); a
        # client header always wins (server/http.py)
        self.default_deadline_s: float = 0.0
        # Query cost plane (docs/OBSERVABILITY.md): per-(tenant, index)
        # usage accounting behind GET /debug/tenants + tenant_* metrics,
        # and the SLO burn-rate engine behind GET /debug/slo + slo_*
        # gauges. Server.open swaps in the configured SLO objectives.
        from pilosa_tpu.qos.slo import SLOEngine
        from pilosa_tpu.utils.cost import CostLedger

        self.cost = CostLedger()
        self.slo = SLOEngine()
        # async TopN cache recount (recalculate_caches): one worker at a
        # time, a request landing mid-recount queues exactly one re-run
        self._recalc_lock = threading.Lock()
        self._recalc_thread: threading.Thread | None = None
        self._recalc_rerun = False
        # background integrity scrubber (parallel/scrub.py); Server.open
        # wires one when scrub-interval > 0. scrub_now() runs ad-hoc
        # passes without it.
        self.scrubber = None
        # multi-process serving runtime (serving/mpserve.py OwnerRuntime)
        # when this process is a device owner fronted by SO_REUSEPORT
        # workers; None in single-process mode.
        self.mpserve = None
        # heat-driven residency tiering worker (storage/tiering.py);
        # Server.open wires one when residency-promote-interval > 0.
        # The write-invalidated result cache itself is the process
        # global (serving/rescache.py — fragment write hooks reach it
        # without plumbing), configured by Server.open via
        # result-cache-bytes; both default OFF.
        self.tierer = None
        # autopilot placement planner (autopilot/planner.py); Server.open
        # wires one when autopilot-enabled = true. The placement-override
        # TABLE it writes lives on the cluster and is honored by every
        # node whenever non-empty — the kill switch gates only the
        # planner ticker, never table adoption, so placement stays
        # consistent cluster-wide under mixed configs.
        self.autopilot = None
        # CDC plane (pilosa_tpu/cdc/): Server.open wires a CdcTailer
        # when cdc-enabled = true on a multi-node member (peers' write
        # events feed the result-cache invalidation path, lifting the
        # cluster-edge refusal), and a CdcFollower when cdc-follow names
        # an upstream (this node serves stale-bounded reads off the
        # feed and rejects writes).
        self.cdc = None
        self.follower = None
        # elastic membership plane (autopilot/elastic.py): Server.open
        # wires an ElasticManager on every clustered node — graceful
        # drain must work with the autopilot ticker off. None on a bare
        # API (no server), where drain endpoints answer 503.
        self.elastic = None
        # declared follower staleness budget in seconds (cdc-staleness-
        # budget knob); a request's X-Pilosa-Max-Staleness header wins
        # when tighter
        self.cdc_staleness_budget_s: float = 1.0

    # ---------------------------------------------------------------- query

    def query_raw(self, index: str, pql: str, shards=None,
                  remote: bool = False, opts: dict | None = None,
                  tenant: str = "default", deadline=None,
                  profile_out: list | None = None,
                  pre_admitted: bool = False,
                  on_submitted=None):
        """Execute and return raw result objects (serializer-agnostic).

        QoS envelope: edge requests (``remote=False``) pass the admission
        gate first — shed requests raise ApiError 429 with a Retry-After
        hint and never reach the pipeline. ``deadline`` (qos.Deadline)
        threads through the executor and every inter-node hop; expiry
        maps to ApiError 504.

        Cost envelope (docs/OBSERVABILITY.md): every request runs under
        a CostContext (device-ms, container scans, cache hits — the
        tenant ledger's feed); ``profile_out`` (a list) additionally
        requests a PQL PROFILE — the finished per-AST-node tree,
        cluster legs grafted, is appended to it. Edge outcomes feed the
        SLO engine (429 sheds excluded: shedding is policy, not
        failure)."""
        import time

        from pilosa_tpu.executor.executor import PQLError
        from pilosa_tpu.pql import ParseError
        from pilosa_tpu.qos import AdmissionError, DeadlineExceeded
        from pilosa_tpu.utils.tracing import global_query_tracker

        tracker = global_query_tracker()
        inflight = tracker.start(index, pql, tenant=tenant, remote=remote)
        inflight_token = (tracker.activate(inflight)
                          if inflight is not None else None)
        prof = (QueryProfile(index, pql, self.node_id())
                if profile_out is not None else None)
        ctx = new_cost_context(tenant, index, prof)
        if ctx is None:
            # cost plane disabled (kill switch): a profile would render
            # as a plausible-looking all-zero tree — mark it instead of
            # sending a debugger down a false trail
            prof = None
        cost_token = activate_cost(ctx)
        t_start = time.perf_counter()
        err_status = None
        slot = None
        try:
            if not remote and not pre_admitted:
                # pre_admitted: a serving worker's gate already admitted
                # this request before it crossed the shared-memory ring
                # (serving/worker.py) — double-gating would shed
                # requests the node as a whole has capacity for
                try:
                    with stage("qos.admit", tenant=tenant):
                        slot = self.qos.admission.admit(tenant)
                except AdmissionError as e:
                    err = ApiError(str(e), 429)
                    err.retry_after = e.retry_after
                    raise err from e
            return self._query_raw_admitted(
                index, pql, shards, remote, opts, tenant, deadline,
                slot, on_submitted,
            )
        except ApiError as e:
            err_status = e.status
            raise
        except Exception:
            err_status = 500
            raise
        finally:
            deactivate_cost(cost_token)
            elapsed = time.perf_counter() - t_start
            if not remote and ctx is not None:
                # one ledger fold + one SLO event per edge request; the
                # cost kill switch (bench baselines) zeroes this path by
                # making ctx None
                error = err_status is not None and err_status >= 500
                self.cost.record_query(tenant, index, ctx, elapsed,
                                       error=error)
                if err_status != 429:
                    self.slo.record(elapsed, error=error)
            if profile_out is not None and err_status is None:
                profile_out.append(
                    prof.to_json(ctx) if prof is not None
                    else {"disabled": True,
                          "reason": "cost plane is disabled on this node"}
                )
            tracker.finish(inflight, inflight_token)

    def _query_raw_admitted(self, index, pql, shards, remote, opts,
                            tenant, deadline, slot, on_submitted=None):
        import time

        from pilosa_tpu.executor.executor import PQLError
        from pilosa_tpu.parallel.cluster import ClusterDegradedError
        from pilosa_tpu.pql import ParseError
        from pilosa_tpu.qos import DeadlineExceeded
        t0 = time.perf_counter()
        try:
            query = pql
            if isinstance(pql, str):
                from pilosa_tpu.pql import parse

                with stage("pql.parse"):
                    query = parse(pql)
            writes = (len(query.write_calls())
                      if hasattr(query, "write_calls") else 1)
            if 0 < self.max_writes_per_request < writes:
                raise ApiError(
                    f"too many writes in request: {writes} > "
                    f"max-writes-per-request {self.max_writes_per_request}"
                )
            if writes and not remote:
                # minority side of a partition is READ-ONLY: an acked
                # write here could be orphaned by the majority's resize
                # (docs/OPERATIONS.md failure model); shed with 503 +
                # Retry-After like the admission gate sheds with 429
                self._check_not_degraded_write()
            kwargs = {"shards": shards}
            if getattr(self.executor, "accepts_remote", False):
                kwargs["remote"] = remote
            if deadline is not None:
                kwargs["deadline"] = deadline
            # Read-only MICRO-BATCHABLE requests ride the coalescing
            # pipeline (waves of concurrent requests share device
            # dispatches — see server/pipeline.py). Requests carrying
            # writes, and host-eager reads (Rows etc.) that submit()
            # would evaluate fully on the dispatcher thread, keep the
            # eager path so request-thread concurrency is unchanged.
            from pilosa_tpu.executor.executor import pipeline_coalescable

            if (writes == 0 and pipeline_coalescable(query)
                    and hasattr(self.executor, "submit")):
                if self._pipeline is None:
                    with self._pipeline_lock:
                        if self._pipeline is None:
                            from pilosa_tpu.server.pipeline import (
                                QueryPipeline,
                            )

                            self._pipeline = QueryPipeline(self)
                # plain edge reads (PQL string, no explicit shards, no
                # deadline, no result options) are dedupe-eligible:
                # identical queries landing in one wave submit once and
                # share results + pre-serialized response bytes
                key = None
                if (isinstance(pql, str) and shards is None
                        and deadline is None and not remote and not opts):
                    # PROFILE requests stay dedupe-eligible: a deduped
                    # follower reports dedupeHit=true with near-zero
                    # measured cost — which is the truth (it rode the
                    # leader's execution); the leader's profile carries
                    # the full tree (server/pipeline.py tags both)
                    key = (index, pql)
                deferreds = self._pipeline.run(index, query, kwargs,
                                               key=key)
                if on_submitted is not None:
                    # the wave containing this request has been formed
                    # and submitted: the multi-process owner uses this
                    # as the dedupe-join cutoff (serving/mpserve.py) —
                    # the same boundary the pipeline's own wave dedupe
                    # draws, so read-your-writes is preserved across
                    # deployment shapes
                    on_submitted()
                # Same stats/trace envelope as Executor.execute (shared
                # helper) — the timer here observes resolve latency,
                # i.e. what this request actually waited for.
                from pilosa_tpu.executor.executor import instrument_calls

                handles = iter(deferreds)
                with stage("executor.resolve"):
                    results = instrument_calls(
                        index, query.calls,
                        lambda call: next(handles).result(),
                    )
            else:
                if on_submitted is not None:
                    on_submitted()  # eager path: executing right now
                with stage("executor.execute"):
                    results = self.executor.execute(index, query, **kwargs)
            if opts:
                results = self._apply_request_opts(index, results, opts)
            if writes:
                # attr writes change results (Row responses carry
                # attrs) WITHOUT a fragment write event — fence every
                # cached result of the index (serving/rescache.py);
                # bit writes already invalidated at their fragments.
                # On a multi-node edge, a routed write's fragment hook
                # fires on the OWNER, not here: fence the coordinator's
                # own cache too, so read-your-writes holds through the
                # write's node ahead of the CDC feed's bounded lag.
                remote_owned = (self.cluster is not None
                                and len(self.cluster.nodes) > 1
                                and not remote)
                if remote_owned or any(
                    c.name in ("SetRowAttrs", "SetColumnAttrs")
                    for c in query.write_calls()
                ):
                    from pilosa_tpu.serving import rescache

                    idx = self.holder.index(index)
                    if idx is not None:
                        rescache.invalidate_index_wide(idx.scope, index)
                # ACK gate: a 200 means DURABLE. In group mode this
                # parks the request until the commit thread has fsynced
                # the group containing its op records (one fsync covers
                # the whole wave of concurrent writers — storage/wal.py);
                # per-op already fsynced inline, flush-only promises
                # nothing, and both make this a no-op.
                self._ack_durable()
            return results
        except DeadlineExceeded as e:
            self.qos.note_deadline_expired()
            raise ApiError(str(e), 504) from e
        except ClusterDegradedError as e:
            # a read that needed shards owned by unreachable peers while
            # this node lacks quorum: 503 so clients back off and retry
            # against a healthy (majority-side) node
            raise self._degraded_error(str(e)) from e
        except (ParseError, PQLError) as e:
            raise ApiError(str(e)) from e
        finally:
            if slot is not None:
                slot.release()
            elapsed = time.perf_counter() - t0
            if self.long_query_time > 0 and elapsed >= self.long_query_time:
                from pilosa_tpu.utils.tracing import current_span

                entry = {
                    "index": index,
                    "pql": (pql if isinstance(pql, str)
                            else str(pql))[:1024],
                    "seconds": round(elapsed, 4),
                    "at": dt.datetime.now(dt.timezone.utc).isoformat(),
                }
                cur = current_span()
                if cur is not None:
                    # sampled offender: the ring keeps its FULL span tree
                    # (snapshot as-of now; open ancestors render with
                    # duration-to-date), so a slow query is explained,
                    # not just counted. Unsampled slow queries keep the
                    # text entry only — raise trace-sample-rate to
                    # explain a recurring one.
                    entry["traceId"] = cur.trace_id
                    entry["trace"] = cur.root().to_json()
                with self._slow_lock:
                    self.slow_queries_total += 1
                self.long_queries.append(entry)
                if self.logger is not None:
                    self.logger.warning(
                        "long query (%.3fs > %.3fs) on %s: %s",
                        elapsed, self.long_query_time, index, entry["pql"],
                    )

    def query(self, index: str, pql: str, shards=None, remote: bool = False,
              opts: dict | None = None, tenant: str = "default",
              deadline=None, profile_out: list | None = None) -> dict:
        results = self.query_raw(index, pql, shards=shards, remote=remote,
                                 opts=opts, tenant=tenant, deadline=deadline,
                                 profile_out=profile_out)
        return {"results": [result_to_json(r) for r in results]}

    def query_json_bytes(self, index: str, pql: str, shards=None,
                         remote: bool = False, opts: dict | None = None,
                         tenant: str = "default", deadline=None,
                         profile_out: list | None = None,
                         pre_admitted: bool = False,
                         on_submitted=None,
                         cache_hit_out: list | None = None) -> bytes:
        """The whole JSON response envelope, pre-serialized (serving fast
        lane): hot result shapes encode straight to bytes — memoized on
        the result objects, so a deduped wave of identical queries
        serializes once — instead of dict-building + json.dumps per
        request (see executor/result.py).

        Result cache (serving/rescache.py): a cache-eligible request —
        the exact ``_SharedDeferred`` dedupe eligibility, persisted
        across waves — is first answered from pre-serialized cached
        bytes (``cache_hit_out`` receives True so callers can tag the
        hit); a miss snapshots the write-version fence BEFORE execution
        and fills afterwards, so a write group-committing concurrently
        with the fill invalidates it (the insert refuses to land)."""
        from pilosa_tpu.executor.result import results_json_bytes
        scope = None
        snap = None
        if (not remote and shards is None and deadline is None and not opts
                and isinstance(pql, str)):
            from pilosa_tpu.serving.rescache import global_result_cache

            cache = global_result_cache()
            # A cluster edge result folds in remote data whose writes
            # land on OTHER nodes' fragments — cacheable only while the
            # CDC tailer is live, feeding peers' write events into the
            # invalidation path (pilosa_tpu/cdc/). Without it (or with
            # a peer's feed lagging) the edge refuses, and the reason is
            # counted so operators can watch the cache turn on
            # (/debug/rescache refusals).
            edge_ok = (self.cluster is None
                       or len(self.cluster.nodes) <= 1)
            if cache.enabled and not edge_ok:
                if self.cdc is not None and self.cdc.live():
                    edge_ok = True
                else:
                    cache.record_refusal(
                        "cluster-no-cdc" if self.cdc is None
                        else "cdc-stale")
            if cache.enabled and edge_ok:
                idx = self.holder.index(index)
                if idx is not None:
                    scope = idx.scope
                    payload = cache.peek(scope, index, pql)
                    if payload is not None:
                        return self._serve_result_cache_hit(
                            cache, scope, index, pql, payload, tenant,
                            profile_out, pre_admitted, on_submitted,
                            cache_hit_out,
                        )
                    if self._result_cacheable(pql):
                        # a MISS only for fillable queries: writes and
                        # host-eager reads must not dilute the hit rate
                        # operators gate on
                        cache.record_miss()
                        snap = cache.version()  # the fill-race cutoff
                    else:
                        scope = None
        results = self.query_raw(index, pql, shards=shards, remote=remote,
                                 opts=opts, tenant=tenant, deadline=deadline,
                                 profile_out=profile_out,
                                 pre_admitted=pre_admitted,
                                 on_submitted=on_submitted)
        with stage("result.encode"):
            payload = results_json_bytes(results)
        if snap is not None and scope is not None:
            from pilosa_tpu.pql import parse
            from pilosa_tpu.serving.rescache import query_field_deps

            query = parse(pql)  # memoized; the request already paid it
            cache.insert(scope, index, pql, payload,
                         query_field_deps(query), snap)
        return payload

    def _result_cacheable(self, pql: str) -> bool:
        """Read-only + pipeline-coalescable — the ``_SharedDeferred``
        dedupe eligibility family, persisted across waves. Parse errors
        defer to query_raw, which surfaces them properly."""
        from pilosa_tpu.executor.executor import pipeline_coalescable
        from pilosa_tpu.pql import parse

        try:
            query = parse(pql)  # memoized
        except Exception:
            return False
        return not query.write_calls() and pipeline_coalescable(query)

    def _serve_result_cache_hit(self, cache, scope, index, pql, payload,
                                tenant, profile_out, pre_admitted,
                                on_submitted, cache_hit_out) -> bytes:
        """The hit half of query_raw's request envelope: admission
        (unless the serving worker already admitted), inflight
        tracking, a trace span, ledger + SLO accounting — a cache hit
        is billed as a query with near-zero device-ms, never invisible.
        Heat is deliberately NOT recorded: residency should follow the
        traffic that actually executes, and a cache hit needs no
        device bytes (invalidation re-heats the shards on the next
        miss)."""
        import time

        from pilosa_tpu.qos import AdmissionError
        from pilosa_tpu.utils.tracing import (
            global_query_tracker,
            global_tracer,
        )

        tracker = global_query_tracker()
        inflight = tracker.start(index, pql, tenant=tenant, remote=False)
        inflight_token = (tracker.activate(inflight)
                          if inflight is not None else None)
        ctx = new_cost_context(tenant, index, None)
        t_start = time.perf_counter()
        err_status = None
        slot = None
        try:
            if not pre_admitted:
                try:
                    with stage("qos.admit", tenant=tenant):
                        slot = self.qos.admission.admit(tenant)
                except AdmissionError as e:
                    err = ApiError(str(e), 429)
                    err.retry_after = e.retry_after
                    raise err from e
            with global_tracer().span("rescache.hit", index=index):
                cache.record_hit(scope, index, pql)
            if on_submitted is not None:
                # the dedupe-join cutoff (serving/mpserve.py): a cache
                # hit resolves immediately, so late identical arrivals
                # must start their own (equally cached) pass
                on_submitted()
            if cache_hit_out is not None:
                cache_hit_out.append(True)
            if profile_out is not None:
                # the honest near-zero tree: no parse, no plan, no
                # dispatch happened — the flag explains it, exactly as
                # dedupeHit does for in-wave followers
                if ctx is not None:
                    profile_out.append({
                        "node": self.node_id(), "index": index,
                        "pql": pql[:1024], "wave": 1,
                        "dedupeHit": False, "resultCacheHit": True,
                        "calls": [], "remote": [],
                        "totals": ctx.totals(),
                    })
                else:
                    profile_out.append(
                        {"disabled": True,
                         "reason": "cost plane is disabled on this node"})
            return payload
        except ApiError as e:
            err_status = e.status
            raise
        except Exception:
            err_status = 500
            raise
        finally:
            if slot is not None:
                slot.release()
            elapsed = time.perf_counter() - t_start
            if ctx is not None:
                error = err_status is not None and err_status >= 500
                # a 429-shed request never received the cached bytes:
                # billed as a query (like query_raw's shed path) but
                # not as a cache hit
                self.cost.record_query(
                    tenant, index, ctx, elapsed, error=error,
                    result_cache_hit=err_status is None,
                )
                if err_status != 429:
                    self.slo.record(elapsed, error=error)
            tracker.finish(inflight, inflight_token)

    def query_batch(self, items: list) -> list:
        """Execute a wave-batched internal request (/internal/query-batch):
        ``items`` is ``[(index, pql, shards), ...]`` (optionally a 4th
        element: the item's ``X-Pilosa-Trace`` context) — remote
        sub-queries a peer coalesced toward this node. Every item is
        SUBMITTED before any is resolved, so the batch shares
        micro-batched device dispatches exactly like a local wave
        (server/pipeline.py).

        Returns one outcome per item: ``("ok", [raw results])`` —
        ``("ok", [raw results], span_tree)`` when the item carried trace
        context — or ``("err", message, status)``; per-item isolation,
        one bad sub-query cannot poison its batchmates. Write calls are
        rejected per item: the batch route exists for coalesced reads,
        and remote write fan-out keeps its eager per-request
        semantics."""
        from pilosa_tpu.executor.executor import PQLError, instrument_calls
        from pilosa_tpu.pql import ParseError, parse
        from pilosa_tpu.utils.tracing import global_tracer, use_span

        tracer = global_tracer()
        submitted: list = []
        for item in items:
            index, pql, shards = item[0], item[1], item[2]
            trace_hdr = item[3] if len(item) > 3 else None
            # one remote-root span per traced batch item; its submit and
            # resolve phases re-activate it below so device spans nest
            # correctly, and the finished subtree rides the response
            # back to the coordinator's tree
            span = tracer.remote_span(trace_hdr, "rpc.query",
                                      node=self.node_id(), index=index,
                                      batched=True)
            try:
                query = parse(pql)
                if query.write_calls():
                    raise ApiError(
                        "writes are not allowed on /internal/query-batch")
                if self.holder.index(index) is None:
                    raise ApiError(f"index {index!r} not found", 404)
                kwargs = {"shards": shards}
                if getattr(self.executor, "accepts_remote", False):
                    kwargs["remote"] = True
                if hasattr(self.executor, "submit"):
                    if span is not None:
                        with use_span(span):
                            handles = self.executor.submit(index, query,
                                                           **kwargs)
                    else:
                        handles = self.executor.submit(index, query,
                                                       **kwargs)
                    submitted.append(("defs", index, query, handles, span))
                else:
                    submitted.append(
                        ("eager", index, query,
                         self.executor.execute(index, query, **kwargs),
                         span))
            except (ParseError, PQLError) as e:
                submitted.append(("err", str(e), 400))
            except ApiError as e:
                submitted.append(("err", str(e), e.status))
            except Exception as e:  # item-level internal error
                submitted.append(("err", f"internal: {e}", 500))
        out: list = []
        for entry in submitted:
            if entry[0] == "err":
                out.append(entry)
                continue
            kind, index, query, payload, span = entry
            try:
                if kind == "defs":
                    handles = iter(payload)
                    if span is not None:
                        with use_span(span):
                            results = instrument_calls(
                                index, query.calls,
                                lambda call: next(handles).result(),
                            )
                    else:
                        results = instrument_calls(
                            index, query.calls,
                            lambda call: next(handles).result(),
                        )
                else:
                    results = payload
                if span is not None:
                    tracer.finish_root(span)
                    out.append(("ok", results, span.to_json()))
                else:
                    out.append(("ok", results))
            except (ParseError, PQLError) as e:
                out.append(("err", str(e), 400))
            except ApiError as e:
                out.append(("err", str(e), e.status))
            except Exception as e:
                out.append(("err", f"internal: {e}", 500))
        return out

    def _degraded_error(self, message: str) -> ApiError:
        """503 + Retry-After for the degraded (minority-partition)
        read-only mode, counted on the QoS shed path so operators see
        partition sheds beside admission sheds."""
        from pilosa_tpu.utils.stats import global_stats

        global_stats().count("qos_shed", 1, {"reason": "cluster_degraded"})
        err = ApiError(message, 503)
        err.retry_after = 5.0
        return err

    def _check_not_degraded_write(self) -> None:
        """Shed edge writes while this node is the minority side of a
        partition (cluster.degraded — docs/OPERATIONS.md failure
        model) OR while its storage is degraded (ENOSPC/EIO tripped
        the StorageHealth latch — storage/integrity.py); locally-owned
        reads still serve either way. A CDC follower is read-only by
        construction — a write landing here would silently diverge the
        mirror from its upstream."""
        self._check_not_follower()
        self._check_not_storage_degraded()
        self._check_not_draining()
        cluster = self.cluster
        if cluster is None or not getattr(cluster, "degraded", False):
            return
        raise self._degraded_error(
            "cluster degraded (no member quorum): writes are shed on "
            "this node until the partition heals; locally-owned reads "
            "still serve"
        )

    def _check_not_draining(self) -> None:
        """Shed edge writes on the target of an in-flight drain
        (elastic plane): its shard groups are moving off, and an acked
        write landing mid-departure is exactly the lost-write window
        the drain closes by shedding FIRST. Reads keep serving the
        tail. 503 + Retry-After with the ``draining`` qos_shed
        reason."""
        cluster = self.cluster
        if cluster is None or not getattr(cluster, "draining", False):
            return
        from pilosa_tpu.qos import SHED_REASON_DRAINING
        from pilosa_tpu.utils.stats import global_stats

        global_stats().count("qos_shed", 1,
                             {"reason": SHED_REASON_DRAINING})
        err = ApiError(
            "node is draining: writes are shed while its shard groups "
            "move off; reads still serve until the drain completes",
            503,
        )
        err.retry_after = 5.0
        raise err

    def _check_not_storage_degraded(self) -> None:
        """503 + Retry-After while the disk is sick (a failed WAL
        fsync, snapshot, or .meta write tripped the read-only
        storage_degraded latch). Auto-clears when the health probe's
        write succeeds — clients that honor Retry-After ride it out."""
        health = getattr(self.holder, "health", None)
        if health is None or not health.degraded:
            return
        from pilosa_tpu.utils.stats import global_stats

        global_stats().count("qos_shed", 1, {"reason": "storage_degraded"})
        err = ApiError(
            f"storage degraded ({health.reason}): writes are shed on "
            "this node until a probe write succeeds; reads still serve",
            503,
        )
        err.retry_after = 5.0
        raise err

    def check_staleness(self, max_staleness_s: float | None = None) -> None:
        """Stale-bounded read gate for CDC followers: reject with 503 +
        Retry-After when this replica's feed lag exceeds the budget —
        the request's ``X-Pilosa-Max-Staleness`` header when given, the
        declared ``cdc-staleness-budget`` otherwise. A no-op on
        non-follower nodes (members answer fresh reads; a staleness
        budget is a follower contract)."""
        follower = self.follower
        if follower is None:
            return
        budget = self.cdc_staleness_budget_s
        if max_staleness_s is not None:
            budget = min(budget, max_staleness_s) if budget > 0 \
                else max_staleness_s
        if budget <= 0:
            return
        staleness = follower.staleness_s()
        if staleness > budget:
            from pilosa_tpu.utils.stats import global_stats

            global_stats().count("qos_shed", 1,
                                 {"reason": "follower_stale"})
            err = ApiError(
                f"read replica is {staleness:.3f}s stale, over the "
                f"{budget:.3f}s staleness budget; retry or relax "
                "X-Pilosa-Max-Staleness", 503,
            )
            # capped: an infinite staleness (still in initial sync)
            # must not overflow the Retry-After int rendering
            err.retry_after = min(30.0, max(0.1, staleness - budget))
            raise err

    def _ack_durable(self) -> None:
        """Group-commit durability barrier for the current request's
        writes (applied on THIS node — a routed write's remote portions
        are barriered by each replica before its own 200). In the
        fsyncing modes the key-translation log syncs too: a keyed
        write's bit without its key→ID mapping would recover attributed
        to a different key."""
        wal = getattr(self.holder, "wal", None)
        if wal is None or wal.mode == MODE_FLUSH_ONLY:
            return
        with stage("wal.barrier"):
            translate = getattr(self.holder, "translate", None)
            if translate is not None:
                translate.sync()
            wal.barrier()

    def _apply_request_opts(self, index: str, results: list,
                            opts: dict) -> list:
        """Request-level result options (reference QueryRequest
        ColumnAttrs / ExcludeColumns / ExcludeRowAttrs — SURVEY.md §2
        #19 handler query args; exact reference spelling is MED, the
        URL-param names mirror the PQL Options() args). Applied on the
        coordinator AFTER the cross-node merge, to every
        row-materializing result of the request."""
        from pilosa_tpu.executor.executor import (
            column_attr_sets,
            strip_columns,
        )
        from pilosa_tpu.executor.result import RowResult

        idx = self.holder.index(index)
        out = []
        for res in results:
            if isinstance(res, RowResult):
                if opts.get("columnAttrs") and idx is not None:
                    res.column_attrs = column_attr_sets(idx, res)
                if opts.get("excludeRowAttrs"):
                    res.attrs = {}
                if opts.get("excludeColumns"):
                    res = strip_columns(res)
            out.append(res)
        return out

    # --------------------------------------------------------------- schema

    def _check_not_follower(self) -> None:
        """A CDC follower is read-only by construction — a local write
        (data or schema) would silently diverge the mirror from its
        upstream. The follower's own tail-apply bypasses the API and
        writes through the holder directly."""
        if self.follower is not None:
            raise ApiError(
                "this node is a CDC read replica (cdc-follow): writes "
                "must go to the upstream cluster", 403,
            )

    def create_index(self, name: str, keys: bool = False,
                     track_existence: bool = True) -> dict:
        self._check_not_follower()
        self._check_not_storage_degraded()  # schema writes hit .meta
        try:
            idx = self.holder.create_index(
                name, keys=keys, track_existence=track_existence
            )
        except ValueError as e:
            status = 409 if "already exists" in str(e) else 400
            raise ApiError(str(e), status) from e
        self._broadcast({"type": "create-index", "index": name, "keys": keys,
                         "trackExistence": track_existence})
        return idx.schema()

    def _broadcast(self, message: dict) -> None:
        if self.cluster is not None:
            self.cluster.send_sync(message)

    def delete_index(self, name: str) -> None:
        self._check_not_follower()
        try:
            self.holder.delete_index(name)
        except KeyError as e:
            raise ApiError(str(e), 404) from e
        self._broadcast({"type": "delete-index", "index": name})

    def create_field(self, index: str, name: str, options: dict | None = None) -> dict:
        self._check_not_follower()
        self._check_not_storage_degraded()  # schema writes hit .meta
        idx = self._index(index)
        try:
            opts = FieldOptions.from_dict(options or {})
            field = idx.create_field(name, opts)
        except ValueError as e:
            status = 409 if "already exists" in str(e) else 400
            raise ApiError(str(e), status) from e
        self._broadcast({"type": "create-field", "index": index, "field": name,
                         "options": field.options.to_dict()})
        return {"name": field.name, "options": field.options.to_dict()}

    def delete_field(self, index: str, name: str) -> None:
        self._check_not_follower()
        idx = self._index(index)
        try:
            idx.delete_field(name)
        except KeyError as e:
            raise ApiError(str(e), 404) from e
        self._broadcast({"type": "delete-field", "index": index, "field": name})

    def schema(self) -> dict:
        return {"indexes": self.holder.schema()}

    # --------------------------------------------------------------- import

    def import_bits(self, index: str, field: str, rows, columns,
                    timestamps=None, clear: bool = False,
                    remote: bool = False) -> int:
        """Bulk bit import (reference api.Import / fragment.bulkImport):
        batches are grouped by shard and written fragment-wise; in a
        cluster, each shard group is routed to every replica owner."""
        idx = self._index(index)
        fld = self._field(idx, field)
        if not remote:
            self._check_not_degraded_write()
        # validate BEFORE routing: the roaring bulk route ships pre-built
        # bitmaps that the receiving end cannot re-validate, so bad input
        # must 400 here, not corrupt or 500 downstream
        try:
            rows_i = np.asarray(rows, dtype=np.int64)
            columns_i = np.asarray(columns, dtype=np.int64)
        except OverflowError as e:
            raise ApiError(f"row/column id out of range: {e}") from e
        if rows_i.shape != columns_i.shape:
            raise ApiError("rows and columns must be the same length")
        if rows_i.size and (rows_i.min() < 0 or columns_i.min() < 0):
            raise ApiError("rows and columns must be non-negative")
        if timestamps is not None and len(timestamps) != rows_i.size:
            raise ApiError("timestamps must match rows length")
        if (fld.options.type == TYPE_BOOL and rows_i.size
                and rows_i.max() > 1):
            raise ApiError("bool field rows must be 0 (false) or 1 (true)")
        if not remote and self.cluster is not None and len(self.cluster.nodes) > 1:
            return self._route_import(
                index, field, rows_i, columns_i, timestamps, clear,
                values=None,
            )
        rows = rows_i.astype(np.uint64)
        columns = columns_i.astype(np.uint64)
        if rows.size == 0:
            return 0
        import time

        from pilosa_tpu.utils.pool import concurrent_map
        from pilosa_tpu.utils.stats import global_stats

        t0 = time.perf_counter()
        order, boundaries, shards_sorted = shard_groups(columns)
        rows, columns = rows[order], columns[order]
        ts_sorted = [timestamps[i] for i in order] if timestamps is not None else None
        # resolve the view ONCE before the fan-out below — Field.view's
        # create lock makes racing creation safe, but there is no reason
        # to funnel every worker through it
        view = None if clear else fld.view(VIEW_STANDARD, create=True)

        def apply_group(i: int) -> int:
            lo, hi = int(boundaries[i]), int(boundaries[i + 1])
            shard = int(shards_sorted[lo])
            pos = columns[lo:hi] & np.uint64(SHARD_WIDTH - 1)
            changed = 0
            if clear:
                for r, p in zip(rows[lo:hi].tolist(), pos.tolist()):
                    changed += fld.clear_bit(
                        int(r), (shard << SHARD_WIDTH_EXP) + int(p)
                    )
                return changed
            # existence rides the same group worker: the batch is
            # already shard-sorted, so the per-batch argsort inside
            # mark_columns_exist (a serial tail ~half as costly as the
            # data write itself) is skipped entirely
            idx.mark_columns_exist_shard(shard, pos)
            frag = view.fragment(shard, create=True)
            if fld.options.type in (TYPE_MUTEX, TYPE_BOOL):
                # single-value fields: the mutex-aware path clears each
                # column's previous row in the same pass — plain
                # bulk_import would leave columns set in several rows
                changed += frag.import_mutex(rows[lo:hi], pos)
            else:
                changed += frag.bulk_import(rows[lo:hi], pos)
            if ts_sorted is not None and fld.options.type == TYPE_TIME:
                # group the timestamped bits by quantum VIEW and write
                # each view's batch with one bulk_import (the standard
                # view already got them above) — a per-bit set_bit loop
                # re-walks view creation and re-writes standard per bit
                from pilosa_tpu.storage.view import views_for_time

                by_view: dict[str, list] = {}
                for j, ts in enumerate(ts_sorted[lo:hi]):
                    if not ts:
                        continue
                    for vname in views_for_time(
                        VIEW_STANDARD, fld.options.time_quantum,
                        _parse_ts(ts),
                    ):
                        by_view.setdefault(vname, []).append(lo + j)
                for vname, idxs in by_view.items():
                    sel = np.asarray(idxs, np.int64)
                    vfrag = fld.view(vname, create=True).fragment(
                        shard, create=True
                    )
                    vfrag.bulk_import(
                        rows[sel], columns[sel] & np.uint64(SHARD_WIDTH - 1)
                    )
            return changed

        n_groups = boundaries.size - 1
        if n_groups > 1 and self.ingest_workers > 1:
            # shard groups touch disjoint fragments (each with its own
            # lock): apply them on a bounded pool — numpy slicing and the
            # op-log fsync both release the GIL, so groups overlap
            changed = sum(concurrent_map(
                apply_group, range(n_groups),
                max_workers=self.ingest_workers,
            ))
        else:
            changed = sum(apply_group(i) for i in range(n_groups))
        elapsed = time.perf_counter() - t0
        from pilosa_tpu.utils.cost import cost_enabled

        if cost_enabled():
            # per-shard write heat for the import (one record per shard
            # group; the fragment-level hook only fires under a request
            # cost context, so this is the bulk path's single record)
            from pilosa_tpu.storage.heat import global_heat

            heat = global_heat()
            for i in range(n_groups):
                lo, hi = int(boundaries[i]), int(boundaries[i + 1])
                heat.record_write(index, field, int(shards_sorted[lo]),
                                  n=float(hi - lo), scope=idx.scope)
        stats = global_stats()
        tags = {"kind": "bits"}
        stats.count("ingest_rows", rows.size, tags=tags)
        stats.observe("ingest_batch_size", rows.size, tags=tags)
        stats.timing("ingest_apply", elapsed, tags=tags)
        if elapsed > 0:
            stats.gauge("ingest_rows_per_sec", rows.size / elapsed, tags=tags)
        if not clear and self.cluster is not None:
            self.cluster.note_local_shards(
                index, np.unique(shards_sorted).tolist()
            )
        self._ack_durable()  # the import 200 means durable, same as query
        return int(changed)

    def _route_import(self, index, field, rows, columns, timestamps, clear,
                      values=None) -> int:
        """Split an import batch by shard owner and fan out CONCURRENTLY
        (reference api.Import routing — SURVEY.md §3.3; fan-out mirrors
        the read path's concurrent_map, so routed wall time is the MAX of
        per-owner latencies, not the sum). Local portions apply with
        remote=True to stop recursion.

        Destination building is one ``shard_groups`` pass + numpy slices
        of the sort permutation — no per-shard ``np.nonzero`` rescans, no
        Python-list element copies. Per-node errors are captured (one
        dead replica cannot abort or hide the others' batches); imports
        are idempotent (set/clear unions, last-write-wins values), so a
        NODE fault earns one retry before surfacing. Any remaining
        failures raise ImportRoutingError naming the failed nodes and the
        count already applied elsewhere."""
        import time

        import numpy as np

        from pilosa_tpu.parallel.client import ClientError
        from pilosa_tpu.utils.pool import concurrent_map
        from pilosa_tpu.utils.stats import global_stats

        try:
            columns_arr = np.asarray(columns, dtype=np.int64)
            rows_arr = (np.asarray(rows, dtype=np.int64)
                        if values is None else None)
            values_arr = (np.asarray(values, dtype=np.int64)
                          if values is not None else None)
        except (OverflowError, ValueError) as e:
            raise ApiError(f"row/column/value out of range: {e}") from e
        if values_arr is not None and columns_arr.shape != values_arr.shape:
            raise ApiError("columns and values must be the same length")
        if columns_arr.size == 0:
            return 0
        ts_arr = (np.asarray(list(timestamps), dtype=object)
                  if timestamps is not None else None)

        bulk_roaring = False
        if values is None:
            # mutex/bool batches must NOT ride the roaring route: its
            # receiver unions blindly, so a remote replica would keep a
            # column's previous row set (single-value invariant broken,
            # replicas diverged) while the local replica cleared it via
            # import_mutex — ship them as import_bits so the remote end
            # re-runs the mutex-aware path
            fld_type = self._field(self._index(index), field).options.type
            bulk_roaring = (timestamps is None and not clear
                            and fld_type not in (TYPE_MUTEX, TYPE_BOOL))

        from pilosa_tpu.parallel.cluster import global_route_stats

        route_stats = global_route_stats()
        order, bounds, shards_sorted = shard_groups(columns_arr)
        local_parts: list[np.ndarray] = []
        remote_parts: dict[str, tuple[object, list[np.ndarray]]] = {}

        def dispatch(node, sel: np.ndarray) -> None:
            if node.id == self.cluster.local.id:
                local_parts.append(sel)
            else:
                remote_parts.setdefault(node.id, (node, []))[1].append(sel)

        for i in range(bounds.size - 1):
            lo, hi = int(bounds[i]), int(bounds[i + 1])
            sel = order[lo:hi]
            shard = int(shards_sorted[lo])
            owners = self.cluster.shard_nodes(index, shard)
            # range-aware write routing (ROADMAP item 2 remainder): a
            # range-split shard's PLAIN SET slices go only to their span
            # owners — anti-entropy's union repair converges the other
            # union owners, which is exactly why only set batches may be
            # narrowed (a clear/mutex/BSI write a union owner missed can
            # never be repaired back out — see range_write_spans)
            spans = (self.cluster.range_write_spans(index, shard)
                     if bulk_roaring else None)
            if spans:
                offs = columns_arr[sel] - shard * SHARD_WIDTH
                covered = np.zeros(sel.size, bool)
                for rlo, rhi, span_nodes in spans:
                    m = (offs >= rlo) & (offs < rhi)
                    if not m.any():
                        continue
                    if span_nodes is None:
                        # a span owner departed: union fan-out carries
                        # this slice until the planner re-plans
                        route_stats.range_fallbacks += 1
                        continue
                    covered |= m
                    route_stats.range_slices += 1
                    for node in span_nodes:
                        dispatch(node, sel[m])
                rest = sel[~covered]
                if rest.size:
                    for node in owners:
                        dispatch(node, rest)
            else:
                route_stats.union_writes += 1
                for node in owners:
                    dispatch(node, sel)

        stats = global_stats()

        def send_once(node, sel: np.ndarray) -> int:
            if values_arr is not None:
                return self.cluster.client.import_values(
                    node.uri, index, field, columns_arr[sel],
                    values_arr[sel], clear=clear,
                )
            if bulk_roaring:
                # plain set-bit batches ship as per-shard roaring bodies
                # — O(bitmap bytes) on the wire (the import-roaring
                # endpoint already unions + tracks existence)
                return self._send_roaring_batch(
                    node, index, field, rows_arr[sel], columns_arr[sel]
                )
            return self.cluster.client.import_bits(
                node.uri, index, field, rows_arr[sel], columns_arr[sel],
                timestamps=(ts_arr[sel].tolist()
                            if ts_arr is not None else None),
                clear=clear,
            )

        def run_local(sel: np.ndarray) -> int:
            if values_arr is not None:
                return self.import_values(
                    index, field, columns_arr[sel], values_arr[sel],
                    clear=clear, remote=True,
                )
            return self.import_bits(
                index, field, rows_arr[sel], columns_arr[sel],
                timestamps=(ts_arr[sel].tolist()
                            if ts_arr is not None else None),
                clear=clear, remote=True,
            )

        def run_remote(node, parts: list[np.ndarray]) -> int:
            sel = parts[0] if len(parts) == 1 else np.concatenate(parts)
            t0 = time.perf_counter()
            try:
                try:
                    return send_once(node, sel)
                except ClientError as e:
                    # imports are idempotent, so a transport/5xx NODE
                    # fault earns one immediate retry (rides out a
                    # heartbeat blip without failing the whole batch);
                    # deterministic 4xx never retries — every replay
                    # would answer the same
                    if not e.is_node_fault:
                        raise
                    stats.count("ingest_retries", 1,
                                tags={"node": node.id})
                    return send_once(node, sel)
            finally:
                stats.timing("ingest_fanout", time.perf_counter() - t0,
                             tags={"node": node.id})

        tasks = []
        labels: list[str | None] = []
        if local_parts:
            sel = (local_parts[0] if len(local_parts) == 1
                   else np.concatenate(local_parts))
            tasks.append(lambda sel=sel: run_local(sel))
            labels.append(None)
        for node, parts in remote_parts.values():
            tasks.append(lambda node=node, parts=parts:
                         run_remote(node, parts))
            labels.append(node.id)

        t0 = time.perf_counter()
        outcomes = concurrent_map(
            lambda fn: fn(), tasks, return_exceptions=True,
        )
        stats.timing("ingest_route_wall", time.perf_counter() - t0)
        stats.observe("ingest_fanout_width", len(tasks))

        changed = 0
        node_errors: dict[str, str] = {}
        status = None
        for label, out in zip(labels, outcomes):
            if isinstance(out, Exception):
                name = label or self.cluster.local.id
                node_errors[name] = str(out)
                stats.count("ingest_node_errors", 1, tags={"node": name})
                # deterministic request errors (local validation, remote
                # 4xx) dominate the surfaced status — they mean the
                # REQUEST is bad, not the node
                if isinstance(out, ApiError):
                    status = out.status
                elif (isinstance(out, ClientError)
                      and not out.is_node_fault and status is None):
                    status = out.status
            else:
                changed += out
        if node_errors:
            raise ImportRoutingError(node_errors, changed,
                                     status=status or 502)
        if changed:
            # remote portions' fragment write hooks fired on the OWNER
            # nodes: fence the coordinator's own cached results for the
            # field so read-your-writes holds through this node ahead
            # of the CDC feed's bounded lag (serving/rescache.py)
            from pilosa_tpu.serving import rescache

            idx = self.holder.index(index)
            if idx is not None:
                rescache.invalidate_write(idx.scope, index, field)
        return changed

    def _send_roaring_batch(self, node, index, field, rows_arr,
                            cols_arr) -> int:
        """Ship one node's slice of a routed set-bit import as per-shard
        roaring bodies (fragment id space: row * SHARD_WIDTH + position).
        ``rows_arr``/``cols_arr`` are the node's already-sliced arrays."""
        import numpy as np

        from pilosa_tpu.parallel.cluster import global_route_stats
        from pilosa_tpu.roaring import RoaringBitmap
        from pilosa_tpu.roaring.format import serialize

        rows_arr = np.asarray(rows_arr).astype(np.uint64)
        cols = np.asarray(cols_arr).astype(np.uint64)
        order, bounds, shards_sorted = shard_groups(cols)
        rows_arr, cols = rows_arr[order], cols[order]
        changed = 0
        route_stats = global_route_stats()
        for i in range(bounds.size - 1):
            lo, hi = int(bounds[i]), int(bounds[i + 1])
            ids = (rows_arr[lo:hi] * np.uint64(SHARD_WIDTH)
                   + (cols[lo:hi] & np.uint64(SHARD_WIDTH - 1)))
            data = serialize(RoaringBitmap.from_ids(np.unique(ids)))
            # per-acked-write wire accounting
            # (routing_range_wire_bytes_total)
            route_stats.wire_bytes += len(data)
            changed += self.cluster.client.import_roaring(
                node.uri, index, field, int(shards_sorted[lo]), data
            )
        return changed

    def import_values(self, index: str, field: str, columns, values,
                      clear: bool = False, remote: bool = False) -> int:
        idx = self._index(index)
        fld = self._field(idx, field)
        if not remote:
            self._check_not_degraded_write()
        if not remote and self.cluster is not None and len(self.cluster.nodes) > 1:
            return self._route_import(
                index, field, None, columns, None, clear, values=values
            )
        if fld.options.type != TYPE_INT:
            raise ApiError(f"field {field!r} is not an int field")
        if len(columns) != len(values):
            raise ApiError("columns and values must be the same length")
        try:
            cols_i = np.asarray(columns, dtype=np.int64)
        except OverflowError as e:  # ids beyond int64: clean 400, not 500
            raise ApiError(f"column id out of range: {e}") from e
        if cols_i.size and cols_i.min() < 0:
            raise ApiError(f"column {int(cols_i.min())} is negative")
        import time

        from pilosa_tpu.utils.stats import global_stats

        t0 = time.perf_counter()
        if clear:
            changed = 0
            for col in cols_i.tolist():
                try:
                    changed += fld.clear_value(int(col))
                except ValueError as e:
                    raise ApiError(str(e)) from e
        else:
            try:
                changed = fld.import_values(
                    cols_i.astype(np.uint64), values
                )
            except (ValueError, OverflowError) as e:
                raise ApiError(str(e)) from e
        elapsed = time.perf_counter() - t0
        from pilosa_tpu.utils.cost import cost_enabled

        if cost_enabled():
            from pilosa_tpu.storage.heat import global_heat

            heat = global_heat()
            shards_u, counts_u = np.unique(
                cols_i >> SHARD_WIDTH_EXP, return_counts=True)
            for shard, n in zip(shards_u.tolist(), counts_u.tolist()):
                heat.record_write(index, field, int(shard), n=float(n),
                                  scope=idx.scope)
        stats = global_stats()
        tags = {"kind": "values"}
        stats.count("ingest_rows", cols_i.size, tags=tags)
        stats.observe("ingest_batch_size", cols_i.size, tags=tags)
        stats.timing("ingest_apply", elapsed, tags=tags)
        if elapsed > 0:
            stats.gauge("ingest_rows_per_sec", cols_i.size / elapsed,
                        tags=tags)
        if not clear:
            idx.mark_columns_exist(cols_i)
            if self.cluster is not None:
                self.cluster.note_local_shards(
                    index,
                    np.unique(cols_i >> SHARD_WIDTH_EXP).tolist(),
                )
        self._ack_durable()
        return int(changed)

    def import_roaring(self, index: str, field: str, shard: int, data: bytes,
                       view: str = VIEW_STANDARD, remote: bool = False,
                       submitted_out: list | None = None) -> int:
        """``submitted_out`` (a list) receives the decoded bit count —
        the HTTP handler bills the tenant ledger by bits SUBMITTED, like
        the row/value import routes, not by bits that happened to
        change (an idempotent retry costs the server the same work)."""
        idx = self._index(index)
        fld = self._field(idx, field)
        if not remote:
            self._check_not_degraded_write()
        frag = fld.view(view, create=True).fragment(shard, create=True)
        from pilosa_tpu.roaring.format import load_any

        try:
            bitmap, _ = load_any(data)
            ids = bitmap.to_ids()
        except ValueError as e:
            raise ApiError(str(e)) from e
        if submitted_out is not None:
            submitted_out.append(int(ids.size))
        # max-writes-per-request applies to EDGE roaring bodies like the
        # JSON/protobuf import routes (a 100k-bit bitmap is no lighter
        # than 100k Set() calls); routed internal slices are exempt —
        # they carry pieces of an already-admitted edge batch
        limit = self.max_writes_per_request
        if not remote and 0 < limit < int(ids.size):
            raise ApiError(
                f"import-roaring body of {int(ids.size)} bits exceeds "
                f"max-writes-per-request {limit}; split the bitmap", 413,
            )
        try:
            changed = frag.add_ids(ids)
        except ValueError as e:
            raise ApiError(str(e)) from e
        from pilosa_tpu.utils.stats import global_stats

        stats = global_stats()
        stats.count("ingest_rows", int(ids.size), tags={"kind": "roaring"})
        stats.observe("ingest_batch_size", int(ids.size),
                      tags={"kind": "roaring"})
        from pilosa_tpu.utils.cost import cost_enabled

        if cost_enabled():
            from pilosa_tpu.storage.heat import global_heat

            global_heat().record_write(index, field, shard,
                                       n=float(ids.size), scope=idx.scope)
        positions = np.unique(ids & np.uint64(SHARD_WIDTH - 1))
        idx.mark_columns_exist(
            ((shard << SHARD_WIDTH_EXP) + positions.astype(np.int64)).tolist()
        )
        if self.cluster is not None:
            self.cluster.note_local_shards(index, [shard])
        self._ack_durable()
        return changed

    # --------------------------------------------------------------- export

    def export_csv(self, index: str, field: str) -> str:
        """CSV of row,column over the standard view (reference api.ExportCSV)."""
        idx = self._index(index)
        fld = self._field(idx, field)
        view = fld.view(VIEW_STANDARD)
        lines = []
        if view is not None:
            for shard in sorted(view.fragments):
                frag = view.fragment(shard)
                for row in frag.row_ids():
                    base = shard << SHARD_WIDTH_EXP
                    for pos in frag.row_columns(row).tolist():
                        lines.append(f"{row},{base + int(pos)}")
        return "\n".join(lines) + ("\n" if lines else "")

    # ---------------------------------------------------------------- info

    def status(self) -> dict:
        # maxWritesPerRequest rides /status so bulk clients (the CLI
        # importer) can clamp their batch size to this server's limit
        # instead of discovering it via 413s
        if self.cluster is not None:
            out = {
                "state": self.cluster.state,
                "nodes": self.cluster.nodes_json(),
                "localID": self.cluster.local.id,
                "maxWritesPerRequest": self.max_writes_per_request,
                # partition-tolerance surface (docs/OPERATIONS.md
                # failure model): the cluster epoch doubles as epoch
                # gossip (peers adopt the max they see), and
                # clusterDegraded tells operators/clients this node is
                # the minority side of a partition (read-only)
                "epoch": self.cluster.epoch,
                "clusterDegraded": bool(self.cluster.degraded),
            }
            # placement-override gossip rides /status (like the epoch):
            # joiners and heartbeat pollers adopt the freshest table
            # without a dedicated round trip. Omitted while no override
            # was ever minted so the common case stays byte-identical
            # to the pre-autopilot wire format.
            if self.cluster.placement.epoch > 0:
                out["placement"] = self.cluster.placement.to_json()
            # the drain record gossips the same way (elastic plane):
            # omitted until a drain has ever run, so the common wire
            # stays byte-identical
            if self.cluster.drain_record.get("epoch"):
                out["drain"] = dict(self.cluster.drain_record)
        else:
            out = {
                "state": "NORMAL",
                "nodes": [{"id": "local", "uri": "localhost",
                           "isCoordinator": True, "state": "NORMAL"}],
                "localID": "local",
                "maxWritesPerRequest": self.max_writes_per_request,
                "epoch": 0,
                "clusterDegraded": False,
            }
        # storage-integrity surface (docs/OPERATIONS.md integrity
        # runbook): storageDegraded = this node's disk tripped the
        # read-only latch (writes shed 503 until a probe write clears)
        health = getattr(self.holder, "health", None)
        out["storageDegraded"] = bool(health is not None
                                      and health.degraded)
        out["storageDegradedReason"] = (health.reason
                                        if health is not None else "")
        # multi-process serving surface (docs/OPERATIONS.md deployment
        # shapes): the worker table tells operators (and the chaos
        # harness) which SO_REUSEPORT workers are alive and which
        # generation each is on
        if self.mpserve is not None:
            out["servingWorkers"] = self.mpserve.workers_json()
        return out

    def info(self) -> dict:
        import jax

        devices = jax.devices()
        return {
            "shardWidth": SHARD_WIDTH,
            "cpuPhysicalCores": 0,
            "version": __version__,
            "devices": [
                {"id": d.id, "platform": d.platform, "kind": getattr(d, "device_kind", "")}
                for d in devices
            ],
        }

    def version(self) -> dict:
        return {"version": __version__}

    def node_id(self) -> str:
        return self.cluster.local.id if self.cluster is not None else "local"

    def cluster_metrics(self) -> dict:
        """Partition-tolerance series (epoch, quorum, heartbeat,
        fencing) for /metrics and /debug/vars — zeros with no cluster
        wired, so the series exist from scrape one either way."""
        if self.cluster is not None and hasattr(self.cluster, "metrics"):
            return self.cluster.metrics()
        return {
            "cluster_epoch": 0, "cluster_quorum": 1,
            "cluster_degraded": 0, "cluster_members": 1,
            "cluster_suspects": 0,
            "cluster_heartbeat_probes_total": 0,
            "cluster_heartbeat_failures_total": 0,
            "cluster_deaths_declared_total": 0,
            "cluster_deaths_vetoed_total": 0,
            "cluster_stale_epoch_rejects_total": 0,
            "cluster_quorum_denials_total": 0,
            "cluster_rejoins_total": 0,
            "cluster_cleanup_deferred_total": 0,
            "cluster_placement_overrides": 0,
            "cluster_placement_epoch": 0,
            "cluster_placement_ranges": 0,
            "elastic_drain_active": 0,
            "elastic_drain_epoch": 0,
            "elastic_draining": 0,
            "elastic_warm_heat_ordered_total": 0,
            "elastic_warm_verified_total": 0,
            "elastic_warm_verify_failed_total": 0,
        }

    def elastic_metrics(self) -> dict:
        """elastic_* drain series for /metrics and /debug/vars — zeros
        with no manager wired, so the series exist from scrape one."""
        if self.elastic is not None:
            return self.elastic.metrics()
        return {
            "elastic_drains_started_total": 0,
            "elastic_drains_completed_total": 0,
            "elastic_drains_failed_total": 0,
            "elastic_drains_aborted_total": 0,
            "elastic_drains_resumed_total": 0,
            "elastic_cursor_handoffs_total": 0,
            "elastic_drain_active": 0,
            "elastic_drain_epoch": 0,
        }

    def elastic_json(self) -> dict:
        """GET /debug/elastic: the drain state machine inspector."""
        if self.elastic is not None:
            return {"enabled": True, **self.elastic.to_json()}
        out = {"enabled": False, "drain": {}, "active": False,
               "draining": False, "metrics": self.elastic_metrics()}
        if self.cluster is not None:
            out["placement"] = self.cluster.placement.to_json()
        return out

    def drain_start(self, node: str) -> dict:
        """POST /cluster/drain/<node>: begin a coordinator-driven
        graceful drain of ``node`` (docs/OPERATIONS.md elastic
        operations runbook)."""
        from pilosa_tpu.autopilot.elastic import ElasticError

        if self.elastic is None:
            raise ApiError("elastic plane not wired on this node", 503)
        try:
            return self.elastic.start_drain(node)
        except ElasticError as e:
            raise ApiError(str(e), e.status)

    def drain_abort(self) -> dict:
        """DELETE /cluster/drain: abort the in-flight drain (the target
        un-sheds; already-moved groups stay where they landed)."""
        from pilosa_tpu.autopilot.elastic import ElasticError

        if self.elastic is None:
            raise ApiError("elastic plane not wired on this node", 503)
        try:
            return self.elastic.abort_drain()
        except ElasticError as e:
            raise ApiError(str(e), e.status)

    def drain_status(self) -> dict:
        """GET /cluster/drain: the drain record + latches."""
        if self.elastic is not None:
            return self.elastic.status()
        return {"drain": {}, "active": False, "draining": False}

    def observability_metrics(self) -> dict:
        """Tracing / inspector / slow-query series for /metrics and
        /debug/vars — every key present from scrape one, zeros included,
        like the other exporter blocks."""
        from pilosa_tpu.utils.tracing import (
            global_query_tracker,
            global_tracer,
        )

        from pilosa_tpu.pql import parser

        out = {"slow_queries_total": self.slow_queries_total,
               "pql_parse_memo_hits_total": parser.memo_hits}
        out.update(global_tracer().metrics())
        out.update(global_query_tracker().metrics())
        return out

    def tenants_json(self, k: int = 10, by: str = "device_ms") -> dict:
        """GET /debug/tenants: the full per-(tenant, index) cost table
        plus the top-K offender view (docs/OBSERVABILITY.md)."""
        return {
            "tenants": self.cost.snapshot(),
            "top": self.cost.top(k, by=by),
            "by": by,
            "totals": self.cost.metrics(),
        }

    def start_device_trace(self, seconds: float) -> dict:
        """Capture a live JAX profiler trace around ``seconds`` of real
        traffic (POST /debug/trace-device) into the configured log dir.
        One capture at a time — the profiler is a process-global
        singleton, so a second concurrent request answers 409."""
        import os
        import time as _time

        from pilosa_tpu.utils.tracing import start_jax_trace

        seconds = float(seconds)
        if not 0 < seconds <= 60:
            raise ApiError("secs must be in (0, 60]")
        log_dir = os.path.expanduser(
            self.trace_log_dir
            or os.path.join(self.holder.data_dir, "jax-traces")
        )
        if not self._device_trace_lock.acquire(blocking=False):
            raise ApiError("a device trace capture is already running", 409)
        try:
            os.makedirs(log_dir, exist_ok=True)
            t0 = _time.perf_counter()
            with start_jax_trace(log_dir):
                _time.sleep(seconds)
            return {
                "logDir": log_dir,
                "seconds": round(_time.perf_counter() - t0, 3),
            }
        finally:
            self._device_trace_lock.release()

    def pipeline_metrics(self) -> dict:
        """Wave-coalescing counters for the exporters (zeros until the
        first pipelined query — the series must exist from scrape one so
        rate()/increase() windows are well-behaved)."""
        pipe = self._pipeline
        if pipe is None:
            return {"waves": 0, "coalesced": 0, "deduped": 0}
        return {"waves": pipe.waves, "coalesced": pipe.coalesced,
                "deduped": pipe.deduped}

    def fastlane_metrics(self) -> dict:
        """Serving fast-lane counters (connection pool + remote wave
        batching) for /metrics and /debug/vars — every key present from
        scrape one, zeros included, so rate() windows never see a series
        appear mid-flight."""
        out = {
            "pool_connections_created_total": 0,
            "pool_connections_reused_total": 0,
            "pool_connections_discarded_total": 0,
            "pool_requests_total": 0,
            "pool_idle_connections": 0,
            "remote_batches_total": 0,
            "remote_batched_queries_total": 0,
            "remote_batch_solo_total": 0,
            "remote_batch_fallbacks_total": 0,
        }
        pool = getattr(getattr(self.cluster, "client", None), "pool", None)
        if pool is not None:
            out.update(pool.metrics())
        batcher = getattr(self.executor, "_wave_batcher", None)
        if batcher is not None:
            out.update(batcher.metrics())
        return out

    def mp_metrics(self) -> dict:
        """Multi-process serving series (docs/OBSERVABILITY.md) —
        present from scrape one with zeros in single-process mode, like
        every sibling exporter block, so the deployment-shape flip
        never makes a series appear mid-flight."""
        if self.mpserve is not None:
            return self.mpserve.metrics()
        return {
            "serving_workers": 0,
            "serving_ring_depth": 0,
            "serving_ring_full_total": 0,
            "serving_owner_batch_size": 0.0,
            "serving_owner_batches_total": 0,
            "serving_owner_batched_requests_total": 0,
            "serving_ring_requests_total": 0,
            "serving_worker_shed_total": 0,
            "serving_worker_proxied_total": 0,
            "serving_worker_respawns_total": 0,
            "serving_workers_reaped_total": 0,
            "serving_responses_dropped_total": 0,
            "serving_ring_queries_total": 0,
            "serving_ring_deduped_total": 0,
        }

    def workers_json(self) -> dict:
        """GET /debug/workers: the worker table (id, generation, pid,
        liveness, ring depth, per-worker counters, ring round-trip
        quantiles)."""
        if self.mpserve is None:
            return {"enabled": False, "workers": []}
        return {
            "enabled": True,
            "port": self.mpserve.port,
            "ownerPort": self.mpserve.owner_port,
            "workers": self.mpserve.workers_json(),
        }

    def rescache_metrics(self) -> dict:
        """result_cache_* series (docs/OBSERVABILITY.md) — present from
        scrape one with zeros while the cache is disabled, like every
        sibling exporter block."""
        from pilosa_tpu.serving.rescache import global_result_cache

        return global_result_cache().metrics()

    def tiering_metrics(self) -> dict:
        """residency_tier_* pass counters (storage/tiering.py) — zeros
        with no tierer wired; the per-tier byte gauges ride the
        residency block."""
        if self.tierer is not None:
            return self.tierer.metrics()
        return {
            "residency_tier_passes_total": 0,
            "residency_tier_pass_promotions_total": 0,
            "residency_tier_pass_demotions_total": 0,
            "residency_tier_promoted_bytes_total": 0,
            "residency_tier_demoted_bytes_total": 0,
            "residency_tier_paced_sleep_seconds_total": 0.0,
            "residency_tier_last_pass_seconds": 0.0,
        }

    def autopilot_metrics(self) -> dict:
        """autopilot_* series (autopilot/planner.py) — zeros while the
        planner is off, EXCEPT the placement gauges, which read the
        cluster's override table directly: a node with the kill switch
        off still adopts (and must report) overrides minted elsewhere."""
        if self.autopilot is not None:
            return self.autopilot.metrics()
        placement = getattr(self.cluster, "placement", None)
        return {
            "autopilot_passes_total": 0,
            "autopilot_plans_total": 0,
            "autopilot_moves_planned_total": 0,
            "autopilot_moves_executed_total": 0,
            "autopilot_splits_total": 0,
            "autopilot_merges_total": 0,
            "autopilot_overrides_pruned_total": 0,
            "autopilot_passes_skipped_total": 0,
            "autopilot_placement_overrides":
                len(placement) if placement is not None else 0,
            "autopilot_placement_epoch":
                placement.epoch if placement is not None else 0,
            "autopilot_last_pass_seconds": 0.0,
            "autopilot_slo_burn_rate": 0.0,
        }

    def rescache_json(self, k: int = 100) -> dict:
        """GET /debug/rescache: the result-cache inspector — entry
        table hottest-first plus totals and config."""
        from pilosa_tpu.serving.rescache import global_result_cache

        cache = global_result_cache()
        out = cache.inspect(k=k)
        out["enabled"] = cache.enabled
        # the cluster-edge story in one place: why edges refused before
        # CDC (refusal-reason counters), and — once the tailer is live —
        # the per-peer feed lag that replaces the refusals
        if self.cdc is not None:
            out["cdc"] = {"live": self.cdc.live(),
                          "peerLag": self.cdc.peer_lag()}
        return out

    def durability_metrics(self) -> dict:
        """Write-path durability counters (group-commit WAL) for
        /metrics and /debug/vars — every key present from scrape one,
        zeros included, like the fast-lane block."""
        wal = getattr(self.holder, "wal", None)
        if wal is None:
            return {}
        return wal.metrics()

    # ------------------------------------------------------------------ CDC

    def wal_tail(self, since: int | None, max_bytes: int = 1 << 20,
                 cursor: str | None = None):
        """Serve one ``GET /internal/wal/tail`` poll: committed WAL
        records after ``since`` as ``(events, next_seq, durable_seq)``.
        ``since=None`` is the attach handshake — no events, just the
        durable high-water mark for the consumer to poll from (a fresh
        consumer owns nothing derived from the feed, so it needs no
        history). A named ``cursor`` registers/advances in the WAL's
        registry — the consumer's acknowledged position pins covered
        segments against GC up to the retention budget. Raises the
        storage plane's TailGone (HTTP layer maps it to 410)."""
        from pilosa_tpu.storage.wal import TailGone

        wal = getattr(self.holder, "wal", None)
        if wal is None or not wal.grouped:
            raise ApiError(
                "wal tail requires durability-mode=group on this node",
                501,
            )
        if since is None:
            durable = wal.durable_seq()
            if cursor:
                wal.register_cursor(cursor, durable)
            return [], durable, durable
        if cursor:
            if cursor not in wal.cursors():
                # the registry is in-memory: a poll naming a cursor this
                # WAL never registered proves the producer restarted
                # (its seq space reset) or force-reclaimed the laggard.
                # Answering 410 here closes the silent-gap window where
                # a restarted producer's fresh seq space races past the
                # consumer's stale position before the since > durable
                # check can catch it — attached consumers get hard
                # restart detection; cursorless polls keep best-effort
                # semantics.
                raise TailGone(wal.tail_floor(), wal.durable_seq())
            # advancing the cursor BEFORE the read: since acknowledges
            # everything at or below it, releasing segment pins early
            wal.register_cursor(cursor, since)
        try:
            return wal.read_tail(since, max_bytes=max_bytes)
        except TailGone:
            if cursor:
                # a gone cursor must stop pinning (and stop holding the
                # floor down): the consumer restarts from the handshake
                wal.drop_cursor(cursor)
            raise

    def cdc_metrics(self) -> dict:
        """cdc_* series (docs/OBSERVABILITY.md): producer-side tail
        counters ride durability_metrics (wal.metrics); this block is
        the consumer side — tailer per-peer lag and follower apply
        counters. Present from scrape one with zeros while CDC is off,
        like every sibling exporter block."""
        out = {
            "cdc_enabled": 1 if self.cdc is not None else 0,
            "cdc_live": 0,
            "cdc_peers": 0,
            "cdc_peer_lag_seconds_max": 0.0,
            "cdc_events_total": 0,
            "cdc_invalidations_total": 0,
            "cdc_resyncs_total": 0,
            "cdc_poll_errors_total": 0,
            "cdc_follower": 1 if self.follower is not None else 0,
            "cdc_follower_staleness_seconds": 0.0,
            "cdc_follower_applied_ops_total": 0,
        }
        if self.cdc is not None:
            out.update(self.cdc.metrics())
        if self.follower is not None:
            out.update(self.follower.metrics())
        return out

    def integrity_metrics(self) -> dict:
        """Storage-integrity series (docs/OBSERVABILITY.md): the
        degraded latch, verified-load / quarantine counters, and the
        scrubber's progress — every key present from scrape one, zeros
        included, like the sibling exporter blocks."""
        from pilosa_tpu.storage.integrity import global_integrity

        out = {
            "storage_degraded": 0,
            "storage_degraded_total": 0,
            "storage_recoveries_total": 0,
            "scrub_passes_total": 0,
            "scrub_fragments_scanned_total": 0,
            "scrub_bytes_total": 0,
            "scrub_corruptions_detected_total": 0,
            "scrub_read_repairs_total": 0,
            "scrub_self_heals_total": 0,
            "scrub_unrepaired_total": 0,
            "scrub_last_pass_seconds": 0.0,
            "scrub_paced_sleep_seconds": 0.0,
        }
        out.update(global_integrity().metrics())
        health = getattr(self.holder, "health", None)
        if health is not None:
            out.update(health.metrics())
        if self.scrubber is not None:
            out.update(self.scrubber.metrics())
        return out

    def scrub_now(self) -> dict:
        """One on-demand scrub pass (``POST /internal/scrub``, CLI
        ``check --host``). Uses the configured scrubber when one is
        running (sharing its pacing budget), an unpaced ad-hoc one
        otherwise."""
        scrubber = self.scrubber
        if scrubber is None:
            from pilosa_tpu.parallel.scrub import Scrubber

            # interval 0: no ticker thread — but keep the instance so
            # repeated on-demand passes accumulate into the scrub_*
            # series on /metrics
            scrubber = self.scrubber = Scrubber(self.holder,
                                                cluster=self.cluster)
        return scrubber.scrub_pass()

    def recalculate_caches(self, remote: bool = False) -> threading.Thread:
        """Authoritative recount of every fragment's TopN row cache
        (reference ``POST /recalculate-caches`` → api.RecalculateCaches:
        broadcast to peers, then recount locally). ``remote=True`` marks
        a peer-originated message: apply locally only, no re-broadcast.

        The local recount runs in a BACKGROUND worker: on a
        large holder the per-fragment row_counts() scans each take the
        fragment lock, so a synchronous recount in the cluster
        message-delivery path stalls heartbeats and message handling for
        seconds. The HTTP handler returns 204 once the work is queued; a
        recount requested while one is running queues exactly one re-run
        (it starts after the current pass, so it observes any writes the
        in-flight pass missed). Returns the worker thread so in-process
        callers (tests, CLI) can join it."""
        if not remote:
            self._broadcast({"type": "recalculate-caches"})

        def recount():
            from pilosa_tpu.serving import rescache

            while True:
                for idx in list(self.holder.indexes.values()):
                    for field in list(idx.fields.values()):
                        for view in list(field.views.values()):
                            for frag in list(view.fragments.values()):
                                frag.recalculate_cache()
                    # an authoritative recount can change TopN results
                    # with no write event: fence the index's cached
                    # responses (serving/rescache.py)
                    rescache.invalidate_index_wide(idx.scope, idx.name)
                with self._recalc_lock:
                    if not self._recalc_rerun:
                        self._recalc_thread = None
                        return
                    self._recalc_rerun = False

        with self._recalc_lock:
            t = self._recalc_thread
            if t is not None and t.is_alive():
                self._recalc_rerun = True
                return t
            t = threading.Thread(target=recount, daemon=True,
                                 name="recalculate-caches")
            self._recalc_thread = t
            t.start()
            return t

    def max_shards(self) -> dict:
        return {
            "standard": {
                name: (idx.available_shards() or [0])[-1]
                for name, idx in self.holder.indexes.items()
            }
        }

    def shard_nodes(self, index: str, shard: int,
                    col: int | None = None) -> list[dict]:
        if self.cluster:
            if col is not None:
                # range-split refinement (elastic plane): a shard-aware
                # client asking with a column gets the span owners
                # preferred for that column's range; every span owner
                # holds the whole fragment, so the fallback below is
                # always correct too
                nodes = self.cluster.range_read_nodes(
                    index, shard, int(col) - shard * SHARD_WIDTH)
                if nodes:
                    return [n.to_json() for n in nodes]
            return self.cluster.shard_nodes_json(index, shard)
        return [{"id": "local", "uri": "localhost"}]

    # -------------------------------------------------------------- helpers

    def _index(self, name: str):
        idx = self.holder.index(name)
        if idx is None:
            raise ApiError(f"index {name!r} not found", 404)
        return idx

    @staticmethod
    def _field(idx, name: str):
        fld = idx.field(name)
        if fld is None:
            raise ApiError(f"field {name!r} not found", 404)
        return fld


def _parse_ts(value):
    if value is None or value == "":
        # protobuf import bodies encode a missing per-bit timestamp as ""
        return None
    if isinstance(value, dt.datetime):
        return value
    return dt.datetime.fromisoformat(str(value))
