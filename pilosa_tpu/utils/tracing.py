"""Distributed tracing: context-propagating sampled spans + query inspector.

Reference: tracing/tracing.go (SURVEY.md §2 #24) — upstream wraps a global
OpenTracing tracer (Jaeger) so every request carries a span context across
goroutines and RPC hops. The r6 port was a thread-local stub: every span
started on a pool thread was orphaned and nothing crossed a node. This
rewrite is the real thing, sized for the serving planes PRs 1-6 built:

- **contextvars, not thread-locals**: the active span rides
  ``contextvars``, and every cross-thread handoff in the system — the
  ``utils.pool`` fan-outs, the serving pipeline's wave queue, hedge legs,
  the wave batcher — captures the submitting context and restores it on
  the worker, so a span started anywhere lands in its request's tree.
- **Sampling, zero-cost off**: ``sample_rate`` (0..1) decides per REQUEST
  ROOT. Rate 0 returns a shared no-op handle — no allocation, no context
  write. Child spans never re-sample: they join the active trace or no-op.
- **Cross-node propagation**: internal hops carry
  ``X-Pilosa-Trace: <trace_id>:<parent_span_id>``; the callee roots a
  remote span under that parent and (for query hops) returns its finished
  subtree in the response, so the coordinator's ``/debug/traces`` renders
  ONE tree spanning the cluster.
- **In-flight inspector**: ``QueryTracker`` (always on, lock-free stage
  updates) backs ``GET /debug/queries`` — upstream's long-running-query
  view: trace id, PQL, index, age, current stage, shards outstanding.
- **One stage site, two clocks, four sinks**: ``stage(name)`` is the
  context manager every layer boundary of the served path uses
  (``STAGES`` is the list). One pair of wall-clock reads feeds the
  always-on cumulative counters (``/metrics`` ``pilosa_tpu_stage_*``,
  ``/debug/vars`` ``stages``), the sampled span tree, a
  ``jax.profiler.TraceAnnotation`` while a device capture runs, and the
  inspector's ``stage``. Where somebody will look (inside a sampled
  trace or a capture) the site also reads the thread's own CPU clock
  inside the wall pair: a stage's wall seconds less its CPU seconds are
  the time its thread was not running. The three root stages keep the
  CPU by thread role (``thread_metrics``: handler, dispatcher, WAL
  commit), at one CPU-clock read every 0.1 s a thread.

On TPU the device-side story stays the JAX profiler; ``start_jax_trace``
wraps ``jax.profiler`` (Python tracer off, so the capture leaves the host
alone) and is exposed live at ``POST /debug/trace-device``;
``trace_report`` reads a capture back (``python -m pilosa_tpu
trace-report``).
"""

from __future__ import annotations

import contextlib
import contextvars
import functools
import glob
import json
import os
import random
import re
import threading
import time
from collections import deque

# Request header carrying trace context on internal hops
# (cluster_exec sub-queries, wave batches, sync manifest/blocks).
TRACE_HEADER = "X-Pilosa-Trace"


def _new_trace_id() -> str:
    return f"{random.getrandbits(64):016x}"


def _new_span_id() -> str:
    return f"{random.getrandbits(48):012x}"


class Span:
    """One timed operation in a trace tree.

    ``children`` may be appended from several threads (list.append is
    atomic under the GIL); ``to_json`` snapshots. ``remote`` holds
    already-serialized subtrees returned by peers over the wire — they
    render as children with their own (peer-assigned) span ids whose
    ``parentId`` is this span's id."""

    __slots__ = ("name", "trace_id", "span_id", "parent_id", "parent",
                 "start", "end", "tags", "children", "remote")

    def __init__(self, name: str, tags: dict | None = None,
                 trace_id: str | None = None, parent: "Span | None" = None,
                 parent_id: str | None = None, start: float | None = None):
        self.name = name
        self.trace_id = trace_id or _new_trace_id()
        self.span_id = _new_span_id()
        self.parent = parent
        self.parent_id = parent.span_id if parent is not None else parent_id
        self.start = time.perf_counter() if start is None else start
        self.end = None
        self.tags = tags if tags is not None else {}
        self.children: list[Span] = []
        self.remote: list[dict] = []

    @property
    def duration(self) -> float:
        return (self.end or time.perf_counter()) - self.start

    def finish(self) -> None:
        if self.end is None:
            self.end = time.perf_counter()

    def root(self) -> "Span":
        s = self
        while s.parent is not None:
            s = s.parent
        return s

    def add_remote(self, subtree: dict) -> None:
        """Attach a peer's serialized span subtree under this span."""
        if isinstance(subtree, dict):
            self.remote.append(subtree)

    def header_value(self) -> str:
        """This span as an ``X-Pilosa-Trace`` value (child hops parent
        to it)."""
        return f"{self.trace_id}:{self.span_id}"

    def to_json(self) -> dict:
        out = {
            "name": self.name,
            "traceId": self.trace_id,
            "spanId": self.span_id,
            "durationMs": round(self.duration * 1e3, 3),
            "tags": self.tags,
            "children": ([c.to_json() for c in list(self.children)]
                         + list(self.remote)),
        }
        if self.parent_id is not None:
            out["parentId"] = self.parent_id
        return out


def parse_trace_header(value: str | None):
    """``"<trace_id>:<span_id>"`` → tuple, or None when absent/malformed
    (a malformed header must degrade to untraced, never 500)."""
    if not value:
        return None
    parts = value.strip().split(":")
    if len(parts) != 2 or not parts[0] or not parts[1]:
        return None
    return parts[0], parts[1]


# The active span of the current logical request. None = not in a trace;
# _NOT_SAMPLED = the request's root made a negative sampling decision, so
# inner span sites must not re-sample their own roots.
_current_span: contextvars.ContextVar = contextvars.ContextVar(
    "pilosa_tpu_trace_span", default=None
)
_NOT_SAMPLED = object()


def current_span() -> Span | None:
    cur = _current_span.get()
    return cur if isinstance(cur, Span) else None


class _NopHandle:
    """Shared no-op span handle: tracing off (or unsampled subtree) costs
    one contextvar read and zero allocations."""

    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_NOP = _NopHandle()


class _SpanHandle:
    """Context manager activating one span in the current context."""

    __slots__ = ("_tracer", "_span", "_token")

    def __init__(self, tracer: "Tracer", span: Span):
        self._tracer = tracer
        self._span = span

    def __enter__(self) -> Span:
        self._token = _current_span.set(self._span)
        return self._span

    def __exit__(self, exc_type, exc, tb):
        span = self._span
        span.finish()
        if exc is not None and "error" not in span.tags:
            span.tags["error"] = str(exc) or exc_type.__name__
        _current_span.reset(self._token)
        if span.parent is None:
            self._tracer._record_root(span)
        return False


class _SuppressHandle:
    """Marks the request NOT SAMPLED for its whole context, so inner span
    sites (executor.Execute, remote legs) cannot root their own traces."""

    __slots__ = ("_token",)

    def __enter__(self):
        self._token = _current_span.set(_NOT_SAMPLED)
        return None

    def __exit__(self, *exc):
        _current_span.reset(self._token)
        return False


@contextlib.contextmanager
def use_span(span: Span):
    """Re-activate an existing span in this context (the query-batch
    receiver runs one item's submit and resolve phases at different
    points of its loop)."""
    token = _current_span.set(span)
    try:
        yield span
    finally:
        _current_span.reset(token)


class Tracer:
    """Sampled, context-propagating tracer; keeps the last N root trees."""

    def __init__(self, keep: int = 64, sample_rate: float = 0.0):
        self.sample_rate = sample_rate
        self.keep = keep
        self._lock = threading.Lock()
        self.finished: deque = deque(maxlen=keep)
        self.sampled_traces = 0
        self.spans_started = 0

    # ------------------------------------------------------------ span sites

    def span(self, name: str, **tags):
        """Child span joining the active trace; no-op outside one.

        Join-only by design: instrumentation sites scattered through the
        planes (conn.checkout, wal.barrier, device.dispatch, ...) must
        never root standalone trees off background traffic — only the
        designated root sites (``request_root``, ``remote_root``,
        ``root_span``) start traces."""
        return self._join(name, tags, None)

    def _join(self, name: str, tags: dict, start: float | None):
        cur = _current_span.get()
        if cur is None or cur is _NOT_SAMPLED:
            return _NOP
        self.spans_started += 1
        span = Span(name, tags, trace_id=cur.trace_id, parent=cur,
                    start=start)
        cur.children.append(span)
        return _SpanHandle(self, span)

    def root_span(self, name: str, **tags):
        """Join the active trace, or — outside one — ROOT a new trace
        subject to sampling. For sites that ARE a sensible trace root
        when reached directly: ``executor.Execute`` (in-process callers,
        tests, CLI) and ``sync.pass`` (the anti-entropy ticker)."""
        cur = _current_span.get()
        if cur is None:
            return self._maybe_root(name, tags)
        return self.span(name, **tags)

    def request_root(self, name: str, **tags):
        """Root span site for an EDGE request: samples once, and on a
        negative decision suppresses sampling for the whole request so
        exactly zero or one tree exists per request."""
        cur = _current_span.get()
        if isinstance(cur, Span):  # nested (in-process client re-entry)
            return self.span(name, **tags)
        rate = self.sample_rate
        if rate <= 0.0:
            return _NOP
        if rate < 1.0 and random.random() >= rate:
            return _SuppressHandle()
        self.sampled_traces += 1
        self.spans_started += 1
        return _SpanHandle(self, Span(name, tags))

    def remote_span(self, header_value: str | None, name: str,
                    **tags) -> Span | None:
        """A DETACHED remote-rooted span for split-phase work: the
        query-batch receiver runs one item's submit and resolve at
        different points of its loop, re-activating the span with
        ``use_span`` each time. Returns None when the header is absent
        or malformed. Close with ``finish_root``. Single-phase handlers
        should use ``remote_root`` (the context-manager form) instead —
        both keep root-span lifecycle accounting inside this class."""
        parsed = parse_trace_header(header_value)
        if parsed is None:
            return None
        self.spans_started += 1
        return Span(name, tags, trace_id=parsed[0], parent_id=parsed[1])

    def finish_root(self, span: Span) -> None:
        """End a detached root span (``remote_span``) and record it in
        the finished ring."""
        span.finish()
        self._record_root(span)

    def remote_root(self, header_value: str | None, name: str, **tags):
        """Root span for a remote hop carrying ``X-Pilosa-Trace``. The
        coordinator already sampled, so the callee always traces when the
        header parses; without one, local sampling is SUPPRESSED — a
        remote sub-query belongs to its root's decision either way."""
        parsed = parse_trace_header(header_value)
        if parsed is None:
            return _SuppressHandle()
        trace_id, parent_id = parsed
        self.spans_started += 1
        return _SpanHandle(
            self, Span(name, tags, trace_id=trace_id, parent_id=parent_id)
        )

    def _maybe_root(self, name: str, tags: dict):
        rate = self.sample_rate
        if rate <= 0.0:
            return _NOP
        if rate < 1.0 and random.random() >= rate:
            return _NOP
        self.sampled_traces += 1
        self.spans_started += 1
        return _SpanHandle(self, Span(name, tags))

    # -------------------------------------------------------------- finished

    def _record_root(self, span: Span) -> None:
        self.finished.append(span)  # deque(maxlen): atomic, bounded

    def record_foreign_tree(self, tree: dict) -> None:
        """Record an ALREADY-SERIALIZED finished tree — a serving
        worker's edge span (its own process rooted and finished it, the
        owner-side subtree already grafted) shipped over the handshake
        channel so this process's /debug/traces shows one tree per
        request whatever the deployment shape."""
        if isinstance(tree, dict):
            self.sampled_traces += 1
            self.finished.append(_ForeignTree(tree))

    def recent(self) -> list[dict]:
        return [s.to_json() for s in list(self.finished)]

    def clear(self) -> None:
        self.finished.clear()
        self.sampled_traces = 0
        self.spans_started = 0

    def metrics(self) -> dict:
        return {
            "tracing_sampled_traces_total": self.sampled_traces,
            "tracing_spans_total": self.spans_started,
            "tracing_finished_traces": len(self.finished),
            "tracing_sample_rate": self.sample_rate,
        }


class _ForeignTree:
    """A finished span tree serialized by ANOTHER process (serving
    worker); quacks like a Span for the finished ring."""

    __slots__ = ("tree",)

    def __init__(self, tree: dict):
        self.tree = tree

    def to_json(self) -> dict:
        return self.tree


_global_tracer: Tracer | None = None


def global_tracer() -> Tracer:
    global _global_tracer
    if _global_tracer is None:
        _global_tracer = Tracer()
    return _global_tracer


def set_global_tracer(tracer: Tracer) -> None:
    global _global_tracer
    _global_tracer = tracer


# ------------------------------------------------------ in-flight inspector


class InflightQuery:
    """One live query's inspector record. ``stage`` and
    ``shards_outstanding`` are plain attribute writes (no lock): the
    writers are the query's own threads and readers tolerate tearing —
    this is a debugging view, not an accounting ledger."""

    __slots__ = ("qid", "trace_id", "index", "pql", "tenant", "remote",
                 "started", "started_wall", "stage", "shards_outstanding")

    def __init__(self, qid: int, index: str, pql: str, tenant: str,
                 remote: bool, trace_id: str | None):
        self.qid = qid
        self.trace_id = trace_id
        self.index = index
        self.pql = pql
        self.tenant = tenant
        self.remote = remote
        self.started = time.perf_counter()
        self.started_wall = time.time()
        self.stage = "start"
        self.shards_outstanding: int | None = None

    def to_json(self) -> dict:
        out = {
            "id": self.qid,
            "index": self.index,
            "pql": self.pql,
            "tenant": self.tenant,
            "remote": self.remote,
            "ageSeconds": round(time.perf_counter() - self.started, 4),
            "stage": self.stage,
        }
        if self.trace_id is not None:
            out["traceId"] = self.trace_id
        if self.shards_outstanding is not None:
            out["shardsOutstanding"] = self.shards_outstanding
        return out


_current_query: contextvars.ContextVar = contextvars.ContextVar(
    "pilosa_tpu_inflight_query", default=None
)


def current_query() -> InflightQuery | None:
    """The inspector record of the query owning this context (rides the
    same capture-and-restore hops as the trace context), so deep layers
    (cluster fan-out) can update stage/shards without plumbing."""
    return _current_query.get()


class QueryTracker:
    """Registry of in-flight queries behind ``GET /debug/queries``.

    Always on by default — the long-running-query view matters exactly
    when something is stuck, regardless of trace sampling. Cost per query
    is one lock round trip each for start/finish; ``enabled = False``
    turns even that off (the bench's bare baseline)."""

    def __init__(self):
        self.enabled = True
        self._lock = threading.Lock()
        self._live: dict[int, InflightQuery] = {}
        self._next = 0
        self.started_total = 0

    def start(self, index: str, pql, tenant: str = "default",
              remote: bool = False) -> InflightQuery | None:
        if not self.enabled:
            return None
        cur = current_span()
        q = InflightQuery(
            0, index,
            (pql[:1024] if isinstance(pql, str) else str(pql)[:1024]),
            tenant, remote, cur.trace_id if cur is not None else None,
        )
        rid = _request_id.get()
        with self._lock:
            if not rid or rid in self._live:
                self._next += 1
                rid = self._next
            q.qid = rid
            self.started_total += 1
            self._live[rid] = q
        return q

    def reserve_id(self) -> int:
        """An id for a request whose record does not exist yet (the
        root stage reserves it; ``start`` adopts it as the qid)."""
        with self._lock:
            self._next += 1
            return self._next

    def activate(self, q: InflightQuery):
        """Bind ``q`` to the current context; returns a reset token."""
        return _current_query.set(q)

    def finish(self, q: InflightQuery | None, token=None) -> None:
        if q is None:
            return
        if token is not None:
            _current_query.reset(token)
        with self._lock:
            self._live.pop(q.qid, None)

    def snapshot(self) -> list[dict]:
        with self._lock:
            live = list(self._live.values())
        return [q.to_json() for q in
                sorted(live, key=lambda q: q.started)]

    def metrics(self) -> dict:
        with self._lock:
            return {
                "inflight_queries": len(self._live),
                "queries_tracked_total": self.started_total,
            }


_global_query_tracker: QueryTracker | None = None


def global_query_tracker() -> QueryTracker:
    global _global_query_tracker
    if _global_query_tracker is None:
        _global_query_tracker = QueryTracker()
    return _global_query_tracker


# --------------------------------------------------------------- stage site
#
# The contract of names (docs/OBSERVABILITY.md "Stages"): PERF.md and
# the benchmark's metric files read the series by these names.

# The flat partition of a request on its handler thread ...
TOP_LEVEL_STAGES = (
    "http.read", "qos.admit", "pql.parse", "pipeline.wave",
    "executor.execute", "executor.resolve", "result.encode", "wal.barrier",
    "http.write",
)
# ... under the root, and the stages nested in them or on other threads.
STAGES = ("http.query",) + TOP_LEVEL_STAGES + (
    "pipeline.gather", "pipeline.submit", "executor.plan",
    "executor.operands", "residency.miss", "residency.decode",
    "residency.upload", "residency.patch", "residency.lock_wait",
    "device.upload", "device.replicate", "device.dispatch",
    "device.readback", "fragment.write", "wal.commit",
    "executor.prune_level",
)
# The outermost stage of each thread role (``enter_thread_role``): its
# exit refreshes the thread's cumulative CPU.
ROLE_ROOT_STAGES = ("http.query", "pipeline.submit", "wal.commit")


class _StageCounter:
    """Entries and wall nanoseconds of one stage, and of the entries
    whose CPU was read their number, wall and CPU nanoseconds; exact
    under threads (all five are only ever changed under ``_lock``)."""

    __slots__ = ("count", "ns", "cpu_count", "cpu_wall_ns", "cpu_ns",
                 "role_root", "_lock")

    def __init__(self, role_root: bool = False):
        self.count = 0
        self.ns = 0
        self.cpu_count = 0
        self.cpu_wall_ns = 0
        self.cpu_ns = 0
        self.role_root = role_root
        self._lock = threading.Lock()


_clock_ns = time.perf_counter_ns
# The calling thread's own CPU. A system call where the wall clock is
# not: 0.3 us on a plain kernel and 5.6 us under the sandboxed kernel of
# the machines that hold the chips (PERF.md 6, PR 36), where two reads a
# site at every site cost a sixth of the rate. So a site reads it only
# where somebody will look at the answer.
_cpu_clock_ns = time.thread_time_ns

_stage_counters: dict[str, _StageCounter] = {
    n: _StageCounter(n in ROLE_ROOT_STAGES) for n in STAGES}
_stage_registry_lock = threading.Lock()

# jax.profiler.TraceAnnotation while a device capture runs, else None:
# start_jax_trace sets it before start_trace and clears it after
# stop_trace, so a site pays for an annotation only inside a capture.
_annotation = None

# The id every stage of one HTTP request carries into the capture
# (``rid``): reserved by the root stage, adopted by QueryTracker.start
# as the inspector's qid, so /debug/queries and the xplane agree.
_request_id: contextvars.ContextVar = contextvars.ContextVar(
    "pilosa_tpu_request_id", default=0
)


class _Stage:
    """One entry of a stage; see ``stage``."""

    __slots__ = ("name", "elapsed", "cpu", "_counter", "_tags", "_root",
                 "_cm", "_t0", "_c0", "_ann", "_query", "_prev",
                 "_rid_token")

    def __init__(self, name: str, counter: _StageCounter, root, tags: dict):
        self.name = name
        self.elapsed = 0.0  # wall seconds, set on exit
        self.cpu = None  # the thread's CPU seconds inside them, if read
        self._counter = counter
        self._tags = tags
        self._root = root
        self._ann = None
        self._rid_token = None

    def __enter__(self) -> Span | None:
        name = self.name
        cm = self._root
        if cm is not None:
            self._rid_token = _request_id.set(
                global_query_tracker().reserve_id())
        q = self._query = _current_query.get()
        if q is not None:
            self._prev = q.stage
            q.stage = name
        annotate = _annotation
        if annotate is not None:
            self._ann = annotate(
                name, rid=q.qid if q is not None else _request_id.get())
            self._ann.__enter__()
        self._t0 = t0 = _clock_ns()
        if cm is None:
            # join-only, checked here so that the common case (no sampled
            # trace) costs one contextvar read and no call
            cur = _current_span.get()
            cm = (_NOP if cur is None or cur is _NOT_SAMPLED
                  else global_tracer()._join(name, self._tags, t0 * 1e-9))
        self._cm = cm
        span = None if cm is _NOP else cm.__enter__()
        if span is not None:
            span.start = t0 * 1e-9
        # the second clock, inside a capture or a sampled trace only:
        # wall then CPU here, CPU then wall on exit, so the CPU interval
        # lies inside the wall interval
        self._c0 = (_cpu_clock_ns()
                    if annotate is not None or span is not None else -1)
        return span

    def __exit__(self, exc_type, exc, tb):
        counter = self._counter
        c0 = self._c0
        if c0 >= 0:
            c1 = _cpu_clock_ns()
        t1 = _clock_ns()
        ns = t1 - self._t0
        self.elapsed = ns * 1e-9
        if c0 >= 0:
            cpu_ns = c1 - c0
            self.cpu = cpu_ns * 1e-9
        with counter._lock:
            counter.count += 1
            counter.ns += ns
            if c0 >= 0:
                counter.cpu_count += 1
                counter.cpu_wall_ns += ns
                counter.cpu_ns += cpu_ns
        if counter.role_root:
            cell = getattr(_role_local, "cell", None)
            if cell is not None and (
                    c0 >= 0 or t1 - cell.read_ns >= ROLE_REFRESH_NS):
                cell.cpu_ns = (c1 if c0 >= 0
                               else _cpu_clock_ns()) - cell.base_ns
                cell.read_ns = t1
        cm = self._cm
        if cm is not _NOP:
            span = getattr(cm, "_span", None)
            if span is not None:
                if c0 >= 0:
                    span.tags["cpu_ms"] = round(cpu_ns * 1e-6, 3)
                if span.end is None:
                    span.end = t1 * 1e-9
            cm.__exit__(exc_type, exc, tb)
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        q = self._query
        if q is not None:
            q.stage = self._prev
        if self._rid_token is not None:
            _request_id.reset(self._rid_token)
        return False


def stage(name: str, _root=None, /, **tags) -> _Stage:
    """The one site every layer boundary of the served path uses.

    ``with stage("device.dispatch", reduce=kind) as span:`` reads the
    wall clock on entry and on exit and feeds four sinks: (1) the
    always-on cumulative counters of ``name`` (entries and seconds,
    exact under threads); (2) the sampled span tree — join-only,
    ``span`` is the child Span or None outside a sampled trace; (3) a
    ``jax.profiler.TraceAnnotation(name, rid=<request id>)`` while a
    device capture runs, which puts the stage on the profiler's own
    clock, on the line of the thread that did the work; (4) the
    in-flight inspector's ``stage`` (restored to the enclosing stage on
    exit). With no sampled trace and no capture it allocates neither a
    Span nor an annotation. ``handle.elapsed`` holds the seconds after
    exit (the cost plane's dispatch timer reads it).

    The second clock: inside a sampled trace or a capture the site also
    reads the calling thread's CPU clock, within the wall pair, and
    counts the entry a second time among the measured ones (entries,
    their wall seconds, their CPU seconds: ``stage_metrics``); the span
    gets the tag ``cpu_ms`` and ``handle.cpu`` the seconds (None when
    not read). Everywhere else the CPU clock is left alone, but for one
    read on exit of the three ROLE_ROOT_STAGES every ROLE_REFRESH_NS a
    thread.

    ``_root`` is a root handle (``Tracer.request_root`` /
    ``remote_root``) for the one stage that is also the trace's root:
    it makes the sampling decision and reserves the request id."""
    counter = _stage_counters.get(name)
    if counter is None:
        with _stage_registry_lock:
            counter = _stage_counters.setdefault(name, _StageCounter())
    return _Stage(name, counter, _root, tags)


def staged(name: str):
    """Decorator form of ``stage`` for a function that is one stage."""

    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            with stage(name):
                return fn(*args, **kwargs)

        return wrapper

    return deco


def stage_metrics() -> dict:
    """``<stage>_total`` and ``<stage>_seconds_total`` (dots to
    underscores) for every stage, zeros included: the ``stage`` block of
    /metrics and group ``stages`` of /debug/vars. Beside them, of the
    entries whose CPU was read (inside a sampled trace or a capture):
    ``<stage>_cpu_entries_total``, ``<stage>_cpu_wall_seconds_total``
    and ``<stage>_cpu_seconds_total``. Over the same entries, wall less
    CPU seconds is the time the stage's threads were not running:
    waiting for the interpreter, the device, a lock, a condition or the
    disk."""
    out: dict = {}
    for name, c in list(_stage_counters.items()):
        key = name.replace(".", "_")
        with c._lock:
            out[f"{key}_total"] = c.count
            out[f"{key}_seconds_total"] = c.ns * 1e-9
            out[f"{key}_cpu_entries_total"] = c.cpu_count
            out[f"{key}_cpu_wall_seconds_total"] = c.cpu_wall_ns * 1e-9
            out[f"{key}_cpu_seconds_total"] = c.cpu_ns * 1e-9
    return out


# ------------------------------------------------------ CPU by thread role
#
# The three kinds of thread the served path runs on. A thread in a role
# keeps one cell: its cumulative CPU (``time.thread_time_ns``, which only
# the thread itself can read) less the reading when it entered the role.
# The cell is refreshed on exit of the thread's root stage
# (ROLE_ROOT_STAGES): from the reading the site took anyway inside a
# capture or a sampled trace, else by a read of its own if the last one
# is ROLE_REFRESH_NS old; and once more when the thread retires. So it
# holds what the thread did between stages too (header parse, ``send``,
# the dispatcher's queue handling), a live thread's share is at most
# that long behind, and a retired thread's CPU is folded into its role's
# total, so a closed connection takes nothing away.

THREAD_ROLES = ("handler", "dispatcher", "wal_commit")
# 0.1 s: under 1 % of a scrape interval of 15 s or a benchmark window of
# 30 s, and ten ticks of the CPU clock of the machines that hold the
# chips. One read at every root exit (two a request) cost `dashboard`
# 1-5 % of its rate there (PERF.md 6, PR 36).
ROLE_REFRESH_NS = 100_000_000


class _RoleCell:
    __slots__ = ("role", "base_ns", "cpu_ns", "read_ns")

    def __init__(self, role: str, base_ns: int):
        self.role = role
        self.base_ns = base_ns
        self.cpu_ns = 0
        self.read_ns = 0  # wall clock of the last refresh


_role_local = threading.local()
_role_lock = threading.Lock()
_role_live: set = set()
_role_retired_ns = dict.fromkeys(THREAD_ROLES, 0)


def enter_thread_role(role: str) -> None:
    """The calling thread serves as ``role`` from here on (a connection's
    handler, the wave dispatcher, a WAL's commit thread)."""
    cell = _RoleCell(role, _cpu_clock_ns())
    _role_local.cell = cell
    with _role_lock:
        _role_live.add(cell)


def retire_thread_role() -> None:
    """The calling thread leaves its role: one last reading of its own
    clock, folded into the role's retired total."""
    cell = getattr(_role_local, "cell", None)
    if cell is None:
        return
    _role_local.cell = None
    cell.cpu_ns = _cpu_clock_ns() - cell.base_ns
    with _role_lock:
        _role_live.discard(cell)
        _role_retired_ns[cell.role] += cell.cpu_ns


def thread_metrics() -> dict:
    """``thread_<role>_cpu_seconds_total`` for the three roles (zero
    where a role has had no thread yet) and ``process_cpu_seconds_total``
    (``time.process_time()``: every thread of the process, JAX's and
    XLA's too): a block of its own on /metrics and group ``threads`` of
    /debug/vars. A role's total is as of each live thread's last
    refresh (at most ROLE_REFRESH_NS before its last root stage); it
    never falls."""
    with _role_lock:
        ns = dict(_role_retired_ns)
        for cell in _role_live:
            ns[cell.role] += cell.cpu_ns
    out = {f"thread_{role}_cpu_seconds_total": ns[role] * 1e-9
           for role in THREAD_ROLES}
    out["process_cpu_seconds_total"] = time.process_time()
    return out


# --------------------------------------------------------- GroupBy levels
#
# The stage site's tags reach a span and the capture, not /metrics, so
# "how many device programs does a GroupBy level take" has two series of
# its own beside the stages: one program a level unless a level exceeds
# batch.groupby_chunk_groups candidates. Two more say whether an answer
# stayed columnar to the response bytes: every GroupBy result built, and
# those a consumer walked group by group (executor/result.py GroupCounts:
# the cluster merge and the internal wire do; the JSON route does not).
# Two say how large the levels are and where their rows came from: the
# real candidates summed over levels (padding left out), and the
# dimensions whose Rows() named a previous, limit or column (a range of
# a field, which owns a stacked matrix of its own in the row cache).
# One says how many level programs had their packed operand placed on
# the device(s) for them: the rest found the array an earlier level of
# the same content had placed (Executor._level_operand), so
# 1 - placements / programs is that memo's hit share. Two say which
# path the work took: the GroupBys counted by prefix pruning (a level a
# dimension, a blocking readback between levels; the rest of
# results_total took one dense level or had nothing to count), and the
# level programs that paged at least one dimension (its rows stay in
# HBM and the kernel copies a row tile in by the candidate's index:
# batch.groupby_tile_plan). Two say how much those programs copy: the
# row tiles their candidates name (candidates x paged dimensions, a grid
# step), and the ones the kernel copies, which is one where a paged
# index differs from the candidate's before (batch.groupby_paged_rows):
# copies / visits is the share of copies made. Four say what a pruned
# GroupBy's marginal round did (every dimension counted alone under the
# filter, one blocking round trip, before any two are crossed): the
# rounds run (over pruned_total: 1.0), the rows they counted and the
# rows with a non-zero count (kept / rows is the share the round let
# through), and the rounds whose survivors' cross product fitted the
# dense rule, so that the final level ran at once.
# Beside them, a block of their own (``plan``): the folds over a view's
# fragments that the plan stage asked for (the non-empty rows of a
# Rows() or a GroupBy dimension, TopN's phase-1 candidates) and those
# that walked the fragments, because the view's version or the shard
# list had changed or the request named its shards (storage/view.py
# View._fold): 1 - walks / folds is the share a view answered at once.

_groupby_lock = threading.Lock()
_groupby_stats = {"levels": 0, "programs": 0, "candidates": 0,
                  "placements": 0, "range_dims": 0, "results": 0,
                  "materialized": 0, "pruned": 0, "paged_programs": 0,
                  "paged_row_visits": 0, "paged_row_copies": 0,
                  "marginal_rounds": 0, "marginal_rows": 0,
                  "marginal_kept": 0, "marginal_dense": 0,
                  "view_folds": 0, "view_walks": 0}


def note_groupby_level(programs: int, candidates: int, paged: int = 0,
                       row_visits: int = 0, row_copies: int = 0) -> None:
    with _groupby_lock:
        _groupby_stats["levels"] += 1
        _groupby_stats["programs"] += programs
        _groupby_stats["candidates"] += candidates
        _groupby_stats["paged_programs"] += paged
        _groupby_stats["paged_row_visits"] += row_visits
        _groupby_stats["paged_row_copies"] += row_copies


def note_groupby_operand_placement() -> None:
    with _groupby_lock:
        _groupby_stats["placements"] += 1


def note_groupby_range_dims(dims: int) -> None:
    with _groupby_lock:
        _groupby_stats["range_dims"] += dims


def note_groupby_result() -> None:
    with _groupby_lock:
        _groupby_stats["results"] += 1


def note_groupby_materialized() -> None:
    with _groupby_lock:
        _groupby_stats["materialized"] += 1


def note_groupby_pruned() -> None:
    with _groupby_lock:
        _groupby_stats["pruned"] += 1


def note_groupby_marginal(rows: int, kept: int, dense: bool) -> None:
    with _groupby_lock:
        _groupby_stats["marginal_rounds"] += 1
        _groupby_stats["marginal_rows"] += rows
        _groupby_stats["marginal_kept"] += kept
        _groupby_stats["marginal_dense"] += dense


def note_plan_view_fold(walked: bool) -> None:
    with _groupby_lock:
        _groupby_stats["view_folds"] += 1
        _groupby_stats["view_walks"] += walked


def plan_metrics() -> dict:
    """The ``plan`` block of /metrics and /debug/vars."""
    with _groupby_lock:
        return {"view_folds_total": _groupby_stats["view_folds"],
                "view_walks_total": _groupby_stats["view_walks"]}


def groupby_metrics() -> dict:
    """The ``groupby`` block of /metrics and /debug/vars."""
    with _groupby_lock:
        return {"levels_total": _groupby_stats["levels"],
                "level_programs_total": _groupby_stats["programs"],
                "level_candidates_total": _groupby_stats["candidates"],
                "operand_placements_total": _groupby_stats["placements"],
                "range_dims_total": _groupby_stats["range_dims"],
                "results_total": _groupby_stats["results"],
                "results_materialized_total": _groupby_stats["materialized"],
                "pruned_total": _groupby_stats["pruned"],
                "paged_programs_total": _groupby_stats["paged_programs"],
                "paged_row_visits_total": _groupby_stats["paged_row_visits"],
                "paged_row_copies_total": _groupby_stats["paged_row_copies"],
                "marginal_rounds_total": _groupby_stats["marginal_rounds"],
                "marginal_rows_total": _groupby_stats["marginal_rows"],
                "marginal_kept_total": _groupby_stats["marginal_kept"],
                "marginal_dense_total": _groupby_stats["marginal_dense"]}


# ------------------------------------------------- device compiles, memory

_compile_lock = threading.Lock()
_compile_stats = {"compiles": 0, "compile_ns": 0, "cache_loads": 0}
_compile_listener_installed = False
_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_LOAD_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"


def _on_jax_duration(event: str, duration: float, **_kw) -> None:
    if event == _BACKEND_COMPILE_EVENT:
        with _compile_lock:
            _compile_stats["compiles"] += 1
            _compile_stats["compile_ns"] += int(duration * 1e9)
    elif event == _CACHE_LOAD_EVENT:
        with _compile_lock:
            _compile_stats["cache_loads"] += 1


def install_compile_listener() -> None:
    """Count every program JAX makes executable from here on (a backend
    compile or a persistent-cache load; loads are counted apart too).
    Idempotent; Server.open calls it before the first query."""
    global _compile_listener_installed
    with _compile_lock:
        if _compile_listener_installed:
            return
        _compile_listener_installed = True
    import jax.monitoring

    jax.monitoring.register_event_duration_secs_listener(_on_jax_duration)


def device_metrics() -> dict:
    """The ``device`` block of /metrics and /debug/vars: programs made
    executable (and the seconds that took, and how many came from the
    persistent cache), and device memory summed over the local devices
    as ``memory_stats()`` gives it at scrape (0 where the backend gives
    none, as the CPU does)."""
    install_compile_listener()
    memory = device_memory_by_device()
    with _compile_lock:
        return {
            "compiles_total": _compile_stats["compiles"],
            "compile_seconds_total": _compile_stats["compile_ns"] * 1e-9,
            "compile_cache_loads_total": _compile_stats["cache_loads"],
            "memory_bytes_in_use": sum(d["bytes_in_use"] for d in memory),
            "memory_peak_bytes": sum(d["peak_bytes"] for d in memory),
        }


def device_memory_by_device() -> list[dict]:
    """Per local device: id, bytes in use and peak (the labelled
    gauges beside the unlabelled sums)."""
    import jax

    out = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        out.append({"device": str(d.id),
                    "bytes_in_use": int(stats.get("bytes_in_use", 0)),
                    "peak_bytes": int(stats.get("peak_bytes_in_use", 0))})
    return out


# ----------------------------------------------------------- device tracing


@contextlib.contextmanager
def start_jax_trace(log_dir: str):
    """Capture an XLA/JAX profiler trace around a block (TPU-side tracing;
    view with xprof/tensorboard, or ``python -m pilosa_tpu trace-report``).
    Live capture around real traffic is exposed at
    ``POST /debug/trace-device?secs=N`` (server/http.py).

    The Python tracer is off (``python_tracer_level = 0``): at its
    default it hooks every Python call of every thread and halves the
    rate of the server it observes. The host tracer keeps its level, so
    the stage sites' ``TraceAnnotation``s are recorded; they are
    switched on for exactly the capture's span."""
    global _annotation
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    _annotation = jax.profiler.TraceAnnotation
    try:
        jax.profiler.start_trace(log_dir, profiler_options=options)
        before = _counter_snapshot()
        try:
            yield
        finally:
            after = _counter_snapshot()
            jax.profiler.stop_trace()
            _write_capture_counters(log_dir, before, after)
    finally:
        _annotation = None


CAPTURE_COUNTERS_FILE = "stages.json"


def _counter_snapshot() -> tuple[int, dict, dict]:
    return _clock_ns(), stage_metrics(), thread_metrics()


def _write_capture_counters(log_dir: str, before, after) -> None:
    """The stage and thread counters' deltas over a capture, beside its
    ``.xplane.pb``: the one place where the device's busy seconds and the
    interpreter's are read over one span (``trace_report`` prints both)."""
    path = _newest_xplane(log_dir)
    (t0, stages0, threads0), (t1, stages1, threads1) = before, after
    body = {"span_s": (t1 - t0) * 1e-9,
            "stages": {k: v - stages0.get(k, 0)
                       for k, v in stages1.items()},
            "threads": {k: v - threads0[k] for k, v in threads1.items()}}
    target = os.path.dirname(path) if path else log_dir
    with open(os.path.join(target, CAPTURE_COUNTERS_FILE), "w") as f:
        json.dump(body, f, indent=1)


def _newest_xplane(log_dir: str) -> str | None:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(paths, key=os.path.getmtime) if paths else None


# ------------------------------------------------------------- trace report
#
# The operator's reading of a capture (``python -m pilosa_tpu
# trace-report <trace-log-dir>``); the labelling rule is written down in
# docs/OBSERVABILITY.md "Reading a capture".

_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
_HOST_PLANE = "/host:CPU"
_CPU_OPS_LINE = re.compile(r"^tf_XLA|^XLA")  # a CPU capture's stand-in
_HLO = re.compile(r"^%?([\w.\-]+) = (\(?)([a-z0-9]+\[[0-9,]*\])")
# A stage that only waits for another thread of this process; it labels
# a gap only when nothing else does (the thread it waits for says why).
WAIT_STAGES = ("pipeline.wave",)
LABEL_MIN_SHARE = 0.10


def _op_name(text: str) -> str:
    """An operation's XLA name and result shape from the HLO text the
    TPU's trace carries as the event name: ``fusion.36 u32[128,12,2048]``."""
    m = _HLO.match(text)
    if not m:
        return text[:80]
    name, is_tuple, shape = m.groups()
    if is_tuple:
        shape += f"x{text.split(') ', 1)[0].count('[')}"
    return f"{name} {shape}"


def _merged(intervals) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _innermost(events) -> list[tuple[float, float, str]]:
    """One thread's nested stage events [(start, end, name)] as a flat
    timeline in which every instant belongs to the innermost stage."""
    out: list[tuple[float, float, str]] = []
    stack: list[tuple[float, str]] = []
    cursor = 0.0
    for start, end, name in sorted(events, key=lambda e: (e[0], -e[1])):
        while stack and stack[-1][0] <= start:
            e_end, e_name = stack.pop()
            if cursor < e_end:
                out.append((cursor, e_end, e_name))
                cursor = e_end
        if stack and cursor < start:
            out.append((cursor, start, stack[-1][1]))
        cursor = start
        stack.append((end, name))
    while stack:
        e_end, e_name = stack.pop()
        if cursor < e_end:
            out.append((cursor, e_end, e_name))
            cursor = e_end
    return out


def label_gap(gap: tuple[float, float], timelines) -> tuple[str, float, list]:
    """What the host was doing in one device idle gap: per stage, the
    union over all host threads of the time the stage was the innermost
    one on its thread inside the gap, as a share of the gap. The label
    is the stage with the largest share (among equal shares, the one
    more threads sat in); a wait stage (WAIT_STAGES) labels the gap only
    when no other stage reaches LABEL_MIN_SHARE; ``no-request`` when
    none does. Returns (label, share, every stage over the threshold as
    [name, share], largest first)."""
    a, b = gap
    length = b - a
    if length <= 0:
        return "no-request", 0.0, []
    per_stage: dict[str, list] = {}
    for timeline in timelines:
        for s, e, name in timeline:
            if e > a and s < b:
                per_stage.setdefault(name, []).append((max(s, a), min(e, b)))
    shares = sorted(
        ((round(sum(y - x for x, y in _merged(iv)) / length, 4),
          sum(y - x for x, y in iv), name)
         for name, iv in per_stage.items()), reverse=True)
    shares = [(sh, n) for sh, _, n in shares if sh >= LABEL_MIN_SHARE]
    ranked = [[n, sh] for sh, n in shares]
    active = [(sh, n) for sh, n in shares if n not in WAIT_STAGES] or shares
    if not active:
        return "no-request", 0.0, ranked
    share, name = active[0]
    return name, share, ranked


def trace_report(log_dir: str, gaps_n: int = 5, top_n: int = 10) -> dict:
    """Reduce the newest ``.xplane.pb`` under ``log_dir`` (or the file
    itself): per device the busy share of its traced extent, seconds per
    XLA module and per operation, and its ``gaps_n`` longest idle gaps,
    each labelled by what the host was doing (``label_gap``); and the
    host threads' seconds by innermost stage, summed over threads."""
    from jax.profiler import ProfileData

    path = log_dir
    if os.path.isdir(log_dir):
        path = _newest_xplane(log_dir)
        if path is None:
            raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    try:
        data = ProfileData.from_file(path)
    except RuntimeError as e:  # a capture cut short, or not a capture
        raise ValueError(f"{path} is not a readable .xplane.pb: {e}") from e
    planes = list(data.planes)
    known = set(_stage_counters)

    timelines = []
    python_events = 0
    for plane in planes:
        if plane.name != _HOST_PLANE:
            continue
        for line in plane.lines:
            events = []
            for e in line.events:
                if e.name in known:
                    events.append((e.start_ns * 1e-9,
                                   (e.start_ns + e.duration_ns) * 1e-9,
                                   e.name))
                elif e.name.startswith("$"):
                    python_events += 1  # the Python tracer's frames
            if events:
                timelines.append(_innermost(events))

    def device_lines(plane, cpu: bool):
        ops, modules = [], []
        for line in plane.lines:
            is_ops = (bool(_CPU_OPS_LINE.match(line.name)) if cpu
                      else line.name == "XLA Ops")
            is_mod = not cpu and line.name == "XLA Modules"
            if not (is_ops or is_mod):
                continue
            for e in line.events:
                if cpu and not e.duration_ns:
                    continue  # thread-pool markers, not operations
                rec = (e.start_ns * 1e-9,
                       (e.start_ns + e.duration_ns) * 1e-9, e.name)
                (ops if is_ops else modules).append(rec)
        return ops, modules

    devices = [(p.name, *device_lines(p, False)) for p in planes
               if _DEVICE_PLANE.match(p.name)]
    if not devices:
        devices = [(p.name, *device_lines(p, True)) for p in planes
                   if p.name == _HOST_PLANE]
    out_devices = []
    for name, ops, modules in devices:
        if not ops:
            continue
        busy = _merged((s, e) for s, e, _ in ops)
        first, last = busy[0][0], busy[-1][1]
        busy_s = sum(b - a for a, b in busy)
        by_op: dict[str, float] = {}
        for s, e, n in ops:
            n = _op_name(n)
            by_op[n] = by_op.get(n, 0.0) + (e - s)
        by_module: dict[str, float] = {}
        for s, e, n in modules:
            n = n.split("(", 1)[0]
            by_module[n] = by_module.get(n, 0.0) + (e - s)
        gaps = sorted(((b0, a1) for (_, b0), (a1, _) in zip(busy, busy[1:])),
                      key=lambda g: g[0] - g[1])[:gaps_n]
        labelled = []
        for gap in gaps:
            label, share, ranked = label_gap(gap, timelines)
            labelled.append({"seconds": gap[1] - gap[0],
                             "at_s": gap[0] - first, "label": label,
                             "share": share, "stages": ranked})
        out_devices.append({
            "device": name,
            "extent_s": last - first,
            "busy_s": busy_s,
            "busy_share": busy_s / (last - first) if last > first else 0.0,
            "modules": sorted(by_module.items(), key=lambda kv: -kv[1])[:top_n],
            "ops": sorted(by_op.items(), key=lambda kv: -kv[1])[:top_n],
            "idle_gaps": labelled,
        })
    host: dict[str, float] = {}
    for timeline in timelines:
        for s, e, n in timeline:
            host[n] = host.get(n, 0.0) + (e - s)
    return {"file": path, "host_threads_with_stages": len(timelines),
            "python_tracer_events": python_events,
            "host_stage_thread_s": sorted(host.items(),
                                          key=lambda kv: -kv[1]),
            "counters": _capture_counters(os.path.dirname(path)),
            "devices": out_devices}


def _capture_counters(capture_dir: str) -> dict | None:
    """What ``start_jax_trace`` wrote beside the capture, reduced to
    per-stage [name, CPU seconds, wall seconds, entries] of the entries
    whose CPU was read (largest CPU first, stages with none left out)
    and CPU seconds by thread role;
    None for a capture that has no such file (an older one, a written
    one)."""
    try:
        with open(os.path.join(capture_dir, CAPTURE_COUNTERS_FILE)) as f:
            body = json.load(f)
    except (OSError, ValueError):
        return None
    stages = body["stages"]
    rows = []
    for name in _stage_counters:
        key = name.replace(".", "_")
        if stages.get(f"{key}_cpu_entries_total"):
            rows.append([name, stages[f"{key}_cpu_seconds_total"],
                         stages[f"{key}_cpu_wall_seconds_total"],
                         stages[f"{key}_cpu_entries_total"]])
    return {"span_s": body["span_s"],
            "stage_cpu_s": sorted(rows, key=lambda r: -r[1]),
            "thread_cpu_s": {k.removesuffix("_cpu_seconds_total"): v
                             for k, v in body["threads"].items()}}


def format_trace_report(report: dict) -> str:
    lines = [f"capture {report['file']}",
             f"host threads with stage annotations: "
             f"{report['host_threads_with_stages']}; Python-tracer events: "
             f"{report['python_tracer_events']}"]
    if report["host_stage_thread_s"]:
        lines.append("host thread-seconds by innermost stage: " + ", ".join(
            f"{n} {s:.3f}" for n, s in report["host_stage_thread_s"]))
    counters = report.get("counters")
    if counters:
        span = counters["span_s"]
        lines.append(
            f"stage CPU seconds over {span:.3f} s (wall seconds, entries): "
            + ", ".join(f"{n} {cpu:.3f} ({wall:.3f}, {count})"
                        for n, cpu, wall, count in counters["stage_cpu_s"]))
        lines.append(
            f"CPU seconds by thread role over {span:.3f} s: " + ", ".join(
                f"{n} {cpu:.3f} ({100 * cpu / span:.1f} % of a core)"
                for n, cpu in counters["thread_cpu_s"].items()))
    if not report["devices"]:
        lines.append("no operation ran on any device in this capture")
    for d in report["devices"]:
        lines.append(f"{d['device']}: busy {100 * d['busy_share']:.1f} % of "
                     f"{d['extent_s']:.3f} s ({d['busy_s']:.3f} s)")
        for title, key in (("XLA module", "modules"), ("operation", "ops")):
            for name, seconds in d[key]:
                lines.append(f"  {title} {name}: {seconds:.4f} s")
        for g in d["idle_gaps"]:
            also = ", ".join(f"{n} {100 * sh:.0f}%" for n, sh in g["stages"]
                             if n != g["label"])
            label = (g["label"] if g["label"] == "no-request"
                     else f"{g['label']} {100 * g['share']:.0f}%")
            lines.append(f"  idle gap {g['seconds']:.4f} s at "
                         f"+{g['at_s']:.3f} s: {label}"
                         + (f" (also {also})" if also else ""))
    return "\n".join(lines)
