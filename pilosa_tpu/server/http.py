"""HTTP handler: the reference's REST surface on the stdlib http server.

Reference: http/handler.go (SURVEY.md §2 #19). External routes:

  POST   /index/{index}/query                 PQL → {"results": [...]}
  POST   /index/{index}                       create index
  GET    /index/{index}                       index schema
  DELETE /index/{index}
  POST   /index/{index}/field/{field}         create field
  DELETE /index/{index}/field/{field}
  POST   /index/{i}/field/{f}/import          JSON bit batches
  POST   /index/{i}/field/{f}/import-value    JSON value batches
  POST   /index/{i}/field/{f}/import-roaring/{shard}  roaring bytes
  GET    /export?index=&field=                CSV
  GET    /schema | /status | /info | /version | /metrics
  GET    /internal/shards/max
  POST   /internal/cluster/message            (cluster control — M4+)
  GET    /internal/fragment/blocks|data       (anti-entropy / resize)

Responses are JSON (the reference also negotiates protobuf; JSON is the
wire format here — the serving tier is host-side control plane, never on
the TPU hot path).
"""

from __future__ import annotations

import json
import re
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from urllib.parse import parse_qs, urlparse

from pilosa_tpu.server.api import API, ApiError
from pilosa_tpu.utils.cost import cost_enabled
from pilosa_tpu.utils.tracing import (
    TRACE_HEADER,
    enter_thread_role,
    global_tracer,
    retire_thread_role,
    stage,
)

_ROUTES: list[tuple[str, re.Pattern, str]] = [
    ("POST", re.compile(r"^/index/([^/]+)/query$"), "post_query"),
    ("POST", re.compile(r"^/index/([^/]+)/field/([^/]+)/import$"), "post_import"),
    ("POST", re.compile(r"^/index/([^/]+)/field/([^/]+)/import-value$"), "post_import_value"),
    ("POST", re.compile(r"^/index/([^/]+)/field/([^/]+)/import-roaring/(\d+)$"), "post_import_roaring"),
    ("POST", re.compile(r"^/index/([^/]+)/field/([^/]+)$"), "post_field"),
    ("DELETE", re.compile(r"^/index/([^/]+)/field/([^/]+)$"), "delete_field"),
    ("POST", re.compile(r"^/index/([^/]+)$"), "post_index"),
    ("GET", re.compile(r"^/index/([^/]+)$"), "get_index"),
    ("DELETE", re.compile(r"^/index/([^/]+)$"), "delete_index"),
    ("GET", re.compile(r"^/schema$"), "get_schema"),
    ("GET", re.compile(r"^/status$"), "get_status"),
    ("GET", re.compile(r"^/info$"), "get_info"),
    ("GET", re.compile(r"^/version$"), "get_version"),
    ("GET", re.compile(r"^/export$"), "get_export"),
    ("GET", re.compile(r"^/metrics$"), "get_metrics"),
    ("POST", re.compile(r"^/recalculate-caches$"), "post_recalculate_caches"),
    ("POST", re.compile(r"^/internal/query-batch$"), "post_query_batch"),
    ("GET", re.compile(r"^/internal/shards/max$"), "get_shards_max"),
    ("GET", re.compile(r"^/internal/shards/list$"), "get_shards_list"),
    ("GET", re.compile(r"^/internal/sync/manifest$"), "get_sync_manifest"),
    ("POST", re.compile(r"^/internal/sync/blocks$"), "post_sync_blocks"),
    ("GET", re.compile(r"^/internal/wal/tail$"), "get_wal_tail"),
    ("POST", re.compile(r"^/internal/scrub$"), "post_scrub"),
    ("GET", re.compile(r"^/internal/fragment/blocks$"), "get_fragment_blocks"),
    ("GET", re.compile(r"^/internal/fragment/block/data$"), "get_fragment_block_data"),
    ("GET", re.compile(r"^/internal/fragment/data$"), "get_fragment_data"),
    ("GET", re.compile(r"^/internal/fragment/nodes$"), "get_fragment_nodes"),
    ("GET", re.compile(r"^/internal/fragments$"), "get_fragments_catalog"),
    ("POST", re.compile(r"^/internal/cluster/message$"), "post_cluster_message"),
    ("GET", re.compile(r"^/internal/attrs/blocks$"), "get_attr_blocks"),
    ("GET", re.compile(r"^/internal/attrs/block/data$"), "get_attr_block_data"),
    ("POST", re.compile(r"^/internal/translate/keys$"), "post_translate_keys"),
    ("GET", re.compile(r"^/internal/translate/data$"), "get_translate_data"),
    ("GET", re.compile(r"^/internal/schema$"), "get_schema"),
    ("GET", re.compile(r"^/debug/faults$"), "get_faults"),
    ("POST", re.compile(r"^/debug/faults$"), "post_faults"),
    ("DELETE", re.compile(r"^/debug/faults$"), "delete_faults"),
    ("GET", re.compile(r"^/debug/traces$"), "get_traces"),
    ("GET", re.compile(r"^/debug/tenants$"), "get_tenants"),
    ("GET", re.compile(r"^/debug/heatmap$"), "get_heatmap"),
    ("GET", re.compile(r"^/debug/rescache$"), "get_rescache"),
    ("GET", re.compile(r"^/debug/autopilot$"), "get_autopilot"),
    ("GET", re.compile(r"^/debug/elastic$"), "get_elastic"),
    ("POST", re.compile(r"^/cluster/drain/([^/]+)$"), "post_drain"),
    ("DELETE", re.compile(r"^/cluster/drain$"), "delete_drain"),
    ("GET", re.compile(r"^/cluster/drain$"), "get_drain"),
    ("GET", re.compile(r"^/debug/slo$"), "get_slo"),
    ("GET", re.compile(r"^/debug/workers$"), "get_workers"),
    ("GET", re.compile(r"^/debug/queries$"), "get_inflight_queries"),
    ("GET", re.compile(r"^/debug/queries/slow$"), "get_long_queries"),
    ("GET", re.compile(r"^/debug/long-queries$"), "get_long_queries"),
    ("POST", re.compile(r"^/debug/trace-device$"), "post_trace_device"),
    ("GET", re.compile(r"^/debug/vars$"), "get_debug_vars"),
    ("GET", re.compile(r"^/debug/pprof/?$"), "get_pprof"),
]


class HTTPHandler(BaseHTTPRequestHandler):
    api: API = None  # set by make_http_server
    protocol_version = "HTTP/1.1"
    # idle keep-alive reaper: a persistent connection that sends nothing
    # for this long is closed (handle_one_request catches the socket
    # timeout), so pooled-but-abandoned client connections cannot pin
    # handler threads forever
    timeout = 120
    # buffered response writes: status line + headers + body leave as
    # ONE syscall/packet per response (handle_one_request flushes after
    # each request) instead of a header write then a body write —
    # responses here are always full Content-Length'd bodies, never
    # streamed, so buffering costs nothing
    wbufsize = -1

    # quiet logging; the server wires its own logger
    def log_message(self, fmt, *args):
        pass

    def setup(self):
        super().setup()
        # connection-count oracle for keep-alive reuse: requests ≫
        # connections proves clients are riding persistent connections.
        # The socket is also registered so server_close can hard-close
        # established keep-alive connections — without that, a "closed"
        # node would keep serving old peers' pooled connections forever
        # (its handler threads outlive the listener), which is graceful
        # drain, not death.
        lock = getattr(self.server, "metrics_lock", None)
        if lock is not None:
            with lock:
                self.server.connections_opened += 1
                self.server.open_connections.add(self.connection)
        # this thread's CPU counts as the handler role's from here to
        # finish (thread_handler_cpu_seconds_total): request line and
        # header parse, the stages, the socket send
        enter_thread_role("handler")

    def finish(self):
        retire_thread_role()
        lock = getattr(self.server, "metrics_lock", None)
        if lock is not None:
            with lock:
                self.server.open_connections.discard(self.connection)
        super().finish()

    def _dispatch(self, method: str):
        self._body_read = False
        lock = getattr(self.server, "metrics_lock", None)
        if lock is not None:
            with lock:
                self.server.requests_served += 1
        if "chunked" in (self.headers.get("Transfer-Encoding") or "").lower():
            # _body/_drain_body only understand Content-Length; chunk
            # framing left in rfile would be parsed as the next request
            # line and poison every later exchange on this connection —
            # reject with 411 and close (RFC 7230 §3.3.3 option)
            self._body_read = True
            # Connection: close both tells the client AND (via
            # send_header's side effect) sets close_connection here
            self._json({"error": "chunked request bodies are not "
                                 "supported; send Content-Length"},
                       status=411, headers={"Connection": "close"})
            return
        parsed = urlparse(self.path)
        for m, pattern, handler in _ROUTES:
            if m != method:
                continue
            match = pattern.match(parsed.path)
            if match:
                try:
                    getattr(self, handler)(*match.groups(), query=parse_qs(parsed.query))
                except ApiError as e:
                    headers = None
                    retry_after = getattr(e, "retry_after", None)
                    if retry_after is not None:
                        # shed at admission: tell the client when to come
                        # back instead of letting it hammer a full queue
                        headers = {"Retry-After": str(max(1, int(retry_after)))}
                    self._drain_body()
                    self._json({"error": str(e)}, status=e.status,
                               headers=headers)
                except Exception as e:  # internal error → 500, not a crash
                    self._drain_body()
                    self._json({"error": f"internal: {e}"}, status=500)
                else:
                    # a handler that never read its body (GET with a
                    # stray body, early-return route) must not leave the
                    # bytes to corrupt the NEXT request on this
                    # keep-alive connection
                    self._drain_body()
                return
        self._drain_body()
        self._json({"error": "not found"}, status=404)

    def do_GET(self):
        self._dispatch("GET")

    def do_POST(self):
        self._dispatch("POST")

    def do_DELETE(self):
        self._dispatch("DELETE")

    # -------------------------------------------------------------- helpers

    def _body(self) -> bytes:
        self._body_read = True
        length = int(self.headers.get("Content-Length", 0))
        return self.rfile.read(length) if length else b""

    def _drain_body(self) -> None:
        """Consume an unread request body so the error (or body-less)
        response leaves the connection aligned on the next request —
        leftover body bytes would be parsed as a request line and poison
        every later exchange on a keep-alive connection."""
        if getattr(self, "_body_read", True):
            return
        self._body_read = True
        try:
            length = int(self.headers.get("Content-Length", 0) or 0)
        except ValueError:
            self.close_connection = True
            return
        while length > 0:
            chunk = self.rfile.read(min(length, 1 << 16))
            if not chunk:
                break
            length -= len(chunk)

    def _json_body(self) -> dict:
        raw = self._body()
        if not raw:
            return {}
        try:
            return json.loads(raw)
        except json.JSONDecodeError as e:
            raise ApiError(f"invalid JSON body: {e}") from e

    def _json(self, obj, status: int = 200, headers: dict | None = None) -> None:
        data = json.dumps(obj).encode()
        self.send_response(status)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(data)))
        for k, v in (headers or {}).items():
            self.send_header(k, v)
        self.end_headers()
        self.wfile.write(data)

    def _qos_envelope(self, remote: bool = False):
        """Tenant + deadline from request headers (the QoS request
        envelope — docs/QOS.md). The deadline header carries remaining
        budget in ms; absent, the server default applies (0 = none) —
        but only to EDGE requests: a remote sub-query's budget belongs
        to its root, and minting a local default for it would let one
        peer's tighter config 504 (and so DEGRADE) healthy nodes."""
        from pilosa_tpu.qos import DEADLINE_HEADER, TENANT_HEADER, Deadline

        tenant = (self.headers.get(TENANT_HEADER) or "default").strip()
        raw = self.headers.get(DEADLINE_HEADER)
        if raw is not None:
            try:
                millis = int(raw)
                if millis <= 0:
                    raise ValueError
            except ValueError:
                raise ApiError(
                    f"invalid {DEADLINE_HEADER} header {raw!r}: must be a "
                    "positive integer of milliseconds"
                ) from None
            return tenant, Deadline.from_millis(millis)
        if not remote and self.api.default_deadline_s > 0:
            return tenant, Deadline.after(self.api.default_deadline_s)
        return tenant, None

    def _staleness_gate(self) -> None:
        """Stale-bounded reads on a CDC follower (docs/OPERATIONS.md
        Replication & CDC): parse ``X-Pilosa-Max-Staleness`` (the
        shared Go-duration grammar — utils/durations.py) and refuse
        503 + Retry-After when this node's replica lag exceeds the
        tighter of the header and the configured budget. A no-op on
        every node that isn't a follower — primaries serve their own
        writes and owe no staleness bound."""
        if self.api.follower is None:
            return
        from pilosa_tpu.qos import STALENESS_HEADER

        raw = self.headers.get(STALENESS_HEADER)
        budget = None
        if raw is not None:
            from pilosa_tpu.utils.durations import parse_duration

            try:
                budget = parse_duration(raw)
            except ValueError as e:
                raise ApiError(
                    f"invalid {STALENESS_HEADER} header {raw!r}: {e}"
                ) from e
        self.api.check_staleness(budget)

    def _note_egress(self, tenant: str, index: str, nbytes: int,
                     remote: bool) -> None:
        """Fold one edge query response's bytes into the tenant ledger
        (docs/OBSERVABILITY.md). Remote hops are exempt — they carry
        pieces of an edge request already accounted on the
        coordinator."""
        if not remote and cost_enabled():
            self.api.cost.add_egress(tenant, index, nbytes)

    def _note_ingest(self, index: str, rows: int, remote: bool) -> None:
        """Fold one edge import's row count into the tenant ledger.
        Tenant attribution via the QoS tenant header, like queries;
        routed internal slices are exempt (already accounted at the
        edge)."""
        from pilosa_tpu.qos import TENANT_HEADER

        if not remote and cost_enabled():
            tenant = (self.headers.get(TENANT_HEADER) or "default").strip()
            self.api.cost.add_ingest(tenant, index, rows)

    def _text(self, text: str, content_type: str = "text/plain") -> None:
        data = text.encode()
        self.send_response(200)
        self.send_header("Content-Type", content_type)
        self.send_header("Content-Length", str(len(data)))
        self.end_headers()
        self.wfile.write(data)

    def _bytes(self, data: bytes, headers: dict | None = None) -> None:
        self.send_response(200)
        self.send_header("Content-Type", "application/octet-stream")
        self.send_header("Content-Length", str(len(data)))
        for k, v in (headers or {}).items():
            self.send_header(k, v)
        self.end_headers()
        self.wfile.write(data)

    # Payloads below this size skip the compression attempt: zlib headers
    # plus the CPU round trip cost more than the bytes saved.
    COMPRESS_MIN_BYTES = 256

    def _bytes_negotiated(self, data: bytes,
                          headers: dict | None = None) -> None:
        """Octet-stream body with optional zlib Content-Encoding,
        negotiated per request: compressed ONLY when the client
        advertised ``Accept-Encoding: deflate`` (the repair client's
        ``repair-compression`` knob controls whether it does) AND
        compression actually shrinks the payload — so plain clients,
        old-wire peers, and incompressible bodies all get identity
        bytes. Roaring fragment payloads compress dramatically (Chambi
        et al. 1402.6407), which is where resize transfer time lives.
        ``headers`` ride either branch (the CDC tail route's seq
        positions must survive the compression decision)."""
        accept = (self.headers.get("Accept-Encoding") or "").lower()
        if "deflate" in accept and len(data) >= self.COMPRESS_MIN_BYTES:
            import zlib

            compressed = zlib.compress(data, 6)
            if len(compressed) < len(data):
                self.send_response(200)
                self.send_header("Content-Type",
                                 "application/octet-stream")
                self.send_header("Content-Encoding", "deflate")
                self.send_header("Content-Length", str(len(compressed)))
                for k, v in (headers or {}).items():
                    self.send_header(k, v)
                self.end_headers()
                self.wfile.write(compressed)
                return
        self._bytes(data, headers)

    def _raw(self, data: bytes, content_type: str = "application/json",
             status: int = 200) -> None:
        """Pre-serialized response body (serving fast lane): no dict
        building, no json.dumps — the bytes were encoded once upstream."""
        self.send_response(status)
        self.send_header("Content-Type", content_type)
        self.send_header("Content-Length", str(len(data)))
        self.end_headers()
        self.wfile.write(data)

    # --------------------------------------------------------------- routes

    def post_query(self, index, query=None):
        # Tracing roots (utils/tracing.py): the root stage http.query
        # spans body read to last byte written. An EDGE request makes
        # the sampling decision here (one tree per request, or a
        # suppressed context so inner sites can't root their own); a
        # REMOTE sub-query carrying X-Pilosa-Trace joins the
        # coordinator's trace and returns its finished span subtree in
        # the response so the caller renders ONE cluster-wide tree. The
        # decision needs only the URL and the headers (every internal
        # client sends remote=true there), so it precedes the body read.
        tracer = global_tracer()
        remote = bool(query and query.get("remote", ["false"])[0] == "true")
        trace_hdr = self.headers.get(TRACE_HEADER) if remote else None
        if remote:
            root_cm = tracer.remote_root(
                trace_hdr, "rpc.query", node=self.api.node_id(),
                index=index,
            )
        else:
            root_cm = tracer.request_root("http.query", index=index)
        with stage("http.query", root_cm) as root:
            self._post_query(index, query, remote, trace_hdr, root)

    def _post_query(self, index, query, remote, trace_hdr, root):
        with stage("http.read"):
            raw = self._body()
            content_type = self.headers.get("Content-Type", "")
            accept = self.headers.get("Accept", "")
            proto_in = "application/x-protobuf" in content_type
            proto_out = "application/x-protobuf" in accept
            want_profile = bool(
                query and query.get("profile", ["false"])[0] == "true"
            )
            if want_profile and proto_out:
                # the profile rides only the JSON envelope; silently
                # paying the profiling overhead and dropping the tree
                # would send a debugger down a false trail (checked
                # before the wire-availability 406 so the answer is
                # deterministic)
                raise ApiError(
                    "profile=true requires a JSON response (drop the "
                    "application/x-protobuf Accept header)"
                )

            if proto_in or proto_out:
                from pilosa_tpu import wire

                if not wire.available():
                    raise ApiError("protobuf wire format unavailable", 406)

            if proto_in:
                from pilosa_tpu.wire.serializer import decode_query_request

                pql, shards, body_remote, opts = decode_query_request(raw)
                remote = remote or body_remote
            else:
                pql = raw.decode()
                shards = None
                if query and "shards" in query:
                    shards = [
                        _int_param(s, "shards")
                        for s in query["shards"][0].split(",")
                    ]
                opts = {}
            # request-level result options also ride URL params for
            # either body encoding (reference handler query args)
            opts.update({
                k: True for k in ("columnAttrs", "excludeColumns",
                                  "excludeRowAttrs")
                if query and query.get(k, ["false"])[0] == "true"
            })

            tenant, deadline = self._qos_envelope(remote=remote)
            if root is not None and not remote:
                root.tags["tenant"] = tenant
            self._staleness_gate()
        # PQL PROFILE (docs/OBSERVABILITY.md): ?profile=true returns a
        # per-AST-node execution profile beside the results; remote hops
        # carry the flag so the coordinator's envelope holds one
        # stitched per-node tree (the trace-graft pattern below)
        profile_out: list | None = [] if want_profile else None

        if not proto_out:
            # the response envelope arrives pre-serialized (hot shapes
            # encode straight to bytes; identical deduped wavemates
            # share one encoding — executor/result.py)
            payload = self.api.query_json_bytes(
                index, pql, shards=shards, remote=remote,
                opts=opts, tenant=tenant, deadline=deadline,
                profile_out=profile_out)
            if root is not None and trace_hdr:
                # splice the finished subtree into the closing brace
                # of the pre-serialized envelope — sampled remote hops
                # are rare (rate-bounded), so the zero-build path is
                # untouched
                root.finish()
                payload = (payload[:-1] + b',"trace":'
                           + json.dumps(
                               root.to_json(),
                               separators=(",", ":")).encode()
                           + b"}")
            if profile_out:
                # same splice as the trace graft: profiled requests
                # are rare debugging traffic
                payload = (payload[:-1] + b',"profile":'
                           + json.dumps(
                               profile_out[0],
                               separators=(",", ":")).encode()
                           + b"}")
            self._note_egress(tenant, index, len(payload), remote)
            # into the connection's write buffer; the one send happens
            # when the handler returns (wbufsize), after the root span
            # is recorded, so /debug/traces already holds a request's
            # tree when its client reads the answer
            with stage("http.write"):
                self._raw(payload)
            return
        from pilosa_tpu.wire.serializer import (
            encode_error,
            encode_results,
        )

        retry_after = None
        try:
            results = self.api.query_raw(index, pql, shards=shards,
                                         remote=remote, opts=opts,
                                         tenant=tenant,
                                         deadline=deadline,
                                         profile_out=profile_out)
            trace_json = None
            if root is not None and trace_hdr:
                root.finish()
                trace_json = root.to_json()
            with stage("result.encode"):
                payload = encode_results(results, trace=trace_json)
            status = 200
        except ApiError as e:
            payload = encode_error(str(e))
            status = e.status
            retry_after = getattr(e, "retry_after", None)
        self._note_egress(tenant, index, len(payload), remote)
        with stage("http.write"):
            self.send_response(status)
            self.send_header("Content-Type", "application/x-protobuf")
            self.send_header("Content-Length", str(len(payload)))
            if retry_after is not None:
                # admission shed: same backoff hint the JSON route sends
                self.send_header("Retry-After",
                                 str(max(1, int(retry_after))))
            self.end_headers()
            self.wfile.write(payload)

    def post_query_batch(self, query=None):
        """Cluster-wide wave batching receiver: several remote
        sub-queries from one peer, executed with every item submitted
        before any resolves (shared micro-batched dispatches), answered
        positionally. Per-item errors ride inside the 200 envelope —
        item isolation, not request failure."""
        raw = self._body()
        content_type = self.headers.get("Content-Type", "")
        accept = self.headers.get("Accept", "")
        if ("application/x-protobuf" in content_type
                or "application/x-protobuf" in accept):
            from pilosa_tpu import wire

            if not wire.available():
                raise ApiError("protobuf wire format unavailable", 406)
            from pilosa_tpu.wire.serializer import (
                decode_batch_request,
                encode_batch_responses,
            )

            outcomes = self.api.query_batch(decode_batch_request(raw))
            self._raw(encode_batch_responses(outcomes),
                      "application/x-protobuf")
            return
        try:
            body = json.loads(raw or b"{}")
        except json.JSONDecodeError as e:
            raise ApiError(f"invalid JSON body: {e}") from e
        items = [
            (q.get("index", ""), q.get("query", ""),
             [int(s) for s in (q.get("shards") or [])],
             q.get("trace") or None)
            for q in body.get("queries", [])
        ]
        from pilosa_tpu.executor.result import results_json_bytes

        parts = []
        for outcome in self.api.query_batch(items):
            if outcome[0] == "ok":
                # identical bytes to a per-query /index/{i}/query
                # response — the batch route must be a pure transport
                # optimization (gated by `make serving-smoke`); a traced
                # item (rare, sample-rate-bounded) splices its span
                # subtree into the envelope like the per-query route
                part = results_json_bytes(outcome[1])
                if len(outcome) > 2 and outcome[2] is not None:
                    part = (part[:-1] + b',"trace":'
                            + json.dumps(outcome[2],
                                         separators=(",", ":")).encode()
                            + b"}")
                parts.append(part)
            else:
                parts.append(json.dumps(
                    {"error": outcome[1], "status": outcome[2]},
                    separators=(",", ":"),
                ).encode())
        self._raw(b'{"responses":[' + b",".join(parts) + b"]}")

    def post_index(self, index, query=None):
        body = self._json_body()
        opts = body.get("options", {})
        self._json(
            self.api.create_index(
                index,
                keys=opts.get("keys", False),
                track_existence=opts.get("trackExistence", True),
            )
        )

    def get_index(self, index, query=None):
        idx = self.api._index(index)
        self._json(idx.schema())

    def delete_index(self, index, query=None):
        self.api.delete_index(index)
        self._json({})

    def post_field(self, index, field, query=None):
        body = self._json_body()
        self._json(self.api.create_field(index, field, body.get("options", {})))

    def delete_field(self, index, field, query=None):
        self.api.delete_field(index, field)
        self._json({})

    def post_import(self, index, field, query=None):
        remote = bool(query and query.get("remote", ["false"])[0] == "true")
        if "application/x-protobuf" in self.headers.get("Content-Type", ""):
            from pilosa_tpu import wire

            if not wire.available():
                raise ApiError("protobuf wire format unavailable", 406)
            from pilosa_tpu.wire.serializer import decode_import_request

            rows, columns, timestamps, clear = decode_import_request(self._body())
        else:
            body = self._json_body()
            rows, columns = body.get("rows", []), body.get("columns", [])
            timestamps = body.get("timestamps")
            clear = bool(body.get("clear", False))
        self._check_import_size(len(columns), remote)
        changed = self.api.import_bits(
            index, field, rows, columns, timestamps=timestamps, clear=clear,
            remote=remote,
        )
        self._note_ingest(index, len(columns), remote)
        self._json({"changed": changed})

    def post_import_value(self, index, field, query=None):
        remote = bool(query and query.get("remote", ["false"])[0] == "true")
        if "application/x-protobuf" in self.headers.get("Content-Type", ""):
            from pilosa_tpu import wire

            if not wire.available():
                raise ApiError("protobuf wire format unavailable", 406)
            from pilosa_tpu.wire.serializer import decode_import_value_request

            columns, values, clear = decode_import_value_request(self._body())
        else:
            body = self._json_body()
            columns, values = body.get("columns", []), body.get("values", [])
            clear = bool(body.get("clear", False))
        self._check_import_size(len(columns), remote)
        changed = self.api.import_values(
            index, field, columns, values, clear=clear, remote=remote,
        )
        self._note_ingest(index, len(columns), remote)
        self._json({"changed": changed})

    def _check_import_size(self, n: int, remote: bool) -> None:
        """Apply max-writes-per-request to EDGE import bodies (the same
        knob the query path enforces — a 100k-row import is no lighter
        than 100k Set() calls). Remote hops are exempt: they carry
        slices of an already-admitted edge batch, and a routed slice
        must never bounce off a peer with a tighter config. 413 so bulk
        clients (CLI --batch-size) can split-and-retry distinguishably
        from validation 400s."""
        limit = self.api.max_writes_per_request
        if not remote and 0 < limit < n:
            raise ApiError(
                f"import batch of {n} rows exceeds max-writes-per-request "
                f"{limit}; split the batch (the CLI clamps --batch-size "
                "to this server's limit automatically)", 413,
            )

    def post_import_roaring(self, index, field, shard, query=None):
        remote = bool(query and query.get("remote", ["false"])[0] == "true")
        submitted: list = []
        changed = self.api.import_roaring(index, field, int(shard),
                                          self._body(), remote=remote,
                                          submitted_out=submitted)
        # bill bits SUBMITTED (like the row/value routes) — billing
        # bits-changed would make a tenant's ledger depend on which
        # wire format its loader picked, not on the data it pushed
        self._note_ingest(index, submitted[0] if submitted else changed,
                          remote)
        self._json({"changed": changed})

    def get_schema(self, query=None):
        self._json(self.api.schema())

    def get_status(self, query=None):
        self._json(self.api.status())

    def get_info(self, query=None):
        self._json(self.api.info())

    def get_version(self, query=None):
        self._json(self.api.version())

    def post_scrub(self, query=None):
        """Trigger one integrity scrub pass (CLI ``check --host``,
        operators mid-incident): verify owned fragments' disk bytes,
        quarantine + read-repair any rot, return the pass record."""
        self._body()  # drain for keep-alive alignment
        self._json(self.api.scrub_now())

    def post_recalculate_caches(self, query=None):
        """Reference parity: authoritative per-node TopN cache recount;
        204 No Content on success, as upstream."""
        self._body()  # drain: unread bytes would corrupt keep-alive reuse
        self.api.recalculate_caches()
        # RFC 7230 §3.3.2: no Content-Length on a 204
        self.send_response(204)
        self.end_headers()

    def get_metrics(self, query=None):
        from pilosa_tpu.storage.residency import global_row_cache
        from pilosa_tpu.utils.stats import global_stats, prometheus_block

        stats = global_stats()
        seen: set = set()  # page-wide family-metadata dedupe
        text = stats.prometheus_text(seen)
        prefix = getattr(stats, "prefix", "pilosa_tpu")
        text += global_row_cache().prometheus_lines(prefix, seen=seen)
        # wave coalescing health: queries/waves ratio is the batch
        # factor operators size concurrency against (OPERATIONS.md);
        # exported as 0 from scrape one so rate() windows never see the
        # series appear mid-flight. Every block below renders through
        # prometheus_block, which leads each family with # HELP/# TYPE
        # (docs/OBSERVABILITY.md — a stock Prometheus scrape must ingest
        # the whole page).
        pm = self.api.pipeline_metrics()
        text += prometheus_block(
            {"waves_total": pm["waves"],
             "coalesced_requests_total": pm["coalesced"],
             "deduped_requests_total": pm["deduped"]},
            prefix, "serving", seen=seen,
        )
        # serving fast lane (connection pool, remote wave batching, HTTP
        # keep-alive oracle): all series present from scrape one, zeros
        # included, like the qos block below
        fastlane = self.api.fastlane_metrics()
        lock = getattr(self.server, "metrics_lock", None)
        if lock is not None:
            with lock:
                fastlane["http_connections_total"] = \
                    self.server.connections_opened
                fastlane["http_requests_total"] = self.server.requests_served
        text += prometheus_block(fastlane, prefix, "serving",
                                  seen=seen)
        # multi-process serving tier (docs/OPERATIONS.md deployment
        # shapes): worker count, ring depth/backpressure, owner batch
        # sizes — zeros in single-process mode, from scrape one
        text += prometheus_block(self.api.mp_metrics(), prefix,
                                 seen=seen)
        # skewed-traffic actuators (docs/OPERATIONS.md skewed traffic):
        # the write-invalidated result cache and the heat-driven
        # residency tiering pass — zeros while disabled, from scrape one
        text += prometheus_block(self.api.rescache_metrics(), prefix,
                                 seen=seen)
        text += prometheus_block(self.api.tiering_metrics(), prefix,
                                 seen=seen)
        # autopilot placement plane (docs/OPERATIONS.md autopilot):
        # planner passes/plans/moves plus the placement-override gauges —
        # the gauges stay live even with the planner off, because this
        # node still adopts overrides minted by the coordinator
        text += prometheus_block(self.api.autopilot_metrics(), prefix,
                                 seen=seen)
        # elastic membership plane (docs/OPERATIONS.md elastic
        # operations): drain state-machine counters plus join warm-up
        # heat-ordering/byte-verify counters — zeros from scrape one;
        # the drain gauges stay live on every node via record gossip
        text += prometheus_block(self.api.elastic_metrics(), prefix,
                                 seen=seen)
        # write-path durability (group-commit WAL): zeros from scrape
        # one, same rate()-window reasoning as the blocks around it
        text += prometheus_block(self.api.durability_metrics(), prefix,
                                 "wal", seen=seen)
        # CDC plane (docs/OPERATIONS.md Replication & CDC): tailer
        # liveness + per-peer lag, invalidation/resync counters,
        # follower staleness and applied ops — producer-side tail
        # counters ride the wal block above; zeros while CDC is off
        text += prometheus_block(self.api.cdc_metrics(), prefix,
                                 seen=seen)
        # storage-integrity plane (docs/OPERATIONS.md integrity
        # runbook): degraded latch, verified-load/quarantine counters,
        # scrubber progress — zeros from scrape one like the rest
        text += prometheus_block(self.api.integrity_metrics(), prefix,
                                 seen=seen)
        # host-path roaring kernels (docs/OPERATIONS.md host-path
        # kernels): batched decode/set-op call counts and materialized
        # id volume — zeros from scrape one; a flat kernel_calls rate
        # under load means traffic is all residency hits
        from pilosa_tpu.roaring.kernels import global_kernel_stats

        text += prometheus_block(global_kernel_stats().metrics(), prefix,
                                 seen=seen)
        # write-path fast lane (docs/OPERATIONS.md): whole-batch merge
        # kernel counters + range-aware write-routing counters — zeros
        # from scrape one; loop_fallbacks rising under bulk load means
        # batches are arriving below the kernel cutover size
        from pilosa_tpu.parallel.cluster import global_route_stats
        from pilosa_tpu.roaring.merge_kernels import global_merge_stats

        text += prometheus_block(global_merge_stats().metrics(), prefix,
                                 seen=seen)
        text += prometheus_block(global_route_stats().metrics(), prefix,
                                 seen=seen)
        # a mesh executor's reductions (docs/OPERATIONS.md multi-chip
        # mesh): dispatches and the bytes they move between chips, from
        # the programs' static shapes; zeros on a one-device server
        from pilosa_tpu.parallel.dist import global_reduce_stats

        text += prometheus_block(global_reduce_stats().snapshot(), prefix,
                                 "dist_reduce", seen=seen)
        # serving-QoS series (admission/deadline/hedge/breaker): emitted
        # from scrape one, zeros included, for the same rate()-window
        # reason as the wave counters above
        text += prometheus_block(self.api.qos.metrics(), prefix, "qos",
                                  seen=seen)
        # observability plane: trace sampling counters, in-flight
        # inspector gauges, and the slow-query ring's counter
        text += prometheus_block(self.api.observability_metrics(), prefix,
                                  seen=seen)
        # the stage sites' always-on counters (docs/OBSERVABILITY.md
        # "Stages"): rate(stage_<x>_seconds_total) / rate(stage_<x>_total)
        # is the mean time a request spends in layer x; every stage
        # present from scrape one. Then the device block: programs made
        # executable (compiled or loaded from the persistent cache) and
        # device memory as memory_stats() gives it now.
        from pilosa_tpu.utils.tracing import (
            device_memory_by_device,
            device_metrics,
            groupby_metrics,
            plan_metrics,
            stage_metrics,
            thread_metrics,
        )

        text += prometheus_block(stage_metrics(), prefix, "stage",
                                 seen=seen)
        # CPU seconds of the three thread roles of the served path and of
        # the whole process: process less the roles is CPU no Python
        # thread of the served path spent
        text += prometheus_block(thread_metrics(), prefix, seen=seen)
        text += prometheus_block(groupby_metrics(), prefix, "groupby",
                                 seen=seen)
        text += prometheus_block(plan_metrics(), prefix, "plan", seen=seen)
        text += prometheus_block(device_metrics(), prefix, "device",
                                 seen=seen)
        for d in device_memory_by_device():
            tag = f'{{device="{d["device"]}"}}'
            text += (f"{prefix}_device_memory_bytes_in_use{tag} "
                     f"{d['bytes_in_use']}\n"
                     f"{prefix}_device_memory_peak_bytes{tag} "
                     f"{d['peak_bytes']}\n")
        # partition-tolerance plane (docs/OPERATIONS.md failure model):
        # epoch, quorum/degraded gauges, heartbeat + fencing counters
        text += prometheus_block(self.api.cluster_metrics(), prefix,
                                  seen=seen)
        # query cost plane (docs/OBSERVABILITY.md): per-tenant usage
        # accounting, per-shard heat, and SLO burn-rate gauges — tagged
        # series are cardinality-capped (full tables live on their
        # /debug endpoints)
        from pilosa_tpu.storage.heat import global_heat

        text += self.api.cost.prometheus_lines(prefix, seen=seen)
        text += global_heat().prometheus_lines(prefix, seen=seen)
        text += self.api.slo.prometheus_lines(prefix, seen=seen)
        self._text(text, "text/plain; version=0.0.4")

    def get_faults(self, query=None):
        """Installed fault-injection rules + hit counters
        (testing/faults.py — docs/OPERATIONS.md failure model)."""
        from pilosa_tpu.testing import faults

        plane = faults.active()
        if plane is None:
            self._json({"enabled": False, "rules": []})
            return
        self._json({"enabled": True, **plane.snapshot()})

    def post_faults(self, query=None):
        """Program the fault plane over HTTP: ``{"rules": [{action, src,
        dst, route, delayMs, status, count}, ...]}`` installs rules
        (creating the plane on first use), ``{"heal": true}`` removes
        every drop rule, ``{"clear": true}`` removes all rules. The
        serving node registers its own name→endpoint mapping when the
        plane appears, so rules can target node names."""
        from pilosa_tpu.testing import faults

        body = self._json_body()
        plane = faults.active()
        if plane is None:
            plane = faults.install()
        if self.api.cluster is not None:
            # register EVERY known member's name→endpoint (from the
            # advertised URIs peers actually dial): rules written
            # against node names must match traffic toward REMOTE
            # nodes too, not only the serving node — a dst="n1" rule
            # posted to n0 is otherwise a silent no-op
            for node in self.api.cluster.sorted_nodes():
                plane.name_endpoint(node.id,
                                    node.uri.split("://", 1)[-1])
        if body.get("clear"):
            plane.clear_rules()
        if body.get("heal"):
            plane.heal()
        installed = []
        for spec in body.get("rules", []):
            try:
                rule = plane.add(
                    spec.get("action", ""),
                    src=spec.get("src", "*"),
                    dst=spec.get("dst", "*"),
                    route=spec.get("route", "*"),
                    delay_ms=float(spec.get("delayMs", 0.0)),
                    status=int(spec.get("status", 503)),
                    count=(int(spec["count"])
                           if spec.get("count") is not None else None),
                )
            except (ValueError, TypeError) as e:
                raise ApiError(f"invalid fault rule {spec!r}: {e}") from e
            installed.append(rule.id)
        self._json({"installed": installed, **plane.snapshot()})

    def delete_faults(self, query=None):
        """Clear every rule and uninstall the plane — the wire is
        guaranteed clean afterwards (the zero-overhead off state)."""
        from pilosa_tpu.testing import faults

        faults.clear()
        self._json({"enabled": False})

    def get_traces(self, query=None):
        from pilosa_tpu.utils.tracing import global_tracer

        tracer = global_tracer()
        self._json({"enabled": tracer.sample_rate > 0.0,
                    "sampleRate": tracer.sample_rate,
                    "traces": tracer.recent()})

    def get_tenants(self, query=None):
        """Per-(tenant, index) usage accounting + top-K offender view
        (``?k=10&by=device_ms`` — docs/OBSERVABILITY.md)."""
        k = _int_param((query.get("k") or ["10"])[0], "k") if query else 10
        if k <= 0:
            # a negative k flows into a Python slice and would return
            # the table MINUS its top offenders — the inverse view
            raise ApiError(f"k must be positive, got {k}")
        by = (query.get("by") or ["device_ms"])[0] if query else "device_ms"
        try:
            self._json(self.api.tenants_json(k=k, by=by))
        except ValueError as e:
            raise ApiError(str(e)) from e

    def get_heatmap(self, query=None):
        """Decayed per-(index, field, shard) access/write heat with the
        HBM-residency overlay (``?k=100`` caps rows) — the promote/
        demote signal for residency tiering (docs/OBSERVABILITY.md).

        ``?tier=true`` adds the tiering manager's world view beside the
        raw heat: each row gains its current tier (resident /
        compressed / host / cold), per-tier bytes, and the last pass's
        decision (promoted / demoted / hold / ...) — so an operator can
        see WHY a shard was demoted, not just that it is cold."""
        from pilosa_tpu.storage.heat import global_heat

        k = _int_param((query.get("k") or ["100"])[0], "k") if query else 100
        if k < 0:
            raise ApiError(f"k must be non-negative, got {k}")
        # k=0 = the FULL table (snapshot's own convention): the autopilot
        # coordinator's peer fetch (client.heatmap) needs every row — a
        # capped view would hide heat and silently blank the plan
        snap = global_heat().snapshot(k=k)
        if query and query.get("tier", ["false"])[0] == "true":
            from pilosa_tpu.storage.residency import global_row_cache

            per_frag, per_stack = global_row_cache().tier_overlay()
            tierer = self.api.tierer
            decisions = (tierer.last_decisions()
                         if tierer is not None else {})

            def label(tiers):
                if tiers["dense"] + tiers["compressed"] > 0:
                    return "resident" if tiers["dense"] else "compressed"
                return "host"

            for r in snap["shards"]:
                fkey = (r.get("scope", ""), r["index"], r["field"],
                        r["shard"])
                tiers = per_frag.get(fkey)
                stiers = per_stack.get(fkey[:3])
                if tiers is not None:
                    r["tier"] = label(tiers)
                    r["tierBytes"] = tiers
                elif stiers is not None:
                    # stacked leaves tier at field granularity: every
                    # shard of the field shows the leaf's tier
                    r["tier"] = label(stiers)
                    r["stackTierBytes"] = stiers
                else:
                    r["tier"] = "cold"
                d = decisions.get(fkey, decisions.get(fkey[:3]))
                if d is not None:
                    r["tierDecision"] = d
            snap["tiering"] = (tierer.to_json() if tierer is not None
                               else {"enabled": False})
        self._json(snap)

    def get_rescache(self, query=None):
        """Result-cache inspector (``?k=100`` caps entries): the entry
        table hottest-first with per-entry decayed score, hits, bytes,
        and dependency fields, plus totals — docs/OPERATIONS.md skewed-
        traffic runbook, step one for a hot-tenant p99 regression."""
        k = _int_param((query.get("k") or ["100"])[0], "k") if query else 100
        if k <= 0:
            raise ApiError(f"k must be positive, got {k}")
        self._json(self.api.rescache_json(k=k))

    def get_autopilot(self, query=None):
        """Autopilot inspector (docs/OPERATIONS.md autopilot runbook):
        planner config + pass counters, the live placement-override
        table, and the recent decision log — or just the adopted table
        when the planner is off on this node (kill switch gates the
        ticker, not table adoption)."""
        autopilot = self.api.autopilot
        if autopilot is not None:
            self._json(autopilot.to_json())
            return
        placement = getattr(self.api.cluster, "placement", None)
        self._json({
            "enabled": False,
            "placement": (placement.to_json() if placement is not None
                          else {"epoch": 0, "overrides": []}),
        })

    def get_elastic(self, query=None):
        """Elastic-plane inspector (docs/OPERATIONS.md elastic
        operations): the drain state machine record, join warm-up
        counters, and the range-keyed placement table — readable on
        every node because the drain record gossips with the epoch."""
        self._json(self.api.elastic_json())

    def post_drain(self, node, query=None):
        """Start a coordinator-driven graceful drain of ``node``:
        mints an epoch, moves every shard group the target owns, hands
        off its CDC cursors, then removes it from the ring."""
        self._body()  # drain unread bytes: keep-alive reuse
        self._json(self.api.drain_start(node))

    def delete_drain(self, query=None):
        """Abort the in-flight drain (coordinator only): stamps the
        record aborted so the worker stops at its next state check."""
        self._body()
        self._json(self.api.drain_abort())

    def get_drain(self, query=None):
        """Drain state machine record plus active/draining flags."""
        self._json(self.api.drain_status())

    def get_slo(self, query=None):
        """Declared objectives with per-window burn rates and breach
        flags (docs/OBSERVABILITY.md)."""
        self._json(self.api.slo.to_json())

    def get_workers(self, query=None):
        """Multi-process serving worker table (docs/OPERATIONS.md
        deployment shapes): one row per SO_REUSEPORT worker with
        generation, pid, liveness, ring depth, and the worker-reported
        ring round-trip quantiles."""
        self._json(self.api.workers_json())

    def get_inflight_queries(self, query=None):
        """Live queries on this node (upstream's long-running-query
        view): trace id, PQL, index, age, current stage, shards
        outstanding — see docs/OBSERVABILITY.md."""
        from pilosa_tpu.utils.tracing import global_query_tracker

        tracker = global_query_tracker()
        self._json({"queries": tracker.snapshot(),
                    "trackedTotal": tracker.started_total})

    def get_long_queries(self, query=None):
        self._json({"threshold": self.api.long_query_time,
                    "total": self.api.slow_queries_total,
                    "queries": list(self.api.long_queries)})

    def post_trace_device(self, query=None):
        """Live JAX profiler capture around real traffic:
        ``POST /debug/trace-device?secs=N`` writes an xprof/tensorboard
        trace into the configured log dir (trace-log-dir knob)."""
        self._body()  # drain: unread bytes would corrupt keep-alive reuse
        raw = (query.get("secs") or ["1"])[0] if query else "1"
        try:
            secs = float(raw)
        except ValueError as e:
            raise ApiError(f"invalid secs parameter {raw!r}") from e
        self._json(self.api.start_device_trace(secs))

    def get_debug_vars(self, query=None):
        from pilosa_tpu.storage.residency import global_row_cache
        from pilosa_tpu.utils.stats import global_stats

        snap = global_stats().snapshot()
        cache = global_row_cache()
        snap["residency"] = dict(cache.metrics(),
                                 residency_device_bytes=cache.device_bytes())
        snap["serving_pipeline"] = self.api.pipeline_metrics()
        snap["qos"] = self.api.qos.metrics()
        fastlane = self.api.fastlane_metrics()
        lock = getattr(self.server, "metrics_lock", None)
        if lock is not None:
            with lock:
                fastlane["http_connections_total"] = \
                    self.server.connections_opened
                fastlane["http_requests_total"] = self.server.requests_served
        snap["serving_fastlane"] = fastlane
        snap["serving_mp"] = self.api.mp_metrics()
        snap["result_cache"] = self.api.rescache_metrics()
        snap["residency_tiering"] = self.api.tiering_metrics()
        snap["autopilot"] = self.api.autopilot_metrics()
        snap["elastic"] = self.api.elastic_metrics()
        snap["durability"] = self.api.durability_metrics()
        snap["cdc"] = self.api.cdc_metrics()
        snap["integrity"] = self.api.integrity_metrics()
        snap["observability"] = self.api.observability_metrics()
        from pilosa_tpu.utils.tracing import (
            device_metrics,
            groupby_metrics,
            plan_metrics,
            stage_metrics,
            thread_metrics,
        )

        snap["stages"] = stage_metrics()
        snap["threads"] = thread_metrics()
        snap["groupby"] = groupby_metrics()
        snap["plan"] = plan_metrics()
        snap["device"] = device_metrics()
        from pilosa_tpu.parallel.dist import global_reduce_stats

        snap["dist_reduce"] = global_reduce_stats().snapshot()
        from pilosa_tpu.storage.heat import global_heat

        snap["tenants"] = self.api.cost.metrics()
        snap["heat"] = global_heat().metrics()
        snap["slo"] = self.api.slo.metrics()
        snap["cluster"] = self.api.cluster_metrics()
        self._json(snap)

    def get_pprof(self, query=None):
        """Thread stack dump (the /debug/pprof role for a python server)."""
        import sys
        import threading
        import traceback

        names = {t.ident: t.name for t in threading.enumerate()}
        out = []
        for ident, frame in sys._current_frames().items():
            out.append(f"--- thread {names.get(ident, ident)} ---")
            out.extend(line.rstrip() for line in traceback.format_stack(frame))
        self._text("\n".join(out))

    def get_export(self, query=None):
        index = (query.get("index") or [""])[0]
        field = (query.get("field") or [""])[0]
        if not index or not field:
            raise ApiError("export requires index= and field=")
        self._text(self.api.export_csv(index, field), "text/csv")

    def get_shards_max(self, query=None):
        self._json(self.api.max_shards())

    def get_fragment_blocks(self, query=None):
        index = (query.get("index") or [""])[0]
        field = (query.get("field") or [""])[0]
        view = (query.get("view") or ["standard"])[0]
        shard = _int_param((query.get("shard") or ["0"])[0], "shard")
        idx = self.api._index(index)
        fld = self.api._field(idx, field)
        v = fld.view(view)
        frag = v.fragment(shard) if v else None
        blocks = frag.blocks() if frag else []
        self._json({"blocks": [{"block": b, "checksum": c} for b, c in blocks]})

    def get_fragment_nodes(self, query=None):
        """Which nodes own a shard (reference /internal/fragment/nodes —
        clients use it to route imports/queries directly to owners)."""
        index = (query.get("index") or [""])[0]
        shard_param = (query.get("shard") or [None])[0]
        if shard_param is None:
            raise ApiError("shard param required", 400)
        col_param = (query.get("col") or [None])[0]
        self._json(self.api.shard_nodes(
            index, _int_param(shard_param, "shard"),
            col=(_int_param(col_param, "col")
                 if col_param is not None else None)))

    def get_fragment_data(self, query=None):
        index = (query.get("index") or [""])[0]
        field = (query.get("field") or [""])[0]
        view = (query.get("view") or ["standard"])[0]
        shard = _int_param((query.get("shard") or ["0"])[0], "shard")
        idx = self.api._index(index)
        fld = self.api._field(idx, field)
        v = fld.view(view)
        frag = v.fragment(shard) if v else None
        data = frag.serialize_snapshot() if frag else b""
        # whole-fragment resize payloads honor Accept-Encoding: deflate
        # (the repair client's repair-compression knob)
        self._bytes_negotiated(data)

    def get_sync_manifest(self, query=None):
        """Batched anti-entropy manifest: every (field, view, shard) →
        checksum-block list of one index in ONE response, so a repair
        pass diffs the whole index against this node in one RTT instead
        of one /internal/fragment/blocks GET per fragment. Protobuf by
        Accept negotiation, JSON fallback (the 406 dance the query path
        uses)."""
        from pilosa_tpu.storage.fragment import build_index_manifest
        from pilosa_tpu.utils.stats import global_stats
        from pilosa_tpu.utils.tracing import TRACE_HEADER, global_tracer

        index = (query.get("index") or [""])[0]
        # a traced repair pass stitches the serving-side cost into the
        # coordinator's tree via this node's local /debug/traces (the
        # subtree stays here — manifest responses are binary/protobuf)
        trace_cm = global_tracer().remote_root(
            self.headers.get(TRACE_HEADER), "rpc.sync-manifest",
            node=self.api.node_id(), index=index,
        )
        with trace_cm:
            # An unknown index answers an EMPTY manifest, not 404:
            # sync-wise this node simply holds nothing for it (a schema
            # broadcast may not have landed yet), and a 404 here would be
            # misread by peers as "route missing" — permanently demoting
            # this node to the per-fragment legacy path. The legacy
            # catalog walk treated the same condition as "no fragments"
            # too (ClientError → []).
            idx = self.api.holder.index(index)
            entries = build_index_manifest(idx) if idx is not None else []
            global_stats().count("sync_manifest_served", 1)
            if "application/x-protobuf" in (self.headers.get("Accept")
                                            or ""):
                from pilosa_tpu import wire

                if not wire.available():
                    raise ApiError("protobuf wire format unavailable", 406)
                from pilosa_tpu.wire.serializer import encode_sync_manifest

                self._raw(encode_sync_manifest(entries),
                          "application/x-protobuf")
                return
            self._json({"fragments": [
                {"field": f, "view": v, "shard": s,
                 "blocks": [{"block": b, "checksum": c}
                            for b, c in blocks]}
                for f, v, s, blocks in entries
            ]})

    def post_sync_blocks(self, query=None):
        """Multi-block delta fetch: the body lists every wanted checksum
        block per fragment (protobuf SyncBlocksRequest or JSON); the
        response streams the blocks back as length-prefixed roaring
        payloads in request order — one POST replaces one
        /internal/fragment/block/data GET per differing block. The data
        plane stays raw roaring bytes whichever control encoding was
        negotiated; Accept-Encoding: deflate compresses the framed
        stream."""
        from pilosa_tpu.roaring import RoaringBitmap
        from pilosa_tpu.roaring.format import serialize
        from pilosa_tpu.utils.stats import global_stats
        from pilosa_tpu.utils.tracing import TRACE_HEADER, global_tracer
        from pilosa_tpu.wire.serializer import encode_block_frames

        trace_cm = global_tracer().remote_root(
            self.headers.get(TRACE_HEADER), "rpc.sync-blocks",
            node=self.api.node_id(),
        )
        raw = self._body()
        if "application/x-protobuf" in (
                self.headers.get("Content-Type") or ""):
            from pilosa_tpu import wire

            if not wire.available():
                raise ApiError("protobuf wire format unavailable", 406)
            from pilosa_tpu.wire.serializer import (
                decode_sync_blocks_request,
            )

            index, fragments = decode_sync_blocks_request(raw)
        else:
            try:
                body = json.loads(raw or b"{}")
            except json.JSONDecodeError as e:
                raise ApiError(f"invalid JSON body: {e}") from e
            index = body.get("index", "")
            fragments = [
                (e.get("field", ""), e.get("view", "standard"),
                 _int_param(str(e.get("shard", 0)), "shard"),
                 [_int_param(str(b), "block")
                  for b in e.get("blocks", [])])
                for e in body.get("fragments", [])
            ]
        # unknown index/field answer empty bitmaps, not 404, for the
        # same reason as the manifest route: a domain 404 would be
        # misread as "route missing" and demote the peer to the legacy
        # path for the process lifetime — and an empty payload is the
        # correct sync answer for data this node doesn't hold
        with trace_cm:
            idx = self.api.holder.index(index)
            payloads = []
            for fname, vname, shard, blocks in fragments:
                fld = idx.field(fname) if idx is not None else None
                v = fld.view(vname) if fld is not None else None
                frag = v.fragment(shard) if v else None
                if frag is None:
                    payloads.extend(
                        serialize(RoaringBitmap.from_ids([]))
                        for _ in blocks)
                    continue
                # one flatten + one id kernel + one boundary search for
                # ALL requested blocks (fragment.blocks_ids) — the old
                # loop re-materialized the whole fragment per block
                by_block = frag.blocks_ids(blocks)
                payloads.extend(
                    serialize(RoaringBitmap.from_ids(by_block[int(b)]))
                    for b in blocks)
            global_stats().count("sync_delta_blocks_served", len(payloads))
            self._bytes_negotiated(encode_block_frames(payloads))

    def get_wal_tail(self, query=None):
        """Resumable CDC tail over the committed WAL (docs/OPERATIONS.md
        Replication & CDC): ``?since=N`` streams seq-framed WAL records
        with seq > N in commit order (cdc/feed.py frame layout);
        ``since`` absent is the attach handshake — registers the named
        ``cursor`` at the durable seq, empty body. ``max-bytes`` caps
        one response (the producer stops at a group boundary and the
        Next-Seq header tells the consumer where to resume). A cursor
        behind the retained tail answers 410 ``{"restartFrom",
        "floor"}`` — restart from a snapshot. Frames honor
        Accept-Encoding: deflate like the sync routes; positions ride
        response headers so the body stays a pure frame stream."""
        from pilosa_tpu.cdc.feed import (
            DURABLE_SEQ_HEADER,
            NEXT_SEQ_HEADER,
            TailGone,
            encode_events,
        )

        since_raw = (query.get("since") or [None])[0] if query else None
        since = (_int_param(since_raw, "since")
                 if since_raw is not None else None)
        mb_raw = (query.get("max-bytes") or [None])[0] if query else None
        max_bytes = (_int_param(mb_raw, "max-bytes")
                     if mb_raw is not None else 1 << 20)
        if max_bytes <= 0:
            raise ApiError(f"max-bytes must be positive, got {max_bytes}")
        cursor = (query.get("cursor") or [""])[0] if query else ""
        try:
            events, next_seq, durable = self.api.wal_tail(
                since, max_bytes=max_bytes, cursor=cursor or None)
        except TailGone as e:
            # 410 Gone, the resumability contract's hard edge: the JSON
            # body carries where to restart so a consumer needn't parse
            # the floor out of the error string
            self._json({"error": str(e), "restartFrom": e.restart_from,
                        "floor": e.floor}, status=410)
            return
        self._bytes_negotiated(encode_events(events), {
            NEXT_SEQ_HEADER: str(next_seq),
            DURABLE_SEQ_HEADER: str(durable),
        })

    def get_shards_list(self, query=None):
        index = (query.get("index") or [""])[0]
        idx = self.api._index(index)
        self._json({"shards": idx.available_shards()})

    def get_fragment_block_data(self, query=None):
        """One checksum block's bits as a roaring-serialized octet-stream.
        The reference moves block data as protobuf bodies (SURVEY.md §2
        #16-17); JSON int lists here cost ~20 bytes/bit, which makes
        dense-block repair two orders of magnitude larger than the data."""
        from pilosa_tpu.roaring import RoaringBitmap
        from pilosa_tpu.roaring.format import serialize

        index = (query.get("index") or [""])[0]
        field = (query.get("field") or [""])[0]
        view = (query.get("view") or ["standard"])[0]
        shard = _int_param((query.get("shard") or ["0"])[0], "shard")
        block = _int_param((query.get("block") or ["0"])[0], "block")
        idx = self.api._index(index)
        fld = self.api._field(idx, field)
        v = fld.view(view)
        frag = v.fragment(shard) if v else None
        ids = frag.block_ids(block) if frag is not None else []
        data = serialize(RoaringBitmap.from_ids(ids))
        self._bytes(data)

    def get_fragments_catalog(self, query=None):
        """Every (field, view, shard) fragment of an index — drives resize
        fetches and anti-entropy enumeration."""
        index = (query.get("index") or [""])[0]
        idx = self.api._index(index)
        out = []
        for fname, fld in sorted(idx.fields.items()):
            for vname, view in sorted(fld.views.items()):
                for shard in sorted(view.fragments):
                    out.append({"field": fname, "view": vname, "shard": shard})
        self._json({"fragments": out})

    def _attr_store(self, query):
        index = (query.get("index") or [""])[0]
        field = (query.get("field") or [""])[0]
        idx = self.api._index(index)
        if not field:
            return idx.column_attrs
        return self.api._field(idx, field).row_attrs

    def get_attr_blocks(self, query=None):
        store = self._attr_store(query)
        self._json({"blocks": [
            {"block": b, "checksum": c} for b, c in (store.blocks() if store else [])
        ]})

    def get_attr_block_data(self, query=None):
        store = self._attr_store(query)
        block = _int_param((query.get("block") or ["0"])[0], "block")
        self._json({"attrs": store.block_data(block) if store else {}})

    def post_translate_keys(self, query=None):
        body = self._json_body()
        ids = self.api.holder.translate.translate(
            body.get("namespace", ""), body.get("keys", []),
            create=bool(body.get("create", False)),
        )
        self._json({"ids": ids})

    def get_translate_data(self, query=None):
        offset = _int_param((query.get("offset") or ["0"])[0], "offset")
        data = self.api.holder.translate.read_log(offset)
        self._bytes(data)

    def post_cluster_message(self, query=None):
        body = self._json_body()
        if self.api.cluster is None:
            self._json({})
            return
        self._json(self.api.cluster.handle_message(body))


def _int_param(value: str, name: str) -> int:
    try:
        return int(value)
    except ValueError as e:
        raise ApiError(f"invalid {name} parameter {value!r}") from e


class PilosaHTTPServer(ThreadingHTTPServer):
    # socketserver's default listen backlog (5) resets connections under
    # a concurrent client wave — exactly the traffic shape the coalescing
    # query pipeline exists to serve (server/pipeline.py).
    request_queue_size = 128
    # disable_nagle_algorithm: responses go out as a header write + a
    # body write; without TCP_NODELAY the second small packet can sit
    # behind Nagle/delayed-ACK interplay on real networks
    disable_nagle_algorithm = True

    def __init__(self, *args, **kwargs):
        # counters/registry exist BEFORE bind: TCPServer.__init__ calls
        # server_close on a bind failure (port in use), which walks the
        # registry — post-construction assignment would turn that into
        # an AttributeError masking the real bind error
        self.metrics_lock = threading.Lock()
        self.connections_opened = 0
        self.requests_served = 0
        self.open_connections = set()
        super().__init__(*args, **kwargs)

    def server_close(self):
        super().server_close()
        # Hard-close ESTABLISHED keep-alive connections too: closing only
        # the listener leaves handler threads serving old peers' pooled
        # connections indefinitely — a closed node must look DEAD to the
        # cluster (peers' pools see EOF, reconnect, get refused, degrade),
        # exactly like a crashed process whose sockets the kernel reset.
        import socket as _socket

        with self.metrics_lock:
            conns = list(self.open_connections)
            self.open_connections.clear()
        for sock in conns:
            try:
                sock.shutdown(_socket.SHUT_RDWR)
            except OSError:
                pass
            try:
                sock.close()
            except OSError:
                pass


def make_http_server(api: API, bind: str = "localhost", port: int = 10101):
    handler = type("BoundHandler", (HTTPHandler,), {"api": api})
    return PilosaHTTPServer((bind, port), handler)


def serve_in_thread(api: API, bind: str = "localhost", port: int = 0):
    """Start a server on an ephemeral port; returns (server, port, thread).
    The in-process equivalent of the reference's test.MustRunCluster node."""
    server = make_http_server(api, bind, port)
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    return server, server.server_address[1], thread
