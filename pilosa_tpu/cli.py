"""CLI: server / import / export / config / inspect / check / version.

Reference: cmd/pilosa + ctl/ (SURVEY.md §2 #28–30) — cobra subcommands with
TOML-config < env < flag precedence. Here: argparse with the same
precedence (PILOSA_TPU_* env vars), talking either to a running server
over HTTP (--host) or directly to a data dir in-process (--data-dir),
which is the TPU-friendly path for bulk imports (no HTTP hop).
"""

from __future__ import annotations

import argparse
import http.client
import json
import os
import sys
import urllib.request

from pilosa_tpu import __version__

DEFAULT_HOST = "http://localhost:10101"

_DEFAULT_TOML = """\
# pilosa-tpu server configuration. Precedence: flags > PILOSA_TPU_* env
# vars > this file > defaults (env var names: key uppercased, dashes ->
# underscores, e.g. PILOSA_TPU_ANTI_ENTROPY_INTERVAL).
data-dir = "~/.pilosa_tpu"
bind = "localhost"
port = 10101
# name = "node-<port>"        # stable node id in the cluster
# advertise = ""              # URI peers should use (default: bind:port)
# seeds = ["http://host:10101"]  # join an existing cluster via any member
replica-n = 1                 # replicas per shard
anti-entropy-interval = 600.0 # seconds; 0 disables the repair ticker
heartbeat-interval = 5.0      # seconds; 0 disables death detection
heartbeat-timeout = 2.0       # tight per-probe timeout for liveness
                              # checks (heartbeat, quorum, death
                              # corroboration) — a hung peer must not
                              # stall detection of other failures
# use-mesh = true             # force the device-mesh executor (default:
                              # auto - mesh when >1 JAX device)
# device-budget-bytes = 0     # HBM residency budget PER CHIP (an entry is
                              # charged what it holds on the fullest
                              # chip: a leaf sharded over a mesh costs its
                              # shard); 0 = measure: 3/4 of the
                              # smallest local chip's memory limit
                              # (~11.8 GiB of a v5e's 16), 4 GiB where
                              # the backend reports none (CPU)
long-query-time = 0.0         # log queries slower than this; 0 = off
max-writes-per-request = 5000 # reject larger write batches; 0 = unlimited
ingest-workers = 1            # local shard-group apply pool per import
                              # batch; raise where fragment writes pay real
                              # disk latency (docs/INGEST.md)

# Serving fast lane (docs/OPERATIONS.md): keep-alive pooling + batching
client-pool-size = 8          # keep-alive connections retained per peer
remote-batch = true           # coalesce same-node remote sub-queries onto
                              # /internal/query-batch (false = per-query)

# Multi-process serving tier (docs/OPERATIONS.md deployment shapes):
# shatters the single-interpreter serving ceiling with N SO_REUSEPORT
# worker processes fronting this (device-owner) process over
# shared-memory rings; requires SO_REUSEPORT (Linux), falls back to
# single-process otherwise
serving-workers = 0           # worker processes; 0 = single-process
ring-slots = 1024             # slots per ring direction per worker
ring-slot-bytes = 65536       # bytes per slot (large responses span
                              # consecutive slots)

# Skewed traffic (docs/OPERATIONS.md): write-invalidated result cache +
# heat-driven HBM residency tiering — the actuators on the heat plane
result-cache-bytes = 0        # pre-serialized hot-query response bytes
                              # kept across waves, invalidated at every
                              # (index,field,shard) write; 0 = off
residency-promote-interval = 0.0  # seconds between tiering passes
                              # (demote cold fragments to the compressed
                              # host tier, promote hot ones back); 0 = off
residency-promote-heat = 4.0  # heat above which host-tier fragments
                              # promote to device residency
residency-demote-heat = 1.0   # heat below which device-resident
                              # fragments demote host-side; the gap to
                              # promote-heat is the hysteresis dead band
residency-host-tier-bytes = 1073741824  # compressed host-tier budget

# Autopilot placement plane (docs/OPERATIONS.md autopilot): the
# coordinator periodically rebalances the hottest (index,shard) groups
# off overloaded nodes via epoch-fenced placement overrides + resize.
# The kill switch gates only the planner — overrides minted elsewhere
# are still honored by every node, keeping placement consistent.
autopilot-enabled = false     # master kill switch for the planner ticker
autopilot-interval = 30.0     # seconds between planner passes
autopilot-heat-budget = 1.5   # per-node heat ceiling as a multiple of
                              # mean node heat; the margin over 1.0 is
                              # the hysteresis dead band
autopilot-max-moves = 4       # shard-group moves per pass (further
                              # shaped by repair-max-bytes-per-sec)
autopilot-min-dwell = 0.0     # seconds a moved shard is frozen before
                              # it may move again; 0 = two intervals
autopilot-split-threshold = 0.0  # shard heat above this multiple of
                              # mean node load splits the shard into
                              # sub-shard column ranges; 0 = off
autopilot-split-ways = 2      # ranges a hot shard is split into

# Write-path durability (docs/OPERATIONS.md): what an HTTP 200 on a
# write means
durability-mode = "group"     # group = one fsync per commit group of
                              # concurrent writers (acked = durable);
                              # per-op = fsync every write; flush-only =
                              # legacy r5 behavior (OS buffer only)
group-commit-max-ms = 2.0     # max time a record waits for its group's
                              # fsync to start (bounds write ACK latency)
group-commit-max-ops = 256    # max op records fsynced per group

# Storage integrity (docs/OPERATIONS.md integrity runbook)
verify-on-load = true         # check fragment snapshots against their
                              # .checksums sidecars at open; corrupt
                              # files quarantine (never served) and
                              # read-repair from replicas
scrub-interval = 0.0          # seconds between background scrub passes
                              # over owned fragments' DISK bytes; 0 = off
scrub-max-bytes-per-sec = 0   # token-bucket budget for scrub reads;
                              # 0 = unpaced

# Anti-entropy / resize data plane (docs/OPERATIONS.md)
sync-workers = 8              # fragment diff/fetch/apply pipeline width
                              # per repair pass
repair-max-bytes-per-sec = 0  # token-bucket pacing of repair/resize
                              # transfers; 0 = unpaced
repair-max-inflight = 0       # concurrent repair transfers; 0 = unbounded
repair-compression = true     # zlib Content-Encoding on fragment and
                              # delta payloads (negotiated per peer)

# Replication & CDC (docs/OPERATIONS.md): WAL tail change feed ->
# cluster-safe result caching, stale-bounded read replicas, and
# `restore --as-of <seq>` point-in-time restore
cdc-enabled = false           # tail peers' WAL feeds to invalidate the
                              # result cache cluster-wide (lifts the
                              # single-node-only cache refusal)
cdc-max-retention-bytes = 67108864  # WAL bytes pinned for lagging tail
                              # cursors before they are forced off
                              # (410 Gone -> consumer resyncs)
cdc-poll-interval = "50ms"    # tailer poll cadence (Go duration)
cdc-max-batch-bytes = 1048576 # max event bytes per tail poll
# cdc-follow = ""             # upstream URI: run as a read replica
                              # (non-quorum follower; writes 403)
cdc-staleness-budget = "1s"   # declared follower staleness bound; reads
                              # past it shed 503 (X-Pilosa-Max-Staleness
                              # can tighten per request); 0 = unbounded

# Serving QoS (docs/QOS.md): admission -> deadline -> hedged reads
qos-max-inflight = 0          # concurrent-query cap; excess sheds 429 (0 = off)
qos-tenant-inflight = 0       # per-tenant cap (X-Pilosa-Tenant); 0 = global
qos-default-deadline = 0.0    # server-default request deadline; 0 = none
qos-hedge-delay = 0.25        # hedge trigger before the p95 tracker warms up
qos-hedge-budget = 0.05       # max hedges as a fraction of reads; 0 disables
qos-breaker-threshold = 5     # consecutive faults before a breaker opens
qos-breaker-cooldown = 5.0    # open -> half-open probe interval (seconds)
trace-sample-rate = 0.0       # probabilistic trace sampling: 0 = off
                              # (zero overhead), 0.01 = 1% of requests
                              # root a cross-node span tree on
                              # /debug/traces (docs/OBSERVABILITY.md)
# trace-log-dir = ""          # where POST /debug/trace-device writes JAX
                              # profiler captures (default:
                              # <data-dir>/jax-traces)

# Query cost plane (docs/OBSERVABILITY.md): PROFILE is per-request
# (?profile=true), the ledger/heat surfaces are always on
slow-query-ring = 100         # offenders kept by /debug/queries/slow
                              # (threshold = long-query-time above)
heat-half-life = 300.0        # decay half-life (seconds) of the
                              # per-shard heat counters (/debug/heatmap)
# slo-objectives = ["reads:latency:100ms:0.99", "avail:errors:0.999"]
                              # declarative SLOs; burn rates exported as
                              # slo_* gauges and GET /debug/slo
# slo-windows = ["300s", "3600s"]  # burn-rate evaluation windows
                              # (default: the classic 5m/1h pair)
# statsd = "127.0.0.1:8125"   # statsd UDP sink (Prometheus /metrics is
                              # always on)
# diagnostics-endpoint = ""   # phone-home URL; empty = off
verbose = false

# [tls]
# certificate = "/path/node.crt"
# key = "/path/node.key"
# skip-verify = false         # accept self-signed peer certs
"""


def _load_config(path: str | None) -> dict:
    cfg: dict = {}
    if path:
        import tomllib

        with open(path, "rb") as f:
            cfg = tomllib.load(f)
    # env overrides file: PILOSA_TPU_DATA_DIR → data-dir
    for key, val in os.environ.items():
        if key.startswith("PILOSA_TPU_"):
            cfg[key[len("PILOSA_TPU_"):].lower().replace("_", "-")] = val
    return cfg


_pool = None


def _client_pool():
    """Process-wide keep-alive pool for CLI HTTP calls: every import
    batch (and the --concurrency workers' parallel POSTs) reuses
    persistent connections instead of paying TCP connect per batch —
    the same fast lane the internal node-to-node client rides."""
    global _pool
    if _pool is None:
        from pilosa_tpu.parallel.connpool import ConnectionPool

        _pool = ConnectionPool(max_per_host=16, timeout=300.0)
    return _pool


class _HTTPStatusError(Exception):
    """Non-2xx response through the pooled client (code + body text)."""

    def __init__(self, code: int, detail: str):
        super().__init__(f"HTTP {code}: {detail}")
        self.code = code
        self.detail = detail


def _http(method: str, url: str, data: bytes | None = None,
          content_type: str = "application/json"):
    headers = {"Content-Type": content_type} if data is not None else {}
    resp = _client_pool().request(method, url, body=data, headers=headers)
    if 300 <= resp.status < 400:
        # the pool does not follow redirects (urllib did): surface a
        # clear error instead of feeding an HTML body to json.loads
        location = resp.headers.get("Location", "")
        raise _HTTPStatusError(
            resp.status,
            "redirect" + (f" to {location}" if location else "")
            + " — point --host at the final URL",
        )
    if resp.status >= 400:
        raise _HTTPStatusError(resp.status,
                               resp.data.decode(errors="replace"))
    return json.loads(resp.data or b"{}")


def _iter_csv_bits(files, batch: float):
    """Stream ``row,col[,ts]`` CSVs as (rows, cols, timestamps|None)
    batches of at most ``batch`` lines — whole-file parse lists never
    materialize, so import memory is O(batch), not O(file)."""
    rows, cols, timestamps = [], [], []
    any_ts = False
    for path in files:
        fh = sys.stdin if path == "-" else open(path)
        try:
            for line in fh:
                line = line.strip()
                if not line or line.startswith("#"):
                    continue
                parts = [p.strip() for p in line.split(",")]
                rows.append(int(parts[0]))
                cols.append(int(parts[1]))
                ts = parts[2] if len(parts) > 2 else None
                timestamps.append(ts)
                any_ts = any_ts or ts is not None
                if len(rows) >= batch:
                    yield rows, cols, (timestamps if any_ts else None)
                    rows, cols, timestamps = [], [], []
                    any_ts = False
        finally:
            if fh is not sys.stdin:
                fh.close()
    if rows:
        yield rows, cols, (timestamps if any_ts else None)


def _iter_csv_values(files, batch: float):
    """Stream ``col,value`` CSVs as (cols, vals) batches (see
    _iter_csv_bits)."""
    cols, vals = [], []
    for path in files:
        fh = sys.stdin if path == "-" else open(path)
        try:
            for line in fh:
                line = line.strip()
                if not line or line.startswith("#"):
                    continue
                parts = [p.strip() for p in line.split(",")]
                cols.append(int(parts[0]))
                vals.append(int(parts[1]))
                if len(cols) >= batch:
                    yield cols, vals
                    cols, vals = [], []
        finally:
            if fh is not sys.stdin:
                fh.close()
    if cols:
        yield cols, vals


def _parse_csv_bits(files):
    """Whole-file form of _iter_csv_bits (small inputs, tests)."""
    return next(_iter_csv_bits(files, float("inf")), ([], [], None))


def _parse_csv_values(files):
    return next(_iter_csv_values(files, float("inf")), ([], []))


def cmd_server(args) -> int:
    from pilosa_tpu.utils import compile_cache

    compile_cache.configure()  # before the first compile of the process
    from pilosa_tpu.server import Server, ServerConfig

    cfg_dict = _load_config(args.config)
    config = ServerConfig.from_dict(cfg_dict)
    if args.data_dir:
        config.data_dir = args.data_dir
    if args.bind:
        config.bind = args.bind
    if args.port is not None:
        config.port = args.port
    if args.verbose:
        config.verbose = True
    server = Server(config).open()
    try:
        import signal
        import threading

        stop = threading.Event()
        signal.signal(signal.SIGINT, lambda *a: stop.set())
        signal.signal(signal.SIGTERM, lambda *a: stop.set())
        stop.wait()
    finally:
        server.close()
    return 0


def cmd_serve_worker(args) -> int:
    """Hidden entry for one SO_REUSEPORT serving worker process —
    spawned by the device owner's OwnerRuntime with an inherited
    listening socket, never run by hand (serving/mpserve.py)."""
    from pilosa_tpu.serving.worker import worker_main

    return worker_main(args.handshake_sock, args.listen_fd,
                       args.worker_id)


def _in_process_api(data_dir: str):
    from pilosa_tpu.server.api import API
    from pilosa_tpu.storage import Holder

    return API(Holder(data_dir).open())


DEFAULT_IMPORT_BATCH = 100_000


def _probe_batch_limit(host: str) -> int:
    """Server write-batch limit from /status (0 = none advertised). A
    probe failure is fine — the 413 split fallback in _post_import still
    converges on an acceptable size."""
    try:
        st = _http("GET", f"{host}/status")
        return int(st.get("maxWritesPerRequest") or 0)
    except (_HTTPStatusError, OSError, http.client.HTTPException,
            ValueError):
        return 0


def _post_import(host: str, path: str, payload: dict) -> int:
    """POST one import body; on a 413 (server max-writes-per-request
    tighter than the client's batch — e.g. the /status probe failed or
    raced a config change) split the batch in half and retry both
    halves. Returns bits changed."""
    body = json.dumps(payload).encode()
    try:
        return _http("POST", f"{host}{path}", body).get("changed", 0)
    except _HTTPStatusError as e:
        n = len(payload["columns"])
        if e.code == 413 and n > 1:
            lo = {k: (v[: n // 2] if isinstance(v, list) else v)
                  for k, v in payload.items()}
            hi = {k: (v[n // 2:] if isinstance(v, list) else v)
                  for k, v in payload.items()}
            return (_post_import(host, path, lo)
                    + _post_import(host, path, hi))
        raise


def cmd_import(args) -> int:
    if args.data_dir:
        api = _in_process_api(args.data_dir)
        if args.create:
            if api.holder.index(args.index) is None:
                api.create_index(args.index)
            if api.holder.index(args.index).field(args.field) is None:
                opts = {"type": "int", "min": args.min, "max": args.max} if args.values else {}
                api.create_field(args.index, args.field, opts)
        # streamed batches: O(batch) memory even for huge CSVs (the
        # in-process path has no HTTP limit to clamp against)
        batch = args.batch_size if args.batch_size > 0 else 1_000_000
        n = 0
        if args.values:
            for cols, vals in _iter_csv_values(args.files, batch):
                n += api.import_values(args.index, args.field, cols, vals,
                                       clear=args.clear)
        else:
            for rows, cols, ts in _iter_csv_bits(args.files, batch):
                n += api.import_bits(args.index, args.field, rows, cols,
                                     timestamps=ts, clear=args.clear)
        api.holder.close()
        print(f"imported: {n} bits changed")
        return 0
    # HTTP mode: stream-parse the CSV and pipeline encode→POST — batch
    # N+1 parses on this thread while batch N's POST is in flight
    # (double-buffer); --concurrency > 1 keeps that many POSTs in
    # flight, which the server routes per shard server-side.
    import collections
    from concurrent.futures import ThreadPoolExecutor

    host = args.host.rstrip("/")
    # <= 0 means "auto" (bare `or` would let a negative through, turning
    # every CSV line into its own single-row POST)
    batch = args.batch_size if args.batch_size > 0 else DEFAULT_IMPORT_BATCH
    limit = _probe_batch_limit(host)
    if limit > 0:
        batch = min(batch, limit)
    workers = max(1, args.concurrency)
    if args.values:
        path = f"/index/{args.index}/field/{args.field}/import-value"
        payloads = (
            {"columns": cols, "values": vals, "clear": args.clear}
            for cols, vals in _iter_csv_values(args.files, batch)
        )
    else:
        path = f"/index/{args.index}/field/{args.field}/import"

        def _bit_payloads():
            for rows, cols, ts in _iter_csv_bits(args.files, batch):
                p = {"rows": rows, "columns": cols, "clear": args.clear}
                if ts:
                    p["timestamps"] = ts
                yield p

        payloads = _bit_payloads()
    total = 0
    try:
        if args.create:
            _http_create(host, args)
        inflight: collections.deque = collections.deque()
        with ThreadPoolExecutor(max_workers=workers) as pool:
            for payload in payloads:
                inflight.append(
                    pool.submit(_post_import, host, path, payload)
                )
                while len(inflight) > workers:
                    total += inflight.popleft().result()
            while inflight:
                total += inflight.popleft().result()
    except _HTTPStatusError as e:
        print(f"error: {e}", file=sys.stderr)
        return 1
    except (OSError, http.client.HTTPException) as e:
        # transport-stage failure through the pooled client: connect
        # refused/unreachable, or a server dying mid-stream (reset,
        # RemoteDisconnected on a fresh connection) — same user-facing
        # failure, same exit
        print(f"error: connection to {host} failed: {e}", file=sys.stderr)
        return 1
    print(f"imported: {total} bits changed")
    return 0


def _http_create(host: str, args) -> None:
    """Best-effort schema creation for --create in HTTP mode (409 = exists)."""
    for url, body in (
        (f"{host}/index/{args.index}", {}),
        (
            f"{host}/index/{args.index}/field/{args.field}",
            {"options": {"type": "int", "min": args.min, "max": args.max}}
            if args.values
            else {},
        ),
    ):
        try:
            _http("POST", url, json.dumps(body).encode())
        except _HTTPStatusError as e:
            if e.code != 409:
                raise


def cmd_export(args) -> int:
    if args.data_dir:
        api = _in_process_api(args.data_dir)
        sys.stdout.write(api.export_csv(args.index, args.field))
        api.holder.close()
        return 0
    host = args.host.rstrip("/")
    url = f"{host}/export?index={args.index}&field={args.field}"
    with urllib.request.urlopen(url) as resp:
        sys.stdout.write(resp.read().decode())
    return 0


def cmd_config(args) -> int:
    cfg = _load_config(args.config)
    from pilosa_tpu.server import ServerConfig

    print(json.dumps(ServerConfig.from_dict(cfg).to_dict(), indent=2))
    return 0


def cmd_generate_config(args) -> int:
    print(_DEFAULT_TOML, end="")
    return 0


def cmd_inspect(args) -> int:
    """Dump fragment/container statistics from a data dir (reference
    ctl/inspect.go)."""
    from pilosa_tpu.roaring.bitmap import ARRAY, BITMAP, RUN
    from pilosa_tpu.storage import Holder

    holder = Holder(args.data_dir).open()
    kind_names = {ARRAY: "array", BITMAP: "bitmap", RUN: "run"}
    for iname, idx in sorted(holder.indexes.items()):
        for fname, field in sorted(idx.fields.items()):
            for vname, view in sorted(field.views.items()):
                for shard, frag in sorted(view.fragments.items()):
                    kinds = {"array": 0, "bitmap": 0, "run": 0}
                    for key in frag.bitmap.keys:
                        kinds[kind_names[frag.bitmap.container(key).kind]] += 1
                    print(
                        f"{iname}/{fname}/{vname}/{shard}: "
                        f"bits={frag.count()} rows={len(frag.row_ids())} "
                        f"containers={len(frag.bitmap.keys)} {kinds} "
                        f"ops={frag.op_n}"
                    )
    holder.close()
    return 0


def cmd_backup(args) -> int:
    """Back up to an incremental manifest directory (the default — only
    blocks changed since any previous generation are written; see
    docs/OPERATIONS.md runbook), or to a legacy whole-tree tar.gz when
    the output path ends in .tar.gz/.tgz. ``--host`` backs up a LIVE
    cluster over the anti-entropy wire (compressed, pacer-shaped);
    ``-d`` walks a data dir in-process and must only run against a
    STOPPED node."""
    if args.output.endswith((".tar.gz", ".tgz")):
        import tarfile

        if not args.data_dir:
            print("error: tar.gz backup requires -d/--data-dir",
                  file=sys.stderr)
            return 1
        data_dir = os.path.expanduser(args.data_dir)
        if not os.path.isdir(data_dir):
            print(f"error: no data dir {data_dir}", file=sys.stderr)
            return 1
        with tarfile.open(args.output, "w:gz") as tar:
            tar.add(data_dir, arcname=".")
        print(f"backed up {data_dir} -> {args.output}")
        return 0
    from pilosa_tpu.storage.backup import backup_from_host, backup_holder

    if args.data_dir:
        from pilosa_tpu.storage import Holder

        if not os.path.isdir(os.path.expanduser(args.data_dir)):
            # same validation the tar path always had: a typo'd path
            # must not produce a confidently empty "backup"
            print(f"error: no data dir {args.data_dir}", file=sys.stderr)
            return 1
        holder = Holder(args.data_dir).open()
        try:
            manifest = backup_holder(holder, args.output)
        finally:
            holder.close()
    else:
        from pilosa_tpu.parallel.client import InternalClient

        client = InternalClient(timeout=300.0)
        if args.max_bytes_per_sec > 0:
            # ride the PR-4 repair pacer so a backup storm can't starve
            # the serving traffic of the node it reads from
            from pilosa_tpu.parallel.pacer import RepairPacer

            client.pacer = RepairPacer(
                max_bytes_per_sec=args.max_bytes_per_sec
            )
        try:
            manifest = backup_from_host(args.host, args.output,
                                        client=client)
        except Exception as e:
            print(f"error: backup from {args.host} failed: {e}",
                  file=sys.stderr)
            return 1
    print(
        f"backup generation {manifest['generation']} -> {args.output}: "
        f"{len(manifest['fragments'])} fragments, "
        f"{manifest['newBlobs']} new blobs, "
        f"{manifest['reusedBlobs']} reused"
    )
    return 0


def cmd_restore(args) -> int:
    data_dir = os.path.expanduser(args.data_dir)
    if os.path.isdir(data_dir) and os.listdir(data_dir):
        print(f"error: {data_dir} exists and is not empty", file=sys.stderr)
        return 1
    if os.path.isfile(args.input):  # legacy whole-tree archive
        import tarfile

        os.makedirs(data_dir, exist_ok=True)
        with tarfile.open(args.input, "r:gz") as tar:
            tar.extractall(data_dir, filter="data")
        print(f"restored {args.input} -> {data_dir}")
        return 0
    from pilosa_tpu.storage.backup import restore_holder

    try:
        manifest = restore_holder(args.input, data_dir,
                                  generation=args.generation,
                                  as_of=args.as_of)
    except (ValueError, OSError) as e:
        print(f"error: {e}", file=sys.stderr)
        return 1
    msg = (
        f"restored generation {manifest['generation']} -> {data_dir}: "
        f"{manifest['restoredFragments']} fragments (digest-verified)"
    )
    if args.as_of is not None:
        msg += (f"; replayed {manifest['replayedOps']} ops to seq "
                f"{manifest['asOfSeq']}")
        if manifest.get("skippedReplayOps"):
            msg += f" ({manifest['skippedReplayOps']} skipped)"
    print(msg)
    return 0


def cmd_check(args) -> int:
    """Integrity check (reference ctl/check.go, grown into the scrub
    front door — docs/OPERATIONS.md integrity runbook): with ``-d``,
    an OFFLINE scrub of a data dir — every fragment file decoded AND
    its block digests verified against the ``.checksums`` sidecar
    (exactly what verify-on-load does at open); with ``--host``, a
    LIVE scrub pass triggered on a running node (``POST
    /internal/scrub`` — the node verifies its own disk bytes,
    quarantines rot, and read-repairs from replicas). Exit 1 when
    anything is corrupt or already quarantined."""
    if getattr(args, "host", None):
        url = f"{args.host.rstrip('/')}/internal/scrub"
        try:
            out = _http("POST", url, b"")
        except Exception as e:
            print(f"error: live scrub via {url} failed: {e}",
                  file=sys.stderr)
            return 1
        print(
            f"live scrub: scanned={out.get('scanned', 0)} "
            f"bytes={out.get('bytes', 0)} corrupt={out.get('corrupt', 0)} "
            f"repaired={out.get('repaired', 0)} "
            f"self_healed={out.get('self_healed', 0)} "
            f"unrepaired={out.get('unrepaired', 0)}"
        )
        return 1 if out.get("unrepaired", 0) else 0
    if not args.data_dir:
        print("error: check needs -d/--data-dir or --host",
              file=sys.stderr)
        return 1
    import glob

    from pilosa_tpu.roaring.format import replay_ops
    from pilosa_tpu.storage import integrity

    bad = 0
    data_dir = os.path.expanduser(args.data_dir)
    pattern = os.path.join(data_dir, "**", "fragments", "*")
    for path in sorted(glob.glob(pattern, recursive=True)):
        if (not os.path.isfile(path)
                or path.endswith((".cache", integrity.CHECKSUM_SUFFIX))
                or integrity.is_quarantined(os.path.basename(path))):
            continue
        try:
            bitmap, data, ops_at = integrity.verify_fragment_file(path)
            n_ops = replay_ops(bitmap, data, ops_at)
            print(f"ok: {path} bits={bitmap.count()} ops={n_ops}")
        except Exception as e:
            bad += 1
            print(f"CORRUPT: {path}: {e}", file=sys.stderr)
    quarantined = integrity.list_quarantined(data_dir)
    for q in quarantined:
        print(f"QUARANTINED: {q}", file=sys.stderr)
    return 1 if bad or quarantined else 0


def cmd_trace_report(args) -> int:
    """Read a device capture back (docs/OBSERVABILITY.md "Reading a
    capture"): busy share, time per XLA module and operation, and the
    longest idle gaps of each device by what the host was doing."""
    from pilosa_tpu.utils.tracing import format_trace_report, trace_report

    try:
        report = trace_report(args.log_dir, gaps_n=args.gaps)
    except (FileNotFoundError, ValueError) as e:
        print(f"trace-report: {e}", file=sys.stderr)
        return 1
    print(json.dumps(report) if args.json else format_trace_report(report))
    return 0


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="pilosa-tpu", description="TPU-native distributed bitmap index"
    )
    sub = parser.add_subparsers(dest="command", required=True)

    p = sub.add_parser("server", help="run a server node")
    p.add_argument("-c", "--config", help="TOML config file")
    p.add_argument("-d", "--data-dir")
    p.add_argument("-b", "--bind")
    p.add_argument("--port", type=int)
    p.add_argument("--verbose", action="store_true")
    p.set_defaults(fn=cmd_server)

    # internal: one SO_REUSEPORT serving worker (spawned by the owner)
    p = sub.add_parser("serve-worker")
    p.add_argument("--handshake-sock", required=True)
    p.add_argument("--listen-fd", type=int, required=True)
    p.add_argument("--worker-id", type=int, required=True)
    p.set_defaults(fn=cmd_serve_worker)

    p = sub.add_parser("import", help="bulk-import CSV (row,col[,ts] or col,value)")
    p.add_argument("-i", "--index", required=True)
    p.add_argument("-f", "--field", required=True)
    p.add_argument("--host", default=DEFAULT_HOST)
    p.add_argument("-d", "--data-dir", help="import in-process against a data dir")
    p.add_argument("--values", action="store_true", help="CSV is col,value (int field)")
    p.add_argument("--clear", action="store_true")
    p.add_argument("--create", action="store_true", help="create index/field if missing")
    p.add_argument("--min", type=int, default=0)
    p.add_argument("--max", type=int, default=1 << 32)
    p.add_argument("--batch-size", type=int, default=0,
                   help="rows per HTTP batch (default 100000, clamped to "
                        "the server's max-writes-per-request)")
    p.add_argument("--concurrency", type=int, default=1,
                   help="parallel in-flight POSTs (server routes per "
                        "shard); >1 reorders batches, so duplicate "
                        "columns across batches lose write order")
    p.add_argument("files", nargs="+", help="CSV files ('-' for stdin)")
    p.set_defaults(fn=cmd_import)

    p = sub.add_parser("export", help="export field as CSV")
    p.add_argument("-i", "--index", required=True)
    p.add_argument("-f", "--field", required=True)
    p.add_argument("--host", default=DEFAULT_HOST)
    p.add_argument("-d", "--data-dir")
    p.set_defaults(fn=cmd_export)

    p = sub.add_parser("config", help="echo resolved config")
    p.add_argument("-c", "--config")
    p.set_defaults(fn=cmd_config)

    p = sub.add_parser("generate-config", help="print default TOML config")
    p.set_defaults(fn=cmd_generate_config)

    p = sub.add_parser("inspect", help="dump fragment statistics")
    p.add_argument("-d", "--data-dir", required=True)
    p.set_defaults(fn=cmd_inspect)

    p = sub.add_parser(
        "check",
        help="verify fragment files against their checksum sidecars "
             "(offline -d scrub, or --host live scrub trigger)",
    )
    p.add_argument("-d", "--data-dir",
                   help="offline scrub of a data dir (node stopped)")
    p.add_argument("--host",
                   help="trigger a live scrub pass on a running node")
    p.set_defaults(fn=cmd_check)

    p = sub.add_parser(
        "backup",
        help="incremental manifest backup of a data dir or live cluster "
             "(legacy tar.gz when -o ends in .tar.gz)",
    )
    p.add_argument("-d", "--data-dir",
                   help="back up a data dir in-process (node must be "
                        "stopped)")
    p.add_argument("--host", default=DEFAULT_HOST,
                   help="back up a LIVE cluster over the sync wire "
                        "(fragment data; keyed/attr stores need -d)")
    p.add_argument("-o", "--output", required=True,
                   help="backup directory (or .tar.gz path for legacy)")
    p.add_argument("--max-bytes-per-sec", type=int, default=0,
                   help="pace live-backup transfers (0 = unpaced)")
    p.set_defaults(fn=cmd_backup)

    p = sub.add_parser(
        "restore",
        help="restore a backup directory (or legacy tar.gz) into an "
             "empty data dir",
    )
    p.add_argument("-d", "--data-dir", required=True)
    p.add_argument("-i", "--input", required=True)
    p.add_argument("--generation", type=int, default=None,
                   help="generation to restore (default: latest)")
    p.add_argument("--as-of", type=int, default=None, dest="as_of",
                   help="restore to an exact WAL seq: nearest anchored "
                        "generation + change-feed replay (needs backups "
                        "taken from a group-durability WAL)")
    p.set_defaults(fn=cmd_restore)

    p = sub.add_parser(
        "trace-report",
        help="summarize the newest POST /debug/trace-device capture",
    )
    p.add_argument("log_dir", help="trace-log-dir (or one .xplane.pb)")
    p.add_argument("--gaps", type=int, default=5,
                   help="longest idle gaps to label, per device")
    p.add_argument("--json", action="store_true")
    p.set_defaults(fn=cmd_trace_report)

    p = sub.add_parser("version", help="print version")
    p.set_defaults(fn=lambda a: (print(__version__), 0)[1])

    args = parser.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
