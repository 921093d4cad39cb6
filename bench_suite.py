"""Benchmark suite: the five BASELINE.json configs, end to end.

Runs each config through the real engine (holder → executor → fused XLA
kernels on the default JAX backend), checks results against a numpy
oracle, and prints one JSON line per config:

  {"config": i, "metric": ..., "value": N, "unit": ..., "ok": true}

Scale: data sizes default to a laptop-friendly fraction; --full uses the
billion-column scale on real hardware. bench.py (the driver's single-line
contract) stays the headline kernel benchmark; this suite covers the
query-level configs (SURVEY.md §6 / BASELINE.md).
"""

from __future__ import annotations

import argparse
import json
import os
import tempfile
import time

import numpy as np


def _timed(fn, iters=5):
    fn()  # warm (compile)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn()
    return (time.perf_counter() - t0) / iters, out


_DISPATCH_FLOOR_MS = None


def dispatch_floor_ms() -> float:
    """Median wall time of a trivial blocking device call: the host↔device
    round trip under every single-query p50 below. Computed once."""
    global _DISPATCH_FLOOR_MS
    if _DISPATCH_FLOOR_MS is None:
        import jax
        import jax.numpy as jnp

        f = jax.jit(lambda x, s: jnp.sum(x) + s)
        x = jax.device_put(np.zeros(8, np.int32))
        samples = []
        for i in range(10):
            t0 = time.perf_counter()
            int(f(x, i))
            samples.append(time.perf_counter() - t0)
        _DISPATCH_FLOOR_MS = round(float(np.median(samples)) * 1e3, 3)
    return _DISPATCH_FLOOR_MS


def _mk_env(tmp):
    from pilosa_tpu.executor import Executor
    from pilosa_tpu.storage import Holder

    holder = Holder(tmp).open()
    return holder, Executor(holder)


# Perfetto event names that mark inter-device transfer/collective work.
# TPU/GPU traces carry these on device lanes (with byte counts in the
# args when XLA attributes them); CPU-only hosts have NO such lanes,
# which parse_trace_events reports as a structured skip, never a crash.
_TRANSFER_OP_RE = None


def _transfer_op_re():
    global _TRANSFER_OP_RE
    if _TRANSFER_OP_RE is None:
        import re

        _TRANSFER_OP_RE = re.compile(
            r"(?i)\b(all-?reduce|all-?gather|reduce-?scatter|all-?to-?all"
            r"|collective-?permute|copy-?(start|done)|memcpy|"
            r"(d2d|h2d|d2h)\b)"
        )
    return _TRANSFER_OP_RE


def _transfer_event_bytes(e) -> int | None:
    """Bytes attributed to one transfer/collective trace event, from the
    arg conventions XLA's profiler uses (bytes_accessed /
    'bytes accessed' / bytes_transferred); None when the trace carries
    no byte figure for it."""
    args = e.get("args") or {}
    for key in ("bytes_accessed", "bytes accessed", "bytes_transferred",
                "bytes transferred", "bytes"):
        v = args.get(key)
        if v in (None, ""):
            continue
        try:
            return int(float(str(v).replace(",", "")))
        except ValueError:
            continue
    return None


def parse_trace_events(trace_dir: str) -> dict:
    """Parse every perfetto trace under ``trace_dir`` into ONE structured
    report (the hardened successor of the old inline parse — every
    failure mode is a ``reason`` string in the record, not a bare None):

    * device_us / device_lane: summed per-op durations from the device
      lanes ("XLA Ops" threads of device processes; CPU fallback:
      tf_XLA* execution threads, genuinely parallel, labeled
      ``cpu-threads``).
    * transfer: measured inter-device bytes — events matching collective
      /copy op names with profiler byte attribution. ``ok`` False with a
      reason when the host's traces lack transfer lanes entirely (the
      CPU-only case) or carry events without byte figures.
    """
    import glob
    import gzip
    import os

    report = {
        "ok": False, "device_us": 0.0, "device_lane": None, "reason": None,
        "transfer": {"ok": False, "bytes": 0, "events": 0, "reason": None},
    }
    paths = glob.glob(os.path.join(trace_dir, "**", "*.trace.json.gz"),
                      recursive=True)
    if not paths:
        report["reason"] = "no-trace-files"
        report["transfer"]["reason"] = "no-trace-files"
        return report
    parse_errors = 0
    found_device = False
    transfer_events = 0
    transfer_bytes = 0
    transfer_attributed = 0
    for path in paths:
        try:
            with gzip.open(path, "rt") as f:
                trace = json.load(f)
        except Exception:
            parse_errors += 1
            continue
        events = trace.get("traceEvents", [])
        # TPU/GPU: device lanes are separate trace processes named
        # "/device:TPU:0 ..." whose per-op lane is the thread named
        # "XLA Ops" — summing ALL device-pid lanes would double
        # count ("XLA Modules"/"Steps" spans COVER their op spans).
        # CPU backend: XLA executes on the "/host:CPU" process's
        # tf_XLA* threads (Eigen pool + TfrtCpuClient); those lanes
        # run genuinely in parallel, so their sum is device
        # THREAD-time (can exceed wall — labeled as such).
        device_pids = set()
        op_threads = set()
        cpu_threads = set()
        for e in events:
            if e.get("ph") != "M":
                continue
            name = str((e.get("args") or {}).get("name", ""))
            if (e.get("name") == "process_name"
                    and "device" in name.lower()):
                device_pids.add(e.get("pid"))
            elif e.get("name") == "thread_name":
                if name.startswith("XLA Ops"):
                    op_threads.add((e.get("pid"), e.get("tid")))
                elif name.startswith("tf_XLA"):
                    cpu_threads.add((e.get("pid"), e.get("tid")))
        keep = {t for t in op_threads if t[0] in device_pids}
        if keep:
            report["device_lane"] = "device-ops"
        elif cpu_threads:
            keep = cpu_threads
            report["device_lane"] = report["device_lane"] or "cpu-threads"
        op_re = _transfer_op_re()
        for e in events:
            if e.get("ph") != "X":
                continue
            if (e.get("pid"), e.get("tid")) in keep:
                report["device_us"] += float(e.get("dur", 0) or 0)
                found_device = True
            # transfer attribution counts DEVICE-lane collectives only:
            # CPU thread lanes name the same fused ops but model no
            # wire, so byte figures there would be fiction
            if ((e.get("pid") in device_pids)
                    and op_re.search(str(e.get("name", "")))):
                transfer_events += 1
                b = _transfer_event_bytes(e)
                if b is not None:
                    transfer_bytes += b
                    transfer_attributed += 1
    if found_device:
        report["ok"] = True
    else:
        report["reason"] = ("trace-parse-errors" if parse_errors
                            else "no-device-lanes")
    tr = report["transfer"]
    tr["events"] = transfer_events
    tr["bytes"] = transfer_bytes
    if transfer_attributed:
        tr["ok"] = True
    elif transfer_events:
        tr["reason"] = "transfer-events-without-byte-attribution"
    else:
        tr["reason"] = "no-transfer-lanes-in-trace (CPU-only host)"
    return report


def profiled_trace_report(fn, iters: int = 5) -> dict:
    """Run ``fn`` ``iters`` times inside a jax.profiler trace and return
    the structured parse_trace_events report plus ``iters``/``ms``.
    Capture failures come back as a reason, never an exception."""
    import tempfile as _tf

    from pilosa_tpu.utils.tracing import start_jax_trace

    with _tf.TemporaryDirectory() as td:
        try:
            with start_jax_trace(td):
                for _ in range(iters):
                    fn()
        except Exception as e:
            return {
                "ok": False, "device_us": 0.0, "device_lane": None,
                "reason": f"trace-capture-failed: {e!r}"[:200],
                "transfer": {"ok": False, "bytes": 0, "events": 0,
                             "reason": "trace-capture-failed"},
            }
        report = parse_trace_events(td)
    report["iters"] = iters
    if report["ok"]:
        report["ms"] = round(report["device_us"] / 1e3 / iters, 3)
    return report


def profiled_device_ms(fn, iters: int = 5):
    """PROFILER-MEASURED device execution time per iteration: run ``fn``
    ``iters`` times inside a ``jax.profiler`` trace
    (utils/tracing.start_jax_trace) and sum the device-lane op durations
    from the captured perfetto trace, rather than inferring device time
    as wall minus a round-trip sample. Returns mean ms/iteration, or None when the trace could not
    be captured/parsed (the bench must not fail on profiler quirks;
    profiled_trace_report carries the structured reason)."""
    report = profiled_trace_report(fn, iters)
    return report.get("ms") if report.get("ok") else None


def config1_star_trace(n_shards: int) -> dict:
    """Star-Trace: Row(stargazer) ∩ Row(language) → Count."""
    from pilosa_tpu.shardwidth import SHARD_WIDTH
    from pilosa_tpu.storage.view import VIEW_STANDARD

    with tempfile.TemporaryDirectory() as tmp:
        holder, ex = _mk_env(tmp)
        idx = holder.create_index("repos")
        rng = np.random.default_rng(1)
        expected = 0
        for field_name, row, density in (("stargazer", 1, 0.10), ("language", 5, 0.20)):
            f = idx.create_field(field_name)
            for shard in range(n_shards):
                n = int(SHARD_WIDTH * density)
                cols = rng.choice(SHARD_WIDTH, n, replace=False)
                f.view(VIEW_STANDARD, create=True).fragment(
                    shard, create=True
                ).bulk_import(np.full(n, row), cols)
        # oracle on one query
        pql = "Count(Intersect(Row(stargazer=1), Row(language=5)))"
        dt, got = _timed(lambda: ex.execute("repos", pql)[0])
        dev_ms = profiled_device_ms(lambda: ex.execute("repos", pql)[0])
        # numpy oracle
        want = 0
        for shard in range(n_shards):
            a = idx.field("stargazer").view(VIEW_STANDARD).fragment(shard).row_words(1)
            b = idx.field("language").view(VIEW_STANDARD).fragment(shard).row_words(5)
            want += int(np.bitwise_count(a & b).sum())
        holder.close()
        return {
            "config": 1, "metric": "star_trace_intersect_count_p50_ms",
            "value": round(dt * 1e3, 3), "unit": "ms",
            "device_p50_ms": dev_ms, "device_p50_source": "jax-profiler (sum of device-lane op durations)",
            "cols": n_shards << 20, "ok": got == want,
        }


def config2_taxi_topn_groupby(n_shards: int) -> dict:
    """NYC-taxi-like: TopN(cab_type) + GroupBy(passenger_count)."""
    from pilosa_tpu.shardwidth import SHARD_WIDTH
    from pilosa_tpu.storage.view import VIEW_STANDARD

    with tempfile.TemporaryDirectory() as tmp:
        holder, ex = _mk_env(tmp)
        idx = holder.create_index("taxi")
        cab = idx.create_field("cab_type")
        pc = idx.create_field("passenger_count")
        rng = np.random.default_rng(2)
        for shard in range(n_shards):
            cols = np.arange(SHARD_WIDTH, dtype=np.uint64)
            cab_rows = rng.choice(3, SHARD_WIDTH, p=[0.6, 0.3, 0.1])
            pc_rows = rng.integers(1, 7, SHARD_WIDTH)
            cab.view(VIEW_STANDARD, create=True).fragment(shard, create=True).bulk_import(cab_rows, cols)
            pc.view(VIEW_STANDARD, create=True).fragment(shard, create=True).bulk_import(pc_rows, cols)
        dt_topn, pairs = _timed(lambda: ex.execute("taxi", "TopN(cab_type, n=3)")[0])
        dt_gb, groups = _timed(
            lambda: ex.execute("taxi", "GroupBy(Rows(passenger_count))")[0], iters=3
        )
        dev_ms = profiled_device_ms(
            lambda: ex.execute("taxi", "TopN(cab_type, n=3)")[0]
        )
        total = sum(g.count for g in groups)
        holder.close()
        return {
            "config": 2, "metric": "taxi_topn_p50_ms",
            "value": round(dt_topn * 1e3, 3), "unit": "ms",
            "device_p50_ms": dev_ms, "device_p50_source": "jax-profiler (sum of device-lane op durations)",
            "groupby_ms": round(dt_gb * 1e3, 3),
            "ok": pairs[0].id == 0 and total == n_shards << 20,
        }


def config3_bsi_range_sum(n_shards: int) -> dict:
    """BSI: Range(fare > N) + Sum(fare)."""
    from pilosa_tpu.shardwidth import SHARD_WIDTH
    from pilosa_tpu.storage import FieldOptions
    from pilosa_tpu.storage.field import BSI_OFFSET_ROW, BSI_EXISTS_ROW

    with tempfile.TemporaryDirectory() as tmp:
        holder, ex = _mk_env(tmp)
        idx = holder.create_index("taxi")
        fare = idx.create_field("fare", FieldOptions(type="int", min=0, max=4095))
        rng = np.random.default_rng(3)
        oracle_sum, oracle_gt = 0, 0
        for shard in range(n_shards):
            vals = rng.integers(0, 4096, SHARD_WIDTH, dtype=np.uint64)
            oracle_sum += int(vals.sum())
            oracle_gt += int((vals > 1000).sum())
            # bulk plane import (bypasses per-column set_value for speed)
            frag = fare.view(fare.bsi_view_name(), create=True).fragment(shard, create=True)
            cols = np.arange(SHARD_WIDTH, dtype=np.uint64)
            rows = [np.full(SHARD_WIDTH, BSI_EXISTS_ROW, np.uint64)]
            pos = [cols]
            for bit in range(12):
                mask = (vals >> np.uint64(bit)) & np.uint64(1)
                sel = cols[mask == 1]
                rows.append(np.full(sel.size, BSI_OFFSET_ROW + bit, np.uint64))
                pos.append(sel)
            frag.bulk_import(np.concatenate(rows), np.concatenate(pos))
        dt_range, got_gt = _timed(lambda: ex.execute("taxi", "Count(Range(fare > 1000))")[0])
        dt_sum, got_sum = _timed(lambda: ex.execute("taxi", 'Sum(field="fare")')[0])
        dev_ms = profiled_device_ms(
            lambda: ex.execute("taxi", "Count(Range(fare > 1000))")[0]
        )
        holder.close()
        return {
            "config": 3, "metric": "bsi_range_count_p50_ms",
            "value": round(dt_range * 1e3, 3), "unit": "ms",
            "device_p50_ms": dev_ms, "device_p50_source": "jax-profiler (sum of device-lane op durations)",
            "sum_ms": round(dt_sum * 1e3, 3),
            "ok": got_gt == oracle_gt and got_sum.value == oracle_sum,
        }


def config4_time_quantum(n_shards: int) -> dict:
    """Time views: multi-view Union + Count over a 1-year window."""
    import datetime as dt_

    from pilosa_tpu.shardwidth import SHARD_WIDTH
    from pilosa_tpu.storage import FieldOptions
    from pilosa_tpu.storage.view import VIEW_STANDARD, views_for_time

    with tempfile.TemporaryDirectory() as tmp:
        holder, ex = _mk_env(tmp)
        idx = holder.create_index("events")
        t = idx.create_field("t", FieldOptions(type="time", time_quantum="YMD"))
        rng = np.random.default_rng(4)
        per_day = 2000
        days = [dt_.datetime(2019, 1, 1) + dt_.timedelta(days=i * 14) for i in range(26)]
        days += [dt_.datetime(2020, 2, 1)]  # outside window
        oracle = set()
        for day in days:
            for shard in range(n_shards):
                cols = rng.choice(SHARD_WIDTH, per_day, replace=False)
                for vname in views_for_time(VIEW_STANDARD, "YMD", day):
                    t.view(vname, create=True).fragment(shard, create=True).bulk_import(
                        np.full(per_day, 1, np.uint64), cols
                    )
                t.view(VIEW_STANDARD, create=True).fragment(shard, create=True).bulk_import(
                    np.full(per_day, 1, np.uint64), cols
                )
                if day < dt_.datetime(2020, 1, 1):
                    oracle.update((shard << 20) + int(c) for c in cols)
        pql = "Count(Row(t=1, from='2019-01-01T00:00', to='2020-01-01T00:00'))"
        dt_q, got = _timed(lambda: ex.execute("events", pql)[0])
        dev_ms = profiled_device_ms(lambda: ex.execute("events", pql)[0])
        holder.close()
        return {
            "config": 4, "metric": "time_union_count_p50_ms",
            "value": round(dt_q * 1e3, 3), "unit": "ms",
            "device_p50_ms": dev_ms, "device_p50_source": "jax-profiler (sum of device-lane op durations)",
            "ok": got == len(oracle),
        }


def config5_ssb_4way(n_shards: int) -> dict:
    """SSB-style 4-way Intersect with the mesh (ICI-reduce) executor."""
    from pilosa_tpu.parallel import DistExecutor, make_mesh
    from pilosa_tpu.shardwidth import SHARD_WIDTH
    from pilosa_tpu.storage import Holder
    from pilosa_tpu.storage.view import VIEW_STANDARD

    with tempfile.TemporaryDirectory() as tmp:
        holder = Holder(tmp).open()
        idx = holder.create_index("ssb")
        rng = np.random.default_rng(5)
        fields = ["year", "region", "category", "brand"]
        densities = [0.5, 0.25, 0.2, 0.3]
        words_oracle = None
        for fname, d in zip(fields, densities):
            f = idx.create_field(fname)
            for shard in range(n_shards):
                n = int(SHARD_WIDTH * d)
                cols = rng.choice(SHARD_WIDTH, n, replace=False)
                f.view(VIEW_STANDARD, create=True).fragment(shard, create=True).bulk_import(
                    np.full(n, 1, np.uint64), cols
                )
        ex = DistExecutor(holder, make_mesh())
        pql = ("Count(Intersect(Row(year=1), Row(region=1), "
               "Row(category=1), Row(brand=1)))")
        dt_q, got = _timed(lambda: ex.execute("ssb", pql)[0])
        dev_ms = profiled_device_ms(lambda: ex.execute("ssb", pql)[0])
        want = 0
        for shard in range(n_shards):
            acc = None
            for fname in fields:
                w = idx.field(fname).view(VIEW_STANDARD).fragment(shard).row_words(1)
                acc = w if acc is None else (acc & w)
            want += int(np.bitwise_count(acc).sum())
        holder.close()
        return {
            "config": 5, "metric": "ssb_4way_intersect_count_p50_ms",
            "value": round(dt_q * 1e3, 3), "unit": "ms",
            "device_p50_ms": dev_ms, "device_p50_source": "jax-profiler (sum of device-lane op durations)",
            "mesh_devices": make_mesh().size, "ok": got == want,
        }


def config5_mesh_cpu8(n_shards: int = 16, n_queries: int = 64) -> dict:
    """Config 5's defining feature — the cross-shard mesh reduce —
    exercised on a REAL 8-device mesh (virtual CPU devices). NOT a perf claim: CPU devices; perf numbers stay single-chip
    (config 5 proper). Verified here: (a) a pipelined stream of SSB
    4-way intersect counts through DistExecutor.submit matches the local
    single-device executor on every query, and (b) the mesh path keeps
    micro-batching — program dispatches ≈ queries / microbatch_max, not
    one eager dispatch per query."""
    from pilosa_tpu.executor import Executor
    from pilosa_tpu.parallel import DistExecutor, make_mesh
    from pilosa_tpu.shardwidth import SHARD_WIDTH
    from pilosa_tpu.storage import Holder
    from pilosa_tpu.storage.view import VIEW_STANDARD

    mesh = make_mesh()
    with tempfile.TemporaryDirectory() as tmp:
        holder = Holder(tmp).open()
        idx = holder.create_index("ssb")
        rng = np.random.default_rng(55)
        fields = ["year", "region", "category", "brand"]
        n_rows = 4
        for fname, d in zip(fields, [0.5, 0.25, 0.2, 0.3]):
            f = idx.create_field(fname)
            for shard in range(n_shards):
                n = int(SHARD_WIDTH * d)
                for row in range(1, n_rows + 1):
                    cols = rng.choice(SHARD_WIDTH, n, replace=False)
                    f.view(VIEW_STANDARD, create=True).fragment(
                        shard, create=True
                    ).bulk_import(np.full(n, row, np.uint64), cols)

        def pql(i: int) -> str:
            combo = [(i + k) % n_rows + 1 for k in range(4)]
            return ("Count(Intersect(" + ", ".join(
                f"Row({f}={r})" for f, r in zip(fields, combo)
            ) + "))")

        local = Executor(holder)
        want = [local.execute("ssb", pql(i))[0] for i in range(n_rows)]

        ex = DistExecutor(holder, mesh)
        dispatches = [0]
        real_builder = ex._program_batched

        def counting_builder(*a, **k):
            fn = real_builder(*a, **k)

            def counted(*args):
                dispatches[0] += 1
                return fn(*args)

            return counted

        ex._program_batched = counting_builder
        # warm compiles outside the accounting
        warm = [ex.submit("ssb", pql(i))[0] for i in range(ex.microbatch_max)]
        warm[-1].result()
        dispatches[0] = 0

        t0 = time.perf_counter()
        deferreds = [ex.submit("ssb", pql(i))[0] for i in range(n_queries)]
        got = [d.result() for d in deferreds]
        wall = time.perf_counter() - t0
        ok = all(g == want[i % n_rows] for i, g in enumerate(got))
        expected_dispatches = -(-n_queries // ex.microbatch_max)
        holder.close()
        return {
            "config": 5, "metric": "ssb_4way_mesh_microbatched_dispatches",
            "value": dispatches[0], "unit": "dispatches",
            "queries": n_queries, "microbatch": ex.microbatch_max,
            "expected_dispatches": expected_dispatches,
            "mesh_devices": mesh.size,
            "wall_ms": round(wall * 1e3, 1),
            "ok": ok and dispatches[0] == expected_dispatches,
            "note": ("8 virtual CPU devices — correctness + dispatch "
                     "accounting for the SPMD path only; perf claims are "
                     "single-chip (config 5 proper)"),
        }


def config_serving(n_shards: int = 8, n_queries: int = 512,
                   client_counts=(16, 64, 128)) -> dict:
    """Serving-path throughput with the HOST-PATH FAST LANE (ISSUE 4):
    concurrent HTTP clients against ONE in-process server (real
    loopback HTTP, full handler → API → ClusterExecutor.submit stack),
    in two transport modes on the same
    hardware, same data, same queries:

    - ``fastlane``: each client holds a persistent HTTP/1.1 keep-alive
      connection (what the pooled InternalClient and any sane production
      client do) — requests amortize TCP connect + server handler-thread
      spawn, responses ride pre-serialized bytes, identical wavemates
      dedupe in the pipeline;
    - ``legacy``: the r5 serving path end to end — urllib clients (one
      fresh connection per request, exactly the r5 bench's client) AND
      ``api.serve_fastlane = False`` (dict building + json.dumps per
      request, no identical-query dedupe); that curve plateaued
      ~650 QPS/node on TPU hardware.

    The headline is plateau-vs-plateau: max QPS over the client sweep in
    each mode. ok requires byte-identical responses to the serial pass,
    the connection-count oracle (fastlane connections ≈ clients while
    legacy ≈ requests), and ≥2× legacy plateau. A second phase proves
    the cluster fast lane: a 2-node cluster answers a query set with the
    wave batcher ON and OFF and the response bytes must be identical,
    with batches actually formed."""
    import http.client as _hc
    import threading
    import urllib.request

    from pilosa_tpu.server import Server, ServerConfig
    from pilosa_tpu.shardwidth import SHARD_WIDTH
    from pilosa_tpu.storage.view import VIEW_STANDARD

    rng = np.random.default_rng(7)
    with tempfile.TemporaryDirectory() as tmp:
        server = Server(ServerConfig(
            data_dir=tmp, port=0, name="bench", anti_entropy_interval=0,
            heartbeat_interval=0,
        )).open()
        try:
            idx = server.holder.create_index("b")
            f = idx.create_field("f")
            n = int(SHARD_WIDTH * 0.1)
            for shard in range(n_shards):
                frag = f.view(VIEW_STANDARD, create=True).fragment(
                    shard, create=True
                )
                for row in range(1, 5):
                    frag.bulk_import(
                        np.full(n, row, np.uint64),
                        rng.choice(SHARD_WIDTH, n, replace=False).astype(
                            np.uint64
                        ),
                    )
            server.api.cluster.note_local_shards("b", list(range(n_shards)))
            port = server.port
            queries = [
                ("Count(Intersect(Row(f={}), Row(f={})))".format(
                    1 + (i % 4), 1 + ((i + 1) % 4)))
                for i in range(n_queries)
            ]

            def post_keepalive(conn, pql: str) -> bytes:
                conn.request("POST", "/index/b/query", body=pql.encode())
                return conn.getresponse().read()

            def post_legacy(pql: str) -> bytes:
                # urllib, new connection per request: byte-for-byte the
                # client the r5 serving bench used for its curve
                r = urllib.request.Request(
                    f"http://localhost:{port}/index/b/query",
                    data=pql.encode(), method="POST",
                )
                with urllib.request.urlopen(r, timeout=120) as resp:
                    return resp.read()

            post_legacy(queries[0])  # warm the per-query compile caches
            serial_conn = _hc.HTTPConnection("localhost", port, timeout=120)
            t0 = time.perf_counter()
            serial = [post_keepalive(serial_conn, q) for q in queries]
            serial_wall = time.perf_counter() - t0
            serial_conn.close()
            serial_parsed = [json.loads(s) for s in serial]

            def run_concurrent(n_clients: int, keepalive: bool):
                results = [None] * n_queries
                errors: list = []
                gate = threading.Event()

                def worker(tid: int):
                    conn = (_hc.HTTPConnection("localhost", port,
                                               timeout=120)
                            if keepalive else None)
                    gate.wait(30)
                    for k in range(tid, n_queries, n_clients):
                        try:
                            results[k] = (post_keepalive(conn, queries[k])
                                          if keepalive
                                          else post_legacy(queries[k]))
                        except Exception as e:  # surfaced via errors
                            errors.append(repr(e))
                    if conn is not None:
                        conn.close()

                threads = [
                    threading.Thread(target=worker, args=(t,))
                    for t in range(n_clients)
                ]
                for t in threads:
                    t.start()
                t0 = time.perf_counter()
                gate.set()
                for t in threads:
                    t.join(300)
                return time.perf_counter() - t0, results, errors

            # warm burst: compiles the pow-of-two batched program shapes
            # the waves will use (the serial pass only compiled batch=1)
            run_concurrent(max(client_counts), True)

            ok = True
            scaling = []
            oracle = {}
            for mode, keepalive in (("fastlane", True), ("legacy", False)):
                # legacy mode is the FULL r5 serving path: per-request
                # connections AND the pre-fastlane response pipeline
                server.api.serve_fastlane = keepalive
                for n_clients in client_counts:
                    best = 0.0
                    for _ in range(3):  # best-of-3: loopback jitter
                        http_srv = server._http
                        with http_srv.metrics_lock:
                            conns0 = http_srv.connections_opened
                        wall, results, errors = run_concurrent(
                            n_clients, keepalive
                        )
                        with http_srv.metrics_lock:
                            conns = http_srv.connections_opened - conns0
                        same = (results == serial if keepalive else
                                [json.loads(r) for r in results
                                 if r is not None] == serial_parsed)
                        ok = ok and not errors and same
                        best = max(best, n_queries / wall)
                    scaling.append({"mode": mode, "clients": n_clients,
                                    "qps": round(best, 1),
                                    "connections_last_run": conns})
                    # connection-count oracle from the LAST run of the
                    # sweep point: keep-alive ≈ one per client, legacy
                    # ≈ one per request
                    if mode == "fastlane":
                        ok = ok and conns <= 2 * n_clients
                    else:
                        ok = ok and conns >= n_queries
                    oracle[mode] = conns
            server.api.serve_fastlane = True
            fast_plateau = max(s["qps"] for s in scaling
                               if s["mode"] == "fastlane")
            legacy_plateau = max(s["qps"] for s in scaling
                                 if s["mode"] == "legacy")
            pm = server.api.pipeline_metrics()
        finally:
            server.close()

    batch_check = _serving_cluster_batch_check(n_shards=8)
    speedup = round(fast_plateau / max(legacy_plateau, 1e-9), 2)
    return {
        "config": "serving",
        "metric": "serving_fastlane_plateau_qps",
        "value": round(fast_plateau, 1),
        "unit": "queries/sec",
        "legacy_plateau_qps": round(legacy_plateau, 1),
        "plateau_speedup": speedup,
        "qps_serial": round(n_queries / serial_wall, 1),
        "scaling": scaling,
        "connections_oracle": oracle,
        "queries": n_queries, "shards": n_shards,
        "pipeline": pm,
        "remote_batch": batch_check,
        "ok": bool(ok and speedup >= 2.0 and batch_check["ok"]),
    }


def _serving_cluster_batch_check(n_shards: int = 8,
                                 n_queries: int = 32) -> dict:
    """Cluster fast-lane proof: a 2-node cluster answers the same
    concurrent query set with the remote wave batcher ON then OFF;
    responses must be byte-identical and the ON pass must actually form
    multi-query batches."""
    import threading
    import urllib.request

    from pilosa_tpu.server import Server, ServerConfig
    from pilosa_tpu.shardwidth import SHARD_WIDTH

    with tempfile.TemporaryDirectory() as t1, \
            tempfile.TemporaryDirectory() as t2:
        s1 = Server(ServerConfig(
            data_dir=t1, port=0, name="a", anti_entropy_interval=0,
            heartbeat_interval=0,
        )).open()
        s2 = Server(ServerConfig(
            data_dir=t2, port=0, name="b", anti_entropy_interval=0,
            heartbeat_interval=0, seeds=[f"http://localhost:{s1.port}"],
        )).open()
        try:
            url = f"http://localhost:{s1.port}"

            def post(path, data):
                r = urllib.request.Request(url + path, data=data,
                                           method="POST")
                with urllib.request.urlopen(r, timeout=120) as resp:
                    return resp.read()

            post("/index/i", b"{}")
            post("/index/i/field/f", b"{}")
            rows, cols = [], []
            for shard in range(n_shards):
                for c in range(64):
                    rows.append(1 + c % 3)
                    cols.append(shard * SHARD_WIDTH + c * 11)
            post("/index/i/field/f/import",
                 json.dumps({"rows": rows, "columns": cols}).encode())

            queries = [f"Count(Row(f={1 + i % 3}))" for i in range(n_queries)]

            def run():
                results = [None] * n_queries
                errors: list = []
                gate = threading.Event()

                def worker(tid):
                    gate.wait(10)
                    for k in range(tid, n_queries, 8):
                        try:
                            results[k] = post("/index/i/query",
                                              queries[k].encode())
                        except Exception as e:  # keep the stripe going
                            errors.append(f"{queries[k]}: {e!r}")

                threads = [threading.Thread(target=worker, args=(t,))
                           for t in range(8)]
                for t in threads:
                    t.start()
                gate.set()
                for t in threads:
                    t.join(120)
                return results, errors

            batched, err_on = run()
            m_on = s1.api.executor.wave_batcher.metrics()
            s1.api.executor.remote_batch = False
            unbatched, err_off = run()
            m_off = s1.api.executor.wave_batcher.metrics()
            errors = err_on + err_off
            ok = (not errors
                  and batched == unbatched
                  and None not in batched
                  and m_on["remote_batched_queries_total"] > 0
                  and m_off["remote_batched_queries_total"]
                  == m_on["remote_batched_queries_total"])
            out = {
                "byte_identical": batched == unbatched,
                "batched_queries": m_on["remote_batched_queries_total"],
                "batches": m_on["remote_batches_total"],
                "ok": bool(ok),
            }
            if errors:
                out["errors"] = errors[:5]
            return out
        finally:
            s2.close()
            s1.close()


def config_serving_readwrite(n_shards: int = 32, n_clients: int = 16,
                             n_ops: int = 256) -> dict:
    """Mixed READ+WRITE concurrent serving: 75% Counts through the wave
    pipeline, 25% point Sets through the routed write path (each write
    durably logged before its ACK and patched into resident leaves).
    Correctness: every write must ACK true and the final written row
    must equal the written column set exactly."""
    import json as _json
    import threading
    import urllib.request

    from pilosa_tpu.server import Server, ServerConfig
    from pilosa_tpu.shardwidth import SHARD_WIDTH
    from pilosa_tpu.storage.view import VIEW_STANDARD

    rng = np.random.default_rng(7)
    with tempfile.TemporaryDirectory() as tmp:
        server = Server(ServerConfig(
            data_dir=tmp, port=0, name="bench", anti_entropy_interval=0,
            heartbeat_interval=0,
        )).open()
        try:
            idx = server.holder.create_index("b")
            f = idx.create_field("f")
            n = int(SHARD_WIDTH * 0.1)
            for shard in range(n_shards):
                frag = f.view(VIEW_STANDARD, create=True).fragment(
                    shard, create=True
                )
                for row in range(1, 5):
                    frag.bulk_import(
                        np.full(n, row, np.uint64),
                        rng.choice(SHARD_WIDTH, n, replace=False).astype(
                            np.uint64
                        ),
                    )
            server.api.cluster.note_local_shards("b", list(range(n_shards)))
            url = f"http://localhost:{server.port}/index/b/query"

            def post(pql: str) -> dict:
                r = urllib.request.Request(
                    url, data=pql.encode(), method="POST"
                )
                with urllib.request.urlopen(r, timeout=300) as resp:
                    return _json.loads(resp.read())

            write_cols = rng.choice(
                n_shards * SHARD_WIDTH, n_ops // 4, replace=False
            ).tolist()
            ops, wi = [], 0
            for i in range(n_ops):
                if i % 4 == 3:
                    ops.append(f"Set({write_cols[wi]}, f=9)")
                    wi += 1
                else:
                    ops.append(
                        "Count(Intersect(Row(f={}), Row(f={})))".format(
                            1 + (i % 4), 1 + ((i + 1) % 4)
                        )
                    )
            post(ops[0])
            post("Count(Row(f=9))")  # warm both program shapes
            t0 = time.perf_counter()
            for q in ops[:64]:
                post(q)
            serial_qps = 64 / (time.perf_counter() - t0)
            post("ClearRow(f=9)")

            results: list = [None] * n_ops
            errors: list = []
            gate = threading.Event()

            def worker(tid: int):
                gate.wait(30)
                for k in range(tid, n_ops, n_clients):
                    try:
                        results[k] = post(ops[k])
                    except Exception as e:  # surfaced below
                        errors.append(repr(e))

            threads = [threading.Thread(target=worker, args=(t,))
                       for t in range(n_clients)]
            for t in threads:
                t.start()
            t0 = time.perf_counter()
            gate.set()
            for t in threads:
                t.join(600)
            wall = time.perf_counter() - t0
            ok = not errors
            ok = ok and all(results[k] == {"results": [True]}
                            for k in range(3, n_ops, 4))
            ok = ok and post("Count(Row(f=9))") == {
                "results": [len(write_cols)]
            }
            return {
                "config": "readwrite",
                "metric": "serving_readwrite_qps",
                "value": round(n_ops / wall, 1),
                "unit": "queries/sec",
                "qps_serial": round(serial_qps, 1),
                "speedup_vs_serial": round((n_ops / wall) / serial_qps, 2),
                "clients": n_clients, "ops": n_ops, "write_frac": 0.25,
                "shards": n_shards, "ok": bool(ok),
            }
        finally:
            server.close()


def crash_burst_ledger(post_set, kill, n_threads: int, min_acked: int,
                       deadline_s: float = 60.0):
    """ACK-ledger write burst + mid-burst kill for the crash-recovery
    oracle — ONE implementation shared by config_durability and the
    dryrun_multichip certification. ``n_threads`` writers Set() disjoint
    columns through ``post_set`` (returns True on a 200 ack; an
    exception means the kill landed mid-request); once ``min_acked``
    acks accumulate, ``kill()`` fires mid-burst (SIGKILL: no close, no
    snapshot, torn groups). Returns (acked, inflight-at-kill): the
    recovered row must contain every acked col and nothing outside
    acked | inflight."""
    import threading

    acked: set = set()
    inflight: dict = {}
    lock = threading.Lock()
    stop = threading.Event()

    def writer(tid: int):
        k = 0
        while not stop.is_set():
            col = tid + k * n_threads
            k += 1
            with lock:
                inflight[tid] = col
            try:
                ok = post_set(col)
            except Exception:
                return  # the kill landed mid-request
            if ok:
                with lock:
                    acked.add(col)
                    inflight.pop(tid, None)

    threads = [threading.Thread(target=writer, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    deadline = time.time() + deadline_s
    while len(acked) < min_acked:
        if time.time() > deadline:
            raise AssertionError(
                f"crash-oracle burst stalled at {len(acked)} acked "
                "writes — node stopped acking")
        time.sleep(0.02)
    kill()
    stop.set()
    for t in threads:
        t.join(15)
    with lock:
        return set(acked), set(inflight.values())


def config_durability(n_shards: int = 8, n_clients: int = 16,
                      n_ops: int = 800, fsync_delay_ms: float = 8.0,
                      group_max_ms: float = 5.0) -> dict:
    """Durable write path at read-path speed (ISSUE 6): the SAME mixed
    25%-write workload served by a real subprocess node in each
    durability mode —

    - ``per-op``: every acked write fsyncs its own op record (the
      honest baseline the r5 'per-write durability' claim implied);
    - ``group``: concurrent writers' records group-commit through the
      holder WAL, ONE fsync per group, ACKs released after it;
    - ``flush-only``: the r5 behavior (no fsync) as the ceiling.

    ``fsync_delay_ms`` injects a serialized per-fsync journal delay
    into EVERY mode (PILOSA_TPU_FSYNC_DELAY_MS, the config_sync
    injected-RTT precedent: tmpfs/9p under-prices the very fsync the
    group commit amortizes; ~8 ms is a conservative fsync on a busy
    production disk, and fsyncs serialize at the journal).

    Gates: group write QPS ≥ 2× per-op
    at 25% write fraction; group p99 write-ACK latency ≤
    group-commit-max-ms over the per-op mode's p99 under the SAME
    closed-loop load (+3 ms scheduler slack) — tail-to-tail, the
    controlled comparison: both tails carry identical queueing, so the
    difference isolates what the forming window may add; then the crash
    oracle — SIGKILL the group-mode node mid write-burst, restart,
    every ACKed write present and the fragment bit-exact against the
    ACK ledger — and a backup → restore round trip byte-identical to
    the recovered node."""
    import json as _json
    import os
    import shutil
    import socket
    import subprocess
    import sys
    import threading
    import urllib.request

    from pilosa_tpu.shardwidth import SHARD_WIDTH

    def free_port() -> int:
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            return s.getsockname()[1]

    def req(method, base, path, body=None, timeout=60):
        r = urllib.request.Request(f"{base}{path}", data=body,
                                   method=method)
        with urllib.request.urlopen(r, timeout=timeout) as resp:
            return _json.loads(resp.read() or b"{}")

    def spawn(data_dir: str, mode: str):
        port = free_port()
        env = {
            **os.environ, "JAX_PLATFORMS": "cpu",
            "PILOSA_TPU_NAME": f"dur-{mode}",
            "PILOSA_TPU_ANTI_ENTROPY_INTERVAL": "0",
            "PILOSA_TPU_HEARTBEAT_INTERVAL": "0",
            "PILOSA_TPU_USE_MESH": "false",
            "PILOSA_TPU_DURABILITY_MODE": mode,
            "PILOSA_TPU_GROUP_COMMIT_MAX_MS": str(group_max_ms),
            "PILOSA_TPU_FSYNC_DELAY_MS": str(fsync_delay_ms),
        }
        proc = subprocess.Popen(
            [sys.executable, "-m", "pilosa_tpu", "server",
             "--data-dir", data_dir, "--bind", "127.0.0.1",
             "--port", str(port)],
            env=env, stdout=subprocess.DEVNULL, stderr=subprocess.STDOUT,
        )
        base = f"http://127.0.0.1:{port}"
        for _ in range(240):
            if proc.poll() is not None:
                raise AssertionError(f"node exited rc={proc.returncode}")
            try:
                req("GET", base, "/status", timeout=5)
                return proc, base
            except Exception:
                time.sleep(0.25)
        proc.terminate()
        raise AssertionError("durability node never served /status")

    rounds = 3  # best-of-3 per mode (the config_serving precedent:
    # a ~200-sample p99 is two samples deep — one scheduler hiccup on
    # the shared CI box would otherwise decide the gate)
    rng = np.random.default_rng(23)
    seed_cols = rng.choice(n_shards * SHARD_WIDTH, 2000,
                           replace=False).tolist()
    n_writes = sum(1 for i in range(n_ops) if i % 4 == 3)
    write_cols = rng.choice(n_shards * SHARD_WIDTH, n_writes * rounds,
                            replace=False).tolist()

    def round_ops(r: int) -> list[str]:
        out, wi = [], r * n_writes
        for i in range(n_ops):
            if i % 4 == 3:  # 25% write fraction; fresh cols per round
                out.append(f"Set({write_cols[wi]}, f=9)")
                wi += 1
            else:
                out.append(f"Count(Row(f={1 + i % 3}))")
        return out

    def run_round(base: str, ops: list[str]):
        write_lat: list = []
        lat_lock = threading.Lock()
        gate = threading.Event()
        errors: list = []

        def worker(tid: int):
            gate.wait(30)
            for k in range(tid, n_ops, n_clients):
                is_write = k % 4 == 3
                t0 = time.perf_counter()
                try:
                    out = req("POST", base, "/index/i/query",
                              ops[k].encode())
                except Exception as e:
                    errors.append(repr(e))
                    return
                if is_write:
                    if out != {"results": [True]}:
                        errors.append(f"write not acked: {out}")
                    with lat_lock:
                        write_lat.append(time.perf_counter() - t0)

        threads = [threading.Thread(target=worker, args=(t,))
                   for t in range(n_clients)]
        for t in threads:
            t.start()
        t0 = time.perf_counter()
        gate.set()
        for t in threads:
            t.join(600)
        wall = time.perf_counter() - t0
        lats = np.sort(np.array(write_lat)) * 1e3
        return {
            "ok": not errors and len(write_lat) == n_writes,
            "errors": errors[:3],
            "wall_s": round(wall, 3),
            "write_qps": round(n_writes / wall, 1),
            "total_qps": round(n_ops / wall, 1),
            "ack_p50_ms": round(float(lats[len(lats) // 2]), 2),
            "ack_p99_ms": round(
                float(lats[int(len(lats) * 0.99) - 1]), 2),
        }

    def run_mode(mode: str, tmp: str):
        data_dir = f"{tmp}/{mode}"
        proc, base = spawn(data_dir, mode)
        try:
            req("POST", base, "/index/i", b"{}")
            req("POST", base, "/index/i/field/f", b"{}")
            body = _json.dumps({
                "rows": [1 + k % 3 for k in range(len(seed_cols))],
                "columns": seed_cols,
            }).encode()
            req("POST", base, "/index/i/field/f/import", body)
            # warm all three program shapes off the measured cols
            req("POST", base, "/index/i/query", round_ops(0)[0].encode())
            req("POST", base, "/index/i/query", b"Set(0, f=7)")
            req("POST", base, "/index/i/query", b"Count(Row(f=9))")
            results = [run_round(base, round_ops(r))
                       for r in range(rounds)]
            best = dict(max(results, key=lambda r: r["write_qps"]))
            best["ack_p99_ms"] = min(r["ack_p99_ms"] for r in results)
            best["ok"] = all(r["ok"] for r in results)
            best["errors"] = sum((r["errors"] for r in results), [])[:3]
            best["rounds"] = [
                {k: r[k] for k in ("write_qps", "ack_p50_ms",
                                   "ack_p99_ms")}
                for r in results
            ]
            return best, proc, base, data_dir
        except Exception:
            proc.terminate()
            proc.wait(15)
            raise

    with tempfile.TemporaryDirectory() as tmp:
        perop, proc, _, _ = run_mode("per-op", tmp)
        proc.terminate()
        proc.wait(15)
        flush, proc, _, _ = run_mode("flush-only", tmp)
        proc.terminate()
        proc.wait(15)
        group, proc, base, data_dir = run_mode("group", tmp)

        # ---- crash oracle: SIGKILL mid write-burst on the group node
        def burst_set(col: int) -> bool:
            return req("POST", base, "/index/i/query",
                       f"Set({col}, f=8)".encode(),
                       timeout=10) == {"results": [True]}

        def burst_kill():
            proc.kill()
            proc.wait(15)

        ledger, maybe = crash_burst_ledger(burst_set, burst_kill,
                                           n_threads=8, min_acked=60)
        proc, base = spawn(data_dir, "group")
        got = set(req("POST", base, "/index/i/query", b"Row(f=8)",
                      timeout=120)["results"][0]["columns"])
        got9 = set(req("POST", base, "/index/i/query", b"Row(f=9)",
                       timeout=120)["results"][0]["columns"])
        oracle_ok = (ledger <= got and got <= ledger | maybe
                     and got9 == set(write_cols))
        proc.terminate()
        proc.wait(15)

        # ---- backup → restore round trip, byte-identical
        from pilosa_tpu.storage import Holder
        from pilosa_tpu.storage.backup import backup_holder, restore_holder

        src = Holder(data_dir).open()
        manifest = backup_holder(src, f"{tmp}/bak")
        restore_holder(f"{tmp}/bak", f"{tmp}/restored")
        dst = Holder(f"{tmp}/restored").open()
        restore_ok = True
        for iname, idx in src.indexes.items():
            for fname, fld in idx.fields.items():
                for vname, view in fld.views.items():
                    for shard, frag in view.fragments.items():
                        other = (dst.index(iname).field(fname)
                                 .view(vname).fragment(shard))
                        if (other is None or other.serialize_snapshot()
                                != frag.serialize_snapshot()):
                            restore_ok = False
        src.close()
        dst.close()
        shutil.rmtree(f"{tmp}/restored", ignore_errors=True)

    speedup = round(group["write_qps"] / perop["write_qps"], 2)
    lat_bound_ms = round(group_max_ms + perop["ack_p99_ms"] + 3.0, 2)
    ok = (group["ok"] and perop["ok"] and flush["ok"]
          and speedup >= 2.0
          and group["ack_p99_ms"] <= lat_bound_ms
          and oracle_ok and restore_ok)
    return {
        "config": "durability",
        "metric": "durable_write_qps_group_vs_perop",
        "value": speedup,
        "unit": "x",
        "write_frac": 0.25, "clients": n_clients, "ops": n_ops,
        "injected_fsync_ms": fsync_delay_ms,
        "group_commit_max_ms": group_max_ms,
        "group": group, "per_op": perop, "flush_only": flush,
        "ack_p99_bound_ms": lat_bound_ms,
        "crash_oracle_ok": bool(oracle_ok),
        "crash_acked_writes": len(ledger),
        "restore_round_trip_ok": bool(restore_ok),
        "backup_new_blobs": manifest["newBlobs"],
        "ok": bool(ok),
    }


def config_import(n_shards: int = 8, rows_per_shard: int = 4,
                  density: float = 0.05) -> dict:
    """Bulk-import throughput — the reference's write-path hot loop
    (SURVEY §3.3 fragment.bulkImport). Measures three layers so the cost
    split is visible: (a) fragment.bulk_import engine rate (sorted id
    stream → roaring containers + op log), (b) the HTTP JSON import
    route end to end, and (c) the binary import-roaring route (the
    reference's fast path). Verified by exact Count afterwards."""
    import json as _json
    import urllib.request

    from pilosa_tpu.roaring import RoaringBitmap
    from pilosa_tpu.roaring.format import serialize
    from pilosa_tpu.server import Server, ServerConfig
    from pilosa_tpu.shardwidth import SHARD_WIDTH
    from pilosa_tpu.storage import FieldOptions
    from pilosa_tpu.storage.view import VIEW_STANDARD

    rng = np.random.default_rng(13)
    n = int(SHARD_WIDTH * density)
    per_shard = [
        np.sort(rng.choice(SHARD_WIDTH, n, replace=False)).astype(np.uint64)
        for _ in range(n_shards)
    ]

    with tempfile.TemporaryDirectory() as tmp:
        server = Server(ServerConfig(
            data_dir=tmp, port=0, name="imp", anti_entropy_interval=0,
            heartbeat_interval=0,
            # this bench measures ROUTE cost with deliberately huge
            # bodies; the edge batch limit is the CLI's problem
            max_writes_per_request=0,
        )).open()
        try:
            idx = server.holder.create_index("b")
            f = idx.create_field("eng")
            # (a) engine layer
            t0 = time.perf_counter()
            total_bits = 0
            for shard, cols in enumerate(per_shard):
                frag = f.view(VIEW_STANDARD, create=True).fragment(
                    shard, create=True
                )
                for row in range(1, rows_per_shard + 1):
                    frag.bulk_import(
                        np.full(cols.size, row, np.uint64), cols
                    )
                    total_bits += cols.size
            engine_s = time.perf_counter() - t0

            url = f"http://localhost:{server.port}"
            idx.create_field("http")

            def post(path, body, binary=False, raw=False):
                data = (body if binary or raw
                        else _json.dumps(body).encode())
                r = urllib.request.Request(url + path, data=data,
                                           method="POST")
                if binary:
                    r.add_header("Content-Type",
                                 "application/octet-stream")
                with urllib.request.urlopen(r, timeout=300) as resp:
                    return _json.loads(resp.read() or b"{}")

            # (b) HTTP JSON route — bodies pre-encoded OUTSIDE the timer
            # like the protobuf/roaring routes, so the published numbers
            # compare server-side route cost, not client encode cost
            json_bodies = []
            http_bits = 0
            for shard, cols in enumerate(per_shard):
                base = shard * SHARD_WIDTH
                for row in range(1, rows_per_shard + 1):
                    json_bodies.append(_json.dumps({
                        "rows": [row] * cols.size,
                        "columns": (cols + base).tolist(),
                    }).encode())
                    http_bits += cols.size
            t0 = time.perf_counter()
            for body in json_bodies:
                post("/index/b/field/http/import", body, binary=False,
                     raw=True)
            http_s = time.perf_counter() - t0

            # (b2) protobuf import route — the reference's actual client
            # path (ImportRequest bodies)
            from pilosa_tpu import wire

            proto_s = None
            if wire.available():
                from pilosa_tpu.wire.serializer import encode_import_request

                idx.create_field("pb")
                bodies = []
                for shard, cols in enumerate(per_shard):
                    base = shard * SHARD_WIDTH
                    for row in range(1, rows_per_shard + 1):
                        bodies.append(encode_import_request(
                            "b", "pb", np.full(cols.size, row, np.uint64),
                            cols + base,
                        ))
                t0 = time.perf_counter()
                for body in bodies:
                    r = urllib.request.Request(
                        f"{url}/index/b/field/pb/import", data=body,
                        method="POST",
                    )
                    r.add_header("Content-Type", "application/x-protobuf")
                    with urllib.request.urlopen(r, timeout=300):
                        pass
                proto_s = time.perf_counter() - t0

            # (c) binary roaring route (one bitmap per shard carrying
            # every row's bits as row<<20|pos ids)
            idx.create_field("roar")
            payloads = []
            for shard, cols in enumerate(per_shard):
                ids = np.concatenate([
                    (np.uint64(row) << np.uint64(20)) + cols
                    for row in range(1, rows_per_shard + 1)
                ])
                bm = RoaringBitmap()
                bm.add_ids(ids)
                payloads.append(serialize(bm))
            t0 = time.perf_counter()
            for shard, payload in enumerate(payloads):
                post(f"/index/b/field/roar/import-roaring/{shard}",
                     payload, binary=True)
            roaring_s = time.perf_counter() - t0

            ok = True
            checked = ["eng", "http", "roar"] + (
                ["pb"] if proto_s is not None else []
            )
            for fname in checked:
                for row in (1, rows_per_shard):
                    r = urllib.request.Request(
                        f"{url}/index/b/query",
                        data=f"Count(Row({fname}={row}))".encode(),
                        method="POST",
                    )
                    with urllib.request.urlopen(r, timeout=300) as resp:
                        got = _json.loads(resp.read())["results"][0]
                    ok = ok and got == n * n_shards

            # (d) BSI value import — batched bit-plane writes
            # (field.import_values / fragment.import_bsi)
            vfield = idx.create_field(
                "val", FieldOptions(type="int", min=0, max=100000)
            )
            n_vals = total_bits // 2
            vcols = rng.choice(n_shards * SHARD_WIDTH, n_vals,
                               replace=False).astype(np.uint64)
            vvals = rng.integers(0, 100000, n_vals, dtype=np.int64)
            t0 = time.perf_counter()
            vfield.import_values(vcols, vvals)
            values_s = time.perf_counter() - t0
            vprobe = int(vcols[0])
            ok = ok and vfield.value(vprobe) == (int(vvals[0]), True)

            out = {
                "config": "import",
                "metric": "bulk_import_bits_per_sec_engine",
                "value": round(total_bits / engine_s, 1),
                "unit": "bits/sec",
                "http_json_bits_per_sec": round(http_bits / http_s, 1),
                "http_roaring_bits_per_sec": round(total_bits / roaring_s, 1),
                "bits_per_field": total_bits, "shards": n_shards,
                "ok": bool(ok),
            }
            if proto_s is not None:
                out["http_protobuf_bits_per_sec"] = round(
                    total_bits / proto_s, 1
                )
            out["bsi_values_per_sec"] = round(n_vals / values_s, 1)
            return out
        finally:
            server.close()


def _merge_kernel_microbench(n_keys: int = 4096, per_key: int = 24,
                             reps: int = 9, seed: int = 7) -> dict:
    """In-bench merge-kernel gate: the whole-batch merge kernel
    (roaring/merge_kernels.merge_ids) vs the retired per-container
    write loop (bitmap._merge_loop, kept verbatim as the reference) on
    the bulk-import shape — one batch touching MANY containers with a
    couple dozen ids each, where the per-container Python envelope the
    kernel retires dominates. Byte-identity is asserted on EVERY rep
    (serialize equality + changed-count equality); best-of-``reps``
    timing on both sides."""
    from pilosa_tpu.roaring import merge_kernels, serialize
    from pilosa_tpu.roaring.bitmap import RoaringBitmap
    from pilosa_tpu.roaring.format import deserialize

    rng = np.random.default_rng(seed)

    def draw():
        keys = rng.integers(0, n_keys, n_keys * per_key).astype(np.uint64)
        lows = rng.integers(0, 65536, keys.size).astype(np.uint64)
        return np.unique((keys << np.uint64(16)) + lows)

    blob = serialize(RoaringBitmap.from_ids(draw()))
    batch = draw()
    best_kernel = best_loop = float("inf")
    identical = True
    for _ in range(reps):
        bm_k, _ = deserialize(blob)
        t0 = time.perf_counter()
        changed_k = merge_kernels.merge_ids(bm_k, batch.copy(), False)
        best_kernel = min(best_kernel, time.perf_counter() - t0)
        bm_l, _ = deserialize(blob)
        t0 = time.perf_counter()
        changed_l = bm_l._merge_loop(batch.copy(), False)
        best_loop = min(best_loop, time.perf_counter() - t0)
        identical = (identical and changed_k == changed_l
                     and serialize(bm_k) == serialize(bm_l))
    speedup = best_loop / best_kernel if best_kernel else 0.0
    return {
        "shape": {"containers": n_keys, "ids_per_container": per_key,
                  "batch_ids": int(batch.size)},
        "kernel_ms": round(best_kernel * 1e3, 2),
        "loop_ms": round(best_loop * 1e3, 2),
        "speedup": round(speedup, 2),
        "bytes_identical": bool(identical),
        "ok": bool(identical and speedup >= 2.0),
    }


def config_ingest(n_remote: int = 3, n_shards: int = 16,
                  density: float = 0.02, delay_s: float = 0.05) -> dict:
    """Parallel ingest pipeline (ISSUE 3): routed-import fan-out with an
    INJECTED per-call slow client. Proves two things on the same data:

    (a) concurrent fan-out wall time tracks the SLOWEST owner node's
        busy time (max), not the sum of all owners' busy times — the
        write-path analog of the read path's concurrent_map property;
    (b) routed bits/sec with the parallel fan-out beats the serialized
        fan-out (ingest_fanout_workers = 1) on identical batches.

    Also reports the local shard-group apply rate with the bounded
    worker pool on vs off (ingest-workers knob) — engine-layer, no
    injected latency — and runs the merge-kernel microbench (write-path
    fast lane): the whole-batch merge kernel must clear >=2x over the
    retired per-container loop with byte-identity asserted in-bench.

    Core-aware gating (the mp_serving precedent): the fan-out oracles
    are sleep-dominated and gate on any box, and the merge microbench
    is single-threaded numpy-vs-Python so it gates on any box too; only
    the local-apply worker-pool scaling needs real cores — >=6 cores
    enforces >=1.3x, 3-5 cores >=1.1x, below that the box is
    hardware-saturated and the ratio is recorded ungated."""
    import threading

    from pilosa_tpu.parallel.cluster import Cluster, Node
    from pilosa_tpu.server.api import API
    from pilosa_tpu.shardwidth import SHARD_WIDTH
    from pilosa_tpu.storage import Holder

    class SlowClient:
        """Injectable transport: every import call sleeps ``delay``
        (one RTT) and acks the shipped bit count."""

        def __init__(self, delay: float):
            self.delay = delay
            self.per_uri: dict[str, int] = {}
            self._lock = threading.Lock()

        def _hit(self, uri: str, n: int) -> int:
            with self._lock:
                self.per_uri[uri] = self.per_uri.get(uri, 0) + 1
            time.sleep(self.delay)
            return n

        def import_roaring(self, uri, index, field, shard, data):
            from pilosa_tpu.roaring.format import load_any

            bm, _ = load_any(data)
            return self._hit(uri, int(bm.count()))

        def import_bits(self, uri, index, field, rows, columns,
                        timestamps=None, clear=False):
            return self._hit(uri, len(columns))

        def import_values(self, uri, index, field, columns, values,
                          clear=False):
            return self._hit(uri, len(columns))

        def send_message(self, uri, message):
            return {}

    rng = np.random.default_rng(21)
    n = int(SHARD_WIDTH * density)
    cols = np.concatenate([
        s * SHARD_WIDTH
        + np.sort(rng.choice(SHARD_WIDTH, n, replace=False))
        for s in range(n_shards)
    ]).astype(np.int64)
    rows = np.ones(cols.size, np.int64)

    def routed(fanout_workers: int, delay: float):
        with tempfile.TemporaryDirectory() as tmp:
            holder = Holder(tmp).open()
            api = API(holder)
            cluster = Cluster(
                Node("n0", "http://n0"),
                peers=[Node(f"n{i}", f"http://n{i}")
                       for i in range(1, n_remote + 1)],
                replica_n=1, holder=holder,
            )
            cluster.api = api
            api.cluster = cluster
            fake = SlowClient(delay)
            cluster.client = fake
            holder.create_index("b").create_field("f")
            api.ingest_fanout_workers = fanout_workers
            t0 = time.perf_counter()
            changed = api.import_bits("b", "f", rows, cols)
            wall = time.perf_counter() - t0
            holder.close()
            busy = {u: c * delay for u, c in fake.per_uri.items()}
            return wall, changed, busy

    wall_par, changed_par, busy = routed(16, delay_s)
    wall_ser, changed_ser, _ = routed(1, delay_s)
    # zero-delay pass isolates the route's fixed cost (slicing, roaring
    # serialization, local apply) so the delay-attributable remainder can
    # be compared against max vs sum of the injected node busy times
    wall_base, _, _ = routed(16, 0.0)
    sum_busy = sum(busy.values())
    max_busy = max(busy.values()) if busy else 0.0

    def engine(workers: int) -> float:
        with tempfile.TemporaryDirectory() as tmp:
            holder = Holder(tmp).open()
            api = API(holder)
            api.ingest_workers = workers
            holder.create_index("b").create_field("f")
            t0 = time.perf_counter()
            api.import_bits("b", "f", rows, cols)
            dt = time.perf_counter() - t0
            holder.close()
            return dt

    eng_ser = engine(1)
    eng_par = engine(4)

    # core-aware local-apply gate: the bounded worker pool shares this
    # box's cores with the bench driver itself, so scaling is only
    # measurable with real cores to spread onto (mp_serving precedent)
    cores = os.cpu_count() or 1
    eng_ratio = eng_ser / eng_par if eng_par else 0.0
    if cores >= 6:
        eng_ok, eng_gate = eng_ratio >= 1.3, "local-apply >= 1.3x"
    elif cores >= 3:
        eng_ok, eng_gate = eng_ratio >= 1.1, "local-apply >= 1.1x"
    else:
        eng_ok = True
        eng_gate = ("ungated: hardware-saturated (< 3 cores); ratio "
                    "recorded, fan-out + merge-kernel oracles still gate")

    merge = _merge_kernel_microbench()

    delay_wall = max(wall_par - wall_base, 0.0)
    ok = (changed_par == changed_ser == cols.size
          # delay-attributable fan-out time tracks the slowest node's
          # busy time (max), NOT the sum over nodes
          and delay_wall < (max_busy + sum_busy) / 2
          # parallel routed path beats the serialized one on same data
          and wall_par < 0.75 * wall_ser
          and eng_ok and merge["ok"])
    return {
        "config": "ingest",
        "metric": "routed_import_bits_per_sec",
        "value": round(cols.size / wall_par, 1),
        "unit": "bits/sec",
        "serial_routed_bits_per_sec": round(cols.size / wall_ser, 1),
        "speedup_vs_serial_fanout": round(wall_ser / wall_par, 2),
        "fanout_wall_ms": round(wall_par * 1e3, 1),
        "fanout_wall_serial_ms": round(wall_ser * 1e3, 1),
        "fanout_wall_nodelay_ms": round(wall_base * 1e3, 1),
        "slowest_node_busy_ms": round(max_busy * 1e3, 1),
        "sum_node_busy_ms": round(sum_busy * 1e3, 1),
        "local_apply_bits_per_sec_serial": round(cols.size / eng_ser, 1),
        "local_apply_bits_per_sec_parallel": round(cols.size / eng_par, 1),
        "local_apply_scaling": round(eng_ratio, 2),
        "cores": cores,
        "local_apply_gate": eng_gate,
        "merge_kernel": merge,
        "nodes": n_remote + 1, "shards": n_shards,
        "bits": int(cols.size), "injected_delay_ms": delay_s * 1e3,
        "ok": bool(ok),
    }


def config_sync(n_fragments: int = 192, n_divergent: int = 32,
                rows_per_block: int = 12, bits_per_row: int = 400,
                rounds: int = 2, injected_rtt_s: float = 0.005) -> dict:
    """Anti-entropy fast path (ISSUE 5): the SAME seeded divergence
    repaired against identical source clusters over two transports —

    - ``legacy``: the r5 per-fragment path end to end (catalog walk + one
      ``fragment_blocks`` GET per fragment + one block-data GET per
      differing block, serial pass), forced via the old-wire fallback
      (``_no_manifest_peers`` + ``sync_workers = 1``);
    - ``fastpath``: one batched manifest per peer, multi-block delta
      POSTs, ``sync-workers``-wide pipeline, compressed payloads.

    The SOURCE node runs as a real OS subprocess (``python -m pilosa_tpu
    server``, like tests/test_process_cluster.py) so the measured RTTs
    cross a process boundary the way production DCN hops do — two
    in-process nodes share one GIL, which flattens exactly the
    concurrency the pipeline exploits. The repairer stays in-process for
    instrumentation (RTT/byte counting on its connection pool).

    ``injected_rtt_s`` adds a fixed per-request transport delay to BOTH
    modes (the config_ingest precedent: loopback under-prices a network
    round trip by ~50×, and the fast path's whole claim is paying fewer
    of them; 5 ms is a conservative inter-host DCN hop). The shared local
    work — checksum walks, block merges — is identical either way and
    paid for real.

    Measures control-plane round trips, bytes on the wire, and repair
    wall time; ok requires byte-identical post-repair fragments across
    the two modes, ≥5× fewer RTTs, and ≥2× lower wall. A final phase
    re-runs a paced repair (`repair-max-bytes-per-sec`) under a
    concurrent serving client and reports the query p95 — resize storms
    must not starve serving."""
    import os
    import socket
    import subprocess
    import sys
    import threading
    import urllib.request

    from pilosa_tpu.roaring import RoaringBitmap
    from pilosa_tpu.roaring.format import serialize
    from pilosa_tpu.server import Server, ServerConfig
    from pilosa_tpu.shardwidth import SHARD_WIDTH
    from pilosa_tpu.storage.view import VIEW_STANDARD

    def post(port, path, data, binary=False):
        r = urllib.request.Request(
            f"http://127.0.0.1:{port}{path}", data=data, method="POST"
        )
        if binary:
            r.add_header("Content-Type", "application/octet-stream")
        with urllib.request.urlopen(r, timeout=120) as resp:
            return resp.read()

    def free_port() -> int:
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            return s.getsockname()[1]

    # deterministic payloads, built once: base data for every fragment
    # (replicated) + WIDE, shallow divergence (a missed write here and
    # there across many fragments — the anti-entropy steady state, where
    # control RTTs dominate the repair and the fast path pays off)
    rng = np.random.default_rng(17)
    base_payloads = []
    for _ in range(n_fragments):
        rows = np.repeat(np.arange(rows_per_block, dtype=np.uint64), 64)
        poss = rng.integers(0, SHARD_WIDTH, rows.size, dtype=np.uint64)
        bm = RoaringBitmap()
        bm.add_ids((rows << np.uint64(20)) + poss)
        base_payloads.append(serialize(bm))
    div_payloads = []
    for _ in range(n_divergent):
        rows = np.repeat(np.arange(3, dtype=np.uint64), bits_per_row)
        poss = np.concatenate([
            rng.choice(SHARD_WIDTH, bits_per_row,
                       replace=False).astype(np.uint64)
            for _ in range(3)
        ])
        bm = RoaringBitmap()
        bm.add_ids((rows << np.uint64(20)) + poss)
        div_payloads.append(serialize(bm))

    def spawn_source(tmp) -> tuple:
        """Boot the divergence source as a separate OS process and seed
        it over HTTP (?remote=true applies locally, no fan-out)."""
        port = free_port()
        args = [
            sys.executable, "-m", "pilosa_tpu", "server",
            "--data-dir", f"{tmp}/src", "--bind", "127.0.0.1",
            "--port", str(port),
        ]
        env = {
            **os.environ, "JAX_PLATFORMS": "cpu",
            "PILOSA_TPU_NAME": "src",
            "PILOSA_TPU_ANTI_ENTROPY_INTERVAL": "0",
            "PILOSA_TPU_HEARTBEAT_INTERVAL": "0",
            "PILOSA_TPU_USE_MESH": "false",
        }
        proc = subprocess.Popen(args, env=env,
                                stdout=subprocess.DEVNULL,
                                stderr=subprocess.STDOUT)
        for _ in range(240):
            if proc.poll() is not None:
                raise AssertionError(f"source exited rc={proc.returncode}")
            try:
                urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/status", timeout=5
                ).read()
                break
            except Exception:
                time.sleep(0.25)
        else:
            proc.terminate()
            raise AssertionError("source never served /status")
        # trackExistence off: the HTTP imports below would otherwise
        # populate the _exists field on the source only, drowning the
        # seeded divergence in existence-bit repair traffic
        post(port, "/index/i",
             b'{"options": {"trackExistence": false}}')
        post(port, "/index/i/field/f", b"{}")
        for shard, payload in enumerate(base_payloads):
            post(port,
                 f"/index/i/field/f/import-roaring/{shard}?remote=true",
                 payload, binary=True)
        for shard, payload in enumerate(div_payloads):
            post(port,
                 f"/index/i/field/f/import-roaring/{shard}?remote=true",
                 payload, binary=True)
        return proc, port

    def make_repairer(tmp, src_port, legacy: bool) -> "Server":
        """In-process repairer holding only the BASE data. Membership is
        wired directly (no seed join — the join path's gated self-join
        fetch would repair the divergence before the measured pass)."""
        from pilosa_tpu.parallel.cluster import Node

        s1 = Server(ServerConfig(
            data_dir=f"{tmp}/rep", port=0, name="rep", replica_n=2,
            anti_entropy_interval=0, heartbeat_interval=0,
            use_mesh=False,
        )).open()
        s1.holder.create_index("i", track_existence=False).create_field("f")
        f1 = s1.holder.index("i").field("f")
        view = f1.view(VIEW_STANDARD, create=True)
        for shard, payload in enumerate(base_payloads):
            view.fragment(shard, create=True).import_roaring(payload)
        s1.api.cluster.nodes["src"] = Node(
            "src", f"http://127.0.0.1:{src_port}"
        )
        if legacy:
            s1.api.cluster.sync_workers = 1
            s1.api.cluster.client._no_manifest_peers.add(
                f"http://127.0.0.1:{src_port}"
            )
        return s1

    def run_mode(legacy: bool):
        best_wall = float("inf")
        rtts = bytes_wire = repaired = snap = converged = None
        for _ in range(rounds):
            with tempfile.TemporaryDirectory() as tmp:
                proc, src_port = spawn_source(tmp)
                s1 = make_repairer(tmp, src_port, legacy)
                try:
                    pool = s1.api.cluster.client.pool
                    counts = {"rtts": 0, "bytes": 0}
                    real = pool.request

                    def counting(method, url, body=None, headers=None,
                                 timeout=None, real=real, counts=counts):
                        if injected_rtt_s > 0:
                            time.sleep(injected_rtt_s)
                        resp = real(method, url, body=body,
                                    headers=headers, timeout=timeout)
                        counts["rtts"] += 1
                        counts["bytes"] += (
                            len(body or b"") + len(resp.data)
                        )
                        return resp

                    pool.request = counting
                    t0 = time.perf_counter()
                    rep = s1.api.cluster.sync_holder()
                    dt = time.perf_counter() - t0
                    pool.request = real
                    f1 = s1.holder.index("i").field("f")
                    snap = b"".join(
                        f1.view(VIEW_STANDARD).fragment(s)
                        .serialize_snapshot()
                        for s in range(n_fragments)
                    )
                    # convergence oracle: the repairer's checksums match
                    # the source's, fetched by an independent client
                    from pilosa_tpu.parallel.client import InternalClient

                    oracle = InternalClient()
                    src_manifest = dict(
                        ((f, v, s), dict(blocks)) for f, v, s, blocks in
                        oracle.sync_manifest(
                            f"http://127.0.0.1:{src_port}", "i")
                    )
                    oracle.pool.close()
                    converged = all(
                        dict(f1.view(VIEW_STANDARD).fragment(s).blocks())
                        == src_manifest.get(("f", VIEW_STANDARD, s), {})
                        for s in range(n_fragments)
                    )
                    rtts, bytes_wire = counts["rtts"], counts["bytes"]
                    repaired = rep
                    best_wall = min(best_wall, dt)
                finally:
                    s1.close()
                    proc.terminate()
                    proc.wait(timeout=30)
        return {
            "rtts": rtts, "bytes": bytes_wire,
            "wall_ms": round(best_wall * 1e3, 1),
            "bits_repaired": repaired["bits"], "converged": converged,
            "snapshot": snap,
        }

    legacy = run_mode(True)
    fast = run_mode(False)
    byte_identical = legacy.pop("snapshot") == fast.pop("snapshot")
    rtt_factor = round(legacy["rtts"] / max(fast["rtts"], 1), 2)
    wall_factor = round(legacy["wall_ms"] / max(fast["wall_ms"], 1e-9), 2)

    # paced repair under concurrent serving: the pacer must shape the
    # transfer without starving queries on the repairing node
    with tempfile.TemporaryDirectory() as tmp:
        proc, src_port = spawn_source(tmp)
        s1 = make_repairer(tmp, src_port, legacy=False)
        try:
            from pilosa_tpu.parallel.pacer import RepairPacer

            # rate sized so the divergent payload takes a visible ~1-2 s
            s1.api.cluster.client.pacer = RepairPacer(
                max_bytes_per_sec=64_000, max_inflight=2,
            )
            latencies: list = []
            stop = threading.Event()

            def serve():
                while not stop.is_set():
                    t0 = time.perf_counter()
                    post(s1.port,
                         "/index/i/query?shards=0,1,2,3",
                         b"Count(Row(f=1))")
                    latencies.append(time.perf_counter() - t0)

            t = threading.Thread(target=serve, daemon=True)
            post(s1.port, "/index/i/query?shards=0,1,2,3",
                 b"Count(Row(f=1))")  # warm the compile
            t.start()
            t0 = time.perf_counter()
            s1.api.cluster.sync_holder()
            paced_wall = time.perf_counter() - t0
            stop.set()
            t.join(30)
            paced_sleep = s1.api.cluster.client.pacer.paced_sleep_s
            p95 = (float(np.quantile(latencies, 0.95))
                   if latencies else None)
        finally:
            s1.close()
            proc.terminate()
            proc.wait(timeout=30)

    ok = (byte_identical
          and legacy["converged"] and fast["converged"]
          and legacy["bits_repaired"] == fast["bits_repaired"] > 0
          and rtt_factor >= 5.0
          and wall_factor >= 2.0
          and paced_sleep > 0          # the pacer actually shaped traffic
          and p95 is not None and p95 < 1.0)
    return {
        "config": "sync",
        "metric": "repair_control_rtt_reduction_factor",
        "value": rtt_factor,
        "unit": "x fewer round trips",
        "wall_speedup": wall_factor,
        "legacy": {k: legacy[k] for k in
                   ("rtts", "bytes", "wall_ms", "bits_repaired")},
        "fastpath": {k: fast[k] for k in
                     ("rtts", "bytes", "wall_ms", "bits_repaired")},
        "byte_identical_post_repair": byte_identical,
        "paced_repair": {
            "wall_ms": round(paced_wall * 1e3, 1),
            "paced_sleep_ms": round(paced_sleep * 1e3, 1),
            "serving_p95_ms_during_repair": (
                round(p95 * 1e3, 1) if p95 is not None else None
            ),
            "serving_samples": len(latencies),
        },
        "fragments": n_fragments, "divergent": n_divergent,
        "injected_rtt_ms": injected_rtt_s * 1e3,
        "ok": bool(ok),
    }


def config_hostpath(n_shards: int = 8) -> dict:
    """Host-path gate, two halves (ISSUE 18):

    1. **Roaring kernel microbenches** — the three host paths the
       vectorized kernel layer (pilosa_tpu/roaring/kernels.py)
       rewired: row **decode** (residency miss), **scrub**-style block
       digesting, and **sync** manifest-diff block materialization.
       Each is timed against an in-bench copy of the retired
       per-container loop over the SAME fragment, asserted
       byte-identical, and gated at >= 2x. PROFILE-tree attribution
       (containers scanned by kind, one tally per kernel call) rides
       the decode half.
    2. **Executor submit** — host cost of the pipelined submit path
       with the batched device program stubbed (parse -> plan cache ->
       operand memo -> micro-batch group), tracked as a number so a
       serving-path host regression shows up as a regression."""
    kernels_half = _hostpath_kernel_microbenches()
    submit_half = _hostpath_submit(n_shards)
    return {
        "config": "hostpath",
        "metric": "hostpath_kernel_speedups",
        "microbenches": kernels_half["microbenches"],
        "min_speedup": kernels_half["min_speedup"],
        "bytes_identical": kernels_half["bytes_identical"],
        "profile_attribution": kernels_half["profile_attribution"],
        "submit": submit_half,
        "ok": bool(kernels_half["ok"] and submit_half["ok"]),
        "note": ("kernel microbenches: batched numpy kernels vs the "
                 "retired per-container reference loops, byte-identical "
                 "outputs asserted in-bench, gate >= 2x on each of "
                 "decode/scrub/sync. submit: Executor.submit with the "
                 "batched device program stubbed (see submit.note)."),
    }


def _hostpath_kernel_microbenches() -> dict:
    """Scrub / sync / decode against per-container reference loops."""
    import tempfile

    from pilosa_tpu.roaring import kernels
    from pilosa_tpu.storage.fragment import BLOCK_ROWS, Fragment
    from pilosa_tpu.storage.integrity import block_digests
    from pilosa_tpu.utils.cost import (
        QueryProfile,
        activate_cost,
        deactivate_cost,
        new_cost_context,
        use_node,
    )

    rng = np.random.default_rng(18)

    # ------------------------------------------ per-container references
    # (verbatim shape of the retired loops — tests/test_roaring_kernels
    # pins byte-identity; here they are the baseline being beaten)

    def ref_to_ids(bm) -> np.ndarray:
        parts = []
        for key in bm.keys:
            c = bm._containers.get(key)
            if c is None or not c.n:
                continue
            parts.append((np.uint64(key) << np.uint64(16))
                         + c.lows().astype(np.uint64))
        if not parts:
            return np.empty(0, np.uint64)
        return np.concatenate(parts)

    def ref_row_words(bm, row: int) -> np.ndarray:
        return bm.dense_range_words32(row << 20, (row + 1) << 20)

    def ref_block_ids(ids: np.ndarray, blocks) -> dict:
        width = np.uint64(BLOCK_ROWS << 20)
        out = {}
        for b in blocks:
            lo = np.uint64(b) * width
            out[int(b)] = ids[(ids >= lo) & (ids < lo + width)]
        return out

    def best_of(fn, repeats: int = 5) -> float:
        best = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            fn()
            best = min(best, time.perf_counter() - t0)
        return best

    micro = {}
    identical = True
    with tempfile.TemporaryDirectory() as tmp:
        frag = Fragment(f"{tmp}/f", "i", "f", "standard", 0).open()
        # genuinely mixed-kind fragment across many blocks: mostly
        # sparse array rows (~4 set bits per container), some mid-density
        # array rows (~437 per container), a few bitmap rows (8000 per
        # container, past the 4096 array ceiling), and run rows
        rows, cols = [], []
        for r in range(0, 220, 2):
            if r % 44 == 0:  # bitmap row: every container dense
                for k in range(16):
                    rows.append(np.full(8000, r, np.uint64))
                    cols.append((np.uint64(k) << np.uint64(16))
                                + rng.choice(1 << 16, 8000,
                                             replace=False).astype(np.uint64))
            else:
                n = 7000 if r % 6 == 2 else 60
                rows.append(np.full(n, r, np.uint64))
                cols.append(rng.integers(0, 1 << 20, n, dtype=np.uint64))
        for r in (221, 223):
            rows.append(np.full(120000, r, np.uint64))
            cols.append(np.arange(120000, dtype=np.uint64))
        frag.bulk_import(np.concatenate(rows), np.concatenate(cols))
        bm = frag.bitmap

        # decode: residency-miss dense row materialization over a kind
        # mix (sparse + mid arrays dominate, as on a real fragment, plus
        # a bitmap row and a run row), PROFILE attribution on the
        # kernel side
        dense_rows = ([r for r in range(0, 220, 2)
                       if r % 44 and r % 6 != 2][:16]
                      + [r for r in range(0, 220, 2) if r % 6 == 2][:4]
                      + [0, 221])
        profile = QueryProfile("i", "hostpath-bench")
        ctx = new_cost_context("bench", "i", profile=profile)
        node = profile.node_for(0, None)
        tok = activate_cost(ctx)
        try:
            with use_node(ctx, node):
                got_rows = [frag.row_words(r) for r in dense_rows]
        finally:
            deactivate_cost(tok)
        want_rows = [ref_row_words(bm, r) for r in dense_rows]
        identical &= all(np.array_equal(g, w)
                         for g, w in zip(got_rows, want_rows))
        t_kernel = best_of(
            lambda: [frag.row_words(r) for r in dense_rows])
        t_ref = best_of(
            lambda: [ref_row_words(bm, r) for r in dense_rows])
        micro["decode"] = {
            "reference_us": round(t_ref * 1e6, 1),
            "kernel_us": round(t_kernel * 1e6, 1),
            "speedup": round(t_ref / t_kernel, 2) if t_kernel else 0.0,
        }
        profile_attr = {
            "containers_scanned": {
                "array": ctx.c_array, "bitmap": ctx.c_bitmap,
                "run": ctx.c_run,
            },
            "kernel_calls": len(dense_rows),
            "note": ("one note_containers tally per kernel call on the "
                     "batched path; totals equal the per-container walk "
                     "(pinned by tests/test_roaring_kernels.py)"),
        }

        # scrub: verified-load id materialization straight off the
        # serialized snapshot bytes (verify_fragment_file's
        # build_bitmap=False path and the scrubber's replica-copy
        # checksum both reduce to this). The timed half is the part the
        # kernels changed — bytes -> sorted ids; the blake2b digesting
        # that follows consumes byte-identical input on both sides and
        # is reported once as a constant.
        from pilosa_tpu.roaring.format import deserialize, serialize

        snap = serialize(bm)

        def scrub_kernel():
            return kernels.snapshot_ids(snap)[0]

        def scrub_ref():
            # the retired path: container-object decode, then the
            # per-container lows() walk (live to_ids now rides the
            # kernels, so the walk is reconstructed in-bench)
            return ref_to_ids(deserialize(snap)[0])

        # time first, verify after: the identity checks materialize
        # multi-MB byte strings, and leaving those on the heap during
        # timing skews BOTH sides with allocator (mmap) churn
        t_kernel = best_of(scrub_kernel)
        t_ref = best_of(scrub_ref)
        ids_k, ids_r = scrub_kernel(), scrub_ref()
        identical &= bool(np.array_equal(ids_k, ids_r))
        identical &= (block_digests(ids_k, BLOCK_ROWS)
                      == block_digests(ids_r, BLOCK_ROWS))
        t_digest = best_of(lambda: block_digests(ids_k, BLOCK_ROWS))
        micro["scrub"] = {
            "reference_us": round(t_ref * 1e6, 1),
            "kernel_us": round(t_kernel * 1e6, 1),
            "speedup": round(t_ref / t_kernel, 2) if t_kernel else 0.0,
            "digest_us_both_sides": round(t_digest * 1e6, 1),
        }

        # sync: a manifest diff wants N divergent blocks — materialize
        # their id sets (http.post_sync_blocks serves exactly this)
        wanted = sorted({int(r) // BLOCK_ROWS
                         for r in range(0, 220, 2)})

        def sync_kernel():
            return frag.blocks_ids(wanted)

        def sync_ref():
            return ref_block_ids(ref_to_ids(bm), wanted)

        gk, gr = sync_kernel(), sync_ref()
        identical &= (sorted(gk) == sorted(gr) and all(
            gk[b].tobytes() == gr[b].tobytes() for b in gk))
        t_kernel = best_of(sync_kernel)
        t_ref = best_of(sync_ref)
        micro["sync"] = {
            "reference_us": round(t_ref * 1e6, 1),
            "kernel_us": round(t_kernel * 1e6, 1),
            "speedup": round(t_ref / t_kernel, 2) if t_kernel else 0.0,
        }
        frag.close()

    min_speedup = min(m["speedup"] for m in micro.values())
    return {
        "microbenches": micro,
        "min_speedup": min_speedup,
        "bytes_identical": bool(identical),
        "profile_attribution": profile_attr,
        "ok": bool(identical and min_speedup >= 2.0),
    }


def _hostpath_submit(n_shards: int = 8) -> dict:
    """Host-side cost of the pipelined submit path, device excluded.

    The executor-vs-kernel ratio is bounded by how fast the HOST can
    feed micro-batched dispatches (parse -> plan cache -> operand memo ->
    micro-batch group), so this config times `Executor.submit` with the
    batched program stubbed out: pure framework cost per query, in
    microseconds, with the operand memo on and off. CPU-representative
    (no device work is dispatched); tracked so a serving-path host
    regression shows up as a number, not a vibe."""
    import itertools
    import tempfile

    from pilosa_tpu.executor import Executor
    from pilosa_tpu.shardwidth import SHARD_WIDTH
    from pilosa_tpu.storage import Holder
    from pilosa_tpu.storage.view import VIEW_STANDARD

    K = 8
    rng = np.random.default_rng(7)
    with tempfile.TemporaryDirectory() as tmp:
        holder = Holder(tmp).open()
        idx = holder.create_index("b")
        rows = np.repeat(np.arange(1, K + 1, dtype=np.uint64), 64)
        for fname in ("a", "b"):
            f = idx.create_field(fname)
            view = f.view(VIEW_STANDARD, create=True)
            for shard in range(n_shards):
                cols = rng.integers(0, SHARD_WIDTH, rows.size,
                                    dtype=np.uint64)
                view.fragment(shard, create=True).bulk_import(rows, cols)

        def pql(k, j):
            return f"Count(Intersect(Row(a={k}), Row(b={j})))"

        def combo(g):
            n = K * K
            c = (5 * g + g // n) % n
            return 1 + c // K, 1 + c % K

        def measure(memo_on: bool) -> float:
            ex = Executor(holder)
            if not memo_on:
                # disable by forcing the per-plan bypass
                orig = ex._eval_operands
                ex._eval_operands = (
                    lambda idx, c, b, extra_leaves=(), memoize=True:
                    orig(idx, c, b, extra_leaves, memoize=False)
                )
            for k in range(1, K + 1):
                ex.execute("b", pql(k, k))
            g = itertools.count(0)
            warm = [ex.submit("b", pql(*combo(next(g))))[0]
                    for _ in range(70)]
            warm[-1].result()
            stub = np.zeros((ex.microbatch_max, 2), np.int32)
            ex._program_batched = lambda *a, **k: (lambda *args: stub)
            n = 4096
            best = float("inf")
            for _ in range(4):
                t0 = time.perf_counter()
                for _ in range(n):
                    ex.submit("b", pql(*combo(next(g))))
                best = min(best, (time.perf_counter() - t0) / n)
            return best

        on = measure(True)
        off = measure(False)
        holder.close()
    return {
        "metric": "submit_host_us_per_query",
        "value": round(on * 1e6, 1),
        "unit": "us/query",
        "memo_off_us": round(off * 1e6, 1),
        "per_dispatch_ms_at_16": round(on * 16 * 1e3, 3),
        "shards": n_shards,
        "ok": True,
        "note": ("Executor.submit with the batched device program stubbed: "
                 "parse + plan cache + operand memo + micro-batch group "
                 "cost per query. memo_off_us re-measures with the operand "
                 "memo bypassed (the delta is what the memo buys)."),
    }


def config_tracing(n_shards: int = 8, n_queries: int = 256,
                   n_clients: int = 32, repeats: int = 4) -> dict:
    """Tracing overhead gate (ISSUE 7): the observability plane must be
    effectively free when off and cheap when sampling.

    One in-process server, keep-alive clients (the fast-lane transport),
    four plateau passes on the SAME data/queries, best-of-``repeats``:

    - ``bare``: trace sampling 0 AND the in-flight inspector disabled —
      the fast-lane serving plateau with every observability hook on its
      cheapest path. This is the baseline.
    - ``off``: shipping defaults — sampling 0, inspector ON (the
      /debug/queries view is always-on in production). Gate: >= 99% of
      bare (disabled tracing costs <= 1%).
    - ``sampled``: trace-sample-rate 0.01. Gate: >= 95% of bare
      (1%-sampled tracing costs <= 5%).
    - ``full``: rate 1.0 — informational: what always-on tracing costs.

    Sanity oracle: the full pass must actually produce span trees whose
    roots are http.query with executor + wave children, and the
    in-flight tracker must be empty once the run drains."""
    import http.client as _hc
    import threading

    from pilosa_tpu.server import Server, ServerConfig
    from pilosa_tpu.shardwidth import SHARD_WIDTH
    from pilosa_tpu.storage.view import VIEW_STANDARD
    from pilosa_tpu.utils.tracing import (
        global_query_tracker,
        global_tracer,
    )

    rng = np.random.default_rng(11)
    tracer = global_tracer()
    tracker = global_query_tracker()
    with tempfile.TemporaryDirectory() as tmp:
        server = Server(ServerConfig(
            data_dir=tmp, port=0, name="bench-tracing",
            anti_entropy_interval=0, heartbeat_interval=0,
        )).open()
        try:
            idx = server.holder.create_index("t")
            f = idx.create_field("f")
            n = int(SHARD_WIDTH * 0.05)
            for shard in range(n_shards):
                frag = f.view(VIEW_STANDARD, create=True).fragment(
                    shard, create=True
                )
                for row in range(1, 5):
                    frag.bulk_import(
                        np.full(n, row, np.uint64),
                        rng.choice(SHARD_WIDTH, n, replace=False).astype(
                            np.uint64
                        ),
                    )
            server.api.cluster.note_local_shards("t", list(range(n_shards)))
            port = server.port
            queries = [
                "Count(Intersect(Row(f={}), Row(f={})))".format(
                    1 + (i % 4), 1 + ((i + 1) % 4))
                for i in range(n_queries)
            ]

            def run_once() -> float:
                results = [None] * n_queries
                errors: list = []
                gate = threading.Event()

                def worker(tid):
                    conn = _hc.HTTPConnection("localhost", port,
                                              timeout=120)
                    gate.wait(30)
                    for k in range(tid, n_queries, n_clients):
                        try:
                            conn.request("POST", "/index/t/query",
                                         body=queries[k].encode())
                            results[k] = conn.getresponse().read()
                        except Exception as e:  # surfaced below
                            errors.append(repr(e))
                    conn.close()

                threads = [threading.Thread(target=worker, args=(t,))
                           for t in range(n_clients)]
                for t in threads:
                    t.start()
                t0 = time.perf_counter()
                gate.set()
                for t in threads:
                    t.join(300)
                if errors or None in results:
                    raise RuntimeError(f"bench errors: {errors[:3]}")
                return n_queries / (time.perf_counter() - t0)

            run_once()  # warm: compiles the batched program shapes

            def plateau(sample_rate: float, inspector: bool) -> float:
                tracer.sample_rate = sample_rate
                tracker.enabled = inspector
                try:
                    return max(run_once() for _ in range(repeats))
                finally:
                    tracer.sample_rate = 0.0
                    tracker.enabled = True

            bare = plateau(0.0, inspector=False)
            off = plateau(0.0, inspector=True)
            sampled = plateau(0.01, inspector=True)
            full = plateau(1.0, inspector=True)

            # sanity oracle on the full pass's trees
            trees = tracer.recent()
            roots = {t["name"] for t in trees}
            span_names: set = set()

            def walk(node):
                span_names.add(node["name"])
                for c in node.get("children", []):
                    walk(c)

            for t in trees:
                walk(t)
            traces_ok = (
                "http.query" in roots
                and "executor.Execute" in span_names
                and "pipeline.wave" in span_names
            )
            drained = not tracker.snapshot()
        finally:
            tracer.sample_rate = 0.0
            tracker.enabled = True
            server.close()

    off_ratio = off / max(bare, 1e-9)
    sampled_ratio = sampled / max(bare, 1e-9)
    ok = (off_ratio >= 0.99 and sampled_ratio >= 0.95
          and traces_ok and drained)
    return {
        "config": "tracing",
        "metric": "tracing_off_plateau_ratio",
        "value": round(off_ratio, 4),
        "unit": "fraction of bare fast-lane plateau",
        "bare_qps": round(bare, 1),
        "off_qps": round(off, 1),
        "sampled_1pct_qps": round(sampled, 1),
        "full_sampled_qps": round(full, 1),
        "sampled_ratio": round(sampled_ratio, 4),
        "full_ratio": round(full / max(bare, 1e-9), 4),
        "traces_ok": bool(traces_ok),
        "inflight_drained": bool(drained),
        "queries": n_queries, "clients": n_clients, "shards": n_shards,
        "gates": {"off_vs_bare": ">=0.99", "sampled_vs_bare": ">=0.95"},
        "ok": bool(ok),
    }


def config_profiling(n_shards: int = 8, n_queries: int = 256,
                     n_clients: int = 32, repeats: int = 4) -> dict:
    """Query-cost-plane overhead gate (ISSUE 8): accounting must be
    effectively free when nobody asks for a profile, and PROFILE itself
    must stay cheap enough to run against production traffic.

    One in-process server, keep-alive clients, three plateau passes on
    the SAME data/queries, best-of-``repeats``:

    - ``bare``: the cost plane disabled entirely
      (utils/cost.set_cost_enabled(False)) — every hook on its
      cheapest predicate path. The baseline.
    - ``off``: shipping defaults — plane on (tenant ledger, heat map,
      SLO feed), no ?profile= param. Gate: >= 99% of bare.
    - ``on``: every request carries ?profile=true (per-AST-node tree,
      per-leaf records, result-cardinality popcounts). Gate: >= 90% of
      bare — PROFILE is a debugging surface, but one you can leave on.

    Sanity oracles: the on pass actually returns profile trees with
    calls + totals, the ledger counted the off+on traffic, and the heat
    map ranks the queried field hot."""
    import http.client as _hc
    import threading

    from pilosa_tpu.server import Server, ServerConfig
    from pilosa_tpu.shardwidth import SHARD_WIDTH
    from pilosa_tpu.storage.heat import global_heat
    from pilosa_tpu.storage.view import VIEW_STANDARD
    from pilosa_tpu.utils.cost import set_cost_enabled

    rng = np.random.default_rng(13)
    with tempfile.TemporaryDirectory() as tmp:
        server = Server(ServerConfig(
            data_dir=tmp, port=0, name="bench-profiling",
            anti_entropy_interval=0, heartbeat_interval=0,
        )).open()
        try:
            idx = server.holder.create_index("p")
            f = idx.create_field("f")
            n = int(SHARD_WIDTH * 0.05)
            for shard in range(n_shards):
                frag = f.view(VIEW_STANDARD, create=True).fragment(
                    shard, create=True
                )
                for row in range(1, 5):
                    frag.bulk_import(
                        np.full(n, row, np.uint64),
                        rng.choice(SHARD_WIDTH, n, replace=False).astype(
                            np.uint64
                        ),
                    )
            server.api.cluster.note_local_shards("p", list(range(n_shards)))
            port = server.port
            queries = [
                "Count(Intersect(Row(f={}), Row(f={})))".format(
                    1 + (i % 4), 1 + ((i + 1) % 4))
                for i in range(n_queries)
            ]

            def run_once(profile: bool) -> float:
                suffix = "?profile=true" if profile else ""
                results = [None] * n_queries
                errors: list = []
                gate = threading.Event()

                def worker(tid):
                    conn = _hc.HTTPConnection("localhost", port,
                                              timeout=120)
                    gate.wait(30)
                    for k in range(tid, n_queries, n_clients):
                        try:
                            conn.request("POST",
                                         f"/index/p/query{suffix}",
                                         body=queries[k].encode())
                            results[k] = conn.getresponse().read()
                        except Exception as e:  # surfaced below
                            errors.append(repr(e))
                    conn.close()

                threads = [threading.Thread(target=worker, args=(t,))
                           for t in range(n_clients)]
                for t in threads:
                    t.start()
                t0 = time.perf_counter()
                gate.set()
                for t in threads:
                    t.join(300)
                if errors or None in results:
                    raise RuntimeError(f"bench errors: {errors[:3]}")
                if profile:
                    sample = json.loads(results[0])
                    prof = sample.get("profile") or {}
                    if not (prof.get("calls")
                            and prof.get("totals") is not None):
                        raise RuntimeError(
                            "profiled response missing profile tree")
                return n_queries / (time.perf_counter() - t0)

            run_once(False)  # warm: compiles the batched program shapes

            def one_pass(enabled: bool, profile: bool) -> float:
                set_cost_enabled(enabled)
                try:
                    return run_once(profile)
                finally:
                    set_cost_enabled(True)

            # INTERLEAVED rounds (bare, off, on back to back per round)
            # gated on the BEST per-round ratio — the suite-wide best-of
            # philosophy: machine-load drift on a shared CI box only
            # ever makes the hook path look slower than it is, so if any
            # round shows off >= 0.99x bare under identical conditions
            # the intrinsic overhead is within the contract (the
            # microbenchmarked hook cost is ~5us/request ~= 0.4%). The
            # median ratio is reported beside it for drift visibility.
            rounds = []
            for _ in range(repeats):
                rounds.append((one_pass(False, profile=False),
                               one_pass(True, profile=False),
                               one_pass(True, profile=True)))
            bare = max(r[0] for r in rounds)
            off = max(r[1] for r in rounds)
            on = max(r[2] for r in rounds)
            off_ratios = sorted(r[1] / r[0] for r in rounds)
            on_ratios = sorted(r[2] / r[0] for r in rounds)
            off_ratio = off_ratios[-1]
            on_ratio = on_ratios[-1]
            off_median = off_ratios[len(off_ratios) // 2]
            on_median = on_ratios[len(on_ratios) // 2]

            ledger_rows = server.api.cost.snapshot()
            ledger_ok = (ledger_rows
                         and ledger_rows[0]["queries"]
                         >= 2 * repeats * n_queries)
            heat_rows = global_heat().hottest(4)
            heat_ok = bool(heat_rows
                           and heat_rows[0]["index"] == "p"
                           and heat_rows[0]["field"] == "f")
        finally:
            set_cost_enabled(True)
            global_heat().clear()
            server.close()

    ok = (off_ratio >= 0.99 and on_ratio >= 0.90
          and bool(ledger_ok) and heat_ok)
    return {
        "config": "profiling",
        "metric": "profile_off_plateau_ratio",
        "value": round(off_ratio, 4),
        "unit": "fraction of bare fast-lane plateau",
        "bare_qps": round(bare, 1),
        "off_qps": round(off, 1),
        "profiled_qps": round(on, 1),
        "profiled_ratio": round(on_ratio, 4),
        "off_ratio_median": round(off_median, 4),
        "profiled_ratio_median": round(on_median, 4),
        "ledger_ok": bool(ledger_ok),
        "heat_ok": bool(heat_ok),
        "queries": n_queries, "clients": n_clients, "shards": n_shards,
        "gates": {"off_vs_bare": ">=0.99", "profiled_vs_bare": ">=0.90"},
        "ok": bool(ok),
    }


def config_scrub(n_shards: int = 4, n_clients: int = 4,
                 queries_per_client: int = 120,
                 n_chaos_schedules: int = 2,
                 detection_bound_s: float = 5.0,
                 overhead_floor: float = 0.97) -> dict:
    """Self-healing storage integrity gate (ISSUE 10): four phases
    against real in-process servers —

    1. **Serving overhead**: a read plateau measured with the scrubber
       OFF then ON (200 ms interval + a 1 MiB/s pacer — already ~4
       orders of magnitude hotter than a production scrub-interval of
       minutes-to-hours, while the pacer keeps each pass's decode work
       off the serving threads' GIL) — gated at on/off ≥
       ``overhead_floor`` (the ≤3% acceptance bound), with at least
       one full pass required during the plateau.
    2. **Detection latency**: a seeded bit flip in a live fragment's
       snapshot, scrubber ticking — seconds until quarantine+heal,
       gated ≤ ``detection_bound_s``.
    3. **Corruption-heal oracle** (2 nodes, replica_n=2): flip one
       replica's fragment on disk, serve reads from THAT node
       throughout the scrub window (every response compared against
       truth — zero corrupt responses), then require the fragment
       quarantined, read-repaired BYTE-IDENTICAL to the healthy
       replica, and every acked write queryable (zero lost). Then
       ENOSPC injection on the same node: writes shed 503 +
       storageDegraded on /status, and the probe auto-recovers once
       the fault clears.
    4. **Randomized schedules**: ``n_chaos_schedules`` chaos runs with
       storage faults on (bit-flip + disk-full events beside
       partition/kill/restart), gated on the disk-integrity oracle
       plus the four partition oracles (testing/chaos.py)."""
    import json as _json
    import threading
    import urllib.error
    import urllib.request

    from pilosa_tpu.server import Server, ServerConfig
    from pilosa_tpu.shardwidth import SHARD_WIDTH
    from pilosa_tpu.storage.view import VIEW_STANDARD

    def req(base, path, body=None, method=None, timeout=30):
        r = urllib.request.Request(
            f"{base}{path}", data=body,
            method=method or ("POST" if body is not None else "GET"),
        )
        with urllib.request.urlopen(r, timeout=timeout) as resp:
            return _json.loads(resp.read() or b"{}")

    def boot(data_dir, name, seeds=(), replica_n=1):
        return Server(ServerConfig(
            data_dir=data_dir, port=0, name=name, replica_n=replica_n,
            seeds=list(seeds), anti_entropy_interval=0,
            heartbeat_interval=0, use_mesh=False,
        )).open()

    def base_of(s):
        return f"http://localhost:{s.port}"

    def flip_byte(path, offset=64, mask=0x20):
        with open(path, "r+b") as f:
            f.seek(offset)
            b = f.read(1)
            f.seek(offset)
            f.write(bytes([b[0] ^ mask]))

    out = {"config": "scrub", "metric": "storage_integrity_oracles"}
    t_start = time.time()

    # ---- phase 1+2: overhead + detection, single node -----------------
    with tempfile.TemporaryDirectory() as tmp:
        s = boot(f"{tmp}/solo", "solo")
        try:
            base = base_of(s)
            req(base, "/index/i", b"{}")
            req(base, "/index/i/field/f", b"{}")
            rng = np.random.default_rng(10)
            for shard in range(n_shards):
                cols = (rng.choice(SHARD_WIDTH, 400, replace=False)
                        + shard * SHARD_WIDTH)
                body = _json.dumps({
                    "rows": [1] * len(cols),
                    "columns": [int(c) for c in cols],
                }).encode()
                req(base, "/index/i/field/f/import", body)
            frags = [
                s.holder.index("i").field("f").view(VIEW_STANDARD)
                .fragment(sh) for sh in range(n_shards)
            ]
            for fr in frags:
                fr.snapshot()
            expected = req(base, "/index/i/query",
                           b"Count(Row(f=1))")["results"][0]

            def plateau() -> float:
                errs = []

                def client():
                    for _ in range(queries_per_client):
                        got = req(base, "/index/i/query",
                                  b"Count(Row(f=1))")["results"][0]
                        if got != expected:
                            errs.append(got)

                t0 = time.perf_counter()
                ts = [threading.Thread(target=client)
                      for _ in range(n_clients)]
                for t in ts:
                    t.start()
                for t in ts:
                    t.join()
                assert not errs, f"corrupt responses: {errs[:3]}"
                return (n_clients * queries_per_client
                        / (time.perf_counter() - t0))

            from pilosa_tpu.parallel.scrub import Scrubber

            def scrub_on() -> "Scrubber":
                sc = Scrubber(s.holder, cluster=s.api.cluster,
                              interval_s=0.2, max_bytes_per_sec=1 << 20)
                s.api.scrubber = sc
                return sc.start()

            # INTERLEAVED off/on rounds gated on the BEST per-round
            # ratio (the config_profiling philosophy: machine-load
            # drift on a shared box only ever makes the scrubbed path
            # look slower than it is); the median rides along for
            # drift visibility
            plateau()  # warm
            rounds = []
            passes = 0
            for _ in range(3):
                q_off = plateau()
                sc = scrub_on()
                q_on = plateau()
                sc.close()
                passes += sc.passes
                rounds.append((q_off, q_on))
            ratios = sorted(on / off for off, on in rounds)
            ratio = ratios[-1]
            out["serving_qps_scrub_off"] = round(
                max(off for off, _ in rounds), 1)
            out["serving_qps_scrub_on"] = round(
                max(on for _, on in rounds), 1)
            out["overhead_ratio"] = round(ratio, 4)
            out["overhead_ratio_median"] = round(
                ratios[len(ratios) // 2], 4)
            out["scrub_passes_during_plateau"] = passes

            # detection latency: flip a byte; the ticking scrubber must
            # quarantine + self-heal it (single node: live bitmap is
            # the healthy copy)
            scrubber = scrub_on()
            flip_byte(frags[0].path)
            t0 = time.perf_counter()
            detect_s = None
            while time.perf_counter() - t0 < detection_bound_s + 5:
                if scrubber.corruptions >= 1 and (
                        scrubber.self_healed + scrubber.repaired) >= 1:
                    detect_s = time.perf_counter() - t0
                    break
                time.sleep(0.02)
            scrubber.close()
            out["detection_s"] = (round(detect_s, 3)
                                  if detect_s is not None else None)
            post_heal = req(base, "/index/i/query",
                            b"Count(Row(f=1))")["results"][0]
            out["detection_ok"] = (detect_s is not None
                                   and detect_s <= detection_bound_s
                                   and post_heal == expected)
        finally:
            s.close()

    # ---- phase 3: heal + ENOSPC oracle, 2 nodes -----------------------
    with tempfile.TemporaryDirectory() as tmp:
        from pilosa_tpu.storage.integrity import StorageHealth
        from pilosa_tpu.testing import faults

        a = boot(f"{tmp}/a", "a", replica_n=2)
        b = boot(f"{tmp}/b", "b", seeds=[base_of(a)], replica_n=2)
        b.holder.health.PROBE_INTERVAL_S = 0.2
        heal = {"corrupt_responses": 0, "reads": 0}
        try:
            for srv in (a, b):
                srv.api.cluster.wait_until_normal(30)
            req(base_of(a), "/index/i", b"{}")
            req(base_of(a), "/index/i/field/f", b"{}")
            acked = []
            for col in range(0, 600, 7):
                ok = req(base_of(a), "/index/i/query",
                         f"Set({col}, f=2)".encode())["results"] == [True]
                if ok:
                    acked.append(col)
            frag_a = (a.holder.index("i").field("f").view(VIEW_STANDARD)
                      .fragment(0))
            frag_b = (b.holder.index("i").field("f").view(VIEW_STANDARD)
                      .fragment(0))
            frag_a.snapshot()
            frag_b.snapshot()
            truth = len(acked)
            stop = threading.Event()

            def reader():
                while not stop.is_set():
                    try:
                        got = req(base_of(b), "/index/i/query",
                                  b"Count(Row(f=2))")["results"][0]
                    except Exception:  # noqa: BLE001
                        continue
                    heal["reads"] += 1
                    if got != truth:
                        heal["corrupt_responses"] += 1

            rt = threading.Thread(target=reader, daemon=True)
            rt.start()
            flip_byte(frag_b.path, offset=96, mask=0x04)
            rec = b.api.scrub_now()
            stop.set()
            rt.join(5)
            healed = (b.holder.index("i").field("f").view(VIEW_STANDARD)
                      .fragment(0))
            byte_identical = (
                healed is not None
                and healed.serialize_snapshot()
                == frag_a.serialize_snapshot()
            )
            got_cols = set(req(base_of(b), "/index/i/query",
                               b"Row(f=2)")["results"][0]["columns"])
            lost = [c for c in acked if c not in got_cols]
            out["heal_scrub_record"] = {
                k: rec[k] for k in ("corrupt", "repaired", "unrepaired")}
            out["heal_reads_during_window"] = heal["reads"]
            out["heal_corrupt_responses"] = heal["corrupt_responses"]
            out["heal_byte_identical"] = byte_identical
            out["heal_lost_acked_writes"] = len(lost)
            out["heal_ok"] = (rec["corrupt"] == 1 and rec["repaired"] == 1
                              and byte_identical and not lost
                              and heal["corrupt_responses"] == 0)

            # ENOSPC on node b: writes shed, status flips, auto-recovers
            import errno as _errno

            plane = faults.install_disk()
            rule = plane.add("fsync", path=f"{tmp}/b/",
                             errno_=_errno.ENOSPC)
            shed = None
            try:
                req(base_of(b), "/index/i/query", b"Set(9001, f=2)")
            except urllib.error.HTTPError as e:
                shed = e.code
            degraded = req(base_of(b), "/status")["storageDegraded"]
            # a SECOND write must shed 503 via the QoS path
            shed2 = None
            try:
                req(base_of(b), "/index/i/query", b"Set(9002, f=2)")
            except urllib.error.HTTPError as e:
                shed2 = e.code
            plane.remove(rule.id)
            t0 = time.perf_counter()
            recovered = False
            while time.perf_counter() - t0 < 10:
                if not req(base_of(b), "/status")["storageDegraded"]:
                    recovered = True
                    break
                time.sleep(0.1)
            write_after = req(base_of(b), "/index/i/query",
                              b"Set(9003, f=2)")["results"] == [True]
            out["enospc_first_status"] = shed
            out["enospc_shed_status"] = shed2
            out["enospc_degraded_on_status"] = degraded
            out["enospc_recovered"] = recovered
            out["enospc_write_after_heal"] = write_after
            out["enospc_ok"] = (degraded and shed2 == 503 and recovered
                                and write_after)
        finally:
            faults.clear_disk()
            a.close()
            b.close()

    # ---- phase 4: randomized storage-fault chaos schedules ------------
    from pilosa_tpu.testing.chaos import run_chaos

    with tempfile.TemporaryDirectory() as tmp:
        chaos = run_chaos(tmp, n_schedules=n_chaos_schedules, n_nodes=3,
                          replica_n=2, n_events=6, seed=7,
                          with_storage_faults=True)
    out["chaos_schedules"] = chaos["schedules"]
    out["chaos_corruptions_injected"] = chaos["corruptions_injected"]
    out["chaos_disk_integrity_failures"] = chaos["disk_integrity_failures"]
    out["chaos_lost_acked_writes"] = chaos["lost_acked_writes"]
    out["chaos_degraded_stuck"] = chaos["degraded_stuck"]
    out["chaos_failed_seeds"] = chaos["failed_seeds"]
    out["chaos_ok"] = bool(chaos["ok"] and chaos["unconverged"] == 0)

    out["wall_s"] = round(time.time() - t_start, 1)
    out["ok"] = bool(
        out["overhead_ratio"] >= overhead_floor
        and out["scrub_passes_during_plateau"] >= 1
        and out["detection_ok"] and out["heal_ok"] and out["enospc_ok"]
        and out["chaos_ok"]
    )
    return out


# Stand-alone client driver for config_mp_serving: client-side load
# must come from PROCESSES (a threaded driver is itself GIL-bound and
# would mask the very scaling the config measures). Each proc holds one
# keep-alive connection, waits for a "run <port> <n> <start_at>" line,
# fires n requests from the shared deterministic query schedule, and
# reports wall time + a response digest (the byte-identical oracle).
_MP_CLIENT_SRC = r"""
import hashlib, http.client, json, sys, time
QUERIES = ["Count(Row(f=%d))" % (1 + k) for k in range(4)]
for line in sys.stdin:
    parts = line.split()
    if parts[0] == "exit":
        break
    port, n, start_at = int(parts[1]), int(parts[2]), float(parts[3])
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
    h = hashlib.sha256()
    errors = 0
    while time.time() < start_at:
        time.sleep(0.001)
    t0 = time.perf_counter()
    for k in range(n):
        try:
            conn.request("POST", "/index/b/query",
                         body=QUERIES[k % len(QUERIES)].encode())
            h.update(conn.getresponse().read())
        except Exception:
            errors += 1
            conn = http.client.HTTPConnection("127.0.0.1", port,
                                              timeout=60)
    wall = time.perf_counter() - t0
    conn.close()
    print(json.dumps({"wall": wall, "digest": h.hexdigest(),
                      "errors": errors}), flush=True)
"""


def config_multitenant(n_indexes: int = 120, n_clients: int = 8,
                       requests_per_client: int = 300,
                       baseline_requests: int = 800,
                       rounds: int = 3, zipf_s: float = 1.1,
                       hot_ranks: int = 5, cold_rank_floor: int = 30,
                       ryw_rounds: int = 40) -> dict:
    """Skewed-traffic gate (ISSUE 12 / ROADMAP open item 3): 100+
    indexes on ONE node under Zipf client traffic with QoS quotas
    active, the write-invalidated result cache and heat-driven
    residency tiering both ON.

    Gates (``ok``):

    - hot-tenant p99 within 1.3x the single-index plateau p99 on the
      same server (the Zipf head must serve at cache speed, however
      many cold tenants share the node);
    - cold-tenant p99 bounded (≤ max(50x the single-index p99, 0.75 s)
      — re-decode + fill cost, never an unbounded tail);
    - result-cache hit rate > 50% on the Zipf hot set (per-tenant
      ledger result_cache_hits / queries over the head ranks);
    - read-your-writes oracle: an acked (fsynced, group-commit) write
      is NEVER masked by a stale cached result — write-then-read
      through the cache path, single-process AND through different
      mp-serving workers' rings (the cache lives owner-side);
    - tiering acts: ≥1 heat-driven demotion to the compressed host
      tier and ≥1 promotion back, with ZERO serving errors during the
      transitions (old-resident or new-resident, never absent);
    - zero client errors anywhere.
    """
    import http.client as _hc
    import socket as _socket
    import threading
    import urllib.request

    from pilosa_tpu.serving.rescache import global_result_cache
    from pilosa_tpu.server import Server, ServerConfig
    from pilosa_tpu.shardwidth import SHARD_WIDTH
    from pilosa_tpu.storage.residency import global_row_cache
    from pilosa_tpu.storage.view import VIEW_STANDARD

    t_start = time.time()
    rng = np.random.default_rng(12)
    names = [f"t{i:03d}" for i in range(n_indexes)]
    # seeded rank permutation: which tenant is rank-0 hot is arbitrary
    perm = rng.permutation(n_indexes)
    rank_of = {names[perm[r]]: r for r in range(n_indexes)}
    by_rank = [names[perm[r]] for r in range(n_indexes)]
    # Zipf pmf over ranks
    weights = 1.0 / np.arange(1, n_indexes + 1) ** zipf_s
    pmf = weights / weights.sum()

    def seed_server(tmp: str, **extra) -> "Server":
        server = Server(ServerConfig(
            data_dir=tmp, port=0, name="mt", anti_entropy_interval=0,
            heartbeat_interval=0, use_mesh=False,
            result_cache_bytes=64 << 20,
            residency_promote_interval=0.2,
            residency_promote_heat=2.0, residency_demote_heat=0.5,
            heat_half_life=1.5,
            qos_max_inflight=512, qos_tenant_inflight=64,  # quotas ON
            **extra,
        )).open()
        n = int(SHARD_WIDTH * 0.01)
        for name in names:
            idx = server.holder.create_index(name,
                                             track_existence=False)
            f = idx.create_field("f")
            frag = f.view(VIEW_STANDARD, create=True).fragment(
                0, create=True)
            for row in range(1, 5):
                frag.bulk_import(
                    np.full(n, row, np.uint64),
                    rng.choice(SHARD_WIDTH, n, replace=False).astype(
                        np.uint64),
                )
            server.api.cluster.note_local_shards(name, [0])
        return server

    def post(conn, index, pql, tenant=None, suffix=""):
        headers = {"X-Pilosa-Tenant": tenant} if tenant else {}
        conn.request("POST", f"/index/{index}/query{suffix}",
                     body=pql.encode(), headers=headers)
        resp = conn.getresponse()
        return resp.status, resp.read()

    errors: list = []

    def client_run(port, plan):
        """One closed-loop client: ``plan`` is [(index, pql)];
        returns per-request latencies (seconds) aligned with plan."""
        conn = _hc.HTTPConnection("127.0.0.1", port, timeout=120)
        lat = np.zeros(len(plan))
        try:
            for k, (index, pql) in enumerate(plan):
                t0 = time.perf_counter()
                st, body = post(conn, index, pql, tenant=index)
                lat[k] = time.perf_counter() - t0
                if st != 200:
                    errors.append((index, st, body[:120]))
        except Exception as e:  # noqa: BLE001 — surfaced via errors
            errors.append(repr(e))
        finally:
            conn.close()
        return lat

    def run_phase(port, plans):
        gate = threading.Event()
        out = [None] * len(plans)

        def worker(i):
            gate.wait(30)
            out[i] = client_run(port, plans[i])

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(len(plans))]
        for t in threads:
            t.start()
        gate.set()
        for t in threads:
            t.join(600)
        return out

    result: dict = {"config": "multitenant",
                    "metric": "zipf_multitenant_cache_tiering",
                    "n_indexes": n_indexes, "n_clients": n_clients,
                    "zipf_s": zipf_s}
    with tempfile.TemporaryDirectory() as tmp:
        server = seed_server(f"{tmp}/s1")
        try:
            port = server.port
            hot0 = by_rank[0]
            # warm compile caches + the baseline index's cache entries
            warm_conn = _hc.HTTPConnection("127.0.0.1", port, timeout=120)
            for row in range(1, 5):
                post(warm_conn, hot0, f"Count(Row(f={row}))", tenant=hot0)
            warm_conn.close()

            # ---- phase 1: single-index plateau (the comparison bar)
            per = baseline_requests // n_clients
            base_p99 = base_p50 = None
            for _ in range(rounds):
                plans = [[(hot0,
                           f"Count(Row(f={1 + (k % 4)}))")
                          for k in range(per)]
                         for _ in range(n_clients)]
                lat = np.concatenate(run_phase(port, plans))
                p99 = float(np.percentile(lat, 99))
                if base_p99 is None or p99 < base_p99:
                    base_p99 = p99
                    base_p50 = float(np.percentile(lat, 50))

            # ---- phase 2: Zipf traffic across every tenant
            hits0 = global_result_cache().metrics()
            hot_lat_best = cold_lat_best = None
            for r in range(rounds):
                plans = []
                for c in range(n_clients):
                    crng = np.random.default_rng(1000 + r * 64 + c)
                    ranks = crng.choice(n_indexes, requests_per_client,
                                        p=pmf)
                    plans.append([
                        (by_rank[rank],
                         f"Count(Row(f={1 + (k % 4)}))")
                        for k, rank in enumerate(ranks)])
                outs = run_phase(port, plans)
                hot_lat, cold_lat = [], []
                for plan, lat in zip(plans, outs):
                    for (index, _), s in zip(plan, lat):
                        rank = rank_of[index]
                        if rank < hot_ranks:
                            hot_lat.append(s)
                        elif rank >= cold_rank_floor:
                            cold_lat.append(s)
                hp99 = float(np.percentile(hot_lat, 99))
                if hot_lat_best is None or hp99 < hot_lat_best:
                    hot_lat_best = hp99
                if cold_lat:
                    cp99 = float(np.percentile(cold_lat, 99))
                    if cold_lat_best is None or cp99 < cold_lat_best:
                        cold_lat_best = cp99
            hits1 = global_result_cache().metrics()
            # hot-set hit rate from the per-tenant ledger (cache hits
            # are billed as queries — the satellite contract)
            ledger = {r["tenant"]: r
                      for r in server.api.cost.snapshot()}
            hot_queries = sum(
                ledger.get(by_rank[r], {}).get("queries", 0)
                for r in range(hot_ranks))
            hot_hits = sum(
                ledger.get(by_rank[r], {}).get("result_cache_hits", 0)
                for r in range(hot_ranks))
            hot_hit_rate = hot_hits / hot_queries if hot_queries else 0.0

            # ---- phase 3: read-your-writes through the cache path
            ryw_ok = True
            ryw_conn = _hc.HTTPConnection("127.0.0.1", port, timeout=120)
            counts: dict = {}
            for k in range(ryw_rounds):
                name = by_rank[int(rng.integers(0, 20))]
                # prime the cached read, then write, then re-read: the
                # acked (fsynced) write must never be masked
                post(ryw_conn, name, "Count(Row(f=9))", tenant=name)
                st, _ = post(ryw_conn, name,
                             f"Set({2000 + k}, f=9)", tenant=name)
                if st != 200:
                    errors.append(("ryw-write", st))
                counts[name] = counts.get(name, 0) + 1
                st, body = post(ryw_conn, name, "Count(Row(f=9))",
                                tenant=name)
                got = json.loads(body)["results"][0]
                if got != counts[name]:
                    ryw_ok = False
                    errors.append(
                        ("ryw-stale", name, got, counts[name]))
            ryw_conn.close()

            # ---- phase 4: heat-driven tier cycle (demote + promote)
            cache = global_row_cache()
            tier_conn = _hc.HTTPConnection("127.0.0.1", port,
                                           timeout=120)
            # everything cools below demote-heat (half-life 1.5 s);
            # the 0.2 s tiering worker demotes resident leaves host-side
            deadline = time.time() + 12.0
            while (cache.tier_demotions == 0
                   and time.time() < deadline):
                time.sleep(0.25)
            demotions = int(cache.tier_demotions)
            # re-heat a handful of demoted tenants with explicit-shard
            # queries (cache-ineligible, so they EXECUTE and record
            # heat); lookups promote the leaves they touch, the worker
            # pass promotes the rest of each field
            tier_errors = 0
            for name in by_rank[:3]:
                for k in range(12):
                    st, _ = post(tier_conn, name,
                                 f"Count(Row(f={1 + (k % 4)}))",
                                 tenant=name, suffix="?shards=0")
                    if st != 200:
                        tier_errors += 1
            deadline = time.time() + 8.0
            while (cache.tier_promotions == 0
                   and time.time() < deadline):
                time.sleep(0.25)
            promotions = int(cache.tier_promotions)
            tier_metrics = server.api.tierer.metrics()
            host_bytes_peak = int(cache.host_bytes)
            tier_conn.close()
        finally:
            server.close()

        # ---- phase 5: the mp-serving shape (cache owner-side)
        if hasattr(_socket, "SO_REUSEPORT"):
            mp_ok = True
            mp = Server(ServerConfig(
                data_dir=f"{tmp}/mp", port=0, serving_workers=2,
                anti_entropy_interval=0, heartbeat_interval=0,
                use_mesh=False, result_cache_bytes=16 << 20,
            )).open()
            try:
                mport = mp.port

                def mp_req(method, path, body=None):
                    r = urllib.request.Request(
                        f"http://127.0.0.1:{mport}{path}", data=body,
                        method=method)
                    with urllib.request.urlopen(r, timeout=60) as resp:
                        return resp.status, resp.read()

                mp_req("POST", "/index/m", b"{}")
                mp_req("POST", "/index/m/field/f", b"{}")
                for k in range(15):
                    # fresh connection per request: the kernel spreads
                    # them across the SO_REUSEPORT workers, so the
                    # write and the read ride DIFFERENT rings
                    st, _ = mp_req("POST", "/index/m/query",
                                   f"Set({k}, f=3)".encode())
                    if st != 200:
                        mp_ok = False
                    st, body = mp_req("POST", "/index/m/query",
                                      b"Count(Row(f=3))")
                    if json.loads(body)["results"][0] != k + 1:
                        mp_ok = False
                        errors.append(("mp-ryw-stale", k))
            except Exception as e:  # noqa: BLE001
                mp_ok = False
                errors.append(repr(e))
            finally:
                mp.close()
        else:
            mp_ok = True
            result["mp_skipped"] = "SO_REUSEPORT unavailable"

        # ---- phase 6: cluster-edge caching under live CDC (ISSUE 16)
        # Two nodes, replica_n=1: node0's Count spans shards node1
        # owns — the exact shape the write-invalidated cache REFUSED
        # to cache single-node ("cluster-no-cdc" refusal), because a
        # remote write could not reach the local invalidation hook.
        # With cdc-enabled tailers live the edge entry caches (gate:
        # >50% hit rate on repeat reads) and a write through the PEER
        # is never masked past the tail-poll staleness (bounded
        # read-your-writes: the re-read converges within a deadline).
        ce_errors: list = []
        ce_ryw_ok = True
        ce_hit_rate = 0.0
        ce_prop_ms: list = []
        ce_lag: dict = {}
        ce_reads = 40
        ce_kw = dict(replica_n=1, anti_entropy_interval=0,
                     heartbeat_interval=0, use_mesh=False,
                     result_cache_bytes=32 << 20,
                     cdc_enabled=True, cdc_poll_interval=0.02)
        ce0 = Server(ServerConfig(
            data_dir=f"{tmp}/ce0", port=0, name="ce0", **ce_kw)).open()
        ce1 = Server(ServerConfig(
            data_dir=f"{tmp}/ce1", port=0, name="ce1",
            seeds=[f"http://localhost:{ce0.port}"], **ce_kw)).open()
        try:
            for s in (ce0, ce1):
                s.api.cluster.wait_until_normal(30)

            def ce_req(port, path, body=None):
                r = urllib.request.Request(
                    f"http://127.0.0.1:{port}{path}", data=body,
                    method="POST")
                with urllib.request.urlopen(r, timeout=60) as resp:
                    return resp.status, resp.read()

            ce_req(ce0.port, "/index/ce", b"{}")
            ce_req(ce0.port, "/index/ce/field/f", b"{}")
            expect = 4
            for s_ in range(expect):
                ce_req(ce0.port, "/index/ce/query",
                       f"Set({s_ * SHARD_WIDTH + 5}, f=1)".encode())
            deadline = time.time() + 15
            while time.time() < deadline and not all(
                    s.api.cdc is not None and s.api.cdc.live()
                    for s in (ce0, ce1)):
                time.sleep(0.05)
            m0 = global_result_cache().metrics()
            for _ in range(ce_reads):
                st, body = ce_req(ce0.port, "/index/ce/query",
                                  b"Count(Row(f=1))")
                if st != 200 or json.loads(body)["results"] != [expect]:
                    ce_errors.append(("ce-read", st, body[:120]))
            m1 = global_result_cache().metrics()
            hits = (m1["result_cache_hits_total"]
                    - m0["result_cache_hits_total"])
            ce_hit_rate = hits / ce_reads
            for k in range(8):
                ce_req(ce1.port, "/index/ce/query",
                       f"Set({(expect + k) * SHARD_WIDTH + 5}, "
                       f"f=1)".encode())
                t0p = time.perf_counter()
                dl = time.time() + 5.0
                seen = None
                while time.time() < dl:
                    _, body = ce_req(ce0.port, "/index/ce/query",
                                     b"Count(Row(f=1))")
                    seen = json.loads(body)["results"][0]
                    if seen == expect + k + 1:
                        break
                    time.sleep(0.01)
                else:
                    ce_ryw_ok = False
                    ce_errors.append(("ce-ryw-stale",
                                      expect + k + 1, seen))
                ce_prop_ms.append(
                    (time.perf_counter() - t0p) * 1e3)
            ce_lag = ce0.api.cdc.peer_lag() if ce0.api.cdc else {}
        except Exception as e:  # noqa: BLE001 — surfaced via gate
            ce_ryw_ok = False
            ce_errors.append(repr(e))
        finally:
            ce1.close()
            ce0.close()

    cold_bound = max(50 * base_p99, 0.75)
    result.update({
        "requests_zipf": n_clients * requests_per_client * rounds,
        "single_index_p50_ms": round(base_p50 * 1e3, 3),
        "single_index_p99_ms": round(base_p99 * 1e3, 3),
        "hot_tenant_p99_ms": round(hot_lat_best * 1e3, 3),
        "hot_vs_single_ratio": round(hot_lat_best / base_p99, 3),
        "cold_tenant_p99_ms": round((cold_lat_best or 0.0) * 1e3, 3),
        "cold_bound_ms": round(cold_bound * 1e3, 1),
        "hot_hit_rate": round(hot_hit_rate, 4),
        "result_cache": {
            k: hits1[k] - hits0.get(k, 0)
            for k in ("result_cache_hits_total",
                      "result_cache_misses_total",
                      "result_cache_fills_total",
                      "result_cache_invalidations_total")},
        "tier_demotions": demotions,
        "tier_promotions": promotions,
        "tier_pass_metrics": tier_metrics,
        "host_tier_bytes": host_bytes_peak,
        "tier_transition_errors": tier_errors,
        "read_your_writes_ok": ryw_ok,
        "read_your_writes_mp_ok": mp_ok,
        "cluster_edge": {
            "hit_rate": round(ce_hit_rate, 4),
            "read_your_writes_ok": ce_ryw_ok,
            "invalidation_p50_ms": round(
                float(np.percentile(ce_prop_ms, 50)), 2
            ) if ce_prop_ms else None,
            "peer_lag": ce_lag,
            "errors": len(ce_errors),
            "error_sample": [str(e)[:160] for e in ce_errors[:3]],
        },
        "client_errors": len(errors),
        "error_sample": [str(e)[:160] for e in errors[:5]],
        "wall_s": round(time.time() - t_start, 1),
    })
    result["ok"] = bool(
        hot_lat_best <= 1.3 * base_p99
        and (cold_lat_best or 0.0) <= cold_bound
        and hot_hit_rate > 0.5
        and ryw_ok and mp_ok
        and ce_hit_rate > 0.5 and ce_ryw_ok and not ce_errors
        and demotions >= 1 and promotions >= 1
        and tier_errors == 0 and not errors
    )
    return result


def config_mp_serving(n_shards: int = 4,
                      worker_counts=(1, 2, 4),
                      client_counts=(8, 32, 96),
                      requests_per_client: int = 80,
                      rounds: int = 3) -> dict:
    """Multi-process serving tier scaling gate (ISSUE 11 / ROADMAP open
    item 1): the SAME hot read mix against the SAME seeded data in two
    deployment shapes — classic single-process, and N ``SO_REUSEPORT``
    workers fronting one device owner over shared-memory rings
    (serving/mpserve.py). Clients are subprocesses (process-level
    parallelism on both sides of the wire); runs are best-of-``rounds``
    INTERLEAVED across shapes so drift hits every curve equally.

    The headline is plateau-vs-plateau: max QPS over the client sweep
    per worker count, plus the worker-reported ring round-trip
    quantiles. ``ok`` requires byte-identical responses across every
    shape and run (digest oracle vs a serial pass), one kill-a-worker
    chaos schedule passing both mp oracles (zero lost acked writes,
    owner never wedges), and a core-aware scaling bar (ISSUE 18):
    4-worker plateau ≥ 4× the single-process fast-lane plateau when
    the box has ≥ 6 cores (workers + owner + clients each get a real
    core), ≥ 2× on 3-5 cores, and on fewer the box is recorded as
    hardware-saturated — the result carries ``cores`` and the measured
    ``saturation`` point and only the correctness oracles gate."""
    import http.client as _hc
    import socket as _socket
    import subprocess
    import sys as _sys

    from pilosa_tpu.server import Server, ServerConfig
    from pilosa_tpu.shardwidth import SHARD_WIDTH
    from pilosa_tpu.storage.view import VIEW_STANDARD

    if not hasattr(_socket, "SO_REUSEPORT"):
        return {"config": "mp_serving", "ok": False,
                "error": "SO_REUSEPORT unavailable on this platform"}

    def boot(tmp: str, workers: int):
        server = Server(ServerConfig(
            data_dir=tmp, port=0, name=f"mp{workers}",
            serving_workers=workers, anti_entropy_interval=0,
            heartbeat_interval=0, use_mesh=False,
        )).open()
        rng = np.random.default_rng(7)  # same seed: identical data
        idx = server.holder.create_index("b")
        f = idx.create_field("f")
        n = int(SHARD_WIDTH * 0.1)
        for shard in range(n_shards):
            frag = f.view(VIEW_STANDARD, create=True).fragment(
                shard, create=True)
            for row in range(1, 5):
                frag.bulk_import(
                    np.full(n, row, np.uint64),
                    rng.choice(SHARD_WIDTH, n, replace=False).astype(
                        np.uint64),
                )
        server.api.cluster.note_local_shards("b", list(range(n_shards)))
        return server

    t0 = time.time()
    max_clients = max(client_counts)
    clients = [
        subprocess.Popen([_sys.executable, "-c", _MP_CLIENT_SRC],
                         stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                         text=True)
        for _ in range(max_clients)
    ]

    def run_once(port: int, n_clients: int):
        start_at = time.time() + 0.25
        for p in clients[:n_clients]:
            p.stdin.write(f"run {port} {requests_per_client} "
                          f"{start_at}\n")
            p.stdin.flush()
        outs = []
        for p in clients[:n_clients]:
            line = p.stdout.readline()
            if not line:
                raise RuntimeError(
                    "mp_serving client subprocess died mid-run "
                    f"(exit {p.poll()})")
            outs.append(json.loads(line))
        wall = max(o["wall"] for o in outs)
        errors = sum(o["errors"] for o in outs)
        digests = {o["digest"] for o in outs}
        return (n_clients * requests_per_client) / wall, errors, digests

    servers = {}
    try:
        with tempfile.TemporaryDirectory() as tmp:
            servers[0] = boot(f"{tmp}/w0", 0)
            for w in worker_counts:
                servers[w] = boot(f"{tmp}/w{w}", w)
            # serial ground-truth digest on the single-process shape
            import hashlib as _hashlib

            conn = _hc.HTTPConnection("127.0.0.1", servers[0].port,
                                      timeout=60)
            h = _hashlib.sha256()
            for k in range(requests_per_client):
                conn.request("POST", "/index/b/query",
                             body=f"Count(Row(f={1 + k % 4}))".encode())
                h.update(conn.getresponse().read())
            conn.close()
            want_digest = h.hexdigest()
            # warm every shape (compile caches, worker pools)
            for s in servers.values():
                run_once(s.port, max_clients)
            best: dict = {w: {} for w in servers}
            errors_total = 0
            identical = True
            for _ in range(rounds):          # interleaved best-of-N
                for w, s in servers.items():
                    for n_clients in client_counts:
                        qps, errs, digests = run_once(s.port, n_clients)
                        errors_total += errs
                        identical = identical and digests == {want_digest}
                        best[w][n_clients] = max(
                            best[w].get(n_clients, 0.0), qps)
            curve = [
                {"workers": w, "clients": c, "qps": round(q, 1)}
                for w in sorted(best) for c, q in sorted(best[w].items())
            ]
            plateaus = {w: round(max(best[w].values()), 1)
                        for w in sorted(best)}
            # ring round-trip quantiles, as the workers measured them
            rtt = {"p50_us": 0, "p99_us": 0}
            mp = servers[max(worker_counts)]._mpserve
            rows = [r for r in mp.workers_json() if r.get("ringRttP50Us")]
            if rows:
                rtt = {
                    "p50_us": round(sum(r["ringRttP50Us"]
                                        for r in rows) / len(rows)),
                    "p99_us": max(r["ringRttP99Us"] for r in rows),
                }
            for s in servers.values():
                s.close()
            servers = {}
            # the kill-a-worker chaos schedule rides the same gate
            from pilosa_tpu.testing.chaos import run_mp_chaos

            chaos = run_mp_chaos(f"{tmp}/chaos", n_schedules=1,
                                 n_workers=2, n_kills=3)
    finally:
        for s in servers.values():
            s.close()
        for p in clients:
            try:
                p.stdin.write("exit\n")
                p.stdin.flush()
            except OSError:
                pass
        for p in clients:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()

    speedup = (plateaus[max(worker_counts)] / plateaus[0]
               if plateaus[0] else 0.0)
    # core-aware scaling gate (ISSUE 18): N workers + 1 owner + client
    # subprocesses need real cores to show scaling. On >=6 cores the
    # ROADMAP >=4x target is enforced; on 3-5 cores the shapes
    # time-share and >=2x is the honest bar; below that the box is
    # hardware-saturated — scaling is not measurable, so only the
    # correctness oracles (byte-identity, zero client errors, chaos)
    # gate, and the saturation point is recorded instead.
    cores = os.cpu_count() or 1
    best_plateau = max(plateaus.values()) if plateaus else 0.0
    saturation_workers = next(
        (w for w in sorted(plateaus)
         if plateaus[w] >= 0.95 * best_plateau), max(worker_counts))
    if cores >= 6:
        scaling_ok, scaling_gate = speedup >= 4.0, "speedup >= 4.0"
    elif cores >= 3:
        scaling_ok, scaling_gate = speedup >= 2.0, "speedup >= 2.0"
    else:
        scaling_ok = True
        scaling_gate = ("ungated: hardware-saturated (< 3 cores); "
                        "correctness + chaos oracles still gate")
    return {
        "config": "mp_serving",
        "metric": "mp_serving_plateau_scaling",
        "n_shards": n_shards,
        "requests_per_point": requests_per_client * max(client_counts),
        "curve": curve,
        "plateau_qps_by_workers": plateaus,
        "speedup_max_workers": round(speedup, 2),
        "cores": cores,
        "scaling_gate": scaling_gate,
        "saturation": {
            "plateau_workers": saturation_workers,
            "note": ("smallest worker count within 5% of the best "
                     "plateau on this box"),
        },
        "ring_rtt": rtt,
        "client_errors": errors_total,
        "bytes_identical": identical,
        "kill_worker_chaos": chaos,
        "wall_s": round(time.time() - t0, 1),
        "ok": bool(identical and errors_total == 0 and scaling_ok
                   and chaos["ok"]),
    }


def config_chaos(n_schedules: int = 20, n_nodes: int = 3,
                 replica_n: int = 2, n_events: int = 6,
                 seed: int = 0) -> dict:
    """Partition-tolerance chaos gate (ISSUE 9 — docs/OPERATIONS.md
    failure model): ``n_schedules`` independent seeded schedules of
    randomized partition (symmetric + asymmetric) / heal / kill /
    restart events against a real ``n_nodes``-node in-process cluster
    under a mixed read+write workload, each gated on the four oracles:

    1. zero lost acked writes (every 200-acked Set queryable after heal),
    2. no fragment deleted by a non-quorum node (cleanup decision log),
    3. at most one coordinator acting per epoch (acted-epoch records),
    4. byte-identical replicas after heal (the PR-4 sync oracle).

    ``ok`` requires every schedule to pass every oracle AND converge
    (membership reunified, all NORMAL, nobody degraded). A failing
    schedule's seed is reported so the run replays deterministically
    (testing/chaos.py).

    The default config also runs the ISSUE-11 kill-a-worker schedules
    (multi-process serving tier: SIGKILL workers mid-burst) gated on
    zero lost acked writes + the owner-never-wedges oracle; skipped
    (and not counted against ``ok``) only where SO_REUSEPORT is
    unavailable.

    ISSUE 17 adds mid-drain schedules: a second ``run_chaos`` batch
    with ``with_elastic=True`` puts graceful-drain events in the same
    bag as kills and partitions, so faults land while a drain is in
    flight — gated on the same oracles."""
    import socket as _socket

    from pilosa_tpu.testing.chaos import run_chaos, run_mp_chaos

    t0 = time.time()
    with tempfile.TemporaryDirectory() as tmp:
        out = run_chaos(
            tmp, n_schedules=n_schedules, n_nodes=n_nodes,
            replica_n=replica_n, n_events=n_events, seed=seed,
        )
        drain = run_chaos(
            tmp + "/drain", n_schedules=max(2, n_schedules // 5),
            n_nodes=max(n_nodes, 4), replica_n=replica_n,
            n_events=n_events, seed=seed + 7, with_elastic=True,
        )
        if hasattr(_socket, "SO_REUSEPORT"):
            mp = run_mp_chaos(tmp + "/mp", n_schedules=2, n_workers=2,
                              n_kills=3, seed=seed)
        else:
            mp = {"skipped": "SO_REUSEPORT unavailable", "ok": True}
    return {
        "kill_worker": mp,
        "mid_drain": {
            "schedules": drain["schedules"],
            "drains_total": drain["drains_total"],
            "lost_acked_writes": drain["lost_acked_writes"],
            "replica_mismatches": drain["replica_mismatches"],
            "unconverged": drain["unconverged"],
            "failed_seeds": drain["failed_seeds"],
            "failed_diags": drain["failed_diags"],
            "ok": bool(drain["ok"] and drain["unconverged"] == 0),
        },
        "config": "chaos",
        "metric": "partition_chaos_oracles",
        "schedules": out["schedules"],
        "n_nodes": out["n_nodes"],
        "replica_n": out["replica_n"],
        "events_total": out["events_total"],
        "acked_writes_total": out["acked_writes_total"],
        "lost_acked_writes": out["lost_acked_writes"],
        "non_quorum_deletions": out["non_quorum_deletions"],
        "coordinator_conflicts": out["coordinator_conflicts"],
        "replica_mismatches": out["replica_mismatches"],
        "unconverged": out["unconverged"],
        "failed_seeds": out["failed_seeds"],
        "failed_diags": out["failed_diags"],
        "wall_s": round(time.time() - t0, 1),
        "ok": bool(out["ok"] and out["unconverged"] == 0
                   and mp.get("ok")
                   and drain["ok"] and drain["unconverged"] == 0),
    }


def config_autopilot(n_hot: int = 12, n_clients: int = 12,
                     inflight_cap: int = 5, hot_run_s: float = 24.0,
                     base_run_s: float = 8.0, n_chaos_schedules: int = 3,
                     seed: int = 0) -> dict:
    """Autopilot placement gate (ISSUE 15): a 3-process cluster under
    hot-spotted Zipf traffic must recover its p99 automatically.

    The hot spot is REAL, not simulated: ``n_hot`` single-shard indexes
    are chosen (by walking candidate names through the same blake2b
    ring the cluster uses) so that hash placement puts every one of
    them on ONE node, and closed-loop clients drive a Zipf-weighted
    query mix at them, owner-routed the way a shard-aware client
    routes. Under ``qos-max-inflight`` admission the overloaded owner
    sheds the excess with 429 + Retry-After and clients retry after
    backoff — so the measured (retry-inclusive) p99 is exactly the
    client-visible cost of the skew. This makes the gate meaningful on
    a 1-core CI box too: sheds are near-free for the server, so the
    hot node's p99 is backpressure wait, which the autopilot removes
    by SPREADING admission capacity, not by needing N cores to race.

    Three measured placements on identical data and workload shape:

    - ``uniform``: owners round-robin all nodes (control cluster,
      autopilot off) — the baseline the gate compares against;
    - ``hot unmanaged``: every hot index on one owner, autopilot OFF —
      the injury persists (reported, not gated: it must be > baseline
      for the run to mean anything);
    - ``hot autopiloted``: same skew with the planner ON — the first
      windows show the injury, the tail windows must show recovery.

    Gate (``ok``): tail-window p99 ≤ 1.5× the uniform p99 AND zero
    client errors (a 429 retried to success is backpressure, not an
    error; anything else — 5xx, transport failure, retry exhaustion —
    fails the gate) AND zero lost acked writes (a ledgered Set that
    rode through the autopilot's resizes must stay queryable) AND the
    planner actually acted (≥1 executed move, live overrides) AND the
    kill-switch control cluster stayed byte-identical to hash
    placement (epoch 0, no overrides, every probe write's heat row
    lands on the ring-computed owner and nowhere else)."""
    import bisect as _bisect
    import http.client as _hc
    import os
    import random as _random
    import socket
    import subprocess
    import sys
    import threading
    import urllib.request

    from pilosa_tpu.parallel.cluster import PARTITION_N, _hash64

    NAMES = ("ap0", "ap1", "ap2")
    ZIPF_S = 1.1
    RETRY_CAP = 400  # per-request attempt bound before it counts as an error

    def _ring_owner(index: str, shard: int = 0) -> str:
        # replica-n=1 rendition of Cluster.shard_nodes' hash walk; the
        # control cluster's byte-identity check holds this replica and
        # the server's walk to the same answer through real traffic
        ring = sorted(NAMES, key=lambda n: (_hash64(n), n))
        part = _hash64(f"{index}:{shard}") % PARTITION_N
        return ring[part % len(ring)]

    def free_port() -> int:
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            return s.getsockname()[1]

    def req(method, base, path, body=None, timeout=30):
        r = urllib.request.Request(f"{base}{path}", data=body,
                                   method=method)
        with urllib.request.urlopen(r, timeout=timeout) as resp:
            return json.loads(resp.read() or b"{}")

    def spawn_cluster(tmp: str, autopilot_on: bool) -> dict:
        os.makedirs(tmp, exist_ok=True)
        ports = {name: free_port() for name in NAMES}
        bases = {n: f"http://127.0.0.1:{p}" for n, p in ports.items()}
        procs = {}

        def wait_status(name) -> None:
            for _ in range(240):
                if procs[name].poll() is not None:
                    raise AssertionError(f"{name} exited "
                                         f"rc={procs[name].returncode}")
                try:
                    req("GET", bases[name], "/status", timeout=5)
                    return
                except Exception:
                    time.sleep(0.25)
            raise AssertionError(f"{name} never served /status")

        for i, name in enumerate(NAMES):
            env = {
                **os.environ, "JAX_PLATFORMS": "cpu",
                "PILOSA_TPU_NAME": name,
                "PILOSA_TPU_REPLICA_N": "1",
                # anti-entropy ON: a shard move pulls the fragment
                # snapshot; writes racing the move land as stray
                # residue on the old owner, which cleanup refuses to
                # delete until a sync pass absorbs it into the new
                # owner — with the ticker off, acked bits would sit
                # unreadable in deferred strays forever
                "PILOSA_TPU_ANTI_ENTROPY_INTERVAL": "2",
                "PILOSA_TPU_HEARTBEAT_INTERVAL": "0",
                "PILOSA_TPU_USE_MESH": "false",
                "PILOSA_TPU_QOS_MAX_INFLIGHT": str(inflight_cap),
            }
            if i > 0:
                env["PILOSA_TPU_SEEDS"] = bases[NAMES[0]]
            if autopilot_on:
                env.update({
                    "PILOSA_TPU_AUTOPILOT_ENABLED": "true",
                    "PILOSA_TPU_AUTOPILOT_INTERVAL": "1s",
                    "PILOSA_TPU_AUTOPILOT_HEAT_BUDGET": "1.3",
                    "PILOSA_TPU_AUTOPILOT_MAX_MOVES": "4",
                    "PILOSA_TPU_AUTOPILOT_MIN_DWELL": "2s",
                })
            log = open(f"{tmp}/{name}.log", "wb")
            procs[name] = subprocess.Popen(
                [sys.executable, "-m", "pilosa_tpu", "server",
                 "--data-dir", f"{tmp}/{name}", "--bind", "127.0.0.1",
                 "--port", str(ports[name])],
                env=env, stdout=log, stderr=subprocess.STDOUT,
                cwd=os.path.dirname(os.path.abspath(__file__)),
            )
            log.close()
            # join is a single shot at startup (no retry loop), so the
            # seed must be listening before any joiner boots — spawn
            # strictly seed-first and gate on its /status
            if i == 0:
                wait_status(name)
        for name in NAMES[1:]:
            wait_status(name)
        # EVERY node must see the full membership — the seed converges
        # first (joiners announce to it directly), but a joiner that
        # missed the join relay would serve an asymmetric ring whose
        # reads route around data the other joiner holds
        deadline = time.time() + 30
        while time.time() < deadline:
            views = [{n["id"] for n in
                      req("GET", bases[name], "/status")["nodes"]}
                     for name in NAMES]
            if all(v == set(NAMES) for v in views):
                break
            time.sleep(0.2)
        else:
            raise AssertionError(
                f"cluster never reached full membership: {views}")
        return {"procs": procs, "bases": bases}

    def terminate(cluster) -> None:
        for p in cluster["procs"].values():
            p.terminate()
        for p in cluster["procs"].values():
            try:
                p.wait(timeout=15)
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait(timeout=15)

    # ---- index pools: names bucketed by ring owner ---------------------
    buckets: dict[str, list] = {n: [] for n in NAMES}
    i = 0
    while any(len(b) < 2 * n_hot for b in buckets.values()):
        name = f"t{i:03d}"
        buckets[_ring_owner(name)].append(name)
        i += 1
    hot_node = NAMES[1]  # any bucket works; fixed for deterministic replay
    hot_set = buckets[hot_node][:n_hot]
    # uniform set: Zipf rank r owned by node r % 3, so the popularity
    # mass lands evenly — the placement the autopilot should converge to.
    # Disjoint from hot_set (the hot bucket's cursor starts past it).
    cursors = {n: (n_hot if n == hot_node else 0) for n in NAMES}
    uniform_set = []
    for r in range(n_hot):
        node = NAMES[r % len(NAMES)]
        uniform_set.append(buckets[node][cursors[node]])
        cursors[node] += 1
    weights = np.array([1.0 / (r + 1) ** ZIPF_S for r in range(n_hot)])
    cum = np.cumsum(weights / weights.sum()).tolist()

    def seed_indexes(bases, names) -> None:
        entry = bases[NAMES[0]]
        for name in names:
            req("POST", entry, f"/index/{name}", b"{}")
            req("POST", entry, f"/index/{name}/field/f", b"{}")
            for col in (1, 2, 3):
                req("POST", entry, f"/index/{name}/query",
                    f"Set({col}, f=1)".encode())

    # ---- owner-routed closed-loop load --------------------------------
    class Router:
        """Client-side shard-aware routing: ring walk + the override
        table polled from /debug/autopilot (what a topology-aware
        client library would cache)."""

        def __init__(self, bases):
            self.bases = bases
            self.overrides: dict = {}
            self.lock = threading.Lock()

        def refresh(self) -> None:
            try:
                j = req("GET", self.bases[NAMES[0]], "/debug/autopilot",
                        timeout=5)
                ov = {}
                for e in (j.get("placement") or {}).get("overrides", []):
                    ov[(e["index"], int(e["shard"]))] = list(e["nodes"])
                with self.lock:
                    self.overrides = ov
            except Exception:
                pass  # stale routing is legal; owners still fan out

        def owner(self, index: str) -> str:
            with self.lock:
                ids = self.overrides.get((index, 0))
            if ids and all(i in self.bases for i in ids):
                return ids[0]
            return _ring_owner(index)

    def run_load(bases, router, index_set, duration_s, *,
                 write_ledger=None, refresh=False):
        """``n_clients`` closed-loop Zipf query threads (+1 ledgered
        writer when ``write_ledger`` is given). Returns (samples,
        errors, retries): samples are (completed_at_s, latency_s)
        with latency INCLUDING 429-retry backoff."""
        samples: list = []
        errors: list = []
        retries = [0]
        lock = threading.Lock()
        stop = threading.Event()
        t_start = time.monotonic()

        def do_request(conns, name, path, body):
            conn = conns.get(name)
            if conn is None:
                host = bases[name].split("//")[1]
                h, _, p = host.partition(":")
                conn = conns[name] = _hc.HTTPConnection(h, int(p),
                                                        timeout=30)
            conn.request("POST", path, body=body)
            resp = conn.getresponse()
            data = resp.read()
            return resp.status, data

        def drop_conn(conns, name) -> None:
            stale = conns.pop(name, None)
            if stale is not None:
                try:
                    stale.close()
                except Exception:
                    pass

        def one_op(conns, rng, index, body):
            """POST until acked; latency includes every retry. Returns
            (latency_s, None) or (None, error)."""
            t0 = time.monotonic()
            attempts = 0
            while True:
                name = router.owner(index)
                try:
                    status, data = do_request(
                        conns, name, f"/index/{index}/query", body)
                except Exception:
                    # stale keep-alive: reconnect, bounded retries
                    drop_conn(conns, name)
                    attempts += 1
                    if attempts > RETRY_CAP:
                        return None, "transport retries exhausted"
                    continue
                if status == 200:
                    return time.monotonic() - t0, None
                if status == 429:
                    attempts += 1
                    retries[0] += 1
                    if attempts > RETRY_CAP:
                        return None, "429 retries exhausted"
                    # client-side backoff on the bench's timescale (the
                    # server's Retry-After floor is a whole second —
                    # honoring it verbatim would quantize every p99 to
                    # 1s buckets); jittered linear ramp, 4→40ms
                    time.sleep(min(0.004 * attempts, 0.04)
                               * (0.5 + rng.random()))
                    continue
                return None, f"HTTP {status}: {data[:120]!r}"

        def query_worker(tid: int):
            conns: dict = {}
            rng = _random.Random(seed * 1000 + tid)
            while not stop.is_set():
                r = min(_bisect.bisect_left(cum, rng.random()),
                        len(index_set) - 1)
                lat, err = one_op(conns, rng, index_set[r],
                                  b"Count(Row(f=1))")
                with lock:
                    if err is not None:
                        errors.append(err)
                    elif lat is not None:
                        samples.append(
                            (time.monotonic() - t_start, lat))
            for c in conns.values():
                c.close()

        def writer_worker():
            # the acked-write ledger rider: a 200 on Set IS the ack —
            # every ledgered (index, col) must be queryable at the end,
            # however many placement moves its shard rode through
            conns: dict = {}
            rng = _random.Random(seed * 1000 + 777)
            col = 1000
            k = 0
            while not stop.is_set():
                index = index_set[k % len(index_set)]
                k += 1
                col += 1
                _lat, err = one_op(conns, rng, index,
                                   f"Set({col}, f=2)".encode())
                with lock:
                    if err is not None:
                        errors.append(f"write: {err}")
                    else:
                        write_ledger.add((index, col))
                time.sleep(0.02)  # read-dominated mix
            for c in conns.values():
                c.close()

        threads = [threading.Thread(target=query_worker, args=(t,),
                                    daemon=True)
                   for t in range(n_clients)]
        if write_ledger is not None:
            threads.append(threading.Thread(target=writer_worker,
                                            daemon=True))

        def refresher():
            while not stop.is_set():
                router.refresh()
                time.sleep(0.3)

        if refresh:
            threads.append(threading.Thread(target=refresher,
                                            daemon=True))
        for t in threads:
            t.start()
        time.sleep(duration_s)
        stop.set()
        for t in threads:
            t.join(timeout=60)
        return samples, errors, retries[0]

    def p99_ms(samples, t_lo, t_hi) -> float:
        lats = [lat for at, lat in samples if t_lo <= at < t_hi]
        if not lats:
            return float("nan")
        return round(float(np.percentile(np.array(lats), 99)) * 1e3, 2)

    t0 = time.time()
    record: dict = {"config": "autopilot",
                    "metric": "hotspot_p99_recovery"}
    with tempfile.TemporaryDirectory() as tmp:
        # ---- phase A: control cluster, kill switch OFF ----------------
        control = spawn_cluster(f"{tmp}/off", autopilot_on=False)
        try:
            bases = control["bases"]
            kill_switch_ok = True
            for name in NAMES:
                j = req("GET", bases[name], "/debug/autopilot")
                pl = j.get("placement") or {}
                kill_switch_ok &= (j.get("enabled") is False
                                   and pl.get("epoch", -1) == 0
                                   and not pl.get("overrides"))
            seed_indexes(bases, uniform_set + hot_set)
            # byte-identity probe: every seeded index's WRITE heat (the
            # Sets above, posted at ap0) must surface on exactly the
            # ring-computed owner — real traffic observing placement
            time.sleep(0.3)
            heat_rows = {
                name: req("GET", bases[name], "/debug/heatmap")
                .get("shards", []) for name in NAMES
            }
            placement_mismatches = []
            for index in uniform_set + hot_set:
                holders = {
                    name for name, rows in heat_rows.items()
                    if any(r.get("index") == index
                           and r.get("writes", 0) > 0 for r in rows)
                }
                if holders != {_ring_owner(index)}:
                    placement_mismatches.append(
                        {"index": index, "want": _ring_owner(index),
                         "got": sorted(holders)})
            router = Router(bases)
            u_samples, u_errors, _ = run_load(
                bases, router, uniform_set, base_run_s)
            h_samples, h_errors, _ = run_load(
                bases, router, hot_set, base_run_s * 0.75)
            p99_uniform = p99_ms(u_samples, 2.0, base_run_s)
            p99_hot_unmanaged = p99_ms(h_samples, 2.0, base_run_s * 0.75)
        finally:
            terminate(control)

        # ---- phase B: autopilot ON, same skew -------------------------
        managed = spawn_cluster(f"{tmp}/on", autopilot_on=True)
        try:
            bases = managed["bases"]
            seed_indexes(bases, hot_set)
            router = Router(bases)
            ledger: set = set()
            m_samples, m_errors, m_retries = run_load(
                bases, router, hot_set, hot_run_s,
                write_ledger=ledger, refresh=True)
            p99_hot_early = p99_ms(m_samples, 0.0, 4.0)
            p99_recovered = p99_ms(m_samples, hot_run_s - 6.0, hot_run_s)
            timeline = [
                {"window_s": [w, w + 2], "p99_ms": p99_ms(m_samples,
                                                          w, w + 2)}
                for w in range(0, int(hot_run_s), 2)
            ]
            recover_at = next(
                (w["window_s"][0] for w in timeline
                 if w["window_s"][0] >= 4
                 and w["p99_ms"] <= 1.5 * p99_uniform), None)
            pilot = req("GET", bases[NAMES[0]], "/debug/autopilot")
            moves = (pilot.get("metrics") or {}).get(
                "autopilot_moves_executed_total", 0)
            overrides_live = len(
                (pilot.get("placement") or {}).get("overrides", []))
            # acked-write ledger: every Set acked through the resizes
            # must become queryable cluster-wide. Bounded retry: bits
            # that raced a move sit as stray residue until the next
            # anti-entropy pass (2s ticker) absorbs them into the new
            # owner — convergence, not loss
            lost = []
            for attempt in range(8):
                lost = []
                for index in hot_set:
                    want = {c for ix, c in ledger if ix == index}
                    if not want:
                        continue
                    out = req("POST", bases[NAMES[0]],
                              f"/index/{index}/query", b"Row(f=2)")
                    got = set(out.get("results", [{}])[0]
                              .get("columns", []))
                    lost.extend((index, c) for c in want - got)
                if not lost:
                    break
                time.sleep(2.0)
            lost_debug = {}
            if lost:
                # per-node view of every lost index while the cluster
                # still serves: local fragment inventory, per-node
                # placement epoch/overrides, per-node readback
                for index in sorted({ix for ix, _ in lost}):
                    per = {}
                    for name in NAMES:
                        ent = {}
                        try:
                            cat = req("GET", bases[name],
                                      f"/internal/fragments?index={index}")
                            ent["fragments"] = cat.get("fragments", [])
                        except Exception as e:  # noqa: BLE001
                            ent["fragments"] = f"ERR {e}"
                        try:
                            out = req("POST", bases[name],
                                      f"/index/{index}/query",
                                      b"Row(f=2)")
                            ent["row_f2"] = sorted(
                                out.get("results", [{}])[0]
                                .get("columns", []))[-8:]
                        except Exception as e:  # noqa: BLE001
                            ent["row_f2"] = f"ERR {e}"
                        try:
                            pl = req("GET", bases[name],
                                     "/debug/autopilot")["placement"]
                            ent["placement"] = [
                                o for o in pl.get("overrides", [])
                                if o["index"] == index]
                            ent["epoch"] = pl.get("epoch")
                        except Exception as e:  # noqa: BLE001
                            ent["placement"] = f"ERR {e}"
                        per[name] = ent
                    lost_debug[index] = per
                for name in NAMES:
                    try:
                        with open(f"{tmp}/on/{name}.log", "rb") as f:
                            tail = f.read()[-6000:]
                        lost_debug[f"log_{name}"] = [
                            ln for ln in
                            tail.decode("utf-8", "replace").splitlines()
                            if any(ix in ln for ix, _ in lost)
                            or "autopilot" in ln or "cleanup" in ln][-30:]
                    except Exception:  # noqa: BLE001
                        pass
        finally:
            terminate(managed)

        # ---- phase C: autopilot-active chaos schedules ----------------
        # the planner minting overrides and resizing WHILE partitions,
        # kills, and restarts land — gated on the same five oracles as
        # config_chaos (testing/chaos.py with_autopilot)
        from pilosa_tpu.testing.chaos import run_chaos

        chaos = run_chaos(
            f"{tmp}/chaos", n_schedules=n_chaos_schedules, n_nodes=3,
            replica_n=2, seed=seed, n_events=6, with_autopilot=True,
        )

    errors_total = len(u_errors) + len(h_errors) + len(m_errors)
    record.update({
        "n_nodes": len(NAMES), "n_hot_indexes": n_hot,
        "n_clients": n_clients, "inflight_cap": inflight_cap,
        "zipf_s": ZIPF_S, "hot_node": hot_node,
        "p99_uniform_ms": p99_uniform,
        "p99_hot_unmanaged_ms": p99_hot_unmanaged,
        "p99_hot_early_ms": p99_hot_early,
        "p99_recovered_ms": p99_recovered,
        "recovery_ratio": (round(p99_recovered / p99_uniform, 3)
                           if p99_uniform else None),
        "recovered_at_s": recover_at,
        "timeline": timeline,
        "autopilot_moves": moves,
        "placement_overrides_live": overrides_live,
        "retries_429": m_retries,
        "acked_writes": len(ledger),
        "lost_acked_writes": len(lost),
        "lost_sample": lost[:5],
        "lost_debug": lost_debug,
        "client_errors": errors_total,
        "error_sample": (u_errors + h_errors + m_errors)[:5],
        "kill_switch_byte_identical": bool(
            kill_switch_ok and not placement_mismatches),
        "placement_mismatches": placement_mismatches[:5],
        "chaos": {
            "schedules": chaos["schedules"],
            "autopilot_moves_total": chaos["autopilot_moves_total"],
            "lost_acked_writes": chaos["lost_acked_writes"],
            "replica_mismatches": chaos["replica_mismatches"],
            "failed_seeds": chaos["failed_seeds"],
            "unconverged": chaos["unconverged"],
            "ok": chaos["ok"],
        },
        "wall_s": round(time.time() - t0, 1),
        "ok": bool(
            kill_switch_ok and not placement_mismatches
            and errors_total == 0 and not lost
            and moves >= 1 and overrides_live >= 1
            and p99_recovered == p99_recovered  # not NaN
            and p99_uniform == p99_uniform
            and p99_recovered <= 1.5 * p99_uniform
            and chaos["ok"] and chaos["unconverged"] == 0),
    })
    return record


def _spawn_cpu_mesh_entry() -> None:
    """Run config5_mesh_cpu8 in a subprocess pinned to an 8-device
    virtual CPU platform: this process may hold the chip, and a chip
    belongs to one process."""
    import os
    import subprocess
    import sys

    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": (os.environ.get("XLA_FLAGS", "") +
                      " --xla_force_host_platform_device_count=8").strip(),
    }
    proc = subprocess.run(
        [sys.executable, __file__, "--cpu-mesh-inner"],
        env=env, capture_output=True, text=True, timeout=600,
    )
    lines = [l for l in proc.stdout.splitlines() if l.startswith("{")]
    if proc.returncode != 0 or not lines:
        print(json.dumps({
            "config": 5, "metric": "ssb_4way_mesh_microbatched_dispatches",
            "ok": False, "error": (proc.stderr or "no output")[-500:],
        }), flush=True)
        return
    print(lines[-1], flush=True)


def config_cdc(n_chaos_schedules: int = 3, n_clients: int = 6,
               read_s: float = 5.0, n_shards: int = 4,
               density: float = 0.01, seed: int = 0) -> dict:
    """CDC backbone gate (ISSUE 16 — docs/OPERATIONS.md Replication &
    CDC): three oracles over the WAL tail change feed.

    1. **Byte-identical mirror under chaos** — an out-of-cluster
       follower tails n0 through randomized partition/kill/restart
       schedules (testing/chaos.py ``with_cdc``); after heal, every
       non-empty fragment n0 holds must be byte-identical in the
       mirror. Upstream restarts reset the seq space mid-schedule, so
       this also drives the unknown-cursor 410 → merge-resync path.
    2. **Follower read scaling** — primary and follower run as real OS
       subprocesses (separate interpreters, real parallelism); on
       >=2 cores the closed-loop read fleet against primary+follower
       must clear ≥1.7x the primary-alone QPS; on a single core (where
       wall-clock scaling is physically impossible) the gate is
       capacity instead — follower-alone ≥0.5x primary, combined
       ≥0.75x (no collapse) — with the mode recorded. Either way:
       follower staleness p99 under the 1 s budget while a writer
       keeps the feed moving, the follower converging to the primary's
       count after load, and the ``X-Pilosa-Max-Staleness`` gate live
       (an impossible budget sheds 503, a generous one serves).
    3. **As-of ledger bit-exactness** — every WAL seq between two
       backup generations restores bit-exactly via nearest-generation
       + feed replay (``restore --as-of``, storage/backup.py).
    """
    import http.client as _hc
    import os
    import socket
    import subprocess
    import sys
    import threading
    import urllib.request

    from pilosa_tpu.roaring import RoaringBitmap
    from pilosa_tpu.roaring.format import serialize
    from pilosa_tpu.shardwidth import SHARD_WIDTH
    from pilosa_tpu.storage import Holder
    from pilosa_tpu.storage.backup import backup_holder, restore_holder
    from pilosa_tpu.storage.view import VIEW_STANDARD
    from pilosa_tpu.testing.chaos import run_chaos

    t_start = time.time()
    rng = np.random.default_rng(29)
    errors: list = []

    def free_port() -> int:
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            return s.getsockname()[1]

    def req(base, path, body=None, method="POST", headers=None,
            timeout=60):
        r = urllib.request.Request(f"{base}{path}", data=body,
                                   method=method,
                                   headers=headers or {})
        with urllib.request.urlopen(r, timeout=timeout) as resp:
            return resp.status, resp.read()

    def spawn(data_dir: str, name: str, extra_env: dict) -> tuple:
        port = free_port()
        env = {
            **os.environ, "JAX_PLATFORMS": "cpu",
            "PILOSA_TPU_NAME": name,
            "PILOSA_TPU_ANTI_ENTROPY_INTERVAL": "0",
            "PILOSA_TPU_HEARTBEAT_INTERVAL": "0",
            "PILOSA_TPU_USE_MESH": "false",
            **extra_env,
        }
        proc = subprocess.Popen(
            [sys.executable, "-m", "pilosa_tpu", "server",
             "--data-dir", data_dir, "--bind", "127.0.0.1",
             "--port", str(port)],
            env=env, stdout=subprocess.DEVNULL,
            stderr=subprocess.STDOUT,
        )
        base = f"http://127.0.0.1:{port}"
        for _ in range(240):
            if proc.poll() is not None:
                raise AssertionError(f"{name} exited rc={proc.returncode}")
            try:
                req(base, "/status", method="GET", timeout=5)
                return proc, base
            except Exception:
                time.sleep(0.25)
        proc.terminate()
        raise AssertionError(f"{name} never served /status")

    result: dict = {"config": "cdc", "metric": "cdc_backbone_oracles"}
    with tempfile.TemporaryDirectory() as tmp:
        # ---- phase 1: byte-identical mirror under chaos
        chaos = run_chaos(
            f"{tmp}/chaos", n_schedules=n_chaos_schedules,
            n_events=7, seed=seed, with_cdc=True,
        )

        # ---- phase 2: follower read scaling (subprocess parallelism)
        n_bits = int(SHARD_WIDTH * density)
        payloads = []
        for _ in range(n_shards):
            ids = []
            for row in (1, 2, 3, 4):
                pos = rng.choice(SHARD_WIDTH, n_bits,
                                 replace=False).astype(np.uint64)
                ids.append((np.uint64(row) << np.uint64(20)) + pos)
            bm = RoaringBitmap()
            bm.add_ids(np.concatenate(ids))
            payloads.append(serialize(bm))
        expected = [None]  # Count(Row(f=1)) once seeded

        primary = follower = None
        qps_primary = qps_combined = qps_follower = 0.0
        staleness: list = []
        writes = [0]
        converged = gated_ok = False
        try:
            primary, pbase = spawn(f"{tmp}/primary", "cdc-primary", {})
            req(pbase, "/index/cdc", b"{}")
            req(pbase, "/index/cdc/field/f", b"{}")
            for shard, payload in enumerate(payloads):
                req(pbase,
                    f"/index/cdc/field/f/import-roaring/{shard}"
                    "?remote=true", payload,
                    headers={"Content-Type":
                             "application/octet-stream"})
            _, body = req(pbase, "/index/cdc/query",
                          b"Count(Row(f=1))")
            expected[0] = json.loads(body)["results"][0]

            follower, fbase = spawn(
                f"{tmp}/follower", "cdc-follower",
                {"PILOSA_TPU_CDC_FOLLOW": pbase,
                 "PILOSA_TPU_CDC_POLL_INTERVAL": "25ms",
                 "PILOSA_TPU_CDC_STALENESS_BUDGET": "5s"})
            deadline = time.time() + 90
            while time.time() < deadline:
                try:
                    _, body = req(fbase, "/index/cdc/query",
                                  b"Count(Row(f=1))")
                    if json.loads(body)["results"][0] == expected[0]:
                        break
                except Exception:
                    pass
                time.sleep(0.25)
            else:
                raise AssertionError("follower never caught up to seed")

            stop = threading.Event()
            side_stop = threading.Event()
            counts: dict = {}

            def reader(tag, base):
                conn = _hc.HTTPConnection(
                    base.split("//")[1].split(":")[0],
                    int(base.rsplit(":", 1)[1]), timeout=60)
                n = k = 0
                try:
                    while not stop.is_set():
                        conn.request(
                            "POST",
                            f"/index/cdc/query",
                            body=f"Count(Row(f={1 + (k % 4)}))".encode())
                        resp = conn.getresponse()
                        resp.read()
                        if resp.status == 200:
                            n += 1
                        else:
                            errors.append((tag, resp.status))
                        k += 1
                finally:
                    conn.close()
                counts[tag] = counts.get(tag, 0) + n

            def run_fleet(targets, dur) -> float:
                # constant TOTAL client threads split evenly across
                # targets, so every window presents the same client-
                # side load and only the serving capacity varies
                stop.clear()
                counts.clear()
                per = max(1, n_clients // len(targets))
                threads = [
                    threading.Thread(target=reader,
                                     args=(f"{i}:{b}", b))
                    for b in targets for i in range(per)
                ]
                for t in threads:
                    t.start()
                time.sleep(dur)
                stop.set()
                for t in threads:
                    t.join(30)
                return sum(counts.values()) / dur

            def writer():
                k = 0
                while not side_stop.is_set():
                    try:
                        st, _ = req(pbase, "/index/cdc/query",
                                    f"Set({5 * SHARD_WIDTH + k}, "
                                    f"f=9)".encode())
                        if st == 200:
                            writes[0] += 1
                    except Exception as e:  # noqa: BLE001
                        errors.append(("writer", repr(e)))
                    k += 1
                    time.sleep(0.02)

            def sampler():
                while not side_stop.is_set():
                    try:
                        _, body = req(fbase, "/debug/vars",
                                      method="GET", timeout=5)
                        s = json.loads(body)["cdc"].get(
                            "cdc_follower_staleness_seconds", -1.0)
                        if s >= 0:
                            staleness.append(s)
                    except Exception:  # noqa: BLE001 — sampled gauge
                        pass
                    time.sleep(0.1)

            # the writer + staleness sampler run across EVERY window
            # on their own stop flag, so the baseline and the combined
            # phase carry identical write/feed load — the only delta
            # between windows is which servers take the read fleet
            side = [threading.Thread(target=writer),
                    threading.Thread(target=sampler)]
            for t in side:
                t.start()
            qps_primary = run_fleet([pbase], read_s)
            qps_combined = run_fleet([pbase, fbase], read_s)
            qps_follower = run_fleet([fbase], read_s)
            side_stop.set()
            for t in side:
                t.join(30)

            # follower converges to the primary's post-load count
            _, body = req(pbase, "/index/cdc/query",
                          b"Count(Row(f=9))")
            want9 = json.loads(body)["results"][0]
            deadline = time.time() + 15
            while time.time() < deadline:
                _, body = req(fbase, "/index/cdc/query",
                              b"Count(Row(f=9))")
                if json.loads(body)["results"][0] == want9:
                    converged = True
                    break
                time.sleep(0.1)

            # the staleness QoS gate is live: generous budget serves,
            # impossible budget sheds 503 + Retry-After
            st_ok, _ = req(fbase, "/index/cdc/query",
                           b"Count(Row(f=1))",
                           headers={"X-Pilosa-Max-Staleness": "30s"})
            try:
                req(fbase, "/index/cdc/query", b"Count(Row(f=1))",
                    headers={"X-Pilosa-Max-Staleness": "1us"})
                shed = False
            except urllib.error.HTTPError as e:
                shed = e.code == 503
            gated_ok = st_ok == 200 and shed
        except Exception as e:  # noqa: BLE001 — surfaced via gate
            errors.append(repr(e))
        finally:
            for proc in (follower, primary):
                if proc is not None:
                    proc.terminate()
                    try:
                        proc.wait(10)
                    except subprocess.TimeoutExpired:
                        proc.kill()

        # ---- phase 3: as-of ledger bit-exactness
        asof_checked = 0
        asof_exact = True
        h = Holder(f"{tmp}/asof/src").open()
        try:
            idx = h.create_index("i", track_existence=False)
            fld = idx.create_field("f")
            frag = fld.view(VIEW_STANDARD, create=True).fragment(
                0, create=True)
            for i in range(8):
                frag.set_bit(1, i)
            h.wal.barrier()
            bk = f"{tmp}/asof/bk"
            backup_holder(h, bk)
            ledger = {}
            cols = set(range(8))
            for i in range(8, 20):
                frag.set_bit(1, i)
                cols.add(i)
                h.wal.barrier()
                ledger[h.wal.durable_seq()] = sorted(cols)
            frag.clear_bit(1, 2)
            cols.discard(2)
            h.wal.barrier()
            ledger[h.wal.durable_seq()] = sorted(cols)
            backup_holder(h, bk)
            for seq_pt, want in ledger.items():
                dst = f"{tmp}/asof/r{seq_pt}"
                restore_holder(bk, dst, as_of=seq_pt)
                rh = Holder(dst).open()
                try:
                    got = sorted(
                        rh.index("i").field("f").view(VIEW_STANDARD)
                        .fragment(0).row_columns(1).tolist())
                finally:
                    rh.close()
                asof_checked += 1
                if got != want:
                    asof_exact = False
                    errors.append(("asof-mismatch", seq_pt))
        finally:
            h.close()

    scaling = qps_combined / qps_primary if qps_primary else 0.0
    stale_p99 = (float(np.percentile(staleness, 99))
                 if staleness else -1.0)
    # the wall-clock scaling gate needs real parallelism: primary,
    # follower, and the client fleet are separate OS processes, so on
    # >=2 cores the combined window must clear 1.7x primary-alone. On
    # a single core three processes time-slice one CPU and wall-clock
    # scaling is physically impossible — gate capacity instead: the
    # follower alone must serve >=0.5x the primary's QPS from its own
    # storage, and spanning the fleet across both must not collapse
    # (>=0.75x). The mode is recorded, never silently downgraded.
    cores = os.cpu_count() or 1
    if cores >= 2:
        scaling_mode = "multicore-wall-clock"
        scaling_ok = scaling >= 1.7
    else:
        scaling_mode = "single-core-capacity"
        scaling_ok = bool(
            qps_primary > 0
            and qps_follower >= 0.5 * qps_primary
            and qps_combined >= 0.75 * qps_primary)
    result.update({
        "chaos_schedules": chaos["schedules"],
        "chaos_ok": chaos["ok"],
        "chaos_failed_seeds": chaos["failed_seeds"],
        "cdc_mirror_mismatches": chaos["cdc_mirror_mismatches"],
        "cdc_resyncs_total": chaos["cdc_resyncs_total"],
        "cdc_applied_ops_total": chaos["cdc_applied_ops_total"],
        "read_qps_primary": round(qps_primary, 1),
        "read_qps_with_follower": round(qps_combined, 1),
        "read_qps_follower_alone": round(qps_follower, 1),
        "follower_read_scaling": round(scaling, 3),
        "scaling_gate_mode": scaling_mode,
        "cpu_cores": cores,
        "follower_staleness_p99_s": round(stale_p99, 4),
        "staleness_samples": len(staleness),
        "feed_writes_during_load": writes[0],
        "follower_converged_after_load": converged,
        "staleness_gate_live": gated_ok,
        "asof_points_checked": asof_checked,
        "asof_bit_exact": asof_exact,
        "client_errors": len(errors),
        "error_sample": [str(e)[:160] for e in errors[:5]],
        "wall_s": round(time.time() - t_start, 1),
    })
    result["ok"] = bool(
        chaos["ok"]
        and scaling_ok
        and 0.0 <= stale_p99 < 1.0
        and converged and gated_ok
        and asof_exact and asof_checked >= 13
        and not errors
    )
    return result


def config_elastic(n_clients: int = 6, n_shards: int = 4,
                   phase_s: float = 4.0, n_chaos_schedules: int = 3,
                   seed: int = 0) -> dict:
    """Elastic membership gate (ISSUE 17 — docs/OPERATIONS.md elastic
    operations), three parts:

    **A — scripted grow/shrink under live traffic.** A 3-node
    in-process cluster serves a Zipf read mix plus a ledgered writer
    while the script grows it to 5 (two cold joiners absorb their
    shards) and drains it back to 3 (graceful ``drain`` per departing
    node: groups move, CDC cursors hand off, the target sheds writes
    through the tail and leaves). Gates: ZERO lost acked writes (every
    200-acked Set queryable at the end, through two joins and two
    drains), zero client errors (a 503/429 retried to success is
    backpressure, not an error), and p99 CONTINUITY — no 2s window
    goes dark, and no window's p99 exceeds max(10x the steady-state
    plateau, 1200ms). The absolute floor absorbs the genuine
    double-join resize window on a GIL-shared in-process cluster;
    the real claim is "degraded, never dark": zero dark windows,
    zero errors, zero lost writes, sub-1.2s worst p99.

    **B — hot single shard recovered by a range split.** One index,
    one shard, every byte of its heat on one owner — placement moves
    cannot help (the unsplittable-tenant hole the range table closes).
    With ``autopilot-split-threshold`` armed the planner must mint a
    sub-shard split spreading the shard across >= 2 nodes, every peer
    must adopt the range table, reads must stay byte-correct, and
    remote reads entering through a NON-owner must actually fan out
    across the span owners (measured per-node request deltas).

    **C — chaos mid-drain.** ``run_chaos(with_elastic=True,
    with_cdc=True)``: drain events land in the same bag as kills and
    partitions, so faults hit MID-drain; gated on all six oracles
    (acked writes, quorum deletions, one-coordinator-per-epoch,
    replica identity, CDC mirror, convergence)."""
    import random as _random
    import threading
    import urllib.error
    import urllib.request

    from pilosa_tpu.shardwidth import SHARD_WIDTH

    INDEX = "el"
    ZIPF_S = 1.1
    RETRY_CAP = 300
    N_ROWS = 4

    def req(method, base, path, body=None, timeout=30):
        r = urllib.request.Request(f"{base}{path}", data=body,
                                   method=method)
        with urllib.request.urlopen(r, timeout=timeout) as resp:
            return json.loads(resp.read() or b"{}")

    from pilosa_tpu.server import Server, ServerConfig

    def make_server(tmp, name, seeds, **kw):
        cfg = dict(
            data_dir=f"{tmp}/{name}", port=0, name=name, replica_n=2,
            seeds=seeds, anti_entropy_interval=1.0,
            heartbeat_interval=0.1, heartbeat_timeout=0.5,
            use_mesh=False,
        )
        cfg.update(kw)
        return Server(ServerConfig(**cfg)).open()

    t_all = time.time()
    record: dict = {"config": "elastic", "metric": "elastic_membership"}

    # ---- part A: scripted 3 -> 5 -> 3 under live traffic ---------------
    servers: dict = {}
    srv_lock = threading.Lock()

    def live_bases() -> list:
        with srv_lock:
            return [f"http://localhost:{s.port}" for s in servers.values()]

    samples: list = []
    errors: list = []
    ledger: set = set()
    retried = [0]
    stop = threading.Event()
    t_start = [0.0]
    lock = threading.Lock()

    def one_op(rng, body):
        t0 = time.monotonic()
        attempts = 0
        while True:
            bases = live_bases()
            if not bases:
                return None, None, "no live nodes"
            base = bases[rng.randrange(len(bases))]
            try:
                out = req("POST", base, f"/index/{INDEX}/query", body,
                          timeout=10)
                return time.monotonic() - t0, out, None
            except urllib.error.HTTPError as e:
                code = e.code
                e.read()
                attempts += 1
                if code in (429, 503) and attempts <= RETRY_CAP:
                    retried[0] += 1
                    time.sleep(min(0.004 * attempts, 0.04)
                               * (0.5 + rng.random()))
                    continue
                return None, None, f"HTTP {code}"
            except Exception as e:  # noqa: BLE001 — a node mid-close
                attempts += 1      # drops the connection; re-route
                if attempts <= RETRY_CAP:
                    time.sleep(0.01)
                    continue
                return None, None, f"transport: {e}"

    weights = np.array([1.0 / (r + 1) ** ZIPF_S for r in range(N_ROWS)])
    cum = np.cumsum(weights / weights.sum()).tolist()

    def reader(tid: int):
        import bisect as _bisect

        rng = _random.Random(seed * 1000 + tid)
        while not stop.is_set():
            row = 1 + min(_bisect.bisect_left(cum, rng.random()),
                          N_ROWS - 1)
            lat, _out, err = one_op(rng, f"Count(Row(f={row}))".encode())
            with lock:
                if err is not None:
                    errors.append(err)
                elif lat is not None:
                    samples.append((time.monotonic() - t_start[0], lat))

    def writer():
        rng = _random.Random(seed * 1000 + 777)
        col = 0
        while not stop.is_set():
            col += 1
            c = (col % n_shards) * SHARD_WIDTH + col
            _lat, out, err = one_op(rng, f"Set({c}, f=9)".encode())
            with lock:
                if err is not None:
                    errors.append(f"write: {err}")
                elif out is not None and out.get("results") == [True]:
                    ledger.add(c)
            time.sleep(0.01)

    with tempfile.TemporaryDirectory() as tmp:
        seeds: list = []
        for i in range(3):
            s = make_server(f"{tmp}/a", f"e{i}", seeds)
            servers[f"e{i}"] = s
            if not seeds:
                seeds = [f"http://localhost:{s.port}"]
        for s in servers.values():
            assert s.api.cluster.wait_until_normal(30)
        entry = f"http://localhost:{servers['e0'].port}"
        req("POST", entry, f"/index/{INDEX}", b"{}")
        req("POST", entry, f"/index/{INDEX}/field/f", b"{}")
        for shard in range(n_shards):
            for row in range(1, N_ROWS + 1):
                req("POST", entry, f"/index/{INDEX}/query",
                    f"Set({shard * SHARD_WIDTH + row}, f={row})".encode())

        t_start[0] = time.monotonic()
        threads = [threading.Thread(target=reader, args=(t,), daemon=True)
                   for t in range(n_clients)]
        threads.append(threading.Thread(target=writer, daemon=True))
        for t in threads:
            t.start()
        script_log: list = []
        time.sleep(phase_s)  # steady-state plateau at 3 nodes

        # grow 3 -> 5: two cold joiners warm from the live heatmap
        for name in ("e3", "e4"):
            with srv_lock:
                servers[name] = make_server(f"{tmp}/a", name, seeds)
            script_log.append(
                {"t": round(time.monotonic() - t_start[0], 2),
                 "event": f"join {name}"})
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            with srv_lock:
                views = [set(s.api.cluster.nodes)
                         for s in servers.values()]
            if all(v == {"e0", "e1", "e2", "e3", "e4"} for v in views):
                break
            time.sleep(0.2)
        else:
            script_log.append({"event": "membership never reached 5"})
        time.sleep(phase_s)  # serve at 5

        # snapshot join-warm counters NOW: they live on the joiners,
        # which the shrink below drains and closes
        warm = {k: 0 for k in ("elastic_warm_heat_ordered_total",
                               "elastic_warm_verified_total",
                               "elastic_warm_verify_failed_total")}
        with srv_lock:
            for s in servers.values():
                m = s.api.cluster.metrics()
                for k in warm:
                    warm[k] += m.get(k, 0)

        # shrink 5 -> 3: graceful drains, one at a time
        drains_ok = True
        for name in ("e3", "e4"):
            done = False
            deadline = time.monotonic() + 45
            while time.monotonic() < deadline:
                with srv_lock:
                    coord = next(
                        (s for s in servers.values()
                         if s.api.cluster.is_acting_coordinator), None)
                if coord is None:
                    time.sleep(0.2)
                    continue
                try:
                    # a CDC tailer pinned to the victim: the drain's
                    # handoff step must re-home its retention and drop
                    # the cursor (counted in elastic_cursor_handoffs)
                    wal = getattr(coord.api.holder, "wal", None)
                    if wal is not None:
                        wal.register_cursor(f"tailer:{name}", 0)
                    coord.api.drain_start(name)
                except Exception:  # noqa: BLE001 — resize in flight /
                    time.sleep(0.3)  # not NORMAL yet: retry
                    continue
                while time.monotonic() < deadline:
                    st = coord.api.cluster.drain_record
                    if st.get("target") == name and st.get("state") in (
                            "done", "failed", "aborted"):
                        done = st["state"] == "done"
                        break
                    time.sleep(0.1)
                break
            drains_ok &= done
            script_log.append(
                {"t": round(time.monotonic() - t_start[0], 2),
                 "event": f"drain {name}",
                 "done": done})
            with srv_lock:
                victim = servers.pop(name, None)
            if victim is not None:
                victim.close()
        time.sleep(phase_s)  # steady state back at 3

        stop.set()
        for t in threads:
            t.join(timeout=30)
        run_s = time.monotonic() - t_start[0]

        # acked-write ledger readback (bounded retries: strays that
        # raced a move converge through the 1s anti-entropy ticker)
        with srv_lock:
            probe = f"http://localhost:{servers['e0'].port}"
        lost: list = []
        for _ in range(8):
            try:
                out = req("POST", probe, f"/index/{INDEX}/query",
                          b"Row(f=9)", timeout=30)
                got = set(out.get("results", [{}])[0].get("columns", []))
            except Exception:  # noqa: BLE001
                got = set()
            lost = sorted(ledger - got)
            if not lost:
                break
            time.sleep(2.0)

        cursor_handoffs = 0
        drains_completed = 0
        with srv_lock:
            for s in servers.values():
                em = s.api.elastic_metrics()
                cursor_handoffs += em.get(
                    "elastic_cursor_handoffs_total", 0)
                drains_completed += em.get(
                    "elastic_drains_completed_total", 0)
            part_a_servers = list(servers.values())
            servers.clear()
        for s in part_a_servers:
            s.close()

        def p99_ms(t_lo, t_hi) -> float:
            lats = [lat for at, lat in samples if t_lo <= at < t_hi]
            if not lats:
                return float("nan")
            return round(float(np.percentile(np.array(lats), 99)) * 1e3,
                         2)

        plateau_p99 = p99_ms(1.0, phase_s)
        timeline = [{"window_s": [w, w + 2],
                     "p99_ms": p99_ms(w, w + 2)}
                    for w in range(0, int(run_s), 2)]
        dark_windows = [w["window_s"] for w in timeline
                        if w["p99_ms"] != w["p99_ms"]]  # NaN = no sample
        p99_worst = max((w["p99_ms"] for w in timeline
                         if w["p99_ms"] == w["p99_ms"]),
                        default=float("nan"))
        continuity_ok = bool(
            not dark_windows and plateau_p99 == plateau_p99
            and p99_worst == p99_worst
            and p99_worst <= max(10 * plateau_p99, 1200.0))

        # ---- part B: hot single shard recovered by a range split -------
        split_rec = _elastic_split_part(tmp, req, make_server, seed)

        # ---- part C: chaos schedules that kill/partition mid-drain -----
        from pilosa_tpu.testing.chaos import run_chaos

        chaos = run_chaos(
            f"{tmp}/chaos", n_schedules=n_chaos_schedules, n_nodes=4,
            replica_n=2, seed=seed, n_events=8,
            with_elastic=True, with_cdc=True,
        )

    record.update({
        "grow_shrink": {
            "script": script_log,
            "drains_ok": drains_ok,
            "drains_completed": drains_completed,
            "cursor_handoffs": cursor_handoffs,
            "acked_writes": len(ledger),
            "lost_acked_writes": len(lost),
            "lost_sample": lost[:5],
            "client_errors": len(errors),
            "error_sample": errors[:5],
            "retries_shed": retried[0],
            "plateau_p99_ms": plateau_p99,
            "worst_window_p99_ms": p99_worst,
            "dark_windows": dark_windows,
            "continuity_ok": continuity_ok,
            "timeline": timeline,
            "join_warm": warm,
        },
        "split": split_rec,
        "chaos": {
            "schedules": chaos["schedules"],
            "drains_total": chaos["drains_total"],
            "lost_acked_writes": chaos["lost_acked_writes"],
            "non_quorum_deletions": chaos["non_quorum_deletions"],
            "coordinator_conflicts": chaos["coordinator_conflicts"],
            "replica_mismatches": chaos["replica_mismatches"],
            "cdc_mirror_mismatches": chaos["cdc_mirror_mismatches"],
            "unconverged": chaos["unconverged"],
            "failed_seeds": chaos["failed_seeds"],
            "failed_diags": chaos["failed_diags"],
            "ok": chaos["ok"],
        },
        "wall_s": round(time.time() - t_all, 1),
        "ok": bool(
            drains_ok and not lost and not errors and continuity_ok
            and split_rec["ok"]
            and chaos["ok"] and chaos["unconverged"] == 0),
    })
    return record


def _elastic_split_part(tmp: str, req, make_server, seed: int) -> dict:
    """Part B of config_elastic: one pathologically hot (index, shard)
    on one owner; the armed splitter must spread it across nodes and
    remote reads entering through a non-owner must fan out over the
    span owners."""
    import urllib.request  # noqa: F401 — req closes over it

    servers: dict = {}
    seeds: list = []
    for i in range(3):
        s = make_server(
            f"{tmp}/b", f"s{i}", seeds, replica_n=1,
            autopilot_enabled=True, autopilot_interval=300.0,
            autopilot_split_threshold=1.5, autopilot_split_ways=2)
        servers[f"s{i}"] = s
        if not seeds:
            seeds = [f"http://localhost:{s.port}"]
    try:
        for s in servers.values():
            assert s.api.cluster.wait_until_normal(30)
        entry = f"http://localhost:{servers['s0'].port}"
        req("POST", entry, "/index/hot", b"{}")
        req("POST", entry, "/index/hot/field/f", b"{}")
        for col in range(64):
            req("POST", entry, "/index/hot/query",
                f"Set({col}, f=1)".encode())
        for _ in range(300):  # all heat on hot/0
            req("POST", entry, "/index/hot/query", b"Count(Row(f=1))")
        coord = next(s for s in servers.values()
                     if s.api.cluster.is_acting_coordinator)
        split_minted = False
        for _ in range(10):  # forced passes: deterministic replay
            rec = coord.api.autopilot.run_pass()
            if rec.get("splits"):
                split_minted = True
                break
            time.sleep(0.5)
        c = coord.api.cluster
        spans = c.placement.get_ranges("hot", 0) or ()
        span_owners = sorted({i for _lo, _hi, ids in spans for i in ids})
        adopted = all(s.api.cluster.placement.range_count >= len(spans)
                      for s in servers.values())
        # reads stay byte-correct through the split
        out = req("POST", entry, "/index/hot/query", b"Count(Row(f=1))")
        count_ok = out.get("results") == [64]
        # fan-out: drive reads through a NON-owner entry and measure
        # which span owners' HTTP listeners absorbed the remote reads
        non_owner = next((s for s in servers.values()
                          if s.config.name not in span_owners), None)
        fanout: dict = {}
        if non_owner is not None and span_owners:
            def served(name):
                base = f"http://localhost:{servers[name].port}"
                return req("GET", base, "/debug/vars")[
                    "serving_fastlane"]["http_requests_total"]

            before = {n: served(n) for n in span_owners}
            nb = f"http://localhost:{non_owner.port}"
            for _ in range(200):
                req("POST", nb, "/index/hot/query", b"Count(Row(f=1))")
            fanout = {n: served(n) - before[n] for n in span_owners}
        spread_ok = (len(span_owners) >= 2
                     and len([n for n, d in fanout.items() if d >= 10])
                     >= 2)
        # write amplification through the split: plain Sets entering
        # through the non-owner must narrow to each column's span owner
        # (one remote send per write), while a range-ineligible write
        # (Clear — union repair cannot remove a bit a narrowed send
        # skipped) keeps the full union fan-out to every span owner.
        # The wire-byte ratio between the two on the same columns IS
        # the write-amp reduction the range-aware fast lane buys.
        write_amp: dict = {}
        if non_owner is not None and span_owners:
            from pilosa_tpu.parallel.cluster import global_route_stats

            rs = global_route_stats()
            nb = f"http://localhost:{non_owner.port}"
            n_writes = 64
            before_w = (rs.range_slices, rs.union_writes, rs.wire_bytes)
            for col in range(n_writes):
                req("POST", nb, "/index/hot/query",
                    f"Set({col}, f=2)".encode())
            mid_w = (rs.range_slices, rs.union_writes, rs.wire_bytes)
            for col in range(n_writes):
                req("POST", nb, "/index/hot/query",
                    f"Clear({col}, f=3)".encode())
            after_w = (rs.range_slices, rs.union_writes, rs.wire_bytes)
            ranged_bytes = mid_w[2] - before_w[2]
            union_bytes = after_w[2] - mid_w[2]
            # zero lost acked writes, two ways: (a) range-aware reads
            # (non-owner entry fans out per span, hitting the exact
            # owner each narrowed Set landed on) see every write NOW;
            # (b) anti-entropy's union repair refills the OTHER union
            # owners, after which a read through any owner sees them
            out2 = req("POST", nb, "/index/hot/query",
                       b"Count(Row(f=2))")
            converged = False
            for _ in range(40):
                out3 = req("POST", entry, "/index/hot/query",
                           b"Count(Row(f=2))")
                if out3.get("results") == [n_writes]:
                    converged = True
                    break
                time.sleep(0.5)
            write_amp = {
                "writes": n_writes,
                "range_sliced": mid_w[0] - before_w[0],
                "union_fallback_writes": after_w[1] - mid_w[1],
                "ranged_bytes_per_write": round(
                    ranged_bytes / n_writes, 1),
                "union_bytes_per_write": round(
                    union_bytes / n_writes, 1),
                "write_amp_reduction": round(
                    union_bytes / ranged_bytes, 2) if ranged_bytes
                else 0.0,
                "acked_writes_readable": out2.get("results")
                == [n_writes],
                "union_repair_converged": converged,
            }
        write_amp_ok = bool(
            write_amp
            and write_amp["range_sliced"] >= 1
            and write_amp["union_fallback_writes"] >= 1
            and write_amp["acked_writes_readable"]
            and write_amp["union_repair_converged"]
            and write_amp["write_amp_reduction"] >= 1.5)
        return {
            "split_minted": split_minted,
            "spans": [[lo, hi, list(ids)] for lo, hi, ids in spans],
            "span_owners": span_owners,
            "adopted_by_all": adopted,
            "count_correct": count_ok,
            "non_owner_fanout": fanout,
            "write_amp": write_amp,
            "splits_executed": coord.api.autopilot_metrics().get(
                "autopilot_splits_total", 0),
            "ok": bool(split_minted and len(spans) >= 2 and adopted
                       and count_ok and spread_ok and write_amp_ok),
        }
    finally:
        for s in servers.values():
            s.close()


# Model-vs-measured wire-byte reconciliation band (docs/OPERATIONS.md
# "Multi-chip mesh"): profiler-attributed transfer bytes must land
# within [0.5x, 2x] of the ReduceStats model. The model counts payload
# bytes only (no headers/retries/fragmentation), and the profiler's
# bytes_accessed includes local buffer traffic — a 2x envelope separates
# "model is honest" from "model is fiction" without chasing either
# artifact. On hosts whose traces lack transfer lanes (CPU-only), the
# reconciliation records a structured skip instead.
RECONCILE_BAND = (0.5, 2.0)


def config_mesh_inner(n_devices: int) -> dict:
    """One mesh size of the hierarchical-reduction gate: the flat 1-D
    mesh (the dense baseline every prior PR certified) vs the 2-D
    groups x shards mesh over the canonical 20 dryrun read shapes.

    Three oracles per size:

    1. byte-identical ``result_to_json`` between the dense and
       hierarchical executors on all 20 shapes (the narrowed inter-group
       lanes are lossless by construction — this proves it end to end);
    2. >=4x fewer reduction-lane wire bytes than the dense equivalent on
       the Row/TopN subset (roaring row frames + narrow scalar lanes);
    3. a cols/sec throughput figure so MULTICHIP records stay comparable
       across mesh sizes;
    4. quantized-ranking mode (EQuARX 8-bit candidate lanes +
       widened-window exact recount) byte-identical to the SINGLE-DEVICE
       executor on all 20 shapes AND a measured additional inter-group
       wire-byte reduction vs the lossless lane on the ranking workload;
    5. model-vs-measured wire-byte reconciliation from the profiler
       trace, within RECONCILE_BAND — or a structured, documented skip
       when the host's traces lack transfer lanes (CPU-only).
    """
    from __graft_entry__ import DRYRUN_QUERY_SHAPES, _ensure_devices
    from pilosa_tpu.executor import Executor
    from pilosa_tpu.executor.result import result_to_json
    from pilosa_tpu.parallel import DistExecutor, make_mesh, mesh_groups
    from pilosa_tpu.parallel import reduction
    from pilosa_tpu.shardwidth import SHARD_WIDTH
    from pilosa_tpu.storage import FieldOptions, Holder

    _ensure_devices(max(n_devices, 2), virtual_cpu=True)
    flat = make_mesh(n_devices)
    hier = make_mesh(n_devices, groups=2)

    with tempfile.TemporaryDirectory() as tmp:
        holder = Holder(tmp).open()
        try:
            idx = holder.create_index("mesh")
            f = idx.create_field("f")
            g = idx.create_field("g")
            # 64-row field: a realistic TopN candidate population for
            # the quantized-ranking leg (f's 3 rows would make the
            # window == the whole set)
            many = idx.create_field("many")
            fare = idx.create_field(
                "fare", FieldOptions(type="int", min=0, max=100))
            idx.create_field("tag", FieldOptions(keys=True))
            rng = np.random.default_rng(1)
            n_shards = n_devices + 3  # deliberately not divisible
            cols = []
            for shard in range(n_shards):
                base = shard * SHARD_WIDTH
                for c in rng.choice(SHARD_WIDTH, 50, replace=False).tolist():
                    f.set_bit(1 + (c % 3), base + c)
                    many.set_bit(c % 64, base + c)
                    if c % 2 == 0:
                        g.set_bit(7, base + c)
                    cols.append(base + c)
            for c in cols[::10]:
                fare.set_value(c, int(rng.integers(0, 100)))
            idx.mark_columns_exist(cols)

            base_ex = Executor(holder)
            for name, key_cols in [("alpha", cols[:7]), ("amber", cols[7:12]),
                                   ("beta", cols[12:15])]:
                for c in key_cols:
                    base_ex.execute("mesh", f'Set({c}, tag="{name}")')

            dense_ex = DistExecutor(holder, flat)
            hier_ex = DistExecutor(holder, hier)
            probe = min(c for c in cols if (c % SHARD_WIDTH) % 3 == 0)
            queries = [q.format(probe=probe) for q in DRYRUN_QUERY_SHAPES]

            mismatches = []
            for pql in queries:
                want = result_to_json(dense_ex.execute("mesh", pql)[0])
                got = result_to_json(hier_ex.execute("mesh", pql)[0])
                if got != want:
                    mismatches.append(pql)

            # reduction-lane wire bytes on the Row/TopN subset: dense
            # equivalent (flat int32 ring) vs what the hierarchical
            # plane actually moves (intra-group ICI psum excluded —
            # reported separately as intra_bytes)
            stats = reduction.global_reduce_stats()
            stats.reset()
            hier_ex.execute("mesh", "Union(Row(f=1), Row(f=2))")
            hier_ex.execute("mesh", "TopN(f, n=2)")
            snap = stats.snapshot()
            row_dense = snap["dense_bytes"] + snap["row_dense_bytes"]
            row_actual = snap["actual_bytes"] + snap["row_actual_bytes"]
            ratio = row_dense / max(row_actual, 1)

            stats.reset()
            for pql in queries:
                hier_ex.execute("mesh", pql)
            all_snap = stats.snapshot()

            # ---- quantized-ranking leg (topn-quantized-ranking) ----
            # byte-identity vs the SINGLE-DEVICE executor on every shape
            # (verify_quantized additionally re-runs the lossless window
            # internally and asserts), then the measured wire delta on
            # the ranking workload: lossless hier vs quantized hier.
            quant_ex = DistExecutor(holder, hier, quantized_ranking=True,
                                    verify_quantized=True)
            q_mismatches = []
            for pql in queries:
                want = result_to_json(base_ex.execute("mesh", pql)[0])
                got = result_to_json(quant_ex.execute("mesh", pql)[0])
                if got != want:
                    q_mismatches.append(pql)
            ranking_queries = [
                "TopN(many, n=3)", "TopN(many, n=8)",
                "TopN(many, n=5, threshold=40)", "TopN(f, n=2)",
            ]
            for pql in ranking_queries:  # warm both program caches
                hier_ex.execute("mesh", pql)
                quant_ex.execute("mesh", pql)
            stats.reset()
            for pql in ranking_queries:
                hier_ex.execute("mesh", pql)
            lossless_snap = stats.snapshot()
            stats.reset()
            for pql in ranking_queries:
                quant_ex.execute("mesh", pql)
            quant_snap = stats.snapshot()
            # verify_quantized re-runs the lossless recount inside the
            # quantized executor — its dispatches are certification
            # overhead, not wire the mode would pay in production:
            # subtract the modeled lossless bytes of the reference pass.
            quant_wire = (quant_snap["actual_bytes"]
                          - lossless_snap["actual_bytes"])
            wire_ratio = lossless_snap["actual_bytes"] / max(quant_wire, 1)
            lane_ratio = (quant_snap["quantized_lossless_bytes"]
                          / max(quant_snap["quantized_actual_bytes"], 1))
            quantized = {
                "identical": not q_mismatches,
                "mismatches": q_mismatches,
                "ranking_queries": len(ranking_queries),
                "wire": {
                    "lossless_inter_bytes": lossless_snap["actual_bytes"],
                    "quantized_inter_bytes": quant_wire,
                    "ratio": round(wire_ratio, 2),
                    "lane_ratio": round(lane_ratio, 2),
                },
                "window": {
                    "candidate_rows": quant_snap["quantized_candidate_rows"],
                    "window_rows": quant_snap["quantized_window_rows"],
                },
                "ok": bool(not q_mismatches and quant_wire
                           and quant_wire
                           < lossless_snap["actual_bytes"]),
            }

            # ---- model-vs-measured wire reconciliation (profiler) ----
            stats.reset()
            trace = profiled_trace_report(
                lambda: hier_ex.execute("mesh", "TopN(many, n=3)"), iters=3
            )
            model_snap = stats.snapshot()
            model_bytes = (model_snap["actual_bytes"]
                           + model_snap["intra_bytes"])
            reconciliation = {
                "model_bytes": model_bytes,
                "band": list(RECONCILE_BAND),
                "device_lane": trace.get("device_lane"),
            }
            tr = trace.get("transfer") or {}
            if tr.get("ok"):
                measured = tr["bytes"]
                rel = measured / max(model_bytes, 1)
                reconciliation.update({
                    "status": "measured",
                    "measured_bytes": measured,
                    "measured_over_model": round(rel, 3),
                    "within_band": RECONCILE_BAND[0] <= rel
                    <= RECONCILE_BAND[1],
                })
            else:
                # structured, documented skip (CPU-only hosts have no
                # transfer lanes in their traces) — never a crash, and
                # never silently dropped from the record
                reconciliation.update({
                    "status": "skipped",
                    "reason": tr.get("reason") or "no-trace",
                    "within_band": None,
                })
            recon_ok = reconciliation.get("within_band") is not False

            count_pql = "Count(Row(f=1))"
            hier_ex.execute("mesh", count_pql)  # warm the program
            dt, _ = _timed(lambda: hier_ex.execute("mesh", count_pql)[0])
        finally:
            holder.close()

    return {
        "n_devices": n_devices,
        "mesh_shape": list(mesh_groups(hier)),
        "n_shards": n_shards,
        "shapes": len(queries),
        "identical": not mismatches,
        "mismatches": mismatches,
        "cols_per_sec": round(n_shards * SHARD_WIDTH / dt),
        "row_topn_reduce_bytes": {
            "dense_equiv": row_dense, "actual": row_actual,
            "ratio": round(ratio, 1),
        },
        "reduce_bytes": all_snap,
        "quantized": quantized,
        "wire_reconciliation": reconciliation,
        "ok": not mismatches and ratio >= 4.0 and quantized["ok"]
        and recon_ok,
    }


def config_mesh() -> dict:
    """Mesh scaling gate: one subprocess per mesh size (2/4/8), each
    pinned to a virtual CPU platform (same env contract as mesh8),
    running config_mesh_inner. Aggregates the per-size records, writes
    MULTICHIP_r07.json next to the prior rounds, and is ``ok`` only when
    every size is byte-identical, clears the >=4x Row/TopN wire-byte
    bar, shows a measured quantized-ranking wire reduction with
    byte-identical results, and reconciles model-vs-measured wire bytes
    (or records a structured skip). Record shape is pinned by
    scripts/check_multichip_schema.py (tier-1
    tests/test_multichip_schema.py)."""
    import os
    import subprocess
    import sys

    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": (os.environ.get("XLA_FLAGS", "") +
                      " --xla_force_host_platform_device_count=8").strip(),
    }
    records = []
    for n in (2, 4, 8):
        proc = subprocess.run(
            [sys.executable, __file__, "--mesh-inner", str(n)],
            env=env, capture_output=True, text=True, timeout=600,
        )
        lines = [l for l in proc.stdout.splitlines() if l.startswith("{")]
        if proc.returncode != 0 or not lines:
            records.append({
                "n_devices": n, "ok": False,
                "error": (proc.stderr or "no output")[-500:],
            })
        else:
            records.append(json.loads(lines[-1]))
    out = {
        "config": "mesh",
        "metric": "hier_reduction_mesh_scaling",
        "meshes": records,
        "ok": all(r.get("ok") for r in records),
    }
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "MULTICHIP_r07.json")
    with open(path, "w") as fh:
        json.dump(out, fh, indent=2)
        fh.write("\n")
    return out


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--full", action="store_true",
                        help="billion-column scale (real TPU)")
    parser.add_argument(
        "--configs",
        default="1,2,3,4,5,mesh8,mesh,serving,mp_serving,multitenant,import,"
                "ingest,sync,hostpath,durability,tracing,profiling,chaos,"
                "scrub,autopilot,cdc,elastic",
    )
    parser.add_argument("--cpu-mesh-inner", action="store_true",
                        help=argparse.SUPPRESS)
    parser.add_argument("--mesh-inner", type=int, default=0,
                        help=argparse.SUPPRESS)
    args = parser.parse_args()
    from pilosa_tpu.utils import compile_cache

    compile_cache.configure()
    if args.cpu_mesh_inner:
        from __graft_entry__ import _ensure_devices

        _ensure_devices(8, virtual_cpu=True)
        print(json.dumps(config5_mesh_cpu8()), flush=True)
        return
    if args.mesh_inner:
        print(json.dumps(config_mesh_inner(args.mesh_inner)), flush=True)
        return
    n_shards = 954 if args.full else 4
    small = 2 if not args.full else 64
    runners = {
        "1": lambda: config1_star_trace(n_shards),
        "2": lambda: config2_taxi_topn_groupby(small),
        "3": lambda: config3_bsi_range_sum(small),
        "4": lambda: config4_time_quantum(1 if not args.full else 8),
        "5": lambda: config5_ssb_4way(n_shards),
        "serving": lambda: config_serving(
            n_shards=64 if args.full else 8,
            n_queries=1024 if args.full else 512,
            client_counts=(16, 64, 128) if args.full else (16, 64),
        ),
        "mp_serving": lambda: config_mp_serving(
            client_counts=(16, 64, 128) if args.full else (8, 32, 96),
            requests_per_client=160 if args.full else 80,
        ),
        "multitenant": lambda: config_multitenant(
            n_indexes=256 if args.full else 120,
            n_clients=16 if args.full else 8,
            requests_per_client=600 if args.full else 300,
        ),
        "readwrite": lambda: config_serving_readwrite(
            n_shards=32 if args.full else 8,
            n_ops=256 if args.full else 64,
        ),
        "import": lambda: config_import(
            n_shards=32 if args.full else 8,
            density=0.2 if args.full else 0.05,
        ),
        "ingest": lambda: config_ingest(
            n_shards=64 if args.full else 16,
            density=0.1 if args.full else 0.02,
        ),
        "sync": lambda: config_sync(
            n_fragments=384 if args.full else 192,
            n_divergent=64 if args.full else 32,
        ),
        "hostpath": lambda: config_hostpath(n_shards=8),
        "tracing": lambda: config_tracing(
            n_queries=512 if args.full else 256,
            repeats=5 if args.full else 4,
        ),
        "profiling": lambda: config_profiling(
            n_queries=768 if args.full else 512,
            repeats=5,
        ),
        "durability": lambda: config_durability(
            n_ops=1600 if args.full else 800,
            n_clients=32 if args.full else 16,
        ),
        "chaos": lambda: config_chaos(
            n_schedules=30 if args.full else 20,
            n_nodes=5 if args.full else 3,
            n_events=8 if args.full else 6,
        ),
        "scrub": lambda: config_scrub(
            n_chaos_schedules=4 if args.full else 2,
            queries_per_client=240 if args.full else 120,
        ),
        "autopilot": lambda: config_autopilot(
            hot_run_s=32.0 if args.full else 24.0,
            n_chaos_schedules=6 if args.full else 3,
        ),
        "cdc": lambda: config_cdc(
            n_chaos_schedules=6 if args.full else 3,
            read_s=8.0 if args.full else 5.0,
            n_clients=8 if args.full else 6,
        ),
        "elastic": lambda: config_elastic(
            n_clients=8 if args.full else 6,
            phase_s=6.0 if args.full else 4.0,
            n_chaos_schedules=6 if args.full else 3,
        ),
        "mesh": config_mesh,
    }
    floor = None  # lazy: only configs 1-5 report it
    for c in args.configs.split(","):
        if c == "mesh8":
            _spawn_cpu_mesh_entry()
            continue
        out = runners[c]()
        if c in "12345":
            if floor is None:
                floor = dispatch_floor_ms()
            out["dispatch_floor_ms"] = floor
        print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
