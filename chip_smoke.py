#!/usr/bin/env python3
"""Chip smoke: the served path, once, on the accelerator, at 2^30 columns.

    python chip_smoke.py                                # on the chip
    python chip_smoke.py --expect-platform cpu --shards 4   # CPU sandbox

This process never imports JAX. It starts ONE ``python -m pilosa_tpu
server`` child with default knobs — the only process that holds the chip
— and is that server's HTTP client:

1. ``GET /info`` must list only ``--expect-platform`` devices, before
   anything is loaded.
2. Data from ``--seed``: set fields ``a``/``b`` (rows 1-8, 512 random
   bits per row and shard) and int field ``v`` (0-1000
   on ~512 columns per shard), loaded over ``/import`` and
   ``/import-value`` in batches under max-writes-per-request.
3. Count-Intersect (cold, then warm), TopN, Sum, BSI range, filtered Sum,
   GroupBy, 16 concurrent Count-Intersects on keep-alive connections,
   then an acknowledged ``Set`` and its read-back — every answer compared
   with a plain numpy reference built here from the same seed.
4. SIGTERM (exit code 0 required), a second server on the same data dir:
   same answers, the ``Set`` still there (durability-mode group: 200 =
   fsynced), and no new entry in the compile cache.

Only when every check passed: exit code 0 and two stdout lines, each one
JSON object. The first is the report (versions, host layers, compile
cache, residency, seconds, ...). The last is the verdict and nothing
else, ``{"ok": true, "device": {"platform": ..., "kind": ..., "count":
...}}``, the device as the server's JAX reports it on ``/info``. Any
failed check, non-2xx response or dead server raises, which ends the run
non-zero with neither line.
"""

from __future__ import annotations

import argparse
import functools
import http.client
import importlib.metadata
import json
import os
import shutil
import signal
import socket
import statistics
import subprocess
import sys
import tempfile
import threading
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)  # a bare copy of this file finds no pilosa_tpu

from pilosa_tpu import native, wire  # noqa: E402  (neither imports JAX)
from pilosa_tpu.shardwidth import SHARD_WIDTH  # noqa: E402
from pilosa_tpu.utils import compile_cache  # noqa: E402

K_ROWS = 8                # rows 1..8 in each of fields a and b
BITS_PER_ROW_SHARD = 512
VALUES_PER_SHARD = 512
V_MAX = 1000
ROW_BYTES = SHARD_WIDTH // 8  # one dense row of one shard on the device
IMPORT_BATCH = 4608           # <= max-writes-per-request (5000)
LOAD_CONNECTIONS = 8
# Cold rows pay roaring decode + upload + an XLA compile per program
# shape; a first answer at 1,024 shards takes tens of seconds.
HTTP_TIMEOUT_S = 900.0
START_TIMEOUT_S = 300.0
STOP_TIMEOUT_S = 180.0

COLD_PAIRS = [(1, 1), (2, 3), (5, 8), (8, 2)]
# same resident rows in new combinations: fresh plans, so the lookups
# reach the residency LRU (a repeated plan is served by the executor's
# operand memo and moves no residency counter)
WARM_PAIRS = [(1, 3), (2, 8), (5, 2), (8, 1)]
BURST_PAIRS = [(k, 1 + (3 * k + j) % K_ROWS)
               for k in range(1, K_ROWS + 1) for j in (0, 1)]
WARM_RTT_SAMPLES = 20


class SmokeError(Exception):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeError(msg)


def log(msg: str) -> None:
    print(f"[chip_smoke {time.strftime('%H:%M:%S')}] {msg}",
          file=sys.stderr, flush=True)


# ------------------------------------------------------------------ client


class Client:
    """One keep-alive connection to the server; non-2xx raises."""

    def __init__(self, port: int):
        self.conn = http.client.HTTPConnection(
            "127.0.0.1", port, timeout=HTTP_TIMEOUT_S)

    def __enter__(self) -> "Client":
        return self

    def __exit__(self, *exc) -> None:
        self.conn.close()

    def raw(self, method: str, path: str, body: bytes | None = None) -> bytes:
        self.conn.request(method, path, body=body)
        resp = self.conn.getresponse()
        data = resp.read()
        check(200 <= resp.status < 300,
              f"{method} {path} -> HTTP {resp.status}: {data[:400]!r}")
        return data

    def json(self, method: str, path: str, body=None):
        if body is not None and not isinstance(body, bytes):
            body = json.dumps(body).encode()
        return json.loads(self.raw(method, path, body) or b"{}")

    def query(self, pql: str):
        """One PQL call -> its single result."""
        out = self.json("POST", "/index/i/query", pql.encode())
        check("results" in out and len(out["results"]) == 1,
              f"{pql}: malformed response {out!r}")
        return out["results"][0]

    def metrics(self) -> dict:
        """Unlabelled samples of GET /metrics."""
        out = {}
        for line in self.raw("GET", "/metrics").decode().splitlines():
            if line and not line.startswith("#") and "{" not in line:
                name, _, value = line.partition(" ")
                out[name] = float(value)
        return out


def metric(samples: dict, name: str) -> float:
    check(name in samples, f"/metrics has no series {name}")
    return samples[name]


def run_parallel(jobs) -> None:
    """Run each callable on its own thread; the first exception any of
    them raised is re-raised here, so a failed worker fails the run."""
    errors: list = []

    def guard(job) -> None:
        try:
            job()
        except BaseException as e:  # re-raised below, on the caller
            errors.append(e)

    threads = [threading.Thread(target=guard, args=(job,)) for job in jobs]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        raise errors[0]


# ------------------------------------------------------------------ server


class ServerProc:
    """The one child process: ``python -m pilosa_tpu server``, default
    knobs, environment inherited untouched (JAX picks the platform)."""

    def __init__(self, data_dir: str, log_path: str):
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            self.port = s.getsockname()[1]
        self.log_path = log_path
        self._log = open(log_path, "ab")
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "pilosa_tpu", "server", "-d", data_dir,
             "--bind", "127.0.0.1", "--port", str(self.port)],
            cwd=HERE, stdout=self._log, stderr=subprocess.STDOUT,
        )

    def wait_ready(self) -> float:
        t0 = time.monotonic()
        while time.monotonic() - t0 < START_TIMEOUT_S:
            check(self.proc.poll() is None,
                  f"server exited rc={self.proc.returncode} during start-up"
                  f"\n{self.log_tail()}")
            try:
                with Client(self.port) as c:
                    c.raw("GET", "/status")
                return time.monotonic() - t0
            except (OSError, http.client.HTTPException):
                time.sleep(0.2)
        raise SmokeError(f"server not ready after {START_TIMEOUT_S:.0f}s"
                         f"\n{self.log_tail()}")

    def stop(self) -> None:
        """SIGTERM -> clean close (snapshots, WAL, chip released)."""
        check(self.proc.poll() is None,
              f"server died rc={self.proc.returncode}\n{self.log_tail()}")
        self.proc.send_signal(signal.SIGTERM)
        rc = self.proc.wait(STOP_TIMEOUT_S)
        self._log.close()
        check(rc == 0, f"server exit code {rc} after SIGTERM"
                       f"\n{self.log_tail()}")

    def kill(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait(30)
        self._log.close()

    def log_tail(self) -> str:
        with open(self.log_path, "rb") as f:
            return f.read()[-8000:].decode(errors="replace")


# -------------------------------------------------------- data + reference


class Reference:
    """The data set and the plain numpy answers to every query shape."""

    def __init__(self, seed: int, n_shards: int):
        rng = np.random.default_rng(seed)
        self.n_shards = n_shards
        base = (np.arange(n_shards, dtype=np.int64) * SHARD_WIDTH)
        # [field][shard, row, bit] -> global column (duplicates possible;
        # the reference dedupes)
        self.set_cols = {
            f: base[:, None, None] + rng.integers(
                0, SHARD_WIDTH, (n_shards, K_ROWS, BITS_PER_ROW_SHARD),
                dtype=np.int64)
            for f in ("a", "b")
        }
        self.rows = {
            f: {k: np.unique(cols[:, k - 1, :])
                for k in range(1, K_ROWS + 1)}
            for f, cols in self.set_cols.items()
        }
        pos = rng.integers(0, SHARD_WIDTH, (n_shards, VALUES_PER_SHARD),
                           dtype=np.int64)
        vcols = np.unique((base[:, None] + pos).ravel())
        self.v_cols = vcols
        self.v_vals = rng.integers(0, V_MAX + 1, vcols.size, dtype=np.int64)

    # ---- import batches (each within one request's write limit)

    def import_batches(self):
        rows = np.repeat(np.arange(1, K_ROWS + 1, dtype=np.int64),
                         BITS_PER_ROW_SHARD).tolist()
        for f, cols in self.set_cols.items():
            for shard in range(self.n_shards):
                yield (f"/index/i/field/{f}/import",
                       {"rows": rows, "columns": cols[shard].ravel().tolist()})
        for lo in range(0, self.v_cols.size, IMPORT_BATCH):
            yield ("/index/i/field/v/import-value",
                   {"columns": self.v_cols[lo:lo + IMPORT_BATCH].tolist(),
                    "values": self.v_vals[lo:lo + IMPORT_BATCH].tolist()})

    def bits_total(self) -> int:
        return sum(r.size for rows in self.rows.values()
                   for r in rows.values())

    # ---- answers

    def count_intersect(self, k: int, j: int) -> int:
        return int(np.intersect1d(self.rows["a"][k], self.rows["b"][j],
                                  assume_unique=True).size)

    def topn_a(self, n: int) -> list:
        ranked = sorted(((int(r.size), k) for k, r in self.rows["a"].items()),
                        key=lambda ck: (-ck[0], ck[1]))
        return [{"id": k, "count": c} for c, k in ranked[:n]]

    def sum_v(self, filter_row: int | None = None) -> dict:
        vals = self.v_vals
        if filter_row is not None:
            vals = vals[np.isin(self.v_cols, self.rows["a"][filter_row])]
        return {"value": int(vals.sum()), "count": int(vals.size)}

    def count_v_gt(self, threshold: int) -> int:
        return int((self.v_vals > threshold).sum())

    def groupby_ab(self) -> list:
        out = []
        for k in range(1, K_ROWS + 1):
            for j in range(1, K_ROWS + 1):
                c = self.count_intersect(k, j)
                if c:
                    out.append({"group": [{"field": "a", "rowID": k},
                                          {"field": "b", "rowID": j}],
                                "count": c})
        return out

    def row_a_in_shard(self, k: int, shard: int) -> list:
        r = self.rows["a"][k]
        lo = shard * SHARD_WIDTH
        return r[(r >= lo) & (r < lo + SHARD_WIDTH)].tolist()

    def pick_unset_column(self) -> int:
        """A column in Row(b=1) but not yet in Row(a=1): setting it moves
        Count(Row(a=1)) and Count(Intersect(Row(a=1), Row(b=1)))."""
        return int(np.setdiff1d(self.rows["b"][1], self.rows["a"][1],
                                assume_unique=True)[0])

    def set_a1(self, col: int) -> None:
        self.rows["a"][1] = np.union1d(self.rows["a"][1], [col])


def ci(k: int, j: int) -> str:
    return f"Count(Intersect(Row(a={k}), Row(b={j})))"


# ------------------------------------------------------------------ phases


def check_devices(c: Client, expect: str) -> dict:
    info = c.json("GET", "/info")
    devices = info.get("devices") or []
    check(bool(devices), f"/info lists no devices: {info!r}")
    wrong = [d for d in devices if d.get("platform") != expect]
    check(not wrong,
          f"expected every device on platform {expect!r}, /info says "
          f"{devices!r}")
    check(info.get("shardWidth") == SHARD_WIDTH,
          f"/info shardWidth {info.get('shardWidth')} != {SHARD_WIDTH}")
    return {"platform": devices[0]["platform"], "kind": devices[0]["kind"],
            "count": len(devices)}


def load(port: int, ref: Reference) -> float:
    with Client(port) as c:
        c.json("POST", "/index/i", {})
        c.json("POST", "/index/i/field/a", {})
        c.json("POST", "/index/i/field/b", {})
        c.json("POST", "/index/i/field/v",
               {"options": {"type": "int", "min": 0, "max": V_MAX}})
    batches = ref.import_batches()
    lock = threading.Lock()
    changed = {"bits": 0, "values": 0}

    def worker() -> None:
        with Client(port) as c:
            while True:
                with lock:
                    item = next(batches, None)
                if item is None:
                    return
                path, payload = item
                n = c.json("POST", path, payload).get("changed", 0)
                with lock:
                    kind = "values" if path.endswith("-value") else "bits"
                    changed[kind] += n

    t0 = time.monotonic()
    run_parallel([worker] * LOAD_CONNECTIONS)
    seconds = time.monotonic() - t0
    check(changed["bits"] == ref.bits_total(),
          f"imports changed {changed['bits']} bits, reference has "
          f"{ref.bits_total()}")
    check(changed["values"] > 0, "import-value changed nothing")
    return seconds


def expect(c: Client, pql: str, want) -> float:
    t0 = time.monotonic()
    got = c.query(pql)
    dt = time.monotonic() - t0
    check(got == want, f"{pql}: got {_short(got)}, want {_short(want)}")
    return dt


def _short(v) -> str:
    s = json.dumps(v)
    return s if len(s) <= 600 else s[:600] + "..."


def padded_shards(n_shards: int, n_devices: int) -> int:
    """Slots on the shard axis of a stacked leaf: padded to a power of
    two and, on a mesh, to a multiple of the device count."""
    padded = 1 << (n_shards - 1).bit_length()
    return -(-padded // n_devices) * n_devices


def serial_queries(c: Client, ref: Reference, padded: int) -> dict:
    """Every single-request shape, each against the reference; residency
    counters read from the server around the cold and warm passes."""
    m0 = c.metrics()
    cold = [expect(c, ci(k, j), ref.count_intersect(k, j))
            for k, j in COLD_PAIRS]
    m1 = c.metrics()
    touched = 2 * len(COLD_PAIRS) * padded * ROW_BYTES
    check(metric(m1, "pilosa_tpu_residency_misses_total")
          > metric(m0, "pilosa_tpu_residency_misses_total"),
          "cold pass moved no residency miss: rows did not go through "
          "the device cache")
    check(metric(m1, "pilosa_tpu_residency_bytes_used") >= touched,
          f"residency holds {metric(m1, 'pilosa_tpu_residency_bytes_used'):.0f}"
          f" bytes after the cold pass, the queries touched {touched}")
    for k, j in COLD_PAIRS + WARM_PAIRS:
        expect(c, ci(k, j), ref.count_intersect(k, j))
    m2 = c.metrics()
    check(metric(m2, "pilosa_tpu_residency_hits_total")
          > metric(m1, "pilosa_tpu_residency_hits_total"),
          "warm pass moved no residency hit")
    check(metric(m2, "pilosa_tpu_residency_misses_total")
          == metric(m1, "pilosa_tpu_residency_misses_total"),
          "warm pass over resident rows missed the residency cache")
    rtts = [expect(c, ci(*COLD_PAIRS[0]), ref.count_intersect(*COLD_PAIRS[0]))
            for _ in range(WARM_RTT_SAMPLES)]

    expect(c, "TopN(a, n=3)", ref.topn_a(3))
    expect(c, 'Sum(field="v")', ref.sum_v())
    expect(c, "Count(Row(v > 500))", ref.count_v_gt(500))
    expect(c, 'Sum(Row(a=1), field="v")', ref.sum_v(filter_row=1))
    expect(c, "GroupBy(Rows(a), Rows(b))", ref.groupby_ab())
    return {"first_answer_s": cold[0],
            "warm_rtt_ms_median": statistics.median(rtts) * 1e3}


def burst(port: int, c: Client, ref: Reference) -> dict:
    """16 different Count-Intersects released together on 16 keep-alive
    connections, so the pipeline can form waves."""
    m0 = c.metrics()
    gate = threading.Barrier(len(BURST_PAIRS))

    def one(k: int, j: int) -> None:
        with Client(port) as cl:
            try:
                cl.raw("GET", "/version")  # connection up before the gate
                gate.wait(60)
            except BaseException:
                gate.abort()  # do not leave the other clients waiting
                raise
            expect(cl, ci(k, j), ref.count_intersect(k, j))

    run_parallel([functools.partial(one, k, j) for k, j in BURST_PAIRS])
    m1 = c.metrics()
    waves = (metric(m1, "pilosa_tpu_serving_waves_total")
             - metric(m0, "pilosa_tpu_serving_waves_total"))
    check(waves >= 1, "pilosa_tpu_serving_waves_total did not move")
    return {"requests": len(BURST_PAIRS), "waves": int(waves)}


def write_and_read_back(c: Client, ref: Reference) -> int:
    col = ref.pick_unset_column()
    expect(c, f"Set({col}, a=1)", True)  # 200 = fsynced (group mode)
    ref.set_a1(col)
    check_write_visible(c, ref, col)
    return col


def check_write_visible(c: Client, ref: Reference, col: int) -> None:
    expect(c, "Count(Row(a=1))", int(ref.rows["a"][1].size))
    expect(c, ci(1, 1), ref.count_intersect(1, 1))
    shard = col // SHARD_WIDTH
    got = c.query(f"Options(Row(a=1), shards=[{shard}])")
    want = ref.row_a_in_shard(1, shard)
    check(got.get("columns") == want,
          f"Row(a=1) in shard {shard}: got {_short(got)}, want "
          f"{_short(want)}")
    check(col in got["columns"], f"acknowledged Set({col}, a=1) not in Row")


def cache_entries() -> int:
    d = compile_cache.cache_dir()
    if not os.path.isdir(d):
        return 0
    return sum(1 for name in os.listdir(d) if name.endswith("-cache"))


def dist_dispatches(c: Client) -> int:
    return int(c.json("GET", "/debug/vars")["dist_reduce"]["dispatches"])


def version_of(dist: str) -> str | None:
    try:
        return importlib.metadata.version(dist)
    except importlib.metadata.PackageNotFoundError:
        return None


# -------------------------------------------------------------------- main


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--expect-platform", default="tpu",
                    help="platform every /info device must report")
    ap.add_argument("--shards", type=int, default=1024,
                    help="shards of 2^20 columns (default 1024 = 2^30)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-dir", default=None,
                    help="keep the servers' logs here (default: a temp "
                         "dir removed at exit; failures print the tail)")
    args = ap.parse_args()
    t_start = time.monotonic()

    # an inherited platform pin that excludes the expected platform is
    # an error now, not after the load
    pinned = os.environ.get("JAX_PLATFORMS", "")
    check(not pinned or args.expect_platform in pinned.split(","),
          f"JAX_PLATFORMS={pinned!r} excludes the expected platform "
          f"{args.expect_platform!r}; the server child inherits it. Pass "
          "--expect-platform to run on another platform on purpose.")

    toolchain = {"g++": shutil.which("g++") is not None,
                 "protoc": shutil.which("protoc") is not None}
    host_layers = {"native": native.available(), "wire": wire.available()}

    # the parent handles SIGTERM like an exception so the child dies too
    signal.signal(signal.SIGTERM, lambda *a: sys.exit(143))

    work = tempfile.mkdtemp(prefix="chip_smoke_")
    data_dir = os.path.join(work, "data")
    log_dir = os.path.abspath(args.log_dir) if args.log_dir else work
    os.makedirs(log_dir, exist_ok=True)
    server = None
    try:
        # ---------------- generation 1: load, query, write
        server = ServerProc(data_dir, os.path.join(log_dir, "server-1.log"))
        start_s = server.wait_ready()
        with Client(server.port) as c:
            device = check_devices(c, args.expect_platform)
        log(f"server up in {start_s:.1f}s on {device}")
        ref = Reference(args.seed, args.shards)
        load_s = load(server.port, ref)
        log(f"loaded {ref.bits_total()} bits + {ref.v_cols.size} values "
            f"in {load_s:.1f}s")
        padded = padded_shards(args.shards, device["count"])
        with Client(server.port) as c:
            d0 = dist_dispatches(c)
            run1 = serial_queries(c, ref, padded)
            log(f"serial queries ok (first answer {run1['first_answer_s']:.1f}s,"
                f" warm rtt {run1['warm_rtt_ms_median']:.2f} ms)")
            burst1 = burst(server.port, c, ref)
            col = write_and_read_back(c, ref)
            mesh_dispatches = dist_dispatches(c) - d0
            residency = c.metrics()
        check((mesh_dispatches > 0) == (device["count"] > 1),
              f"{device['count']} device(s) but {mesh_dispatches} mesh "
              "dispatches: the server did not pick its executor from the "
              "device count")
        server.stop()
        entries_run1 = cache_entries()
        check(entries_run1 > 0,
              f"no compile-cache entry in {compile_cache.cache_dir()}")
        log(f"generation 1 closed cleanly; {entries_run1} cache entries")

        # ---------------- generation 2: same data dir, same answers
        server = ServerProc(data_dir, os.path.join(log_dir, "server-2.log"))
        server.wait_ready()
        with Client(server.port) as c:
            check(check_devices(c, args.expect_platform) == device,
                  "second server reports different devices")
            run2 = serial_queries(c, ref, padded)
            check_write_visible(c, ref, col)
            # counted before the burst: which micro-batch sizes a burst
            # compiles depends on how its waves happen to form
            entries_run2 = cache_entries()
            check(entries_run2 == entries_run1,
                  f"restart compiled {entries_run2 - entries_run1} programs "
                  "the cache should have held")
            burst(server.port, c, ref)
        server.stop()
        server = None
    finally:
        if server is not None:
            server.kill()
        shutil.rmtree(work, ignore_errors=True)

    check("jax" not in sys.modules, "the smoke's parent imported jax")
    report = {
        "report": "chip_smoke",
        "versions": {"python": sys.version.split()[0],
                     **{d: version_of(d)
                        for d in ("jax", "jaxlib", "libtpu", "numpy")}},
        "shards": args.shards,
        "columns": args.shards * SHARD_WIDTH,
        "seed": args.seed,
        "executor": "mesh" if mesh_dispatches else "single-device",
        "host_layers": host_layers,
        "toolchain": toolchain,
        "compile_cache": {"dir": compile_cache.cache_dir(),
                          "entries_run1": entries_run1,
                          "entries_run2": entries_run2},
        "residency": {
            key: int(metric(residency, f"pilosa_tpu_residency_{key}"))
            for key in ("bytes_used", "budget_bytes", "misses_total",
                        "hits_total", "evictions_total")
        },
        "seconds": {
            "server_start": round(start_s, 2),
            "load": round(load_s, 2),
            "first_answer_cold": round(run1["first_answer_s"], 2),
            "first_answer_after_restart": round(run2["first_answer_s"], 2),
            "total": round(time.monotonic() - t_start, 2),
        },
        # client-side observations, not benchmark metrics: the warm round
        # trip is an upper bound on the dispatch floor (ROADMAP S1(e))
        "info": {
            "warm_count_intersect_rtt_ms_median":
                round(run1["warm_rtt_ms_median"], 3),
            "warm_rtt_samples": WARM_RTT_SAMPLES,
            "burst": burst1,
            "set_column": col,
        },
    }
    print(json.dumps(report))
    # the last line is the verdict alone: exactly these keys
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeError as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        sys.exit(1)
