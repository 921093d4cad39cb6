"""The stage site (ISSUE 25 / docs/OBSERVABILITY.md "Stages"): one context
manager at every layer boundary of the served path feeding four sinks —
always-on counters, the sampled span tree, a profiler annotation while a
device capture runs, and the inspector's ``stage`` — plus named programs,
the compile and memory series, and ``trace-report``.

On the CPU; nothing here is timed against a wall-clock limit.
"""

import glob
import os
import re
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from cluster_helpers import req, uri
from pilosa_tpu.utils import tracing
from pilosa_tpu.utils.tracing import (
    STAGES,
    TOP_LEVEL_STAGES,
    global_tracer,
    stage,
    stage_metrics,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "tests", "benchmarks"))

READ_PATH = ("http.query", "http.read", "qos.admit", "pql.parse",
             "pipeline.wave", "pipeline.submit", "executor.plan",
             "executor.operands", "residency.miss", "device.dispatch",
             "executor.resolve", "device.readback", "result.encode",
             "http.write")
WRITE_PATH = ("http.query", "http.read", "qos.admit", "pql.parse",
              "executor.execute", "fragment.write", "residency.patch",
              "wal.barrier", "result.encode", "http.write")


@pytest.fixture(autouse=True)
def _sampling_off():
    tracer = global_tracer()
    tracer.sample_rate = 0.0
    tracer.clear()
    yield
    tracer.sample_rate = 0.0
    tracer.clear()


@pytest.fixture()
def server(tmp_path):
    from pilosa_tpu.server import Server, ServerConfig

    s = Server(ServerConfig(
        data_dir=str(tmp_path / "node"), port=0, name="t",
        anti_entropy_interval=0, heartbeat_interval=0,
    )).open()
    base = uri(s)
    req("POST", f"{base}/index/i", {})
    req("POST", f"{base}/index/i/field/f", {})
    req("POST", f"{base}/index/i/field/f/import",
        {"rows": [1, 1, 2, 2], "columns": [1, 2, 2, 3]})
    yield s
    s.close()


SUFFIXES = ("_total", "_seconds_total", "_cpu_entries_total",
            "_cpu_wall_seconds_total", "_cpu_seconds_total")
THREAD_SERIES = ("thread_handler_cpu_seconds_total",
                 "thread_dispatcher_cpu_seconds_total",
                 "thread_wal_commit_cpu_seconds_total",
                 "process_cpu_seconds_total")


def _key(name: str) -> str:
    return name.replace(".", "_")


def _counts() -> dict:
    m = stage_metrics()
    return {n: (m[f"{_key(n)}_total"], m[f"{_key(n)}_seconds_total"])
            for n in STAGES}


def _entered(before: dict, after: dict) -> dict:
    return {n: after[n][0] - before[n][0] for n in STAGES}


# ------------------------------------------------------------ the counters


def test_stage_counts_are_exact_under_threads():
    """8 threads x 10,000 entries with the interpreter switching threads
    every 10 us: an unlocked ``+=`` loses updates here, the counter must
    not."""
    name = "test.exact"

    def work():
        for _ in range(10_000):
            with stage(name):
                pass

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=work) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(interval)
    m = stage_metrics()
    assert m["test_exact_total"] == 80_000
    assert m["test_exact_seconds_total"] > 0


def test_every_stage_series_is_present_and_zero_on_the_first_scrape(tmp_path):
    """A fresh server process: every stage's two series on /metrics and in
    /debug/vars ``stages``, value 0, before any query ran (rate() windows
    and the benchmark's deltas never see a series appear mid-flight)."""
    log = tmp_path / "server.log"
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"),
               PYTHONPATH=ROOT + os.pathsep + os.environ.get("PYTHONPATH", ""))
    with open(log, "wb") as out:
        proc = subprocess.Popen(
            [sys.executable, "-m", "pilosa_tpu", "server", "-d",
             str(tmp_path / "data"), "--bind", "127.0.0.1", "--port", "0"],
            cwd=ROOT, env=env, stdout=out, stderr=subprocess.STDOUT)
    try:
        port = 0
        for _ in range(600):
            m = re.search(rb"listening on https?://[^ :]+:(\d+) ",
                          log.read_bytes())
            if m:
                port = int(m.group(1))
                break
            assert proc.poll() is None, log.read_text()[-2000:]
            time.sleep(0.1)
        assert port, log.read_text()[-2000:]
        base = f"http://127.0.0.1:{port}"
        text = req("GET", f"{base}/metrics", raw=True).decode()
        samples = dict(line.split(" ", 1) for line in text.splitlines()
                       if line and not line.startswith("#")
                       and "{" not in line)
        assert len(STAGES) == 26  # executor.prune_level joined in PR 42
        for name in STAGES:
            for suffix in SUFFIXES:
                series = f"pilosa_tpu_stage_{_key(name)}{suffix}"
                assert samples.get(series) == "0", series
                assert f"# TYPE {series} counter" in text
                assert f"# HELP {series} " in text
        # the CPU of the three thread roles and of the process: unlabelled
        # counters in a block of their own, there before any query ran
        for series in THREAD_SERIES:
            assert float(samples[f"pilosa_tpu_{series}"]) >= 0, series
            assert f"# TYPE pilosa_tpu_{series} counter" in text
            assert f"# HELP pilosa_tpu_{series} " in text
        for role in ("dispatcher", "wal_commit"):  # no such thread yet
            assert samples[
                f"pilosa_tpu_thread_{role}_cpu_seconds_total"] == "0"
        assert float(samples["pilosa_tpu_process_cpu_seconds_total"]) > 0
        for series in ("pilosa_tpu_device_compiles_total",
                       "pilosa_tpu_device_compile_seconds_total",
                       "pilosa_tpu_device_compile_cache_loads_total",
                       "pilosa_tpu_device_memory_bytes_in_use",
                       "pilosa_tpu_device_memory_peak_bytes"):
            assert series in samples, series
        # the per-device gauges beside the unlabelled sums
        assert 'pilosa_tpu_device_memory_bytes_in_use{device="0"}' in text
        debug_vars = req("GET", f"{base}/debug/vars")
        stages = debug_vars["stages"]
        assert set(stages) == {f"{_key(n)}{s}" for n in STAGES
                               for s in SUFFIXES}
        assert all(v == 0 for v in stages.values())
        assert set(debug_vars["threads"]) == set(THREAD_SERIES)
    finally:
        proc.terminate()
        proc.wait(60)


def test_a_served_read_and_a_served_write_enter_their_stages(server):
    base = uri(server)
    before = _counts()
    assert req("POST", f"{base}/index/i/query",
               b"Count(Intersect(Row(f=1), Row(f=2)))") == {"results": [1]}
    mid = _counts()
    entered = _entered(before, mid)
    for name in READ_PATH:
        assert entered[name] >= 1, (name, entered)
    assert entered["http.query"] == 1
    for name in ("executor.execute", "wal.barrier", "fragment.write"):
        assert entered[name] == 0, name
    # the flat partition: the top-level stages never cover more than
    # their root
    top = sum(mid[n][1] - before[n][1] for n in TOP_LEVEL_STAGES)
    root = mid["http.query"][1] - before["http.query"][1]
    assert 0 < top <= root

    # the row is resident now, so the write patches it on the device
    assert req("POST", f"{base}/index/i/query",
               b"Set(9, f=1)") == {"results": [True]}
    after = _counts()
    entered = _entered(mid, after)
    for name in WRITE_PATH:
        assert entered[name] >= 1, (name, entered)
    for name in ("pipeline.wave", "pipeline.submit", "executor.resolve"):
        assert entered[name] == 0, name
    top = sum(after[n][1] - mid[n][1] for n in TOP_LEVEL_STAGES)
    root = after["http.query"][1] - mid["http.query"][1]
    assert 0 < top <= root
    # and the counters are what /debug/vars serves
    stages = req("GET", f"{base}/debug/vars")["stages"]
    assert stages["wal_barrier_total"] >= 1
    assert stages["http_query_seconds_total"] > 0


def test_dispatch_elapsed_feeds_the_cost_plane_from_the_same_clock_pair(
        server):
    """One clock-read pair at device.dispatch: the PROFILE's deviceMs is
    the stage's own elapsed time, so the cost plane can never report more
    dispatch time than the stage counted."""
    base = uri(server)
    before = _counts()["device.dispatch"]
    out = req("POST", f"{base}/index/i/query?profile=true",
              b"Count(Row(f=2))")
    after = _counts()["device.dispatch"]
    assert out["results"] == [2]
    assert after[0] - before[0] >= 1
    device_ms = out["profile"]["totals"]["deviceMs"]
    assert 0 < device_ms <= (after[1] - before[1]) * 1e3 + 1e-3  # rounded
    import inspect

    from pilosa_tpu.executor.executor import Executor

    for fn in (Executor._dispatch, Executor._flush_group_locked):
        assert "perf_counter" not in inspect.getsource(fn)


# ------------------------------------------------------ nothing when off


def _counting(cls):
    """A subclass of ``cls`` that counts its constructions."""

    class Counted(cls):
        n = 0

        def __init__(self, *a, **kw):
            Counted.n += 1
            super().__init__(*a, **kw)

    return Counted


def test_no_span_and_no_annotation_with_sampling_off_and_no_capture(
        server, monkeypatch):
    import jax

    spans = _counting(tracing.Span)
    notes = _counting(jax.profiler.TraceAnnotation)
    monkeypatch.setattr(tracing, "Span", spans)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", notes)
    base = uri(server)
    before = _counts()["http.query"][0]
    req("POST", f"{base}/index/i/query", b"Count(Row(f=1))")
    req("POST", f"{base}/index/i/query", b"Set(11, f=2)")
    assert _counts()["http.query"][0] == before + 2  # the sites did run
    assert spans.n == 0 and notes.n == 0
    # the power of the check: both constructors are the ones the sites use
    global_tracer().sample_rate = 1.0
    req("POST", f"{base}/index/i/query", b"Count(Row(f=1))")
    assert spans.n >= len(READ_PATH) - 1  # residency.miss: row is resident
    assert notes.n == 0
    global_tracer().sample_rate = 0.0
    spans.n = 0
    with tracing.start_jax_trace(str(server.config.data_dir) + "/cap"):
        req("POST", f"{base}/index/i/query", b"Count(Row(f=1))")
    assert notes.n >= len(READ_PATH) - 1 and spans.n == 0
    assert tracing._annotation is None  # cleared after stop_trace


def test_a_sampled_tree_holds_the_old_names_and_the_new_children(server):
    base = uri(server)
    global_tracer().sample_rate = 1.0
    req("POST", f"{base}/index/i/query", b"Count(Row(f=1))")
    req("POST", f"{base}/index/i/query", b"Set(12, f=1)")
    trees = req("GET", f"{base}/debug/traces")["traces"]
    assert [t["name"] for t in trees] == ["http.query", "http.query"]

    def names(tree, out):
        out.append(tree["name"])
        for c in tree["children"]:
            names(c, out)
        return out

    read, write = (set(names(t, [])) for t in trees)
    # before this PR
    assert {"http.query", "qos.admit", "pipeline.wave", "executor.Execute",
            "executeCount", "device.dispatch"} <= read
    assert {"http.query", "qos.admit", "wal.barrier"} <= write
    # new with the stage site
    assert {"http.read", "pql.parse", "pipeline.submit", "executor.plan",
            "executor.operands", "executor.resolve", "device.readback",
            "result.encode", "http.write"} <= read
    assert {"http.read", "pql.parse", "executor.execute", "fragment.write",
            "result.encode", "http.write"} <= write
    # children nest where the work happened; the dispatcher's submit
    # runs under the context captured before the wait began, so it is
    # the wave's sibling, as device.dispatch always was
    tree = trees[0]
    top = {c["name"]: c for c in tree["children"]}
    submit = [c["name"] for c in top["pipeline.submit"]["children"]]
    assert "executor.plan" in submit and "executor.operands" in submit
    assert tree["tags"]["tenant"] == "default"
    # one clock: a child never outlasts its parent
    assert top["pipeline.wave"]["durationMs"] <= tree["durationMs"]
    # the second clock reaches the sampled span: a stage's CPU inside its
    # wall time (both rounded to a microsecond)
    for child in [tree, *top.values()]:
        assert 0 <= child["tags"]["cpu_ms"] <= child["durationMs"] + 0.001


# ------------------------------------------------------------ the inspector


def test_debug_queries_stage_follows_the_sites(server):
    base = uri(server)
    gate, release = threading.Event(), threading.Event()
    seen = {}

    def held(at: str, fn):
        def wrapper(*a, **kw):
            if not gate.is_set():
                seen[at] = req("GET", f"{base}/debug/queries")["queries"]
                gate.set()
                release.wait(10)
            return fn(*a, **kw)
        return wrapper

    def run(at: str, pql: bytes, patch):
        gate.clear(), release.clear()
        restore = patch(at)
        try:
            t = threading.Thread(
                target=req, args=("POST", f"{base}/index/i/query", pql),
                daemon=True)
            t.start()
            assert gate.wait(10)
            release.set()
            t.join(30)
        finally:
            restore()
        (entry,) = seen[at]
        return entry

    def patch_submit(at):
        ex = server.api.executor
        real = ex.submit
        ex.submit = held(at, real)
        return lambda: setattr(ex, "submit", real)

    def patch_barrier(at):
        wal = server.api.holder.wal
        real = wal.barrier
        wal.barrier = held(at, real)
        return lambda: setattr(wal, "barrier", real)

    entry = run("submit", b"Count(Row(f=2))", patch_submit)
    assert entry["stage"] == "pipeline.submit" and entry["pql"] == \
        "Count(Row(f=2))"
    entry = run("barrier", b"Set(13, f=2)", patch_barrier)
    assert entry["stage"] == "wal.barrier"
    assert req("GET", f"{base}/debug/queries")["queries"] == []
    # one notation: the hand-written assignments are gone
    for rel in ("pilosa_tpu/server/api.py", "pilosa_tpu/server/http.py",
                "pilosa_tpu/server/pipeline.py"):
        with open(os.path.join(ROOT, rel)) as f:
            assert "inflight.stage =" not in f.read(), rel


# --------------------------------------------------------------- the capture


def test_a_capture_holds_the_stages_with_a_request_id_and_no_python_frames(
        server):
    from jax.profiler import ProfileData

    base = uri(server)
    stop = threading.Event()

    def load():
        while not stop.is_set():
            req("POST", f"{base}/index/i/query", b"Count(Row(f=1))")
            req("POST", f"{base}/index/i/query", b"Set(14, f=2)")

    t = threading.Thread(target=load, daemon=True)
    t.start()
    try:
        out = req("POST", f"{base}/debug/trace-device?secs=0.5")
    finally:
        stop.set()
        t.join(30)
    assert set(out) == {"logDir", "seconds"}  # the response keeps its keys
    (path,) = glob.glob(os.path.join(out["logDir"], "**", "*.xplane.pb"),
                        recursive=True)
    data = ProfileData.from_file(path)
    host = [p for p in data.planes if p.name == "/host:CPU"]
    assert host
    by_name: dict = {}
    python_frames = 0
    for plane in host:
        for line in plane.lines:
            for e in line.events:
                if e.name in STAGES:
                    by_name.setdefault(e.name, []).append(
                        {k: v for k, v in e.stats})
                elif e.name.startswith("$"):
                    python_frames += 1
    assert python_frames == 0  # python_tracer_level = 0
    for name in ("http.query", "pipeline.wave", "pipeline.submit",
                 "device.dispatch", "executor.resolve", "wal.barrier",
                 "fragment.write", "http.write"):
        assert name in by_name, (name, sorted(by_name))
    assert all("rid" in stats for evs in by_name.values() for stats in evs)
    # one identifier per request, shared by its stages on both threads
    rids = {s["rid"] for s in by_name["pipeline.submit"]}
    assert rids and 0 not in rids
    # (a request in flight when the capture starts or stops loses the
    # annotations that began before or end after it)
    for outer in ("http.query", "pipeline.wave"):
        shared = rids & {s["rid"] for s in by_name[outer]}
        assert shared and len(rids - shared) <= 2, outer
    # and the operator's reading of the same file
    report = tracing.trace_report(out["logDir"])
    assert report["python_tracer_events"] == 0
    assert report["host_threads_with_stages"] >= 2
    # the capture carries the counters: inside it every site read both
    # clocks, and stages.json beside the .xplane.pb holds the deltas
    import json

    with open(os.path.join(os.path.dirname(path), "stages.json")) as f:
        counters = json.load(f)
    assert 0 < counters["span_s"] <= out["seconds"]
    assert set(counters["threads"]) == set(THREAD_SERIES)
    stages = counters["stages"]
    for name in ("http.query", "pipeline.submit", "wal.commit",
                 "device.dispatch"):
        key = _key(name)
        assert 1 <= stages[f"{key}_cpu_entries_total"] <= stages[
            f"{key}_total"], name
        assert 0 <= stages[f"{key}_cpu_seconds_total"] <= stages[
            f"{key}_cpu_wall_seconds_total"], name
    by_stage = {row[0]: row for row in report["counters"]["stage_cpu_s"]}
    assert by_stage["http.query"][3] == stages["http_query_cpu_entries_total"]
    assert report["counters"]["thread_cpu_s"]["thread_handler"] > 0
    text = tracing.format_trace_report(report)
    assert "stage CPU seconds over " in text
    assert "CPU seconds by thread role over " in text


def test_trace_report_labels_gaps_by_the_rule():
    """A written trace whose answers are known: device busy 0-1 ms, 5-6 ms,
    9-10 ms and 20-21 ms. The dispatcher thread is in pipeline.submit
    (device.dispatch nested in its tail) over the first gap while a request
    thread waits in pipeline.wave; nothing is staged over the second gap;
    only a waiting request thread covers the third."""
    from xplane_writer import xspace

    us = 1000
    planes = [
        ("/device:TPU:0", [
            ("XLA Modules", [("jit_count_b8(1)", 0, 1000 * us),
                             ("jit_count_b8(1)", 5000 * us, 1000 * us),
                             ("jit_groupby_level(2)", 9000 * us, 1000 * us),
                             ("jit_count_b8(1)", 20000 * us, 1000 * us)]),
            ("XLA Ops", [("%fusion.1 = u32[8]{0} fusion()", 0, 1000 * us),
                         ("%fusion.1 = u32[8]{0} fusion()", 5000 * us,
                          1000 * us),
                         ("%fusion.2 = u32[8]{0} fusion()", 9000 * us,
                          1000 * us),
                         ("%fusion.1 = u32[8]{0} fusion()", 20000 * us,
                          1000 * us)]),
        ]),
        ("/host:CPU", [
            ("python/1", [("pipeline.submit", 1200 * us, 3600 * us),
                          ("device.dispatch", 4400 * us, 400 * us),
                          ("PjitFunction(count_b8)", 4450 * us, 300 * us)]),
            ("python/2", [("http.query", 500 * us, 19000 * us),
                          ("pipeline.wave", 1000 * us, 4000 * us),
                          ("pipeline.wave", 10000 * us, 9500 * us)]),
        ]),
    ]
    import tempfile

    with tempfile.TemporaryDirectory() as d:
        os.makedirs(os.path.join(d, "plugins", "profile", "x"))
        with open(os.path.join(d, "plugins", "profile", "x",
                               "t.xplane.pb"), "wb") as f:
            f.write(xspace(planes))
        report = tracing.trace_report(d)
        cli = subprocess.run(
            [sys.executable, "-m", "pilosa_tpu", "trace-report", d],
            cwd=ROOT, capture_output=True, text=True, timeout=120,
            env=dict(os.environ, JAX_PLATFORMS="cpu"))
    (dev,) = report["devices"]
    assert dev["device"] == "/device:TPU:0"
    assert dev["busy_s"] == pytest.approx(0.004)
    assert dev["busy_share"] == pytest.approx(4 / 21)
    assert dict(dev["modules"]) == pytest.approx(
        {"jit_count_b8": 0.003, "jit_groupby_level": 0.001})
    assert dict(dev["ops"])["fusion.1 u32[8]"] == pytest.approx(0.003)
    gaps = {round(g["seconds"] * 1e3): g for g in dev["idle_gaps"]}
    assert sorted(gaps) == [3, 4, 10]
    # 1-5 ms: submit is innermost 1.2-4.4 (80 %), dispatch 4.4-4.8 (10 %);
    # the waiting request thread covers all of it and does not win
    first = gaps[4]
    assert first["label"] == "pipeline.submit"
    assert first["share"] == pytest.approx(0.8)
    assert dict(first["stages"])["pipeline.wave"] == pytest.approx(1.0)
    assert dict(first["stages"])["device.dispatch"] == pytest.approx(0.1)
    # 6-9 ms: only the request's root overlaps, which is a stage too
    assert gaps[3]["label"] == "http.query"
    # 10-20 ms: nothing but a wait stage, so the wait stage labels it
    assert gaps[10]["label"] == "pipeline.wave"
    assert gaps[10]["share"] == pytest.approx(0.95)
    assert cli.returncode == 0, cli.stderr[-2000:]
    assert "idle gap 0.0040 s at +0.001 s: pipeline.submit 80%" in cli.stdout
    assert "XLA module jit_count_b8: 0.0030 s" in cli.stdout
    assert "busy 19.0 %" in cli.stdout


def _capture_dir(tmp_path, payload: bytes | None):
    d = tmp_path / "plugins" / "profile" / "x"
    d.mkdir(parents=True)
    if payload is not None:
        (d / "t.xplane.pb").write_bytes(payload)
    return str(tmp_path)


def _cpu_only_capture():
    from xplane_writer import xspace

    us = 1000
    return xspace([("/host:CPU", [
        # a CPU capture's stand-in for device lanes; its zero-length
        # thread-pool markers are not operations
        ("tf_XLA_worker_0", [("fusion.7", 0, 25 * us),
                             ("ThreadpoolListener::Record", 30 * us, 0),
                             ("fusion.7", 50 * us, 25 * us)]),
        ("python/1", [("pipeline.submit", 10 * us, 30 * us)]),
    ])])


def _stages_only_capture():
    from xplane_writer import xspace

    us = 1000
    return xspace([("/host:CPU", [
        ("python/1", [("http.query", 0, 900 * us),
                      ("pql.parse", 100 * us, 200 * us)]),
    ])])


@pytest.mark.parametrize("payload,said,busy_s", [
    (None, "no .xplane.pb under", None),
    (lambda: b"not a protobuf at all", "is not a readable .xplane.pb", None),
    (lambda: b"", "no operation ran on any device in this capture", 0),
    (_stages_only_capture,
     "no operation ran on any device in this capture", 0),
    (_cpu_only_capture, "/host:CPU: busy 66.7 % of 0.000 s", 50e-6),
], ids=["empty-directory", "corrupt-file", "empty-file",
        "no-device-operation", "cpu-only-host"])
def test_trace_report_on_a_malformed_or_poor_capture(tmp_path, capsys,
                                                     payload, said, busy_s):
    """What an operator can point ``trace-report`` at after a capture
    went wrong: a directory with no capture in it, a file that is not
    one or was cut short, a capture in which nothing ran on a device, a
    CPU host's. Each is a line that says so and an exit code, never a
    traceback; and a CPU host's worker threads stand in for the device,
    their zero-length markers left out."""
    from pilosa_tpu import cli

    d = _capture_dir(tmp_path, payload() if payload else None)
    rc = cli.main(["trace-report", d])
    out = capsys.readouterr()
    if busy_s is None:  # unreadable: said on stderr, exit code 1
        assert rc == 1 and said in out.err, out
        return
    assert rc == 0 and said in out.out, out
    report = tracing.trace_report(d)
    assert [dev["busy_s"] for dev in report["devices"]] == (
        [pytest.approx(busy_s)] if busy_s else [])


def test_an_uncovered_gap_reads_no_request():
    timelines = [tracing._innermost([(0.0, 1.0, "http.query"),
                                     (0.2, 0.4, "pql.parse")])]
    assert tracing._innermost([(0.0, 1.0, "a"), (0.2, 0.4, "b")]) == [
        (0.0, 0.2, "a"), (0.2, 0.4, "b"), (0.4, 1.0, "a")]
    assert tracing.label_gap((2.0, 3.0), timelines) == ("no-request", 0.0, [])
    # under the 10 % threshold is as good as nothing
    label, share, ranked = tracing.label_gap((0.95, 2.0), timelines)
    assert label == "no-request" and ranked == []
    label, share, ranked = tracing.label_gap((0.1, 0.6), timelines)
    assert (label, share) == ("http.query", pytest.approx(0.6))
    assert ranked[1] == ["pql.parse", pytest.approx(0.4)]


# ------------------------------------------------------- programs with names


def _u32(*shape):
    import jax.numpy as jnp

    return jnp.zeros(shape, jnp.uint32)


def _module_name(lowered_text: str) -> str:
    return re.search(r"module @(\S+)", lowered_text).group(1)


PROGRAMS = {
    "jit_count": lambda b, e, r: b.local_fn(
        ("count", ("and", ("leaf", 0), ("leaf", 1))), "count", (1, 1), 0
    ).lower(_u32(2, 64), _u32(2, 64)),
    "jit_count_b8": lambda b, e, r: b.local_fn_batched(
        ("count", ("leaf", 0)), "count", (1,), 0, 8
    ).lower(*[_u32(2, 64)] * 8),
    "jit_countrows": lambda b, e, r: b.local_fn(
        ("countrows", 0, None), "countrows", (2,), 0).lower(_u32(2, 4, 64)),
    "jit_bsisum_b2": lambda b, e, r: b.local_fn_batched(
        ("bsisum", 0, None), "bsisum", (2,), 0, 2
    ).lower(*[_u32(2, 6, 64)] * 2),
    "jit_max": lambda b, e, r: b.local_fn(
        ("bsiminmax", 1, 0, None), "max", (2,), 0).lower(_u32(2, 6, 64)),
    "jit_row": lambda b, e, r: b.local_fn(
        ("or", ("leaf", 0), ("leaf", 1)), "row", (1, 1), 0
    ).lower(_u32(2, 64), _u32(2, 64)),
    "jit_groupby_level": lambda b, e, r: b.local_groupby_level_fn(
        ("leaf", 0), 1, 0, 1, 0
    ).lower(_u32(2, 128), _u32(2, 4, 128), np.zeros(8, np.int32)),
    "jit_or_delta": lambda b, e, r: b._or_delta.lower(
        _u32(2, 64), b._delta_args((0,), [5])),
    "jit_andnot_delta": lambda b, e, r: b._andnot_delta.lower(
        _u32(2, 64), b._delta_args((0,), [5])),
    "jit_or_delta_row": lambda b, e, r: b._or_delta_row.lower(
        _u32(2, 4, 64), b._delta_args((0, 1), [5])),
    "jit_andnot_delta_row": lambda b, e, r: b._andnot_delta_row.lower(
        _u32(2, 4, 64), b._delta_args((0, 1), [5])),
    "jit_expr": lambda b, e, r: e._build(("leaf", 0)).lower(
        (_u32(64),), ()),
    "jit_gather_blocks": lambda b, e, r: r._gather_blocks.lower(
        _u32(2048), np.zeros(1, np.int32), block_words=1024),
    "jit_scatter_blocks": lambda b, e, r: r._scatter_blocks.lower(
        _u32(1, 1024), np.zeros(1, np.int32), n_blocks=2, block_words=1024),
}


@pytest.mark.parametrize("want", sorted(PROGRAMS))
def test_a_program_builder_names_its_module(want):
    from pilosa_tpu.executor import batch, expr
    from pilosa_tpu.storage import residency

    text = PROGRAMS[want](batch, expr, residency).as_text()
    assert _module_name(text) == want
    assert "jit_body" not in text


DIST_PROGRAMS = {
    "jit_dist_count": lambda d, mesh: d._dist_fn(
        mesh, ("count", ("leaf", 0)), "count", (1,), 0),
    "jit_dist_count_b4": lambda d, mesh: d._dist_fn_batched(
        mesh, ("count", ("leaf", 0)), "count", (1,), 0, 4),
    "jit_dist_groupby_level": lambda d, mesh: d._dist_groupby_level_fn(
        mesh, ("leaf", 0), 1, 0, 1, 0),
}


@pytest.mark.parametrize("want", sorted(DIST_PROGRAMS))
def test_a_mesh_program_builder_names_its_module(want):
    from pilosa_tpu.parallel import dist
    from pilosa_tpu.parallel.mesh import make_mesh

    mesh = make_mesh()
    fn = DIST_PROGRAMS[want](dist, mesh)
    n = mesh.size
    if want == "jit_dist_count":
        args = [_u32(n, 64)]
    elif want == "jit_dist_count_b4":
        args = [_u32(n, 64)] * 4
    else:
        args = [_u32(n, 128), _u32(n, 4, 128), np.zeros(8, np.int32)]
    assert _module_name(fn.lower(*args).as_text()) == want


def test_groupby_level_scopes_its_kernel_and_a_filter_made_outside_it():
    from pilosa_tpu.executor import batch

    text = PROGRAMS["jit_groupby_level"](batch, None, None).as_text(
        debug_info=True)
    assert "groupby_level" in text and "groupby_filter" not in text
    # a Shift cannot be taken tile by tile: XLA evaluates it, in a scope
    shifted = batch.local_groupby_level_fn(
        ("shift", ("leaf", 0), 0), 1, 1, 1, 0
    ).lower(_u32(2, 128), _u32(2, 4, 128), np.zeros(9, np.int32))
    assert "groupby_filter" in shifted.as_text(debug_info=True)


# ------------------------------------------------- compiles and device memory


def test_compiles_total_rises_on_a_new_shape_and_not_on_a_repeat():
    from pilosa_tpu.utils.compile_cache import named_jit

    fn = named_jit("stage_test_probe", lambda x: (x * 3).sum())
    a, b = np.ones((7, 3), np.float32), np.ones((9, 3), np.float32)
    base = tracing.device_metrics()  # installs the listener
    fn(a).block_until_ready()
    first = tracing.device_metrics()
    assert first["compiles_total"] == base["compiles_total"] + 1
    assert first["compile_seconds_total"] > base["compile_seconds_total"]
    fn(a).block_until_ready()
    assert tracing.device_metrics()["compiles_total"] == \
        first["compiles_total"]
    fn(b).block_until_ready()
    assert tracing.device_metrics()["compiles_total"] == \
        first["compiles_total"] + 1


def test_memory_gauges_and_compile_series_are_served(server):
    base = uri(server)
    text = req("GET", f"{base}/metrics", raw=True).decode()
    for series in ("device_memory_bytes_in_use", "device_memory_peak_bytes"):
        assert f"# TYPE pilosa_tpu_{series} gauge" in text
        assert re.search(rf"^pilosa_tpu_{series} \d+$", text, re.M)
        assert re.search(rf'^pilosa_tpu_{series}{{device="\d+"}} \d+$',
                         text, re.M)
    assert "# TYPE pilosa_tpu_device_compiles_total counter" in text
    device = req("GET", f"{base}/debug/vars")["device"]
    assert set(device) == {"compiles_total", "compile_seconds_total",
                           "compile_cache_loads_total",
                           "memory_bytes_in_use", "memory_peak_bytes"}
    assert device["compiles_total"] >= 0
    obs = req("GET", f"{base}/debug/vars")["observability"]
    assert "pql_parse_memo_hits_total" in obs


def test_the_tracing_boolean_is_gone():
    from pilosa_tpu.cli import main
    from pilosa_tpu.server import ServerConfig
    from pilosa_tpu.utils.tracing import Tracer

    with pytest.raises(TypeError):
        ServerConfig(tracing=True)
    assert not hasattr(Tracer(), "enabled")
    assert "tracing" not in ServerConfig.from_dict(
        {"tracing": True, "trace-sample-rate": 0.5}).to_dict()
