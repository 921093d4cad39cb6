"""The stage site's second clock (ISSUE 36 / docs/OBSERVABILITY.md
"Stages"): inside a sampled trace or a capture every entry reads the
calling thread's CPU clock inside its wall-clock interval, so over those
entries a stage's wall seconds less its CPU seconds are the time its
thread was not running; everywhere else the CPU clock is left alone (it
costs 5.6 us a read on the machines that hold the chips) but for one read
every 0.1 s a thread on exit of the three root stages, from which the CPU
of the handler, dispatcher and WAL-commit threads is kept by role; ``wal.commit`` is the
stage of the commit thread.

On the CPU. The two timing cases compare a thread's own two clocks over
50 ms and nothing with a rate or a limit of the machine's.
"""

import contextlib
import http.client
import sys
import threading
import time

import pytest

from cluster_helpers import req, uri
from pilosa_tpu.utils import tracing
from pilosa_tpu.utils.tracing import (
    STAGES,
    global_tracer,
    stage,
    stage_metrics,
    thread_metrics,
)

HANDLER = "thread_handler_cpu_seconds_total"
DISPATCHER = "thread_dispatcher_cpu_seconds_total"
WAL_COMMIT = "thread_wal_commit_cpu_seconds_total"
PROCESS = "process_cpu_seconds_total"


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


def _read(name: str) -> tuple[int, float, float]:
    """(entries, wall seconds, CPU seconds) of one stage so far, over the
    entries whose CPU was read."""
    m, key = stage_metrics(), name.replace(".", "_")
    return (m.get(f"{key}_cpu_entries_total", 0),
            m.get(f"{key}_cpu_wall_seconds_total", 0.0),
            m.get(f"{key}_cpu_seconds_total", 0.0))


def _delta(name: str, before: tuple) -> tuple[int, float, float]:
    return tuple(a - b for a, b in zip(_read(name), before))


def _wall(name: str) -> tuple[int, float]:
    """(entries, wall seconds) of one stage so far, every entry."""
    m, key = stage_metrics(), name.replace(".", "_")
    return m.get(f"{key}_total", 0), m.get(f"{key}_seconds_total", 0.0)


def _entries(name: str) -> int:
    return _wall(name)[0]


@contextlib.contextmanager
def sampled():
    """Inside a sampled trace, where a site reads both clocks."""
    global_tracer().sample_rate = 1.0
    with global_tracer().request_root("test.root"):
        yield


def _spin(seconds: float) -> None:
    end = time.perf_counter() + seconds
    while time.perf_counter() < end:
        pass


def _burn(seconds: float) -> None:
    """``seconds`` of the calling thread's own CPU, however long a loaded
    machine takes to give them."""
    end = time.thread_time() + seconds
    while time.thread_time() < end:
        pass


# ------------------------------------------------------------- two clocks


def test_a_stage_that_sleeps_reads_wall_seconds_and_no_cpu():
    before = _read("test.cpu.sleeps")
    with sampled():
        with stage("test.cpu.sleeps") as span:
            time.sleep(0.05)
    assert span is not None
    n, wall, cpu = _delta("test.cpu.sleeps", before)
    assert n == 1 and wall >= 0.05 and cpu < 0.01


def test_a_stage_that_spins_reads_cpu_seconds_close_to_its_wall_seconds():
    """A thread that computes for 50 ms spends 50 ms of CPU unless the
    machine takes the core away meanwhile, which six test workers beside
    this one do now and then: the best of five entries is within 20 %."""
    shares = []
    with sampled():
        for _ in range(5):
            handle = stage("test.cpu.spins")
            with handle:
                _spin(0.05)
            assert handle.elapsed >= 0.05 and handle.cpu <= handle.elapsed
            shares.append(handle.cpu / handle.elapsed)
            if shares[-1] >= 0.8:
                break
    assert max(shares) >= 0.8, shares
    n, wall, cpu = _read("test.cpu.spins")
    assert n == len(shares) and 0 < cpu <= wall


def test_the_cpu_clock_is_read_only_where_somebody_will_look(monkeypatch):
    """Outside a sampled trace and a capture a site does not touch the
    CPU clock (a system call, 5.6 us on the machines that hold the
    chips); inside one it reads it twice, within the wall pair, and
    counts the entry among the measured ones. (A role's root stage:
    the next test.)"""
    reads = []
    real = tracing._cpu_clock_ns

    def counting():
        reads.append(1)
        return real()

    monkeypatch.setattr(tracing, "_cpu_clock_ns", counting)
    before, n0 = _read("pql.parse"), _entries("pql.parse")
    handle = stage("pql.parse")
    with handle:
        pass
    assert reads == [] and handle.cpu is None and handle.elapsed > 0
    assert _entries("pql.parse") == n0 + 1
    assert _delta("pql.parse", before) == (0, 0.0, 0.0)
    for root in tracing.ROLE_ROOT_STAGES:  # on a thread in no role
        with stage(root):
            pass
    assert reads == []
    with sampled():
        handle = stage("pql.parse")
        with handle:
            pass
    assert reads == [1, 1] and 0 <= handle.cpu <= handle.elapsed
    n, wall, cpu = _delta("pql.parse", before)
    assert n == 1 and 0 <= cpu <= wall
    assert _entries("pql.parse") == n0 + 2


def test_a_root_stage_refreshes_its_threads_role_every_tenth_of_a_second(
        monkeypatch):
    """On a thread in a role the root stage's exit reads the CPU clock
    when the thread's last reading is ROLE_REFRESH_NS old (the first
    exit, then at most one in 0.1 s however many requests pass), and at
    every exit inside a sampled trace, from the reading the site took
    anyway; retiring reads once more, so nothing the thread did is
    lost."""
    reads = []
    real = tracing._cpu_clock_ns

    def counting():
        reads.append(1)
        return real()

    got = {}

    def serve():
        tracing.enter_thread_role("dispatcher")
        cell = tracing._role_local.cell
        del reads[:]
        for _ in range(50):
            with stage("pipeline.submit"):
                pass
        got["gated"] = len(reads)
        with stage("pql.parse"):  # not a root: never refreshes
            pass
        got["other"] = len(reads)
        _burn(0.02)
        cell.read_ns -= tracing.ROLE_REFRESH_NS  # as if 0.1 s had passed
        before = thread_metrics()[DISPATCHER]
        with stage("pipeline.submit"):
            pass
        got["stale"] = len(reads)
        got["rose"] = thread_metrics()[DISPATCHER] - before
        with sampled():
            for _ in range(3):
                with stage("pipeline.submit"):
                    pass
        got["sampled"] = len(reads)
        _burn(0.02)
        before = thread_metrics()[DISPATCHER]
        tracing.retire_thread_role()
        got["retired"] = thread_metrics()[DISPATCHER] - before

    monkeypatch.setattr(tracing, "_cpu_clock_ns", counting)
    # a minute, so that a loaded machine cannot stretch the 50 exits
    # below past a refresh of its own; the gate is the same comparison
    monkeypatch.setattr(tracing, "ROLE_REFRESH_NS", 60_000_000_000)
    t = threading.Thread(target=serve)
    t.start()
    t.join(60)
    assert not t.is_alive()
    assert got["gated"] == got["other"] == 1
    assert got["stale"] == 2 and got["rose"] >= 0.015
    assert got["sampled"] == 2 + 3 * 2  # the site's pair, no third read
    assert got["retired"] >= 0.015


def test_cpu_is_inside_wall_for_every_entry_and_counts_are_exact():
    """8 threads x 1,000 entries, the interpreter switching every 10 us:
    the CPU interval lies inside the wall interval by the order of the
    four clock reads, so ``cpu <= wall`` holds entry by entry (a thread
    that lost the interpreter inside the stage has wall without CPU,
    never the reverse), and the three totals lose no update."""
    name = "test.cpu.exact"
    per_thread: list = []

    def work():
        wall = cpu = 0.0
        worst = 0.0
        with sampled():  # a trace of its own a thread
            for i in range(1_000):
                handle = stage(name)
                with handle:
                    if i % 50 == 0:
                        time.sleep(0.0002)  # some entries wait, most compute
                worst = max(worst, handle.cpu - handle.elapsed)
                wall += handle.elapsed
                cpu += handle.cpu
        per_thread.append((wall, cpu, worst))

    before = _read(name)
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
    n, wall, cpu = _delta(name, before)
    assert n == 8_000 == _entries(name) and len(per_thread) == 8
    assert all(worst <= 0 for _, _, worst in per_thread), per_thread
    assert 0 < cpu <= wall
    # the totals are the sums of what the handles read (integer
    # nanoseconds inside, floats here)
    assert wall == pytest.approx(sum(w for w, _, _ in per_thread), rel=1e-6)
    assert cpu == pytest.approx(sum(c for _, c, _ in per_thread), rel=1e-6)


def test_a_stage_that_raises_still_counts_both_clocks():
    before = _read("test.cpu.raises")
    with sampled(), pytest.raises(KeyError):
        with stage("test.cpu.raises"):
            _spin(0.002)
            raise KeyError("x")
    n, wall, cpu = _delta("test.cpu.raises", before)
    assert n == 1 and wall >= 0.002 and 0 < cpu <= wall


def test_a_site_still_allocates_nothing_outside_a_trace_and_a_capture(
        monkeypatch):
    """Neither a ``Span`` nor an annotation with sampling off and no
    capture; and the tag ``cpu_ms`` exists where the span does, inside a
    sampled trace."""
    made = []
    real = tracing.Span

    class Counted(real):
        def __init__(self, *a, **kw):
            made.append(a[0])
            super().__init__(*a, **kw)

    monkeypatch.setattr(tracing, "Span", Counted)
    with stage("test.cpu.bare") as span:
        pass
    assert span is None and made == [] and tracing._annotation is None
    with sampled():
        with stage("test.cpu.bare") as span:
            _spin(0.002)
    assert made == ["test.root", "test.cpu.bare"]
    assert 0 < span.tags["cpu_ms"] <= span.duration * 1e3 + 0.001


# --------------------------------------------------- the series, served


def _samples(base: str) -> tuple[dict, str]:
    text = req("GET", f"{base}/metrics", raw=True).decode()
    return ({line.split(" ", 1)[0]: float(line.split(" ", 1)[1])
             for line in text.splitlines()
             if line and not line.startswith("#") and "{" not in line}, text)


CPU_SUFFIXES = ("_cpu_entries_total", "_cpu_wall_seconds_total",
                "_cpu_seconds_total")


def test_cpu_series_are_typed_counters_on_metrics_and_in_debug_vars(server):
    base = uri(server)
    global_tracer().sample_rate = 1.0  # so that the sites read both clocks
    req("POST", f"{base}/index/i/query", b"Count(Row(f=1))")
    global_tracer().sample_rate = 0.0
    req("POST", f"{base}/index/i/query", b"Count(Row(f=2))")
    samples, text = _samples(base)
    wanted = [f"pilosa_tpu_stage_{n.replace('.', '_')}{suffix}"
              for n in STAGES for suffix in CPU_SUFFIXES]
    wanted += [f"pilosa_tpu_{s}" for s in (HANDLER, DISPATCHER, WAL_COMMIT,
                                           PROCESS)]
    assert len(wanted) == 26 * 3 + 4
    for series in wanted:
        assert series in samples, series
        assert text.count(f"# TYPE {series} counter\n") == 1, series
        assert text.count(f"# HELP {series} ") == 1, series
    # unlabelled: the harness's scrape skips every line with a brace
    assert not [l for l in text.splitlines()
                if "_cpu_" in l.split("{")[0] and "{" in l]
    # the measured entries are some of the entries; their CPU is inside
    # their wall seconds, also as served
    for n in STAGES:
        key = f"pilosa_tpu_stage_{n.replace('.', '_')}"
        assert samples[f"{key}_cpu_entries_total"] <= samples[
            f"{key}_total"], n
        assert (samples[f"{key}_cpu_seconds_total"]
                <= samples[f"{key}_cpu_wall_seconds_total"]
                <= samples[f"{key}_seconds_total"]), n
    debug_vars = req("GET", f"{base}/debug/vars")
    assert set(debug_vars["threads"]) == {HANDLER, DISPATCHER, WAL_COMMIT,
                                          PROCESS}
    stages = debug_vars["stages"]
    assert stages["http_query_cpu_entries_total"] >= 1
    assert stages["http_query_total"] > stages["http_query_cpu_entries_total"]
    assert stages["http_query_cpu_seconds_total"] > 0
    assert debug_vars["threads"][PROCESS] > 0


def _wait_handlers_retired(server, but: int = 0) -> None:
    """Until the server has no open connection left but ``but``."""
    http_server = server._http
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        with http_server.metrics_lock:
            if len(http_server.open_connections) <= but:
                break
        time.sleep(0.01)
    time.sleep(0.05)  # finish() retires the role before it drops the socket


def _live_handler_cells() -> set:
    with tracing._role_lock:
        return {c for c in tracing._role_live if c.role == "handler"}


def test_handler_cpu_is_monotone_across_closed_connections(server):
    """Open, query, close, scrape; open another, scrape: a closed
    connection takes nothing away (its thread's CPU is folded into the
    retired total), and the role holds at least the CPU of its root
    stage, ``http.query``, plus what no stage sees (request line and
    header parse, ``send``)."""
    _wait_handlers_retired(server)
    live0 = _live_handler_cells()
    h0, q0 = thread_metrics()[HANDLER], _read("http.query")
    conn = http.client.HTTPConnection("localhost", server.port, timeout=60)
    global_tracer().sample_rate = 1.0  # C(http.query) is read in a trace
    for _ in range(20):
        conn.request("POST", "/index/i/query", body=b"Count(Row(f=1))")
        assert conn.getresponse().read().strip() == b'{"results":[2]}'
    global_tracer().sample_rate = 0.0
    live = thread_metrics()[HANDLER]
    n, _, root_cpu = _delta("http.query", q0)
    assert n == 20
    # as of the thread's last root stage: all twenty are in it
    assert live - h0 >= root_cpu > 0
    conn.close()
    _wait_handlers_retired(server)
    closed = thread_metrics()[HANDLER]
    assert closed >= live
    readings = [closed]
    for _ in range(3):  # each scrape is a connection that opens and closes
        samples, _ = _samples(uri(server))
        readings.append(samples[f"pilosa_tpu_{HANDLER}"])
    # (the page prints twelve digits: a nanosecond of slack)
    assert all(b >= a - 1e-9 for a, b in zip(readings, readings[1:]))
    # a handler that served no query (a scrape) is counted when it retires
    _wait_handlers_retired(server)
    assert thread_metrics()[HANDLER] > closed
    assert _live_handler_cells() <= live0  # and its cell is let go


def test_dispatcher_cpu_rises_by_a_served_query(server):
    base = uri(server)
    req("POST", f"{base}/index/i/query", b"Count(Row(f=1))")  # thread is up
    d0, s0 = thread_metrics()[DISPATCHER], _read("pipeline.submit")
    global_tracer().sample_rate = 1.0  # the submit joins the request's trace
    assert req("POST", f"{base}/index/i/query",
               b"Count(Intersect(Row(f=1), Row(f=2)))") == {"results": [1]}
    global_tracer().sample_rate = 0.0
    n, _, submit_cpu = _delta("pipeline.submit", s0)
    assert n == 1
    assert thread_metrics()[DISPATCHER] - d0 >= submit_cpu > 0
    assert thread_metrics()[PROCESS] >= sum(
        thread_metrics()[k] for k in (HANDLER, DISPATCHER, WAL_COMMIT)) > 0


def test_wal_commit_counts_the_groups_and_is_closed_while_the_thread_waits(
        server, monkeypatch):
    """``durability-mode = group`` (the default): a burst of acknowledged
    ``Set``s from four connections commits in groups; N(``wal.commit``)
    is ``wal_groups_total``'s delta, its seconds are one thread's busy
    time, the thread's CPU is in its role's series, and the stage stands
    still while the commit thread waits on ``_cond`` for the next
    record. (The commit thread belongs to no request, so its stage's own
    CPU series move inside a capture only.)"""
    base = uri(server)
    assert server.api.holder.wal.mode == "group"
    # the burst may be over within one refresh interval of the role
    monkeypatch.setattr(tracing, "ROLE_REFRESH_NS", 0)
    req("POST", f"{base}/index/i/query", b"Set(100, f=3)")  # thread is up
    before, c0 = _wall("wal.commit"), thread_metrics()[WAL_COMMIT]
    groups0 = _samples(base)[0]["pilosa_tpu_wal_groups_total"]
    t0 = time.perf_counter()

    def writer(k: int) -> None:
        for i in range(25):
            assert req("POST", f"{base}/index/i/query",
                       f"Set({1000 * k + i}, f=3)".encode()) == {
                           "results": [True]}

    threads = [threading.Thread(target=writer, args=(k,)) for k in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120)
    assert not any(t.is_alive() for t in threads)
    elapsed = time.perf_counter() - t0
    n, wall = (a - b for a, b in zip(_wall("wal.commit"), before))
    groups = _samples(base)[0]["pilosa_tpu_wal_groups_total"] - groups0
    assert 1 <= n == groups <= 100
    assert 0 < wall < elapsed  # one thread, and it also waits
    assert 0 < thread_metrics()[WAL_COMMIT] - c0 <= wall
    idle = _wall("wal.commit")
    time.sleep(0.3)  # the commit thread is in _cond.wait() all the while
    assert _wall("wal.commit") == idle
