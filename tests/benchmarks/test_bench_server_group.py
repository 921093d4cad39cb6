"""A server child that has children (ISSUE 47). ``ServerProc`` starts the
child as the leader of a process group of its own; SIGTERM goes to the
child alone, a kill to the whole group, and a group that outlives a clean
stop is an error that names the pids. First against a stand-in program
that spawns a process and does nothing else (cheap, and it can misbehave
on purpose); then the deployment the rule on ``server_knobs`` was written
for, ``serving-workers = 2`` (``docs/OPERATIONS.md``, "Deployment shapes"),
rehearsed on the CPU from a scratch configuration that is never committed
under ``benchmarks/configs/``: ``correct`` with the ``lost-write`` control
holding, the queries counted by the ring and not by the proxy, exit 0 on
SIGTERM and nothing of the group alive afterwards; and the same run
killed half way, which leaves no process and no shared memory.
"""

import importlib.util
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import time

import pytest

from bench_helpers import BENCH, ROOT, last_line
from harness import serving
from test_bench_manifest import knobs_keep_to_the_rule, toy

CELL = "taxi-rides.point-rw"
needs_reuseport = pytest.mark.skipif(
    not hasattr(socket, "SO_REUSEPORT"),
    reason="multi-process serving needs SO_REUSEPORT")

# ``python -m pilosa_tpu server`` as far as ServerProc can tell: it leaves
# a process of its own behind, says where, and waits for SIGTERM
STAND_IN = '''
import os, signal, subprocess, sys, time


def main(argv):
    data_dir = argv[argv.index("-d") + 1]
    mode = open(os.path.join(data_dir, "mode")).read()
    worker = subprocess.Popen([sys.executable, "-c",
                               "import time; time.sleep(600)"])
    stop = []
    signal.signal(signal.SIGTERM, lambda *a: stop.append(1))
    with open(os.path.join(data_dir, "pids.tmp"), "w") as f:
        f.write(f"{os.getpid()} {worker.pid}")
    os.rename(os.path.join(data_dir, "pids.tmp"),
              os.path.join(data_dir, "pids"))
    while not stop:
        time.sleep(0.02)
    if mode == "tidy":
        worker.kill()
        worker.wait()
    sys.exit(0)  # not a return: server_child.py would go on to import JAX
'''


def alive(pid: int) -> bool:
    try:
        with open(f"/proc/{pid}/stat", "rb") as f:
            return f.read().rpartition(b")")[2].split()[0] != b"Z"
    except OSError:
        return False


def workers_among(pids) -> list:
    """The pids whose command line is the program's ``serve-worker``."""
    found = []
    for pid in pids:
        try:
            with open(f"/proc/{pid}/cmdline", "rb") as f:
                if b"serve-worker" in f.read().split(b"\0"):
                    found.append(pid)
        except OSError:
            pass
    return found


def gone_within(pids, seconds: float) -> bool:
    deadline = time.monotonic() + seconds
    while any(alive(p) for p in pids):
        if time.monotonic() > deadline:
            return False
        time.sleep(0.02)
    return True


@pytest.fixture
def stand_in(tmp_path):
    """``start(mode)`` gives a ServerProc over the stand-in program and the
    pids of the child and of the process the child spawned."""
    root = tmp_path / "root"
    (root / "pilosa_tpu").mkdir(parents=True)
    (root / "pilosa_tpu" / "__init__.py").write_text("")
    (root / "pilosa_tpu" / "cli.py").write_text(STAND_IN)
    started = []

    def start(mode: str):
        data = tmp_path / f"data{len(started)}"
        data.mkdir()
        (data / "mode").write_text(mode)
        proc = serving.ServerProc(str(root), str(data),
                                  str(data / "server.log"), {}, {})
        started.append(proc)
        deadline = time.monotonic() + 30
        while not (data / "pids").exists():
            assert time.monotonic() < deadline, proc.log_tail()
            time.sleep(0.02)
        child, worker = map(int, (data / "pids").read_text().split())
        assert child == proc.proc.pid == proc.pgid
        return proc, child, worker

    yield start
    for proc in started:
        proc.kill()


def test_the_child_leads_a_group_of_its_own_and_its_children_are_in_it(
        stand_in):
    proc, child, worker = stand_in("tidy")
    assert os.getpgid(child) == child != os.getpgid(0)
    assert os.getsid(child) == child
    assert os.getpgid(worker) == child
    assert sorted(proc.group_pids()) == sorted([child, worker])


def test_a_clean_stop_that_empties_the_group_returns_the_exit_code(stand_in):
    proc, child, worker = stand_in("tidy")
    proc.terminate()
    assert proc.wait_stopped() == 0
    assert proc.group_pids() == [] and not alive(worker)
    proc.kill()  # nothing left to signal, and no other group is signalled


def test_sigterm_goes_to_the_child_alone(stand_in):
    """The clean close is the program's to make: the child's own process
    is not signalled by the harness, so it is there until the child (or a
    kill) ends it."""
    proc, child, worker = stand_in("leaky")
    proc.terminate()
    assert gone_within([child], 30)
    assert alive(worker)


def test_a_group_that_outlives_a_clean_stop_is_killed_and_named(
        stand_in, monkeypatch):
    monkeypatch.setattr(serving, "GROUP_EMPTY_S", 0.5)
    proc, child, worker = stand_in("leaky")
    proc.terminate()
    with pytest.raises(serving.HarnessError) as e:
        proc.wait_stopped()
    assert f"[{worker}]" in str(e.value) and "exit code 0" in str(e.value)
    assert gone_within([worker], 5)


def test_kill_signals_the_whole_group(stand_in):
    proc, child, worker = stand_in("leaky")
    proc.kill()
    assert proc.proc.returncode == -signal.SIGKILL
    assert gone_within([child, worker], 5)
    assert proc.group_pids() == []


def test_a_stop_that_times_out_kills_the_whole_group(stand_in, monkeypatch):
    monkeypatch.setattr(serving, "STOP_TIMEOUT_S", 0.3)
    proc, child, worker = stand_in("leaky")
    assert proc.wait_stopped() == -signal.SIGKILL  # never asked to stop
    assert gone_within([child, worker], 5)


def test_a_harness_that_is_killed_takes_its_server_child_with_it(tmp_path,
                                                                 stand_in):
    """The child is outside the harness's group now, so a signal to that
    group misses it: ``server_child.py`` asks the kernel for SIGTERM, the
    clean close, when its parent is gone."""
    root, data = tmp_path / "root", tmp_path / "orphan"
    data.mkdir()
    (data / "mode").write_text("tidy")
    harness = subprocess.Popen([sys.executable, "-c", f"""
import sys, time
sys.path.insert(0, {BENCH!r})
from harness import serving
serving.ServerProc({str(root)!r}, {str(data)!r}, {str(data / 'log')!r}, {{}}, {{}})
time.sleep(600)
"""])
    try:
        deadline = time.monotonic() + 30
        while not (data / "pids").exists():
            assert time.monotonic() < deadline and harness.poll() is None
            time.sleep(0.02)
        child, worker = map(int, (data / "pids").read_text().split())
        harness.kill()
        harness.wait()
        assert gone_within([child, worker], 30)
    finally:
        harness.kill()
        for pid in map(int, (data / "pids").read_text().split()):
            if alive(pid):
                os.kill(pid, signal.SIGKILL)


# ----------------------------------------------- the serving tier rehearsed

@pytest.fixture(scope="module")
def scratch(tmp_path_factory):
    """A checkout of its own (the benchmark as it stands, the program by
    symlink) whose ``taxi-rides.json`` states the tier; ``run.py`` of that
    checkout as a module, and the ServerProcs it makes."""
    root = tmp_path_factory.mktemp("mp-checkout")
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(BENCH, root / "benchmarks",
                    ignore=shutil.ignore_patterns(".work", "__pycache__"))
    os.symlink(os.path.join(ROOT, "pilosa_tpu"), root / "pilosa_tpu")
    body = toy()  # taxi-rides with serving-workers = 2, its why, its name
    knobs_keep_to_the_rule(body)  # what a committed one would be held to
    with open(root / "benchmarks" / "configs" / "taxi-rides.json", "w") as f:
        json.dump(body, f)
    spec = importlib.util.spec_from_file_location(
        "bench_run_mp", str(root / "benchmarks" / "run.py"))
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    assert run.ROOT == str(root)
    return run


def recording(run, monkeypatch):
    made, scrapes = [], []

    class Recording(run.ServerProc):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            made.append(self)

    real = run.Conn.metrics

    def keeping(self):
        scrapes.append(real(self))
        return scrapes[-1]

    monkeypatch.setattr(run, "ServerProc", Recording)
    monkeypatch.setattr(run.Conn, "metrics", keeping)
    return made, scrapes


@needs_reuseport
def test_point_rw_with_two_serving_workers_is_correct_and_leaves_nothing(
        scratch, capfd, monkeypatch):
    made, scrapes = recording(scratch, monkeypatch)
    in_window, workers = [], []
    shm_before = set(os.listdir("/dev/shm"))
    real = scratch.loadgen.run

    def watching(port, index, clients, seconds=None, **kw):
        if seconds == 3.0:
            in_window.extend(made[0].group_pids())
            workers.extend(workers_among(in_window))
        return real(port, index, clients, seconds=seconds, **kw)

    monkeypatch.setattr(scratch.loadgen, "run", watching)
    rc = scratch.main(["--workload", CELL, "--seed", "4700000029",
                       "--seconds", "3.0", "--trace", "0", "--rehearse",
                       "--control", "lost-write"])
    out = capfd.readouterr()
    assert rc == 0, out.err[-3000:] + out.out[-2000:]
    line = last_line(out.out)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    checks = [l for l in out.out.splitlines() if l.startswith("check ")]
    assert len(checks) == 3 and all(" wrong=0 limit=0" in l for l in checks)
    # an acknowledged write lost comes out not correct through the tier too
    assert "control[lost-write]: correct=False" in out.out
    # the run says what it handed the child
    assert ', server_knobs {"serving-workers": 2}\n' in out.err
    # the owner and its two workers (and the trackers of their shared
    # memory), all in the child's group
    (proc,) = made
    assert proc.pgid in in_window and len(workers) == 2
    # the window's queries rode the ring and were not proxied
    before, after = scrapes[-2], scrapes[-1]
    ring = (after["pilosa_tpu_serving_ring_requests_total"]
            - before["pilosa_tpu_serving_ring_requests_total"])
    proxied = (after["pilosa_tpu_serving_worker_proxied_total"]
               - before["pilosa_tpu_serving_worker_proxied_total"])
    # (a worker posts its count to the control block now and then, so the
    # scrape is a little behind the window)
    assert ring > 0.9 * line["attempted"] > 10 * proxied
    assert after["pilosa_tpu_serving_workers"] == 2
    # SIGTERM to the owner alone: exit 0, and the group emptied by itself
    assert "SIGTERM to exit 0" in out.err
    assert proc.proc.returncode == 0
    assert not any(alive(p) for p in in_window)
    assert set(os.listdir("/dev/shm")) <= shm_before  # the rings unlinked


@needs_reuseport
def test_the_same_run_killed_half_way_leaves_nothing(scratch, capfd,
                                                     monkeypatch):
    made, _ = recording(scratch, monkeypatch)
    in_window, workers, rings = [], [], []
    shm_before = set(os.listdir("/dev/shm"))

    def dying(port, index, clients, seconds=None, **kw):
        if seconds == 2.5:
            in_window.extend(made[0].group_pids())
            workers.extend(workers_among(in_window))
            rings.extend(set(os.listdir("/dev/shm")) - shm_before)
            raise scratch.HarnessError("killed half way (the test's doing)")
        return real(port, index, clients, seconds=seconds, **kw)

    real = scratch.loadgen.run
    monkeypatch.setattr(scratch.loadgen, "run", dying)
    rc = scratch.main(["--workload", CELL, "--seed", "4700000031",
                       "--seconds", "2.5", "--trace", "0", "--rehearse"])
    out = capfd.readouterr()
    assert rc == 1 and "killed half way" in out.err
    assert out.out.strip() == "" or '"correct"' not in out.out.splitlines()[-1]
    (proc,) = made
    assert proc.pgid in in_window and len(workers) == 2
    assert proc.proc.returncode == -signal.SIGKILL
    assert gone_within(in_window, 5)
    # a killed owner cannot unlink its rings: the harness removed what the
    # group had mapped (a ring each way a worker, and the control block)
    assert len(rings) >= 5
    assert set(os.listdir("/dev/shm")) <= shm_before
