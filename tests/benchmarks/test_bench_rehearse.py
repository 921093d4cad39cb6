"""The whole harness on the CPU (``--rehearse``: 2 shards, one server
child, the cell's own mix and concurrency): the files the data writer
makes are the server's own, every template's answers equal the numpy
reference's, the last line is the contract's, the control and a broken
timed path come out not correct, and no TPU without ``--rehearse`` is an
error before anything is loaded."""

import os
import re
import shutil
import subprocess
import sys
import time

import pytest

from bench_helpers import (BENCH, CELLS, CONTROL, KINDS, MANIFEST, ROOT, RUN,
                           SHARED_SEED, import_run, last_line, load_config,
                           load_mix, rehearsals, rehearse, shared_dir,
                           shared_key)
from harness import datagen, traffic

# the traced line is held to the contract too in the cells with metrics
# no other cell reads
TRACED = {"taxi-rides.point-rw"}


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    """Every cell's untraced rehearsal with its control, and the traced
    one of TRACED: the runs every file of this directory shares. The taxi
    cells first: the SSB cells, 100-150 s each, are made meanwhile by the
    workers that run those cells' own files."""
    return rehearsals(
        tmp_path_factory, [(name, 0) for name in sorted(CELLS, reverse=True)]
        + [(name, 1) for name in sorted(TRACED & set(CELLS))])


@pytest.mark.parametrize("name", sorted(CELLS))
def test_rehearsal_is_correct_on_every_template(runs, name):
    p = runs[name, 0]
    assert p.returncode == 0, p.stderr[-3000:] + p.stdout[-2000:]
    line = last_line(p.stdout)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    mix = load_mix(CELLS[name]["traffic"])
    checked = {l.split()[1].rstrip(":") for l in p.stdout.splitlines()
               if l.startswith("check answers.")}
    reads = {f"answers.{t}" for t, tp in mix["templates"].items()
             if tp["kind"] not in traffic.WRITE_KINDS}
    assert checked == reads
    for l in p.stdout.splitlines():
        if l.startswith("check "):
            assert " wrong=0 limit=0" in l, l


@pytest.mark.parametrize("name", sorted(CELLS))
def test_last_line_has_exactly_the_contracts_keys(runs, name):
    line_keeps_to_the_contract(name, last_line(runs[name, 0].stdout), False)
    if name in TRACED:
        line_keeps_to_the_contract(name, last_line(runs[name, 1].stdout),
                                   True)


def line_keeps_to_the_contract(name: str, line: dict, traced: bool) -> None:
    want = {"correct", "attempted", "failed", "metrics", "device"}
    assert set(line) == want | ({"breakdown"} if traced else set())
    device = {"platform", "kind", "count", "memory_peak_bytes"}
    assert set(line["device"]) == device | (
        {"busy_s", "window_s"} if traced else set())
    # a rehearsal is stamped cpu: never mistaken for a device number
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] == CELLS[name]["chips"]
    key = "per_layer" if traced else "end_to_end"
    listed = {m["name"]: m for m in MANIFEST[key]
              if name in m.get("workloads", [name])}
    assert set(line["metrics"]) <= set(listed)
    for metric, body in line["metrics"].items():
        assert set(body) == {"value", "unit"}
        assert body["unit"] == listed[metric]["unit"]
        assert isinstance(body["value"], (int, float))
    if traced:
        assert {"generator_busy_share", "compiles_in_window", "wave_depth",
                "device_idle_share"} <= set(line["metrics"])
        assert len(line["breakdown"]["device_ops"]) <= 10
        assert len(line["breakdown"]["idle_gaps"]) <= 10
        assert 0 < line["device"]["busy_s"]
    else:
        assert set(line["metrics"]) == set(listed)
        assert all(v["value"] > 0 for v in line["metrics"].values())


@pytest.mark.parametrize("name", sorted(CELLS))
def test_control_comes_out_not_correct(runs, name):
    """The reference with one stated guarantee broken (answers sampled
    and scaled instead of exact; an acknowledged write lost) has to fail
    at least one of the cell's numbers. run.py exits 3 if it passes."""
    p = runs[name, 0]
    kind = CONTROL[name]
    assert f"control[{kind}]: correct=False" in p.stdout
    wrong = [l for l in p.stdout.splitlines()
             if l.startswith(f"control[{kind}] ") and " wrong=0 " not in l]
    assert wrong


# the files that start a rehearsal of their own, and why the shared runs
# would not do
OWN_REHEARSALS = {
    "bench_helpers.py": "the one place that spells the command",
    "test_bench_rehearse.py": "the timed path broken in this process; a "
                              "cell that is not there",
    "test_bench_rehearse_knobs.py": "the work directories, watched from "
                                    "inside the run",
    "test_bench_mesh_cell.py": "its own seed (ISSUE 27's)",
    "test_bench_server_group.py": "a scratch configuration",
}


def test_a_cell_is_rehearsed_in_two_kinds_and_no_third(runs, tmp_path_factory):
    """Tier-1 rehearses a shipped cell twice: untraced with its control,
    and traced, both with the shared seed. The helper names no other
    kind, what the session has kept is of those kinds, and a file that
    starts a rehearsal of its own is on the list above."""
    keys = {shared_key(cell, trace) for cell in CELLS for trace in KINDS}
    assert len(keys) == 2 * len(CELLS)
    assert shared_key("taxi-rides.point-rw", 0) == (
        f"taxi-rides.point-rw.t0.lost-write.{SHARED_SEED}")
    assert shared_key("taxi-rides.dashboard", 1) == (
        f"taxi-rides.dashboard.t1.none.{SHARED_SEED}")
    for cell, trace in (("taxi-rides.dashboard", 2), ("no-such.cell", 0)):
        with pytest.raises(ValueError, match="no shared rehearsal"):
            shared_key(cell, trace)
    kept = {name for name in os.listdir(shared_dir(tmp_path_factory))
            if not name.endswith(".lock")}
    assert {shared_key(cell, trace) for cell, trace in runs} <= kept <= keys
    here = os.path.dirname(os.path.abspath(__file__))
    own = set()
    for name in sorted(os.listdir(here)):
        if name.endswith(".py"):
            with open(os.path.join(here, name)) as f:
                text = f.read()
            # the flag as an argument, or a call of the plain helper
            if '"--rehearse"' in text or re.search(r"(?<![\w.`])rehearse\(",
                                                   text):
                own.add(name)
    assert own == set(OWN_REHEARSALS)


def test_every_acknowledged_write_is_read_back(runs):
    if "taxi-rides.point-rw" not in CELLS:
        pytest.skip("no cell with writes")
    out = runs["taxi-rides.point-rw", 1].stdout
    acked = [l for l in out.splitlines()
             if l.startswith("check writes.acknowledged_bits_read_back")][0]
    window = [l for l in out.splitlines() if l.startswith("window:")][0]
    n_window = int(window.split("acknowledged_writes=")[1].split()[0])
    n_compared = int(acked.split("compared=")[1].split()[0])
    # the window's writes, plus those of the sample and the warm-up
    assert n_compared >= n_window > 0
    line = last_line(out)
    assert line["metrics"]["fsyncs_per_write"]["value"] > 0
    assert line["metrics"]["write_ack_p50_ms"]["value"] > 0


def test_a_wrong_answer_in_the_window_makes_correct_false(capsys, monkeypatch):
    """The rest of a run driven in this process with the timed path broken
    underneath: one answer of the measured window is altered where the
    harness receives it."""
    run = import_run()
    real = run.loadgen.run
    seconds = 2.5
    state = {"altered": 0}

    def broken(port, index, clients, seconds=None, **kw):
        win = real(port, index, clients, seconds=seconds, **kw)
        if seconds == 2.5:
            victim = next(r for r in win.records if r.ok and r.body)
            victim.body = victim.body.replace(b'"count":', b'"count":1', 1) \
                if b'"count":' in victim.body else b'{"results":[-1]}'
            state["altered"] += 1
        return win

    monkeypatch.setattr(run.loadgen, "run", broken)
    rc = run.main(["--workload", "taxi-rides.dashboard", "--seed",
                   "2700000001", "--seconds", str(seconds), "--trace", "0",
                   "--rehearse"])
    out = capsys.readouterr().out
    assert rc == 0 and state["altered"] == 1
    assert last_line(out)["correct"] is False
    bad = [l for l in out.splitlines()
           if l.startswith("check answers.") and " wrong=1 " in l]
    assert len(bad) == 1


def test_no_tpu_and_no_rehearse_exits_before_loading(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    t0 = time.monotonic()
    p = subprocess.run(
        [sys.executable, RUN, "--workload", sorted(CELLS)[0], "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == "", "no result line"
    assert "TPU" in p.stderr
    assert time.monotonic() - t0 < 30


def test_refuses_a_directory_with_only_the_benchmark(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns(".work", "__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    p = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", sorted(CELLS)[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_the_port_is_the_one_the_server_says_it_bound(runs):
    """The child binds port 0; the harness reads the port from the
    server's own start-up line, whatever else the log holds."""
    from harness.serving import LISTENING
    log = (b"WARNING something about hugepages\n"
           b"2026-09-27 INFO pilosa_tpu listening on http://127.0.0.1:43817 "
           b"(data-dir /x/data, node node-43817, devices 1 x tpu TPU v5 lite)\n")
    assert int(LISTENING.search(log).group(1)) == 43817
    assert LISTENING.search(b"listening soon\n") is None
    p = runs[sorted(CELLS)[0], 0]
    # and the run says what it handed the child: the default knobs
    assert "server up on" in p.stderr and ", server_knobs {}\n" in p.stderr


def test_unknown_workload_is_refused():
    p = rehearse("no-such.cell")
    assert p.returncode != 0 and p.stdout.strip() == ""


@pytest.mark.parametrize("config_name,mix_name", sorted(
    {(c["config"], c["traffic"]) for c in CELLS.values() if c["chips"] == 1}))
def test_written_files_are_what_the_server_would_have_written(
        tmp_path, config_name, mix_name):
    """``cli check -d`` decodes every fragment file the writer made and
    ``cli inspect`` counts in them exactly the bits of the columns."""
    config, mix = load_config(config_name), load_mix(mix_name)
    fields = traffic.fields_read(mix, config)
    cols = datagen.make_columns(config, 2_800_000_001, 2, fields)
    datagen.write_data_dir(str(tmp_path), config, cols, 2, fields)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    check = subprocess.run(
        [sys.executable, "-m", "pilosa_tpu", "check", "-d", str(tmp_path)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert check.returncode == 0, check.stdout[-2000:] + check.stderr[-2000:]
    inspect = subprocess.run(
        [sys.executable, "-m", "pilosa_tpu", "inspect", "-d", str(tmp_path)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert inspect.returncode == 0
    bits = {}
    for l in inspect.stdout.splitlines():
        path, rest = l.split(": ", 1)
        bits[path] = (int(rest.split("bits=")[1].split()[0]),
                      int(rest.split("ops=")[1].split()[0]))
    index = config["index"]
    for f in fields:
        spec = config["fields"][f]
        for shard in range(2):
            if spec["type"] == "set":
                got = bits[f"{index}/{f}/standard/{shard}"]
                assert got == (datagen.SHARD_WIDTH, 0)  # one value a column
            else:
                # a plane holds value - min (lo_revenue's min is 90000)
                v = cols[f][shard << 20:(shard + 1) << 20].astype(
                    "int64") - spec["min"]
                assert int(v.min()) >= 0
                ones = sum(int(((v >> i) & 1).sum()) for i in range(32))
                got = bits[f"{index}/{f}/bsig_{f}/{shard}"]
                assert got == (ones + datagen.SHARD_WIDTH, 0)
    assert bits[f"{index}/_exists/standard/1"] == (datagen.SHARD_WIDTH, 0)
