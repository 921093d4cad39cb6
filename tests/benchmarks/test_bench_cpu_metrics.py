"""The per-layer metrics that read the stage site's second clock (ISSUE 36):
eleven data files of the ``ratio`` reader over the series the program
exports since then (``pilosa_tpu_stage_<x>_cpu_seconds_total``, the three
``pilosa_tpu_thread_<role>_cpu_seconds_total``,
``pilosa_tpu_process_cpu_seconds_total``, and the stage ``wal.commit``).
Against a program that lacks the series (the parent) each reader returns
nothing and raises nothing. A traced rehearsal of ``taxi-rides.point-rw``
prints all eleven, one of ``taxi-rides.dashboard`` the nine that move in
every cell; in both, CPU never exceeds the wall time it lies inside. A
stage's CPU is read inside the traced run's capture (and inside a sampled
trace) only, so its metric divides by the entries it was read in. The
two rehearsals are the traced ones every file of this directory shares.
"""

import json
import os
import sys

import pytest

from bench_helpers import (BENCH, CELLS, MANIFEST, ROOT, last_line,
                           rehearsals)
from harness import readers

sys.path.insert(0, ROOT)

POINT_RW, DASHBOARD = "taxi-rides.point-rw", "taxi-rides.dashboard"
PROCESS, HTTP = "process", "HTTP handler, PQL parse"
# name: (unit, better, source, layer, moves, workloads), as ISSUE 36's table
TABLE = {
    "process_cpu_ms": ("ms/req", "lower", "program_counter", PROCESS,
                       "throughput", None),
    "handler_cpu_ms": ("ms/req", "lower", "program_counter", HTTP,
                       "throughput", None),
    "http_query_cpu_ms": ("ms/req", "lower", "program_span", HTTP,
                          "throughput", None),
    "dispatcher_cpu_ms": ("ms/req", "lower", "program_counter",
                          "wave pipeline", "throughput", None),
    "python_threads_cpu_share": ("%", "lower", "program_counter", PROCESS,
                                 "throughput", None),
    "compute_stage_cpu_share": ("%", "higher", "program_span", PROCESS,
                                "read_p50_ms", None),
    "resolve_cpu_ms": ("ms/read", "lower", "program_span",
                       "readback, serialisation", "read_p50_ms", None),
    "plan_operands_cpu_ms": ("ms/submit", "lower", "program_span",
                             "plan + operand memo", "throughput", None),
    "dispatch_cpu_ms": ("ms/program", "lower", "program_span", "device",
                        "throughput", None),
    "residency_patch_cpu_ms": ("ms/patch", "lower", "program_span",
                               "residency", "read_p95_ms", [POINT_RW]),
    "wal_commit_ms": ("ms/group", "lower", "program_span",
                      "WAL group commit", "write_ack_p95_ms", [POINT_RW]),
}
EVERY_CELL = [n for n, row in TABLE.items() if row[5] is None]
POINT_RW_ONLY = [n for n, row in TABLE.items() if row[5] == [POINT_RW]]
COMPUTE_STAGES = ("pql.parse", "executor.plan", "result.encode",
                  "http.write")


def _stage(stage: str, suffix: str) -> str:
    return f"pilosa_tpu_stage_{stage.replace('.', '_')}_{suffix}"


def _thread(role: str) -> str:
    return f"pilosa_tpu_thread_{role}_cpu_seconds_total"


REQUESTS = [_stage("http.query", "total")]
# name: (numerator series, denominator series, scale). A stage's CPU is
# read inside a sampled trace or a capture only, so it is divided by the
# entries (or the wall seconds) of the entries it was read in
READS = {
    "process_cpu_ms": (["pilosa_tpu_process_cpu_seconds_total"], REQUESTS,
                       1000),
    "handler_cpu_ms": ([_thread("handler")], REQUESTS, 1000),
    "http_query_cpu_ms": ([_stage("http.query", "cpu_seconds_total")],
                          [_stage("http.query", "cpu_entries_total")], 1000),
    "dispatcher_cpu_ms": ([_thread("dispatcher")], REQUESTS, 1000),
    "python_threads_cpu_share": (
        [_thread(r) for r in ("handler", "dispatcher", "wal_commit")],
        ["gen.window_seconds"], 100),
    "compute_stage_cpu_share": (
        [_stage(s, "cpu_seconds_total") for s in COMPUTE_STAGES],
        [_stage(s, "cpu_wall_seconds_total") for s in COMPUTE_STAGES], 100),
    "resolve_cpu_ms": ([_stage("executor.resolve", "cpu_seconds_total")],
                       [_stage("executor.resolve", "cpu_entries_total")],
                       1000),
    "plan_operands_cpu_ms": (
        [_stage("executor.plan", "cpu_seconds_total"),
         _stage("executor.operands", "cpu_seconds_total")],
        [_stage("pipeline.submit", "cpu_entries_total")], 1000),
    "dispatch_cpu_ms": ([_stage("device.dispatch", "cpu_seconds_total")],
                        [_stage("device.dispatch", "cpu_entries_total")],
                        1000),
    "residency_patch_cpu_ms": (
        [_stage("residency.patch", "cpu_seconds_total")],
        [_stage("residency.patch", "cpu_entries_total")], 1000),
    "wal_commit_ms": ([_stage("wal.commit", "seconds_total")],
                      [_stage("wal.commit", "total")], 1000),
}
# (CPU twin, the accepted wall metric over the same stages)
TWINS = [("resolve_cpu_ms", "resolve_ms"),
         ("dispatch_cpu_ms", "dispatch_ms"),
         ("plan_operands_cpu_ms", "plan_operands_ms"),
         ("residency_patch_cpu_ms", "residency_patch_ms")]
# the twins whose stage waits by design (for the device, for the
# runtime): their CPU is a small part of their wall seconds, whichever
# entries either was read over
WAITING_TWINS = [("resolve_cpu_ms", "resolve_ms"),
                 ("residency_patch_cpu_ms", "residency_patch_ms")]


def spec_of(name: str) -> dict:
    with open(os.path.join(BENCH, "layer_metrics", name + ".json")) as f:
        return json.load(f)


# ------------------------------------------------- the files, as ISSUE 36


@pytest.mark.parametrize("name", sorted(TABLE))
def test_entry_is_the_row_of_the_table(name):
    unit, better, source, layer, moves, workloads = TABLE[name]
    entry = {m["name"]: m for m in MANIFEST["per_layer"]}[name]
    want = {"name": name, "unit": unit, "better": better, "source": source,
            "layer": layer, "moves": moves}
    if workloads is not None:
        want["workloads"] = workloads
    assert entry == want
    # a layer the benchmark already names keeps its name, letter for letter
    others = {m["layer"] for m in MANIFEST["per_layer"]
              if m["name"] not in TABLE}
    assert layer in others or layer == PROCESS


@pytest.mark.parametrize("name", sorted(TABLE))
def test_file_is_a_ratio_over_the_named_series(name):
    numerator, denominator, scale = READS[name]
    spec = spec_of(name)
    assert set(spec) == {"reader", "numerator", "denominator", "scale",
                         "what"}
    assert spec["reader"] == "ratio" and spec["what"]
    assert (spec["numerator"], spec["denominator"], spec["scale"]) == (
        numerator, denominator, scale)


@pytest.mark.parametrize("name", sorted(TABLE))
def test_reader_returns_nothing_against_a_program_without_the_series(name):
    """The parent's ``/metrics``: the counts and wall seconds are there,
    no CPU series, no thread block, no ``wal.commit``."""
    from pilosa_tpu.utils.tracing import STAGES

    old = {_stage(s, suffix): 1.0 for s in STAGES if s != "wal.commit"
           for suffix in ("total", "seconds_total")}
    assert not any("_cpu_" in k for k in old)
    old["gen.window_seconds"] = 30.0
    assert readers.read(BENCH, name, {}, {}, None, {}) is None
    assert readers.read(BENCH, name, {}, old, None, {}) is None


def test_every_series_read_is_one_the_program_exports():
    from pilosa_tpu.utils.tracing import (STAGES, THREAD_ROLES, stage_metrics,
                                          thread_metrics)

    exported = {f"pilosa_tpu_stage_{k}" for k in stage_metrics()}
    exported |= {f"pilosa_tpu_{k}" for k in thread_metrics()}
    exported.add("gen.window_seconds")
    assert "wal.commit" in STAGES
    assert THREAD_ROLES == ("handler", "dispatcher", "wal_commit")
    for name, (numerator, denominator, _) in READS.items():
        assert set(numerator + denominator) <= exported, name


def test_the_eleven_follow_the_metrics_the_benchmark_had():
    """Added at the end, in the table's order; every metric the benchmark
    had keeps its place and its entry's ``workloads`` (none of the new
    names appears before ``residency_upload_mib_s``, PR 34's last)."""
    names = [m["name"] for m in MANIFEST["per_layer"]]
    at = [names.index(n) for n in TABLE]
    assert at == list(range(at[0], at[0] + len(TABLE)))
    assert at[0] > names.index("residency_upload_mib_s")
    assert len(TABLE) == 11 and len(EVERY_CELL) == 9
    assert POINT_RW_ONLY == ["residency_patch_cpu_ms", "wal_commit_ms"]
    # a CPU twin is listed wherever its wall twin is, and only there
    entries = {m["name"]: m for m in MANIFEST["per_layer"]}
    for cpu, wall in TWINS:
        assert entries[cpu].get("workloads") == entries[wall].get(
            "workloads"), cpu
        assert entries[cpu]["layer"] == entries[wall]["layer"], cpu
        assert entries[cpu]["moves"] == entries[wall]["moves"], cpu


# ------------------------------------------------------ the cells rehearsed


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """The two cells' traced rehearsals, which every file of this
    directory shares (``bench_helpers.rehearsals``)."""
    return rehearsals(tmp_path_factory, [(POINT_RW, 1), (DASHBOARD, 1)])


@pytest.mark.parametrize("cell,printed,left_out", [
    (POINT_RW, sorted(TABLE), []),
    (DASHBOARD, sorted(EVERY_CELL), sorted(POINT_RW_ONLY)),
], ids=["point-rw", "dashboard"])
def test_traced_rehearsal_prints_the_cpu_metrics_of_its_cell(
        traced, cell, printed, left_out):
    assert cell in CELLS
    p = traced[cell, 1]
    assert p.returncode == 0, p.stderr[-3000:] + p.stdout[-2000:]
    line = last_line(p.stdout)
    assert line["correct"] is True and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"  # counts, never device numbers
    metrics = line["metrics"]
    for name in printed:
        assert metrics[name]["unit"] == TABLE[name][0], name
        assert metrics[name]["value"] > 0, name
    for name in left_out:
        assert name not in metrics
    value = {n: m["value"] for n, m in metrics.items()}
    # the root stage's CPU is part of its thread's, a thread's of the
    # process's
    assert (value["http_query_cpu_ms"] <= value["handler_cpu_ms"]
            <= value["process_cpu_ms"])
    assert value["dispatcher_cpu_ms"] <= value["process_cpu_ms"]
    assert 0 < value["compute_stage_cpu_share"] <= 100
    # CPU lies inside the wall time of the same entries (the share says
    # so); a CPU twin is over the capture's entries and its wall twin over
    # the window's, so the two are compared where the stage waits by design
    for cpu, wall in WAITING_TWINS:
        if cpu in printed:
            assert value[cpu] <= value[wall], (cpu, value[cpu], value[wall])
