"""The per-layer metrics that read the stage counters (ISSUE 25): each is
a data file over the ``ratio`` reader, every series it names is one the
program exports, and a traced rehearsal of each cell carries exactly the
new metrics its ``workloads`` key allows, each with a value."""

import json
import os
import sys

import pytest

from bench_helpers import (BENCH, CELLS, MANIFEST, ROOT, last_line,
                           rehearsals)

sys.path.insert(0, ROOT)

STAGE_METRICS = {
    "http_parse_ms", "wave_wait_ms", "dispatcher_busy_share",
    "plan_operands_ms", "dispatch_ms", "resolve_ms", "encode_ms",
    "wal_barrier_ms", "residency_patch_ms", "residency_lock_wait_ms",
    "host_attributed_share", "program_compiles_in_window",
}
ENTRIES = {m["name"]: m for m in MANIFEST["per_layer"]
           if m["name"] in STAGE_METRICS}


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """Every cell's traced rehearsal: the runs every file of this
    directory shares, each with a compile cache of its own. The taxi cells
    first: the SSB cells, 100-150 s each, are made meanwhile by the
    workers that run those cells' own files."""
    return rehearsals(tmp_path_factory,
                      [(cell, 1) for cell in sorted(CELLS, reverse=True)])


def spec_of(name: str) -> dict:
    with open(os.path.join(BENCH, "layer_metrics", name + ".json")) as f:
        return json.load(f)


def test_the_twelve_are_listed_after_the_seven_the_benchmark_had():
    names = [m["name"] for m in MANIFEST["per_layer"]]
    assert set(ENTRIES) == STAGE_METRICS
    assert names[:7] == ["generator_busy_share", "wave_depth",
                         "residency_hit_share", "compiles_in_window",
                         "fsyncs_per_write", "write_ack_p50_ms",
                         "device_idle_share"]
    # after the seven and together, in ISSUE 25's order; later PRs append
    # past them, so the tail is theirs and no count is pinned
    assert set(names[7:]) >= STAGE_METRICS
    assert names[7:7 + len(STAGE_METRICS)] == [
        "http_parse_ms", "wave_wait_ms", "dispatcher_busy_share",
        "plan_operands_ms", "dispatch_ms", "resolve_ms", "encode_ms",
        "wal_barrier_ms", "residency_patch_ms", "residency_lock_wait_ms",
        "host_attributed_share", "program_compiles_in_window"]


@pytest.mark.parametrize("name", sorted(STAGE_METRICS))
def test_metric_reads_series_the_program_exports(name):
    from pilosa_tpu.utils.tracing import STAGES

    spec = spec_of(name)
    assert spec["reader"] == "ratio" and spec["what"]
    exported = {f"pilosa_tpu_stage_{s.replace('.', '_')}{suffix}"
                for s in STAGES for suffix in ("_total", "_seconds_total")}
    exported |= {"pilosa_tpu_device_compiles_total", "gen.window_seconds"}
    for series in spec["numerator"] + spec.get("denominator", []):
        assert series in exported, series
    entry = ENTRIES[name]
    assert entry["source"] == ("program_counter"
                               if name == "program_compiles_in_window"
                               else "program_span")


def test_host_attributed_share_sums_the_nine_top_level_stages():
    from pilosa_tpu.utils.tracing import TOP_LEVEL_STAGES

    spec = spec_of("host_attributed_share")
    assert sorted(spec["numerator"]) == sorted(
        f"pilosa_tpu_stage_{s.replace('.', '_')}_seconds_total"
        for s in TOP_LEVEL_STAGES)
    assert len(spec["numerator"]) == 9
    assert spec["denominator"] == ["pilosa_tpu_stage_http_query_seconds_total"]


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_traced_rehearsal_carries_the_metrics_its_workloads_key_allows(
        traced, cell):
    p = traced[cell, 1]
    assert p.returncode == 0, p.stderr[-3000:] + p.stdout[-2000:]
    line = last_line(p.stdout)
    assert line["correct"] is True and line["failed"] == 0
    allowed = {n for n, m in ENTRIES.items()
               if cell in m.get("workloads", [cell])}
    got = set(line["metrics"]) & STAGE_METRICS
    assert got == allowed  # none left out for a missing series, none extra
    for name in allowed:
        body = line["metrics"][name]
        assert body["unit"] == ENTRIES[name]["unit"]
        assert isinstance(body["value"], (int, float))
        assert body["value"] >= 0
    # the partition holds in a served window: the nine top-level stages
    # cover the request, and never more than the request
    assert 50 < line["metrics"]["host_attributed_share"]["value"] <= 100
    # every program the window compiled is one JAX's events counted (the
    # events count persistent-cache loads too, the cache's files do not)
    assert (line["metrics"]["program_compiles_in_window"]["value"]
            >= line["metrics"]["compiles_in_window"]["value"])
