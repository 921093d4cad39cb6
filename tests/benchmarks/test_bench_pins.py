"""The pins the benchmark's tests hold on ``BENCHMARK.json`` survive what a
later PR may do to it: append a configuration, a cell and a per-layer
metric (ISSUE 41; ROADMAP R0 a, d, h). Five of them did not, and were red
on every line of the ledger from PR 27 to PR 40, because the PR that
appends may not edit the test that pinned the tail or the count.

The proof is the pins themselves: the manifest-level tests of the seven
files below are run once more, in a process of their own, against a copy
of the manifest with one of each appended (``BENCH_MANIFEST``, read by
``bench_helpers.py`` alone)."""

import json
import os
import re
import subprocess
import sys

from bench_helpers import MANIFEST, ROOT

HERE = os.path.dirname(os.path.abspath(__file__))
# every test of these files that reads the manifest and rehearses nothing
PINS = {
    "test_bench_stage_metrics.py": [
        "test_the_twelve_are_listed_after_the_seven_the_benchmark_had"],
    "test_bench_grid_cell.py": [
        "test_configuration_is_taxi_rides_with_the_two_grid_fields",
        "test_both_cells_are_one_chip_and_say_what_they_do",
        "test_metric_entry_lists_the_grid_cell_alone",
        "test_the_five_are_appended_and_the_groupby_lists_stand"],
    "test_bench_ssb_cell.py": [
        "test_configuration_is_ssb_lineorder_at_sf_10",
        "test_cell_is_one_chip_and_says_what_it_holds",
        "test_metric_entry_lists_the_three_cells_that_run_groupby"],
    "test_bench_mesh_cell.py": [
        "test_metric_file_names_a_reader_that_exists"],
    "test_bench_grid_x4_cell.py": [
        "test_configuration_is_the_grid_schema_at_the_x4_scale",
        "test_the_two_cells_are_appended_and_say_what_they_do",
        "test_metric_entry_and_file",
        "test_the_seven_are_appended_in_the_issues_order"],
    "test_bench_cpu_metrics.py": [
        "test_entry_is_the_row_of_the_table",
        "test_the_eleven_follow_the_metrics_the_benchmark_had"],
    "test_bench_golden_traffic.py": [
        "test_shipped_mix_sends_what_it_sent_at_the_parent",
        "test_golden_holds_every_mix_a_cell_names"],
}


def appended() -> dict:
    """The manifest as a later ``model_config`` and ``tracing`` PR would
    leave it: one configuration, one cell on it and one metric that
    lists it, each after everything the benchmark has."""
    grown = json.loads(json.dumps(MANIFEST))
    grown["configs"].append({
        "name": "ssb-lineorder-flights",
        "source": "a configuration a later PR appends",
        "file": "benchmarks/configs/ssb-lineorder-flights.json",
        "reduced": ["columns"], "why": "appended after the five"})
    grown["workloads"].append({
        "name": "ssb-lineorder-flights.q-flight",
        "config": "ssb-lineorder-flights", "traffic": "q-flight",
        "chips": 1, "why": "a cell a later PR appends"})
    grown["per_layer"].append({
        "name": "bsi_compare_share", "unit": "%", "better": "lower",
        "source": "device_trace", "layer": "device", "moves": "throughput",
        "workloads": ["ssb-lineorder-flights.q-flight",
                      "taxi-rides-grid.cell-lookup"]})
    grown["per_layer"].append({
        "name": "appended_everywhere", "unit": "count", "better": "lower",
        "source": "program_counter", "layer": "device",
        "moves": "throughput"})
    return grown


def test_every_pin_holds_with_a_configuration_a_cell_and_metrics_appended(
        tmp_path):
    grown = appended()
    assert len(grown["configs"]) == len(MANIFEST["configs"]) + 1
    assert len(grown["workloads"]) == len(MANIFEST["workloads"]) + 1
    assert len(grown["per_layer"]) == len(MANIFEST["per_layer"]) + 2
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(grown))
    ids = [f"{os.path.join(HERE, f)}::{t}" for f, tests in PINS.items()
           for t in tests]
    env = dict(os.environ, BENCH_MANIFEST=str(path))
    p = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         "-p", "no:randomly", *ids],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    tail = p.stdout[-3000:] + p.stderr[-2000:]
    assert p.returncode == 0, tail
    passed = int(re.search(r"(\d+) passed", p.stdout).group(1))
    # the parametrised ones count a case each: well over one a pin
    assert passed >= 60 and "failed" not in p.stdout, tail


def test_the_helper_reads_the_shipped_manifest_unless_told():
    assert "BENCH_MANIFEST" not in os.environ
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        assert json.load(f) == MANIFEST
