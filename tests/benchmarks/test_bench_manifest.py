"""BENCHMARK.json against the contract's mechanical rules, and against
the files it names."""

import json
import os
import re

import pytest

from bench_helpers import BENCH, MANIFEST, ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_exactly_the_contracts_keys():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= MANIFEST["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 << 10


def test_command_and_paths_stay_inside_the_benchmark():
    assert MANIFEST["command"] == ["python3", "benchmarks/run.py"]
    for p in MANIFEST["paths"]:
        assert os.path.isdir(os.path.join(ROOT, p))
        assert not p.startswith("/") and ".." not in p.split("/")


@pytest.mark.parametrize("cfg", MANIFEST["configs"], ids=lambda c: c["name"])
def test_configuration_entry(cfg):
    assert set(cfg) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(cfg["name"])
    assert 1 <= len(cfg["source"]) <= 200 and 1 <= len(cfg["why"]) <= 200
    assert cfg["file"].startswith("benchmarks/configs/")
    with open(os.path.join(ROOT, cfg["file"])) as f:
        body = json.load(f)
    assert body["name"] == cfg["name"]
    assert body["source"] == cfg["source"]
    assert body["reduced"] == cfg["reduced"] and len(cfg["reduced"]) <= 16
    assert all(NAME.match(k) for k in cfg["reduced"])
    assert set(body["reduced_why"]) == set(cfg["reduced"])
    # the guarantees are part of the result: stated in the file
    assert body["guarantees"]["durability_mode"] == "group"
    assert body["server_knobs"] == {}, "cells run the default knobs"
    assert any(w["config"] == cfg["name"] for w in MANIFEST["workloads"])


@pytest.mark.parametrize("cell", MANIFEST["workloads"], ids=lambda c: c["name"])
def test_cell_entry(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
    assert cell["chips"] in (1, 4) and 1 <= len(cell["why"]) <= 200
    assert cell["config"] in {c["name"] for c in MANIFEST["configs"]}
    assert os.path.isfile(os.path.join(BENCH, "traffic",
                                       cell["traffic"] + ".json"))


def test_cells_are_unique_and_few_take_four_chips():
    cells = MANIFEST["workloads"]
    pairs = [(c["config"], c["traffic"]) for c in cells]
    assert len(set(pairs)) == len(pairs)
    four = [c["name"] for c in cells if c["chips"] == 4]
    assert len(four) <= max(1, len(cells) // 2)


@pytest.mark.parametrize("m", MANIFEST["end_to_end"], ids=lambda m: m["name"])
def test_end_to_end_metric(m):
    assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                      "source"}
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher")
    assert m["source"] in ("host_clock", "device_trace")
    assert 0.01 <= m["bound"] <= 0.25


@pytest.mark.parametrize("m", MANIFEST["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metric_is_a_data_file_of_a_known_reader(m):
    assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                      "layer", "moves"}
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["source"] in SOURCES and m["better"] in ("lower", "higher")
    e2e = {e["name"]: e for e in MANIFEST["end_to_end"]}
    assert m["moves"] in e2e
    cells = {c["name"] for c in MANIFEST["workloads"]}
    mine = set(m.get("workloads", cells))
    assert mine <= cells
    # every cell that reads it reports the end-to-end metric it moves
    assert mine <= set(e2e[m["moves"]].get("workloads", cells))
    with open(os.path.join(BENCH, "layer_metrics", m["name"] + ".json")) as f:
        spec = json.load(f)
    assert spec["reader"] in ("ratio", "trace_idle", "trace_ops", "end_to_end")


def test_names_are_unique_and_setup_is_reported():
    metrics = [m["name"] for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]]
    assert len(set(metrics)) == len(metrics)
    setup = [m for m in MANIFEST["end_to_end"] if m["name"] == "setup_s"]
    assert len(setup) == 1 and "workloads" not in setup[0]
    assert setup[0]["bound"] <= 0.25


def test_peaks_table_names_its_source():
    with open(os.path.join(BENCH, "peaks.json")) as f:
        peaks = json.load(f)
    assert "TPU v5e" in peaks["source"]
    assert peaks["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9


def test_every_file_under_paths_is_named_from_a_names_characters():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    listed = os.popen(f"cd {ROOT} && git ls-files -co --exclude-standard "
                      + " ".join(MANIFEST["paths"])).read().split()
    assert listed and all(ok.match(p) for p in listed)
