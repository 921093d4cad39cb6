"""Shared by the benchmark's tests: where things are, and one way to run
a rehearsal (``benchmarks/run.py --rehearse``: the whole harness on the
CPU at 2 shards, one server child)."""

from __future__ import annotations

import importlib.util
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmarks")
RUN = os.path.join(BENCH, "run.py")
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

# BENCH_MANIFEST: a manifest other than the shipped one, for the one test
# that runs the pins of these files against a copy with entries appended
# (test_bench_pins.py); nothing else sets it
with open(os.environ.get("BENCH_MANIFEST")
          or os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
CELLS = {w["name"]: w for w in MANIFEST["workloads"]}
CONTROL = {name: ("lost-write" if cell["traffic"] == "point-rw" else "sampled")
           for name, cell in CELLS.items()}


def load_config(name: str) -> dict:
    files = {c["name"]: c["file"] for c in MANIFEST["configs"]}
    with open(os.path.join(ROOT, files[name])) as f:
        return json.load(f)


def load_mix(name: str) -> dict:
    from harness import traffic

    return traffic.load_mix(traffic.mix_path(BENCH, name))


# Kinds of field, template, filter term and draw that no shipped mix uses
# yet (they are what the SSB flights in PERF.md's Open questions need, and
# a later PR can only add data files), exercised by the tests on a toy of
# their own. The last three templates are in the forms of SSB's Q1.1 (a Sum
# under two range terms and a row), Q3.3 (a GroupBy under two ``in`` terms,
# on its own dimension and on another field) and Q4.1 (a GroupBy under two
# rows and an ``in``).
TOY_CONFIG = {
    "name": "toy", "index": "toy", "shards": 2, "fields": {
        "brand": {"type": "set", "uniform": 80},
        "category": {"type": "set", "rows": 10,
                     "derived": {"field": "brand", "div": 8}},
        "region": {"type": "set", "uniform": 5},
        "year": {"type": "set", "uniform": 7},
        "city": {"type": "set", "uniform": 20},
        "nation": {"type": "set", "rows": 5,
                   "derived": {"field": "city", "div": 4}},
        "revenue": {"type": "int", "min": 0, "max": 5000,
                    "uniform_int": [100, 5000]},
        "quantity": {"type": "int", "min": 0, "max": 50,
                     "uniform_int": [1, 50]},
        "discount": {"type": "int", "min": 0, "max": 10,
                     "uniform_int": [0, 10]}}}
_BY_YEAR_AND_BRAND = {"kind": "groupby", "sum": "revenue"}
TOY_MIX = {
    "name": "toy-flight", "preload": False,
    "groups": [{"name": "analysts", "clients": 4, "loop": "closed",
                "rotation": ["category_page", "brand_span", "one_brand",
                             "discounted_revenue", "city_pair",
                             "nation_by_year"]}],
    "templates": {
        "discounted_revenue": {
            "kind": "sum", "sum": "revenue",
            "filter": [["year", "Y"],
                       ["discount", {"between": ["LO", "HI"]}],
                       ["quantity", {"lt": 25}]],
            "draw": {"Y": {"row_of": "year"}, "LO": {"int": [1, 5]},
                     "HI": {"affine": ["LO", 1, 2]}}},
        "city_pair": {
            "kind": "groupby", "sum": "revenue",
            "dims": [{"field": "city"}, {"field": "year", "limit": 6}],
            "filter": [["city", {"in": ["A", "B"]}],
                       ["region", {"in": ["R", "S"]}]],
            "draw": {"A": {"row_of": "city", "span": 5},
                     "B": {"affine": ["A", 1, 4]},
                     "R": {"row_of": "region", "span": 2},
                     "S": {"affine": ["R", 1, 1]}}},
        "nation_by_year": {
            "kind": "groupby", "sum": "revenue",
            "dims": [{"field": "year"}, {"field": "nation"}],
            "filter": [["region", "R"], ["category", "C"],
                       ["brand", {"in": ["B", "B2"]}]],
            "draw": {"R": {"row_of": "region"}, "C": {"row_of": "category"},
                     "B": {"affine": ["C", 8, 0]},
                     "B2": {"affine": ["C", 8, 5]}}},
        "category_page": dict(
            _BY_YEAR_AND_BRAND,
            dims=[{"field": "year"},
                  {"field": "brand", "previous": "P", "limit": 8}],
            filter=[["category", "C"], ["region", "R"]],
            draw={"C": {"row_of": "category"}, "R": {"row_of": "region"},
                  "P": {"affine": ["C", 8, -1]}}),
        "brand_span": dict(
            _BY_YEAR_AND_BRAND,
            dims=[{"field": "year"},
                  {"field": "brand", "previous": "P", "limit": 4}],
            filter=[["region", "R"]],
            draw={"B": {"row_of": "brand", "span": 4},
                  "R": {"row_of": "region"}, "P": {"affine": ["B", 1, -1]}}),
        "one_brand": {
            "kind": "sum", "sum": "revenue",
            "filter": [["brand", "B"], ["region", "R"]],
            "draw": {"B": {"row_of": "brand"}, "R": {"row_of": "region"}}}}}
TOY_CELL = {"name": "toy.toy-flight", "config": "toy", "traffic": "toy-flight"}


def config_and_mix(cell: dict) -> tuple[dict, dict]:
    if cell is TOY_CELL:
        return TOY_CONFIG, TOY_MIX
    return load_config(cell["config"]), load_mix(cell["traffic"])


def rehearse(workload: str, *extra: str, seed: int = 2_600_000_011,
             seconds: float = 3.0, trace: int = 0, env: dict | None = None):
    """One rehearsal as a subprocess; returns the CompletedProcess."""
    return subprocess.run(
        [sys.executable, RUN, "--workload", workload, "--seed", str(seed),
         "--seconds", str(seconds), "--trace", str(trace), "--rehearse",
         *extra],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)


def last_line(stdout: str) -> dict:
    return json.loads(stdout.strip().splitlines()[-1])


def import_run():
    """``benchmarks/run.py`` as a module, for tests that break the timed
    path underneath it."""
    spec = importlib.util.spec_from_file_location("bench_run", RUN)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
