"""The cell ``ssb-lineorder-flights.q-flight`` (ISSUE 42): all 13 queries
of the Star Schema Benchmark over lineorder at SF 10 on one chip. The
configuration and the mix are data files, held here to the issue: the 17
fields at the source's row counts, the 13 templates with their draws, 4
closed-loop clients, ``preload`` true, one chip. The eight per-layer
metrics are data files of readers the harness has: they read made-up
scrapes as they say, and return nothing (and raise nothing) against a
program that lacks the counters and the stage PR 42 added, as the parent
does. The mix's first requests render to the PQL that
``test_bench_terms.py::test_the_forms_are_written_as_ssb_writes_them``
pins for the 13 forms.

The mix is the issue's letter for letter: a rotation of the 13 once,
every term as the source writes it. Two older tests of this directory
could not take that until PR 47 (one counted 24 requests a client, the
other handed Q4.3 to the parent's reference: 5.5e9 cells), so this file
holds the cell to what they were written to hold: every template in its
fixed proportion over whole rotations, and the harness's reference equal
to the parent's on every template the parent's can tabulate, and to a
filter-then-count of the columns on all 13.

Pins are by membership and relative order, never by tail or count. The
cell's two rehearsals (~150 s of CPU each) are the ones every file of
this directory shares: run here or read from the worker that ran them.
"""

import collections
import json
import math
import os
import sys

import numpy as np
import pytest

from bench_helpers import (BENCH, CELLS, MANIFEST, ROOT, last_line,
                           load_config, load_mix, rehearsals)
from harness import datagen, readers, reference, trace, traffic
from xplane_writer import xspace

CELL = "ssb-lineorder-flights.q-flight"
CONFIG = "ssb-lineorder-flights"
ORDER = ["q1_1", "q1_2", "q1_3", "q2_1", "q2_2", "q2_3", "q3_1", "q3_2",
         "q3_3", "q3_4", "q4_1", "q4_2", "q4_3"]
MS = 1_000_000  # ns
G = "pilosa_tpu_groupby_{}".format
PRUNE = "pilosa_tpu_stage_executor_prune_level_{}".format
# name: (unit, better, source, layer, moves)
NEW = {
    "flight_pruned_groupby_share": (
        "%", "higher", "program_counter", "plan + operand memo",
        "throughput"),
    "flight_levels_per_groupby": (
        "levels/groupby", "lower", "program_counter", "plan + operand memo",
        "throughput"),
    "flight_prune_level_ms": (
        "ms/level", "lower", "program_span", "readback, serialisation",
        "read_p50_ms"),
    "flight_prune_level_cpu_ms": (
        "ms/level", "lower", "program_span", "readback, serialisation",
        "read_p50_ms"),
    "flight_paged_program_share": (
        "%", "lower", "program_counter", "device", "throughput"),
    "flight_groupby_level_share": (
        "%", "higher", "device_trace", "device", "throughput"),
    "flight_level_programs_per_level": (
        "programs/level", "lower", "program_counter", "device",
        "throughput"),
    "flight_candidates_per_level": (
        "cand/level", "higher", "program_counter", "device", "throughput"),
}
# the twin and the shipped metric whose list is pinned to other cells
TWINS = {"flight_groupby_level_share": "groupby_level_share",
         "flight_level_programs_per_level": "level_programs_per_level",
         "flight_candidates_per_level": "candidates_per_level",
         "flight_prune_level_cpu_ms": None}


def spec_of(name: str) -> dict:
    with open(os.path.join(BENCH, "layer_metrics", name + ".json")) as f:
        return json.load(f)


def side(p: str) -> dict:
    return {f"{p}_city": {"type": "set", "uniform": 250},
            f"{p}_nation": {"type": "set", "rows": 25,
                            "derived": {"field": f"{p}_city", "div": 10}},
            f"{p}_region": {"type": "set", "rows": 5,
                            "derived": {"field": f"{p}_city", "div": 50}}}


# ------------------------------------------------- the files, as ISSUE 42


def test_configuration_is_ssb_lineorder_with_the_17_columns_of_13_queries():
    config, base = load_config(CONFIG), load_config("ssb-lineorder")
    assert list(config) == list(base)  # the same keys in the same order
    assert (config["name"], config["index"], config["chips"],
            config["shards"], config["rehearse_shards"]) == (
        CONFIG, "lineorder", 1, 58, 1)
    assert 57 * datagen.SHARD_WIDTH < 59_986_052 <= 58 * datagen.SHARD_WIDTH
    assert config["server_knobs"] == {}
    assert config["guarantees"] == base["guarantees"]  # word for word
    assert config["reduced"] == ["columns"] == list(config["reduced_why"])
    assert "not cut" in config["reduced_why"]["columns"]
    for words in ("Star Schema Benchmark", "rev. 3", "flights 1-4",
                  "Q1.1-Q4.3", "SF 10 (59,986,052 rows)", "customer",
                  "supplier", "part", "date"):
        assert words in config["source"], words
    assert len(config["source"]) <= 200
    assert config["fields"] == {
        **side("c"), **side("s"),
        "p_brand1": {"type": "set", "uniform": 1000},
        "p_category": {"type": "set", "rows": 25,
                       "derived": {"field": "p_brand1", "div": 40}},
        "p_mfgr": {"type": "set", "rows": 5,
                   "derived": {"field": "p_brand1", "div": 200}},
        "d_yearmonthnum": {"type": "set", "uniform": 84},
        "d_year": {"type": "set", "rows": 7, "labels_from": 1992,
                   "derived": {"field": "d_yearmonthnum", "div": 12}},
        "d_weeknuminyear": {"type": "set", "uniform": 53},
        "lo_discount": {"type": "int", "min": 0, "max": 10,
                        "uniform_int": [0, 10]},
        "lo_quantity": {"type": "int", "min": 1, "max": 50,
                        "uniform_int": [1, 50]},
        "lo_revenue": base["fields"]["lo_revenue"],
        "lo_ext_disc": {"type": "int", "min": 0, "max": 104949500,
                        "uniform_int": [0, 104949500]},
        "lo_profit": {"type": "int", "min": -35939, "max": 10440950,
                      "uniform_int": [-35939, 10440950]}}
    # what it shares with ssb-lineorder it shares letter for letter
    for f in ("p_brand1", "p_category", "lo_revenue"):
        assert config["fields"][f] == base["fields"][f]
    # the planes a measure takes: value - min, as the program holds it
    depth = {f: (s["max"] - s["min"]).bit_length()
             for f, s in config["fields"].items() if s["type"] == "int"}
    assert depth == {"lo_discount": 4, "lo_quantity": 6, "lo_revenue": 24,
                     "lo_ext_disc": 27, "lo_profit": 24}
    assumed = " ".join(config["assumed"])
    for words in ("independent and uniform", "lo_extendedprice x lo_discount",
                  "lo_revenue - lo_supplycost", "no expression over two",
                  "every column of every loaded shard holds an order line"):
        assert words in assumed, words
    entry = {c["name"]: c for c in MANIFEST["configs"]}[CONFIG]
    assert entry["source"] == config["source"]
    assert entry["file"] == f"benchmarks/configs/{CONFIG}.json"
    assert entry["reduced"] == ["columns"] and len(entry["why"]) <= 200
    assert entry["source"] not in {c["source"] for c in MANIFEST["configs"]
                                   if c is not entry}


def test_the_cell_is_one_chip_and_appended():
    cell = CELLS[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "q-flight", 1)
    for words in ("4 closed-loop clients", "Q1.1-Q4.3", "SF 10", "pruned",
                  "paged"):
        assert words in cell["why"], words
    assert len(cell["why"]) <= 200
    names = list(CELLS)
    assert names.index("ssb-lineorder.brand-sweep") < names.index(CELL)
    configs = [c["name"] for c in MANIFEST["configs"]]
    assert configs.index("taxi-rides-grid-x4") < configs.index(CONFIG)
    four = [n for n, c in CELLS.items() if c["chips"] == 4]
    assert CELL not in four and len(four) <= len(CELLS) // 2


def test_the_mix_is_the_13_queries_in_the_sources_order():
    mix = load_mix("q-flight")
    assert mix["name"] == "q-flight" and mix["preload"] is True
    (group,) = mix["groups"]
    assert (group["clients"], group["loop"]) == (4, "closed")
    assert list(mix["templates"]) == ORDER
    assert group["rotation"] == ORDER  # each once: 6 of 10 GroupBys prune
    assert all(mix["templates"][t]["kind"] != "set" for t in ORDER)
    lookup = load_mix("brand-lookup")
    for t in ("q2_1", "q2_2", "q2_3"):  # letter for letter
        assert mix["templates"][t] == lookup["templates"][t]


def test_the_templates_and_their_draws_are_the_issues():
    t = load_mix("q-flight")["templates"]
    windows = {"LO": {"int": [1, 8]}, "HI": {"affine": ["LO", 1, 2]},
               "QL": {"int": [1, 41]}, "QH": {"affine": ["QL", 1, 9]}}
    drawn = [["lo_discount", {"between": ["LO", "HI"]}],
             ["lo_quantity", {"between": ["QL", "QH"]}]]
    assert t["q1_1"] == {
        "kind": "sum", "sum": "lo_ext_disc",
        "filter": [["d_year", "Y"], ["lo_discount", {"between": [1, 3]}],
                   ["lo_quantity", {"lt": 25}]],
        "draw": {"Y": {"row_of": "d_year"}}}
    assert t["q1_2"] == {
        "kind": "sum", "sum": "lo_ext_disc",
        "filter": [["d_yearmonthnum", "M"]] + drawn,
        "draw": {"M": {"row_of": "d_yearmonthnum"}, **windows}}
    assert t["q1_3"] == {
        "kind": "sum", "sum": "lo_ext_disc",
        "filter": [["d_weeknuminyear", "W"], ["d_year", "Y"]] + drawn,
        "draw": {"W": {"row_of": "d_weeknuminyear"},
                 "Y": {"row_of": "d_year"}, **windows}}
    years = {"field": "d_year", "limit": 6}
    last_two = {"field": "d_year", "previous": 4, "limit": 2}
    cities = [{"field": "c_city"}, {"field": "s_city"}, years]
    both = [["c_city", {"in": ["A", "B"]}], ["s_city", {"in": ["A", "B"]}]]
    two = {"A": {"row_of": "c_city", "top": 50}, "B": {"affine": ["A", 1, 4]}}
    region = {"R": {"row_of": "c_region"}}
    makers = [["c_region", "R"], ["s_region", "R"],
              ["p_mfgr", {"in": [0, 1]}]]
    assert t["q3_1"] == {
        "kind": "groupby", "sum": "lo_revenue",
        "dims": [{"field": "c_nation"}, {"field": "s_nation"}, years],
        "filter": [["c_region", "R"], ["s_region", "R"]], "draw": region}
    assert t["q3_2"] == {
        "kind": "groupby", "sum": "lo_revenue", "dims": cities,
        "filter": [["c_nation", "N"], ["s_nation", "N"]],
        "draw": {"N": {"row_of": "c_nation"}}}
    assert t["q3_3"] == {"kind": "groupby", "sum": "lo_revenue",
                         "dims": cities, "filter": both, "draw": two}
    assert t["q3_4"] == {
        "kind": "groupby", "sum": "lo_revenue", "dims": cities,
        "filter": both + [["d_yearmonthnum", "M"]],
        "draw": {**two, "M": {"row_of": "d_yearmonthnum"}}}
    assert t["q4_1"] == {
        "kind": "groupby", "sum": "lo_profit",
        "dims": [{"field": "d_year"}, {"field": "c_nation"}],
        "filter": makers, "draw": region}
    assert t["q4_2"] == {
        "kind": "groupby", "sum": "lo_profit",
        "dims": [last_two, {"field": "s_nation"}, {"field": "p_category"}],
        "filter": makers, "draw": region}
    assert t["q4_3"] == {
        "kind": "groupby", "sum": "lo_profit",
        "dims": [last_two, {"field": "s_city"},
                 {"field": "p_brand1", "previous": "P", "limit": 40}],
        "filter": [["c_region", "R"], ["s_nation", "N"],
                   ["p_category", "C"]],
        "draw": {**region, "N": {"row_of": "s_nation"},
                 "C": {"row_of": "p_category", "top": 5},
                 "P": {"affine": ["C", 40, -1]}}}


def test_every_field_is_read_and_the_filter_rows_are_preloaded():
    config, mix = load_config(CONFIG), load_mix("q-flight")
    assert traffic.fields_read(mix, config) == list(config["fields"])
    rows = traffic.preload_rows(mix, config)
    by_field: dict = {}
    for f, r in rows:
        by_field.setdefault(f, set()).add(r)
    whole = {"d_year": 7, "d_yearmonthnum": 84, "d_weeknuminyear": 53,
             "c_region": 5, "s_region": 5, "c_nation": 25, "s_nation": 25}
    for f, n in whole.items():
        assert by_field[f] == set(range(n)), f
    # one region's cities, the top 5 categories, the two manufacturers
    assert by_field["c_city"] == set(range(50))
    assert by_field["p_category"] == set(range(5))
    assert by_field["p_mfgr"] == {0, 1}
    assert "p_brand1" not in by_field and "s_city" not in by_field


@pytest.mark.parametrize("seed", [7, 2_147_483_659, 4_200_000_011])
def test_the_first_requests_render_to_the_forms_ssb_writes(seed):
    """What ``test_bench_terms.py::test_the_forms_are_written_as_ssb_
    writes_them`` pins for the toy's 13 forms, on the shipped mix at the
    source's sizes; every constant inside what a draw may name."""
    config, mix = load_config(CONFIG), load_mix("q-flight")
    clients = traffic.clients(mix, config, config["shards"], seed, "window")
    assert len(clients) == 4
    # client k starts at offset k of the rotation
    first = [c.next() for c in clients]
    assert [name for name, _, _ in first] == ORDER[:4]
    text: dict = {}
    for _ in range(48):
        name, pql, sem = clients[0].next()
        assert pql == traffic.render(sem)
        text.setdefault(name, pql)
    assert list(text) == ORDER[1:] + ORDER[:1]
    assert text["q1_1"].startswith("Sum(Intersect(Row(d_year=")
    assert text["q1_1"].endswith(
        "Row(lo_discount >< [1, 3]), Row(lo_quantity < 25)), "
        "field=\"lo_ext_disc\")")
    assert "Row(lo_discount >< [" in text["q1_2"]
    assert "Row(lo_quantity >< [" in text["q1_2"]
    assert "Row(d_weeknuminyear=" in text["q1_3"]
    assert text["q2_1"].startswith(
        "GroupBy(Rows(d_year), Rows(p_brand1, previous=")
    assert text["q3_1"].startswith(
        "GroupBy(Rows(c_nation), Rows(s_nation), Rows(d_year, limit=6), "
        "filter=Intersect(Row(c_region=")
    assert text["q3_2"].startswith(
        "GroupBy(Rows(c_city), Rows(s_city), Rows(d_year, limit=6), "
        "filter=Intersect(Row(c_nation=")
    assert "Union" not in text["q3_2"] and "Union" not in text["q4_3"]
    assert "Intersect(Union(Row(c_city=" in text["q3_3"]
    assert "Union(Row(s_city=" in text["q3_4"]
    assert "Row(d_yearmonthnum=" in text["q3_4"]
    assert "Union(Row(p_mfgr=0), Row(p_mfgr=1))" in text["q4_1"]
    assert text["q4_2"].startswith(
        "GroupBy(Rows(d_year, previous=4, limit=2), Rows(s_nation), "
        "Rows(p_category), filter=Intersect(Row(c_region=")
    assert text["q4_3"].startswith(
        "GroupBy(Rows(d_year, previous=4, limit=2), Rows(s_city), "
        "Rows(p_brand1, previous=")
    assert ", limit=40), filter=Intersect(Row(c_region=" in text["q4_3"]
    assert text["q4_3"].endswith("aggregate=Sum(field=\"lo_profit\"))")


@pytest.mark.parametrize("seed", [7, 2_147_483_659, 4_200_000_011])
def test_every_seed_draws_inside_the_hot_set(seed):
    """The constants a window can name are the rows the memory was
    reckoned for: 54 cities a side, Q2.1's five categories for Q4.3 too,
    windows of one width."""
    config, mix = load_config(CONFIG), load_mix("q-flight")
    cities, categories = set(), set()
    for client in traffic.clients(mix, config, config["shards"], seed, "w"):
        for _ in range(600):
            name, _pql, sem = client.next()
            terms = {f: spec for f, spec in sem.get("filter", ())}
            if name in ("q3_3", "q3_4"):
                a, b = terms["c_city"]["in"]
                assert terms["s_city"]["in"] == [a, b] and b == a + 4
                cities.update((a, b))
            if name in ("q1_2", "q1_3"):
                lo, hi = terms["lo_discount"]["between"]
                ql, qh = terms["lo_quantity"]["between"]
                assert (hi - lo, qh - ql) == (2, 9)
                assert 1 <= lo and hi <= 10 and 1 <= ql and qh <= 50
            if name == "q4_3":
                brands = sem["dims"][2]
                assert brands["previous"] == 40 * terms["p_category"] - 1
                categories.add(terms["p_category"])
            if name == "q3_2":
                assert terms["c_nation"] == terms["s_nation"]
    assert cities <= set(range(54)) and {0, 53} <= cities
    assert categories == set(range(5))


# ---- what two older tests, written for rotations that divide 24 and for
# ---- tables the parent's reference can hold, hold of this cell

SEEDS = (4_100_000_007, 41, 2_147_483_659)


def test_rotation_keeps_templates_in_fixed_proportions_over_whole_rotations():
    """``test_bench_traffic.py``'s test of that name, counting a client's
    first lcm(24, 13) requests and not its first 24."""
    config, mix = load_config(CONFIG), load_mix("q-flight")
    (group,) = mix["groups"]
    n = math.lcm(24, len(group["rotation"]))
    clients = traffic.clients(mix, config, config["shards"], 3, "window")
    got = collections.Counter(c.next()[0] for c in clients for _ in range(n))
    assert got == {t: n * group["clients"] // 13 for t in ORDER}


def filter_then_count(cols: dict, sem: dict):
    """One request answered from the columns, nothing kept between
    requests: the terms pick columns, numpy counts them by group."""
    keep = np.ones(len(cols["d_year"]), bool)
    for f, spec in sem["filter"]:
        (op, v), = spec.items() if isinstance(spec, dict) else [("in", [spec])]
        keep &= (np.isin(cols[f], v) if op == "in" else cols[f] < v
                 if op == "lt" else (cols[f] >= v[0]) & (cols[f] <= v[1]))
    if sem["kind"] == "sum":
        return {"value": int(cols[sem["sum"]][keep].sum(dtype=np.int64)),
                "count": int(keep.sum())}
    groups: dict = {}
    for d in sem["dims"]:
        rows = np.unique(cols[d["field"]])
        rows = rows[rows > d.get("previous", -1)][:d.get("limit")]
        keep &= np.isin(cols[d["field"]], rows)
    at = np.nonzero(keep)[0]
    key = zip(*(cols[d["field"]][at].tolist() for d in sem["dims"]))
    for rows, value in zip(key, cols[sem["sum"]][at].tolist()):
        g = groups.setdefault(rows, [0, 0])
        g[0] += 1
        g[1] += value
    return [{"group": [{"field": d["field"], "rowID": r}
                       for d, r in zip(sem["dims"], rows)],
             "count": c, "sum": total}
            for rows, (c, total) in sorted(groups.items())]


@pytest.mark.parametrize("seed", SEEDS)
def test_the_reference_agrees_with_the_parents_and_with_the_columns(seed):
    """``test_bench_terms.py::test_old_and_new_reference_agree`` on this
    cell, leaving to the parent's reference the templates whose joint
    table it can hold (2^20 cells: Q2.1-Q2.3 and Q3.1; the others' terms
    it cannot say, or Q3.2's 273 M and Q4.3's 5.5e9 cells); all 13 against
    a filter-then-count of the columns."""
    from test_bench_terms import (PARENTS_TABLE_MAX, ParentReference,
                                  parents_table_cells, plain)
    config, mix = load_config(CONFIG), load_mix("q-flight")
    cols = datagen.make_columns(config, seed, 2,
                                traffic.fields_read(mix, config))
    old, new = ParentReference(config, cols), reference.Reference(config, cols)
    fits = {name for name, t in mix["templates"].items()
            if plain(t)
            and parents_table_cells(config, t) <= PARENTS_TABLE_MAX}
    assert fits == {"q2_1", "q2_2", "q2_3", "q3_1"}
    assert {name for name, t in mix["templates"].items() if plain(t)} \
        == fits | {"q3_2", "q4_3"}
    compared = collections.Counter()
    client = traffic.clients(mix, config, 2, seed, "agree")[0]
    for _ in range(2 * len(ORDER)):
        name, _pql, sem = client.next()
        answer = new.answer(sem)
        assert answer == filter_then_count(cols, sem), (name, sem)
        if name in fits:
            assert answer == old.answer(sem), (name, sem)
        compared[name] += 1
    assert compared == dict.fromkeys(ORDER, 2)


def test_the_hot_set_by_its_shapes_fits_the_budget():
    """Rows resident together, by the shapes (64 slots a row on the
    device: 58 shards padded; the executor's zero rows 40 -> 41, 8 -> 9,
    1 -> 3, 2 -> 3): 1,270 rows of 8 MiB = 9.92 GiB of the ~11.8 GiB a
    v5e's row cache is given. Q4.3's brands written whole would be one
    1,000-row matrix of 7.8 GiB beside them."""
    matrices = (2 * 250 + 5 * (41 + 9 + 3) + 3 * 25   # cities, brands, 25s
                + 7 + 6 + 3)                          # d_year, three shapes
    planes = 26 + 26 + 29 + 6 + 8   # revenue, profit, ext_disc, discount, qty
    leaves = 84 + 53 + 7 + 5 + 5 + 25 + 25 + 5 + 2 + 2 * 54
    row = 64 * (datagen.SHARD_WIDTH // 8)
    assert (matrices, planes, leaves) == (856, 95, 319)
    assert (matrices + planes + leaves) * row == 10_653_532_160
    assert (matrices + planes + leaves + 1001) * row > 12_682_002_048


# ------------------------------------------------- the eight metric files


@pytest.mark.parametrize("name", list(NEW))
def test_metric_entry_lists_the_flights_cell_alone(name):
    entry = {m["name"]: m for m in MANIFEST["per_layer"]}[name]
    unit, better, source, layer, moves = NEW[name]
    assert entry == {"name": name, "unit": unit, "better": better,
                     "source": source, "layer": layer, "moves": moves,
                     "workloads": [CELL]}
    spec = spec_of(name)
    assert spec["reader"] == ("trace_ops" if source == "device_trace"
                              else "ratio") and spec["what"]
    # nothing to read (no scrape, no trace): nothing returned, none raised
    assert readers.read(BENCH, name, {}, {}, None, {}) is None


def test_the_eight_follow_what_the_benchmark_had_and_its_lists_stand():
    names = [m["name"] for m in MANIFEST["per_layer"]]
    assert [n for n in names if n in NEW] == list(NEW)
    assert names.index("mesh_residency_misses_per_read") < names.index(
        "flight_pruned_groupby_share")
    for m in MANIFEST["per_layer"]:
        if m["name"] not in NEW:
            assert CELL not in m.get("workloads", [])
    # a layer the benchmark names already, letter for letter
    layers = {m["layer"] for m in MANIFEST["per_layer"] if m["name"] not in NEW}
    assert {NEW[n][3] for n in NEW} <= layers


def test_twins_read_what_their_namesakes_read():
    for twin, shipped in TWINS.items():
        if shipped is None:
            continue
        a, b = spec_of(twin), spec_of(shipped)
        assert {k: v for k, v in a.items() if k != "what"} == {
            k: v for k, v in b.items() if k != "what"}
    ms, cpu = spec_of("flight_prune_level_ms"), spec_of(
        "flight_prune_level_cpu_ms")
    assert (ms["numerator"], ms["denominator"], ms["scale"]) == (
        [PRUNE("seconds_total")], [PRUNE("total")], 1000)
    assert (cpu["numerator"], cpu["denominator"], cpu["scale"]) == (
        [PRUNE("cpu_seconds_total")], [PRUNE("cpu_entries_total")], 1000)


def test_ratio_metrics_read_a_made_up_scrape():
    """Ten shipped rotations: 13 + 11 requests each, 18 of them GroupBys,
    12 pruned at three levels; 3 + 3 non-final levels of 40 ms wall."""
    before = {G("results_total"): 100.0, G("pruned_total"): 50.0,
              G("levels_total"): 200.0, G("level_programs_total"): 260.0,
              G("level_candidates_total"): 5_000.0,
              G("paged_programs_total"): 30.0,
              PRUNE("total"): 100.0, PRUNE("seconds_total"): 4.0,
              PRUNE("cpu_entries_total"): 10.0,
              PRUNE("cpu_seconds_total"): 0.01}
    after = {G("results_total"): 280.0, G("pruned_total"): 170.0,
             G("levels_total"): 620.0, G("level_programs_total"): 860.0,
             G("level_candidates_total"): 173_000.0,
             G("paged_programs_total"): 360.0,
             PRUNE("total"): 340.0, PRUNE("seconds_total"): 13.6,
             PRUNE("cpu_entries_total"): 70.0,
             PRUNE("cpu_seconds_total"): 0.07}

    def read(name, b=before, a=after):
        return readers.read(BENCH, name, b, a, None, {})

    assert read("flight_pruned_groupby_share") == pytest.approx(100 * 12 / 18)
    assert read("flight_levels_per_groupby") == pytest.approx(42 / 18)
    assert read("flight_prune_level_ms") == pytest.approx(40.0)
    assert read("flight_prune_level_cpu_ms") == pytest.approx(1.0)
    assert read("flight_paged_program_share") == pytest.approx(55.0)
    assert read("flight_level_programs_per_level") == pytest.approx(600 / 420)
    assert read("flight_candidates_per_level") == pytest.approx(400.0)
    # the parent: no pruned or paged counter, no prune_level stage. The
    # four that read them are left out, the twins of what it has are not
    new = (G("pruned_total"), G("paged_programs_total"), PRUNE("total"),
           PRUNE("seconds_total"), PRUNE("cpu_entries_total"),
           PRUNE("cpu_seconds_total"))
    old_b = {k: v for k, v in before.items() if k not in new}
    old_a = {k: v for k, v in after.items() if k not in new}
    for name in ("flight_pruned_groupby_share", "flight_prune_level_ms",
                 "flight_prune_level_cpu_ms", "flight_paged_program_share"):
        assert read(name, old_b, old_a) is None, name
    assert read("flight_levels_per_groupby", old_b, old_a) == pytest.approx(
        42 / 18)
    assert read("flight_candidates_per_level", old_b, old_a) == 400.0
    # an untraced window measures no CPU entry: the twin is left out
    idle = dict(after, **{PRUNE("cpu_entries_total"): 10.0,
                          PRUNE("cpu_seconds_total"): 0.01})
    assert read("flight_prune_level_cpu_ms", before, idle) is None
    # a window without a GroupBy: no denominator, left out
    for name in NEW:
        if NEW[name][2] != "device_trace":
            assert read(name, after, after) is None, name


def test_the_program_exports_the_series_the_ratios_name():
    sys.path.insert(0, ROOT)
    from pilosa_tpu.utils.tracing import STAGES, groupby_metrics

    assert "executor.prune_level" in STAGES
    exported = {G(k) for k in groupby_metrics()}
    exported |= {f"pilosa_tpu_stage_{s.replace('.', '_')}{suffix}"
                 for s in STAGES
                 for suffix in ("_total", "_seconds_total",
                                "_cpu_seconds_total", "_cpu_entries_total")}
    for name in NEW:
        spec = spec_of(name)
        if spec["reader"] == "ratio":
            assert set(spec["numerator"] + spec["denominator"]) <= exported


def test_flight_groupby_level_share_finds_dense_and_pruned_levels(tmp_path):
    """The level kernel as the chip's trace names it in this cell: Q2.1's
    dense 256 candidates x 26 quantities, Q3.2's count-only second level
    (2,500 candidates padded to 4,096), beside Q1.1's Sum fusion."""
    dense = ("%groupby_level.1 = s32[6656,128]{1,0:T(8,128)} custom-call("
             "u32[64,41,32768]{2,0,1} %p), custom_call_target=\"tpu_custom_call\"")
    pruned = ("%groupby_level.1 = s32[4096,128]{1,0:T(8,128)} custom-call("
              "u32[64,250,32768]{2,0,1} %q)")
    fusion = "%convert_reduce_fusion = s32[64,27]{1,0} fusion(u32[64,29,32768] %r)"
    path = tmp_path / "flights.xplane.pb"
    path.write_bytes(xspace([("/device:TPU:0", [("XLA Ops", [
        (dense, 0, 11 * MS), (fusion, 12 * MS, 1 * MS),
        (pruned, 20 * MS, 14 * MS)])])]))
    reduced = trace.reduce(str(path), 0.05)
    assert spec_of("flight_groupby_level_share")["pattern"] == "groupby_level"
    assert readers.read(BENCH, "flight_groupby_level_share", {}, {}, reduced,
                        {}) == pytest.approx(50.0)
    assert readers.read(BENCH, "device_idle_share", {}, {}, reduced,
                        {}) == pytest.approx(48.0)


# ------------------------------------------------------- the cell rehearsed


@pytest.fixture(scope="module")
def both(tmp_path_factory):
    """The cell's two rehearsals, which every file of this directory
    shares (``bench_helpers.rehearsals``)."""
    return rehearsals(tmp_path_factory, [(CELL, 0), (CELL, 1)])


def test_rehearsal_is_correct_on_the_thirteen_queries_and_its_control_is_not(
        both):
    p = both[CELL, 0]
    assert p.returncode == 0, p.stderr[-3000:] + p.stdout[-2000:]
    line = last_line(p.stdout)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) == {"throughput", "read_p50_ms",
                                    "read_p95_ms", "setup_s"}
    checks = [l for l in p.stdout.splitlines()
              if l.startswith("check answers.")]
    assert [l.split()[1] for l in checks] == [f"answers.{t}:" for t in ORDER]
    assert all(" wrong=0 limit=0" in l for l in checks)
    # a Sum or a group's count over half the shards, doubled, is neither
    assert "control[sampled]: correct=False" in p.stdout
    wrong = [l for l in p.stdout.splitlines()
             if l.startswith("control[sampled] ") and " wrong=0 " not in l]
    assert len(wrong) >= 10


def test_traced_rehearsal_prints_the_plan_metrics_of_the_flights(both):
    p = both[CELL, 1]
    assert p.returncode == 0, p.stderr[-3000:] + p.stdout[-2000:]
    line = last_line(p.stdout)
    assert line["correct"] is True and line["failed"] == 0
    metrics = line["metrics"]
    listed = {m["name"] for m in MANIFEST["per_layer"]
              if CELL in m.get("workloads", [CELL])}
    assert set(metrics) <= listed
    # the counters every GroupBy moves are read at one shard too; the
    # prune stage's twins are left out where no level was pruned
    for name in ("flight_pruned_groupby_share", "flight_levels_per_groupby",
                 "flight_level_programs_per_level",
                 "flight_candidates_per_level", "flight_paged_program_share"):
        assert metrics[name]["unit"] == NEW[name][0], name
        assert metrics[name]["value"] >= 0, name
    assert metrics["flight_levels_per_groupby"]["value"] >= 1.0
    assert metrics["residency_evictions_in_window"]["value"] == 0.0
