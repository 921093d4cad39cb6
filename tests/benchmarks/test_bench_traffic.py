"""The traffic generator: the seed draws constants, never work."""

import collections
import math

import pytest

from bench_helpers import (CELLS, TOY_CELL, config_and_mix, load_config,
                           load_mix)
from harness import traffic

SEEDS = (3, 2_147_483_659, 4_111_222_333)

# the cells of BENCHMARK.json, and the tests' toy (bench_helpers.py)
PAIRS = list(CELLS.values()) + [TOY_CELL]


def stream(cell, seed, n=240, stream="window"):
    config, mix = config_and_mix(cell)
    cl = traffic.clients(mix, config, config["shards"], seed, stream)
    return [[c.next() for _ in range(n // len(cl) + 1)] for c in cl]


def shape(sem: dict):
    """What decides a request's cost: kind, fields, dimension sizes."""
    return (sem["kind"],
            tuple(f for f, _ in sem.get("filter", ()))
            if sem["kind"] != "count" else len(sem.get("filter", ())),
            tuple((d["field"], d.get("limit")) for d in sem.get("dims", ())),
            sem.get("sum"), sem.get("field") if sem["kind"] == "topn" else None)


@pytest.mark.parametrize("cell", PAIRS, ids=lambda c: c["name"])
def test_same_seed_same_requests(cell):
    a, b = stream(cell, SEEDS[1]), stream(cell, SEEDS[1])
    assert [[r[1] for r in c] for c in a] == [[r[1] for r in c] for c in b]


@pytest.mark.parametrize("cell", PAIRS, ids=lambda c: c["name"])
def test_other_seed_other_constants_same_shapes_and_counts(cell):
    runs = [stream(cell, s) for s in SEEDS]
    texts = [[r[1] for c in run for r in c] for run in runs]
    assert texts[0] != texts[1] != texts[2]
    for run in runs[1:]:
        for c0, c1 in zip(runs[0], run):
            # client by client, request by request: the same template and
            # the same shape of work, whatever the seed drew
            assert [r[0] for r in c0] == [r[0] for r in c1]
            assert [shape(r[2]) for r in c0] == [shape(r[2]) for r in c1]


@pytest.mark.parametrize("cell", PAIRS, ids=lambda c: c["name"])
def test_rotation_keeps_templates_in_fixed_proportions(cell):
    mix = config_and_mix(cell)[1]
    # whole rotations of every group: a rotation's length need not divide
    # 24 (the 13 SSB queries do not)
    n = math.lcm(24, *(len(g["rotation"]) for g in mix["groups"]))
    run = stream(cell, SEEDS[0], n=n * sum(g["clients"] for g in mix["groups"]))
    got = collections.Counter(r[0] for c in run for r in c[:n])
    want = collections.Counter()
    for g in mix["groups"]:
        for t in g["rotation"]:
            want[t] += n * g["clients"] // len(g["rotation"])
    assert got == want


@pytest.mark.parametrize("cell", PAIRS, ids=lambda c: c["name"])
def test_group_spaces_do_not_depend_on_the_seed(cell):
    """A GroupBy's dimensions and page sizes, a TopN's field, the number
    of Rows a filter intersects: fixed by the template alone."""
    mix = config_and_mix(cell)[1]
    per_seed = []
    for s in SEEDS:
        spaces = collections.defaultdict(set)
        for c in stream(cell, s):
            for name, _, sem in c:
                spaces[name].add(shape(sem) if sem["kind"] != "count" else
                                 ("count", len(sem["filter"])))
        per_seed.append(dict(spaces))
    assert per_seed[0] == per_seed[1] == per_seed[2]
    for name, t in mix["templates"].items():
        if t["kind"] == "groupby":
            assert len(per_seed[0][name]) == 1


def test_constants_stay_inside_their_domains():
    cell, config = TOY_CELL, config_and_mix(TOY_CELL)[0]
    from harness.datagen import field_rows
    seen = set()
    for c in stream(cell, SEEDS[2]):
        for name, _, sem in c:
            for f, spec in sem.get("filter", ()):
                field = config["fields"][f]
                (op, v), = (spec.items() if isinstance(spec, dict)
                            else [("in", [spec])])
                seen.add((name, f, op))
                if field["type"] == "set":
                    assert op == "in" and v
                    assert all(0 <= r < field_rows(field) for r in v)
                else:
                    lo, hi = field["uniform_int"]
                    ends = v if op == "between" else [v]
                    assert all(lo <= x <= hi for x in ends)
                    assert ends == sorted(ends)
            for d in sem.get("dims", ()):
                if d.get("previous") is not None:
                    n = field_rows(config["fields"][d["field"]])
                    assert -1 <= d["previous"] <= n - d["limit"] - 1
    # the toy's terms of every kind were met
    assert {("discounted_revenue", "discount", "between"),
            ("discounted_revenue", "quantity", "lt"),
            ("city_pair", "city", "in"), ("city_pair", "region", "in"),
            ("nation_by_year", "brand", "in")} <= seen


def test_a_big_seed_is_taken():
    cell = next(iter(CELLS.values()))
    assert stream(cell, 2**31 + 12345)


def test_render_is_the_pql_the_issue_names():
    assert traffic.render({"kind": "count", "filter": [("a", 1), ("b", 2)]}) \
        == "Count(Intersect(Row(a=1), Row(b=2)))"
    assert traffic.render({"kind": "topn", "field": "cab_type",
                           "filter": [("pickup_year", 3)]}) \
        == "TopN(cab_type, Row(pickup_year=3))"
    assert traffic.render({
        "kind": "groupby", "sum": "lo_revenue",
        "dims": [{"field": "d_year"},
                 {"field": "p_brand1", "previous": 39, "limit": 40}],
        "filter": [("p_category", 1), ("s_region", 2)]}) == (
        "GroupBy(Rows(d_year), Rows(p_brand1, previous=39, limit=40), "
        "filter=Intersect(Row(p_category=1), Row(s_region=2)), "
        "aggregate=Sum(field=\"lo_revenue\"))")
    assert traffic.render({"kind": "set", "column": 9, "field": "f",
                           "row": 2}) == "Set(9, f=2)"


def test_render_writes_the_new_terms_in_the_parsers_own_forms():
    """ISSUE 41: a Union inside the Intersect, the BSI comparisons and the
    between form of ``pilosa_tpu/pql/parser.py``; a one-value ``in`` is the
    plain Row, a lone term stands without an Intersect."""
    assert traffic.render({"kind": "sum", "sum": "lo_ext_disc", "filter": [
        ("d_year", 1), ("lo_discount", {"between": [1, 3]}),
        ("lo_quantity", {"lt": 25})]}) == (
        "Sum(Intersect(Row(d_year=1), Row(lo_discount >< [1, 3]), "
        "Row(lo_quantity < 25)), field=\"lo_ext_disc\")")
    assert traffic.render({"kind": "count", "filter": [
        ("c_city", {"in": [221, 225]}), ("s_city", {"in": [221]})]}) == (
        "Count(Intersect(Union(Row(c_city=221), Row(c_city=225)), "
        "Row(s_city=221)))")
    assert traffic.render({"kind": "count", "filter": [
        ("p_mfgr", {"in": [0, 1]})]}) == (
        "Count(Union(Row(p_mfgr=0), Row(p_mfgr=1)))")
    assert traffic.render({"kind": "count", "filter": [("q", {"lt": 7})]}) \
        == "Count(Row(q < 7))"
    # SSB needs "lt" and "between" alone; "gte" and the like are not said
    for bad in ({"near": 3}, {"gte": 3}, {"in": []}):
        with pytest.raises(ValueError):
            traffic.render({"kind": "count", "filter": [("q", bad)]})


def test_an_int_draw_is_uniform_over_both_ends_and_moves_a_window():
    config, mix = config_and_mix(TOY_CELL)
    only = dict(mix["groups"][0], rotation=["discounted_revenue"])
    client = traffic.Client(mix, config, 2, only, 0, SEEDS[1], "w")
    windows = collections.Counter()
    for _ in range(600):
        _, pql, sem = client.next()
        lo, hi = dict(sem["filter"])["discount"]["between"]
        assert hi - lo == 2 and f"Row(discount >< [{lo}, {hi}])" in pql
        assert "Row(quantity < 25)" in pql
        windows[lo] += 1
    assert sorted(windows) == [1, 2, 3, 4, 5]  # windows of one width
    assert min(windows.values()) > 80


def test_fields_read_and_preload_rows_learn_the_new_terms():
    """An int field named only in a range term is materialised; the rows
    an ``in`` names are preloaded, drawn or written out."""
    config, mix = config_and_mix(TOY_CELL)
    assert {"quantity", "discount", "city", "nation"} <= set(
        traffic.fields_read(mix, config))
    only = {"name": "m", "groups": [], "templates": {"t": {
        "kind": "count",
        "filter": [["quantity", {"lt": 26}], ["region", {"in": [1, "R"]}],
                   ["category", {"in": [0, 9]}]],
        "draw": {"R": {"row_of": "region", "top": 2}}}}}
    assert traffic.fields_read(only, config) == ["category", "region",
                                                 "quantity"]
    rows = traffic.preload_rows(only, config)
    assert ("category", 0) in rows and ("category", 9) in rows
    assert ("region", 1) in rows
    assert {r for f, r in rows if f == "region"} >= {1} and all(
        f in ("category", "region") for f, _ in rows)


def test_fields_read_are_only_what_the_mix_touches():
    config = load_config("taxi-rides")
    dash = traffic.fields_read(load_mix("dashboard"), config)
    assert "pickup_day" not in dash and "pickup_hour" not in dash
    assert {"cab_type", "passenger_count", "pickup_year", "pickup_month",
            "dist_miles", "total_amount_cents"} == set(dash)
