"""Star Schema Benchmark query flight 2 (ISSUE 32) below the harness: a
toy ``lineorder`` (3 shards, so one zero slot of 4; 1000 brands in 25
categories, 5 regions, 7 years, a 24-bit revenue) through ``API.query``,
on the local executor and on the mesh executor over virtual CPU devices.
Every answer is compared with a plain numpy group-by written here from
the columns: nothing of the benchmark's harness, nothing of the program.

What the shapes make the program do that no other tier-1 test does
together: ``Rows(previous=, limit=)`` dimensions over a 1000-row field
whose rows are array containers, a Sum of 26 planes, a level of 280
candidates that takes two programs and a ``concat``, a ShardBlock with a
zero slot. The two counters ISSUE 32 adds (``groupby_level_candidates_
total``, ``groupby_range_dims_total``) are read around every query.
"""

import numpy as np
import pytest

from pilosa_tpu.executor import batch
from pilosa_tpu.parallel import DistExecutor, make_mesh
from pilosa_tpu.server.api import API
from pilosa_tpu.shardwidth import SHARD_WIDTH
from pilosa_tpu.storage import Holder
from pilosa_tpu.utils.tracing import groupby_metrics

INDEX = "lineorder"
N_SHARDS = 3
PER_SHARD = 30_000
BRANDS, PER_CATEGORY, REGIONS, YEARS = 1000, 40, 5, 7
REVENUE = (90_000, 10_494_950)   # 24 bits above min: 26 planes
QUANTITY = (1, 50)               # 6 bits: 8 planes, 280 candidates fit one
MISSING = (140, 1)               # brand 140 holds no row in shard 1


@pytest.fixture(scope="module")
def columns():
    """One order line a column, drawn as dbgen draws them: every column
    independent and uniform. Brand 140 is moved out of shard 1."""
    rng = np.random.default_rng(32)
    col = np.concatenate([
        np.sort(rng.choice(SHARD_WIDTH, PER_SHARD, replace=False))
        + shard * SHARD_WIDTH for shard in range(N_SHARDS)])
    n = col.size
    brand = rng.integers(0, BRANDS, n)
    hole = (brand == MISSING[0]) & (col // SHARD_WIDTH == MISSING[1])
    assert hole.any()
    brand[hole] = MISSING[0] + 1
    return {
        "column": col,
        "p_brand1": brand,
        "p_category": brand // PER_CATEGORY,
        "s_region": rng.integers(0, REGIONS, n),
        "d_year": rng.integers(0, YEARS, n),
        "lo_revenue": rng.integers(REVENUE[0], REVENUE[1] + 1, n),
        "lo_quantity": rng.integers(QUANTITY[0], QUANTITY[1] + 1, n),
    }


@pytest.fixture(scope="module")
def apis(tmp_path_factory, columns):
    holder = Holder(str(tmp_path_factory.mktemp("ssb") / "data")).open()
    api = API(holder)
    api.create_index(INDEX)
    for name in ("p_brand1", "p_category", "s_region", "d_year"):
        api.create_field(INDEX, name)
        api.import_bits(INDEX, name, columns[name], columns["column"])
    for name, (lo, hi) in (("lo_revenue", REVENUE), ("lo_quantity", QUANTITY)):
        api.create_field(INDEX, name, {"type": "int", "min": lo, "max": hi})
        api.import_values(INDEX, name, columns["column"], columns[name])
    mesh_api = API(holder)
    mesh_api.executor = DistExecutor(holder, make_mesh(n_devices=4))
    assert holder.index(INDEX).field("lo_revenue").options.bit_depth == 24
    yield {"local": api, "mesh": mesh_api}
    holder.close()


def numpy_groupby(columns, previous, limit, terms, sum_field):
    """GroupBy(Rows(d_year), Rows(p_brand1, previous=, limit=), filter=,
    aggregate=Sum) from the columns: a dimension's rows are the field's
    non-empty rows after ``previous``, at most ``limit`` of them."""
    brands = np.unique(columns["p_brand1"])
    brands = brands[brands > previous][:limit]
    keep = np.isin(columns["p_brand1"], brands)
    for field, row in terms:
        keep &= columns[field] == row
    key = columns["d_year"][keep] * BRANDS + columns["p_brand1"][keep]
    counts = np.bincount(key, minlength=YEARS * BRANDS)
    sums = np.bincount(key, weights=columns[sum_field][keep],
                       minlength=YEARS * BRANDS)  # < 2^53: exact
    out = []
    for year in range(YEARS):
        for b in brands.tolist():
            n = int(counts[year * BRANDS + b])
            if n:
                out.append({"group": [{"field": "d_year", "rowID": year},
                                      {"field": "p_brand1", "rowID": b}],
                            "count": n,
                            "sum": int(sums[year * BRANDS + b])})
    return out, len(brands)


def pql(previous, limit, terms, sum_field):
    rows = [f"Row({f}={r})" for f, r in terms]
    filt = rows[0] if len(rows) == 1 else f"Intersect({', '.join(rows)})"
    return (f"GroupBy(Rows(d_year), Rows(p_brand1, previous={previous}, "
            f"limit={limit}), filter={filt}, "
            f"aggregate=Sum(field=\"{sum_field}\"))")


def q2_1(category, region):
    """SSB Q2.1: a category's 40 brands in one region, by year."""
    return (PER_CATEGORY * category - 1, 40,
            [("p_category", category), ("s_region", region)])


def q2_2(category, region):
    """SSB Q2.2: brands MFGR#cc21 to MFGR#cc28."""
    return (PER_CATEGORY * category + 19, 8, [("s_region", region)])


def q2_3(category, region):
    """SSB Q2.3: brand MFGR#cc39."""
    return (PER_CATEGORY * category + 37, 1, [("s_region", region)])


# name: ((previous, limit, filter terms), Sum field, brands the range
# holds, programs its one level takes)
CASES = {
    "q2_1-first-category-previous-minus-1": (q2_1(0, 3), "lo_revenue", 40, 2),
    "q2_1-last-category": (q2_1(24, 0), "lo_revenue", 40, 2),
    "q2_2-eight-brands": (q2_2(12, 4), "lo_revenue", 8, 1),
    "q2_3-one-brand": (q2_3(7, 2), "lo_revenue", 1, 1),
    "range-runs-past-the-last-brand": ((995, 8, [("s_region", 1)]),
                                       "lo_revenue", 4, 1),
    "brand-with-no-row-in-one-shard": (q2_2(3, 0), "lo_revenue", 8, 1),
    # the same 280 candidates under a Sum of 8 planes fit one program
    "q2_1-shallow-sum-one-program": (q2_1(0, 3), "lo_quantity", 40, 1),
}


def run(api, case):
    (previous, limit, terms), sum_field, n_brands, programs = CASES[case]
    before = groupby_metrics()
    (got,) = api.query(INDEX, pql(previous, limit, terms, sum_field))["results"]
    after = groupby_metrics()
    delta = {k: after[k] - before[k] for k in after}
    return got, delta, n_brands, programs


@pytest.mark.parametrize("case", list(CASES))
@pytest.mark.parametrize("builder", ["local", "mesh"])
def test_flight_2_answers_are_the_columns(apis, columns, builder, case):
    (previous, limit, terms), sum_field, _, _ = CASES[case]
    got, delta, n_brands, programs = run(apis[builder], case)
    want, rows = numpy_groupby(columns, previous, limit, terms, sum_field)
    assert rows == n_brands
    assert got == want                  # counts and sums, integers exactly
    assert len(got) > n_brands          # most (year, brand) pairs hold a row
    # one dense level of years x brands candidates, one narrowed dimension
    assert delta["levels_total"] == 1
    assert delta["level_candidates_total"] == YEARS * n_brands
    assert delta["level_programs_total"] == programs
    assert delta["range_dims_total"] == 1
    assert delta["results_total"] == 1


def test_the_chunk_bound_is_what_splits_280_candidates():
    depth = REVENUE[1] - REVENUE[0]
    assert depth.bit_length() == 24
    assert batch.groupby_chunk_groups(2 + 24) == 256 < YEARS * 40
    assert batch.groupby_chunk_groups(2 + 6) >= YEARS * 40


@pytest.mark.parametrize("builder", ["local", "mesh"])
def test_two_programs_answer_as_one_program_does(apis, builder):
    """The level of 280 candidates in two programs and a concat, and the
    same level under a Sum shallow enough for one program, name the same
    groups with the same counts."""
    deep, d_deep, _, _ = run(apis[builder],
                             "q2_1-first-category-previous-minus-1")
    shallow, d_shallow, _, _ = run(apis[builder],
                                   "q2_1-shallow-sum-one-program")
    assert (d_deep["level_programs_total"],
            d_shallow["level_programs_total"]) == (2, 1)
    strip = lambda groups: [(g["group"], g["count"]) for g in groups]
    assert strip(deep) == strip(shallow)
    # candidates are year-major: the second program's are year 6, brands 16-39
    tail = [g for g in deep if g["group"][0]["rowID"] == YEARS - 1
            and g["group"][1]["rowID"] >= 256 - (YEARS - 1) * 40]
    assert len(tail) > 12


def test_the_missing_brand_is_missing_from_one_shard_only(apis, columns):
    """The hole is in the data the executors read, not only in the
    oracle: shard 1 holds no bit of brand 140, the other shards do."""
    brand, shard = MISSING
    per_shard = [
        apis["local"].query(INDEX, f"Count(Row(p_brand1={brand}))",
                            shards=[s])["results"][0]
        for s in range(N_SHARDS)]
    at = columns["column"][columns["p_brand1"] == brand] // SHARD_WIDTH
    assert per_shard == np.bincount(at, minlength=N_SHARDS).tolist()
    assert per_shard[shard] == 0 and all(
        n > 0 for s, n in enumerate(per_shard) if s != shard)


@pytest.mark.parametrize("builder", ["local", "mesh"])
def test_a_plain_dimension_moves_no_range_counter(apis, builder):
    before = groupby_metrics()
    (got,) = apis[builder].query(
        INDEX, "GroupBy(Rows(d_year), Rows(s_region))")["results"]
    after = groupby_metrics()
    assert len(got) == YEARS * REGIONS
    assert after["range_dims_total"] == before["range_dims_total"]
    assert (after["level_candidates_total"]
            - before["level_candidates_total"]) == YEARS * REGIONS


def test_both_counters_are_on_metrics_and_debug_vars(tmp_path):
    """From scrape one, zeros included, beside the block's other series."""
    from cluster_helpers import req, uri
    from pilosa_tpu.server import Server, ServerConfig

    s = Server(ServerConfig(
        data_dir=str(tmp_path / "node"), port=0, name="t",
        anti_entropy_interval=0, heartbeat_interval=0,
    )).open()
    try:
        text = req("GET", f"{uri(s)}/metrics", raw=True)
        text = text.decode() if isinstance(text, bytes) else text
        now = groupby_metrics()
        for name in ("level_candidates_total", "range_dims_total"):
            assert f"pilosa_tpu_groupby_{name} {now[name]}\n" in text
            assert f"# TYPE pilosa_tpu_groupby_{name} " in text
        assert req("GET", f"{uri(s)}/debug/vars")["groupby"] == now
    finally:
        s.close()
