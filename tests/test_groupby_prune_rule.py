"""A GroupBy is counted in one dense level or by prefix pruning according
to the work the dense level would be (ISSUE 42): dense while the cross
product is at most ``GROUPBY_DENSE_MAX_PROGRAMS`` level programs of
``batch.groupby_chunk_groups`` candidates (and, as before, at most
``GROUPBY_DENSE_MAX_GROUPS`` groups), and always with one dimension (no
prefix to prune). Either path gives the same ``GroupCounts``, group
for group; ``groupby_pruned_total`` and ``groupby_paged_programs_total``
say which path and which kind of program ran, and the stage
``executor.prune_level`` times a pruned GroupBy's non-final round trips.
Since ISSUE 44 a pruned GroupBy starts with a marginal round (every
dimension counted alone under the filter: a level a dimension, ONE
round trip), so the level, program and ``prune_levels`` counts pinned
here are that plan's; ``tests/test_groupby_marginal_prune.py`` holds the
round itself. Every answer here is compared with a plain numpy group-by
of the columns.
"""

import sys

import numpy as np
import pytest

from pilosa_tpu.executor import Executor, batch
from pilosa_tpu.executor import executor as ex_mod
from pilosa_tpu.executor.result import GroupCounts, result_json_bytes
from pilosa_tpu.parallel import DistExecutor, dist, make_mesh
from pilosa_tpu.shardwidth import SHARD_WIDTH
from pilosa_tpu.storage import FieldOptions, Holder
from pilosa_tpu.utils.tracing import groupby_metrics, stage_metrics

N_SHARDS = 3
ROWS = {"f": 5, "g": 4, "h": 9}
VALUES = (0, 63)          # a 6-bit Sum: 8 quantities a candidate
BUILDERS = ["local", "mesh"]
PATHS = {"dense": 10 ** 9, "pruned": 0}


@pytest.fixture(scope="module")
def data(tmp_path_factory):
    holder = Holder(str(tmp_path_factory.mktemp("prune") / "data")).open()
    rng = np.random.default_rng(42)
    idx = holder.create_index("i")
    fields = {name: idx.create_field(name) for name in ROWS}
    v = idx.create_field("v", FieldOptions(type="int", min=VALUES[0],
                                           max=VALUES[1]))
    col = np.concatenate([
        np.sort(rng.choice(SHARD_WIDTH, 50, replace=False))
        + shard * SHARD_WIDTH for shard in range(N_SHARDS)])
    columns = {"column": col,
               "v": rng.integers(VALUES[0], VALUES[1] + 1, col.size)}
    for name, n in ROWS.items():
        columns[name] = rng.integers(0, n, col.size)
    for i, c in enumerate(col.tolist()):
        for name, field in fields.items():
            field.set_bit(int(columns[name][i]), c)
        v.set_value(c, int(columns["v"][i]))
    idx.mark_columns_exist(col.tolist())
    yield holder, columns
    holder.close()


def executor(holder, builder):
    if builder == "local":
        return Executor(holder)
    return DistExecutor(holder, make_mesh(n_devices=4))


def numpy_groupby(columns, dims, keep=None, with_sum=False):
    """[(rows, count, sum or None)] in the order a GroupBy answers."""
    keep = np.ones(columns["column"].size, bool) if keep is None else keep
    out: dict = {}
    for i in np.flatnonzero(keep):
        key = tuple(int(columns[d][i]) for d in dims)
        n, total = out.get(key, (0, 0))
        out[key] = (n + 1, total + int(columns["v"][i]))
    return [(k, n, total if with_sum else None)
            for k, (n, total) in sorted(out.items())]


def answer(ex, pql):
    (groups,) = ex.execute("i", pql)
    assert isinstance(groups, GroupCounts)
    return groups, [(tuple(fr["rowID"] for fr in gc.group), gc.count, gc.sum)
                    for gc in groups]


class Around:
    """Deltas of the GroupBy counters and the pruned levels' stage, each
    under its attribute's name."""

    FIELDS = {"pruned": "pruned_total", "paged": "paged_programs_total",
              "levels": "levels_total", "programs": "level_programs_total",
              "results": "results_total"}

    def read(self):
        g = groupby_metrics()
        return {**{attr: g[name] for attr, name in self.FIELDS.items()},
                "prune_levels": stage_metrics()["executor_prune_level_total"]}

    def __enter__(self):
        self.before = self.read()
        return self

    def __exit__(self, *exc):
        for attr, after in self.read().items():
            setattr(self, attr, after - self.before[attr])


# ---------------------------------------------------------------- the rule

# (dimension sizes, n_planes, dense?) at the kernel's own candidate
# bounds: 8,192 a program count-only, 256 with a 24-bit Sum (26 planes)
RULE = {
    "count-only-at-the-group-bound": ((64, 64), 0, True),
    "count-only-past-the-group-bound-in-one-program": ((64, 65), 0, False),
    "count-only-two-programs": ((128, 128), 0, False),
    "sum24-one-program": ((7, 36), 26, True),
    "sum24-two-programs-brand-lookup": ((7, 40), 26, True),
    "sum24-two-programs-exactly": ((2, 256), 26, True),
    "sum24-three-programs": ((3, 171), 26, False),
    "sum2-two-programs-past-the-group-bound": ((64, 65), 4, False),
    "q3_1-nations-by-year": ((25, 25, 6), 26, False),
    "q4_1-year-by-nation": ((7, 25), 26, True),
    "q4_2-year-nation-category": ((2, 25, 25), 26, False),
    "one-dimension-whatever-its-rows": ((100_000,), 26, True),
    "dashboard-hour-by-day": ((24, 7), 0, True),
    "groupby-scan-640": ((10, 8, 8), 0, True),
}


@pytest.mark.parametrize("case", list(RULE))
def test_dense_while_the_level_is_at_most_two_programs(case):
    sizes, n_planes, dense = RULE[case]
    assert batch.groupby_chunk_groups(0) == 8192
    assert batch.groupby_chunk_groups(26) == 256
    assert ex_mod.GROUPBY_DENSE_MAX_PROGRAMS == 2
    assert ex_mod.GROUPBY_DENSE_MAX_GROUPS == 4096
    assert ex_mod._groupby_dense(sizes, n_planes) is dense
    groups = int(np.prod(sizes))
    programs = -(-groups // batch.groupby_chunk_groups(n_planes))
    # past 4,096 groups nothing is dense that pruned before the rule
    assert dense == (len(sizes) == 1 or (programs <= 2 and groups <= 4096))


# the boundary through the executor, at a candidate bound small enough
# for the CPU: 16 a program count-only, 8 with the Sum. Each case:
# (PQL, reference, levels, programs, timed round trips). A pruned
# GroupBy's marginal round (ISSUE 44) is a level and a program a
# dimension and ONE round trip; nothing filters here, so every row
# survives and the two dimensions' final level follows at once
BOUNDARY = {
    # f x g = 20 candidates
    "count-only-two-programs-dense": ("GroupBy(Rows(f), Rows(g))",
                                      (("f", "g"), None, False), 1, 2, 0),
    # f x h = 45 candidates = three programs: marginals of 5 and of 9,
    # then 45 (before the round: a level of 5, then 45: 2 and 4)
    "count-only-three-programs-pruned": ("GroupBy(Rows(f), Rows(h))",
                                         (("f", "h"), None, False), 3, 5, 1),
    # g x f with a Sum = 20 candidates, three programs of 8: marginals
    # of 4 and of 5 (count-only), then 20 (before: 2 and 4)
    "sum-three-programs-pruned": (
        'GroupBy(Rows(g), Rows(f), aggregate=Sum(field="v"))',
        (("g", "f"), None, True), 3, 5, 1),
    # one dimension: dense whatever its programs (9 rows, two of 8)
    "sum-one-dimension-dense": (
        'GroupBy(Rows(h), aggregate=Sum(field="v"))',
        (("h",), None, True), 1, 2, 0),
}


@pytest.mark.parametrize("case", list(BOUNDARY))
def test_the_boundary_through_the_executor(data, case, monkeypatch):
    holder, columns = data
    pql, ref, levels, programs, round_trips = BOUNDARY[case]
    monkeypatch.setattr(batch, "groupby_chunk_groups",
                        lambda n_planes: 8 if n_planes else 16)
    ex = executor(holder, "local")
    with Around() as d:
        _, got = answer(ex, pql)
    assert got == numpy_groupby(columns, *ref) and got
    assert (d.levels, d.programs, d.results) == (levels, programs, 1)
    assert d.pruned == (levels > 1)
    assert d.prune_levels == round_trips


# ------------------------------------------------ either path, one answer

# query: (PQL, the numpy reference's arguments)
QUERIES = {
    "2dims-count": ("GroupBy(Rows(f), Rows(g))",
                    lambda c: (("f", "g"), None, False)),
    "2dims-sum": ('GroupBy(Rows(f), Rows(g), aggregate=Sum(field="v"))',
                  lambda c: (("f", "g"), None, True)),
    "3dims-sum-rows": (
        'GroupBy(Rows(f), Rows(g), Rows(h), filter=Intersect(Row(f=1), '
        'Row(g=2)), aggregate=Sum(field="v"))',
        lambda c: (("f", "g", "h"), (c["f"] == 1) & (c["g"] == 2), True)),
    "3dims-bsi-compare": (
        "GroupBy(Rows(f), Rows(g), Rows(h), filter=Row(v > 10))",
        lambda c: (("f", "g", "h"), c["v"] > 10, False)),
    "2dims-sum-bsi-between-and-union": (
        'GroupBy(Rows(h), Rows(f), filter=Intersect(Row(v >< [5, 40]), '
        'Union(Row(g=1), Row(g=3))), aggregate=Sum(field="v"))',
        lambda c: (("h", "f"), (c["v"] >= 5) & (c["v"] <= 40)
                   & np.isin(c["g"], (1, 3)), True)),
    "3dims-limit-previous": (
        'GroupBy(Rows(g, previous=0, limit=2), Rows(f), Rows(h), '
        'filter=Union(Row(g=1), Row(g=2)), aggregate=Sum(field="v"))',
        lambda c: (("g", "f", "h"), np.isin(c["g"], (1, 2)), True)),
}


def page_the_largest(dim_rows, other_rows, slots, words):
    """A tile plan that pages the dimension(s) of the most rows, if past
    eight (h's nine): what ``groupby_tile_plan`` does for a 250-row
    dimension beside a Sum's planes on the chip."""
    big = max(dim_rows)
    return min(words, 4096), tuple(n == big and big > 8 for n in dim_rows)


@pytest.mark.parametrize("paged", [False, True], ids=["resident", "paged"])
@pytest.mark.parametrize("query", list(QUERIES))
@pytest.mark.parametrize("builder", BUILDERS)
def test_either_path_answers_group_for_group(data, builder, query, paged,
                                             monkeypatch):
    holder, columns = data
    pql, ref = QUERIES[query]
    want = numpy_groupby(columns, *ref(columns))
    assert want
    if paged:
        # programs traced under the real plan are not the paged ones
        monkeypatch.setattr(batch, "groupby_tile_plan", page_the_largest)
        monkeypatch.setattr(batch, "_LOCAL_JIT_CACHE", {})
        monkeypatch.setattr(dist, "_DIST_JIT_CACHE", {})
    n_dims = pql.count("Rows(")
    answers = {}
    for path, bound in PATHS.items():
        monkeypatch.setattr(ex_mod, "GROUPBY_DENSE_MAX_PROGRAMS", bound)
        ex = executor(holder, builder)
        with Around() as d:
            groups, got = answer(ex, pql)
        assert got == want, path
        answers[path] = result_json_bytes(groups)
        pruned = path == "pruned"
        assert (d.results, d.pruned) == (1, pruned)
        # pruned (ISSUE 44): a marginal level a dimension, a joint level
        # from the second dimension to the one before the last (the
        # bound of 0 keeps the survivors' product from the final level
        # too), the final level; before the round it was one a dimension
        assert d.levels == d.programs == (2 * n_dims - 1 if pruned else 1)
        # the marginal round is one timed round trip, each joint level
        # another: as many as when dimension 0 had a level of its own
        assert d.prune_levels == (n_dims - 1 if pruned else 0)
        # a program pages where h is among its dimensions: every dense
        # one; pruned, h's own marginal and the one level that reaches
        # it (h is first of two dimensions or last of three)
        if not (paged and "Rows(h)" in pql):
            assert d.paged == 0
        elif pruned:
            assert d.paged == 2
        else:
            assert d.paged == d.programs
    assert answers["dense"] == answers["pruned"]


@pytest.mark.parametrize("builder", BUILDERS)
def test_the_counter_asks_the_plan_the_body_builds_its_kernel_by(
        data, builder, monkeypatch):
    """``groupby_paged_programs_total`` is counted where a program is
    dispatched, from the executor's view of the shapes (the whole block's
    slots over the mesh's size); the kernel is built inside the program,
    from one device's. Both ask ``batch.groupby_level_plan``: with every
    argument the same, on one device and with 3 shards on a mesh of 4,
    for every kind of filter and either path."""
    holder, _ = data
    asked = {"_groupby_level_enqueue": set(), "groupby_level_body": set()}
    real = batch.groupby_level_plan

    def spy(filt_structure, leaf_ndims, *shapes):
        by = sys._getframe(1).f_code.co_name
        asked[by].add((filt_structure, tuple(leaf_ndims)) + shapes)
        return real(filt_structure, leaf_ndims, *shapes)

    monkeypatch.setattr(batch, "groupby_level_plan", spy)
    # every program is traced anew, so that the body asks too
    monkeypatch.setattr(batch, "_LOCAL_JIT_CACHE", {})
    monkeypatch.setattr(dist, "_DIST_JIT_CACHE", {})
    for bound in PATHS.values():
        monkeypatch.setattr(ex_mod, "GROUPBY_DENSE_MAX_PROGRAMS", bound)
        ex = executor(holder, builder)
        for pql, _ in QUERIES.values():
            answer(ex, pql)
    dispatched, built = asked.values()
    assert dispatched and dispatched == built


@pytest.mark.parametrize("builder", BUILDERS)
def test_an_empty_level_ends_the_groupby(data, builder, monkeypatch):
    """Nothing survives the filter: the marginal round (three levels,
    one a dimension, ONE timed round trip; before ISSUE 44 the first
    dimension's level alone), an empty ``GroupCounts`` and no later
    level."""
    holder, _ = data
    monkeypatch.setattr(ex_mod, "GROUPBY_DENSE_MAX_PROGRAMS", 0)
    ex = executor(holder, builder)
    with Around() as d:
        groups, got = answer(
            ex, 'GroupBy(Rows(f), Rows(g), Rows(h), filter=Row(v > 63), '
                'aggregate=Sum(field="v"))')
    assert got == [] and len(groups) == 0
    assert (d.pruned, d.levels, d.programs, d.prune_levels,
            d.results) == (1, 3, 3, 1, 1)


def test_prefixes_can_survive_to_a_final_level_that_keeps_none(
        data, monkeypatch):
    """Prefixes survive until the final level, which keeps none: one row
    of f and one of g that each have members under the filter and none
    together. (Before ISSUE 44 the case was a g row with no member under
    the filter at all; the marginal round ends that one before any final
    level, as ``test_an_empty_level_ends_the_groupby`` has it.)"""
    holder, columns = data
    monkeypatch.setattr(ex_mod, "GROUPBY_DENSE_MAX_PROGRAMS", 0)
    ex = executor(holder, "local")
    f, g, h = (columns[n] for n in "fgh")
    a, b, x = next(
        (a, b, x) for x in range(ROWS["h"]) for a in range(1, ROWS["f"])
        for b in range(1, ROWS["g"])
        if ((f == a) & (h == x)).any() and ((g == b) & (h == x)).any()
        and not ((f == a) & (g == b) & (h == x)).any())
    with Around() as d:
        _, got = answer(
            ex, f"GroupBy(Rows(f, previous={a - 1}, limit=1), "
                f"Rows(g, previous={b - 1}, limit=1), filter=Row(h={x}))")
    assert got == []
    # two marginal levels in one round trip, then the final level
    assert (d.pruned, d.levels, d.prune_levels) == (1, 3, 1)


# ------------------------------------------------------- the served series


def test_the_series_are_exported_from_the_first_scrape(tmp_path):
    from cluster_helpers import req, uri
    from pilosa_tpu.server import Server, ServerConfig

    srv = Server(ServerConfig(
        data_dir=str(tmp_path / "node"), port=0, name="t",
        anti_entropy_interval=0, heartbeat_interval=0)).open()
    try:
        text = req("GET", uri(srv) + "/metrics", raw=True).decode()
        names = {l.split(" ")[0] for l in text.splitlines()}
        assert {"pilosa_tpu_groupby_pruned_total",
                "pilosa_tpu_groupby_paged_programs_total",
                "pilosa_tpu_stage_executor_prune_level_total",
                "pilosa_tpu_stage_executor_prune_level_seconds_total",
                "pilosa_tpu_stage_executor_prune_level_cpu_seconds_total",
                "pilosa_tpu_stage_executor_prune_level_cpu_entries_total",
                } <= names
        snap = req("GET", uri(srv) + "/debug/vars")
        assert {"pruned_total", "paged_programs_total"} <= set(snap["groupby"])
    finally:
        srv.close()
