"""A pruned GroupBy counts each dimension alone under the filter before
it crosses any two (ISSUE 44). A group (a, b, c) has members under the
filter F only if a & F, b & F and c & F each have, so ``run_pruned``
starts with a marginal round: a count-only level of every dimension
alone, all enqueued before ONE blocking readback (one entry of
``executor.prune_level``). Only the rows that survive are crossed: at
once in the final level where their cross product fits the dense rule,
else prefix by prefix. The answer is the dense path's byte for byte;
what changes is which never-reported candidates are counted on the way.
Every answer here is compared with a plain numpy group-by of the columns
and with the dense path's ``result_json_bytes``.
"""

import json

import numpy as np
import pytest
from test_groupby_prune_rule import (
    BUILDERS,
    Around as PruneAround,
    executor,
    page_the_largest,
)

from pilosa_tpu.executor import Executor, batch
from pilosa_tpu.executor import executor as ex_mod
from pilosa_tpu.executor.result import GroupCounts, result_json_bytes
from pilosa_tpu.parallel import DistExecutor, dist, make_mesh
from pilosa_tpu.shardwidth import SHARD_WIDTH
from pilosa_tpu.storage import FieldOptions, Holder

N_SHARDS = 3
ROWS = {"f": 5, "g": 4, "h": 9}
# k's keys in the order their row ids are given: not the order they emit
KEYS = ["pear", "apple", "quince", "fig", "cherry", "banana"]
VALUES = (0, 63)          # a 6-bit Sum: 8 quantities a candidate
SUM = 'aggregate=Sum(field="v")'
# the selector field's rows, as a filter sees the columns
SELECT = {
    0: lambda c: np.isin(c["h"], (1, 4, 7)),        # rows 1, 4, 7 of 9
    1: lambda c: (c["f"] == 2) & (c["g"] == 1),     # one row of f, of g
    # members in f's rows 0, 1 and in g's rows 0, 1, none in (0, 1), (1, 0)
    2: lambda c: (c["f"] == c["g"]) & (c["f"] < 2),
    3: lambda c: c["h"] < 0,                        # columns in no row of h
}


@pytest.fixture(scope="module")
def data(tmp_path_factory):
    holder = Holder(str(tmp_path_factory.mktemp("marginal") / "data")).open()
    rng = np.random.default_rng(44)
    idx = holder.create_index("i")
    fields = {name: idx.create_field(name) for name in (*ROWS, "s")}
    idx.create_field("k", FieldOptions(keys=True))
    v = idx.create_field("v", FieldOptions(type="int", min=VALUES[0],
                                           max=VALUES[1]))
    col = np.concatenate([
        np.sort(rng.choice(SHARD_WIDTH, 90, replace=False))
        + shard * SHARD_WIDTH for shard in range(N_SHARDS)])
    columns = {"column": col,
               "v": rng.integers(VALUES[0], VALUES[1] + 1, col.size),
               "k": rng.integers(0, len(KEYS), col.size)}
    for name, n in ROWS.items():
        columns[name] = rng.integers(0, n, col.size)
    columns["h"][rng.random(col.size) < 0.1] = -1
    ex = Executor(holder)
    # the keys' row ids in KEYS' order, whatever column comes first
    for i, key in enumerate(KEYS):
        first = int(col[np.flatnonzero(columns["k"] == i)[0]])
        ex.execute("i", f"Set({first}, k={json.dumps(key)})")
    k = idx.field("k")
    assert ex._row_keys(idx, k, list(range(len(KEYS)))) == KEYS
    selected = {row: select(columns) for row, select in SELECT.items()}
    for i, c in enumerate(col.tolist()):
        for name in ROWS:
            if columns[name][i] >= 0:
                fields[name].set_bit(int(columns[name][i]), c)
        k.set_bit(int(columns["k"][i]), c)
        v.set_value(c, int(columns["v"][i]))
        for row, mask in selected.items():
            if mask[i]:
                fields["s"].set_bit(row, c)
    idx.mark_columns_exist(col.tolist())
    yield holder, columns
    holder.close()


def numpy_groupby(columns, dims, keep=None, with_sum=False, limit=0,
                  rows=None):
    """[(emitted rows, count, sum or None)] in the order a GroupBy
    answers: ids numerically, k's rows by their keys. ``rows`` holds the
    row ids a dimension's ``Rows(previous, limit)`` leaves it."""
    keep = np.ones(columns["column"].size, bool) if keep is None else keep
    for d in dims:
        keep = keep & (columns[d] >= 0)
        if rows and d in rows:
            keep = keep & np.isin(columns[d], rows[d])
    out: dict = {}
    for i in np.flatnonzero(keep):
        key = tuple(KEYS[columns[d][i]] if d == "k" else int(columns[d][i])
                    for d in dims)
        n, total = out.get(key, (0, 0))
        out[key] = (n + 1, total + int(columns["v"][i]))
    groups = [(key, n, total if with_sum else None)
              for key, (n, total) in sorted(out.items())]
    return groups[:limit] if limit else groups


def answer(ex, pql):
    (groups,) = ex.execute("i", pql)
    assert isinstance(groups, GroupCounts)
    return groups, [
        (tuple(fr.get("rowKey", fr.get("rowID")) for fr in gc.group),
         gc.count, gc.sum) for gc in groups]


class Around(PruneAround):
    FIELDS = {**PruneAround.FIELDS,
              "candidates": "level_candidates_total",
              "placements": "operand_placements_total",
              "rounds": "marginal_rounds_total",
              "rows": "marginal_rows_total", "kept": "marginal_kept_total",
              "dense": "marginal_dense_total"}


@pytest.fixture
def small_programs(monkeypatch):
    """The kernel's candidate bound small enough for the CPU (16 a
    program count-only, 8 with the Sum), so that the real rule sends
    f x g x h = 180 groups down the pruned path and takes a survivors'
    product of at most 32 (16 with the Sum) dense."""
    monkeypatch.setattr(batch, "groupby_chunk_groups",
                        lambda n_planes: 8 if n_planes else 16)
    assert ex_mod.GROUPBY_DENSE_MAX_PROGRAMS == 2


def page(monkeypatch):
    # programs traced under the real plan are not the paged ones
    monkeypatch.setattr(batch, "groupby_tile_plan", page_the_largest)
    monkeypatch.setattr(batch, "_LOCAL_JIT_CACHE", {})
    monkeypatch.setattr(dist, "_DIST_JIT_CACHE", {})


def dense_bytes(holder, builder, pql, monkeypatch):
    """The answer's bytes by ONE dense level of every group."""
    with monkeypatch.context() as m:
        m.setattr(ex_mod, "GROUPBY_DENSE_MAX_PROGRAMS", 10 ** 9)
        with Around() as d:
            groups, _ = answer(executor(holder, builder), pql)
        assert (d.pruned, d.levels, d.rounds) == (0, 1, 0)
        return result_json_bytes(groups)


def spy_levels(ex, monkeypatch):
    """[(dimensions, candidates)] of every level ``ex`` enqueues and, in
    ``order``, "enqueue" and "readback" as each happens."""
    levels, order = [], []
    enqueue = ex._groupby_level_enqueue
    readback = ex_mod._readback

    def spy_enqueue(block, filt_leaves, filt_node, scalars, dim_mats, cand,
                    *args, **kw):
        levels.append((len(dim_mats), np.array(cand)))
        order.append("enqueue")
        return enqueue(block, filt_leaves, filt_node, scalars, dim_mats,
                       cand, *args, **kw)

    def spy_readback(device_array):
        order.append("readback")
        return readback(device_array)

    monkeypatch.setattr(ex, "_groupby_level_enqueue", spy_enqueue)
    monkeypatch.setattr(ex_mod, "_readback", spy_readback)
    return levels, order


def survivors_of(columns, dims, keep):
    return [np.unique(columns[d][keep & (columns[d] >= 0)]) for d in dims]


# ------------------------------------------------------------ the helpers


def test_index_cross_extends_by_the_rows_it_is_given():
    prefixes = np.array([[2, 0], [5, 3]], np.int32)
    got = ex_mod._index_cross(prefixes, np.array([1, 4, 7], np.int32))
    assert got.dtype == np.int32
    assert got.tolist() == [[2, 0, 1], [2, 0, 4], [2, 0, 7],
                            [5, 3, 1], [5, 3, 4], [5, 3, 7]]
    # a dense level's candidates are the same helper over every row
    assert ex_mod._dense_candidates((2, 3)).tolist() == [
        [a, b] for a in range(2) for b in range(3)]
    assert ex_mod._index_cross(prefixes, np.zeros(0, np.int32)).shape == (0, 3)


# ------------------------------- (a) survivors that fit: the final level


@pytest.mark.parametrize("paged", [False, True], ids=["resident", "paged"])
@pytest.mark.parametrize("builder", BUILDERS)
def test_survivors_that_fit_the_dense_rule_go_straight_to_the_final_level(
        data, builder, paged, small_programs, monkeypatch):
    holder, columns = data
    if paged:
        page(monkeypatch)
    pql = f"GroupBy(Rows(f), Rows(g), Rows(h), filter=Row(s=1), {SUM})"
    keep = SELECT[1](columns)
    want = numpy_groupby(columns, "fgh", keep, with_sum=True)
    s_f, s_g, s_h = survivors_of(columns, "fgh", keep)
    assert (s_f.tolist(), s_g.tolist()) == ([2], [1]) and 1 < s_h.size <= 9
    ex = executor(holder, builder)
    levels, order = spy_levels(ex, monkeypatch)
    with Around() as d:
        groups, got = answer(ex, pql)
    assert got == want and got
    assert result_json_bytes(groups) == dense_bytes(
        holder, builder, pql, monkeypatch)
    # three marginals in ONE timed round trip, then the final level over
    # 1 x 1 x |h's survivors|: no joint count-only level
    assert [(n, c.shape[0]) for n, c in levels] == [
        (1, 5), (1, 4), (1, 9), (3, s_h.size)]
    assert levels[-1][1].tolist() == [[2, 1, h] for h in s_h.tolist()]
    assert order[:6] == ["enqueue"] * 3 + ["readback"] * 3
    assert (d.pruned, d.levels, d.prune_levels) == (1, 4, 1)
    assert (d.rounds, d.rows, d.kept, d.dense) == (1, 18, 2 + s_h.size, 1)
    assert d.candidates == 18 + s_h.size


# ------------------------- (b) survivors that do not fit: joint levels


@pytest.mark.parametrize("paged", [False, True], ids=["resident", "paged"])
@pytest.mark.parametrize("builder", BUILDERS)
def test_survivors_that_do_not_fit_are_crossed_a_dimension_at_a_time(
        data, builder, paged, small_programs, monkeypatch):
    holder, columns = data
    if paged:
        page(monkeypatch)
    pql = "GroupBy(Rows(f), Rows(g), Rows(h), filter=Row(s=0))"
    keep = SELECT[0](columns)
    want = numpy_groupby(columns, "fgh", keep)
    pairs = sorted({k[:2] for k, _, _ in want})
    assert 12 < len(pairs) <= 20
    ex = executor(holder, builder)
    levels, order = spy_levels(ex, monkeypatch)
    with Around() as d:
        groups, got = answer(ex, pql)
    assert got == want
    assert result_json_bytes(groups) == dense_bytes(
        holder, builder, pql, monkeypatch)
    # every row of f and g survives, rows 1, 4, 7 of h: 5 x 4 x 3 = 60
    # groups are past the 32 the rule takes dense, so f's survivors are
    # crossed with g's (20 pairs, not a second level of f alone), and
    # the pairs that have members with h's three (never with its nine)
    assert [(n, c.shape[0]) for n, c in levels] == [
        (1, 5), (1, 4), (1, 9), (2, 20), (3, 3 * len(pairs))]
    assert levels[3][1].tolist() == [[a, b] for a in range(5)
                                     for b in range(4)]
    assert levels[4][1].tolist() == [[a, b, h] for a, b in pairs
                                     for h in (1, 4, 7)]
    assert order[:6] == ["enqueue"] * 3 + ["readback"] * 3
    assert (d.pruned, d.levels, d.prune_levels) == (1, 5, 2)
    assert (d.rounds, d.rows, d.kept, d.dense) == (1, 18, 5 + 4 + 3, 0)
    assert d.candidates == 18 + 20 + 3 * len(pairs)
    # the marginals' operands are found again, the joint levels' are not:
    # 20 and 3 x pairs candidates are two and three programs of 16
    with Around() as again:
        answer(ex, pql)
    programs = 3 + 2 + -(-3 * len(pairs) // 16)
    assert (d.programs, d.placements) == (programs, programs)
    assert (again.programs, again.placements) == (programs, programs - 3)


# --------------------- (c) non-contiguous survivors: order and truncation

ORDER = {
    # k's rows emit their keys, in the keys' order, not the ids'
    "keyed-first-limit": (
        "GroupBy(Rows(k), Rows(h), filter=Row(s=0), limit=5)",
        dict(dims="kh", limit=5)),
    # h's rows 2 to 8: its survivors 4 and 7 are indices 2 and 5
    "previous-keyed-sum-limit": (
        f"GroupBy(Rows(h, previous=1), Rows(k), Rows(f), filter=Row(s=0), "
        f"limit=7, {SUM})",
        dict(dims="hkf", limit=7, with_sum=True,
             rows={"h": list(range(2, 9))})),
    # h's rows 0 to 4: its survivors 1 and 4
    "rows-limit-keyed-last": (
        "GroupBy(Rows(f), Rows(h, limit=5), Rows(k), filter=Row(s=0))",
        dict(dims="fhk", rows={"h": list(range(5))})),
}


@pytest.mark.parametrize("paged", [False, True], ids=["resident", "paged"])
@pytest.mark.parametrize("query", list(ORDER))
@pytest.mark.parametrize("builder", BUILDERS)
def test_non_contiguous_survivors_keep_the_dense_paths_order_and_truncation(
        data, builder, query, paged, small_programs, monkeypatch):
    holder, columns = data
    if paged:
        page(monkeypatch)
    pql, ref = ORDER[query]
    want = numpy_groupby(columns, keep=SELECT[0](columns), **ref)
    ex = executor(holder, builder)
    levels, _ = spy_levels(ex, monkeypatch)
    with Around() as d:
        groups, got = answer(ex, pql)
    assert got == want and got
    if ref.get("limit"):
        assert len(got) == ref["limit"]
    assert result_json_bytes(groups) == dense_bytes(
        holder, builder, pql, monkeypatch)
    assert (d.pruned, d.rounds) == (1, 1)
    # no level past the round names a row of h outside 1, 4, 7
    h_at = ref["dims"].index("h")
    h_ids = np.asarray(ref.get("rows", {}).get("h", range(9)))
    for n_dims, cand in levels[len(ref["dims"]):]:
        if n_dims > h_at:
            assert set(h_ids[cand[:, h_at]].tolist()) <= {1, 4, 7}


# ------------------------- (d) a dimension whose every row survives

NOTHING_TO_PRUNE = {
    # no filter: the round keeps all 18 rows and the answer is the same
    "no-filter": ("GroupBy(Rows(f), Rows(g), Rows(h))", None, 18),
    # f and g whole, h's three
    "one-dimension-pruned": (
        f"GroupBy(Rows(f), Rows(g), Rows(h), filter=Row(s=0), {SUM})", 0, 12),
}


@pytest.mark.parametrize("case", list(NOTHING_TO_PRUNE))
@pytest.mark.parametrize("builder", BUILDERS)
def test_a_dimension_whose_every_row_survives_answers_the_same(
        data, builder, case, small_programs, monkeypatch):
    holder, columns = data
    pql, select, kept = NOTHING_TO_PRUNE[case]
    keep = None if select is None else SELECT[select](columns)
    want = numpy_groupby(columns, "fgh", keep, with_sum="Sum" in pql)
    ex = executor(holder, builder)
    levels, _ = spy_levels(ex, monkeypatch)
    with Around() as d:
        groups, got = answer(ex, pql)
    assert got == want and got
    assert result_json_bytes(groups) == dense_bytes(
        holder, builder, pql, monkeypatch)
    assert (d.pruned, d.rounds, d.rows, d.kept, d.dense) == (1, 1, 18, kept, 0)
    # f x g is crossed whole either way, as before the round
    assert [(n, c.shape[0]) for n, c in levels[:4]] == [
        (1, 5), (1, 4), (1, 9), (2, 20)]


# ----------------------- (e) one dimension empty under the filter


@pytest.mark.parametrize("builder", BUILDERS)
def test_one_dimension_empty_under_the_filter_ends_after_the_round(
        data, builder, small_programs, monkeypatch):
    holder, columns = data
    keep = SELECT[3](columns)
    s_f, s_g = survivors_of(columns, "fg", keep)
    assert s_f.size and s_g.size
    ex = executor(holder, builder)
    levels, order = spy_levels(ex, monkeypatch)
    with Around() as d:
        groups, got = answer(
            ex, f"GroupBy(Rows(f), Rows(g), Rows(h), filter=Row(s=3), {SUM})")
    assert got == [] and len(groups) == 0
    # f and g have members under the filter, h has none: one round trip
    # and no level after it
    assert [(n, c.shape[0]) for n, c in levels] == [(1, 5), (1, 4), (1, 9)]
    assert order == ["enqueue"] * 3 + ["readback"] * 3
    assert (d.pruned, d.levels, d.prune_levels) == (1, 3, 1)
    assert (d.rounds, d.rows, d.kept, d.dense) == (
        1, 18, s_f.size + s_g.size, 0)


# --------- (f) members in every marginal, none together: still dropped

TOGETHER = {
    # two dimensions: the final level follows the round at once
    "two-dimensions": (
        f"GroupBy(Rows(f), Rows(g), filter=Row(s=2), {SUM})", "fg",
        lambda c: SELECT[2](c), True),
    # three whose survivors 2 x 2 x 3 fit the rule: the final level too
    "three-dimensions-straight": (
        f"GroupBy(Rows(f), Rows(g), Rows(h), "
        f"filter=Intersect(Row(s=2), Row(s=0)), {SUM})", "fgh",
        lambda c: SELECT[2](c) & SELECT[0](c), True),
    # three whose survivors do not fit: the joint level drops the pairs
    "three-dimensions-joint": (
        f"GroupBy(Rows(f), Rows(g), Rows(h), filter=Row(s=2), {SUM})", "fgh",
        lambda c: SELECT[2](c), False),
}


@pytest.mark.parametrize("case", list(TOGETHER))
@pytest.mark.parametrize("builder", BUILDERS)
def test_groups_with_members_in_every_marginal_and_none_together_are_dropped(
        data, builder, case, small_programs, monkeypatch):
    holder, columns = data
    pql, dims, select, straight = TOGETHER[case]
    keep = select(columns)
    want = numpy_groupby(columns, dims, keep, with_sum=True)
    assert {k[:2] for k, _, _ in want} == {(0, 0), (1, 1)}
    survivors = survivors_of(columns, dims, keep)
    assert [s.tolist() for s in survivors[:2]] == [[0, 1], [0, 1]]
    product = int(np.prod([s.size for s in survivors]))
    assert (product <= 16) == straight or len(dims) == 2
    ex = executor(holder, builder)
    levels, _ = spy_levels(ex, monkeypatch)
    with Around() as d:
        groups, got = answer(ex, pql)
    assert got == want
    assert result_json_bytes(groups) == dense_bytes(
        holder, builder, pql, monkeypatch)
    assert (d.pruned, d.rounds, d.dense) == (1, 1, straight)
    n_dims, final = levels[-1]
    assert n_dims == len(dims)
    if straight:
        # they reach the final level, which counts them 0 and drops them
        assert final.shape[0] == product > len(want)
        assert {(0, 1), (1, 0)} <= {tuple(c[:2]) for c in final.tolist()}
    else:
        # (0, 1) and (1, 0) went at the joint level
        assert levels[-2][1].tolist() == [[0, 0], [0, 1], [1, 0], [1, 1]]
        assert {tuple(c[:2]) for c in final.tolist()} == {(0, 0), (1, 1)}


# ------------------------------ (g) flat meshes of 2, 6 and 8 devices


@pytest.mark.parametrize("query", [
    f"GroupBy(Rows(f), Rows(g), Rows(h), filter=Row(s=1), {SUM})",
    "GroupBy(Rows(f), Rows(g), Rows(h), filter=Row(s=0))",
    "GroupBy(Rows(k), Rows(h), filter=Row(s=0), limit=5)",
    f"GroupBy(Rows(f), Rows(g), Rows(h), filter=Row(s=3), {SUM})",
], ids=["straight", "joint", "keyed-limit", "empty"])
@pytest.mark.parametrize("n_devices", [2, 6, 8],
                         ids=["2dev", "6dev", "8dev"])
def test_a_pruned_groupby_on_any_flat_mesh_answers_the_dense_paths_bytes(
        data, n_devices, query, small_programs, monkeypatch):
    """The tests above run a mesh of four devices. Two hold two of the
    three shards' slots each, six and eight have devices with no shard
    (six is a count the padded slots are not a power of two of): the
    marginal round, the joint levels and the final one sum over each as
    over one device, to the dense path's bytes."""
    holder, _ = data
    want = dense_bytes(holder, "local", query, monkeypatch)
    ex = DistExecutor(holder, make_mesh(n_devices))
    with Around() as d:
        groups, _ = answer(ex, query)
    assert result_json_bytes(groups) == want
    assert (d.pruned, d.rounds) == (1, 1)


# ------------------------------------------------- (h) the served series


def test_the_marginal_series_are_exported_from_the_first_scrape(tmp_path):
    from cluster_helpers import req, uri
    from pilosa_tpu.server import Server, ServerConfig

    srv = Server(ServerConfig(
        data_dir=str(tmp_path / "node"), port=0, name="t",
        anti_entropy_interval=0, heartbeat_interval=0)).open()
    try:
        text = req("GET", uri(srv) + "/metrics", raw=True).decode()
        names = {l.split(" ")[0] for l in text.splitlines()}
        series = {"marginal_rounds_total", "marginal_rows_total",
                  "marginal_kept_total", "marginal_dense_total"}
        assert {f"pilosa_tpu_groupby_{s}" for s in series} <= names
        assert series <= set(req("GET", uri(srv) + "/debug/vars")["groupby"])
    finally:
        srv.close()
