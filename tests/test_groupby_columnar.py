"""A GroupBy's answer stays columnar from the level's packed counts to the
response bytes (executor/result.py GroupCounts): every case compares the
bytes and the ``result_to_json`` value with a plain per-group reference
written here: the groups counted from Python sets, then walked one by one
into dicts and ``json.dumps`` as the executor did before PR 30."""

import itertools
import json

import numpy as np
import pytest

import pilosa_tpu.executor.executor as ex_mod
from pilosa_tpu.executor import Executor, batch
from pilosa_tpu.executor.executor import _groupby_sums
from pilosa_tpu.executor.result import (
    GroupCount,
    GroupCounts,
    result_json_bytes,
    result_to_json,
)
from pilosa_tpu.shardwidth import SHARD_WIDTH
from pilosa_tpu.storage import FieldOptions, Holder
from pilosa_tpu.utils.tracing import groupby_metrics

from cluster_helpers import make_cluster, req, settle, uri

COLUMNS = list(range(0, 66)) + list(range(SHARD_WIDTH, SHARD_WIDTH + 30))
SET_ROWS = {"a": [1, 2, 3, 5], "b": [10, 11, 12], "c": [0, 7]}
# keys whose order is not their ids' order (ids are given 0, 1, 2), and a
# row written by id alone: the keyed dimension emits it as a rowID
KEYS = ["zulu", "alpha", 'mi"ke']
KEYLESS_ROW = 40
INT_FIELDS = {
    "pos": dict(min=100, max=1000),          # positive base
    "neg": dict(min=-500, max=500),          # negative base
    # depth 63: five values near 2**62 in a group pass 2**63
    "wide": dict(min=0, max=(1 << 63) - 1),
}


def int_value(name: str, col: int) -> int:
    c = col % SHARD_WIDTH + 3 * (col // SHARD_WIDTH)
    return {"pos": 100 + (c * 37) % 901,
            "neg": -500 + (c * 53) % 1001,
            "wide": (1 << 62) + c * 12345}[name]


class Data:
    """What was written, as Python sets and dicts: the reference's input."""

    def __init__(self):
        self.cols: dict[str, dict[int, set]] = {}
        self.values: dict[str, dict[int, int]] = {}
        self.keys: dict[str, dict[int, str]] = {}


def write(ex, index="g"):
    holder = ex.holder
    idx = holder.create_index(index, track_existence=False)
    data = Data()
    for i, (name, rows) in enumerate(SET_ROWS.items()):
        f = idx.create_field(name)
        data.cols[name] = {r: set() for r in rows}
        for col in COLUMNS:
            if (col + i) % 5 == 4:
                continue  # some columns are in no row of the field
            row = rows[(col // (i + 1) + 2 * (col // SHARD_WIDTH)) % len(rows)]
            f.set_bit(row, col)
            data.cols[name][row].add(col)
    idx.create_field("k", FieldOptions(keys=True))
    data.cols["k"] = {}
    for col in COLUMNS:
        key = KEYS[(col // 2) % len(KEYS)]
        ex.execute(index, f"Set({col}, k={json.dumps(key)})")
    kfield = idx.field("k")
    key_ids = list(range(len(KEYS)))  # given in the order first written
    assert ex._row_keys(idx, kfield, key_ids) == KEYS
    data.keys["k"] = dict(zip(key_ids, KEYS))
    for col in COLUMNS:
        data.cols["k"].setdefault((col // 2) % len(KEYS), set()).add(col)
    for col in COLUMNS[::7]:
        kfield.set_bit(KEYLESS_ROW, col)
        data.cols["k"].setdefault(KEYLESS_ROW, set()).add(col)
    for name, opts in INT_FIELDS.items():
        f = idx.create_field(name, FieldOptions(type="int", **opts))
        data.values[name] = {}
        for col in COLUMNS:
            if col % 11 == 10:
                continue  # null
            f.set_value(col, int_value(name, col))
            data.values[name][col] = int_value(name, col)
    # an empty field: Rows(empty) has no rows
    idx.create_field("empty")
    return data


@pytest.fixture(scope="module")
def env(tmp_path_factory):
    holder = Holder(str(tmp_path_factory.mktemp("gbc") / "data")).open()
    ex = Executor(holder)
    yield ex, write(ex)
    holder.close()


# ------------------------------------------------------------ the reference


def reference_groups(dim_rows, counts, sums, keys, has_agg, limit=0,
                     having=None) -> list[dict]:
    """The per-group walk the executor made before PR 30 (its
    ``_groupby_result``): dicts in, the ``result_to_json`` value out.
    ``dim_rows`` is [(field, row ids)], ``counts`` / ``sums`` are keyed
    by the tuple of row ids, ``keys`` maps a keyed field to {row: key}."""
    if having is not None:
        counts = {k: c for k, c in counts.items()
                  if having(c, sums.get(k))}

    def emitted(i, row):
        name = dim_rows[i][0]
        key = keys.get(name, {}).get(row)
        return (1, key) if key is not None else (0, row)

    out = []
    for gkey, c in sorted(
            counts.items(),
            key=lambda kv: tuple(emitted(i, r) for i, r in enumerate(kv[0]))):
        group = []
        for i, row in enumerate(gkey):
            kind, val = emitted(i, row)
            group.append({"field": dim_rows[i][0],
                          "rowKey" if kind else "rowID": val})
        entry = {"group": group, "count": c}
        if has_agg:
            entry["sum"] = sums.get(gkey)
        out.append(entry)
    return out[: int(limit)] if limit else out


def reference(data: Data, dims, filt=None, agg=None, limit=0, having=None):
    """Count every group of the cross product from the written sets."""
    dim_rows = []
    for name, rows in dims:
        rows = sorted(data.cols.get(name, {})) if rows is None else rows
        dim_rows.append((name, rows))
    counts, sums = {}, {}
    for gkey in itertools.product(*[rows for _, rows in dim_rows]):
        cols = None
        for (name, _), row in zip(dim_rows, gkey):
            members = data.cols[name][row]
            cols = set(members) if cols is None else cols & members
        if filt is not None:
            cols &= filt
        if not cols:
            continue
        counts[gkey] = len(cols)
        if agg is not None:
            sums[gkey] = sum(data.values[agg][c] for c in cols
                             if c in data.values[agg])
    return reference_groups(dim_rows, counts, sums, data.keys,
                            agg is not None, limit, having)


def dumps(value) -> bytes:
    return json.dumps(value, separators=(",", ":")).encode()


# ---------------------------------------------------------------- the cases
#
# name: (PQL, reference arguments). A dimension's rows are None for "every
# row of the field", or the list Rows(previous=, limit=) leaves.

CASES = {
    "1dim": ("GroupBy(Rows(a))", dict(dims=[("a", None)])),
    "2dims": ("GroupBy(Rows(a), Rows(b))",
              dict(dims=[("a", None), ("b", None)])),
    "3dims": ("GroupBy(Rows(a), Rows(b), Rows(c))",
              dict(dims=[("a", None), ("b", None), ("c", None)])),
    "2dims-filter": (
        "GroupBy(Rows(b), Rows(c), filter=Row(a=2))",
        dict(dims=[("b", None), ("c", None)], filt=("a", 2))),
    "sum-positive-base": (
        'GroupBy(Rows(a), aggregate=Sum(field="pos"))',
        dict(dims=[("a", None)], agg="pos")),
    "sum-negative-base-2dims": (
        'GroupBy(Rows(a), Rows(b), aggregate=Sum(field="neg"))',
        dict(dims=[("a", None), ("b", None)], agg="neg")),
    "sum-depth-63-passes-int64": (
        'GroupBy(Rows(c), aggregate=Sum(field="wide"))',
        dict(dims=[("c", None)], agg="wide")),
    "keyed": ("GroupBy(Rows(k))", dict(dims=[("k", None)])),
    "keyed-then-unkeyed": ("GroupBy(Rows(k), Rows(a))",
                           dict(dims=[("k", None), ("a", None)])),
    "unkeyed-then-keyed-sum": (
        'GroupBy(Rows(b), Rows(k), aggregate=Sum(field="neg"))',
        dict(dims=[("b", None), ("k", None)], agg="neg")),
    "limit": ("GroupBy(Rows(a), Rows(b), limit=5)",
              dict(dims=[("a", None), ("b", None)], limit=5)),
    "limit-keyed": ("GroupBy(Rows(k), Rows(c), limit=3)",
                    dict(dims=[("k", None), ("c", None)], limit=3)),
    "having-count": (
        "GroupBy(Rows(a), Rows(b), having=Condition(count > 4))",
        dict(dims=[("a", None), ("b", None)],
             having=lambda c, s: c > 4)),
    "having-count-limit": (
        "GroupBy(Rows(a), Rows(b), limit=2, having=Condition(count < 5))",
        dict(dims=[("a", None), ("b", None)], limit=2,
             having=lambda c, s: c < 5)),
    "having-sum": (
        'GroupBy(Rows(a), Rows(c), aggregate=Sum(field="neg"), '
        "having=Condition(sum < 0))",
        dict(dims=[("a", None), ("c", None)], agg="neg",
             having=lambda c, s: s < 0)),
    "previous": ("GroupBy(Rows(a, previous=2), Rows(b))",
                 dict(dims=[("a", [3, 5]), ("b", None)])),
    "rows-limit": ("GroupBy(Rows(a, limit=2), Rows(b, previous=10))",
                   dict(dims=[("a", [1, 2]), ("b", [11, 12])])),
    "empty-no-rows": ("GroupBy(Rows(a), Rows(empty))", None),
    "all-counts-zero": (
        "GroupBy(Rows(a), Rows(b), filter=Row(a=99))",
        dict(dims=[("a", None), ("b", None)], filt=("a", 99))),
    "all-counts-zero-sum": (
        'GroupBy(Rows(a), filter=Row(c=99), aggregate=Sum(field="pos"))',
        dict(dims=[("a", None)], filt=("c", 99), agg="pos")),
}

# How the final level is evaluated: every group in one dense level, the
# pruned path (taken past GROUPBY_DENSE_MAX_PROGRAMS programs), and a level
# of more candidates than one program holds.
PATHS = ["dense", "pruned", "chunked"]


def take_path(path, monkeypatch):
    if path == "pruned":
        monkeypatch.setattr(ex_mod, "GROUPBY_DENSE_MAX_PROGRAMS", 0)
    elif path == "chunked":
        # a dense level of many programs, which the rule would prune
        monkeypatch.setattr(ex_mod, "GROUPBY_DENSE_MAX_PROGRAMS", 10 ** 9)
        monkeypatch.setattr(batch, "groupby_chunk_groups",
                            lambda n_planes: 4)


@pytest.mark.parametrize("path", PATHS)
@pytest.mark.parametrize("case", list(CASES))
def test_bytes_and_json_equal_the_per_group_reference(env, case, path,
                                                      monkeypatch):
    ex, data = env
    pql, spec = CASES[case]
    take_path(path, monkeypatch)
    if spec is None:
        want = []
    else:
        spec = dict(spec)
        if "filt" in spec:
            field, row = spec["filt"]
            spec["filt"] = data.cols[field].get(row, set())
        want = reference(data, **spec)
    before = groupby_metrics()
    (res,) = ex.execute("g", pql)
    assert isinstance(res, GroupCounts)
    assert len(res) == len(want)
    assert result_json_bytes(res) == dumps(want)
    after = groupby_metrics()
    assert after["results_total"] == before["results_total"] + 1
    # bytes and length took no per-group object
    assert (after["results_materialized_total"]
            == before["results_materialized_total"])
    assert result_to_json(res) == want
    if case.startswith("all-counts-zero") or case == "empty-no-rows":
        assert want == []
    else:
        assert want, "the case must have groups to compare"


def test_a_cross_product_past_the_dense_bound_is_pruned(tmp_path):
    """More than GROUPBY_DENSE_MAX_PROGRAMS programs with nothing patched:
    17 x 16 x 16 = 4,352 candidates (9 programs of the 512 a 13-bit Sum
    leaves room for), most of them empty."""
    holder = Holder(str(tmp_path / "data")).open()
    try:
        ex = Executor(holder)
        idx = holder.create_index("p", track_existence=False)
        data = Data()
        sizes = {"x": 17, "y": 16, "z": 16}
        assert not ex_mod._groupby_dense(tuple(sizes.values()), 2 + 13)
        for i, (name, n) in enumerate(sizes.items()):
            f = idx.create_field(name)
            data.cols[name] = {}
            for col in range(200):
                row = (col * (i + 3) + col // 7) % n
                f.set_bit(row, col)
                data.cols[name].setdefault(row, set()).add(col)
            assert len(data.cols[name]) == n
        amount = idx.create_field(
            "amount", FieldOptions(type="int", min=-50, max=5000))
        data.values["amount"] = {c: c * 25 - 50 for c in range(0, 200, 3)}
        for c, v in data.values["amount"].items():
            amount.set_value(c, v)
        dims = [(name, None) for name in sizes]
        (res,) = ex.execute(
            "p", 'GroupBy(Rows(x), Rows(y), Rows(z), '
                 'aggregate=Sum(field="amount"))')
        want = reference(data, dims, agg="amount")
        assert len(want) > 100
        assert result_json_bytes(res) == dumps(want)
        assert result_to_json(res) == want
    finally:
        holder.close()


# ------------------------------------------- the columns, without a device
#
# Rows(...) hands a dimension's rows over ascending, so a dimension whose
# row ids do not ascend reaches _groupby_counts only from here.


class _Options:
    def __init__(self, keys=False, base=0, bit_depth=1):
        self.keys, self.base, self.bit_depth = keys, base, bit_depth


class _Field:
    def __init__(self, name, **options):
        self.name, self.options = name, _Options(**options)


class _Index:
    name = "direct"

    def __init__(self, *fields):
        self.fields = {f.name: f for f in fields}

    def field(self, name):
        return self.fields.get(name)


DIRECT = {
    # (dims, row keys by field)
    "ascending": ([("a", [1, 2, 9]), ("b", [4, 6])], {}),
    "first-descending": ([("a", [9, 2, 1]), ("b", [4, 6])], {}),
    "last-shuffled": ([("a", [1, 2, 9]), ("b", [6, 4, 5])], {}),
    "both-shuffled-3dims": (
        [("a", [2, 9, 1]), ("b", [6, 4]), ("c", [3, 0, 8, 1])], {}),
    "keyed-first": (
        [("k", [1, 2, 3]), ("b", [4, 6])],
        {"k": {1: "pear", 2: "apple", 3: "fig"}}),
    "keyed-last-some-rows-keyless": (
        [("a", [9, 1]), ("k", [1, 2, 3, 4])],
        {"k": {1: "pear", 2: None, 3: "apple", 4: None}}),
    "key-needs-escaping": (
        [("k", [1, 2])], {"k": {1: 'q"uote', 2: "café \\ tab\t"}}),
}


@pytest.mark.parametrize("with_sum", [False, True], ids=["count", "sum"])
@pytest.mark.parametrize("limit", [0, 3])
@pytest.mark.parametrize("case", list(DIRECT))
def test_columns_from_a_level_in_any_row_order(case, limit, with_sum):
    dims, keys = DIRECT[case]
    ex = Executor.__new__(Executor)
    ex._row_keys = lambda idx, field, rows: [
        keys[field.name][r] for r in rows]
    idx = _Index(*[_Field(name, keys=name in keys) for name, _ in dims])
    sizes = [len(rows) for _, rows in dims]
    cand = np.stack([g.ravel() for g in np.meshgrid(
        *[np.arange(n) for n in sizes], indexing="ij")], axis=1)
    rng = np.random.default_rng(len(case))
    counts = rng.integers(0, 4, len(cand)).astype(np.int64)  # zeros too
    depth, base = 5, -7
    agg_arrs = agg = None
    if with_sum:
        n = np.minimum(counts, rng.integers(0, 4, len(cand)))
        pc = rng.integers(0, 4, (depth, len(cand))).astype(np.int64)
        pc = np.minimum(pc, n)
        agg_arrs, agg = (n, pc), _Field("v", base=base, bit_depth=depth)
    want_counts, want_sums = {}, {}
    for j, ix in enumerate(cand.tolist()):
        if counts[j] <= 0:
            continue
        gkey = tuple(dims[d][1][i] for d, i in enumerate(ix))
        want_counts[gkey] = int(counts[j])
        if with_sum:
            want_sums[gkey] = (
                sum(int(v) << b for b, v in enumerate(pc[:, j]))
                + base * int(n[j]))
    want = reference_groups(dims, want_counts, want_sums, keys, with_sum,
                            limit)
    res = ex._groupby_counts(idx, dims, cand, counts, agg_arrs, agg,
                             SHARD_WIDTH, limit)
    assert result_json_bytes(res) == dumps(want)
    assert result_to_json(res) == want
    assert res.rows.dtype == np.int64 and res.counts.dtype == np.int64


@pytest.mark.parametrize("depth,base,columns,exact_int64", [
    (16, 0, 128 * SHARD_WIDTH, True),          # the dashboard's q2
    (16, -(1 << 15), 1024 * SHARD_WIDTH, True),
    (31, 1 << 30, 1024 * SHARD_WIDTH, True),   # 2**31 * 2**30 = 2**61
    (32, 0, 1024 * SHARD_WIDTH, True),         # 2**62 - 2**30
    (33, 0, 1024 * SHARD_WIDTH, False),        # past it: Python integers
    (42, 0, SHARD_WIDTH, True),
    (43, 0, SHARD_WIDTH, False),
    (8, -(1 << 45), 4 * SHARD_WIDTH, False),   # the base alone passes it
    (63, 1 << 62, 2 * SHARD_WIDTH, False),
])
def test_sum_is_exact_on_both_sides_of_62_bits(depth, base, columns,
                                               exact_int64):
    rng = np.random.default_rng(depth)
    # the largest counts a block of that many columns can give, and some
    n = np.array([columns, columns, 0, 1, int(rng.integers(columns))],
                 np.int64)
    pc = np.stack([np.array([columns, 0, 0, b % 2,
                             int(rng.integers(n[4] + 1))], np.int64)
                   for b in range(depth)])
    want = [sum(int(v) << b for b, v in enumerate(pc[:, j].tolist()))
            + base * int(n[j]) for j in range(len(n))]
    got = _groupby_sums(n, pc, base, depth, columns)
    assert (got.dtype == np.int64) == exact_int64
    assert got.tolist() == want
    assert all(type(v) is int for v in got.tolist())


# ------------------------------------------------------- GroupCounts, alone


def _two_groups():
    return GroupCounts(
        ["a", "k"],
        np.array([[1, 7], [2, 8]], np.int64),
        np.array([3, 4], np.int64),
        np.array([-5, 1 << 40], np.int64),
        [None, {7: "seven"}],
    )


def _two_groups_as_list():
    return [
        GroupCount([{"field": "a", "rowID": 1},
                    {"field": "k", "rowKey": "seven"}], 3, sum=-5),
        GroupCount([{"field": "a", "rowID": 2},
                    {"field": "k", "rowID": 8}], 4, sum=1 << 40),
    ]


def test_groupcounts_equals_its_list_both_ways():
    assert _two_groups() == _two_groups_as_list()
    assert _two_groups_as_list() == _two_groups()
    assert _two_groups() == _two_groups()
    assert _two_groups() != _two_groups_as_list()[:1]
    assert _two_groups_as_list()[1:] != _two_groups()
    assert GroupCounts() == [] and [] == GroupCounts()
    assert not GroupCounts() and _two_groups()
    assert _two_groups() != "groups"


def test_groupcounts_index_slice_and_iteration():
    res, want = _two_groups(), _two_groups_as_list()
    assert len(res) == 2
    assert res[0] == want[0] and res[-1] == want[1]
    assert res[:1] == want[:1]
    assert list(res) == want
    assert [g.to_json() for g in res] == [g.to_json() for g in want]
    assert res[0] is res[0]  # built once
    with pytest.raises(IndexError):
        res[2]
    assert "GroupCount(" in repr(res)


def test_groups_are_made_once_and_counted_once():
    res = _two_groups()
    before = groupby_metrics()["results_materialized_total"]
    assert len(res) == 2 and result_json_bytes(res)
    assert groupby_metrics()["results_materialized_total"] == before
    list(res), res[0], res == [], result_to_json(res)
    assert groupby_metrics()["results_materialized_total"] == before + 1


def test_wire_round_trip(env):
    from pilosa_tpu.wire.serializer import (
        decode_results_json,
        encode_results,
    )

    ex, data = env
    pql = 'GroupBy(Rows(k), Rows(a), aggregate=Sum(field="neg"))'
    (res,) = ex.execute("g", pql)
    (empty,) = ex.execute("g", "GroupBy(Rows(a), filter=Row(a=99))")
    assert isinstance(empty, GroupCounts) and len(empty) == 0
    want = reference(data, [("k", None), ("a", None)], agg="neg")
    decoded = decode_results_json(encode_results([res, empty, 7]))
    assert decoded["results"] == [want, [], 7]
    # the same bytes a list of GroupCount gives
    assert (encode_results([res, empty])
            == encode_results([list(res), []]))


# ------------------------------------------------------------- served paths


@pytest.fixture(scope="module")
def cluster(tmp_path_factory):
    servers = make_cluster(tmp_path_factory.mktemp("gbc-cluster"), 2)
    try:
        settle(servers)
        node0 = servers[0]
        req("POST", f"{uri(node0)}/index/i", {})
        req("POST", f"{uri(node0)}/index/i/field/f", {})
        req("POST", f"{uri(node0)}/index/i/field/k",
            {"options": {"keys": True}})
        req("POST", f"{uri(node0)}/index/i/field/v",
            {"options": {"type": "int", "min": -100, "max": 1000}})
        data = Data()
        data.cols = {"f": {}, "k": {}}
        data.values = {"v": {}}
        key_rows: dict[str, set] = {}
        sets = []
        for shard in range(8):
            for j in range(6):
                col = shard * SHARD_WIDTH + j
                row = (shard + j) % 3 + 1
                key = ["north", "east", "west"][(shard * 2 + j) % 3]
                sets.append(f"Set({col}, f={row})")
                sets.append(f'Set({col}, k="{key}")')
                sets.append(f"Set({col}, v={col % 977 - 100})")
                data.cols["f"].setdefault(row, set()).add(col)
                key_rows.setdefault(key, set()).add(col)
                data.values["v"][col] = col % 977 - 100
        req("POST", f"{uri(node0)}/index/i/query", " ".join(sets).encode())
        yield servers, data, key_rows
    finally:
        for s in servers:
            s.close()


def _keyed_reference(data, key_rows, dims, **kw):
    """The cluster's key ids are its own: name a keyed row by a stand-in
    id in key order and let the reference emit the key."""
    ids = {key: i for i, key in enumerate(sorted(key_rows))}
    data.cols["k"] = {ids[key]: cols for key, cols in key_rows.items()}
    data.keys["k"] = {i: key for key, i in ids.items()}
    return reference(data, dims, **kw)


def test_two_node_cluster_merges_groupcounts_parts(cluster):
    servers, data, key_rows = cluster
    owners = {
        n.id for shard in range(8)
        for n in servers[0].api.cluster.shard_nodes("i", shard)[:1]
    }
    assert len(owners) == 2, "both nodes must own shards of the index"
    want = _keyed_reference(data, key_rows, [("f", None), ("k", None)],
                            agg="v")
    pql = 'GroupBy(Rows(f), Rows(k), aggregate=Sum(field="v"))'
    for node in servers:
        before = groupby_metrics()["results_materialized_total"]
        got = req("POST", f"{uri(node)}/index/i/query", pql.encode())
        assert got == {"results": [want]}
        # the local part is a GroupCounts and the merge walks it
        assert groupby_metrics()["results_materialized_total"] > before
    limited = _keyed_reference(
        data, key_rows, [("f", None), ("k", None)], limit=4,
        having=lambda c, s: c >= 5)
    got = req("POST", f"{uri(servers[1])}/index/i/query",
              b"GroupBy(Rows(f), Rows(k), limit=4, "
              b"having=Condition(count >= 5))")
    assert got == {"results": [limited]}


def test_json_route_builds_no_group_object_and_the_wire_builds_them_once(
        tmp_path):
    import urllib.request

    from pilosa_tpu.wire.serializer import decode_results_json

    (server,) = make_cluster(tmp_path, 1)
    try:
        base = uri(server)
        req("POST", f"{base}/index/i", {})
        req("POST", f"{base}/index/i/field/f", {})
        req("POST", f"{base}/index/i/field/g", {})
        req("POST", f"{base}/index/i/query",
            b"Set(1, f=1) Set(2, f=1) Set(3, f=2) "
            b"Set(1, g=5) Set(2, g=6) Set(3, g=5)")
        want = [
            {"group": [{"field": "f", "rowID": 1},
                       {"field": "g", "rowID": 5}], "count": 1},
            {"group": [{"field": "f", "rowID": 1},
                       {"field": "g", "rowID": 6}], "count": 1},
            {"group": [{"field": "f", "rowID": 2},
                       {"field": "g", "rowID": 5}], "count": 1},
        ]
        before = groupby_metrics()
        for spaces in range(3):  # three texts: none answered from a cache
            raw = req("POST", f"{base}/index/i/query",
                      b" " * spaces + b"GroupBy(Rows(f), Rows(g))", raw=True)
            assert raw == b'{"results":[' + dumps(want) + b"]}"
        after = groupby_metrics()
        assert after["results_total"] == before["results_total"] + 3
        assert (after["results_materialized_total"]
                == before["results_materialized_total"])

        r = urllib.request.Request(
            f"{base}/index/i/query", data=b"  GroupBy(Rows(g), Rows(f))",
            method="POST", headers={"Accept": "application/x-protobuf"})
        with urllib.request.urlopen(r, timeout=60) as resp:
            decoded = decode_results_json(resp.read())
        assert [g["count"] for g in decoded["results"][0]] == [1, 1, 1]
        wired = groupby_metrics()
        assert wired["results_total"] == after["results_total"] + 1
        assert (wired["results_materialized_total"]
                == after["results_materialized_total"] + 1)

        metrics = req("GET", f"{base}/metrics", raw=True).decode()
        assert (f"pilosa_tpu_groupby_results_total {wired['results_total']}"
                in metrics)
        assert ("pilosa_tpu_groupby_results_materialized_total "
                f"{wired['results_materialized_total']}" in metrics)
        debug_vars = req("GET", f"{base}/debug/vars")
        assert (debug_vars["groupby"]["results_materialized_total"]
                == wired["results_materialized_total"])
    finally:
        server.close()
