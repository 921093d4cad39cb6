"""The data writer and the reference, without a server: the snapshot
bytes decode (by a decoder of this file's own) to the bits they were
made from, and the reference's histogram answers equal brute force."""

import itertools
import struct

import numpy as np
import pytest

from bench_helpers import TOY_CONFIG, TOY_MIX, load_config
from harness import datagen, traffic
from harness.reference import Reference

SW = datagen.SHARD_WIDTH


def decode(blob: bytes) -> dict:
    """{row: sorted positions} from a snapshot, written from the format's
    description in pilosa_tpu/roaring/format.py alone."""
    magic, version, _, n, payload = struct.unpack_from("<IHHIQ", blob, 0)
    assert magic == 0x50C4B175 and version == 1
    pos, data = 20, 20 + 16 * n
    assert len(blob) == data + payload
    rows: dict = {}
    last_key = -1
    for _ in range(n):
        key, kind, n1, length = struct.unpack_from("<QHHI", blob, pos)
        pos += 16
        assert key > last_key, "container keys ascend"
        last_key = key
        raw = blob[data:data + length]
        data += length
        if kind == 1:
            lows = np.frombuffer(raw, "<u2").astype(np.int64)
            assert lows.size <= 4096 and np.all(np.diff(lows) > 0)
        elif kind == 2:
            assert length == 8192
            lows = np.nonzero(np.unpackbits(np.frombuffer(raw, np.uint8),
                                            bitorder="little"))[0]
            assert lows.size > 4096
        else:
            runs = np.frombuffer(raw, "<u2").reshape(-1, 2).astype(np.int64)
            lows = np.concatenate([np.arange(a, b + 1) for a, b in runs])
        assert lows.size == n1 + 1
        rows.setdefault(key >> 4, []).append(((key & 15) << 16) + lows)
    return {r: np.concatenate(p) for r, p in rows.items()}


@pytest.mark.parametrize("field", ["cab_type", "passenger_count",
                                   "pickup_day", "dist_miles"])
def test_set_field_fragment_decodes_to_its_column(field):
    config = load_config("taxi-rides")
    cols = datagen.make_columns(config, 2_500_000_001, 1, [field])
    n_rows = datagen.field_rows(config["fields"][field])
    blob, cache = datagen.encode_set_fragment(cols[field], n_rows)
    rows = decode(blob)
    for r in range(n_rows):
        want = np.nonzero(cols[field] == r)[0]
        assert np.array_equal(rows.get(r, np.empty(0, np.int64)), want)
    assert dict(cache) == {r: p.size for r, p in rows.items()}
    assert sum(p.size for p in rows.values()) == SW


def test_thin_rows_of_a_wide_field_are_arrays():
    config = {"fields": dict(TOY_CONFIG["fields"],
                             brand={"type": "set", "uniform": 1000})}
    cols = datagen.make_columns(config, 7, 1, ["brand", "category", "revenue"])
    blob, _ = datagen.encode_set_fragment(cols["brand"], 1000)
    rows = decode(blob)
    assert len(rows) == 1000
    assert np.array_equal(rows[417], np.nonzero(cols["brand"] == 417)[0])
    assert np.array_equal(cols["category"], cols["brand"] // 8)
    assert 100 <= cols["revenue"].min() and cols["revenue"].max() <= 5000


def test_bsi_planes_decode_to_the_values():
    config = load_config("taxi-rides")
    cols = datagen.make_columns(config, 11, 1, ["total_amount_cents"])
    vals = cols["total_amount_cents"]
    rows_ids, bits = datagen._bsi_bits(vals, 16)
    rows = decode(datagen.encode_fragment(rows_ids, bits)[0])
    assert rows[0].size == SW  # the exists row: every column, as runs
    got = np.zeros(SW, np.int64)
    for i in range(16):
        if 2 + i in rows:
            got[rows[2 + i]] |= 1 << i
    assert np.array_equal(got, vals)
    assert 250 <= vals.min() and vals.max() <= 50000


def test_same_seed_same_data_whichever_fields_are_materialised():
    config = load_config("taxi-rides")
    a = datagen.make_columns(config, 5, 1, ["pickup_year", "dist_miles"])
    b = datagen.make_columns(config, 5, 1, list(config["fields"]))
    c = datagen.make_columns(config, 6, 1, ["pickup_year"])
    assert np.array_equal(a["pickup_year"], b["pickup_year"])
    assert np.array_equal(a["dist_miles"], b["dist_miles"])
    assert not np.array_equal(a["pickup_year"], c["pickup_year"])


def test_every_row_of_every_set_field_is_drawn():
    config = load_config("taxi-rides")
    cols = datagen.make_columns(config, 9, 4, [
        f for f, s in config["fields"].items() if s["type"] == "set"])
    for f, vals in cols.items():
        n = datagen.field_rows(config["fields"][f])
        assert np.count_nonzero(np.bincount(vals, minlength=n)) == n, f


@pytest.fixture(scope="module")
def small_ref():
    config = load_config("taxi-rides")
    cols = datagen.make_columns(config, 13, 1, list(config["fields"]))
    return Reference(config, cols), cols


def test_reference_count_equals_brute_force(small_ref):
    ref, c = small_ref
    assert ref.count([("pickup_year", 3), ("dist_miles", 2)]) == int(
        np.count_nonzero((c["pickup_year"] == 3) & (c["dist_miles"] == 2)))
    assert ref.count([("cab_type", 0)]) == int(np.count_nonzero(c["cab_type"] == 0))


def test_reference_topn_orders_by_count_then_id(small_ref):
    ref, c = small_ref
    got = ref.topn("cab_type", [("pickup_year", 1)])
    counts = [int(np.count_nonzero((c["cab_type"] == r) & (c["pickup_year"] == 1)))
              for r in range(3)]
    assert got == [{"id": r, "count": counts[r]}
                   for r in sorted(range(3), key=lambda r: (-counts[r], r))
                   if counts[r]]


def test_reference_groupby_with_sum_and_paging(small_ref):
    ref, c = small_ref
    got = ref.groupby([{"field": "passenger_count", "previous": 0, "limit": 2},
                       {"field": "pickup_month"}],
                      [("pickup_year", 2)], "total_amount_cents")
    keys = [(g["group"][0]["rowID"], g["group"][1]["rowID"]) for g in got]
    assert keys == sorted(keys) and {k[0] for k in keys} == {1, 2}
    g = got[5]
    p, m = g["group"][0]["rowID"], g["group"][1]["rowID"]
    sel = ((c["passenger_count"] == p) & (c["pickup_month"] == m)
           & (c["pickup_year"] == 2))
    assert g["count"] == int(sel.sum())
    assert g["sum"] == int(c["total_amount_cents"][sel].sum())


def test_reference_holds_reads_between_acknowledged_and_sent_writes(small_ref):
    ref, c = small_ref
    ref = Reference(ref.config, c)
    col = int(np.nonzero((c["pickup_year"] != 4) & (c["cab_type"] == 1))[0][0])
    terms = [("pickup_year", 4), ("cab_type", 1)]
    base = ref.count(terms)
    ref.note_write("pickup_year", 4, col, t_sent=10.0, t_acked=11.0)
    assert ref.count(terms, acked_before=10.5) == base      # not yet owed
    assert ref.count(terms, sent_before=10.5) == base + 1   # may be seen
    assert ref.count(terms, acked_before=11.5) == base + 1  # owed
    assert ref.count(terms, sent_before=9.0) == base
    assert ref.row_count("pickup_year", 4, acked_only=True) == \
        int(np.count_nonzero(c["pickup_year"] == 4)) + 1
    # a write of a bit the column already holds changes nothing
    ref.note_write("cab_type", 1, col, 12.0, 13.0)
    assert ref.count(terms, acked_before=20.0) == base + 1
    # an unacknowledged write may be either way
    ref.note_write("pickup_year", 4, col + 1 if c["pickup_year"][col + 1] != 4
                   else col + 2, 14.0, None)
    assert ref.row_count("pickup_year", 4, acked_only=False) >= \
        ref.row_count("pickup_year", 4, acked_only=True)


def admitted(c: dict, term) -> np.ndarray:
    """The columns a filter term admits, by a mask of this file's own."""
    f, spec = term
    (op, v), = spec.items() if isinstance(spec, dict) else [("in", [spec])]
    if op == "in":
        return np.isin(c[f], v)
    if op == "between":
        return (c[f] >= v[0]) & (c[f] <= v[1])
    assert op == "lt", op
    return c[f] < v


def brute_force(config: dict, c: dict, sem: dict):
    """One answer from masks over the columns, cell by cell."""
    sel = np.ones(len(next(iter(c.values()))), bool)
    for term in sem.get("filter", ()):
        sel &= admitted(c, term)
    if sem["kind"] == "count":
        return int(sel.sum())
    if sem["kind"] == "sum":
        return {"value": int(c[sem["sum"]][sel].sum()), "count": int(sel.sum())}
    pages = []
    for d in sem["dims"]:
        rows = [r for r in range(datagen.field_rows(config["fields"][d["field"]]))
                if (c[d["field"]] == r).any() and r > d.get("previous", -1)]
        pages.append(rows[:d["limit"]] if d.get("limit") else rows)
    want = []
    for rows in itertools.product(*pages):
        cell = sel.copy()
        for d, r in zip(sem["dims"], rows):
            cell &= c[d["field"]] == r
        if cell.any():
            item = {"group": [{"field": d["field"], "rowID": r}
                              for d, r in zip(sem["dims"], rows)],
                    "count": int(cell.sum())}
            if sem.get("sum"):
                item["sum"] = int(c[sem["sum"]][cell].sum())
            want.append(item)
    return want


@pytest.mark.parametrize("template", sorted(TOY_MIX["templates"]))
def test_reference_answers_the_toy_templates_as_brute_force(template):
    """Derived and uniform fields, paged dimensions, a Sum under a filter,
    range terms on int fields and ``in`` terms on a dimension and beside
    one: what no shipped mix asks yet, against masks over the columns."""
    c = datagen.make_columns(TOY_CONFIG, 17, 2, list(TOY_CONFIG["fields"]))
    ref = Reference(TOY_CONFIG, c)
    only = dict(TOY_MIX["groups"][0], rotation=[template])
    client = traffic.Client(TOY_MIX, TOY_CONFIG, 2, only, 0, 17, "t")
    for _ in range(6):
        _, _, sem = client.next()
        want = brute_force(TOY_CONFIG, c, sem)
        assert ref.answer(sem) == want and want


@pytest.mark.parametrize("fold, list_values, how", [
    (0, 1 << 16, "listed"), (0, 0, "masked"), (40, 6, "mixed")])
def test_reference_lists_or_masks_what_it_cannot_fold_and_answers_the_same(
        monkeypatch, fold, list_values, how):
    """With all the room, every field a filter names is an axis of the
    table. With none, the columns are listed by those fields' joint value
    and a request tabulates the ones it admits; with no room for that
    either, every term is a mask over the columns; with a little of both,
    some of each. The answers are the same, and a term twice on one field
    intersects."""
    from harness import reference

    c = datagen.make_columns(TOY_CONFIG, 19, 2, list(TOY_CONFIG["fields"]))
    sems = []
    for template in sorted(TOY_MIX["templates"]):
        only = dict(TOY_MIX["groups"][0], rotation=[template])
        client = traffic.Client(TOY_MIX, TOY_CONFIG, 2, only, 0, 19, "m")
        sems += [client.next()[2] for _ in range(3)]
    sems.append({"kind": "count", "filter": [("region", {"in": [1, 2]}),
                                             ("region", 2), ("year", 3)]})
    sems.append({"kind": "topn", "field": "nation",
                 "filter": [("quantity", {"between": [26, 50]}),
                            ("nation", {"in": [0, 3]})]})
    sems.append({"kind": "count", "filter": [("region", {"in": [7, 1]}),
                                             ("quantity", {"lt": 1})]})
    whole = Reference(TOY_CONFIG, c)
    folded = [whole.answer(s) for s in sems]
    assert not whole._lists and all(
        listed == () and set(named) <= set(fields)
        for (_, named), (fields, listed) in whole._plans.items())
    monkeypatch.setattr(reference, "FOLD_CELLS", fold)
    monkeypatch.setattr(reference, "LIST_VALUES", list_values)
    ref = Reference(TOY_CONFIG, c)
    assert [ref.answer(s) for s in sems] == folded
    assert folded[-3] == int(((c["region"] == 2) & (c["year"] == 3)).sum())
    assert [p["id"] for p in folded[-2]] == sorted(
        (0, 3), key=lambda n: -int(((c["nation"] == n)
                                    & (c["quantity"] >= 26)).sum()))
    assert folded[-1] == 0
    # of the fields a filter names beside its dimensions: (all, the
    # table's further axes, what the columns are listed by)
    plans = [(set(named) - set(dims), set(fields) - set(dims), set(listed))
             for (dims, named), (fields, listed) in ref._plans.items()]
    if how == "listed":
        assert all(not axes and lists == named for named, axes, lists in plans)
    elif how == "masked":
        assert all(not axes and not lists for _, axes, lists in plans)
    else:
        assert any(axes for _, axes, _ in plans)
        assert any(named - axes - lists for named, axes, lists in plans)
    assert bool(ref._lists) == (how != "masked")
    # only a table over every column is kept
    assert all(len(k) == 2 for k in ref._hist)


def test_reference_tabulates_a_pair_of_fields_once(small_ref):
    ref, c = small_ref
    ref = Reference(ref.config, c)
    a = ref.count([("pickup_year", 3), ("dist_miles", 2)])
    b = ref.count([("dist_miles", 2), ("pickup_year", 3)])
    assert a == b and len(ref._hist) == 1
