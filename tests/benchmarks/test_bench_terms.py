"""ISSUE 41: the harness can say and check all 13 Star Schema Benchmark
queries. Filter terms beyond ``Row(f=r)`` (a ``Union`` of rows, ``<``,
``between``) and an integer draw in ``traffic.py``; a reference that
lists the columns by what it cannot tabulate by, so that its tables stay
at the size of the dimensions (``reference.py``).

- Old and new reference agree: every template of every shipped mix and
  of the toy, answered by the parent's ``_sliced`` path (kept below) and
  by the new one, on 2 shards and 3 seeds, ``point-rw`` with writes in
  play (both bounds of ``count``, both of ``row_count``).
- The 13 SSB query forms at a toy's sizes render to PQL the program
  parses, a CPU executor opened on the written files answers each as the
  reference does, and the harness's own ``verify`` calls the ``sampled``
  control not correct on every one of them.
- The Q4.3 form at SSB's published row counts allocates no array of more
  than its dimensions' 1.75 M cells (5.5e9 at the parent).
"""

import collections
import math
import os
import tracemalloc
import types

import numpy as np
import pytest

from bench_helpers import (CELLS, TOY_CELL, TOY_CONFIG, TOY_MIX,
                           config_and_mix, import_run)
from harness import datagen, reference, traffic
from harness.datagen import SHARD_WIDTH, field_rows
from harness.reference import Reference

CHUNK = SHARD_WIDTH
SEEDS = (4_100_000_007, 41, 2_147_483_659)


class ParentReference:
    """``harness/reference.py`` of 9a8c9f6, word for word: one joint
    histogram over the dimensions AND every filter field (``_sliced``)."""

    def __init__(self, config: dict, columns: dict[str, np.ndarray]):
        self.config = config
        self.columns = columns
        self.n_columns = len(next(iter(columns.values())))
        self._hist: dict[tuple, np.ndarray] = {}
        # (field, row) -> {column: [(t_sent, t_acked)]}; t_acked is None
        # for a write that was sent and never acknowledged
        self.writes: dict[tuple, dict] = {}

    # ----------------------------------------------------------- histograms

    def n_rows(self, field: str) -> int:
        return field_rows(self.config["fields"][field])

    def joint(self, fields: tuple, weight: str | None = None) -> np.ndarray:
        """Counts (or sums of int field ``weight``) for every combination
        of row ids of ``fields``: an array with one axis per field."""
        memo = (fields, weight)
        if memo in self._hist:
            return self._hist[memo]
        dims = [self.n_rows(f) for f in fields]
        cells = int(np.prod(dims))
        key_t = np.uint16 if cells <= 1 << 16 else np.int64
        total = np.zeros(cells, np.int64)
        for lo in range(0, self.n_columns, CHUNK):
            key = np.zeros(min(CHUNK, self.n_columns - lo), key_t)
            for f, d in zip(fields, dims):
                key *= key_t(d)
                key += self.columns[f][lo:lo + CHUNK]
            if weight is None:
                total += np.bincount(key, minlength=cells)
            else:
                # < 2^20 values under 2^31 each: exact in float64
                w = self.columns[weight][lo:lo + CHUNK]
                total += np.bincount(key, weights=w, minlength=cells
                                     ).astype(np.int64)
        self._hist[memo] = total.reshape(dims)
        return self._hist[memo]

    def _sliced(self, dims: list[str], terms: list, weight=None) -> np.ndarray:
        """Joint table over ``dims`` restricted to ``terms`` [(field, row)]."""
        # filter fields in one order, so a pair is tabulated once
        fields = tuple(dims) + tuple(sorted(
            {f for f, _ in terms if f not in dims}))
        table = self.joint(fields, weight)
        index = [slice(None)] * len(fields)
        for f, r in terms:
            index[fields.index(f)] = slice(r, r + 1) if f in dims else r
        return table[tuple(index)]

    # ------------------------------------------------------------- answers

    def count(self, terms: list, sent_before: float | None = None,
              acked_before: float | None = None) -> int:
        """|intersection of Row(field=row) over terms|. With writes in
        play, ``acked_before`` counts only writes acknowledged before
        that time (the least a read sent then may see) and
        ``sent_before`` those sent before it (the most one may see)."""
        base = int(self._sliced([], terms))
        return base + self._written(terms, sent_before, acked_before)

    def _written(self, terms, sent_before, acked_before) -> int:
        def landed(w) -> bool:
            t_sent, t_acked = w
            if acked_before is not None:
                return t_acked is not None and t_acked < acked_before
            return sent_before is None or t_sent < sent_before

        def holds(field, row, col) -> bool:
            if int(self.columns[field][col]) == row:
                return True
            return any(map(landed,
                           self.writes.get((field, row), {}).get(col, ())))

        fresh = {col for f, r in terms
                 for col, ws in self.writes.get((f, r), {}).items()
                 if any(map(landed, ws))}
        return sum(
            1 for col in fresh
            if all(holds(f, r, col) for f, r in terms)
            and not all(int(self.columns[f][col]) == r for f, r in terms))

    def topn(self, field: str, terms: list, n: int = 10) -> list:
        counts = self._sliced([field], terms).reshape(-1)
        pairs = sorted(((int(c), r) for r, c in enumerate(counts) if c),
                       key=lambda cr: (-cr[0], cr[1]))
        return [{"id": r, "count": c} for c, r in pairs[:n]]

    def groupby(self, dims: list, terms: list, sum_field: str | None) -> list:
        """``dims`` is [{"field", "previous"?, "limit"?}]; rows of a
        dimension are its non-empty rows after ``previous``, at most
        ``limit`` of them, as ``Rows()`` pages them."""
        names = [d["field"] for d in dims]
        counts = self._sliced(names, terms).reshape(
            [self.n_rows(f) for f in names])
        sums = None
        if sum_field is not None:
            sums = self._sliced(names, terms, sum_field).reshape(counts.shape)
        row_lists = []
        for d in dims:
            rows = np.nonzero(self.joint((d["field"],)))[0].tolist()
            if d.get("previous") is not None:
                rows = [r for r in rows if r > d["previous"]]
            if d.get("limit"):
                rows = rows[:d["limit"]]
            row_lists.append(rows)
        out = []
        for key in np.ndindex(*[len(r) for r in row_lists]):
            rows = tuple(row_lists[i][k] for i, k in enumerate(key))
            c = int(counts[rows])
            if not c:
                continue
            item = {"group": [{"field": f, "rowID": r}
                              for f, r in zip(names, rows)], "count": c}
            if sums is not None:
                item["sum"] = int(sums[rows])
            out.append(item)
        return out

    def answer(self, sem: dict):
        """The expected JSON result of one read request (see traffic.py
        for the semantic form)."""
        terms = [tuple(t) for t in sem.get("filter", [])]
        if sem["kind"] == "count":
            return self.count(terms)
        if sem["kind"] == "sum":
            return {"value": int(self._sliced([], terms, sem["sum"])),
                    "count": int(self._sliced([], terms))}
        if sem["kind"] == "topn":
            return self.topn(sem["field"], terms)
        if sem["kind"] == "groupby":
            return self.groupby(sem["dims"], terms, sem.get("sum"))
        raise ValueError(f"no reference answer for kind {sem['kind']!r}")

    # -------------------------------------------------------------- writes

    def note_write(self, field: str, row: int, col: int, t_sent: float,
                   t_acked: float | None) -> None:
        self.writes.setdefault((field, row), {}).setdefault(col, []).append(
            (t_sent, t_acked))

    def row_count(self, field: str, row: int, acked_only: bool) -> int:
        """Bits in Row(field=row) after the writes: with ``acked_only``
        the acknowledged ones, else every one that was sent."""
        base = int(self.joint((field,))[row])
        cols = {col for col, ws in self.writes.get((field, row), {}).items()
                if not acked_only or any(w[1] is not None for w in ws)}
        return base + sum(1 for c in cols
                          if int(self.columns[field][c]) != row)


# ---------------------------------------------- old and new reference agree

PAIRS = list(CELLS.values()) + [TOY_CELL]


def plain(template: dict) -> bool:
    """A template the parent could say: every term a ``[field, row]``."""
    return not any(isinstance(spec, dict)
                   for _, spec in template.get("filter", ()))


# the parent's reference tabulates a template by the joint table of its
# dimensions and its filter fields; past this many cells it is not asked
# (SSB Q3.2: 273 M cells; Q4.3: 5.5e9, 44 GB)
PARENTS_TABLE_MAX = 1 << 20


def parents_table_cells(config: dict, template: dict) -> int:
    """Cells of the joint table ``ParentReference._sliced`` builds for a
    template: its dimensions' and its filter fields' row counts (a field
    the seed draws counts as the configuration's largest set field)."""
    fields = {d["field"] for d in template.get("dims", ())}
    fields |= {f for f, _ in template.get("filter", ())}
    drawn = max(field_rows(spec) for spec in config["fields"].values()
                if spec["type"] == "set")
    return math.prod(field_rows(config["fields"][f])
                     if f in config["fields"] else drawn for f in fields)


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("cell", PAIRS, ids=lambda c: c["name"])
def test_old_and_new_reference_agree(cell, seed):
    config, mix = config_and_mix(cell)
    sayable = {name for name, t in mix["templates"].items()
               if t["kind"] not in traffic.WRITE_KINDS and plain(t)}
    fits = {name for name in sayable if parents_table_cells(
        config, mix["templates"][name]) <= PARENTS_TABLE_MAX}
    for name in sorted(sayable - fits):
        print(f"{cell['name']}: template {name} left out for size: the "
              f"parent's joint table would have "
              f"{parents_table_cells(config, mix['templates'][name]):,} "
              f"cells, over 2^20")
    cols = datagen.make_columns(config, seed, 2,
                                traffic.fields_read(mix, config))
    old, new = ParentReference(config, cols), Reference(config, cols)
    compared = collections.Counter()
    writes, reads = [], []
    for client in traffic.clients(mix, config, 2, seed, "agree"):
        for _ in range(2 * len(client.group["rotation"])):
            name, _pql, sem = client.next()
            if sem["kind"] in traffic.WRITE_KINDS:
                writes.append(sem)
            elif name in fits:
                reads.append((name, sem))
    if writes:
        # beside the mix's own writes (random columns, which seldom meet a
        # read's rows), writes aimed at the reads: on a column where only
        # the read's other row holds (it counts anew), on one where
        # neither does (both rows written: it counts when both landed),
        # on one where both do (nothing changes)
        for _, sem in reads[:60]:
            (f, a), (g, b) = sem["filter"]
            has_f, has_g = cols[f] == a, cols[g] == b
            for where, fields in ((~has_f & has_g, [(f, a)]),
                                  (~has_f & ~has_g, [(f, a), (g, b)]),
                                  (has_f & has_g, [(g, b)])):
                at = np.nonzero(where)[0]
                if at.size:
                    writes += [{"field": x, "row": r, "column": int(at[0])}
                               for x, r in fields]
    # writes land one a second from t = 100; every third is never
    # acknowledged, the others two seconds after they were sent
    for i, w in enumerate(writes):
        for ref in (old, new):
            ref.note_write(w["field"], w["row"], w["column"], 100.0 + i,
                           None if i % 3 == 2 else 102.0 + i)
    for name, sem in reads:
        assert new.answer(sem) == old.answer(sem), (name, sem)
        compared[name] += 1
        if sem["kind"] == "count" and writes:
            terms = [tuple(t) for t in sem["filter"]]
            for t in (99.0, 100.5 + len(writes) / 2, 200.0 + len(writes)):
                assert (new.count(terms, acked_before=t)
                        == old.count(terms, acked_before=t))
                assert (new.count(terms, sent_before=t)
                        == old.count(terms, sent_before=t))
    for w in writes:
        for acked_only in (True, False):
            assert (new.row_count(w["field"], w["row"], acked_only)
                    == old.row_count(w["field"], w["row"], acked_only))
    # every template the parent could say and could hold was compared
    assert set(compared) == fits and min(compared.values()) >= 2
    if cell["traffic"] == "point-rw":
        assert len(writes) >= 200
        moved = [sem for _, sem in reads if new.count(
            [tuple(t) for t in sem["filter"]], sent_before=1e9)
            > new.count([tuple(t) for t in sem["filter"]], acked_before=0.0)]
        assert len(moved) >= 40  # the bounds are apart where writes landed
        # a write that a read's two rows make count: the bounds differ
        w = writes[0]
        other = next(f for f in cols if f != w["field"])
        col = w["column"]
        terms = [(w["field"], w["row"]), (other, int(cols[other][col]))]
        if int(cols[w["field"]][col]) != w["row"]:
            assert (new.count(terms, sent_before=101.0)
                    == new.count(terms, acked_before=101.0) + 1
                    == old.count(terms, sent_before=101.0))
    # every table the shipped templates need is one the parent built too:
    # every field they filter by is an axis, none lists or masks columns,
    # so their reference costs what it did
    assert not new._lists and all(
        listed == () and set(named) <= set(fields)
        for (_, named), (fields, listed) in new._plans.items())
    assert {k: v.shape for k, v in new._hist.items()} == {
        k: v.shape for k, v in old._hist.items()}


# ------------------------------------------------- the 13 SSB query forms

# SSB's lineorder, denormalised, at a toy's sizes: 20 cities in 5 nations
# in 2 regions a side, 120 brands in 15 categories of 3 manufacturers, 84
# months in 7 years; the hierarchies are the source's (a city names its
# nation and region), as the next configuration's will be. ``lo_ext_disc``
# stands for lo_extendedprice * lo_discount and ``lo_profit`` for
# lo_revenue - lo_supplycost, held as int fields.
def _side(prefix: str) -> dict:
    city = f"{prefix}_city"
    return {city: {"type": "set", "uniform": 20},
            f"{prefix}_nation": {"type": "set", "rows": 5,
                                 "derived": {"field": city, "div": 4}},
            f"{prefix}_region": {"type": "set", "rows": 2,
                                 "derived": {"field": city, "div": 10}}}


SSB_TOY = {
    "name": "ssb-toy", "index": "lineorder", "shards": 2, "fields": {
        **_side("c"), **_side("s"),
        "p_brand1": {"type": "set", "uniform": 120},
        "p_category": {"type": "set", "rows": 15,
                       "derived": {"field": "p_brand1", "div": 8}},
        "p_mfgr": {"type": "set", "rows": 3,
                   "derived": {"field": "p_brand1", "div": 40}},
        "d_yearmonthnum": {"type": "set", "uniform": 84},
        "d_year": {"type": "set", "rows": 7,
                   "derived": {"field": "d_yearmonthnum", "div": 12}},
        "d_weeknuminyear": {"type": "set", "uniform": 53},
        "lo_discount": {"type": "int", "min": 0, "max": 10,
                        "uniform_int": [0, 10]},
        "lo_quantity": {"type": "int", "min": 1, "max": 50,
                        "uniform_int": [1, 50]},
        "lo_revenue": {"type": "int", "min": 900, "max": 104949,
                       "uniform_int": [900, 104949]},
        "lo_ext_disc": {"type": "int", "min": 0, "max": 550000,
                        "uniform_int": [0, 550000]},
        "lo_profit": {"type": "int", "min": -50000, "max": 100000,
                      "uniform_int": [-50000, 100000]}}}
_YEARS = {"field": "d_year", "limit": 6}           # 1992 .. 1997
_LAST_TWO = {"field": "d_year", "previous": 4, "limit": 2}   # 1997, 1998
_REGION = {"R": {"row_of": "c_region"}}
_DISCOUNT = {"LO": {"int": [1, 8]}, "HI": {"affine": ["LO", 1, 2]}}
_QUANTITY = {"QL": {"int": [1, 41]}, "QH": {"affine": ["QL", 1, 9]}}
_TWO_CITIES = {"A": {"row_of": "c_city", "span": 5},
               "B": {"affine": ["A", 1, 4]}}
_BOTH_SIDES = [["c_city", {"in": ["A", "B"]}], ["s_city", {"in": ["A", "B"]}]]
_CITIES_BY_YEAR = [{"field": "c_city"}, {"field": "s_city"}, _YEARS]


def _brands(limit: int, offset: int) -> dict:
    return {"kind": "groupby", "sum": "lo_revenue",
            "dims": [{"field": "d_year"},
                     {"field": "p_brand1", "previous": "P", "limit": limit}],
            "filter": ([["p_category", "C"]] if limit == 8 else [])
            + [["s_region", "R"]],
            "draw": {"C": {"row_of": "p_category"},
                     "R": {"row_of": "s_region"},
                     "P": {"affine": ["C", 8, offset - 1]}}}


SSB_FORMS = {
    "q1_1": {"kind": "sum", "sum": "lo_ext_disc",
             "filter": [["d_year", "Y"], ["lo_discount", {"between": [1, 3]}],
                        ["lo_quantity", {"lt": 25}]],
             "draw": {"Y": {"row_of": "d_year"}}},
    "q1_2": {"kind": "sum", "sum": "lo_ext_disc",
             "filter": [["d_yearmonthnum", "M"],
                        ["lo_discount", {"between": ["LO", "HI"]}],
                        ["lo_quantity", {"between": ["QL", "QH"]}]],
             "draw": {"M": {"row_of": "d_yearmonthnum"}, **_DISCOUNT,
                      **_QUANTITY}},
    "q1_3": {"kind": "sum", "sum": "lo_ext_disc",
             "filter": [["d_weeknuminyear", "W"], ["d_year", "Y"],
                        ["lo_discount", {"between": ["LO", "HI"]}],
                        ["lo_quantity", {"between": ["QL", "QH"]}]],
             "draw": {"W": {"row_of": "d_weeknuminyear"},
                      "Y": {"row_of": "d_year"}, **_DISCOUNT, **_QUANTITY}},
    "q2_1": _brands(8, 0), "q2_2": _brands(3, 2), "q2_3": _brands(1, 6),
    "q3_1": {"kind": "groupby", "sum": "lo_revenue",
             "dims": [{"field": "c_nation"}, {"field": "s_nation"}, _YEARS],
             "filter": [["c_region", "R"], ["s_region", "R"]],
             "draw": _REGION},
    "q3_2": {"kind": "groupby", "sum": "lo_revenue", "dims": _CITIES_BY_YEAR,
             "filter": [["c_nation", "N"], ["s_nation", "N"]],
             "draw": {"N": {"row_of": "c_nation"}}},
    "q3_3": {"kind": "groupby", "sum": "lo_revenue", "dims": _CITIES_BY_YEAR,
             "filter": _BOTH_SIDES, "draw": _TWO_CITIES},
    "q3_4": {"kind": "groupby", "sum": "lo_revenue", "dims": _CITIES_BY_YEAR,
             "filter": _BOTH_SIDES + [["d_yearmonthnum", "M"]],
             "draw": {**_TWO_CITIES, "M": {"row_of": "d_yearmonthnum"}}},
    "q4_1": {"kind": "groupby", "sum": "lo_profit",
             "dims": [{"field": "d_year"}, {"field": "c_nation"}],
             "filter": [["c_region", "R"], ["s_region", "R"],
                        ["p_mfgr", {"in": [0, 1]}]],
             "draw": _REGION},
    "q4_2": {"kind": "groupby", "sum": "lo_profit",
             "dims": [_LAST_TWO, {"field": "s_nation"},
                      {"field": "p_category"}],
             "filter": [["c_region", "R"], ["s_region", "R"],
                        ["p_mfgr", {"in": [0, 1]}]],
             "draw": _REGION},
    "q4_3": {"kind": "groupby", "sum": "lo_profit",
             "dims": [_LAST_TWO, {"field": "s_city"}, {"field": "p_brand1"}],
             "filter": [["c_region", "R"], ["s_nation", "N"],
                        ["p_category", "C"]],
             "draw": {**_REGION, "N": {"row_of": "s_nation"},
                      "C": {"row_of": "p_category"}}},
}
SSB_MIX = {"name": "q-flight", "preload": False, "templates": SSB_FORMS,
           "groups": [{"name": "analysts", "clients": 1, "loop": "closed",
                       "rotation": list(SSB_FORMS)}]}
PER_FORM = 2


def served(config: dict, mix: dict, data_dir: str, seed: int) -> tuple:
    """The configuration's columns written as the harness writes a
    cell's, opened by a holder under the one-chip executor on the CPU;
    ``PER_FORM`` requests of each template of the mix, answered once.
    Returns the columns and [(template, PQL, semantic form, parsed call,
    the executor's answer as JSON)]."""
    from pilosa_tpu.executor import Executor
    from pilosa_tpu.executor.result import result_to_json
    from pilosa_tpu.pql.parser import parse
    from pilosa_tpu.storage import Holder

    fields = traffic.fields_read(mix, config)
    assert set(fields) == set(config["fields"])  # range-only fields too
    cols = datagen.make_columns(config, seed, 2, fields)
    os.makedirs(data_dir)
    datagen.write_data_dir(data_dir, config, cols, 2, fields)
    holder = Holder(data_dir).open()
    executor = Executor(holder)
    asked = []
    try:
        for name in mix["templates"]:
            only = dict(mix["groups"][0], rotation=[name])
            client = traffic.Client(mix, config, 2, only, 0, seed, "q")
            for _ in range(PER_FORM):
                _, pql, sem = client.next()
                (call,) = parse(pql).calls  # the program's parser takes it
                (got,) = executor.execute(config["index"], pql)
                asked.append((name, pql, sem, call, result_to_json(got)))
    finally:
        holder.close()
    return cols, asked


@pytest.fixture(scope="module")
def flight(tmp_path_factory):
    assert len(SSB_FORMS) == 13
    return served(SSB_TOY, SSB_MIX,
                  str(tmp_path_factory.mktemp("ssb-toy") / "data"), SEEDS[0])


def test_a_server_on_the_toys_files_answers_its_templates_as_the_reference(
        tmp_path):
    """``bench_helpers.py``'s toy, the new kinds among its six templates:
    text and semantic form agree because one is rendered from the other,
    and the program answers the text as the reference answers the form."""
    cols, asked = served(TOY_CONFIG, TOY_MIX, str(tmp_path / "data"),
                         SEEDS[1])
    ref = Reference(TOY_CONFIG, cols)
    assert len(asked) == PER_FORM * len(TOY_MIX["templates"]) == 12
    for name, pql, sem, _call, got in asked:
        assert pql == traffic.render(sem)
        assert got == ref.answer(sem) and got, (name, pql)


@pytest.mark.parametrize("name", list(SSB_FORMS))
def test_ssb_form_is_parsed_and_answered_as_the_reference_does(flight, name):
    cols, asked = flight
    ref = Reference(SSB_TOY, cols)
    mine = [a for a in asked if a[0] == name]
    assert len(mine) == PER_FORM
    for _, pql, sem, call, got in mine:
        assert call.name == {"sum": "Sum", "groupby": "GroupBy"}[sem["kind"]]
        want = ref.answer(sem)
        assert got == want, pql
        assert want and (sem["kind"] != "sum" or want["count"] > 0), pql


def test_the_forms_are_written_as_ssb_writes_them(flight):
    _, asked = flight
    text = {name: pql for name, pql, *_ in asked}
    assert text["q1_1"].startswith("Sum(Intersect(Row(d_year=")
    assert text["q1_1"].endswith(
        "Row(lo_discount >< [1, 3]), Row(lo_quantity < 25)), "
        "field=\"lo_ext_disc\")")
    assert "Row(lo_quantity >< [" in text["q1_2"]
    assert "Row(d_weeknuminyear=" in text["q1_3"]
    assert text["q3_1"].startswith(
        "GroupBy(Rows(c_nation), Rows(s_nation), Rows(d_year, limit=6), "
        "filter=Intersect(Row(c_region=")
    assert "Intersect(Union(Row(c_city=" in text["q3_3"]
    assert "Union(Row(s_city=" in text["q3_4"]
    assert "Row(d_yearmonthnum=" in text["q3_4"]
    assert "Union(Row(p_mfgr=0), Row(p_mfgr=1))" in text["q4_1"]
    assert text["q4_3"].startswith(
        "GroupBy(Rows(d_year, previous=4, limit=2), Rows(s_city), "
        "Rows(p_brand1), filter=Intersect(Row(c_region=")
    assert text["q4_3"].endswith("aggregate=Sum(field=\"lo_profit\"))")


def test_sampled_control_is_not_correct_on_any_of_the_13(flight, capsys):
    """``run.py``'s own ``verify`` over the executor's answers reads 0
    wrong on every form; over the ``sampled`` control's (the reference
    on half the shards, doubled) it reads wrong on every form."""
    run = import_run()
    cols, asked = flight
    answers = [(types.SimpleNamespace(template=name, sem=sem, t_sent=0.0,
                                      t_done=0.0), got)
               for name, _, sem, _, got in asked]
    ref = Reference(SSB_TOY, cols)
    sound = run.Checks()
    run.verify(ref, False, answers, [], {}, {}, sound, list(SSB_FORMS))
    assert sound.ok is False  # 2 a form, and a run wants 8 to compare
    lines = [l for l in capsys.readouterr().out.splitlines()
             if l.startswith("check answers.")]
    assert len(lines) == 13 and all(
        f"compared={PER_FORM} wrong=0 limit=0" in l for l in lines)
    broken = run.Checks("control[sampled]")
    c_answers, _, _ = run.control("sampled", SSB_TOY, cols, 2, answers, [],
                                  {}, {})
    run.verify(ref, False, c_answers, [], {}, {}, broken, list(SSB_FORMS))
    lines = [l for l in capsys.readouterr().out.splitlines()
             if l.startswith("control[sampled] answers.")]
    assert len(lines) == 13 and broken.ok is False
    assert all(f"wrong={PER_FORM} " in l for l in lines), lines


# --------------------------------------- Q4.3 at SSB's published row counts

Q43 = {"name": "ssb-q43", "index": "lineorder", "shards": 2, "fields": {
    "d_year": {"type": "set", "uniform": 7},
    "s_city": {"type": "set", "uniform": 250},
    "s_nation": {"type": "set", "rows": 25,
                 "derived": {"field": "s_city", "div": 10}},
    "c_region": {"type": "set", "uniform": 5},
    "p_brand1": {"type": "set", "uniform": 1000},
    "p_category": {"type": "set", "rows": 25,
                   "derived": {"field": "p_brand1", "div": 40}},
    "lo_profit": {"type": "int", "min": 0, "max": 10000000,
                  "uniform_int": [0, 10000000]}}}
Q43_CELLS = 7 * 250 * 1000


class _CountingNumpy:
    """``numpy`` with its functions' calls counted and the arrays they
    return measured: what ``reference.py`` builds through
    ``np.<function>``."""

    def __init__(self):
        self.largest = 0
        self.calls = collections.Counter()

    def __getattr__(self, name):
        attr = getattr(np, name)
        if not callable(attr) or isinstance(attr, type):
            return attr

        def measured(*args, **kwargs):
            self.calls[name] += 1
            out = attr(*args, **kwargs)
            for a in out if isinstance(out, tuple) else (out,):
                if isinstance(a, np.ndarray):
                    self.largest = max(self.largest, a.size)
            return out
        return measured


def test_q4_3_allocates_nothing_larger_than_its_dimensions(monkeypatch):
    """``d_year, s_city, p_brand1`` under ``c_region, s_nation,
    p_category``: the parent's joint table is 7 x 250 x 1000 x 5 x 25 x 25
    = 5.5e9 cells, 44 GB, and cannot be built; the table is 1.75 M cells
    whatever the filter names."""
    cols = datagen.make_columns(Q43, SEEDS[2], 2, list(Q43["fields"]))
    sem = {"kind": "groupby", "sum": "lo_profit",
           "dims": [{"field": "d_year", "previous": 4, "limit": 2},
                    {"field": "s_city"}, {"field": "p_brand1"}],
           "filter": [("c_region", 1), ("s_nation", 24), ("p_category", 3)]}
    assert Q43_CELLS * 5 * 25 * 25 > 5e9 and Q43_CELLS > reference.FOLD_CELLS
    counting = _CountingNumpy()
    monkeypatch.setattr(reference, "np", counting)
    ref = Reference(Q43, cols)
    tracemalloc.start()
    try:
        got = ref.answer(sem)
        _, peak = tracemalloc.get_traced_memory()
    finally:
        tracemalloc.stop()
    assert CHUNK < counting.largest <= Q43_CELLS
    # a handful of tables of 1.75 M int64 cells and chunk-sized keys
    assert peak < 12 * 8 * Q43_CELLS
    sel = ((cols["c_region"] == 1) & (cols["s_nation"] == 24)
           & (cols["p_category"] == 3) & (cols["d_year"] >= 5))
    assert sum(g["count"] for g in got) == int(sel.sum()) > 50
    assert sum(g["sum"] for g in got) == int(cols["lo_profit"][sel].sum())
    g = got[len(got) // 2]
    year, city, brand = (x["rowID"] for x in g["group"])
    one = sel & (cols["d_year"] == year) & (cols["s_city"] == city) & (
        cols["p_brand1"] == brand)
    assert g["count"] == int(one.sum()) and city // 10 == 24
    assert [x["field"] for x in g["group"]] == ["d_year", "s_city",
                                                "p_brand1"]
    keys = [tuple(x["rowID"] for x in g["group"]) for g in got]
    assert keys == sorted(keys)  # as the server orders its groups


def test_constants_cost_no_table_of_their_own_in_any_order(monkeypatch):
    """Q1.2's form draws its month among 84 and its two windows among
    8 x 41. All three fields are axes of one table of 84 x 11 x 50 cells
    (an int field by its value above ``min``), built once for the counts
    and once for the sums, whatever the constants and the order they are
    asked in. With room for one of the three, the columns are listed once
    by the two others and each request tabulates its own. (A shipped
    template neither lists nor masks:
    ``test_old_and_new_reference_agree``.)"""
    cols = datagen.make_columns(SSB_TOY, SEEDS[1], 2,
                                traffic.fields_read(SSB_MIX, SSB_TOY))
    only = dict(SSB_MIX["groups"][0], rotation=["q1_2"])
    client = traffic.Client(SSB_MIX, SSB_TOY, 2, only, 0, SEEDS[1], "m")
    sems = [client.next()[2] for _ in range(40)]
    windows = {str(s["filter"][1:]) for s in sems}
    assert 20 < len(windows) <= 40
    sems += sems[:20]  # constants come again, as in a window
    counting = _CountingNumpy()
    monkeypatch.setattr(reference, "np", counting)
    ref = Reference(SSB_TOY, cols)
    want = [ref.answer(s) for s in sems]
    chunks = 2  # shards: a table over every column is a bincount a chunk
    assert counting.calls["bincount"] == chunks * 2 and not ref._lists
    assert counting.largest <= CHUNK
    for s, w in zip(sems[:5], want):
        month, (_, disc), (_, qty) = s["filter"]
        sel = ((cols["d_yearmonthnum"] == month[1])
               & (cols["lo_discount"] >= disc["between"][0])
               & (cols["lo_discount"] <= disc["between"][1])
               & (cols["lo_quantity"] >= qty["between"][0])
               & (cols["lo_quantity"] <= qty["between"][1]))
        assert w == {"value": int(cols["lo_ext_disc"][sel].sum()),
                     "count": int(sel.sum())} and w["count"] > 100
    # room for the discount alone: month and quantity list the columns
    monkeypatch.setattr(reference, "FOLD_CELLS", 11)
    counting.calls.clear()
    ref = Reference(SSB_TOY, cols)
    assert [ref.answer(s) for s in reversed(sems)] == want[::-1]
    assert list(ref._lists) == [("d_yearmonthnum", "lo_quantity")]
    # one for each chunk's list, one a request's counts, one its sums
    assert counting.calls["bincount"] == chunks + 2 * len(sems)
    assert counting.calls["argsort"] == chunks
