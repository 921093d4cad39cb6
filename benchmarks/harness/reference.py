"""The plain reference: numpy over the generated columns.

No roaring, no JAX, nothing of the program. Every answer the mixes ask
for is a count, a sum or a ranking over value frequencies, so the
reference tabulates: it counts (or sums an int field) by a key of row
ids. The key is made of the dimensions and, while the table stays under
``FOLD_CELLS`` cells, of the fields the filter names (an int field by
its value above ``min``): such a table is built once for the template
and every constant drawn picks rows of it (what every mix shipped before
the SSB flights needs, and a comparison on an int field of few values).
A table never has more cells than that, or than its dimensions' own row
counts multiply to. What the filter names beyond that is not five more
axes: the columns are listed once in the order of those fields' joint
value, a request takes the stretches its constants admit and tabulates
those columns alone; a field of too many values for either is a mask
over the columns taken. No table made for some constants is kept, so
the order answers are asked in costs nothing. Writes (``Set``) are kept
beside the columns as extra bits with the times they were sent and
acknowledged, so a read that ran while writes were landing is held to
the two states it may lawfully have seen.

A filter term is ``(field, spec)`` as ``traffic.py`` writes it: a row id,
``{"in": rows}``, or for an int field ``{"lt": v}`` or
``{"between": [lo, hi]}``.
"""

from __future__ import annotations

import numpy as np

from harness.datagen import SHARD_WIDTH, field_rows

CHUNK = SHARD_WIDTH
# cells a table may grow to by taking filter fields into its key
FOLD_CELLS = 1 << 20
# joint values the filter's other fields may have for the columns to be
# listed by them (a 16-bit key: a chunk's order is a radix sort)
LIST_VALUES = 1 << 16


def parse_term(term) -> tuple:
    """(field, op, constants): ``in`` with a sorted tuple of row ids,
    ``lt`` with its value, ``between`` with both ends."""
    field, spec = term
    if not isinstance(spec, dict):
        return field, "in", (int(spec),)
    (op, v), = spec.items()
    if op == "in":
        return field, "in", tuple(sorted({int(r) for r in v}))
    if op == "between":
        return field, "between", (int(v[0]), int(v[1]))
    if op == "lt":
        return field, "lt", int(v)
    raise ValueError(f"unknown filter term {spec!r} on {field}")


def admits(values, op: str, v):
    """Which of ``values`` (an array or one number) a parsed term admits."""
    if op == "in":
        return values == v[0] if len(v) == 1 else np.isin(values, v)
    if op == "between":
        return (values >= v[0]) & (values <= v[1])
    return values < v


class Reference:
    def __init__(self, config: dict, columns: dict[str, np.ndarray]):
        self.config = config
        self.columns = columns
        self.n_columns = len(next(iter(columns.values())))
        # (fields, weight) -> table over every column: one a template,
        # kept for the run
        self._hist: dict[tuple, np.ndarray] = {}
        # fields -> _listed's answer
        self._lists: dict[tuple, list] = {}
        # (dimensions, the filter's fields) -> _plan's answer
        self._plans: dict[tuple, tuple] = {}
        # (field, row) -> {column: [(t_sent, t_acked)]}; t_acked is None
        # for a write that was sent and never acknowledged
        self.writes: dict[tuple, dict] = {}

    # --------------------------------------------------------------- tables

    def _values(self, field: str) -> tuple[int, int]:
        """A field's least value and how many it has: a set field's row
        ids, an int field's ``min`` to ``max``."""
        spec = self.config["fields"][field]
        if spec["type"] == "int":
            return spec["min"], spec["max"] - spec["min"] + 1
        return 0, field_rows(spec)

    def _key(self, fields: tuple, at, n: int) -> np.ndarray:
        """The joint value of ``fields`` on the ``n`` columns ``at`` (a
        slice or an array of columns), the first field the widest step."""
        sizes = [self._values(f)[1] for f in fields]
        key_t = np.uint16 if int(np.prod(sizes)) <= 1 << 16 else np.int64
        key = np.zeros(n, key_t)
        for f, d in zip(fields, sizes):
            first = self._values(f)[0]
            v = self.columns[f][at]
            key *= key_t(d)
            np.add(key, v - first if first else v, out=key, casting="unsafe")
        return key

    def joint(self, fields: tuple, weight: str | None = None,
              cols: np.ndarray | None = None, masks: tuple = ()) -> np.ndarray:
        """Counts (or sums of int field ``weight``) for every combination
        of values of ``fields``: an array with one axis per field. Over
        every column, or over ``cols`` alone, and of those the ones every
        parsed term of ``masks`` admits."""
        whole = cols is None and not masks
        if whole and (fields, weight) in self._hist:
            return self._hist[fields, weight]
        dims = [self._values(f)[1] for f in fields]
        cells = int(np.prod(dims))
        total = np.zeros(cells, np.int64)
        for lo in range(0, self.n_columns if cols is None else len(cols),
                        CHUNK):
            at = (slice(lo, min(lo + CHUNK, self.n_columns)) if cols is None
                  else cols[lo:lo + CHUNK])
            if masks and cols is None:
                at = np.arange(at.start, at.stop)
            for f, op, v in masks:
                at = at[admits(self.columns[f][at], op, v)]
            n = at.stop - at.start if isinstance(at, slice) else len(at)
            key = self._key(fields, at, n)
            if weight is None:
                total += np.bincount(key, minlength=cells)
            else:
                # <= 2^20 values under 2^31 each: exact in float64
                total += np.bincount(key, weights=self.columns[weight][at],
                                     minlength=cells).astype(np.int64)
        table = total.reshape(dims)
        if whole:
            self._hist[fields, weight] = table
        return table

    def _plan(self, dims: tuple, named: tuple) -> tuple:
        """For a template's dimensions and the fields its filter names:
        the fields its table is keyed by (the dimensions, then what folds
        into the key) and the fields its columns are listed by. What is
        in neither is a mask."""
        cells = int(np.prod([self._values(f)[1] for f in dims]))
        listed_cells = 1
        folded, listed = [], []
        # narrowest first and by name, so the same fields fold whatever
        # order the terms come in, and a pair is tabulated once
        for f in sorted(set(named) - set(dims),
                        key=lambda f: (self._values(f)[1], f)):
            n = self._values(f)[1]
            if cells * n <= FOLD_CELLS:
                folded.append(f)
                cells *= n
            elif listed_cells * n <= LIST_VALUES:
                listed.append(f)
                listed_cells *= n
        return dims + tuple(sorted(folded)), tuple(sorted(listed))

    def _listed(self, fields: tuple) -> list:
        """A chunk at a time: its columns in the order of ``fields``'
        joint value, and where in that order each value's columns start."""
        if fields not in self._lists:
            cells = int(np.prod([self._values(f)[1] for f in fields]))
            lists = []
            for lo in range(0, self.n_columns, CHUNK):
                hi = min(lo + CHUNK, self.n_columns)
                key = self._key(fields, slice(lo, hi), hi - lo)
                # stable on 16-bit keys is a radix sort
                order = (np.argsort(key, kind="stable") + lo).astype(np.uint32)
                starts = np.concatenate(([0], np.cumsum(
                    np.bincount(key, minlength=cells))))
                lists.append((order, starts))
            self._lists[fields] = lists
        return self._lists[fields]

    def _columns_of(self, fields: tuple, admitted: dict) -> np.ndarray:
        """The columns whose value in each of ``fields`` is one that
        ``admitted[field]`` (a flag a value) admits."""
        values = np.zeros(1, np.int64)
        for f in fields:
            values = (values[:, None] * len(admitted[f])
                      + np.flatnonzero(admitted[f])).reshape(-1)
        return np.concatenate(
            [order[starts[k]:starts[k + 1]]
             for order, starts in self._listed(fields) for k in values]
            or [np.zeros(0, np.uint32)])

    def _sliced(self, dims: list[str], terms: list, weight=None) -> np.ndarray:
        """Table over ``dims`` (an axis each, whole) restricted to
        ``terms``: a dimension's rows that a term on it leaves out read 0."""
        parsed = [parse_term(t) for t in terms]
        shape = tuple(dims), tuple(sorted({t[0] for t in parsed}))
        if shape not in self._plans:
            self._plans[shape] = self._plan(*shape)
        fields, listed = self._plans[shape]
        admitted: dict = {}  # field -> a flag a value: every term admits it
        masks = []
        for f, op, v in parsed:
            if f in fields or f in listed:
                first, n = self._values(f)
                ok = admits(np.arange(first, first + n), op, v)
                admitted[f] = admitted[f] & ok if f in admitted else ok
                if not (listed and f in dims):
                    continue
            # a mask; and columns taken one by one are thinned by a term
            # on a dimension too, before they are keyed
            masks.append((f, op, v))
        cols = self._columns_of(listed, admitted) if listed else None
        table = self.joint(fields, weight, cols, tuple(masks))
        index = [slice(None)] * len(fields)
        for axis, f in enumerate(fields):
            if f not in admitted:
                continue
            rows = np.flatnonzero(admitted[f])
            if axis < len(dims):
                picked = np.zeros_like(table)
                at = [slice(None)] * table.ndim
                at[axis] = rows
                picked[tuple(at)] = table[tuple(at)]
                table = picked
            elif len(rows) == 1:
                index[axis] = rows[0]
            else:
                table = table.take(rows, axis=axis).sum(axis=axis,
                                                        keepdims=True)
                index[axis] = 0
        return table[tuple(index)]

    # ------------------------------------------------------------- answers

    def count(self, terms: list, sent_before: float | None = None,
              acked_before: float | None = None) -> int:
        """|intersection of the terms' rows|. With writes in play,
        ``acked_before`` counts only writes acknowledged before that
        time (the least a read sent then may see) and ``sent_before``
        those sent before it (the most one may see)."""
        base = int(self._sliced([], terms))
        return base + self._written(terms, sent_before, acked_before)

    def _written(self, terms, sent_before, acked_before) -> int:
        if not self.writes or not terms:
            return 0
        parsed = [parse_term(t) for t in terms]

        def landed(w) -> bool:
            t_sent, t_acked = w
            if acked_before is not None:
                return t_acked is not None and t_acked < acked_before
            return sent_before is None or t_sent < sent_before

        # per term, the columns on which a landed write set one of its rows
        wrote = [np.fromiter(
            {col for r in v for col, ws in self.writes.get((f, r), {}).items()
             if any(map(landed, ws))} if op == "in" else (), np.int64)
            for f, op, v in parsed]
        cols = np.unique(np.concatenate(wrote))
        # a column counts anew where every term holds, by the column's own
        # value or by such a write, and the values alone did not do
        held = [admits(self.columns[f][cols], op, v) for f, op, v in parsed]
        holds = [h | np.isin(cols, w) for h, w in zip(held, wrote)]
        return int(np.count_nonzero(np.logical_and.reduce(holds)
                                    & ~np.logical_and.reduce(held)))

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
        row_lists = []
        for d in dims:
            rows = np.nonzero(self.joint((d["field"],)))[0]
            if d.get("previous") is not None:
                rows = rows[rows > d["previous"]]
            if d.get("limit"):
                rows = rows[:d["limit"]]
            row_lists.append(rows)
        paged = np.ix_(*row_lists)
        counts = self._sliced(names, terms)[paged]
        sums = None
        if sum_field is not None:
            sums = self._sliced(names, terms, sum_field)[paged]
        out = []
        # the groups that hold a column, in the order of their row ids
        for key in zip(*np.nonzero(counts)):
            item = {"group": [{"field": f, "rowID": int(rows[k])}
                              for f, rows, k in zip(names, row_lists, key)],
                    "count": int(counts[key])}
            if sums is not None:
                item["sum"] = int(sums[key])
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
