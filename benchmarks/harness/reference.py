"""The plain reference: numpy over the generated columns.

No roaring, no JAX, nothing of the program. Every answer the mixes ask
for is a count, a sum or a ranking over joint value frequencies, so the
reference keeps one joint histogram per set of fields (built chunk by
chunk, memoised) and reads every answer off it. Writes (``Set``) are
kept beside the columns as extra bits with the times they were sent and
acknowledged, so a read that ran while writes were landing is held to
the two states it may lawfully have seen.
"""

from __future__ import annotations

import numpy as np

from harness.datagen import SHARD_WIDTH, field_rows

CHUNK = SHARD_WIDTH


class Reference:
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
