"""Result types for query execution.

Reference: row.go (SURVEY.md §2 #2) — a Row is per-shard segments each
wrapping a bitmap, so cross-shard merges are cheap concatenation; plus the
pair/group shapes the executor reduces (a list of Pair for TopN; for
GroupBy one GroupCounts, the groups as columns, which renders itself to
JSON and makes a GroupCount per group only for a consumer that walks it).
"""

from __future__ import annotations

import json
from collections.abc import Sequence

import numpy as np

from pilosa_tpu.ops.packing import popcount_words, unpack_bits
from pilosa_tpu.shardwidth import SHARD_WIDTH
from pilosa_tpu.utils.tracing import (
    note_groupby_materialized,
    note_groupby_result,
)


class RowResult:
    """Query-result set of columns: shard → dense uint32 words (host)."""

    def __init__(self, segments: dict[int, np.ndarray] | None = None, attrs=None, keys=None):
        self.segments = segments or {}
        self.attrs = attrs or {}
        self.keys = keys  # translated column keys, when the index uses keys
        self.column_attrs = None  # [{"id": col, "attrs": {...}}] via Options(columnAttrs=true)

    def columns(self) -> np.ndarray:
        parts = [
            unpack_bits(words, offset=shard * SHARD_WIDTH)
            for shard, words in sorted(self.segments.items())
        ]
        if not parts:
            return np.empty(0, np.uint64)
        return np.concatenate(parts)

    def count(self) -> int:
        return sum(popcount_words(w) for w in self.segments.values())

    def merge(self, other: "RowResult") -> "RowResult":
        """Cross-node reduce: union segments (shards are disjoint across
        owners, so collisions only appear with replication — union is
        correct either way)."""
        out = dict(self.segments)
        for shard, words in other.segments.items():
            if shard in out:
                out[shard] = np.bitwise_or(out[shard], words)
            else:
                out[shard] = words
        return RowResult(out, {**other.attrs, **self.attrs})

    def to_json(self) -> dict:
        if self.keys is not None:
            out = {"attrs": self.attrs, "keys": self.keys}
        else:
            out = {"attrs": self.attrs, "columns": self.columns().tolist()}
        if self.column_attrs is not None:
            out["columnAttrs"] = self.column_attrs
        return out


class Pair:
    """TopN result element (reference Pair{ID, Count})."""

    __slots__ = ("id", "count", "key")

    def __init__(self, id: int, count: int, key: str | None = None):
        self.id = id
        self.count = count
        self.key = key

    def to_json(self) -> dict:
        d = {"id": self.id, "count": self.count}
        if self.key is not None:
            d["key"] = self.key
        return d

    def __eq__(self, other):
        if not isinstance(other, Pair):
            return NotImplemented
        return (self.id == other.id and self.count == other.count
                and self.key == other.key)

    def __hash__(self):
        # key is attached after construction for keyed fields; exclude it
        # so the hash is stable over the Pair's lifetime
        return hash((self.id, self.count))

    def __repr__(self) -> str:
        return f"Pair(id={self.id}, count={self.count}, key={self.key!r})"


class ValCount:
    """Sum/Min/Max result (reference ValCount{Val, Count})."""

    __slots__ = ("value", "count")

    def __init__(self, value: int, count: int):
        self.value = value
        self.count = count

    def to_json(self) -> dict:
        return {"value": self.value, "count": self.count}

    def __eq__(self, other):
        if not isinstance(other, ValCount):
            return NotImplemented
        return self.value == other.value and self.count == other.count

    def __hash__(self):
        return hash((self.value, self.count))

    def __repr__(self) -> str:
        return f"ValCount(value={self.value}, count={self.count})"


class GroupCount:
    """GroupBy result element (reference GroupCount; ``sum`` set when the
    call carries aggregate=Sum(...))."""

    __slots__ = ("group", "count", "sum")

    def __init__(self, group: list[dict], count: int, sum: int | None = None):
        self.group = group  # [{"field": name, "rowID": id}, ...]
        self.count = count
        self.sum = sum

    def to_json(self) -> dict:
        out = {"group": self.group, "count": self.count}
        if self.sum is not None:
            out["sum"] = self.sum
        return out

    def __eq__(self, other):
        if not isinstance(other, GroupCount):
            return NotImplemented
        return (self.group == other.group and self.count == other.count
                and self.sum == other.sum)

    # value-equal but holds a list; deliberately unhashable
    __hash__ = None

    def __repr__(self) -> str:
        return (f"GroupCount(group={self.group}, count={self.count}, "
                f"sum={self.sum})")


class GroupCounts(Sequence):
    """A GroupBy's whole answer as columns, in result order: group g is
    row ``rows[g, d]`` of field ``fields[d]`` for every dimension d, with
    ``counts[g]`` and, under aggregate=Sum(...), ``sums[g]``.
    ``row_keys[d]`` maps a keyed dimension's row ids to their keys (a row
    it lacks is emitted by id), None for an un-keyed dimension.

    ``json_bytes`` goes from the columns to the response bytes; as a
    sequence it equals the list of GroupCount it stands for and builds
    that list once, for the first consumer that indexes, iterates or
    compares it (the cluster merge, the internal wire, tests)."""

    __slots__ = ("fields", "rows", "counts", "sums", "row_keys", "_groups")

    def __init__(self, fields=(), rows=None, counts=None, sums=None,
                 row_keys=None):
        self.fields = list(fields)
        self.rows = (np.zeros((0, len(self.fields)), np.int64)
                     if rows is None else rows)
        self.counts = np.zeros(0, np.int64) if counts is None else counts
        self.sums = sums
        self.row_keys = row_keys or [None] * len(self.fields)
        self._groups: list[GroupCount] | None = None
        note_groupby_result()

    def groups(self) -> list[GroupCount]:
        """The per-group objects, built on first use."""
        if self._groups is None:
            note_groupby_materialized()
            dims = list(zip(self.fields, self.row_keys))
            counts = self.counts.tolist()
            sums = ([None] * len(counts) if self.sums is None
                    else self.sums.tolist())
            self._groups = [
                GroupCount(
                    [{"field": f, "rowID": r}
                     if keys is None or keys.get(r) is None
                     else {"field": f, "rowKey": keys[r]}
                     for (f, keys), r in zip(dims, row)],
                    c, sum=s)
                for row, c, s in zip(self.rows.tolist(), counts, sums)
            ]
        return self._groups

    def to_json(self) -> list[dict]:
        return [g.to_json() for g in self.groups()]

    def json_bytes(self) -> bytes:
        """``_dumps(self.to_json())`` byte for byte, without the objects:
        a ``{"field", "rowID"|"rowKey"}`` entry is rendered once a
        distinct row of its dimension, and a group is one string built
        from its entries, count and sum."""
        if not len(self.counts):
            return b"[]"
        columns = []
        for field, keys, column in zip(self.fields, self.row_keys,
                                       self.rows.T.tolist()):
            name = json.dumps(field)
            keys = keys or {}
            entries = {
                row: f'{{"field":{name},"rowID":{row}}}'
                if keys.get(row) is None else
                f'{{"field":{name},"rowKey":{json.dumps(keys[row])}}}'
                for row in set(column)
            }
            columns.append([entries[row] for row in column])
        groups = (columns[0] if len(columns) == 1
                  else map(",".join, zip(*columns)))
        counts = self.counts.tolist()
        if self.sums is None:
            out = [f'{{"group":[{g}],"count":{c}}}'
                   for g, c in zip(groups, counts)]
        else:
            out = [f'{{"group":[{g}],"count":{c},"sum":{s}}}'
                   for g, c, s in zip(groups, counts, self.sums.tolist())]
        return ("[" + ",".join(out) + "]").encode()

    def __len__(self) -> int:
        return len(self.counts)

    def __getitem__(self, i):
        return self.groups()[i]

    def __iter__(self):
        return iter(self.groups())

    def __eq__(self, other):
        if isinstance(other, GroupCounts):
            other = other.groups()
        if not isinstance(other, list):
            return NotImplemented
        return self.groups() == other

    __hash__ = None

    def __repr__(self) -> str:
        return f"GroupCounts({self.groups()!r})"


def result_to_json(res):
    """Serialize any executor result for the HTTP response envelope."""
    if isinstance(res, (RowResult, Pair, ValCount, GroupCount, GroupCounts)):
        return res.to_json()
    if isinstance(res, list):
        return [result_to_json(r) for r in res]
    if isinstance(res, np.integer):
        return int(res)
    return res


# ------------------------------------------------- pre-serialized responses
#
# The serving fast lane encodes hot result shapes (Count, Row, ValCount,
# GroupBy's GroupCounts) straight to compact-JSON bytes once, instead of
# dict-building then json.dumps per request (TopN's pairs still do
# that). RowResult encodings memoize ON the result
# object — the encoded-bytes cache keyed by result identity — so a wave of
# identical coalesced queries (server/pipeline.py dedupe) pays the
# segment-unpack + encode exactly once however many clients asked.


def _dumps(obj) -> bytes:
    return json.dumps(obj, separators=(",", ":")).encode()


def result_json_bytes(res) -> bytes:
    """Compact-JSON bytes of ``result_to_json(res)`` (exact same JSON
    value; whitespace-free encoding)."""
    if isinstance(res, bool):  # before int — bool subclasses int
        return b"true" if res else b"false"
    if isinstance(res, (int, np.integer)):
        return b"%d" % int(res)
    if isinstance(res, RowResult):
        cached = getattr(res, "_json_bytes", None)
        if cached is None:
            cached = res._json_bytes = _dumps(res.to_json())
        return cached
    if isinstance(res, ValCount):
        return b'{"value":%d,"count":%d}' % (res.value, res.count)
    if isinstance(res, GroupCounts):
        return res.json_bytes()
    return _dumps(result_to_json(res))


def results_json_bytes(results) -> bytes:
    """The whole ``{"results": [...]}`` response envelope as bytes."""
    return (b'{"results":['
            + b",".join(result_json_bytes(r) for r in results) + b"]}")
