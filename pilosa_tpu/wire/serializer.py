"""Serializer between executor results and protobuf wire messages.

Reference: encoding/proto Serializer (SURVEY.md §2 #16). The JSON path
(result_to_json) stays canonical; this maps the same result objects to
QueryResponse protos for clients negotiating application/x-protobuf.
"""

from __future__ import annotations

import numpy as np

from pilosa_tpu.executor.result import (
    GroupCount,
    GroupCounts,
    Pair,
    RowResult,
    ValCount,
)
from pilosa_tpu.utils import as_int_list
from pilosa_tpu.wire import pb2

RESULT_NIL = 0
RESULT_ROW = 1
RESULT_PAIRS = 2
RESULT_COUNT = 3
RESULT_CHANGED = 4
RESULT_VALCOUNT = 5
RESULT_GROUPS = 6
RESULT_ROW_IDS = 7
RESULT_ROW_KEYS = 8


def _attrs_to_proto(m, attrs: dict) -> None:
    for k, v in sorted(attrs.items()):
        a = m.add()
        a.key = k
        if isinstance(v, bool):
            a.type, a.bool_value = 3, v
        elif isinstance(v, int):
            a.type, a.int_value = 2, v
        elif isinstance(v, float):
            a.type, a.float_value = 4, v
        else:
            a.type, a.string_value = 1, str(v)


def attrs_from_proto(attrs) -> dict:
    out = {}
    for a in attrs:
        out[a.key] = {
            1: a.string_value, 2: a.int_value, 3: a.bool_value, 4: a.float_value,
        }.get(a.type, a.string_value)
    return out


def encode_results(results, trace: dict | None = None) -> bytes:
    """``trace``: a finished span subtree (dict) from a traced remote
    sub-query, carried back to the coordinator as QueryResponse.trace_json."""
    import json as _json

    p = pb2()
    resp = p.QueryResponse()
    for res in results:
        qr = resp.results.add()
        _encode_result(qr, res)
    if trace is not None:
        resp.trace_json = _json.dumps(trace, separators=(",", ":"))
    return resp.SerializeToString()


def _encode_result(qr, res) -> None:
    if res is None:
        qr.type = RESULT_NIL
    elif isinstance(res, RowResult):
        qr.type = RESULT_ROW
        if res.keys is not None:
            qr.row.keys.extend(res.keys)
        else:
            qr.row.columns.extend(int(c) for c in res.columns().tolist())
        _attrs_to_proto(qr.row.attrs, res.attrs)
        if res.column_attrs:
            for entry in res.column_attrs:
                cs = qr.column_attrs.add()
                cs.id = int(entry["id"])
                _attrs_to_proto(cs.attrs, entry["attrs"])
    elif isinstance(res, bool):
        qr.type = RESULT_CHANGED
        qr.changed = res
    elif isinstance(res, int):
        qr.type = RESULT_COUNT
        qr.n = res
    elif isinstance(res, ValCount):
        qr.type = RESULT_VALCOUNT
        qr.val_count.value = res.value
        qr.val_count.count = res.count
    elif isinstance(res, list) and res and isinstance(res[0], Pair):
        qr.type = RESULT_PAIRS
        for pair in res:
            pp = qr.pairs.add()
            pp.id = pair.id
            pp.count = pair.count
            if pair.key is not None:
                pp.key = pair.key
    elif (isinstance(res, (list, GroupCounts)) and res
          and isinstance(res[0], GroupCount)):
        qr.type = RESULT_GROUPS
        for g in res:
            gg = qr.groups.add()
            gg.count = g.count
            if g.sum is not None:
                gg.has_sum = True
                gg.sum = g.sum
            for entry in g.group:
                fr = gg.group.add()
                fr.field = entry["field"]
                if "rowKey" in entry:
                    fr.row_key = entry["rowKey"]
                else:
                    fr.row_id = entry["rowID"]
    elif isinstance(res, list) and res and isinstance(res[0], str):
        qr.type = RESULT_ROW_KEYS
        qr.row_keys.extend(res)
    elif isinstance(res, (list, GroupCounts)):  # an empty GroupBy too
        qr.type = RESULT_ROW_IDS
        qr.row_ids.extend(int(r) for r in res)
    else:
        qr.type = RESULT_NIL


def encode_error(message: str) -> bytes:
    p = pb2()
    resp = p.QueryResponse()
    resp.err = message
    return resp.SerializeToString()


def decode_query_request(data: bytes):
    """Returns (pql, shards, remote, opts) — opts holds the true
    request-level result options under their URL-param names."""
    p = pb2()
    req = p.QueryRequest()
    req.ParseFromString(data)
    opts = {}
    if req.column_attrs:
        opts["columnAttrs"] = True
    if req.exclude_columns:
        opts["excludeColumns"] = True
    if req.exclude_row_attrs:
        opts["excludeRowAttrs"] = True
    return (
        req.query,
        list(req.shards) if req.shards else None,
        req.remote,
        opts,
    )


def decode_import_request(data: bytes):
    p = pb2()
    req = p.ImportRequest()
    req.ParseFromString(data)
    # numpy straight from the repeated fields: the import path converts
    # to arrays anyway, and round-tripping 50k-element Python int lists
    # costs more than the protobuf parse itself
    n = len(req.row_ids)
    return (
        np.fromiter(req.row_ids, np.uint64, count=n),
        np.fromiter(req.column_ids, np.uint64, count=len(req.column_ids)),
        list(req.timestamps) or None,
        req.clear,
    )


def decode_import_value_request(data: bytes):
    p = pb2()
    req = p.ImportValueRequest()
    req.ParseFromString(data)
    return (
        np.fromiter(req.column_ids, np.uint64,
                    count=len(req.column_ids)),
        np.fromiter(req.values, np.int64, count=len(req.values)),
        req.clear,
    )


# ------------------------------------------------------- request encoders
#
# The internal client's side of the negotiated wire (reference: every
# node-to-node hop is protobuf — SURVEY.md §2 #16-17). Varint-packed id
# lists are ~2-5x smaller than JSON int lists; bulk set-bit imports go
# smaller still via the octet-stream roaring path (api._route_import).


def encode_import_request(index: str, field: str, rows, columns,
                          timestamps=None, clear: bool = False) -> bytes:
    p = pb2()
    req = p.ImportRequest()
    req.index, req.field, req.clear = index, field, clear
    req.row_ids.extend(as_int_list(rows))
    req.column_ids.extend(as_int_list(columns))
    if timestamps is not None:
        req.timestamps.extend("" if t is None else str(t) for t in timestamps)
    return req.SerializeToString()


def encode_import_value_request(index: str, field: str, columns, values,
                                clear: bool = False) -> bytes:
    p = pb2()
    req = p.ImportValueRequest()
    req.index, req.field, req.clear = index, field, clear
    req.column_ids.extend(as_int_list(columns))
    req.values.extend(as_int_list(values))
    return req.SerializeToString()


def encode_batch_request(items) -> bytes:
    """``items``: [(index, pql, shards), ...] — optionally a 4th element
    carrying the item's X-Pilosa-Trace context — → BatchQueryRequest
    bytes (the wave-batched internal hop — one request per node per
    wave)."""
    p = pb2()
    req = p.BatchQueryRequest()
    for item in items:
        unit = req.queries.add()
        unit.index = item[0]
        unit.query = item[1]
        unit.shards.extend(int(s) for s in item[2])
        if len(item) > 3 and item[3]:
            unit.trace = item[3]
    return req.SerializeToString()


def decode_batch_request(data: bytes) -> list[tuple]:
    p = pb2()
    req = p.BatchQueryRequest()
    req.ParseFromString(data)
    return [(u.index, u.query, list(u.shards), u.trace or None)
            for u in req.queries]


def encode_batch_responses(outcomes) -> bytes:
    """``outcomes``: one entry per batched sub-query, either
    ``("ok", [raw results])`` (optionally a 3rd element: the item's span
    subtree) or ``("err", message, status)`` → BatchQueryResponse bytes
    (positional with the request)."""
    import json as _json

    p = pb2()
    batch = p.BatchQueryResponse()
    for outcome in outcomes:
        resp = batch.responses.add()
        if outcome[0] == "ok":
            for res in outcome[1]:
                _encode_result(resp.results.add(), res)
            if len(outcome) > 2 and outcome[2] is not None:
                resp.trace_json = _json.dumps(outcome[2],
                                              separators=(",", ":"))
        else:
            resp.err = outcome[1]
            resp.status = int(outcome[2])
    return batch.SerializeToString()


def decode_batch_responses(data: bytes) -> list[dict]:
    """BatchQueryResponse bytes → one dict per sub-query, the same
    shapes query_node returns: ``{"results": [...]}`` on success (plus a
    ``"trace"`` key for traced items), ``{"error": ..., "status": ...}``
    on a per-item error."""
    p = pb2()
    batch = p.BatchQueryResponse()
    batch.ParseFromString(data)
    out = []
    for resp in batch.responses:
        if resp.err:
            out.append({"error": resp.err, "status": int(resp.status) or None})
        else:
            out.append(_response_results_json(resp))
    return out


# ------------------------------------------------- anti-entropy fast path
#
# Batched sync manifests + multi-block deltas (docs/OPERATIONS.md). The
# control halves (manifest, block list) negotiate protobuf like every
# other internal hop; the delta payloads themselves ride a raw
# octet-stream of length-prefixed roaring bitmaps — the framing helpers
# below are protobuf-independent so a JSON-only peer still moves binary
# block data.


def encode_sync_manifest(entries) -> bytes:
    """``entries``: [(field, view, shard, [(block, checksum), ...]), ...]
    → SyncManifest bytes (one response for a whole index)."""
    p = pb2()
    manifest = p.SyncManifest()
    for field, view, shard, blocks in entries:
        fm = manifest.fragments.add()
        fm.field, fm.view, fm.shard = field, view, int(shard)
        for block, checksum in blocks:
            bc = fm.blocks.add()
            bc.block, bc.checksum = int(block), checksum
    return manifest.SerializeToString()


def decode_sync_manifest(data: bytes):
    p = pb2()
    manifest = p.SyncManifest()
    manifest.ParseFromString(data)
    return [
        (fm.field, fm.view, int(fm.shard),
         [(int(bc.block), bc.checksum) for bc in fm.blocks])
        for fm in manifest.fragments
    ]


def encode_sync_blocks_request(index: str, fragments) -> bytes:
    """``fragments``: [(field, view, shard, [block, ...]), ...] →
    SyncBlocksRequest bytes (one POST fetches every wanted block)."""
    p = pb2()
    req = p.SyncBlocksRequest()
    req.index = index
    for field, view, shard, blocks in fragments:
        fl = req.fragments.add()
        fl.field, fl.view, fl.shard = field, view, int(shard)
        fl.blocks.extend(int(b) for b in blocks)
    return req.SerializeToString()


def decode_sync_blocks_request(data: bytes):
    p = pb2()
    req = p.SyncBlocksRequest()
    req.ParseFromString(data)
    return req.index, [
        (fl.field, fl.view, int(fl.shard), [int(b) for b in fl.blocks])
        for fl in req.fragments
    ]


def encode_block_frames(payloads) -> bytes:
    """Length-prefixed concatenation of roaring payloads (the delta
    response body): ``!I`` byte length then the payload, in request
    order. Pure struct framing — works without the protobuf runtime."""
    import struct

    parts = []
    for payload in payloads:
        parts.append(struct.pack("!I", len(payload)))
        parts.append(payload)
    return b"".join(parts)


def decode_block_frames(data: bytes) -> list[bytes]:
    """Inverse of encode_block_frames; raises ValueError on a truncated
    or over-long stream (a torn response must not silently drop the tail
    blocks of a repair)."""
    import struct

    out = []
    offset = 0
    n = len(data)
    while offset < n:
        if offset + 4 > n:
            raise ValueError("truncated block frame header")
        (length,) = struct.unpack_from("!I", data, offset)
        offset += 4
        if offset + length > n:
            raise ValueError("truncated block frame payload")
        out.append(data[offset:offset + length])
        offset += length
    return out


def decode_results_json(data: bytes) -> dict:
    """Parse a QueryResponse into the SAME dict shapes the JSON surface
    emits (executor/result.py to_json), so callers reduce remote partials
    identically whichever encoding the hop negotiated."""
    p = pb2()
    resp = p.QueryResponse()
    resp.ParseFromString(data)
    if resp.err:
        return {"error": resp.err}
    return _response_results_json(resp)


def _response_results_json(resp) -> dict:
    """The result-decoding body shared by single and batched responses."""
    import json as _json

    trace = None
    raw_trace = resp.trace_json
    if raw_trace:
        try:
            trace = _json.loads(raw_trace)
        except ValueError:
            trace = None  # malformed subtree degrades to untraced
    out = []
    for qr in resp.results:
        t = qr.type
        if t == RESULT_ROW:
            row: dict = {"attrs": attrs_from_proto(qr.row.attrs)}
            if qr.row.keys:
                row["keys"] = list(qr.row.keys)
            else:
                row["columns"] = list(qr.row.columns)
            if qr.column_attrs:
                row["columnAttrs"] = [
                    {"id": cs.id, "attrs": attrs_from_proto(cs.attrs)}
                    for cs in qr.column_attrs
                ]
            out.append(row)
        elif t == RESULT_PAIRS:
            out.append([
                {"id": pp.id, "count": pp.count, **({"key": pp.key} if pp.key else {})}
                for pp in qr.pairs
            ])
        elif t == RESULT_COUNT:
            out.append(int(qr.n))
        elif t == RESULT_CHANGED:
            out.append(bool(qr.changed))
        elif t == RESULT_VALCOUNT:
            out.append({"value": qr.val_count.value, "count": qr.val_count.count})
        elif t == RESULT_GROUPS:
            groups = []
            for gg in qr.groups:
                g: dict = {
                    "group": [
                        {"field": fr.field, "rowKey": fr.row_key}
                        if fr.row_key else {"field": fr.field, "rowID": fr.row_id}
                        for fr in gg.group
                    ],
                    "count": gg.count,
                }
                if gg.has_sum:
                    g["sum"] = gg.sum
                groups.append(g)
            out.append(groups)
        elif t == RESULT_ROW_IDS:
            out.append(list(qr.row_ids))
        elif t == RESULT_ROW_KEYS:
            out.append(list(qr.row_keys))
        else:
            out.append(None)
    envelope = {"results": out}
    if trace is not None:
        envelope["trace"] = trace
    return envelope
