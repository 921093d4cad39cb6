"""Protobuf wire-format tests: content negotiation on /query and imports
(reference encoding/proto + handler negotiation — SURVEY.md §2 #16)."""

import urllib.request

import numpy as np
import pytest

from pilosa_tpu import wire
from tests.test_http import node, node_api, req  # fixture reuse

requires_proto = pytest.mark.skipif(
    not wire.available(), reason="protoc/protobuf runtime unavailable"
)


def praw(method, url, body=None, content_type=None, accept=None):
    r = urllib.request.Request(url, data=body, method=method)
    if content_type:
        r.add_header("Content-Type", content_type)
    if accept:
        r.add_header("Accept", accept)
    with urllib.request.urlopen(r) as resp:
        return resp.read(), resp.headers.get("Content-Type")


@requires_proto
def test_query_protobuf_roundtrip(node):
    from pilosa_tpu.wire import pb2
    from pilosa_tpu.wire.serializer import (
        RESULT_CHANGED, RESULT_COUNT, RESULT_PAIRS, RESULT_ROW, RESULT_VALCOUNT,
    )

    req("POST", f"{node}/index/i", {})
    req("POST", f"{node}/index/i/field/f", {})
    req("POST", f"{node}/index/i/field/v",
        {"options": {"type": "int", "min": 0, "max": 100}})

    p = pb2()
    # protobuf request body + protobuf response
    qr = p.QueryRequest(query="Set(3, f=1) Set(5, f=1)")
    raw, ct = praw(
        "POST", f"{node}/index/i/query", qr.SerializeToString(),
        content_type="application/x-protobuf", accept="application/x-protobuf",
    )
    assert ct == "application/x-protobuf"
    resp = p.QueryResponse(); resp.ParseFromString(raw)
    assert [r.type for r in resp.results] == [RESULT_CHANGED] * 2
    assert all(r.changed for r in resp.results)

    req("POST", f"{node}/index/i/field/v/import-value",
        {"columns": [3, 5], "values": [10, 20]})

    qr = p.QueryRequest(
        query='Row(f=1) Count(Row(f=1)) TopN(f, n=1) Sum(field="v")'
    )
    raw, _ = praw(
        "POST", f"{node}/index/i/query", qr.SerializeToString(),
        content_type="application/x-protobuf", accept="application/x-protobuf",
    )
    resp = p.QueryResponse(); resp.ParseFromString(raw)
    row, count, topn, vc = resp.results
    assert row.type == RESULT_ROW and list(row.row.columns) == [3, 5]
    assert count.type == RESULT_COUNT and count.n == 2
    assert topn.type == RESULT_PAIRS and topn.pairs[0].count == 2
    assert vc.type == RESULT_VALCOUNT and (vc.val_count.value, vc.val_count.count) == (30, 2)


@requires_proto
def test_protobuf_request_json_response(node):
    from pilosa_tpu.wire import pb2

    req("POST", f"{node}/index/i", {})
    req("POST", f"{node}/index/i/field/f", {})
    p = pb2()
    qr = p.QueryRequest(query="Count(Row(f=1))")
    raw, ct = praw(
        "POST", f"{node}/index/i/query", qr.SerializeToString(),
        content_type="application/x-protobuf",
    )
    assert ct == "application/json"
    import json

    assert json.loads(raw) == {"results": [0]}


@requires_proto
def test_protobuf_import(node):
    from pilosa_tpu.wire import pb2

    req("POST", f"{node}/index/i", {})
    req("POST", f"{node}/index/i/field/f", {})
    p = pb2()
    imp = p.ImportRequest(row_ids=[1, 1, 2], column_ids=[10, 11, 10])
    out, _ = praw(
        "POST", f"{node}/index/i/field/f/import", imp.SerializeToString(),
        content_type="application/x-protobuf",
    )
    import json

    assert json.loads(out)["changed"] == 3
    assert req("POST", f"{node}/index/i/query", b"Count(Row(f=1))")["results"] == [2]

    vimp = p.ImportValueRequest(column_ids=[7], values=[42])
    req("POST", f"{node}/index/i/field/vv",
        {"options": {"type": "int", "min": 0, "max": 100}})
    out, _ = praw(
        "POST", f"{node}/index/i/field/vv/import-value", vimp.SerializeToString(),
        content_type="application/x-protobuf",
    )
    assert json.loads(out)["changed"] == 1


@requires_proto
def test_protobuf_error_response(node):
    from pilosa_tpu.wire import pb2

    req("POST", f"{node}/index/i", {})
    p = pb2()
    qr = p.QueryRequest(query="Row(missing=1)")
    r = urllib.request.Request(
        f"{node}/index/i/query", data=qr.SerializeToString(), method="POST")
    r.add_header("Content-Type", "application/x-protobuf")
    r.add_header("Accept", "application/x-protobuf")
    with pytest.raises(urllib.error.HTTPError) as e:
        urllib.request.urlopen(r)
    resp = p.QueryResponse(); resp.ParseFromString(e.value.read())
    assert "missing" in resp.err


@requires_proto
def test_groupby_and_keys_over_protobuf(node):
    from pilosa_tpu.wire import pb2
    from pilosa_tpu.wire.serializer import RESULT_GROUPS, RESULT_ROW

    req("POST", f"{node}/index/k", {"options": {"keys": True}})
    req("POST", f"{node}/index/k/field/likes", {"options": {"keys": True}})
    req("POST", f"{node}/index/k/query", b'Set("a", likes="x") Set("b", likes="x")')
    p = pb2()
    qr = p.QueryRequest(query='Row(likes="x")')
    raw, _ = praw("POST", f"{node}/index/k/query", qr.SerializeToString(),
                  content_type="application/x-protobuf",
                  accept="application/x-protobuf")
    resp = p.QueryResponse(); resp.ParseFromString(raw)
    assert resp.results[0].type == RESULT_ROW
    assert sorted(resp.results[0].row.keys) == ["a", "b"]


@requires_proto
def test_keyed_groupby_over_protobuf(node):
    from pilosa_tpu.wire import pb2
    from pilosa_tpu.wire.serializer import RESULT_GROUPS

    req("POST", f"{node}/index/g", {})
    req("POST", f"{node}/index/g/field/lang", {"options": {"keys": True}})
    req("POST", f"{node}/index/g/query",
        b'Set(1, lang="go") Set(2, lang="go") Set(2, lang="py")')
    p = pb2()
    qr = p.QueryRequest(query="GroupBy(Rows(lang))")
    raw, _ = praw("POST", f"{node}/index/g/query", qr.SerializeToString(),
                  content_type="application/x-protobuf",
                  accept="application/x-protobuf")
    resp = p.QueryResponse(); resp.ParseFromString(raw)
    assert resp.results[0].type == RESULT_GROUPS
    got = {g.group[0].row_key: g.count for g in resp.results[0].groups}
    assert got == {"go": 2, "py": 1}
    assert all(g.group[0].field == "lang" for g in resp.results[0].groups)


@requires_proto
def test_import_request_encoders_roundtrip():
    """Client-side request encoders invert the server-side decoders — the
    routed-import protobuf hop (parallel/client.py import_bits/values)."""
    from pilosa_tpu.wire.serializer import (
        decode_import_request,
        decode_import_value_request,
        encode_import_request,
        encode_import_value_request,
    )

    body = encode_import_request(
        "i", "f", [1, 2, 3], [10, 20, 1 << 40],
        timestamps=["2019-01-15T00:00", None, ""], clear=True,
    )
    rows, cols, ts, clear = decode_import_request(body)
    # decoders return numpy (the import path consumes arrays directly)
    assert rows.dtype == np.uint64 and rows.tolist() == [1, 2, 3]
    assert cols.tolist() == [10, 20, 1 << 40]
    assert ts == ["2019-01-15T00:00", "", ""]  # None -> "" (= no timestamp)
    assert clear is True

    body = encode_import_value_request("i", "v", [5, 6], [-7, 1 << 40],
                                       clear=False)
    cols, values, clear = decode_import_value_request(body)
    assert cols.tolist() == [5, 6]
    assert values.dtype == np.int64 and values.tolist() == [-7, 1 << 40]
    assert clear is False


@requires_proto
def test_decode_results_json_matches_json_shapes():
    """decode_results_json (the remote-partial decoder) emits exactly the
    shapes executor/result.py to_json emits, for every result type the
    cluster reducer consumes."""
    import numpy as np

    from pilosa_tpu.executor.result import (
        GroupCount,
        Pair,
        RowResult,
        ValCount,
        result_to_json,
    )
    from pilosa_tpu.ops.packing import pack_bits
    from pilosa_tpu.wire.serializer import decode_results_json, encode_results

    row = RowResult({0: np.asarray(pack_bits(np.asarray([3, 17]), 1 << 20))})
    keyed = RowResult({}, keys=["alice", "bob"])
    results = [
        row, keyed, 42, True, None, ValCount(-5, 3),
        [Pair(1, 9), Pair(2, 4, key="k")],
        [GroupCount([{"field": "a", "rowID": 1},
                     {"field": "b", "rowKey": "x"}], 7, sum=-2)],
        [10, 20], ["r1", "r2"],
    ]
    got = decode_results_json(encode_results(results))["results"]
    want = [result_to_json(r) for r in results]
    # RowResult JSON carries attrs; the reducer reads columns/keys
    assert got[0]["columns"] == want[0]["columns"]
    assert got[1]["keys"] == want[1]["keys"]
    for g, w in zip(got[2:], want[2:]):
        assert g == w, (g, w)


@requires_proto
def test_column_attrs_survive_protobuf():
    """columnAttrs option output rides the wire (QueryResult.column_attrs)
    and decodes back to the JSON surface's columnAttrs shape."""
    import numpy as np

    from pilosa_tpu.executor.result import RowResult, result_to_json
    from pilosa_tpu.ops.packing import pack_bits
    from pilosa_tpu.wire.serializer import decode_results_json, encode_results

    row = RowResult({0: np.asarray(pack_bits(np.asarray([1, 2]), 1 << 20))})
    row.column_attrs = [
        {"id": 1, "attrs": {"city": "nyc", "n": 3, "vip": True}},
    ]
    (got,) = decode_results_json(encode_results([row]))["results"]
    assert got["columnAttrs"] == result_to_json(row)["columnAttrs"]


@requires_proto
def test_protobuf_request_carries_result_options(node):
    """Protobuf clients set request-level result options as QueryRequest
    fields (reference QueryRequest ColumnAttrs/ExcludeColumns/
    ExcludeRowAttrs), equivalent to the JSON surface's URL params."""
    import json

    from pilosa_tpu.wire import pb2

    req("POST", f"{node}/index/i", {})
    req("POST", f"{node}/index/i/field/f", {})
    req("POST", f"{node}/index/i/query",
        b'Set(1, f=1) SetColumnAttrs(1, city="nyc") '
        b'SetRowAttrs(f, 1, team="blue")')
    p = pb2()
    qr = p.QueryRequest(query="Row(f=1)", column_attrs=True,
                        exclude_row_attrs=True)
    raw, ct = praw(
        "POST", f"{node}/index/i/query", qr.SerializeToString(),
        content_type="application/x-protobuf",
    )
    assert ct == "application/json"
    (out,) = json.loads(raw)["results"]
    assert out["attrs"] == {}  # excludeRowAttrs
    assert out["columns"] == [1]
    assert out["columnAttrs"] == [{"id": 1, "attrs": {"city": "nyc"}}]


def test_stale_generated_module_is_regenerated_not_imported():
    """internal_pb2.py is keyed by a hash of internal.proto on its first
    line. A planted file without the current stamp must never be
    imported: with protoc it is regenerated, without protoc the layer
    reports unavailable (JSON only)."""
    import os
    import shutil
    import subprocess
    import sys

    gen = os.path.join(os.path.dirname(wire.__file__), "internal_pb2.py")
    with open(gen, "w") as f:
        f.write("# source-sha256: 0000\nraise RuntimeError('stale module')\n")
    code = (
        "from pilosa_tpu import wire\n"
        "p = wire.pb2()\n"
        "print('none' if p is None else p.QueryRequest.__name__)\n"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        timeout=120,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    if shutil.which("protoc"):
        assert proc.stdout.strip() == "QueryRequest"
        with open(gen, "rb") as f:
            assert f.readline() == wire._stamp()
    else:
        assert proc.stdout.strip() == "none"
