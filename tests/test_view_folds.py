"""A view answers "which rows are non-empty" and "which rows rank" once
per version of it (ISSUE 50): ``View.rows_fold`` / ``View.topn_fold`` keep
the fold over the fragments that ``Executor._rows_ids`` and
``_submit_topn`` used to make a request, under ``View.version``, which
every change to a fragment's bitmap or ranked cache moves after the
change is readable. Every served answer here is compared with the answer
of a request that names its shards (it walks the fragments by rule and
keeps nothing) and with the rows read straight from the fragments.
"""

import sys
import threading
import time

import numpy as np
import pytest

from cluster_helpers import make_cluster, req, uri
from pilosa_tpu.executor import Executor
from pilosa_tpu.parallel import DistExecutor, make_mesh
from pilosa_tpu.roaring import OP_ADD, OP_REMOVE
from pilosa_tpu.shardwidth import SHARD_WIDTH
from pilosa_tpu.storage import Holder
from pilosa_tpu.storage.view import View
from pilosa_tpu.utils.tracing import plan_metrics

BUILDERS = ["local", "mesh"]
N_SHARDS = 5
QUERIES = [
    "Rows(f)",
    "Rows(f, previous=3, limit=4)",
    "Rows(f, previous=100)",
    "GroupBy(Rows(f))",
    "GroupBy(Rows(f), Rows(g))",
    "TopN(f, n=3)",
    "TopN(f, n=1)",
]


def executor(holder, builder):
    if builder == "local":
        return Executor(holder)
    return DistExecutor(holder, make_mesh(n_devices=4))


def fill(holder, rng, n_shards=N_SHARDS, rows=8):
    idx = holder.create_index("i")
    f, g = idx.create_field("f"), idx.create_field("g")
    for shard in range(n_shards):
        for col in rng.choice(200, 30, replace=False).tolist():
            c = shard * SHARD_WIDTH + int(col)
            f.set_bit(int(rng.integers(0, rows)), c)
            g.set_bit(int(rng.integers(0, 3)), c)
    return idx


@pytest.fixture
def holder(tmp_path):
    h = Holder(str(tmp_path / "data")).open()
    yield h
    h.close()


def plain(result):
    """An answer as plain data, whatever the call."""
    (r,) = result
    if isinstance(r, list) and r and hasattr(r[0], "id"):
        return [(p.id, p.count) for p in r]
    if isinstance(r, list):
        return r
    return [(tuple(fr["rowID"] for fr in gc.group), gc.count) for gc in r]


def walked_rows(idx, field="f"):
    """The non-empty rows read straight from the fragments' bitmaps."""
    rows = set()
    for frag in idx.field(field).view("standard").fragments.values():
        rows.update((frag.bitmap.to_ids() >> np.uint64(20)).tolist())
    return sorted(int(r) for r in rows)


def check(ex, idx):
    """Every query served from the view equals the same query of a
    request that names every shard, which walks."""
    named = list(idx.available_shards())
    for pql in QUERIES:
        assert plain(ex.execute("i", pql)) == plain(
            ex.execute("i", pql, shards=named)), pql
    assert plain(ex.execute("i", "Rows(f)")) == walked_rows(idx)


# --------------------------------------------- (a) equivalence under writes

def op_set(ex, idx, rng):
    ex.execute("i", f"Set({int(rng.integers(0, N_SHARDS * SHARD_WIDTH))}, "
                    f"f={int(rng.integers(0, 40))})")


def op_clear(ex, idx, rng):
    frag = idx.field("f").view("standard").fragment(
        int(rng.integers(0, N_SHARDS)))
    ids = frag.bitmap.to_ids()
    bit = int(ids[int(rng.integers(0, ids.size))])
    ex.execute("i", f"Clear({frag.shard * SHARD_WIDTH + (bit & 0xFFFFF)}, "
                    f"f={bit >> 20})")


def op_clear_row(ex, idx, rng):
    ex.execute("i", f"ClearRow(f={int(rng.choice(walked_rows(idx)))})")


def op_store(ex, idx, rng):
    ex.execute("i", f"Store(Row(g={int(rng.integers(0, 3))}), "
                    f"f={int(rng.integers(40, 60))})")


def op_import(ex, idx, rng):
    shard = int(rng.integers(0, N_SHARDS))
    frag = idx.field("f").view("standard").fragment(shard, create=True)
    frag.bulk_import(rng.integers(60, 90, 50), rng.choice(5000, 50,
                                                          replace=False))


def op_recalculate(ex, idx, rng):
    frag = idx.field("f").view("standard").fragment(
        int(rng.integers(0, N_SHARDS)))
    # a ranked cache that drifted: the recount changes what top() says
    frag.row_cache.add(int(rng.integers(90, 99)), 10_000)
    frag._on_change()
    check(ex, idx)  # the drifted cache is served, by both paths alike
    frag.recalculate_cache()


def op_apply_recovered(ex, idx, rng):
    frag = idx.field("f").view("standard").fragment(
        int(rng.integers(0, N_SHARDS)))
    row = int(rng.integers(100, 120))
    frag.apply_recovered(OP_ADD, np.array([(row << 20) + 7], np.uint64))
    check(ex, idx)
    frag.apply_recovered(OP_REMOVE, frag.bitmap.to_ids()[:3])
    check(ex, idx)
    frag.recalculate_cache()


def op_create_fragment(ex, idx, rng):
    # the first write to a shard the index does not have yet
    shard = max(idx.available_shards()) + 1
    ex.execute("i", f"Set({shard * SHARD_WIDTH + 5}, "
                    f"f={int(rng.integers(120, 140))})")


def op_create_fragment_of_a_known_shard(ex, idx, rng):
    # field h alone gets the shard first, so f's view has no fragment
    # in a shard of the list; then it gets one and the shard set stays
    shard = max(idx.available_shards()) + 1
    h = idx.field("h") or idx.create_field("h")
    h.set_bit(0, shard * SHARD_WIDTH)
    check(ex, idx)
    before = list(idx.available_shards())
    ex.execute("i", f"Set({shard * SHARD_WIDTH + 9}, "
                    f"f={int(rng.integers(140, 160))})")
    assert idx.available_shards() == before


def op_remove_fragment(ex, idx, rng):
    view = idx.field("f").view("standard")
    view.remove_fragment(int(rng.choice(sorted(view.fragments))))


OPS = {f.__name__[3:]: f for f in (
    op_set, op_clear, op_clear_row, op_store, op_import, op_recalculate,
    op_apply_recovered, op_create_fragment,
    op_create_fragment_of_a_known_shard, op_remove_fragment)}


@pytest.mark.parametrize("op", list(OPS))
@pytest.mark.parametrize("builder", BUILDERS)
def test_a_change_is_in_the_next_answer(holder, builder, op):
    rng = np.random.default_rng(50)
    idx = fill(holder, rng)
    ex = executor(holder, builder)
    check(ex, idx)
    check(ex, idx)  # from the view's folds this time
    view = idx.field("f").view("standard")
    version = view.version
    OPS[op](ex, idx, rng)
    assert view.version > version
    check(ex, idx)


@pytest.mark.parametrize("seed", [1, 2, 3])
@pytest.mark.parametrize("builder", BUILDERS)
def test_a_seeded_interleaving_of_writes_and_reads(holder, builder, seed):
    rng = np.random.default_rng(seed)
    idx = fill(holder, rng)
    ex = executor(holder, builder)
    names = list(OPS)
    rng.shuffle(names)
    for name in names:
        if name == "remove_fragment" and len(
                idx.field("f").view("standard").fragments) < 3:
            continue
        OPS[name](ex, idx, rng)
        check(ex, idx)


# ------------------------------- (b) an acknowledged write, through a server

@pytest.fixture
def server(tmp_path):
    (s,) = make_cluster(tmp_path, 1)
    yield s
    s.close()


def query(s, pql):
    return req("POST", uri(s) + "/index/i/query", pql.encode())["results"][0]


def test_an_acknowledged_set_is_in_the_next_rows_and_topn(server):
    """Twenty rows of falling counts in one shard: TopN(n=1) takes the
    11 best of the fragment as candidates, so row 19 is outside them
    until acknowledged Sets promote it past every other row."""
    s = server
    idx = s.holder.create_index("i")
    f = idx.create_field("f")
    for row in range(20):
        for col in range(40 - row):
            f.set_bit(row, col)
    f.set_bit(0, SHARD_WIDTH)  # a second shard
    # the first served Set of a shard makes the existence field's
    # fragment there, and with it a new shard list: have that over with,
    # so that the Sets below change nothing but their own view
    assert query(s, "Set(200, f=0)") and query(s, f"Set({SHARD_WIDTH + 1}, f=0)")
    for _ in range(2):
        assert [r["id"] for r in query(s, "TopN(f, n=1)")] == [0]
        assert query(s, "Rows(f)") == list(range(20))
        assert len(query(s, "GroupBy(Rows(f))")) == 20
    shard_list = idx.available_shards()
    assert query(s, f"Set({SHARD_WIDTH + 3}, f=77)") is True
    assert query(s, "Rows(f)") == list(range(20)) + [77]
    assert len(query(s, "GroupBy(Rows(f))")) == 21
    for col in range(100, 130):
        assert query(s, f"Set({col}, f=19)") is True
    assert idx.available_shards() is shard_list
    assert query(s, "TopN(f, n=1)") == [{"id": 19, "count": 51}]
    assert query(s, "ClearRow(f=77)") is True
    assert query(s, "Rows(f)") == list(range(20))


def test_the_served_path_asks_the_view_and_a_second_request_walks_nothing(
        server):
    """The cluster executor of a node alone hands the local executor no
    shard list, so the view's folds are found again request after
    request; a request that names shards walks."""
    s = server
    fill(s.holder, np.random.default_rng(7))
    mix = ["GroupBy(Rows(f))", "GroupBy(Rows(f), Rows(g))", "TopN(f, n=3)"]
    for pql in mix:
        query(s, pql)
    before = plan_metrics()
    want = [query(s, pql) for pql in mix]
    after = plan_metrics()
    assert after["view_folds_total"] - before["view_folds_total"] == 4
    assert after["view_walks_total"] == before["view_walks_total"]
    named = [req("POST", uri(s) + "/index/i/query?shards=0,1,2,3,4",
                 pql.encode())["results"][0] for pql in mix]
    assert named == want
    assert plan_metrics()["view_walks_total"] == after["view_walks_total"] + 4
    text = req("GET", uri(s) + "/metrics", raw=True).decode()
    exported = plan_metrics()
    assert f"pilosa_tpu_plan_view_folds_total {exported['view_folds_total']}" \
        in text
    assert "# TYPE pilosa_tpu_plan_view_walks_total counter" in text
    assert req("GET", uri(s) + "/debug/vars")["plan"] == exported


def test_a_node_alone_routes_without_asking_a_shard(server, tmp_path):
    ce = server.api.executor
    assert ce._route("i", [3, 1, 2]) == ([3, 1, 2], [])
    (peer,) = make_cluster(tmp_path, 1, prefix="peer")
    try:
        from pilosa_tpu.parallel.cluster import Node

        ce.cluster.nodes["p0"] = Node("p0", uri(peer))
        ce.cluster._note_membership_changed_locked()
        local, groups = ce._route("i", list(range(32)))
        assert sorted(local + [s for _, g in groups for s in g]) == list(
            range(32))
        assert groups and local  # the hash ring splits 32 shards
    finally:
        del ce.cluster.nodes["p0"]
        ce.cluster._note_membership_changed_locked()
        peer.close()


# ------------------------------------------ (c) concurrent writers, a reader

def test_two_writers_of_two_fragments_never_leave_a_reader_behind(holder):
    """Writer k sets a new row in ITS fragment and only then says so; the
    reader notes what was said, asks, and must find every row said."""
    idx = fill(holder, np.random.default_rng(3), n_shards=2)
    f = idx.field("f")
    view = f.view("standard")
    ex = Executor(holder)
    said = [0, 0]
    stop = threading.Event()
    errors: list = []

    def writer(k):
        try:
            i = 0
            while not stop.is_set() and i < 900:
                i += 1
                assert f.set_bit(1000 * (k + 1) + i, k * SHARD_WIDTH + i)
                said[k] = i
        except Exception as e:  # noqa: BLE001 — reported by the test
            errors.append(e)

    def reader():
        try:
            while not stop.is_set():
                heard = list(said)
                rows = set(plain(ex.execute("i", "Rows(f)")))
                # phase 1 alone, as _submit_topn asks it: the recount of
                # a thousand candidates is not what is under test
                cands = set(view.topn_fold(idx.available_shards(), 10 ** 6,
                                           keep=True))
                for k in (0, 1):
                    want = {1000 * (k + 1) + i
                            for i in range(1, heard[k] + 1)}
                    assert want <= rows, sorted(want - rows)[:5]
                    assert want <= cands, sorted(want - cands)[:5]
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=writer, args=(k,)) for k in (0, 1)]
    threads.append(threading.Thread(target=reader))
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        time.sleep(2.0)
        stop.set()
        for t in threads:
            t.join(30)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert not errors, errors[0]
    assert min(said) > 20, said
    assert plain(ex.execute("i", "Rows(f)")) == walked_rows(idx)


def test_concurrent_touches_are_all_counted(holder):
    """A lost increment could put back a version some reader holds: the
    version rises once a touch, whoever touches. (CPython 3.12 switches
    threads at calls and backward jumps only, so a bare ``+= 1`` passes
    this too; the lock is what holds on an interpreter that does not.)"""
    idx = fill(holder, np.random.default_rng(4), n_shards=1)
    view = idx.field("f").view("standard")
    start = view.version
    n_threads, each = 8, 4000

    def touch():
        for _ in range(each):
            view.touch()

    threads = [threading.Thread(target=touch) for _ in range(n_threads)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert view.version == start + n_threads * each


def test_a_write_that_lands_during_a_walk_forces_one_more(holder,
                                                          monkeypatch):
    """The version is read before the walk: a write between the walk's
    visit of its fragment and the fold's being kept leaves the fold
    under an older version."""
    idx = fill(holder, np.random.default_rng(5), n_shards=3)
    f = idx.field("f")
    view = f.view("standard")
    ex = Executor(holder)
    real = View.fragment
    armed = [True]

    def fragment(self, shard, create=False):
        frag = real(self, shard, create)
        if armed[0] and self is view and shard == 2 and not create:
            armed[0] = False
            f.set_bit(500, 3)  # shard 0, already visited
        return frag

    monkeypatch.setattr(View, "fragment", fragment)
    assert 500 not in plain(ex.execute("i", "Rows(f)"))
    assert 500 in plain(ex.execute("i", "Rows(f)"))


# --------------------------------- (d) O(1), (e) named shards, (f) counters

class Visits:
    """Calls of ``View.fragment`` and the deltas of the two counters over
    a ``with`` block."""

    def __init__(self, monkeypatch):
        self.monkeypatch = monkeypatch

    def __enter__(self):
        self.fragments = 0
        real = View.fragment

        def fragment(view, shard, create=False):
            self.fragments += 1
            return real(view, shard, create)

        self.monkeypatch.setattr(View, "fragment", fragment)
        self.real = real
        self.before = plan_metrics()
        return self

    def __exit__(self, *exc):
        self.monkeypatch.setattr(View, "fragment", self.real)
        after = plan_metrics()
        self.folds = after["view_folds_total"] - self.before["view_folds_total"]
        self.walks = after["view_walks_total"] - self.before["view_walks_total"]


@pytest.mark.parametrize("n_shards", [8, 64])
@pytest.mark.parametrize("builder", BUILDERS)
def test_a_second_identical_request_visits_no_fragment(holder, builder,
                                                       n_shards, monkeypatch):
    idx = fill(holder, np.random.default_rng(6), n_shards=n_shards)
    ex = executor(holder, builder)
    mix = ["GroupBy(Rows(f), Rows(g))", "TopN(f, n=3)"]
    with Visits(monkeypatch) as first:
        want = [plain(ex.execute("i", pql)) for pql in mix]
    assert (first.folds, first.walks) == (3, 3)
    assert first.fragments >= 3 * n_shards
    for _ in range(2):
        with Visits(monkeypatch) as again:
            assert [plain(ex.execute("i", pql)) for pql in mix] == want
        assert (again.fragments, again.folds, again.walks) == (0, 3, 0)
    # another overfetch replaces TopN's one entry, and back again
    with Visits(monkeypatch) as other:
        ex.execute("i", "TopN(f, n=30)")
        ex.execute("i", "TopN(f, n=3)")
        ex.execute("i", "TopN(f, n=3)")
    assert (other.folds, other.walks) == (3, 2)
    assert len(idx.field("f").view("standard")._folds) == 2
    idx.field("f").set_bit(2, 1)
    with Visits(monkeypatch) as written:
        assert plain(ex.execute("i", mix[0])) != want[0]
    assert (written.folds, written.walks) == (2, 1)  # g's view stood still


@pytest.mark.parametrize("builder", BUILDERS)
def test_a_request_that_names_shards_walks_and_keeps_nothing(holder, builder,
                                                             monkeypatch):
    idx = fill(holder, np.random.default_rng(8))
    ex = executor(holder, builder)
    view = idx.field("f").view("standard")
    ex.execute("i", "Rows(f)")
    ex.execute("i", "TopN(f, n=3)")
    kept = dict(view._folds)
    assert sorted(kept) == ["rows", "topn"]
    for pql, kw in [("Rows(f)", {"shards": [0, 1]}),
                    ("TopN(f, n=3)", {"shards": [0, 1]}),
                    ("GroupBy(Rows(f))", {"shards": [2]}),
                    ("Options(Rows(f), shards=[0, 3])", {}),
                    ("Options(TopN(f, n=5), shards=[1])", {})]:
        with Visits(monkeypatch) as named:
            ex.execute("i", pql, **kw)
        assert (named.folds, named.walks) == (1, 1), pql
        assert named.fragments >= 1
    assert view._folds == kept
    assert all(view._folds[k] is kept[k] for k in kept)
    with Visits(monkeypatch) as again:
        assert plain(ex.execute("i", "Rows(f)")) == walked_rows(idx)
    assert (again.fragments, again.walks) == (0, 0)


def test_a_fold_is_the_views_own_and_a_caller_gets_a_list(holder):
    idx = fill(holder, np.random.default_rng(9))
    ex = Executor(holder)
    view = idx.field("f").view("standard")
    (first,) = ex.execute("i", "Rows(f)")
    first.append(10 ** 6)
    first.reverse()
    assert plain(ex.execute("i", "Rows(f)")) == walked_rows(idx)
    assert isinstance(view._folds["rows"][3], tuple)
    assert plain(ex.execute("i", "Rows(f, limit=-1)")) == walked_rows(idx)[:-1]
    assert plain(ex.execute("i", "Rows(f, previous=2, limit=-1)")) == [
        r for r in walked_rows(idx) if r > 2][:-1]
