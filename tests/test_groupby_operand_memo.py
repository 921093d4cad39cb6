"""A GroupBy level's packed operand is placed on the device(s) once per
distinct content (ISSUE 33): ``Executor._level_operand`` keeps what a
dense level placed and hands it to every later level of the same
candidates and scalars, on one device and on the mesh. Every answer here
is compared with a plain numpy group-by of the columns; the counter
``groupby_operand_placements_total`` and the operand stages
(``device.upload`` / ``device.replicate``) are read around every query.
"""

import re
import sys
import threading

import numpy as np
import pytest

from cluster_helpers import req, uri
from pilosa_tpu.executor import Executor, batch
from pilosa_tpu.executor import executor as ex_mod
from pilosa_tpu.parallel import DistExecutor, make_mesh
from pilosa_tpu.shardwidth import SHARD_WIDTH
from pilosa_tpu.storage import FieldOptions, Holder
from pilosa_tpu.utils.tracing import groupby_metrics, stage_metrics

N_SHARDS = 3
ROWS = {"f": 5, "g": 4}
VALUES = (0, 63)
BUILDERS = ["local", "mesh"]
STAGE = {"local": "device_upload_total", "mesh": "device_replicate_total"}


def fill(holder, rng, per_shard=40):
    """Index ``i``: set fields f and g, int field v; returns the columns."""
    idx = holder.create_index("i")
    f, g = idx.create_field("f"), idx.create_field("g")
    v = idx.create_field("v", FieldOptions(type="int", min=VALUES[0],
                                           max=VALUES[1]))
    col = np.concatenate([
        np.sort(rng.choice(SHARD_WIDTH, per_shard, replace=False))
        + shard * SHARD_WIDTH for shard in range(N_SHARDS)])
    columns = {"column": col,
               "f": rng.integers(0, ROWS["f"], col.size),
               "g": rng.integers(0, ROWS["g"], col.size),
               "v": rng.integers(VALUES[0], VALUES[1] + 1, col.size)}
    for c, fr, gr, val in zip(col.tolist(), columns["f"], columns["g"],
                              columns["v"]):
        f.set_bit(int(fr), c)
        g.set_bit(int(gr), c)
        v.set_value(c, int(val))
    idx.mark_columns_exist(col.tolist())
    return columns


@pytest.fixture(scope="module")
def data(tmp_path_factory):
    holder = Holder(str(tmp_path_factory.mktemp("opmemo") / "data")).open()
    columns = fill(holder, np.random.default_rng(33))
    yield holder, columns
    holder.close()


def executor(holder, builder):
    """A fresh executor (an empty memo) over the shared holder; the mesh
    one on four of tier-1's eight virtual devices."""
    if builder == "local":
        return Executor(holder)
    return DistExecutor(holder, make_mesh(n_devices=4))


def numpy_groupby(columns, dims, keep=None, with_sum=False):
    """{(row of each dim): (count, sum of v or None)} from the columns."""
    keep = np.ones(columns["column"].size, bool) if keep is None else keep
    out: dict = {}
    for i in np.flatnonzero(keep):
        key = tuple(int(columns[d][i]) for d in dims)
        n, total = out.get(key, (0, 0))
        out[key] = (n + 1, total + int(columns["v"][i]))
    return {k: (n, total if with_sum else None)
            for k, (n, total) in out.items()}


def answer(ex, pql):
    (groups,) = ex.execute("i", pql)
    return {tuple(fr["rowID"] for fr in gc.group): (gc.count, gc.sum)
            for gc in groups}


class Around:
    """Deltas of the GroupBy counters and the operand stage of
    ``builder`` over a ``with`` block."""

    def __init__(self, builder):
        self.stage = STAGE[builder]

    def read(self):
        g = groupby_metrics()
        return np.array([g["operand_placements_total"],
                         g["level_programs_total"], g["levels_total"],
                         stage_metrics()[self.stage]])

    def __enter__(self):
        self.before = self.read()
        return self

    def __exit__(self, *exc):
        (self.placements, self.programs, self.levels,
         self.staged) = (self.read() - self.before).tolist()


# template: (PQL, the numpy reference's arguments)
TEMPLATES = {
    "1dim-leaf-sum": (
        'GroupBy(Rows(f), filter=Row(g=1), aggregate=Sum(field="v"))',
        lambda c: (("f",), c["g"] == 1, True)),
    "2dims-nofilter": (
        "GroupBy(Rows(f), Rows(g))", lambda c: (("f", "g"), None, False)),
    "2dims-intersect": (
        "GroupBy(Rows(f), Rows(g), filter=Intersect(Row(g=2), Row(f=3)))",
        lambda c: (("f", "g"), (c["g"] == 2) & (c["f"] == 3), False)),
}


@pytest.mark.parametrize("template", list(TEMPLATES))
@pytest.mark.parametrize("builder", BUILDERS)
def test_second_identical_level_reuses_the_placed_array(data, builder,
                                                        template):
    holder, columns = data
    pql, ref = TEMPLATES[template]
    want = numpy_groupby(columns, *ref(columns))
    ex = executor(holder, builder)
    with Around(builder) as first:
        assert answer(ex, pql) == want and want
    assert (first.placements, first.programs, first.staged) == (1, 1, 1)
    (placed,) = ex._placed_operands.values()
    for _ in range(3):
        with Around(builder) as again:
            assert answer(ex, pql) == want
        assert (again.placements, again.programs, again.staged) == (0, 1, 0)
    assert list(ex._placed_operands.values()) == [placed]
    assert next(iter(ex._placed_operands.values())) is placed
    if builder == "mesh":
        assert placed.sharding.is_fully_replicated
        assert len(placed.sharding.device_set) == 4


def test_the_memo_hands_the_call_the_array_it_holds(data, monkeypatch):
    """_operand_place is reached only on a miss, and what the program is
    called with on a hit is the entry itself."""
    holder, columns = data
    ex = executor(holder, "local")
    placed, called_with = [], []
    place = ex._operand_place
    monkeypatch.setattr(ex, "_operand_place",
                        lambda packed: placed.append(place(packed))
                        or placed[-1])
    program = ex._groupby_level_program

    def spy(*a, **kw):
        fn = program(*a, **kw)

        def call(*args):
            called_with.append(args[-1])
            return fn(*args)
        return call

    monkeypatch.setattr(ex, "_groupby_level_program", spy)
    for _ in range(3):
        answer(ex, "GroupBy(Rows(f), Rows(g))")
    assert len(placed) == 1
    assert all(op is placed[0] for op in called_with) and len(called_with) == 3
    assert np.asarray(placed[0]).tolist() == (
        np.repeat(np.arange(5), 4).tolist() + [-1] * 12
        + np.tile(np.arange(4), 5).tolist() + [-1] * 12)


# what makes two operands differ: the two queries, the entries they leave
DIFFERENT = {
    "scalars": ("GroupBy(Rows(f), filter=Row(v > 10))",
                "GroupBy(Rows(f), filter=Row(v > 20))",
                lambda c: (("f",), c["v"] > 10, False),
                lambda c: (("f",), c["v"] > 20, False)),
    "n_gather": ("GroupBy(Rows(f))", "GroupBy(Rows(f), Rows(g))",
                 lambda c: (("f",), None, False),
                 lambda c: (("f", "g"), None, False)),
    "sizes": ("GroupBy(Rows(f))", "GroupBy(Rows(g))",
              lambda c: (("f",), None, False),
              lambda c: (("g",), None, False)),
}


@pytest.mark.parametrize("what", list(DIFFERENT))
@pytest.mark.parametrize("builder", BUILDERS)
def test_different_content_is_a_different_entry(data, builder, what):
    holder, columns = data
    one, other, ref_one, ref_other = DIFFERENT[what]
    ex = executor(holder, builder)
    with Around(builder) as first:
        assert answer(ex, one) == numpy_groupby(columns, *ref_one(columns))
        assert answer(ex, other) == numpy_groupby(columns,
                                                  *ref_other(columns))
    assert (first.placements, first.staged) == (2, 2)
    assert len(ex._placed_operands) == 2
    a, b = (np.asarray(v).tolist() for v in ex._placed_operands.values())
    assert a != b
    with Around(builder) as again:
        assert answer(ex, other) == numpy_groupby(columns,
                                                  *ref_other(columns))
        assert answer(ex, one) == numpy_groupby(columns, *ref_one(columns))
    assert (again.placements, again.programs, again.staged) == (0, 2, 0)


@pytest.mark.parametrize("builder", BUILDERS)
def test_each_chunk_of_a_level_is_an_entry_of_its_own(data, builder,
                                                      monkeypatch):
    """20 candidates against a bound of 8: chunks of 8, 8 and 4 (padded to
    8), three programs and three entries; the same level again places
    nothing."""
    holder, columns = data
    monkeypatch.setattr(batch, "groupby_chunk_groups", lambda n_planes: 8)
    # three programs would be pruned by the rule: this is the dense level
    monkeypatch.setattr(ex_mod, "GROUPBY_DENSE_MAX_PROGRAMS", 3)
    want = numpy_groupby(columns, ("f", "g"))
    ex = executor(holder, builder)
    with Around(builder) as first:
        assert answer(ex, "GroupBy(Rows(f), Rows(g))") == want
    assert (first.placements, first.programs, first.levels) == (3, 3, 1)
    assert sorted(k[1:4] for k in ex._placed_operands) == [
        (0, 8, 8), (8, 16, 8), (16, 20, 8)]
    with Around(builder) as again:
        assert answer(ex, "GroupBy(Rows(f), Rows(g))") == want
    assert (again.placements, again.programs, again.staged) == (0, 3, 0)
    # the same candidates under another bound: the first 16 are a new
    # entry, the last 4 in 8 the one that is there
    monkeypatch.setattr(batch, "groupby_chunk_groups", lambda n_planes: 16)
    with Around(builder) as rechunked:
        assert answer(ex, "GroupBy(Rows(f), Rows(g))") == want
    assert (rechunked.placements, rechunked.programs) == (1, 2)
    assert sorted(k[1:4] for k in ex._placed_operands) == [
        (0, 8, 8), (0, 16, 16), (8, 16, 8), (16, 20, 8)]


def test_a_different_padded_width_is_a_different_entry(data):
    """The same five candidates padded to 8 and to 16 are two arrays."""
    holder, _ = data
    ex = executor(holder, "local")
    cand = ex_mod._dense_candidates((5,))
    narrow = ex._level_operand(cand, 0, 5, 8, (), (5,))
    wide = ex._level_operand(cand, 0, 5, 16, (), (5,))
    assert np.asarray(narrow).tolist() == [0, 1, 2, 3, 4, -1, -1, -1]
    assert np.asarray(wide).tolist() == [0, 1, 2, 3, 4] + [-1] * 11
    assert ex._level_operand(cand, 0, 5, 8, (), (5,)) is narrow
    assert ex._level_operand(cand, 0, 5, 16, (), (5,)) is wide
    assert len(ex._placed_operands) == 2
    # without a key: placed as ever, and forgotten
    before = groupby_metrics()["operand_placements_total"]
    loose = ex._level_operand(cand, 0, 5, 8, (), None)
    assert loose is not narrow
    assert np.asarray(loose).tolist() == np.asarray(narrow).tolist()
    assert groupby_metrics()["operand_placements_total"] == before + 1
    assert len(ex._placed_operands) == 2


# three queries, three operands: (PQL, the numpy reference's arguments)
THREE_OPERANDS = [
    ("GroupBy(Rows(f))", (("f",), None, False)),
    ("GroupBy(Rows(g))", (("g",), None, False)),
    ("GroupBy(Rows(f), Rows(g))", (("f", "g"), None, False)),
]


@pytest.mark.parametrize("builder", BUILDERS)
def test_the_bound_clears_whole_and_refills(data, builder):
    holder, columns = data
    ex = executor(holder, builder)
    ex.PLACED_OPERANDS_MAX = 2
    queries = THREE_OPERANDS
    with Around(builder) as first:
        for pql, ref in queries:
            assert answer(ex, pql) == numpy_groupby(columns, *ref)
    assert first.placements == 3
    # the third found the memo at its bound: cleared, then entered
    assert [k[0] for k in ex._placed_operands] == [(5, 4)]
    with Around(builder) as again:
        for pql, ref in queries:
            assert answer(ex, pql) == numpy_groupby(columns, *ref)
    # f enters beside f x g; g finds the bound (cleared, entered); f x g
    # went with the clear and is placed again
    assert again.placements == 3
    assert [k[0] for k in ex._placed_operands] == [(4,), (5, 4)]
    ex.PLACED_OPERANDS_MAX = 512
    with Around(builder) as roomy:
        for _ in range(2):
            for pql, ref in queries:
                assert answer(ex, pql) == numpy_groupby(columns, *ref)
    assert roomy.placements == 1 and len(ex._placed_operands) == 3


@pytest.mark.parametrize("builder", BUILDERS)
def test_pruned_levels_after_the_first_do_not_enter(data, builder,
                                                    monkeypatch):
    """On the pruned path a joint level's candidates are what the
    readback before it kept: one-shot, placed and forgotten. The marginal
    round (ISSUE 44) counts every row of each dimension alone, the entry
    a dense level of that one dimension uses too: before it only the
    first dimension had such a level (2 levels, 2 programs, 1 entry)."""
    holder, columns = data
    monkeypatch.setattr(ex_mod, "GROUPBY_DENSE_MAX_PROGRAMS", 0)
    want = numpy_groupby(columns, ("f", "g"))
    ex = executor(holder, builder)
    with Around(builder) as first:
        assert answer(ex, "GroupBy(Rows(f), Rows(g))") == want
    assert (first.levels, first.programs, first.placements) == (3, 3, 3)
    assert list(ex._placed_operands) == [
        ((ROWS["f"],), 0, ROWS["f"], 8, ()),
        ((ROWS["g"],), 0, ROWS["g"], 8, ())]
    with Around(builder) as again:
        assert answer(ex, "GroupBy(Rows(f), Rows(g))") == want
    assert (again.levels, again.programs, again.placements,
            again.staged) == (3, 3, 1, 1)
    assert len(ex._placed_operands) == 2
    monkeypatch.undo()
    with Around(builder) as dense:
        assert answer(ex, "GroupBy(Rows(f))") == numpy_groupby(columns,
                                                               ("f",))
    assert (dense.programs, dense.placements) == (1, 0)


@pytest.mark.parametrize("builder", BUILDERS)
def test_two_threads_filling_one_key_give_one_answer(data, builder,
                                                     monkeypatch):
    """Both threads miss, both place (the barrier holds each inside the
    hook until the other is there), either array stays: same answer, one
    entry, and nothing placed after."""
    holder, columns = data
    want = numpy_groupby(columns, ("f", "g"))
    ex = executor(holder, builder)
    # compile outside the race; then forget what that run placed
    assert answer(ex, "GroupBy(Rows(f), Rows(g))") == want
    ex._placed_operands.clear()
    both_inside = threading.Barrier(2, timeout=60)
    place = ex._operand_place

    def gated(packed):
        both_inside.wait()
        return place(packed)

    monkeypatch.setattr(ex, "_operand_place", gated)
    got, errors = [], []

    def run():
        try:
            got.append(answer(ex, "GroupBy(Rows(f), Rows(g))"))
        except Exception as e:   # reported below, with the thread joined
            errors.append(e)

    with Around(builder) as raced:
        threads = [threading.Thread(target=run) for _ in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads)
    assert not errors and got == [want, want]
    assert raced.placements == 2 and len(ex._placed_operands) == 1
    monkeypatch.setattr(ex, "_operand_place", place)
    with Around(builder) as after:
        assert answer(ex, "GroupBy(Rows(f), Rows(g))") == want
    assert after.placements == 0


def test_many_threads_over_a_small_bound_lose_no_answer(data):
    """More threads than cores, a switch interval of microseconds and a
    bound of 2 under 3 templates, so clears, fills and hits interleave:
    every answer is the reference's and the memo never outgrows its
    bound by more than the fills in flight."""
    holder, columns = data
    ex = executor(holder, "local")
    ex.PLACED_OPERANDS_MAX = 2
    queries = THREE_OPERANDS
    want = [numpy_groupby(columns, *ref) for _, ref in queries]
    for pql, _ in queries:
        answer(ex, pql)     # compiled before the clock starts
    wrong, errors, sizes = [], [], []
    n_threads = 16

    def run(seed):
        rng = np.random.default_rng(seed)
        try:
            for _ in range(25):
                q = int(rng.integers(0, len(queries)))
                if answer(ex, queries[q][0]) != want[q]:
                    wrong.append(q)
                sizes.append(len(ex._placed_operands))
        except Exception as e:
            errors.append(e)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=run, args=(s,))
                   for s in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=240)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(interval)
    assert not errors and not wrong
    assert len(sizes) == n_threads * 25
    assert max(sizes) <= ex.PLACED_OPERANDS_MAX + n_threads


@pytest.mark.parametrize("builder", BUILDERS)
def test_a_set_between_changes_the_answer_and_not_the_entry(tmp_path,
                                                            builder):
    """An entry holds indices and scalars, nothing read from a fragment:
    a write moves the count and leaves the placed array where it is."""
    holder = Holder(str(tmp_path / "data")).open()
    try:
        columns = fill(holder, np.random.default_rng(7), per_shard=12)
        ex = executor(holder, builder)
        pql = 'GroupBy(Rows(f), aggregate=Sum(field="v"))'
        assert answer(ex, pql) == numpy_groupby(columns, ("f",),
                                                with_sum=True)
        (placed,) = ex._placed_operands.values()
        col = int(columns["column"].max()) + 3
        with Around(builder) as writes:
            assert ex.execute("i", f"Set({col}, f=1)") == [True]
            assert ex.execute("i", f"Set({col}, v=9)") == [True]
        assert writes.placements == 0
        for name, value in (("column", col), ("f", 1), ("g", 0), ("v", 9)):
            columns[name] = np.append(columns[name], value)
        want = numpy_groupby(columns, ("f",), with_sum=True)
        with Around(builder) as after:
            got = answer(ex, pql)
        assert got == want
        assert (after.placements, after.programs, after.staged) == (0, 1, 0)
        assert next(iter(ex._placed_operands.values())) is placed
    finally:
        holder.close()


def test_dense_candidates_are_shared_and_read_only():
    cand = ex_mod._dense_candidates((3, 2))
    assert cand.tolist() == [[0, 0], [0, 1], [1, 0], [1, 1], [2, 0], [2, 1]]
    assert cand.dtype == np.int32
    assert ex_mod._dense_candidates((3, 2)) is cand
    with pytest.raises(ValueError):
        cand[0, 0] = 7


def test_hit_share_is_read_from_metrics(tmp_path):
    """Over HTTP: the new series is on /metrics and /debug/vars beside
    the programs it is a share of; 1 - placements / programs is the hit
    share PERF.md quotes."""
    from pilosa_tpu.server import Server, ServerConfig

    s = Server(ServerConfig(
        data_dir=str(tmp_path / "node"), port=0, name="t",
        anti_entropy_interval=0, heartbeat_interval=0,
    )).open()
    try:
        base = uri(s)
        req("POST", f"{base}/index/i", {})
        rng = np.random.default_rng(3)
        cols = np.arange(0, 2 * SHARD_WIDTH, 9973)
        for field, n_rows in ROWS.items():
            req("POST", f"{base}/index/i/field/{field}", {})
            req("POST", f"{base}/index/i/field/{field}/import",
                {"rows": rng.integers(0, n_rows, cols.size).tolist(),
                 "columns": cols.tolist()})

        def scrape():
            text = req("GET", f"{base}/metrics", raw=True).decode()
            return {n: float(re.search(
                rf"^pilosa_tpu_groupby_{n} (\S+)$", text, re.M).group(1))
                for n in ("operand_placements_total",
                          "level_programs_total")}

        before = scrape()
        for i in range(20):
            pql = ("GroupBy(Rows(f))", "GroupBy(Rows(f), Rows(g))")[i % 2]
            out = req("POST", f"{base}/index/i/query", pql.encode())
            assert out["results"][0]
        after = scrape()
        placements = (after["operand_placements_total"]
                      - before["operand_placements_total"])
        programs = (after["level_programs_total"]
                    - before["level_programs_total"])
        assert (placements, programs) == (2, 20)
        assert 1 - placements / programs == pytest.approx(0.9)
        wired = req("GET", f"{base}/debug/vars")["groupby"]
        assert wired["operand_placements_total"] == \
            after["operand_placements_total"]
    finally:
        s.close()
