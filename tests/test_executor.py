"""Executor behavioral tests: PQL strings against a single in-process node
(the bulk of the reference's coverage — executor_test.go style per
SURVEY.md §4), with numpy/python set oracles."""

import numpy as np
import pytest

from pilosa_tpu.executor import Executor
from pilosa_tpu.executor.executor import PQLError
from pilosa_tpu.executor.result import GroupCount, Pair, RowResult, ValCount
from pilosa_tpu.shardwidth import SHARD_WIDTH
from pilosa_tpu.storage import FieldOptions, Holder


@pytest.fixture
def env(tmp_path):
    holder = Holder(str(tmp_path / "data")).open()
    yield holder, Executor(holder)
    holder.close()


def setup_stars(holder):
    """Star-Trace-like dataset (BASELINE config #1): stargazer rows over
    repo columns, language as second field, spanning two shards."""
    idx = holder.create_index("repos")
    stargazer = idx.create_field("stargazer")
    language = idx.create_field("language")
    s2 = SHARD_WIDTH  # a column in shard 1
    data = {
        1: [10, 20, 30, s2 + 1],
        2: [20, 30, 40],
        3: [s2 + 1, s2 + 2],
    }
    for row, cols in data.items():
        for c in cols:
            stargazer.set_bit(row, c)
    langs = {5: [10, 20, s2 + 1], 6: [30, 40, s2 + 2]}
    for row, cols in langs.items():
        for c in cols:
            language.set_bit(row, c)
    all_cols = {c for cols in data.values() for c in cols} | {
        c for cols in langs.values() for c in cols
    }
    idx.mark_columns_exist(sorted(all_cols))
    return idx, data, langs


class TestBitmapCalls:
    def test_row(self, env):
        holder, ex = env
        _, data, _ = setup_stars(holder)
        (res,) = ex.execute("repos", "Row(stargazer=1)")
        assert res.columns().tolist() == data[1]

    def test_union_intersect_difference_xor(self, env):
        holder, ex = env
        _, data, _ = setup_stars(holder)
        s1, s2, s3 = (set(data[i]) for i in (1, 2, 3))
        cases = {
            "Union(Row(stargazer=1), Row(stargazer=2))": s1 | s2,
            "Intersect(Row(stargazer=1), Row(stargazer=2))": s1 & s2,
            "Difference(Row(stargazer=1), Row(stargazer=2))": s1 - s2,
            "Xor(Row(stargazer=1), Row(stargazer=2))": s1 ^ s2,
            "Union(Row(stargazer=1), Row(stargazer=2), Row(stargazer=3))": s1 | s2 | s3,
        }
        for pql, want in cases.items():
            (res,) = ex.execute("repos", pql)
            assert res.columns().tolist() == sorted(want), pql

    def test_count_fused(self, env):
        holder, ex = env
        _, data, langs = setup_stars(holder)
        (n,) = ex.execute(
            "repos", "Count(Intersect(Row(stargazer=1), Row(language=5)))"
        )
        assert n == len(set(data[1]) & set(langs[5]))

    def test_not_and_all(self, env):
        holder, ex = env
        _, data, langs = setup_stars(holder)
        universe = {c for cols in data.values() for c in cols} | {
            c for cols in langs.values() for c in cols
        }
        (res,) = ex.execute("repos", "Not(Row(stargazer=1))")
        assert res.columns().tolist() == sorted(universe - set(data[1]))
        (res,) = ex.execute("repos", "All()")
        assert res.columns().tolist() == sorted(universe)

    def test_shift(self, env):
        holder, ex = env
        _, data, _ = setup_stars(holder)
        (res,) = ex.execute("repos", "Shift(Row(stargazer=2), n=3)")
        assert res.columns().tolist() == [c + 3 for c in data[2]]

    def test_empty_row(self, env):
        holder, ex = env
        setup_stars(holder)
        (res,) = ex.execute("repos", "Row(stargazer=99)")
        assert res.columns().size == 0
        (n,) = ex.execute("repos", "Count(Row(stargazer=99))")
        assert n == 0


class TestWrites:
    def test_set_clear(self, env):
        holder, ex = env
        idx = holder.create_index("i")
        idx.create_field("f")
        assert ex.execute("i", "Set(10, f=1)") == [True]
        assert ex.execute("i", "Set(10, f=1)") == [False]
        (res,) = ex.execute("i", "Row(f=1)")
        assert res.columns().tolist() == [10]
        assert ex.execute("i", "Clear(10, f=1)") == [True]
        (res,) = ex.execute("i", "Row(f=1)")
        assert res.columns().size == 0

    def test_set_marks_existence(self, env):
        holder, ex = env
        idx = holder.create_index("i")
        idx.create_field("f")
        ex.execute("i", "Set(7, f=1) Set(9, f=2)")
        (res,) = ex.execute("i", "All()")
        assert res.columns().tolist() == [7, 9]

    def test_clear_row_and_store(self, env):
        holder, ex = env
        idx = holder.create_index("i")
        idx.create_field("f")
        ex.execute("i", "Set(1, f=1) Set(2, f=1) Set(3, f=2)")
        ex.execute("i", "Store(Row(f=1), f=9)")
        (res,) = ex.execute("i", "Row(f=9)")
        assert res.columns().tolist() == [1, 2]
        assert ex.execute("i", "ClearRow(f=1)") == [True]
        (res,) = ex.execute("i", "Row(f=1)")
        assert res.columns().size == 0
        # stored row unaffected
        (res,) = ex.execute("i", "Row(f=9)")
        assert res.columns().tolist() == [1, 2]

    def test_v0_aliases_execute(self, env):
        holder, ex = env
        idx = holder.create_index("i")
        idx.create_field("f")
        assert ex.execute("i", "SetBit(5, f=1)") == [True]
        (res,) = ex.execute("i", "Bitmap(f=1)")
        assert res.columns().tolist() == [5]


class TestBSI:
    def setup_fares(self, holder):
        idx = holder.create_index("taxi")
        fare = idx.create_field(
            "fare", FieldOptions(type="int", min=-50, max=500)
        )
        self.values = {0: -50, 1: 0, 2: 10, 3: 11, 4: 499, 5: 500,
                       SHARD_WIDTH + 7: 42}
        for col, v in self.values.items():
            fare.set_value(col, v)
        idx.mark_columns_exist(sorted(self.values))
        return idx

    @pytest.mark.parametrize(
        "op,py",
        [("<", lambda v, p: v < p), ("<=", lambda v, p: v <= p),
         (">", lambda v, p: v > p), (">=", lambda v, p: v >= p),
         ("==", lambda v, p: v == p), ("!=", lambda v, p: v != p)],
    )
    @pytest.mark.parametrize("pred", [-51, -50, 0, 10, 42, 500, 501])
    def test_range_ops(self, env, op, py, pred):
        holder, ex = env
        self.setup_fares(holder)
        (res,) = ex.execute("taxi", f"Range(fare {op} {pred})")
        want = sorted(c for c, v in self.values.items() if py(v, pred))
        assert res.columns().tolist() == want, f"fare {op} {pred}"

    def test_between(self, env):
        holder, ex = env
        self.setup_fares(holder)
        (res,) = ex.execute("taxi", "Range(fare >< [0, 42])")
        want = sorted(c for c, v in self.values.items() if 0 <= v <= 42)
        assert res.columns().tolist() == want

    @pytest.mark.parametrize(
        "op,py",
        [("<", lambda v, p: v < p), ("<=", lambda v, p: v <= p),
         (">", lambda v, p: v > p), (">=", lambda v, p: v >= p),
         ("==", lambda v, p: v == p), ("!=", lambda v, p: v != p)],
    )
    @pytest.mark.parametrize("pred", [-50.5, 0.5, 10.5, 499.5])
    def test_range_fractional_predicate(self, env, op, py, pred):
        # Stored values are integers; a fractional predicate must map onto
        # the integer lattice exactly (x < 10.5 ⇔ x <= 10, never x < 10).
        holder, ex = env
        self.setup_fares(holder)
        (res,) = ex.execute("taxi", f"Range(fare {op} {pred})")
        want = sorted(c for c, v in self.values.items() if py(v, pred))
        assert res.columns().tolist() == want, f"fare {op} {pred}"

    def test_range_huge_predicate(self, env):
        # Predicates beyond float range must hit the out-of-range clamp,
        # not crash (float(10**400) raises OverflowError).
        holder, ex = env
        self.setup_fares(holder)
        huge = 10 ** 400
        (res,) = ex.execute("taxi", f"Range(fare < {huge})")
        assert res.columns().tolist() == sorted(self.values)
        (res,) = ex.execute("taxi", f"Range(fare > {huge})")
        assert res.columns().tolist() == []

    def test_range_infinite_fractional_predicate(self, env):
        # A ~330-digit literal WITH a fractional part parses to float
        # +/-inf; math.floor(inf) would raise, so the inf clamp must
        # short-circuit to universe/empty.
        holder, ex = env
        self.setup_fares(holder)
        big = "9" * 330 + ".5"
        every = sorted(self.values)
        for op, want in (("<", every), ("<=", every), (">", []), (">=", []),
                         ("==", []), ("!=", every)):
            (res,) = ex.execute("taxi", f"Range(fare {op} {big})")
            assert res.columns().tolist() == want, f"fare {op} inf"
        for op, want in (("<", []), ("<=", []), (">", every), (">=", every)):
            (res,) = ex.execute("taxi", f"Range(fare {op} -{big})")
            assert res.columns().tolist() == want, f"fare {op} -inf"

    def test_between_fractional(self, env):
        holder, ex = env
        self.setup_fares(holder)
        (res,) = ex.execute("taxi", "Range(fare >< [0.5, 42.5])")
        want = sorted(c for c, v in self.values.items() if 0.5 <= v <= 42.5)
        assert res.columns().tolist() == want

    def test_row_condition_alias(self, env):
        holder, ex = env
        self.setup_fares(holder)
        # v1.3+ allows Row(fare > 10) as alias for Range
        (res,) = ex.execute("taxi", "Row(fare > 10)")
        want = sorted(c for c, v in self.values.items() if v > 10)
        assert res.columns().tolist() == want

    def test_sum_min_max(self, env):
        holder, ex = env
        self.setup_fares(holder)
        vals = self.values
        (s,) = ex.execute("taxi", 'Sum(field="fare")')
        assert (s.value, s.count) == (sum(vals.values()), len(vals))
        (mn,) = ex.execute("taxi", 'Min(field="fare")')
        assert (mn.value, mn.count) == (-50, 1)
        (mx,) = ex.execute("taxi", 'Max(field="fare")')
        assert (mx.value, mx.count) == (500, 1)

    def test_sum_with_filter(self, env):
        holder, ex = env
        self.setup_fares(holder)
        (s,) = ex.execute("taxi", 'Sum(Range(fare > 0), field="fare")')
        want = [v for v in self.values.values() if v > 0]
        assert (s.value, s.count) == (sum(want), len(want))

    def test_min_max_tie_counts(self, env):
        holder, ex = env
        idx = holder.create_index("t2")
        f = idx.create_field("v", FieldOptions(type="int", min=0, max=10))
        for col, v in [(0, 3), (1, 3), (2, 7)]:
            f.set_value(col, v)
        (mn,) = ex.execute("t2", 'Min(field="v")')
        assert (mn.value, mn.count) == (3, 2)

    def test_empty_aggregate(self, env):
        holder, ex = env
        idx = holder.create_index("t3")
        idx.create_field("v", FieldOptions(type="int", min=0, max=10))
        (s,) = ex.execute("t3", 'Sum(field="v")')
        assert (s.value, s.count) == (0, 0)
        (mn,) = ex.execute("t3", 'Min(field="v")')
        assert (mn.value, mn.count) == (0, 0)


class TestTopNRowsGroupBy:
    def setup_ranked(self, holder):
        idx = holder.create_index("r")
        f = idx.create_field("f")
        g = idx.create_field("g")
        counts = {1: 5, 2: 50, 3: 20, 4: 35}
        for row, n in counts.items():
            for c in range(n):
                f.set_bit(row, c)
        # second shard contribution for row 3
        for c in range(15):
            f.set_bit(3, SHARD_WIDTH + c)
        for c in range(0, 60, 2):
            g.set_bit(7, c)
        cols = set(range(60)) | {SHARD_WIDTH + c for c in range(15)}
        idx.mark_columns_exist(sorted(cols))
        return idx

    def test_topn(self, env):
        holder, ex = env
        self.setup_ranked(holder)
        (pairs,) = ex.execute("r", "TopN(f, n=3)")
        assert [(p.id, p.count) for p in pairs] == [(2, 50), (3, 35), (4, 35)]

    def test_topn_with_filter(self, env):
        holder, ex = env
        self.setup_ranked(holder)
        (pairs,) = ex.execute("r", "TopN(f, Row(g=7), n=2)")
        # row2 ∩ evens<60: 25; row4 ∩ evens<60 (g covers 0..58): 18
        assert (pairs[0].id, pairs[0].count) == (2, 25)

    def test_topn_explicit_ids(self, env):
        holder, ex = env
        self.setup_ranked(holder)
        (pairs,) = ex.execute("r", "TopN(f, ids=[1, 3], n=5)")
        assert [(p.id, p.count) for p in pairs] == [(3, 35), (1, 5)]

    def test_rows(self, env):
        holder, ex = env
        self.setup_ranked(holder)
        assert ex.execute("r", "Rows(f)") == [[1, 2, 3, 4]]
        assert ex.execute("r", "Rows(f, limit=2)") == [[1, 2]]
        assert ex.execute("r", "Rows(f, previous=2)") == [[3, 4]]
        assert ex.execute("r", "Rows(f, column=40)") == [[2]]  # only row2 ⊇ 40

    def test_groupby(self, env):
        holder, ex = env
        self.setup_ranked(holder)
        (groups,) = ex.execute("r", "GroupBy(Rows(f), Rows(g))")
        got = {
            tuple(e["rowID"] for e in g.group): g.count for g in groups
        }
        # row1 (0..4) ∩ evens<60 = {0,2,4} → 3; row2 (0..49) ∩ evens → 25
        assert got[(1, 7)] == 3
        assert got[(2, 7)] == 25
        assert got[(4, 7)] == 18
        (groups,) = ex.execute("r", "GroupBy(Rows(f), Rows(g), limit=2)")
        assert len(groups) == 2

    def test_groupby_filter(self, env):
        holder, ex = env
        self.setup_ranked(holder)
        (groups,) = ex.execute(
            "r", "GroupBy(Rows(f), filter=Row(g=7))"
        )
        got = {g.group[0]["rowID"]: g.count for g in groups}
        assert got[1] == 3 and got[2] == 25

    def test_topn_threshold(self, env):
        """TopN(threshold=) — SURVEY-LOW surface (Appendix B: exact
        upstream semantics unverifiable, mount empty). Conservative
        reading under test: a minimum-global-count filter applied after
        the exact phase-2 recount, before trimming to n."""
        holder, ex = env
        self.setup_ranked(holder)
        # counts: row2=50, row3=35, row4=35, row1=5
        (pairs,) = ex.execute("r", "TopN(f, n=10, threshold=35)")
        assert [(p.id, p.count) for p in pairs] == [(2, 50), (3, 35), (4, 35)]
        (pairs,) = ex.execute("r", "TopN(f, n=10, threshold=36)")
        assert [(p.id, p.count) for p in pairs] == [(2, 50)]
        # threshold composes with n (filter first, then trim)
        (pairs,) = ex.execute("r", "TopN(f, n=1, threshold=35)")
        assert [(p.id, p.count) for p in pairs] == [(2, 50)]
        # explicit-ids recount respects the floor too
        (pairs,) = ex.execute("r", "TopN(f, ids=[1, 3], n=5, threshold=10)")
        assert [(p.id, p.count) for p in pairs] == [(3, 35)]

    def test_groupby_having_count(self, env):
        """GroupBy(having=Condition(count <op> N)) — SURVEY-LOW surface
        (Appendix B). Conservative reading under test: one condition on
        the merged group count, applied before limit."""
        holder, ex = env
        self.setup_ranked(holder)
        # base counts: (1,7)=3 (2,7)=25 (3,7)=10 (4,7)=18
        (groups,) = ex.execute(
            "r", "GroupBy(Rows(f), Rows(g), having=Condition(count > 10))"
        )
        got = {g.group[0]["rowID"]: g.count for g in groups}
        assert got == {2: 25, 4: 18}
        (groups,) = ex.execute(
            "r", "GroupBy(Rows(f), Rows(g), having=Condition(count >< [3, 18]))"
        )
        assert {g.group[0]["rowID"] for g in groups} == {1, 3, 4}
        # having applies BEFORE limit: the one survivor is returned even
        # though it sorts after the groups having filtered out
        (groups,) = ex.execute(
            "r",
            "GroupBy(Rows(f), Rows(g), limit=1, having=Condition(count == 18))",
        )
        assert [(g.group[0]["rowID"], g.count) for g in groups] == [(4, 18)]
        # float thresholds must not truncate: count < 3.5 keeps the
        # count==3 group (int(3.5) → "< 3" would drop it)
        (groups,) = ex.execute(
            "r", "GroupBy(Rows(f), Rows(g), having=Condition(count < 3.5))"
        )
        assert {g.group[0]["rowID"]: g.count for g in groups} == {1: 3}
        (groups,) = ex.execute(
            "r", "GroupBy(Rows(f), Rows(g), having=Condition(count >< [3.0, 10.5]))"
        )
        assert {g.group[0]["rowID"] for g in groups} == {1, 3}

    def test_condition_value_coercion(self):
        """Quoted numeric thresholds coerce; junk raises PQLError (not a
        bare TypeError that would 500 at the HTTP layer)."""
        from pilosa_tpu.executor.executor import PQLError, condition_test
        from pilosa_tpu.pql.ast import Condition

        assert condition_test(Condition(">", "5"), 6)
        assert not condition_test(Condition(">", "5"), 5)
        assert condition_test(Condition("<", "1.5"), 1)
        assert condition_test(Condition("><", ["3", "10.5"]), 10)
        with pytest.raises(PQLError, match="not numeric"):
            condition_test(Condition(">", "abc"), 1)

    def test_groupby_having_sum_requires_aggregate(self, env):
        from pilosa_tpu.executor.executor import PQLError

        holder, ex = env
        self.setup_ranked(holder)
        with pytest.raises(PQLError, match="aggregate"):
            ex.execute(
                "r", "GroupBy(Rows(f), having=Condition(sum > 10))"
            )
        with pytest.raises(PQLError, match="count or sum"):
            ex.execute(
                "r", "GroupBy(Rows(f), having=Condition(bogus > 10))"
            )
        with pytest.raises(PQLError, match="Condition"):
            ex.execute("r", "GroupBy(Rows(f), having=5)")

    def test_groupby_having_sum(self, env):
        holder, ex = env
        idx = holder.create_index("hs")
        f = idx.create_field("f")
        amt = idx.create_field("amt", FieldOptions(type="int", min=0, max=100))
        # group 1: cols 0..4 value 10 (sum 50); group 2: cols 5..6 value 40 (sum 80)
        for c in range(5):
            f.set_bit(1, c)
            amt.set_value(c, 10)
        for c in range(5, 7):
            f.set_bit(2, c)
            amt.set_value(c, 40)
        (groups,) = ex.execute(
            "hs",
            'GroupBy(Rows(f), aggregate=Sum(field="amt"), '
            "having=Condition(sum > 60))",
        )
        assert [(g.group[0]["rowID"], g.count, g.sum) for g in groups] == [
            (2, 2, 80)
        ]


class TestTimeViews:
    def test_row_time_range(self, env):
        holder, ex = env
        idx = holder.create_index("ev")
        idx.create_field(
            "t", FieldOptions(type="time", time_quantum="YMD")
        )
        ex.execute("ev", "Set(1, t=1, timestamp='2019-01-15T00:00')")
        ex.execute("ev", "Set(2, t=1, timestamp='2019-03-02T00:00')")
        ex.execute("ev", "Set(3, t=1, timestamp='2020-01-01T00:00')")
        (res,) = ex.execute(
            "ev", "Row(t=1, from='2019-01-01T00:00', to='2019-12-31T00:00')"
        )
        assert res.columns().tolist() == [1, 2]
        (res,) = ex.execute(
            "ev", "Row(t=1, from='2019-03-01T00:00', to='2020-06-01T00:00')"
        )
        assert res.columns().tolist() == [2, 3]
        # no time bounds → standard view has all
        (res,) = ex.execute("ev", "Row(t=1)")
        assert res.columns().tolist() == [1, 2, 3]


class TestErrors:
    def test_unknown_index_field(self, env):
        holder, ex = env
        with pytest.raises(PQLError):
            ex.execute("nope", "Row(f=1)")
        holder.create_index("i")
        with pytest.raises(PQLError):
            ex.execute("i", "Row(f=1)")

    def test_negative_column_rejected(self, env):
        holder, ex = env
        idx = holder.create_index("i")
        idx.create_field("f")
        with pytest.raises(PQLError):
            ex.execute("i", "Set(-5, f=1)")
        with pytest.raises(PQLError):
            ex.execute("i", "Clear(-5, f=1)")

    def test_range_on_set_field(self, env):
        holder, ex = env
        idx = holder.create_index("i")
        idx.create_field("f")
        with pytest.raises(PQLError):
            ex.execute("i", "Range(f > 3)")

    def test_options_shards(self, env):
        holder, ex = env
        _, data, _ = setup_stars(holder)
        (res,) = ex.execute(
            "repos", "Options(Row(stargazer=1), shards=[0])"
        )
        assert res.columns().tolist() == [c for c in data[1] if c < SHARD_WIDTH]

    def test_includes_column(self, env):
        holder, ex = env
        _, data, _ = setup_stars(holder)
        assert ex.execute(
            "repos", "IncludesColumn(Row(stargazer=1), column=10)"
        ) == [True]
        assert ex.execute(
            "repos", "IncludesColumn(Row(stargazer=1), column=11)"
        ) == [False]


class TestSubmitPipelined:
    def test_submit_count_matches_execute(self, env):
        holder, ex = env
        _, data, langs = setup_stars(holder)
        pql = "Count(Intersect(Row(stargazer=1), Row(language=5)))"
        want = ex.execute("repos", pql)[0]
        (d,) = ex.submit("repos", pql)
        assert d.result() == want
        assert d.result() == want  # idempotent resolve

    def test_submit_pipeline_resolves_in_order(self, env):
        """Enqueue several salted Shift queries without blocking, then
        resolve; each matches its eager counterpart (scalars are runtime
        args, so one compiled program serves every salt)."""
        holder, ex = env
        setup_stars(holder)
        pqls = [
            f"Count(Intersect(Row(stargazer=1), Shift(Row(language=5), n={n})))"
            for n in range(4)
        ]
        defs = [ex.submit("repos", p)[0] for p in pqls]
        want = [ex.execute("repos", p)[0] for p in pqls]
        assert [d.result() for d in defs] == want

    def test_submit_sum_min_max_deferred(self, env):
        holder, ex = env
        idx = holder.create_index("vals")
        f = idx.create_field("n", FieldOptions(type="int", min=0, max=1000))
        for col, v in ((1, 7), (2, 100), (3, 900)):
            f.set_value(col, v)
        for name, want in (("Sum", ValCount(1007, 3)), ("Min", ValCount(7, 1)),
                           ("Max", ValCount(900, 1))):
            (d,) = ex.submit("vals", f'{name}(field="n")')
            assert d.result() == want

    def test_submit_row_defers_readback(self, env, monkeypatch):
        """Pipelined bitmap calls enqueue their program at submit but
        perform the [padded, words] readback only at result()."""
        holder, ex = env
        _, data, _ = setup_stars(holder)
        reads = []
        real_asarray = np.asarray

        def counting_asarray(x, *a, **k):
            import jax

            if isinstance(x, jax.Array):
                reads.append(type(x).__name__)
            return real_asarray(x, *a, **k)

        monkeypatch.setattr(
            "pilosa_tpu.executor.executor.np.asarray", counting_asarray
        )
        (d,) = ex.submit("repos", "Row(stargazer=1)")
        assert reads == []  # no device readback at submit time
        assert d.result().columns().tolist() == data[1]
        assert len(reads) == 1

    def test_operand_memo_reuses_assembly_until_write(self, env):
        """Steady-state repeat queries hit the operand memo; any write
        bumps the residency generation, whose listener clears the memo
        EAGERLY (so evictions actually free HBM), and the next assembly
        picks up the patched leaves."""
        from pilosa_tpu.storage import residency

        holder, ex = env
        setup_stars(holder)
        pql = "Count(Row(stargazer=1))"
        before = ex.execute("repos", pql)[0]
        assert ex.execute("repos", pql)[0] == before
        assert len(ex._operand_memo) >= 1  # warmed
        entry_count = len(ex._operand_memo)
        gen0 = residency.global_row_cache().generation
        ex.execute("repos", pql)
        assert len(ex._operand_memo) == entry_count  # hit, no growth
        assert residency.global_row_cache().generation == gen0
        ex.execute("repos", "Set(424242, stargazer=1)")
        assert residency.global_row_cache().generation > gen0
        assert len(ex._operand_memo) == 0  # listener cleared eagerly
        assert ex.execute("repos", pql)[0] == before + 1

    def test_operand_memo_rejects_stale_generation_entry(self, env):
        """A racing store can insert an entry assembled under an old
        generation AFTER the clear (assembler preempted across a write);
        the per-entry generation tag must keep it from ever being
        served."""
        from pilosa_tpu.storage import residency

        holder, ex = env
        setup_stars(holder)
        pql = "Count(Row(stargazer=1))"
        before = ex.execute("repos", pql)[0]
        ex.execute("repos", pql)  # warm the memo
        (mkey, entry), = [(k, v) for k, v in ex._operand_memo.items()][:1]
        # simulate the race: re-insert the pre-write entry with its OLD
        # generation tag after a write cleared the memo
        ex.execute("repos", "Set(424243, stargazer=1)")
        assert len(ex._operand_memo) == 0
        ex._operand_memo[mkey] = entry
        ex._operand_memo_gen = residency.global_row_cache().generation
        assert ex.execute("repos", pql)[0] == before + 1  # not served stale

    def test_topn_does_not_pollute_operand_memo(self, env):
        """TopN phase 2 builds a per-call _Compiled; memoize=False keeps
        those dead-on-arrival entries out of the memo."""
        holder, ex = env
        idx = holder.create_index("i")
        f = idx.create_field("f", FieldOptions(cache_type="ranked"))
        for row in range(5):
            for col in range(row + 1):
                f.set_bit(row, col)
        ex.execute("i", "TopN(f, n=3)")
        n0 = len(ex._operand_memo)
        for _ in range(5):
            ex.execute("i", "TopN(f, n=3)")
        assert len(ex._operand_memo) == n0  # no per-call growth

    def test_submit_snapshots_leaves_against_later_writes(self, env):
        """A pipelined read captures its leaves at submit time: a write
        landing between submit and the (lazy) flush patches the residency
        cache functionally, so the in-flight query still answers from its
        submit-time snapshot while a post-write submit sees the write."""
        holder, ex = env
        _, data, _ = setup_stars(holder)
        pql = "Count(Row(stargazer=1))"
        before = ex.execute("repos", pql)[0]
        (d_old,) = ex.submit("repos", pql)  # enqueued, not yet flushed
        ex.execute("repos", "Set(999999, stargazer=1)")  # lands pre-flush
        (d_new,) = ex.submit("repos", pql)
        assert d_old.result() == before
        assert d_new.result() == before + 1

    def test_submit_writes_and_host_reads_stay_eager(self, env):
        """Writes and host-only reads must execute AT submit time (an
        already-resolved Deferred) — read-your-writes ordering within a
        submitted stream depends on it."""
        holder, ex = env
        setup_stars(holder)
        (d,) = ex.submit("repos", "Set(999, stargazer=1)")
        assert d._finalize is None  # already resolved
        assert d.result() is True
        # the write is visible to a submit enqueued right after
        (d2,) = ex.submit("repos", "Count(Row(stargazer=1))")
        (rows,) = ex.submit("repos", "Rows(stargazer)")
        assert rows._finalize is None  # host-only read: eager
        assert 999 in set(
            ex.execute("repos", "Row(stargazer=1)")[0].columns().tolist()
        )
        assert d2.result() == ex.execute(
            "repos", "Count(Row(stargazer=1))"
        )[0]

    def test_submit_count_microbatch_coalesces(self, env):
        """Pipelined same-shape Counts dispatch as ONE micro-batched
        program; each Deferred gets its own slice of the [B, 2] packed
        readback. Resolving any Deferred flushes a partial group."""
        holder, ex = env
        _, data, langs = setup_stars(holder)
        pqls = [
            "Count(Row(stargazer=1))",
            "Count(Row(stargazer=2))",
            "Count(Row(stargazer=3))",
            "Count(Row(language=5))",
            "Count(Row(language=6))",
        ]
        want = [ex.execute("repos", p)[0] for p in pqls]
        defs = [ex.submit("repos", p)[0] for p in pqls]
        assert ex._pending  # partial group still pending (5 < batch max)
        got = [d.result() for d in defs]  # first resolve flushes the group
        assert got == want
        assert not ex._pending

    def test_submit_microbatch_flushes_at_max(self, env):
        holder, ex = env
        setup_stars(holder)
        ex.microbatch_max = 2
        defs = [
            ex.submit("repos", f"Count(Row(stargazer={r}))")[0]
            for r in (1, 2, 3)
        ]
        # first two flushed as a pair at max; third still pending
        assert sum(len(g["rows"]) for g in ex._pending.values()) == 1
        assert [d.result() for d in defs] == [4, 3, 2]

    def test_submit_microbatch_caps_group_by_argument_bytes(self, env, monkeypatch):
        """Wide queries (many leaves) cap the micro-batch below
        microbatch_max so the batched program's total argument bytes
        stay under budget — XLA accounts every parameter as distinct
        HBM storage, so a 16-query batch of 4-leaf queries at full
        shard counts would fail to compile."""
        holder, ex = env
        setup_stars(holder)
        # each Count(Intersect(a, b)) carries 2 stacked leaves; size the
        # budget so exactly 2 queries (4 leaves) fit per dispatch
        pql = "Count(Intersect(Row(stargazer=1), Row(language=5)))"
        d0 = ex.submit("repos", pql)[0]
        (group,) = ex._pending.values()
        leaf_bytes = sum(l.nbytes for l in group["rows"][0][0])
        d0.result()  # flush the probe group

        ex.microbatch_arg_budget = 2 * leaf_bytes
        flushes = []
        orig = ex._program_batched

        def counting(structure, rk, lr, ns, nq):
            flushes.append(nq)
            return orig(structure, rk, lr, ns, nq)

        monkeypatch.setattr(ex, "_program_batched", counting)
        want = ex.execute("repos", pql)[0]
        defs = [ex.submit("repos", pql)[0] for _ in range(6)]
        assert [d.result() for d in defs] == [want] * 6
        assert flushes == [2, 2, 2], flushes

    def test_store_rejected_row_leaves_no_phantom_field(self, env):
        """A Store with an invalid row must not implicitly create its
        target field (rejected queries leave no schema side effects)."""
        holder, ex = env
        idx = holder.create_index("i")
        idx.create_field("f")
        ex.execute("i", "Set(1, f=1)")
        with pytest.raises(PQLError):
            ex.execute("i", "Store(Row(f=1), g=-3)")
        assert idx.field("g") is None
        with pytest.raises(PQLError):  # string row: implicit field has no keys
            ex.execute("i", 'Store(Row(f=1), g="name")')
        assert idx.field("g") is None

    def test_topn_sees_write_to_highest_candidate(self, env):
        """Regression: the padded candidate matrix must route writes to
        the REAL slot of the highest candidate id (a pad row duplicating
        it would swallow the patch and serve stale counts)."""
        holder, ex = env
        idx = holder.create_index("r")
        f = idx.create_field("f")
        for row, n_bits in [(1, 5), (2, 9), (5, 7)]:  # 3 rows → pads to 4
            for c in range(n_bits):
                f.set_bit(row, c)
        (pairs,) = ex.execute("r", "TopN(f, n=5)")
        assert dict((p.id, p.count) for p in pairs)[5] == 7
        # write to the HIGHEST candidate id, then re-query
        for c in range(20, 25):
            f.set_bit(5, c)
        (pairs,) = ex.execute("r", "TopN(f, n=5)")
        assert dict((p.id, p.count) for p in pairs)[5] == 12

    def test_topn_matrix_chunking_tiny_budget(self, env, monkeypatch):
        """A matrix byte budget so small every chunk holds one candidate
        must still produce identical TopN results (chunk concat)."""
        import pilosa_tpu.executor.executor as ex_mod

        holder, ex = env
        idx = holder.create_index("r")
        f = idx.create_field("f")
        for row, n_bits in [(1, 5), (2, 9), (3, 7), (4, 3)]:
            for c in range(n_bits):
                f.set_bit(row, c)
        (want,) = ex.execute("r", "TopN(f, n=4)")
        monkeypatch.setattr(ex_mod, "TOPN_MATRIX_BUDGET_BYTES", 1)
        (got,) = ex.execute("r", "TopN(f, n=4)")
        assert [(p.id, p.count) for p in got] == [
            (p.id, p.count) for p in want
        ]
        # pipelined too
        d = ex.submit("r", "TopN(f, n=4)")[0]
        assert [(p.id, p.count) for p in d.result()] == [
            (p.id, p.count) for p in want
        ]

    def test_submit_topn_pipelines_phase2(self, env, monkeypatch):
        """Pipelined TopNs micro-batch their phase-2 recounts: a stream
        of same-field TopNs (same padded candidate shape) dispatches as
        ONE countrows program, with results matching execute()."""
        holder, ex = env
        setup_stars(holder)
        flushes = []
        orig = ex._program_batched

        def counting(structure, rk, lr, ns, nq):
            flushes.append((rk, nq))
            return orig(structure, rk, lr, ns, nq)

        monkeypatch.setattr(ex, "_program_batched", counting)
        want = ex.execute("repos", "TopN(stargazer, n=2)")[0]
        pqls = ["TopN(stargazer, n=2)", "TopN(stargazer, n=3)",
                "TopN(stargazer, n=2)"]
        defs = [ex.submit("repos", p)[0] for p in pqls]
        got = [d.result() for d in defs]
        assert [(p.id, p.count) for p in got[0]] == [
            (p.id, p.count) for p in want
        ]
        assert [(p.id, p.count) for p in got[2]] == [
            (p.id, p.count) for p in want
        ]
        assert len(got[1]) == 3
        # all three phase-2 recounts rode ONE countrows dispatch (the
        # batch axis pads 3 -> 4, the next power of two)
        assert ("countrows", 4) in flushes, flushes
        assert len([f for f in flushes if f[0] == "countrows"]) == 1

    def test_submit_groupby_defers_readback(self, env, monkeypatch):
        """Pipelined dense GroupBys enqueue their level program at
        submit time but perform the host readback only at result():
        submit() must not call np.asarray on the packed result."""
        import pilosa_tpu.executor.executor as ex_mod

        holder, ex = env
        setup_stars(holder)
        want = ex.execute("repos", "GroupBy(Rows(stargazer))")[0]

        unpacks = []
        real_unpack = ex_mod._groupby_level_unpack

        def counting_unpack(*a, **k):
            unpacks.append(1)
            return real_unpack(*a, **k)

        monkeypatch.setattr(ex_mod, "_groupby_level_unpack", counting_unpack)
        d = ex.submit("repos", "GroupBy(Rows(stargazer))")[0]
        assert unpacks == []  # no readback at submit time
        got = d.result()
        assert unpacks == [1]
        assert [g.to_json() for g in got] == [g.to_json() for g in want]

    def test_submit_microbatch_mixed_shapes_group_separately(self, env):
        """Different program shapes (plain vs Shift trees) land in
        different groups and both resolve correctly."""
        holder, ex = env
        setup_stars(holder)
        a = ex.submit("repos", "Count(Row(stargazer=1))")[0]
        b = ex.submit(
            "repos", "Count(Intersect(Row(stargazer=1), Shift(Row(language=5), n=0)))"
        )[0]
        want_b = ex.execute(
            "repos", "Count(Intersect(Row(stargazer=1), Row(language=5)))"
        )[0]
        assert len(ex._pending) == 2
        assert a.result() == 4
        assert b.result() == want_b


class TestPlanCache:
    """_compile_cached: repeated query text (one parse-memoized Call tree)
    reuses the compiled plan; schema changes and BSI shape growth
    invalidate; unknown-key plans are never memoized."""

    def test_repeat_query_hits_cache_and_stays_correct(self, env):
        holder, ex = env
        setup_stars(holder)
        q = "Count(Intersect(Row(stargazer=1), Row(language=5)))"
        assert ex.execute("repos", q)[0] == 3
        assert len(ex._plan_cache) == 1
        entry = next(iter(ex._plan_cache.values()))
        assert ex.execute("repos", q)[0] == 3
        assert next(iter(ex._plan_cache.values())) is entry  # reused

    def test_write_through_cached_plan(self, env):
        holder, ex = env
        setup_stars(holder)
        q = "Count(Row(stargazer=2))"
        assert ex.execute("repos", q)[0] == 3
        holder.index("repos").field("stargazer").set_bit(2, 77)
        assert ex.execute("repos", q)[0] == 4  # plan reused, data fresh

    def test_field_recreate_invalidates(self, env):
        holder, ex = env
        idx = holder.create_index("repos")
        idx.create_field("stargazer").set_bit(1, 5)
        q = "Count(Row(stargazer=1))"
        assert ex.execute("repos", q)[0] == 1
        idx.delete_field("stargazer")
        idx.create_field("stargazer").set_bit(1, 6)
        idx.field("stargazer").set_bit(1, 7)
        assert ex.execute("repos", q)[0] == 2

    def test_bsi_range_recreate_invalidates(self, env):
        """A cached compare plan bakes in base/bit_depth (predicate
        shifting + clamping); recreating the field with a different range
        must not reuse it."""
        holder, ex = env
        idx = holder.create_index("metrics")
        f = idx.create_field("size", FieldOptions(type="int", min=0, max=100))
        f.set_value(1, 50)
        q = "Count(Row(size > 40))"
        assert ex.execute("metrics", q)[0] == 1
        idx.delete_field("size")
        f = idx.create_field(
            "size", FieldOptions(type="int", min=0, max=100000)
        )
        f.set_value(1, 50)
        f.set_value(2, 99999)
        assert ex.execute("metrics", q)[0] == 2

    def test_unknown_key_plan_not_cached(self, env):
        holder, ex = env
        idx = holder.create_index("people", keys=False)
        f = idx.create_field("name", FieldOptions(keys=True))
        ex.execute("people", 'Set(9, name="bob")')  # materialize the field
        q = 'Count(Row(name="alice"))'
        assert ex.execute("people", q)[0] == 0
        assert not ex._plan_cache  # const0 plan: not memoized
        # create the key after the first compile; the same query text
        # (same memoized Call tree) must now see the new row
        ex.execute("people", 'Set(3, name="alice")')
        assert ex.execute("people", q)[0] == 1

    def test_field_delete_shrinks_shard_list(self, env):
        """available_shards memo: a delete_field followed by equal-count
        fragment creation must not alias the memoized shard list."""
        holder, ex = env
        idx = holder.create_index("repos", track_existence=False)
        idx.create_field("a").set_bit(1, 0)  # shard 0
        assert idx.available_shards() == [0]
        idx.delete_field("a")
        idx.create_field("b").set_bit(1, 5 * SHARD_WIDTH)  # shard 5
        assert idx.available_shards() == [5]
        assert ex.execute("repos", "Count(Row(b=1))")[0] == 1

    def test_index_recreate_same_name_invalidates(self, env):
        """delete_index + create_index under one name restarts plan_epoch;
        the cached plan must not survive into the new index."""
        holder, ex = env
        idx = holder.create_index("repos", track_existence=False)
        idx.create_field("f").set_bit(1, 10)
        q = "Count(Row(f=1))"
        assert ex.execute("repos", q)[0] == 1
        holder.delete_index("repos")
        idx2 = holder.create_index("repos", track_existence=False)
        idx2.create_field("f").set_bit(1, 20)
        idx2.field("f").set_bit(1, 21)
        assert ex.execute("repos", q)[0] == 2


class TestSubmitBSIAggregates:
    def setup_vals(self, holder):
        from pilosa_tpu.storage import FieldOptions

        idx = holder.create_index("m", track_existence=False)
        f = idx.create_field("v", FieldOptions(type="int", min=-10, max=500))
        self.values = {0: -10, 1: 0, 5: 42, SHARD_WIDTH + 2: 499}
        for c, v in self.values.items():
            f.set_value(c, v)
        g = idx.create_field("w", FieldOptions(type="int", min=0, max=100))
        for c in (3, 7):
            g.set_value(c, c * 10)
        return idx

    def test_pipelined_sums_coalesce_into_one_dispatch(self, env):
        """Pipelined same-shape Sum queries micro-batch like Counts: one
        device program, per-query slices of the packed readback."""
        holder, ex = env
        self.setup_vals(holder)
        want_v = ex.execute("m", 'Sum(field="v")')[0]
        want_v2 = ex.execute("m", 'Sum(Row(v > 0), field="v")')[0]
        defs = [ex.submit("m", 'Sum(field="v")')[0],
                ex.submit("m", 'Sum(field="v")')[0]]
        assert ex._pending  # grouped, not yet dispatched
        got = [d.result() for d in defs]
        assert got == [want_v, want_v]
        assert not ex._pending
        # filtered Sum (different shape) still correct via submit
        assert ex.submit("m", 'Sum(Row(v > 0), field="v")')[0].result() == want_v2

    def test_pipelined_min_max_via_submit(self, env):
        holder, ex = env
        self.setup_vals(holder)
        for pql in ('Min(field="v")', 'Max(field="v")', 'Min(field="w")'):
            want = ex.execute("m", pql)[0]
            assert ex.submit("m", pql)[0].result() == want

    def test_plan_cache_survives_concurrent_ddl_churn(self, env):
        """Queries racing create/delete of an unrelated field must never
        serve a stale plan or crash; the epoch snapshot taken before
        compile prevents a racing DDL from tagging a stale plan current."""
        import threading

        holder, ex = env
        idx = holder.create_index("repos", track_existence=False)
        f = idx.create_field("f")
        for c in (1, 5, 9):
            f.set_bit(1, c)
        errors = []
        stop = threading.Event()

        def churn():
            try:
                for i in range(60):
                    g = idx.create_field("tmp")
                    g.set_bit(1, 2)
                    idx.delete_field("tmp")
            except Exception as e:  # noqa: BLE001
                errors.append(e)
            finally:
                stop.set()

        def query():
            try:
                while not stop.is_set():
                    assert ex.execute("repos", "Count(Row(f=1))")[0] == 3
            except Exception as e:  # noqa: BLE001
                errors.append(e)

        threads = [threading.Thread(target=churn)] + [
            threading.Thread(target=query) for _ in range(2)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not errors, errors[0]
        assert ex.execute("repos", "Count(Row(f=1))")[0] == 3


class TestOptionsShardEdges:
    def test_options_duplicate_shards_count_once(self, env):
        holder, ex = env
        idx = holder.create_index("i")
        f = idx.create_field("f")
        for s in range(3):
            f.set_bit(1, s * SHARD_WIDTH + 1)
        assert ex.execute("i", "Options(Count(Row(f=1)), shards=[2, 2, 2])") == [1]
        assert ex.execute("i", "Options(Count(Row(f=1)), shards=[0, 1, 1])") == [2]

    def test_options_shards_restricts_includes_column(self, env):
        holder, ex = env
        idx = holder.create_index("i")
        f = idx.create_field("f")
        col = 2 * SHARD_WIDTH + 7  # shard 2
        f.set_bit(1, col)
        assert ex.execute(
            "i", f"IncludesColumn(Row(f=1), column={col})"
        ) == [True]
        assert ex.execute(
            "i", f"Options(IncludesColumn(Row(f=1), column={col}), shards=[0])"
        ) == [False]
        assert ex.execute(
            "i", f"Options(IncludesColumn(Row(f=1), column={col}), shards=[2])"
        ) == [True]
