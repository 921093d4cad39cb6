"""M1 storage-tree tests (modeled on the reference's fragment_test.go /
field_test.go / index_test.go / holder_test.go coverage — SURVEY.md §4):
temp-dir fragments, set/clear round-trips, durability (op log + snapshot),
checksum blocks, field-type semantics, holder reopen."""

import datetime as dt

import numpy as np
import pytest

from pilosa_tpu.shardwidth import SHARD_WIDTH
from pilosa_tpu.storage import Field, FieldOptions, Fragment, Holder
from pilosa_tpu.storage.field import BSI_EXISTS_ROW, BSI_OFFSET_ROW
from pilosa_tpu.storage.view import (
    VIEW_STANDARD,
    views_by_time_range,
    views_for_time,
)


@pytest.fixture
def frag(tmp_path):
    f = Fragment(str(tmp_path / "0"), "i", "f", "standard", 0).open()
    yield f
    f.close()


class TestFragment:
    def test_set_clear_roundtrip(self, frag):
        assert frag.set_bit(3, 100)
        assert not frag.set_bit(3, 100)  # already set
        assert frag.contains(3, 100)
        assert frag.count_row(3) == 1
        assert frag.clear_bit(3, 100)
        assert not frag.clear_bit(3, 100)
        assert frag.count_row(3) == 0

    def test_row_words_and_device_row(self, frag):
        cols = [0, 7, 31, 32, 65535, 65536, SHARD_WIDTH - 1]
        for c in cols:
            frag.set_bit(2, c)
        words = frag.row_words(2)
        from pilosa_tpu.ops.packing import unpack_bits

        np.testing.assert_array_equal(unpack_bits(words), np.array(cols, np.uint64))
        dev = np.asarray(frag.device_row(2))
        np.testing.assert_array_equal(dev, words)

    def test_persistence_and_oplog(self, tmp_path):
        path = str(tmp_path / "5")
        f = Fragment(path, "i", "f", "standard", 5).open()
        f.bulk_import([1, 1, 2], [10, 20, 30])
        f.set_bit(9, 99)
        f.clear_bit(1, 10)
        f.close()

        f2 = Fragment(path, "i", "f", "standard", 5).open()
        assert not f2.contains(1, 10)
        assert f2.contains(1, 20)
        assert f2.contains(2, 30)
        assert f2.contains(9, 99)
        assert f2.op_n == 3  # bulk + set + clear replayed from the log
        f2.close()

    def test_snapshot_compacts(self, tmp_path):
        path = str(tmp_path / "0")
        f = Fragment(path, "i", "f", "standard", 0, snapshot_threshold=5).open()
        for i in range(12):
            f.set_bit(0, i)
        assert f.op_n <= 5  # crossed threshold -> compacted
        f.close()
        f2 = Fragment(path, "i", "f", "standard", 0).open()
        assert f2.count_row(0) == 12
        f2.close()

    def test_bulk_import_and_rowids(self, frag):
        rows = np.repeat([0, 4, 7], 1000)
        pos = np.tile(np.arange(1000) * 37 % SHARD_WIDTH, 3)
        changed = frag.bulk_import(rows, pos)
        assert changed == len(np.unique((rows.astype(np.uint64) << np.uint64(20)) + pos))
        assert frag.row_ids() == [0, 4, 7]
        assert frag.max_row_id() == 7

    def test_import_roaring(self, frag):
        from pilosa_tpu.roaring import RoaringBitmap, serialize

        other = RoaringBitmap.from_ids([(1 << 20) + 5, (1 << 20) + 6, 3])
        assert frag.import_roaring(serialize(other)) == 3
        assert frag.contains(1, 5) and frag.contains(1, 6) and frag.contains(0, 3)

    def test_blocks_checksums(self, frag):
        frag.set_bit(0, 1)
        frag.set_bit(99, 1)   # same block (rows 0-99)
        frag.set_bit(100, 1)  # next block
        blocks = dict(frag.blocks())
        assert set(blocks) == {0, 1}
        before = blocks[0]
        frag.set_bit(5, 5)
        assert dict(frag.blocks())[0] != before
        assert dict(frag.blocks())[1] == blocks[1]
        np.testing.assert_array_equal(
            frag.block_ids(1), np.array([(100 << 20) + 1], np.uint64)
        )

    def test_top_pairs(self, frag):
        for row, n in [(1, 5), (2, 50), (3, 20)]:
            frag.bulk_import([row] * n, list(range(n)))
        assert frag.top(2) == [(2, 50), (3, 20)]
        assert frag.top(10, row_ids=[1, 3]) == [(3, 20), (1, 5)]

    def test_write_row_words(self, frag):
        frag.set_bit(0, 1)
        from pilosa_tpu.ops.packing import pack_shard_row

        frag.write_row_words(0, pack_shard_row([2, 3]))
        assert not frag.contains(0, 1)
        assert frag.contains(0, 2) and frag.contains(0, 3)

    def test_position_validation(self, frag):
        with pytest.raises(ValueError):
            frag.set_bit(0, SHARD_WIDTH)
        with pytest.raises(ValueError):
            frag.bulk_import([0], [SHARD_WIDTH + 3])


class TestFieldTypes:
    def test_set_field(self, tmp_path):
        f = Field(str(tmp_path / "f"), "i", "f").open()
        assert f.set_bit(1, 10)
        assert f.set_bit(2, 10)  # multi-value ok
        frag = f.view(VIEW_STANDARD).fragment(0)
        assert frag.contains(1, 10) and frag.contains(2, 10)
        f.close()

    def test_mutex_field(self, tmp_path):
        f = Field(str(tmp_path / "m"), "i", "m", FieldOptions(type="mutex")).open()
        f.set_bit(1, 10)
        f.set_bit(2, 10)  # clears row 1 for column 10
        frag = f.view(VIEW_STANDARD).fragment(0)
        assert not frag.contains(1, 10)
        assert frag.contains(2, 10)
        f.close()

    def test_bool_field(self, tmp_path):
        f = Field(str(tmp_path / "b"), "i", "b", FieldOptions(type="bool")).open()
        f.set_bit(1, 7)
        f.set_bit(0, 7)
        frag = f.view(VIEW_STANDARD).fragment(0)
        assert frag.contains(0, 7) and not frag.contains(1, 7)
        with pytest.raises(ValueError):
            f.set_bit(2, 7)
        f.close()

    def test_int_field_roundtrip(self, tmp_path):
        f = Field(
            str(tmp_path / "v"), "i", "v", FieldOptions(type="int", min=-10, max=1000)
        ).open()
        for col, val in [(0, -10), (1, 0), (2, 777), (3, 1000), (1 << 20, 5)]:
            f.set_value(col, val)
        for col, val in [(0, -10), (1, 0), (2, 777), (3, 1000), (1 << 20, 5)]:
            assert f.value(col) == (val, True)
        assert f.value(99) == (0, False)
        # overwrite clears stale plane bits
        f.set_value(2, 1)
        assert f.value(2) == (1, True)
        with pytest.raises(ValueError):
            f.set_value(0, 1001)
        f.clear_value(3)
        assert f.value(3) == (0, False)
        f.close()

    def test_int_field_planes(self, tmp_path):
        f = Field(
            str(tmp_path / "v"), "i", "v", FieldOptions(type="int", min=0, max=7)
        ).open()
        f.set_value(4, 5)  # 0b101
        frag = f.view(f.bsi_view_name()).fragment(0)
        assert frag.contains(BSI_EXISTS_ROW, 4)
        assert frag.contains(BSI_OFFSET_ROW + 0, 4)
        assert not frag.contains(BSI_OFFSET_ROW + 1, 4)
        assert frag.contains(BSI_OFFSET_ROW + 2, 4)
        f.close()

    def test_time_field_views(self, tmp_path):
        f = Field(
            str(tmp_path / "t"), "i", "t",
            FieldOptions(type="time", time_quantum="YMD"),
        ).open()
        ts = dt.datetime(2019, 1, 2, 15)
        f.set_bit(1, 10, timestamp=ts)
        assert set(f.views) >= {
            "standard", "standard_2019", "standard_201901", "standard_20190102",
        }
        f.close()

    def test_field_meta_persistence(self, tmp_path):
        Field(
            str(tmp_path / "v"), "i", "v", FieldOptions(type="int", min=3, max=9)
        ).open().close()
        f2 = Field(str(tmp_path / "v"), "i", "v").open()
        assert f2.options.type == "int"
        assert (f2.options.min, f2.options.max) == (3, 9)
        f2.close()


class TestTimeViewNames:
    def test_views_for_time(self):
        ts = dt.datetime(2019, 1, 2, 15)
        assert views_for_time("standard", "YMDH", ts) == [
            "standard_2019", "standard_201901", "standard_20190102",
            "standard_2019010215",
        ]

    def test_views_by_time_range_minimal_cover(self):
        got = views_by_time_range(
            "standard", "YMD",
            dt.datetime(2018, 12, 30), dt.datetime(2019, 2, 2),
        )
        assert got == [
            "standard_20181230", "standard_20181231", "standard_201901",
            "standard_20190201",
        ]

    def test_views_by_time_range_full_years(self):
        got = views_by_time_range(
            "standard", "YMDH", dt.datetime(2018, 1, 1), dt.datetime(2020, 1, 1)
        )
        assert got == ["standard_2018", "standard_2019"]


class TestHolder:
    def test_create_open_reopen(self, tmp_path):
        h = Holder(str(tmp_path / "data")).open()
        idx = h.create_index("stars")
        f = idx.create_field("stargazer")
        f.set_bit(1, 100)
        f.set_bit(1, SHARD_WIDTH + 5)  # second shard
        idx.mark_columns_exist([100, SHARD_WIDTH + 5])
        assert idx.available_shards() == [0, 1]
        h.close()

        h2 = Holder(str(tmp_path / "data")).open()
        idx2 = h2.index("stars")
        assert idx2 is not None
        f2 = idx2.field("stargazer")
        assert f2.view(VIEW_STANDARD).fragment(0).contains(1, 100)
        assert f2.view(VIEW_STANDARD).fragment(1).contains(1, 5)
        ex = idx2.existence_fragment(0)
        assert ex.contains(0, 100)
        assert [i["name"] for i in h2.schema()] == ["stars"]
        h2.close()

    def test_delete_index_and_field(self, tmp_path):
        h = Holder(str(tmp_path / "data")).open()
        idx = h.create_index("a")
        idx.create_field("x")
        idx.delete_field("x")
        assert idx.field("x") is None
        h.delete_index("a")
        assert h.index("a") is None
        h2 = Holder(str(tmp_path / "data")).open()
        assert h2.schema() == []
        h.close(); h2.close()

    def test_invalid_names(self, tmp_path):
        h = Holder(str(tmp_path / "data")).open()
        with pytest.raises(ValueError):
            h.create_index("9bad")
        idx = h.create_index("ok")
        with pytest.raises(ValueError):
            idx.create_field("_internal")
        h.close()


def test_concurrent_fragment_writes_do_not_lose_updates(tmp_path):
    """Per-fragment lock (reference fragment.mu): N threads hammering the
    same fragment must land every bit and keep the op log coherent through
    snapshot + reopen."""
    import threading

    from pilosa_tpu.storage.fragment import Fragment

    frag = Fragment(str(tmp_path / "frag"), "i", "f", "standard", 0,
                    snapshot_threshold=64).open()
    n_threads, per_thread = 8, 200
    errs = []

    def worker(t):
        try:
            for k in range(per_thread):
                frag.set_bit(t, k * 7 % (1 << 20))
        except Exception as e:  # pragma: no cover
            errs.append(e)

    threads = [threading.Thread(target=worker, args=(t,)) for t in range(n_threads)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert not errs
    want_per_row = len({k * 7 % (1 << 20) for k in range(per_thread)})
    for t in range(n_threads):
        assert frag.count_row(t) == want_per_row, t
    frag.close()
    # reopen: snapshot + op log replay reproduce the same state
    frag2 = Fragment(str(tmp_path / "frag"), "i", "f", "standard", 0).open()
    for t in range(n_threads):
        assert frag2.count_row(t) == want_per_row, t
    frag2.close()


class TestRowCounts:
    def test_row_counts_matches_per_row_oracle(self, tmp_path):
        from pilosa_tpu.storage import Holder

        holder = Holder(str(tmp_path / "d")).open()
        f = holder.create_index("i").create_field("f")
        frag = f.view("standard", create=True).fragment(0, create=True)
        rng = np.random.default_rng(3)
        rows = rng.integers(0, 5000, 4000, dtype=np.uint64)
        poss = rng.integers(0, 1 << 20, 4000, dtype=np.uint64)
        frag.bulk_import(rows, poss)
        got_rows, got_counts = frag.row_counts()
        want = {}
        for r in np.unique(rows).tolist():
            c = frag.count_row(int(r))
            if c:
                want[int(r)] = c
        assert dict(zip(got_rows.tolist(), got_counts.tolist())) == want
        holder.close()

    def test_row_counts_empty(self, tmp_path):
        from pilosa_tpu.storage import Holder

        holder = Holder(str(tmp_path / "d")).open()
        f = holder.create_index("i").create_field("f")
        frag = f.view("standard", create=True).fragment(0, create=True)
        rows, counts = frag.row_counts()
        assert rows.size == 0 and counts.size == 0
        holder.close()

    def test_discovery_paths_avoid_per_row_counts(self, tmp_path, monkeypatch):
        """Rows() discovery and cold-cache TopN phase 1 must not call
        count_row per row (multi-second host loops at
        50k rows x 1k shards)."""
        from pilosa_tpu.executor import Executor
        from pilosa_tpu.storage import Holder
        from pilosa_tpu.storage.cache import CACHE_TYPE_NONE
        from pilosa_tpu.storage import FieldOptions
        from pilosa_tpu.storage.fragment import Fragment

        holder = Holder(str(tmp_path / "d")).open()
        idx = holder.create_index("i", track_existence=False)
        f = idx.create_field("f", FieldOptions(cache_type=CACHE_TYPE_NONE))
        rng = np.random.default_rng(4)
        seen = set()
        for s in range(4):
            frag = f.view("standard", create=True).fragment(s, create=True)
            rows = rng.integers(0, 2000, 3000, dtype=np.uint64)
            seen.update(rows.tolist())
            frag.bulk_import(rows, rng.integers(0, 1 << 20, 3000, dtype=np.uint64))
        ex = Executor(holder)
        calls = {"n": 0}
        orig = Fragment.count_row

        def counting(self, row):
            calls["n"] += 1
            return orig(self, row)

        monkeypatch.setattr(Fragment, "count_row", counting)
        (rows_res,) = ex.execute("i", "Rows(f)")
        assert rows_res == sorted(seen)
        assert calls["n"] == 0  # discovery is metadata-only
        # cold-cache TopN phase 1: fragment.top falls back to row_counts
        pairs = f.view("standard").fragment(0).top(5)
        assert len(pairs) == 5 and calls["n"] == 0
        holder.close()


class TestBatchedBSIImport:
    def _mk(self, tmp_path, lo=-10, hi=1000):
        from pilosa_tpu.storage.field import Field

        return Field(
            str(tmp_path / "v"), "i", "v",
            FieldOptions(type="int", min=lo, max=hi),
        ).open()

    def test_matches_set_value_loop(self, tmp_path):
        """import_values == a sequential set_value loop: same final
        values, same changed count, incl. overwrites of existing columns
        and in-batch duplicates (last wins)."""
        import numpy as np

        rng = np.random.default_rng(5)
        a = self._mk(tmp_path / "a")
        b = self._mk(tmp_path / "b")
        cols = rng.integers(0, 3 * (1 << 20), 400, dtype=np.uint64)
        vals = rng.integers(-10, 1001, 400, dtype=np.int64)
        # two waves so the second overwrites some of the first
        for wave in (slice(0, 250), slice(150, 400)):
            loop_changed = 0
            seen = {}
            for c, v in zip(cols[wave].tolist(), vals[wave].tolist()):
                loop_changed += a.set_value(int(c), int(v))
                seen[int(c)] = int(v)
            batch_changed = b.import_values(cols[wave], vals[wave])
            assert batch_changed == loop_changed
            for c, v in seen.items():
                assert a.value(c) == (v, True)
                assert b.value(c) == (v, True), c
        a.close()
        b.close()

    def test_duplicate_columns_last_wins(self, tmp_path):
        f = self._mk(tmp_path)
        assert f.import_values([7, 7, 7], [5, 900, 42]) == 1
        assert f.value(7) == (42, True)
        # unchanged re-import reports zero
        assert f.import_values([7], [42]) == 0
        f.close()

    def test_range_validation(self, tmp_path):
        import pytest

        f = self._mk(tmp_path)
        with pytest.raises(ValueError, match="outside field range"):
            f.import_values([1, 2], [5, 2000])
        # nothing applied
        assert f.value(1) == (0, False)
        f.close()


class TestMutexBulkImport:
    def test_import_clears_previous_rows(self, tmp_path):
        """Bulk import into a mutex field preserves the single-value
        invariant: each imported column's previous row is cleared
        (reference bulkImportMutex). Previously plain bulk_import left
        columns set in SEVERAL rows."""
        import numpy as np

        from pilosa_tpu.storage.field import Field

        f = Field(str(tmp_path / "m"), "i", "m",
                  FieldOptions(type="mutex")).open()
        frag = f.view("standard", create=True).fragment(0, create=True)
        for col, row in [(5, 1), (6, 1), (7, 2)]:
            f.set_bit(row, col)
        # move 5 -> row 2, keep 6, add 8 -> row 3; duplicate col 9 keeps last
        changed = frag.import_mutex(
            np.array([2, 1, 3, 1, 2], np.uint64),
            np.array([5, 6, 8, 9, 9], np.uint64),
        )
        assert changed == 3  # 5 moved, 8 new, 9 new (6 was a no-op)
        got = {r: frag.row_columns(r).tolist() for r in frag.row_ids()}
        got = {r: c for r, c in got.items() if c}
        assert got == {1: [6], 2: [5, 7, 9], 3: [8]}
        f.close()

    def test_api_routes_mutex_and_bool_imports(self, tmp_path):
        from pilosa_tpu.server.api import API, ApiError

        holder = Holder(str(tmp_path / "h")).open()
        idx = holder.create_index("i")
        idx.create_field("m", FieldOptions(type="mutex"))
        idx.create_field("b", FieldOptions(type="bool"))
        api = API(holder)
        from pilosa_tpu.executor import Executor

        ex = Executor(holder)
        ex.execute("i", "Set(5, m=1)")
        api.import_bits("i", "m", [2], [5])
        assert ex.execute("i", "Row(m=1)")[0].columns().tolist() == []
        assert ex.execute("i", "Row(m=2)")[0].columns().tolist() == [5]
        api.import_bits("i", "b", [1, 0, 1], [10, 11, 10])
        assert ex.execute("i", "Row(b=true)")[0].columns().tolist() == [10]
        assert ex.execute("i", "Row(b=false)")[0].columns().tolist() == [11]
        import pytest

        with pytest.raises(ApiError, match="bool field rows"):
            api.import_bits("i", "b", [2], [12])
        holder.close()
