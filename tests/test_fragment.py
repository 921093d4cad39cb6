import os

import numpy as np
import pytest

from pilosa_tpu.roaring import kernels, serialize
from pilosa_tpu.roaring.bitmap import RoaringBitmap
from pilosa_tpu.storage.fragment import Fragment


class TestRowsContaining:
    def test_matches_per_row_contains(self, tmp_path):
        import numpy as np

        from pilosa_tpu.storage.fragment import Fragment

        frag = Fragment(str(tmp_path / "f"), "i", "f", "standard", 0).open()
        rng = np.random.default_rng(11)
        # mixed container kinds: sparse rows (array), a dense run row, a
        # bitmap-container row
        rows, cols = [], []
        for r in range(40):
            n = 50 if r % 3 else 6000
            rows.append(np.full(n, r, np.uint64))
            cols.append(rng.integers(0, 1 << 20, n, dtype=np.uint64))
        rows.append(np.full(70000, 40, np.uint64))
        cols.append(np.arange(70000, dtype=np.uint64))  # run containers
        frag.bulk_import(np.concatenate(rows), np.concatenate(cols))

        for pos in [0, 1, 77, 65535, 65536, 69999, 70000, (1 << 20) - 1,
                    int(cols[0][0]), int(cols[3][0])]:
            want = sorted(
                r for r in frag.row_ids() if frag.contains(r, pos)
            )
            assert sorted(frag.rows_containing(pos)) == want, pos
        frag.close()

    def test_contains_low_all_kinds(self):
        import numpy as np

        from pilosa_tpu.roaring.bitmap import Container

        # array
        c = Container.from_lows(np.asarray([3, 9, 1000], np.uint16))
        assert c.contains_low(9) and not c.contains_low(8)
        # run
        c = Container.from_lows(np.arange(100, 4200, dtype=np.uint16))
        assert c.kind == 3 and c.contains_low(100) and c.contains_low(4199)
        assert not c.contains_low(99) and not c.contains_low(4200)
        # bitmap
        lows = np.unique(
            np.random.default_rng(0).integers(0, 65536, 8000).astype(np.uint16)
        )
        c = Container.from_lows(lows)
        assert c.kind == 2
        s = set(lows.tolist())
        for v in [0, 1, 17, 65535, int(lows[0]), int(lows[-1])]:
            assert c.contains_low(v) == (v in s)
        # empty
        c = Container.from_lows(np.empty(0, np.uint16))
        assert not c.contains_low(0)


class TestRowCountsMemo:
    def test_row_counts_memoized_and_invalidated_by_writes(self, tmp_path):
        import numpy as np

        from pilosa_tpu.storage.fragment import Fragment

        frag = Fragment(str(tmp_path / "f"), "i", "f", "standard", 0).open()
        frag.bulk_import(np.asarray([1, 1, 2], np.uint64),
                         np.asarray([10, 20, 30], np.uint64))
        rows, counts = frag.row_counts()
        assert rows.tolist() == [1, 2] and counts.tolist() == [2, 1]
        # memo hit: identical object back while unmutated
        assert frag.row_counts()[0] is rows
        # any write invalidates: a NEW row must appear
        frag.set_bit(7, 40)
        rows2, counts2 = frag.row_counts()
        assert rows2.tolist() == [1, 2, 7]
        assert counts2.tolist() == [2, 1, 1]
        # clears too
        frag.clear_bit(7, 40)
        assert frag.row_counts()[0].tolist() == [1, 2]
        frag.close()


# ------------------------------- the container directory's life (ISSUE 40)
#
# A fragment holds its snapshot's container directory
# (roaring/bitmap.py ContainerDirectory) exactly while its bitmap equals
# the snapshot's bytes: from an open that replayed no op, or a snapshot
# of a fragment that stays open, until the first mutation.


def _snapshot_file(tmp_path, ids):
    path = str(tmp_path / "f")
    with open(path, "wb") as f:
        f.write(serialize(RoaringBitmap.from_ids(np.asarray(ids, np.uint64))))
    return path


def _assert_directory_is_the_bitmap(frag):
    d, f = frag.bitmap.directory, kernels.flatten(frag.bitmap)
    np.testing.assert_array_equal(d.keys, f.keys)
    np.testing.assert_array_equal(d.kinds, f.kinds)
    np.testing.assert_array_equal(d.cards, f.cards)
    if d.all_arrays:
        np.testing.assert_array_equal(d.payload, f.arr_data)


_IDS = [(1 << 20) + 5, (1 << 20) + 70_000, (2 << 20) + 9, (5 << 20) + 1]

_MUTATORS = {
    "set_bit": lambda f: f.set_bit(3, 77),
    "clear_bit": lambda f: f.clear_bit(1, 5),
    "clear_row": lambda f: f.clear_row(2),
    "write_row_words": lambda f: f.write_row_words(
        4, np.ones(1 << 15, np.uint32)),
    "bulk_import": lambda f: f.bulk_import([3, 3], [1, 2]),
    "bulk_import_through_the_merge_kernel": lambda f: f.bulk_import(
        np.full(500, 3), np.arange(500) * 1000),
    "import_mutex": lambda f: f.import_mutex(
        np.asarray([7]), np.asarray([5])),
    "import_bsi": lambda f: f.import_bsi(
        np.asarray([40]), np.asarray([6]), 4),
    "import_roaring": lambda f: f.import_roaring(
        serialize(RoaringBitmap.from_ids(np.asarray([123], np.uint64)))),
    "add_ids": lambda f: f.add_ids([(6 << 20) + 3]),
    "add_ids_mutex": lambda f: f.add_ids_mutex([(6 << 20) + 3]),
    "add_ids_value": lambda f: f.add_ids_value([(6 << 20) + 3]),
    "apply_recovered": lambda f: f.apply_recovered(1, [(6 << 20) + 3]),
}


class TestContainerDirectory:
    def test_an_open_that_replays_nothing_has_it(self, tmp_path):
        frag = Fragment(_snapshot_file(tmp_path, _IDS), "i", "f",
                        "standard", 0).open()
        assert frag.op_n == 0
        _assert_directory_is_the_bitmap(frag)
        frag.close()

    @pytest.mark.parametrize("name", sorted(_MUTATORS))
    def test_every_mutator_drops_it_and_a_snapshot_brings_it_back(
            self, name, tmp_path):
        frag = Fragment(_snapshot_file(tmp_path, _IDS), "i", "f",
                        "standard", 0).open()
        held = frag.bitmap.directory
        assert held is not None
        before = frag.bitmap.to_ids().tolist()
        _MUTATORS[name](frag)
        assert frag.bitmap.to_ids().tolist() != before
        assert frag.bitmap.directory is None
        # what a reader took before the write is as it was
        assert held.keys.tolist() == sorted({i >> 16 for i in before})
        frag.snapshot()
        assert frag.bitmap.directory is not held
        _assert_directory_is_the_bitmap(frag)
        frag.close()

    def test_an_open_that_replays_ops_has_none(self, tmp_path):
        path = _snapshot_file(tmp_path, _IDS)
        frag = Fragment(path, "i", "f", "standard", 0).open()
        frag.set_bit(3, 77)
        frag.close()  # no WAL: the op stays in the file's log
        frag = Fragment(path, "i", "f", "standard", 0).open()
        assert frag.op_n == 1 and frag.contains(3, 77)
        assert frag.bitmap.directory is None
        frag.snapshot()
        _assert_directory_is_the_bitmap(frag)
        frag.close()
        frag = Fragment(path, "i", "f", "standard", 0).open()
        assert frag.op_n == 0 and frag.contains(3, 77)
        _assert_directory_is_the_bitmap(frag)
        frag.close()

    def test_an_irregular_snapshot_opens_without_one(self, tmp_path):
        """Descriptors last key first (each payload in its descriptor's
        turn): the reference decoder reads it, no directory describes
        it, and the fragment serves it by the walk."""
        path = _snapshot_file(tmp_path, [5, (1 << 20) + 70_000])
        buf = open(path, "rb").read()
        assert len(buf) == 20 + 2 * 16 + 2 * 2
        with open(path, "wb") as f:
            f.write(buf[:20] + buf[36:52] + buf[20:36] + buf[54:56]
                    + buf[52:54])
        frag = Fragment(path, "i", "f", "standard", 0).open()
        assert frag.bitmap.directory is None
        assert frag.contains(0, 5) and frag.contains(1, 70_000)
        assert frag.count() == 2
        frag.close()

    def test_a_snapshot_that_fails_leaves_what_was_there(self, tmp_path,
                                                         monkeypatch):
        frag = Fragment(_snapshot_file(tmp_path, _IDS), "i", "f",
                        "standard", 0).open()
        frag.set_bit(3, 77)

        def no_space(*a, **kw):
            raise OSError(28, "No space left on device")

        monkeypatch.setattr(os, "replace", no_space)
        with pytest.raises(OSError):
            frag.snapshot()
        monkeypatch.undo()
        assert frag.bitmap.directory is None and frag.contains(3, 77)
        frag.close()
