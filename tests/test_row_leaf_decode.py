"""The whole-leaf row decode (ISSUE 35): ``batch.host_leaf`` against the
per-shard stack it replaced on a residency miss.

The contract is byte identity with ``block.stack(host_row)``, which calls
``Fragment.row_words`` a shard: for every container kind and their mixes,
an empty row, a missing field, view or fragment, a zero slot past the
shards, several views ORed, a ``local_slots`` sub-span (the mesh's
``ShardAssignment``), and a leaf of array containers dense enough that
``dense_words32`` would take its window-sized bool image. Beside the
bytes: the per-request container tally keeps its totals, the
``hostpath_*`` counters rise by one a leaf, and no temporary of the
decode is as large as the leaf.

The index is a stand-in (``field`` / ``view`` / ``fragment`` lookups over
dicts); the fragments are real ``Fragment`` objects, never opened, whose
bitmaps are built here, so the reference side runs the real
``row_words``.
"""

import os
import tracemalloc

import numpy as np
import pytest

from pilosa_tpu.executor import batch
from pilosa_tpu.executor.executor import _RowSpec
from pilosa_tpu.roaring import kernels
from pilosa_tpu.roaring.bitmap import ARRAY, BITMAP, RUN, RoaringBitmap
from pilosa_tpu.roaring.format import serialize
from pilosa_tpu.shardwidth import WORDS_PER_SHARD
from pilosa_tpu.storage.fragment import Fragment

ROW = 7          # the row the leaves name; rows 6 and 9 lie beside it
VIEW = "standard"


# ---------------------------------------------------------------- stand-ins


class _View:
    def __init__(self, fragments: dict):
        self.fragments = fragments

    def fragment(self, shard: int):
        return self.fragments.get(shard)


class _Field:
    def __init__(self, views: dict):
        self.views = views

    def view(self, name: str):
        return self.views.get(name)


class _Index:
    """``views``: view name -> {shard: RoaringBitmap, or a Fragment as it
    is}, all of field "f"."""

    scope, name = "", "i"

    def __init__(self, views: dict):
        self.fields = {"f": _Field({
            vname: _View({shard: (bm if isinstance(bm, Fragment)
                                  else _fragment(vname, shard, bm))
                          for shard, bm in by_shard.items()})
            for vname, by_shard in views.items()})}

    def field(self, name: str):
        return self.fields.get(name)


def _fragment(view: str, shard: int, bitmap: RoaringBitmap) -> Fragment:
    frag = Fragment("/nonexistent", "i", "f", view, shard)  # never opened
    frag.bitmap = bitmap
    return frag


# ------------------------------------------------------------ shard makers


def _lows(rng, kind: str) -> np.ndarray:
    """Low 16 bits of one container that ``Container.from_lows`` stores
    as ``kind``."""
    if kind == "array":
        return rng.choice(65536, int(rng.integers(1, 2000)), replace=False)
    if kind == "thick":  # an array container near its 4,096 limit
        return rng.choice(65536, int(rng.integers(3000, 4097)), replace=False)
    if kind == "past":   # sixteen of them are past 1/128 of a row
        return rng.choice(65536, int(rng.integers(600, 900)), replace=False)
    if kind == "bitmap":
        return rng.choice(65536, int(rng.integers(4200, 30000)), replace=False)
    if kind == "full":
        return np.arange(65536)
    if kind == "single":
        return rng.choice(65536, 1)
    starts = rng.choice(65000, int(rng.integers(1, 8)), replace=False)
    return np.concatenate([np.arange(s, min(s + int(rng.integers(70, 900)),
                                            65536)) for s in starts.tolist()])


def _shard(rng, kinds, containers=None) -> RoaringBitmap:
    """One shard's bitmap: row ROW's containers drawn from ``kinds``
    (``containers`` of the 16, all when None), and sparse rows on either
    side so that the window is found, not assumed."""
    ids = [np.asarray([(6 << 20) + 5, (9 << 20) + 70_000], np.uint64)]
    slots = (range(16) if containers is None else
             rng.choice(16, containers, replace=False).tolist())
    for k in slots:
        lows = np.unique(_lows(rng, str(rng.choice(kinds))))
        ids.append(lows.astype(np.uint64) + np.uint64((ROW << 20) + (k << 16)))
    return RoaringBitmap.from_ids(np.concatenate(ids))


def _kinds_of(idx, views=(VIEW,)) -> set:
    out = set()
    for v in views:
        for frag in idx.field("f").view(v).fragments.values():
            f = kernels.flatten(frag.bitmap, ROW * 16, ROW * 16 + 15)
            out |= set(f.kinds.tolist())
    return out


# ---------------------------------------------------------------- the check


@pytest.fixture
def poisoned_staging(monkeypatch):
    """Every staging array goes out holding all-ones, as a recycled one
    may hold anything: a word the decode forgets to write shows."""
    real = batch._staging_array

    def poisoned(shape):
        buf = real(shape)
        buf.fill(0xFFFFFFFF)
        return buf

    monkeypatch.setattr(batch, "_staging_array", poisoned)


def _stacked_reference(idx, spec, block) -> np.ndarray:
    return block.stack(lambda shard: batch.host_row(idx, spec, shard),
                       inner=(WORDS_PER_SHARD,)).copy()


def _assert_identical(idx, spec, block) -> np.ndarray:
    want = _stacked_reference(idx, spec, block)
    got = batch.host_leaf(idx, spec, block)
    assert got.dtype == np.uint32 and got.shape == want.shape
    assert got.tobytes() == want.tobytes()
    return got


LEAVES = {
    # name: (kinds of the row's containers, containers a shard, kinds
    #        the built leaf must hold)
    "array_only": (["array", "single"], None, {ARRAY}),
    "bitmap_only": (["bitmap"], None, {BITMAP}),
    "bitmap_some": (["bitmap"], 5, {BITMAP}),
    "run": (["run", "full"], 9, {RUN}),
    "mixed": (["array", "bitmap", "run", "full", "single", "thick"], 11,
              {ARRAY, BITMAP, RUN}),
}


@pytest.mark.parametrize("seed", range(3))
@pytest.mark.parametrize("name", sorted(LEAVES))
def test_leaf_equals_the_stack_of_row_words(name, seed, poisoned_staging):
    """Three shards, so one zero slot of four; every container kind."""
    kinds, containers, holds = LEAVES[name]
    rng = np.random.default_rng([35, seed])
    idx = _Index({VIEW: {s: _shard(rng, kinds, containers)
                         for s in (0, 1, 5)}})
    assert _kinds_of(idx) == holds
    block = batch.ShardBlock([0, 1, 5])
    got = _assert_identical(idx, _RowSpec("f", (VIEW,), ROW), block)
    assert got[:3].any(axis=1).all() and not got[3].any()


def test_bitmaps_that_cover_the_leaf_are_copied_without_a_fill(
        poisoned_staging):
    """Every container of every slot a bitmap: the slot-wise copy alone
    writes the leaf (no zero fill is needed, and none is missed)."""
    rng = np.random.default_rng(351)
    idx = _Index({VIEW: {s: _shard(rng, ["bitmap"]) for s in (2, 3)}})
    assert _kinds_of(idx) == {BITMAP}
    _assert_identical(idx, _RowSpec("f", (VIEW,), ROW),
                      batch.ShardBlock([2, 3]))


@pytest.mark.parametrize("what", ["empty_row", "missing_fragment",
                                  "missing_view", "missing_field",
                                  "no_shards"])
def test_what_is_not_there_reads_zeros(what, poisoned_staging):
    rng = np.random.default_rng(352)
    idx = _Index({VIEW: {s: _shard(rng, ["array", "bitmap"], 4)
                         for s in (0, 1, 2)}})
    spec = _RowSpec("f", (VIEW,), ROW)
    block = batch.ShardBlock([0, 1, 2])
    if what == "empty_row":
        spec = _RowSpec("f", (VIEW,), 8)  # between the rows written
    elif what == "missing_fragment":
        block = batch.ShardBlock([0, 1, 2, 3, 4])  # 3 and 4 have no file
    elif what == "missing_view":
        spec = _RowSpec("f", ("standard_2026",), ROW)
    elif what == "missing_field":
        spec = _RowSpec("g", (VIEW,), ROW)
    else:
        block = batch.ShardBlock([])
    got = _assert_identical(idx, spec, block)
    if what == "missing_fragment":
        assert got[:3].any(axis=1).all() and not got[3:].any()
    else:
        assert not got.any()


@pytest.mark.parametrize("seed", range(3))
def test_two_views_are_ored(seed, poisoned_staging):
    """A time range's views: the same container key in both (bitmap over
    bitmap, array over run, ...), a shard only one of them has, and a view
    the field lacks."""
    rng = np.random.default_rng([353, seed])
    kinds = ["array", "bitmap", "run", "full", "thick"]
    idx = _Index({
        "standard_2025": {s: _shard(rng, kinds, 12) for s in (0, 1, 2)},
        "standard_2026": {s: _shard(rng, kinds, 12) for s in (1, 2, 4)},
    })
    views = ("standard_2025", "standard_2024", "standard_2026")
    assert _kinds_of(idx, (views[0], views[2])) == {ARRAY, BITMAP, RUN}
    block = batch.ShardBlock([0, 1, 2, 4])
    got = _assert_identical(idx, _RowSpec("f", views, ROW), block)
    one = _stacked_reference(idx, _RowSpec("f", views[:1], ROW), block)
    assert (got | one).tobytes() == got.tobytes() != one.tobytes()


@pytest.mark.parametrize("span", [(0, 4), (4, 8), (2, 5), (6, 8), (3, 3)])
def test_a_sub_span_of_local_slots(span, poisoned_staging):
    """The mesh's ShardAssignment narrows ``local_slots``: a process
    decodes its own slots only, zero slots past the shards included."""
    rng = np.random.default_rng(354)
    shards = [0, 1, 2, 3, 4, 5]  # padded to 8 slots
    idx = _Index({VIEW: {s: _shard(rng, ["array", "bitmap", "run"], 8)
                         for s in shards}})
    spec = _RowSpec("f", (VIEW,), ROW)
    whole = _stacked_reference(idx, spec, batch.ShardBlock(shards))
    block = batch.ShardBlock(shards)
    block.local_slots = span
    got = _assert_identical(idx, spec, block)
    assert got.shape == (span[1] - span[0], WORDS_PER_SHARD)
    assert got.tobytes() == whole[span[0]:span[1]].tobytes()


def test_dense_array_containers_need_no_temporary_as_large_as_the_leaf(
        poisoned_staging):
    """Array containers past 1/128 of the row, where ``dense_words32``
    writes a bool image of its window: at leaf scale that image would be
    eight times the leaf. Here 32 slots: rows 0-27 past the threshold (a
    row's image at a time), rows 28-30 sparse (the one scatter), row 31
    thick arrays beside bitmaps."""
    rng = np.random.default_rng(355)
    by_shard = {s: _shard(rng, ["past"]) for s in range(28)}
    by_shard |= {s: _shard(rng, ["array"], 4) for s in (28, 29, 30)}
    by_shard[31] = _shard(rng, ["thick", "bitmap"])
    idx = _Index({VIEW: by_shard})
    spec = _RowSpec("f", (VIEW,), ROW)
    block = batch.ShardBlock(list(range(32)))
    flat = kernels.flatten_rows(list(by_shard.items()), ROW)
    per_row = np.bincount(flat.keys[flat.arr_sel] // 16,
                          weights=np.diff(flat.arr_off), minlength=32)
    threshold = 16 << 9  # dense_words32's, for a row's 16 containers
    assert (per_row[:28] >= threshold).all() and per_row[31] >= threshold
    assert (per_row[28:31] < threshold).all()
    del flat
    want = _stacked_reference(idx, spec, block)
    batch.host_leaf(idx, spec, block)  # the staging array exists from here
    tracemalloc.start()
    try:
        base = tracemalloc.get_traced_memory()[0]
        tracemalloc.reset_peak()
        got = batch.host_leaf(idx, spec, block)
        peak = tracemalloc.get_traced_memory()[1] - base
    finally:
        tracemalloc.stop()
    assert got.tobytes() == want.tobytes()
    # everything the decode held at once (the gathered array containers'
    # copy, one row's bool image, its positions) is under the 4 MiB leaf;
    # a bool image of the leaf alone would be 32 MiB
    assert peak < want.nbytes, (peak, want.nbytes)


def test_the_counters_rise_once_a_leaf_and_the_tally_keeps_its_totals():
    """``hostpath_dense_decodes_total`` and ``hostpath_kernel_calls_total``
    rise by one a leaf where the stack raised them by one a shard; the
    containers flattened, and the per-request tally by kind, are the
    same sums."""
    from pilosa_tpu.utils.cost import (
        activate_cost, deactivate_cost, new_cost_context, set_cost_enabled,
    )

    rng = np.random.default_rng(356)
    idx = _Index({VIEW: {s: _shard(rng, ["array", "bitmap", "run"], 10)
                         for s in (0, 1, 2, 3, 4)}})
    spec = _RowSpec("f", (VIEW,), ROW)
    block = batch.ShardBlock([0, 1, 2, 3, 4])
    stats = kernels.global_kernel_stats()
    set_cost_enabled(True)

    def run(fn) -> tuple:
        ctx = new_cost_context("t", "i")
        tok = activate_cost(ctx)
        before = dict(stats.metrics())
        try:
            fn()
        finally:
            deactivate_cost(tok)
        moved = {k: v - before[k] for k, v in stats.metrics().items()}
        return (ctx.c_array, ctx.c_bitmap, ctx.c_run), moved

    tally_a, moved_a = run(lambda: _stacked_reference(idx, spec, block))
    tally_b, moved_b = run(lambda: batch.host_leaf(idx, spec, block))
    assert tally_a == tally_b and sum(tally_b) == 50 and min(tally_b) > 0
    assert moved_a["hostpath_dense_decodes_total"] == 5
    assert moved_b["hostpath_dense_decodes_total"] == 1
    assert moved_a["hostpath_kernel_calls_total"] == 5
    assert moved_b["hostpath_kernel_calls_total"] == 1
    assert (moved_a["hostpath_containers_flattened_total"]
            == moved_b["hostpath_containers_flattened_total"] == 50)


def test_a_container_emptied_or_gone_under_the_walk_is_skipped():
    """``flatten``'s lock-free discipline: a key whose container a writer
    has just removed (``.get`` answers None) or emptied contributes
    nothing and is not counted."""
    rng = np.random.default_rng(357)
    bm = _shard(rng, ["array"], 6)
    present = [k for k in bm.keys if k >> 4 == ROW]
    gone, emptied = present[0], present[1]
    del bm._containers[gone]              # the key list still names it
    bm._containers[emptied].n = 0
    flat = kernels.flatten_rows([(0, bm)], ROW)
    assert flat.n_containers == 4
    assert set(flat.keys.tolist()) == {k - ROW * 16 for k in present[2:]}
    out = np.full((1, WORDS_PER_SHARD), 0xFFFFFFFF, np.uint32)
    kernels.dense_rows32(flat, out)
    want = kernels.dense_words32(
        kernels.flatten(bm, ROW * 16, ROW * 16 + 15), ROW * 16, 16)
    assert out.tobytes() == want.tobytes()


def test_an_array_container_out_of_order_decodes_as_row_words_does():
    """A corrupt-but-decodable file can hold an array container whose
    values are not sorted, or repeat: both paths set whatever bits the
    payload names."""
    rng = np.random.default_rng(358)
    by_shard = {s: _shard(rng, ["array", "bitmap"], 7) for s in range(6)}
    for bm in by_shard.values():
        for key in bm.keys:
            c = bm._containers[key]
            if key >> 4 == ROW and c.kind == ARRAY and c.data.size > 3:
                c.data = np.concatenate((c.data[::-1], c.data[:3]))
    idx = _Index({VIEW: by_shard})
    _assert_identical(idx, _RowSpec("f", (VIEW,), ROW),
                      batch.ShardBlock(list(by_shard)))


def test_dense_rows32_refuses_an_array_it_could_not_write_in_place():
    flat = kernels.flatten_rows([], ROW)
    with pytest.raises(ValueError):
        kernels.dense_rows32(flat, np.zeros((2, 2 * WORDS_PER_SHARD),
                                            np.uint32)[:, ::2])


# ------------------------------------------------- the sparse form (ISSUE 38)
#
# With ``sparse=True`` (the row cache places the leaf itself) a leaf whose
# containers are all arrays comes back as its set bits, and the cache's
# expansion program makes the dense leaf. The contract is the same byte
# identity, held on what is PLACED: ``block.stack(host_row)`` against the
# device array. Three shards, so four slot rows: buckets of 8,192 and
# 16,384 listed bits, the second an eighth of the leaf's 512 KiB.


def _exact_bits(rng, n_bits: int, shards=(0, 1, 5)) -> dict:
    """{shard: bitmap} with ``n_bits`` bits in row ROW over ``shards``, a
    few hundred a container at most (array containers), and the sparse
    rows beside it that ``_shard`` writes."""
    pos = np.sort(rng.choice(len(shards) << 20, n_bits, replace=False))
    out = {}
    for i, shard in enumerate(shards):
        mine = pos[pos >> 20 == i] & ((1 << 20) - 1)
        ids = np.concatenate((
            np.asarray([(6 << 20) + 5, (9 << 20) + 70_000], np.uint64),
            mine.astype(np.uint64) + np.uint64(ROW << 20)))
        out[shard] = RoaringBitmap.from_ids(ids)
    return out


def _with_one(rng, kind: str) -> dict:
    """Array containers everywhere but one of shard 1's, which is a
    ``kind`` container."""
    by_shard = _exact_bits(rng, 3000)
    lows = np.unique(_lows(rng, kind)).astype(np.uint64)
    ids = np.concatenate((by_shard[1].to_ids(),
                          lows + np.uint64((ROW << 20) + (3 << 16))))
    by_shard[1] = RoaringBitmap.from_ids(np.unique(ids))
    return by_shard


def _sparse_case(name: str, rng):
    """(index, spec, sparse path taken?) of one case."""
    spec = _RowSpec("f", (VIEW,), ROW)
    if name == "empty_row":
        return _Index({VIEW: _exact_bits(rng, 500)}), _RowSpec(
            "f", (VIEW,), 8), True
    if name == "absent_from_shards_and_a_view":
        # shard 1 has no bit of the row, shard 5 no fragment, and the
        # second view the spec names does not exist: one view is read,
        # its keys ascend, the leaf is sparse
        by_shard = _exact_bits(rng, 700, shards=(0,))
        by_shard[1] = _exact_bits(rng, 0, shards=(1,))[1]
        return _Index({VIEW: by_shard}), _RowSpec(
            "f", (VIEW, "standard_2026"), ROW), True
    if name.startswith("bits_"):
        n = int(name[5:])
        # one past the largest bucket falls back to the dense decode
        return _Index({VIEW: _exact_bits(rng, n)}), spec, n <= 16384
    if name == "zero_slot_past_the_shards":
        return _Index({VIEW: _exact_bits(rng, 2500)}), spec, True
    if name in ("one_bitmap_among_arrays", "one_run_among_arrays"):
        kind = "bitmap" if "bitmap" in name else "run"
        return _Index({VIEW: _with_one(rng, kind)}), spec, False
    if name == "two_views_share_a_bit":
        # the leaf ORs two views of each slot, so a container key comes
        # twice and a tile's bits are no longer one stretch of the list
        # (and a bit set in both views would be listed twice): dense
        a = _exact_bits(rng, 900)
        b = {s: RoaringBitmap.from_ids(np.concatenate((
            bm.to_ids()[:40], [np.uint64((ROW << 20) + 123_456)])))
            for s, bm in _exact_bits(rng, 900).items()}
        for s in a:
            a[s] = RoaringBitmap.from_ids(np.unique(np.concatenate((
                a[s].to_ids(), b[s].to_ids()[:20]))))
        return (_Index({"standard_2025": a, "standard_2026": b}),
                _RowSpec("f", ("standard_2025", "standard_2026"), ROW),
                False)
    by_shard = _exact_bits(rng, 1200)
    for bm in by_shard.values():
        for key in bm.keys:
            c = bm._containers[key]
            if key >> 4 == ROW and c.kind == ARRAY and c.data.size > 3:
                if name == "array_repeats_a_value":
                    # still in tile order: listed twice, ORed once
                    c.data = np.concatenate((c.data, c.data[-1:]))
                else:
                    c.data = c.data[::-1]
    return (_Index({VIEW: by_shard}), spec,
            name == "array_repeats_a_value")


SPARSE_CASES = [
    "empty_row", "absent_from_shards_and_a_view",
    "bits_1", "bits_8192", "bits_8193", "bits_16384", "bits_16385",
    "zero_slot_past_the_shards", "one_bitmap_among_arrays",
    "one_run_among_arrays", "two_views_share_a_bit",
    "array_repeats_a_value", "array_out_of_tile_order",
]


@pytest.fixture
def staging_of_threes(monkeypatch):
    """Every staging array goes out holding 3s: a list entry the decode
    leaves as it found it names bit 3 of the leaf."""
    real = batch._staging_array

    def poisoned(shape):
        buf = real(shape)
        buf.fill(3)
        return buf

    monkeypatch.setattr(batch, "_staging_array", poisoned)


@pytest.mark.parametrize("name", SPARSE_CASES)
def test_sparse_leaf_is_placed_word_for_word(name, staging_of_threes):
    from pilosa_tpu.storage.residency import DeviceRowCache

    rng = np.random.default_rng([38, SPARSE_CASES.index(name)])
    idx, spec, sparse = _sparse_case(name, rng)
    block = batch.ShardBlock([0, 1, 5])
    want = _stacked_reference(idx, spec, block)
    stats = kernels.global_kernel_stats()
    dense_before = stats.dense_decodes
    host = batch.host_leaf(idx, spec, block, sparse=True)
    assert isinstance(host, kernels.SparseRows) == sparse
    views = [v for v in spec.views if idx.field("f").view(v)]
    kinds = _kinds_of(idx, views)
    if "one_" in name:
        assert kinds == {ARRAY, BITMAP if "bitmap" in name else RUN}
    else:
        assert kinds <= {ARRAY}
    assert stats.dense_decodes - dense_before == (0 if sparse else 1)
    if sparse:
        assert host.n_rows == 4
        assert host.packed.nbytes * 4 <= want.nbytes + 4 * (
            kernels.sparse_starts_len(4) * 4)
        assert host.n_pad == (16384 if name in ("bits_8193", "bits_16384")
                              else 8192)
        np.testing.assert_array_equal(
            host.tiles, np.flatnonzero(want.reshape(-1, 1024).any(axis=1)))
        # the padding after the listed bits names a bit no leaf has
        t1 = kernels.sparse_starts_len(4)
        assert host.packed.size == t1 + host.n_pad
        listed = int(host.packed[4 * 32])
        assert listed >= int(np.bitwise_count(want).sum())
        assert (host.packed[t1 + listed:] == 0x7FFFFFFF).all()
        assert (host.packed[t1:t1 + listed] < want.size * 32).all()
    cache = DeviceRowCache(budget_bytes=8 << 20)
    got = np.asarray(cache.get_or_build(("leaf", name), None, None,
                                        lambda: host))
    assert got.dtype == np.uint32 and got.shape == want.shape
    assert got.tobytes() == want.tobytes()
    assert cache.sparse_misses == int(sparse)
    assert cache.miss_bytes == want.nbytes
    if name == "zero_slot_past_the_shards":
        assert got[:3].any(axis=1).all() and not got[3].any()
    if name == "empty_row":
        assert not got.any()


def test_sparse_is_asked_for_only_where_the_cache_places_the_leaf():
    """``host_leaf`` alone, as the write probe's callers and the mesh's
    placement use it, is the dense decode it was."""
    rng = np.random.default_rng(381)
    idx = _Index({VIEW: _exact_bits(rng, 900)})
    got = batch.host_leaf(idx, _RowSpec("f", (VIEW,), ROW),
                          batch.ShardBlock([0, 1, 5]))
    assert isinstance(got, np.ndarray)


# ------------------------------------- the container directory (ISSUE 40)
#
# A fragment opened from a snapshot holds the snapshot's container
# directory, and ``flatten_rows`` slices a leaf of array containers from
# the directories instead of walking the containers. The contract is the
# view the walk gives, element for element, whatever a fragment holds or
# lacks; the fragments here are real ones, opened from files, and the
# reference is the walk of the same containers with the directories
# taken away (and, for the leaf, ``block.stack(host_row)`` as above).


@pytest.fixture
def opened(tmp_path):
    """``opened(view, shard, bitmap)``: a Fragment opened from a snapshot
    file of ``bitmap`` (from the file that is there for None), closed
    when the test ends."""
    made = []

    def make(view: str, shard: int, bitmap: RoaringBitmap | None) -> Fragment:
        path = str(tmp_path / view / str(shard))
        os.makedirs(os.path.dirname(path), exist_ok=True)
        if bitmap is not None:
            with open(path, "wb") as f:
                f.write(serialize(bitmap))
        made.append(Fragment(path, "i", "f", view, shard).open())
        return made[-1]

    yield make
    for frag in made:
        frag.close()


def _leaf_bitmaps(idx, spec, shards) -> list:
    """The (slot, bitmap) pairs ``host_leaf`` hands to ``flatten_rows``."""
    out = []
    for vname in spec.views:
        view = idx.field(spec.field).view(vname)
        for slot, shard in enumerate(shards if view else ()):
            frag = view.fragment(shard)
            if frag is not None:
                out.append((slot, frag.bitmap))
    return out


def _without_directories(bitmaps: list) -> list:
    """The same containers behind bitmaps that have no directory."""
    out = []
    for slot, bm in bitmaps:
        bare = RoaringBitmap()
        bare.keys, bare._containers = bm.keys, bm._containers
        out.append((slot, bare))
    return out


def _with_container(bitmap: RoaringBitmap, rng, kind: str, row: int):
    """``bitmap`` and one ``kind`` container in ``row``."""
    lows = np.unique(_lows(rng, kind)).astype(np.uint64)
    return RoaringBitmap.from_ids(np.unique(np.concatenate((
        bitmap.to_ids(), lows + np.uint64((row << 20) + (3 << 16))))))


N_DIR = 8  # fragments of a directory case (128 where the case says so)


def _directory_case(name: str, rng, opened):
    """(index, spec, shards, (windows read from a directory, windows
    walked)) of one case."""
    spec = _RowSpec("f", (VIEW,), ROW)
    shards = list(range(N_DIR))
    by_shard = _exact_bits(rng, 600 * N_DIR, shards=shards)
    if name == "all_arrays":
        expect = (N_DIR, 0)
    elif name in ("bitmap_in_another_row", "run_in_another_row"):
        kind = "bitmap" if "bitmap" in name else "run"
        by_shard[2] = _with_container(by_shard[2], rng, kind, ROW + 2)
        expect = (N_DIR, 0)
    elif name in ("bitmap_in_the_row", "run_in_the_row"):
        kind = "bitmap" if "bitmap" in name else "run"
        by_shard[2] = _with_container(by_shard[2], rng, kind, ROW)
        expect = (0, N_DIR)  # the whole leaf is walked
    elif name == "missing_fragment":
        del by_shard[3]
        expect = (N_DIR - 1, 0)
    elif name == "empty_window":
        by_shard[3] = _exact_bits(rng, 0, shards=(3,))[3]
        expect = (N_DIR, 0)
    elif name == "row_past_the_last_key":
        spec = _RowSpec("f", (VIEW,), 11)  # the files end in row 9
        expect = (N_DIR, 0)
    elif name == "row_that_ends_the_file":
        spec = _RowSpec("f", (VIEW,), 9)
        expect = (N_DIR, 0)
    elif name == "several_views":
        frags = {s: opened(VIEW, s, bm) for s, bm in by_shard.items()}
        other = {s: opened("standard_2026", s, bm) for s, bm in _exact_bits(
            rng, 300 * N_DIR, shards=shards).items()}
        return (_Index({VIEW: frags, "standard_2026": other}),
                _RowSpec("f", (VIEW, "standard_2026"), ROW), shards,
                (0, 2 * N_DIR))
    elif name in ("written_after_open", "reopened_after_snapshot"):
        shards = list(range(128))
        by_shard = _exact_bits(rng, 200 * 128, shards=shards)
        expect = (127, 1) if name == "written_after_open" else (128, 0)
    frags = {s: opened(VIEW, s, bm) for s, bm in by_shard.items()}
    assert all(f.bitmap.directory is not None for f in frags.values())
    if name in ("written_after_open", "reopened_after_snapshot"):
        frag = frags[77]
        assert not frag.contains(ROW, 70_001)
        assert frag.set_bit(ROW, 70_001)
        assert frag.bitmap.directory is None
        if name == "reopened_after_snapshot":
            frag.snapshot()
            assert frag.bitmap.directory is not None
            frag.close()
            frags[77] = opened(VIEW, 77, None)
            assert frags[77].bitmap.directory is not None
    return _Index({VIEW: frags}), spec, shards, expect


DIRECTORY_CASES = [
    "all_arrays", "bitmap_in_another_row", "run_in_another_row",
    "bitmap_in_the_row", "run_in_the_row", "written_after_open",
    "missing_fragment", "empty_window", "row_past_the_last_key",
    "row_that_ends_the_file", "several_views", "reopened_after_snapshot",
]


def _staging_of(value: int):
    def staging(shape):
        return np.full(shape, value, np.uint32)
    return staging


@pytest.mark.parametrize("name", DIRECTORY_CASES)
def test_directory_gather_is_the_walked_view(name, opened, poisoned_staging):
    rng = np.random.default_rng([40, DIRECTORY_CASES.index(name)])
    idx, spec, shards, expect = _directory_case(name, rng, opened)
    bitmaps = _leaf_bitmaps(idx, spec, shards)
    stats = kernels.global_kernel_stats()
    before = stats.metrics()
    got = kernels.flatten_rows(bitmaps, spec.row)
    moved = {k: v - before[k] for k, v in stats.metrics().items()}
    assert (moved["hostpath_directory_windows_total"],
            moved["hostpath_walked_windows_total"]) == expect
    want = kernels.flatten_rows(_without_directories(bitmaps), spec.row)
    assert moved["hostpath_containers_flattened_total"] == want.n_containers
    # the leaf comes again: where every fragment has its directory the
    # view is now read from their stack, and is the same view
    again = kernels.flatten_rows(bitmaps, spec.row)
    stacked = type(kernels._stacks.get(id(bitmaps[0][1].directory))
                   ) is kernels._DirectoryStack
    assert stacked == (name not in ("several_views", "written_after_open"))
    after = stats.metrics()
    assert (after["hostpath_directory_windows_total"]
            - before["hostpath_directory_windows_total"],
            after["hostpath_walked_windows_total"]
            - before["hostpath_walked_windows_total"]) == (
        2 * expect[0], 2 * expect[1] + len(bitmaps))  # and want's walk
    for flat in (got, again):
        for field in ("keys", "kinds", "cards", "kind_row", "arr_sel",
                      "arr_off", "arr_data", "bmp_sel", "run_sel",
                      "run_data", "run_off"):
            a, b = getattr(flat, field), getattr(want, field)
            assert a.dtype == b.dtype and a.shape == b.shape, field
            np.testing.assert_array_equal(a, b, err_msg=field)
        assert flat.kind_counts() == want.kind_counts()
    if name in ("written_after_open", "reopened_after_snapshot"):
        # the bit written is in the view: container 1 of slot 77
        at = int(np.searchsorted(got.keys, 77 * 16 + 1))
        assert got.keys[at] == 77 * 16 + 1
        assert 70_001 - 65_536 in got.arr_data[
            got.arr_off[at]:got.arr_off[at + 1]].tolist()
    # the same listing of set bits, in one share and in four
    n_rows = len(shards)
    for parts in (1, 4):
        a = kernels.sparse_rows32(got, n_rows, _staging_of(3), parts)
        b = kernels.sparse_rows32(want, n_rows, _staging_of(3), parts)
        assert (a is None) == (b is None) == (
            "in_the_row" in name or name == "several_views")
        if a is not None:
            assert (a.n_rows, a.n_pad, a.parts) == (b.n_rows, b.n_pad, parts)
            assert a.packed.tobytes() == b.packed.tobytes()
            np.testing.assert_array_equal(a.tiles, b.tiles)
    # and the same dense leaf, which is the stack of row_words
    block = batch.ShardBlock(shards)
    leaf = _assert_identical(idx, spec, block)
    out = np.full_like(leaf, 0xFFFFFFFF)
    kernels.dense_rows32(got, out)
    assert out.tobytes() == leaf.tobytes()


def test_a_directory_taken_before_a_write_reads_the_older_snapshot(opened):
    """The gather holds what it took: a write that lands after a reader
    took the directory swaps containers the reader never looks at."""
    rng = np.random.default_rng(401)
    frag = opened(VIEW, 0, _exact_bits(rng, 900, shards=(0,))[0])
    bm = frag.bitmap
    held = RoaringBitmap()
    held.keys, held._containers = bm.keys, bm._containers
    held.directory = bm.directory
    before = kernels.flatten_rows([(0, bm)], ROW)
    assert frag.set_bit(ROW, 70_001) and bm.directory is None
    after = kernels.flatten_rows([(0, held)], ROW)  # containers swapped
    assert after.arr_data.tobytes() == before.arr_data.tobytes()
    assert kernels.flatten_rows([(0, bm)], ROW).total() == before.total() + 1


def test_a_stack_lives_as_long_as_its_directories(opened):
    """The stack of a leaf's directories is made when the leaf comes the
    second time, serves it until a write drops one of them (the leaf is
    then sliced a fragment at a time, the written one walked), and is
    made anew from the directory a snapshot brings."""
    rng = np.random.default_rng(402)
    shards = list(range(6))
    frags = [opened(VIEW, s, bm) for s, bm in _exact_bits(
        rng, 3000, shards=shards).items()]
    bitmaps = [(f.shard, f.bitmap) for f in frags]

    def stack():
        held = kernels._stacks.get(id(bitmaps[0][1].directory))
        return held if type(held) is kernels._DirectoryStack else None

    def view():
        flat = kernels.flatten_rows(bitmaps, ROW)
        want = kernels.flatten_rows(_without_directories(bitmaps), ROW)
        assert flat.arr_data.tobytes() == want.arr_data.tobytes()
        assert flat.keys.tolist() == want.keys.tolist()
        assert flat.arr_off.tolist() == want.arr_off.tolist()
        return flat

    view()
    assert stack() is None  # seen once: remembered, not stacked
    view()
    first = stack()
    assert first is not None
    assert first.dirs == [b.directory for _, b in bitmaps]
    assert view().total() == 3000 and stack() is first
    assert frags[3].set_bit(ROW, 70_001)
    assert view().total() == 3001  # sliced, the written fragment walked
    frags[3].snapshot()
    # the new list is seen once: the stale stack goes, none is made yet
    assert view().total() == 3001 and stack() is None
    assert view().total() == 3001
    second = stack()
    assert second is not first and second.dirs[3] is frags[3].bitmap.directory
    # a stale stack is never read: the first fragment's own rewrite
    assert frags[0].clear_bit(ROW, int(view().arr_data[0]))
    frags[0].snapshot()
    assert view().total() == 3000 and view().total() == 3000


def test_more_leaves_than_stacks_are_kept_never_pay_for_one(opened):
    """Leaves that do not come again before they are forgotten are
    sliced a fragment at a time every time."""
    rng = np.random.default_rng(403)
    kernels._stacks.clear()
    leaves = []
    for n in range(kernels._STACKS_KEPT + 1):
        frags = [opened(f"v{n}", s, bm) for s, bm in _exact_bits(
            rng, 500, shards=(0, 1)).items()]
        leaves.append([(f.shard, f.bitmap) for f in frags])
    for _ in range(3):
        for bitmaps in leaves:
            assert kernels.flatten_rows(bitmaps, ROW).total() == 500
    assert len(kernels._stacks) == kernels._STACKS_KEPT
    assert not any(type(v) is kernels._DirectoryStack
                   for v in kernels._stacks.values())
    for _ in range(2):  # one of them comes twice in a row: stacked
        assert kernels.flatten_rows(leaves[0], ROW).total() == 500
    assert type(kernels._stacks[id(leaves[0][0][1].directory)]
                ) is kernels._DirectoryStack
