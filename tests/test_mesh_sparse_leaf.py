"""A mesh-sharded sparse row leaf's residency miss (ISSUE 39): where a
one-process mesh places a ``_RowSpec`` leaf whose containers are all
arrays, ``batch.host_leaf`` lists the set bits of every chip's slot rows
(``kernels.sparse_rows32`` in ``mesh.size`` shares padded to one bucket),
the lists are placed as one array split over the shard axis, and one
program (``jit_dist_expand_rows``: the one-chip expansion under
``shard_map``) returns the dense leaf sharded as the dense path shards it.

The contract is byte identity of what is PLACED with
``block.stack(host_row)``, at 8 slots (2 a chip, the rehearsal's) and at
512 (128 a chip, the four-chip cell's), and byte identity of every
fall-back with the dense path it takes. On four of tier-1's eight virtual
devices; off the TPU the kernel runs through Pallas' interpreter. The
stand-in index is ``test_row_leaf_decode.py``'s; the served half runs
real fragments through ``API.query`` against numpy over the columns.
"""

import jax
import numpy as np
import pytest

from pilosa_tpu.executor import batch
from pilosa_tpu.executor.executor import _RowSpec
from pilosa_tpu.parallel import DistExecutor, make_mesh
from pilosa_tpu.parallel.mesh import ShardAssignment
from pilosa_tpu.roaring import kernels
from pilosa_tpu.roaring.bitmap import ARRAY, BITMAP, RUN, RoaringBitmap
from pilosa_tpu.server.api import API
from pilosa_tpu.shardwidth import SHARD_WIDTH, WORDS_PER_SHARD
from pilosa_tpu.storage import Holder, residency
from pilosa_tpu.storage.residency import chip_bytes
from pilosa_tpu.utils import tracing
from pilosa_tpu.utils.tracing import stage_metrics

from test_row_leaf_decode import (
    ROW, VIEW, _Index, _kinds_of, _lows, _stacked_reference,
)

CHIPS = 4
# slots of the leaf: shards it is built over (the last slots are padding)
SHARDS = {8: 7, 512: 500}


@pytest.fixture(scope="module")
def mesh():
    if len(jax.devices()) < CHIPS:
        pytest.skip("needs four devices")
    return make_mesh(n_devices=CHIPS)


@pytest.fixture(scope="module")
def executor(mesh):
    return DistExecutor(None, mesh)


@pytest.fixture
def cache():
    """The process's row cache replaced by an empty one."""
    fresh = residency.DeviceRowCache(budget_bytes=1 << 30)
    old = residency.global_row_cache()
    residency.set_global_row_cache(fresh)
    yield fresh
    residency.set_global_row_cache(old)
    fresh.clear()


@pytest.fixture
def staging_of_threes(monkeypatch):
    """Every staging array goes out holding 3s: a list entry the listing
    leaves as it found it names bit 3 of a share."""
    real = batch._staging_array

    def poisoned(shape):
        buf = real(shape)
        buf.fill(3)
        return buf

    monkeypatch.setattr(batch, "_staging_array", poisoned)


def _bitmaps(pos: np.ndarray, n_shards: int) -> dict:
    """{shard: bitmap} holding row ROW's bits ``pos`` (numbers within the
    leaf: ``slot << 20 | column``) and two sparse rows beside it, so that
    the row's window is found and not assumed."""
    pos = np.sort(np.asarray(pos, np.int64))
    cuts = np.searchsorted(pos, np.arange(n_shards + 1) << 20)
    beside = np.asarray([(6 << 20) + 5, (9 << 20) + 70_000], np.uint64)
    return {s: RoaringBitmap.from_ids(np.concatenate((
        beside, (pos[cuts[s]:cuts[s + 1]] & (SHARD_WIDTH - 1)).astype(
            np.uint64) + np.uint64(ROW << 20))))
        for s in range(n_shards)}


def _share_bits(rng, chip: int, rows: int, n: int) -> np.ndarray:
    """``n`` distinct bits of the slot rows chip ``chip`` holds, at most
    4,000 a container, so every container is an array."""
    lo = chip * rows
    per = -(-n // (rows * 16))
    assert per <= 4000
    out = []
    for k in range(rows * 16):
        take = min(per, n - len(out) * per) if n > len(out) * per else 0
        if take <= 0:
            break
        out.append(((lo * 16 + k) << 16)
                   + np.sort(rng.choice(65536, take, replace=False)))
    return np.concatenate(out)


def _case(name: str, slots: int, rng):
    """(positions, bucket the listing must choose or None for the dense
    path) of one leaf of ``slots`` slot rows."""
    n_shards, rows = SHARDS[slots], slots // CHIPS
    largest = kernels.sparse_buckets(rows)[-1]
    if name == "empty_row":
        return np.empty(0, np.int64), 8192
    if name == "one_bit_a_shard":
        return (np.arange(n_shards) << 20) + rng.integers(
            0, SHARD_WIDTH, n_shards), 8192
    if name == "empty_share_beside_a_share_at_the_edge":
        # chip 0 fills its bucket to the last entry, chip 1 holds nothing
        return np.concatenate((
            _share_bits(rng, 0, rows, 8192),
            _share_bits(rng, 2, rows, 700),
            _share_bits(rng, 3, rows, 5))), 8192
    if name == "one_share_one_past_the_edge":
        # the next bucket for every share; at 2 slot rows a chip there is
        # none, and the leaf is decoded dense
        return np.concatenate((
            _share_bits(rng, 1, rows, 40),
            _share_bits(rng, 2, rows, 8193))), (
                16384 if largest >= 16384 else None)
    if name == "both_sides_of_every_chip_boundary":
        edges = np.arange(1, CHIPS) * rows << 20
        return np.concatenate((
            [0], edges - 1, edges, edges + 32, edges - 33,
            [(n_shards << 20) - 1])), 8192
    if name == "a_share_past_its_largest_bucket":
        return np.concatenate((
            _share_bits(rng, 0, rows, 100),
            _share_bits(rng, 2, rows, largest + 1),
        )), None
    assert name.startswith("random")
    n = int(rng.integers(200, 4 * 8192 if slots == 8 else 200_000))
    return rng.choice(n_shards << 20, n, replace=False), "any"


CASES = ["empty_row", "one_bit_a_shard",
         "empty_share_beside_a_share_at_the_edge",
         "one_share_one_past_the_edge",
         "both_sides_of_every_chip_boundary",
         "a_share_past_its_largest_bucket",
         "random0", "random1", "random2"]


@pytest.mark.parametrize("slots", sorted(SHARDS))
@pytest.mark.parametrize("name", CASES)
def test_mesh_leaf_is_placed_word_for_word(name, slots, executor, cache,
                                           staging_of_threes):
    rng = np.random.default_rng([39, slots, CASES.index(name)])
    pos, bucket = _case(name, slots, rng)
    n_shards = SHARDS[slots]
    # a chip that has no shard at all (slot rows past the shards)
    # expands an empty share
    shards = list(range(n_shards))
    idx = _Index({VIEW: _bitmaps(pos, n_shards)})
    assert _kinds_of(idx) <= {ARRAY}
    spec = _RowSpec("f", (VIEW,), 8 if name == "empty_row" else ROW)
    block = ShardAssignment(shards, executor.mesh)
    assert block.padded == slots and block.local_slots == (0, slots)
    want = _stacked_reference(idx, spec, block)
    assert int(np.bitwise_count(want).sum()) == (
        0 if name == "empty_row" else np.unique(pos).size)
    put = executor._leaf_put(block)
    host = batch.host_leaf(idx, spec, block, sparse=put.sparse)
    sparse = bucket is not None
    assert isinstance(host, kernels.SparseRows) == sparse
    if sparse:
        rows = slots // CHIPS
        t1 = kernels.sparse_starts_len(rows)
        assert (host.n_rows, host.parts) == (slots, CHIPS)
        assert bucket == "any" or host.n_pad == bucket
        assert host.packed.shape == (CHIPS * (t1 + host.n_pad),)
        shares = host.packed.reshape(CHIPS, t1 + host.n_pad)
        n_tiles = rows * WORDS_PER_SHARD // 1024
        listed = shares[:, n_tiles].astype(np.int64)
        assert listed.sum() == np.bitwise_count(want).sum()
        assert listed.max() <= host.n_pad
        assert host.n_pad == 8192 or listed.max() > host.n_pad // 2
        for share, n in zip(shares, listed.tolist()):
            # a bit's number within its share; the padding names no bit
            assert (share[t1:t1 + n] < rows * SHARD_WIDTH).all()
            assert (share[t1 + n:] == 0x7FFFFFFF).all()
        np.testing.assert_array_equal(
            host.tiles, np.flatnonzero(want.reshape(-1, 1024).any(axis=1)))
    stats = kernels.global_kernel_stats()
    k0 = dict(stats.metrics())
    got = batch.stacked_leaf(idx, spec, block, put)
    k = {n: v - k0[n] for n, v in stats.metrics().items()}
    assert got.dtype == np.uint32 and got.shape == want.shape
    assert np.asarray(got).tobytes() == want.tobytes()
    # resident, charged and sharded as the dense path's leaf is
    dense = put(want)
    assert got.sharding == dense.sharding
    assert chip_bytes(got) == want.nbytes // CHIPS == cache.bytes_used
    for piece, ref in zip(got.addressable_shards, dense.addressable_shards):
        assert piece.device == ref.device and piece.index == ref.index
    m = cache.metrics()
    assert m["residency_misses"] == 1
    assert m["residency_sparse_misses"] == int(sparse)
    assert m["residency_miss_bytes"] == want.nbytes
    assert m["residency_miss_transfer_bytes"] == (
        host.packed.nbytes if sparse else want.nbytes)
    assert k["hostpath_kernel_calls_total"] == 1
    assert k["hostpath_dense_decodes_total"] == int(not sparse)
    if sparse:
        assert m["residency_miss_transfer_bytes"] <= want.nbytes // 8 + (
            CHIPS * kernels.sparse_starts_len(slots // CHIPS) * 4)


# ------------------------------------------------------------- fall-backs


def _fallback(name: str, rng, mesh, monkeypatch):
    """(index, spec, block, kinds the leaf holds) of a leaf of 8 slots
    that must take the dense path."""
    n_shards = SHARDS[8]
    pos = rng.choice(n_shards << 20, 3000, replace=False)
    by_shard = _bitmaps(pos, n_shards)
    spec = _RowSpec("f", (VIEW,), ROW)
    views = {VIEW: by_shard}
    kinds = {ARRAY}
    if name in ("one_bitmap_among_arrays", "one_run_among_arrays"):
        kind = "bitmap" if "bitmap" in name else "run"
        lows = np.unique(_lows(rng, kind)).astype(np.uint64)
        by_shard[4] = RoaringBitmap.from_ids(np.unique(np.concatenate((
            by_shard[4].to_ids(),
            lows + np.uint64((ROW << 20) + (3 << 16))))))
        kinds = {ARRAY, BITMAP if kind == "bitmap" else RUN}
    elif name == "two_views":
        views["standard_2026"] = _bitmaps(
            rng.choice(n_shards << 20, 900, replace=False), n_shards)
        spec = _RowSpec("f", (VIEW, "standard_2026"), ROW)
    elif name == "two_processes":
        monkeypatch.setattr(jax, "process_count", lambda: 2)
    block = ShardAssignment(list(range(n_shards)), mesh)
    return _Index(views), spec, block, kinds


FALLBACKS = ["one_bitmap_among_arrays", "one_run_among_arrays", "two_views",
             "two_processes"]


@pytest.mark.parametrize("name", FALLBACKS)
def test_every_fallback_is_the_dense_path_byte_for_byte(
        name, executor, cache, monkeypatch, staging_of_threes):
    """What the mesh placement is handed is the array
    ``block.stack(host_row)`` builds, as before this PR, and no sparse
    miss is counted."""
    rng = np.random.default_rng([391, FALLBACKS.index(name)])
    idx, spec, block, kinds = _fallback(name, rng, executor.mesh,
                                        monkeypatch)
    assert _kinds_of(idx, [v for v in spec.views]) == kinds
    want = _stacked_reference(idx, spec, block)
    put = executor._leaf_put(block)
    assert bool(getattr(put, "sparse", 0)) == (name != "two_processes")
    handed = []

    def recording(host):
        handed.append(host)
        return put(host)

    recording.sparse = getattr(put, "sparse", 0)
    recording.expand = getattr(put, "expand", None)
    recording.mesh = executor.mesh
    stats = kernels.global_kernel_stats()
    dense_before = stats.dense_decodes
    got = batch.stacked_leaf(idx, spec, block, recording)
    assert len(handed) == 1 and isinstance(handed[0], np.ndarray)
    assert handed[0].tobytes() == want.tobytes()
    assert np.asarray(got).tobytes() == want.tobytes()
    assert stats.dense_decodes - dense_before == 1
    m = cache.metrics()
    assert (m["residency_misses"], m["residency_sparse_misses"]) == (1, 0)
    assert m["residency_miss_transfer_bytes"] == want.nbytes


def test_a_placement_that_cannot_expand_is_never_asked(cache, mesh):
    """A plain callable (the multi-process put, a test's stand-in) has no
    ``sparse``: the leaf is decoded dense for it."""
    rng = np.random.default_rng(392)
    idx = _Index({VIEW: _bitmaps(rng.choice(7 << 20, 500, replace=False), 7)})
    block = ShardAssignment(list(range(7)), mesh)
    handed = []

    def put(host):
        handed.append(host)
        return jax.device_put(host)

    batch.stacked_leaf(idx, _RowSpec("f", (VIEW,), ROW), block, put)
    assert isinstance(handed[0], np.ndarray)
    assert cache.sparse_misses == 0


def test_every_buckets_mesh_program_is_compiled_with_the_first(
        executor, cache):
    """The first sparse leaf a mesh places compiles the expansion of every
    bucket of its row count; leaves of two other buckets compile nothing
    (16 slot rows a chip: four buckets)."""
    from pilosa_tpu.parallel import dist

    slots, n_shards = 64, 60
    rows = slots // CHIPS
    assert kernels.sparse_buckets(rows) == (8192, 16384, 32768, 65536)
    # as in a process that has expanded nothing yet
    residency._expansions_ready.clear()
    for key in [k for k in dist._DIST_JIT_CACHE if k[0] == "expand_rows"]:
        del dist._DIST_JIT_CACHE[key]
    tracing.install_compile_listener()

    def compiles():  # programs made executable: compiled, or loaded
        m = tracing.device_metrics()
        return m["compiles_total"] + m["compile_cache_loads_total"]

    block = ShardAssignment(list(range(n_shards)), executor.mesh)
    put = executor._leaf_put(block)
    rng = np.random.default_rng(393)
    c0 = c1 = compiles()
    for i, (n_bits, n_pad) in enumerate(((3000, 8192), (70_000, 32768),
                                         (200_000, 65536))):
        idx = _Index({VIEW: _bitmaps(
            rng.choice(n_shards << 20, n_bits, replace=False), n_shards)})
        spec = _RowSpec("f", (VIEW,), ROW)
        want = _stacked_reference(idx, spec, block)
        host = batch.host_leaf(idx, spec, block, sparse=put.sparse)
        assert host.n_pad == n_pad
        got = cache.get_or_build(("leaf", i), None, None, lambda: host,
                                 device_put=put)
        assert np.asarray(got).tobytes() == want.tobytes()
        if i == 0:
            assert compiles() - c0 >= 4
            c1 = compiles()
    assert compiles() == c1 and cache.sparse_misses == 3


# ------------------------------------------------- the served path, and numpy
#
# A toy ``rides`` index of 7 shards (8 slots, 2 a chip) through
# ``API.query`` with the mesh executor: a sparse grid field, an hour
# field, an amount. Every answer is numpy's over the columns, before and
# after the grid rows are evicted and after a ``Set`` patches a resident
# sharded leaf.

INDEX = "rides"
N_SHARDS, SLOTS = 7, 8
PER_SHARD = 20_000
HOURS, AMOUNT = 24, (0, 65_535)
ROW_LEAF = SLOTS * residency.ROW_BYTES


@pytest.fixture(scope="module")
def columns():
    rng = np.random.default_rng(394)
    col = np.concatenate([
        np.sort(rng.choice(SHARD_WIDTH, PER_SHARD, replace=False))
        + shard * SHARD_WIDTH for shard in range(N_SHARDS)])
    n = col.size
    return {
        "column": col,
        "grid": np.minimum(rng.geometric(0.05, n) - 1, 9_999),
        "hour": rng.integers(0, HOURS, n),
        "amount": rng.integers(AMOUNT[0], AMOUNT[1] + 1, n),
    }


@pytest.fixture(scope="module")
def api(tmp_path_factory, columns, mesh):
    holder = Holder(str(tmp_path_factory.mktemp("meshgrid") / "data")).open()
    api = API(holder)
    api.executor = DistExecutor(holder, mesh)
    api.create_index(INDEX)
    for name in ("grid", "hour"):
        api.create_field(INDEX, name)
        api.import_bits(INDEX, name, columns[name], columns["column"])
    api.create_field(INDEX, "amount",
                     {"type": "int", "min": AMOUNT[0], "max": AMOUNT[1]})
    api.import_values(INDEX, "amount", columns["column"], columns["amount"])
    yield api
    holder.close()


def _answers(api, c, g) -> None:
    """Count, GroupBy under a filter leaf and BSI Sum over grid row ``g``
    against numpy."""
    keep = c["grid"] == g
    assert api.query(INDEX, f"Count(Row(grid={g}))")["results"] == [
        int(keep.sum())]
    counts = np.bincount(c["hour"][keep], minlength=HOURS)
    assert api.query(
        INDEX, f"GroupBy(Rows(hour), filter=Row(grid={g}))")["results"] == [[
            {"group": [{"field": "hour", "rowID": h}], "count": int(n)}
            for h, n in enumerate(counts) if n]]
    assert api.query(
        INDEX, f'Sum(Row(grid={g}), field="amount")')["results"] == [
            {"value": int(c["amount"][keep].sum()), "count": int(keep.sum())}]


def test_answers_are_numpys_across_eviction_and_patch(api, columns, cache):
    c = {k: v.copy() for k, v in columns.items()}
    s0 = stage_metrics()
    for g in (0, 3, 11, 40):
        _answers(api, c, g)
    m = cache.metrics()
    # four grid rows went sparse; the hour matrix and the amount's planes
    # (a matrix, planes: dense by kind) did not
    assert m["residency_sparse_misses"] == 4
    assert m["residency_misses"] == 6
    s = {n: v - s0[n] for n, v in stage_metrics().items()}
    assert (s["residency_decode_total"] == s["residency_upload_total"]
            == s["residency_miss_total"] == 6)
    assert 4 * ROW_LEAF < m["residency_miss_bytes"]
    before = dict(m)
    # evict everything; the same answers from leaves placed anew
    cache.clear()
    for g in (3, 40):
        _answers(api, c, g)
    m = cache.metrics()
    assert (m["residency_sparse_misses"]
            - before["residency_sparse_misses"]) == 2
    # a Set patches the resident sharded leaf of row 3: a column of shard
    # 5 (chip 2's share) that held another cell
    column = int(c["column"][np.flatnonzero(
        (c["column"] >> 20 == 5) & (c["grid"] != 3))[0]])
    misses = cache.metrics()["residency_misses"]
    assert api.query(INDEX, f"Set({column}, grid=3)")["results"] == [True]
    c["grid"] = c["grid"].copy()
    at = int(np.flatnonzero(c["column"] == column)[0])
    old = int(c["grid"][at])
    # a set field holds both rows now: numpy's view of row 3 gains the column
    keep3 = (c["grid"] == 3)
    keep3[at] = True
    assert api.query(INDEX, "Count(Row(grid=3))")["results"] == [
        int(keep3.sum())]
    assert api.query(INDEX, f"Count(Row(grid={old}))")["results"] == [
        int((c["grid"] == old).sum())]
    counts = np.bincount(c["hour"][keep3], minlength=HOURS)
    assert api.query(
        INDEX, "GroupBy(Rows(hour), filter=Row(grid=3))")["results"] == [[
            {"group": [{"field": "hour", "rowID": h}], "count": int(n)}
            for h, n in enumerate(counts) if n]]
    assert api.query(INDEX, 'Sum(Row(grid=3), field="amount")')[
        "results"] == [{"value": int(c["amount"][keep3].sum()),
                        "count": int(keep3.sum())}]
    after = cache.metrics()
    assert after["residency_updates"] >= 1   # patched in place,
    assert after["residency_misses"] - misses <= 1  # row `old` at most
