"""Property tests: vectorized roaring kernels vs the per-container
reference paths (pilosa_tpu/roaring/kernels.py).

The kernels' contract is BYTE-IDENTITY with the per-container
implementations they replaced, so the reference loops live on here
verbatim — every op, digest, decode, and diff is checked against them
over randomized array/bitmap/run mixes plus the degenerate shapes
(empty fragment, full container, single-container, single-bit).
"""

import hashlib

import numpy as np
import pytest

from pilosa_tpu.roaring import kernels, serialize
from pilosa_tpu.roaring.bitmap import RoaringBitmap, ARRAY, BITMAP, RUN
from pilosa_tpu.roaring.format import deserialize, encode_op, OP_ADD
from pilosa_tpu.storage.integrity import block_digests

# ------------------------------------------------- per-container reference


def ref_to_ids(bm: RoaringBitmap) -> np.ndarray:
    """The pre-kernel RoaringBitmap.to_ids, verbatim."""
    parts = []
    for key in bm.keys:
        c = bm._containers.get(key)
        if c is None:
            continue
        lows = c.lows().astype(np.uint64)
        parts.append(lows + (np.uint64(key) << np.uint64(16)))
    if not parts:
        return np.empty(0, np.uint64)
    return np.concatenate(parts)


def ref_dense_range_words32(bm: RoaringBitmap, start: int,
                            stop: int) -> np.ndarray:
    """The pre-kernel RoaringBitmap.dense_range_words32, verbatim."""
    n_containers = (stop - start) >> 16
    out = np.zeros((n_containers, 2048), np.uint32)
    base_key = start >> 16
    for i in range(n_containers):
        c = bm._containers.get(base_key + i)
        if c is not None:
            out[i] = c.dense_words32()
    return out.reshape(-1)


def ref_range_ids(bm: RoaringBitmap, start: int, stop: int) -> np.ndarray:
    ids = ref_to_ids(bm)
    return ids[(ids >= np.uint64(start)) & (ids < np.uint64(stop))]


def ref_op(bm_a: RoaringBitmap, bm_b: RoaringBitmap, op: str) -> np.ndarray:
    """Set-algebra reference on materialized id sets (independent
    formulation, not shared machinery with the kernels)."""
    a = set(ref_to_ids(bm_a).tolist())
    b = set(ref_to_ids(bm_b).tolist())
    out = {"and": a & b, "or": a | b, "xor": a ^ b, "andnot": a - b}[op]
    return np.asarray(sorted(out), np.uint64)


# ---------------------------------------------------------- fragment maker


def make_bitmap(rng: np.random.Generator, n_containers: int,
                kinds: str = "mixed", key_span: int = 64) -> RoaringBitmap:
    """Random bitmap with a controlled container-kind mix. Kinds are
    steered through Container.from_lows by the shape of the lows."""
    bm = RoaringBitmap()
    keys = rng.choice(key_span, size=min(n_containers, key_span),
                      replace=False)
    ids = []
    for key in keys.tolist():
        kind = (rng.choice(["array", "bitmap", "run", "full", "single"])
                if kinds == "mixed" else kinds)
        if kind == "array":
            n = int(rng.integers(1, 2000))
            lows = rng.choice(65536, size=n, replace=False)
        elif kind == "bitmap":
            n = int(rng.integers(4200, 20000))
            lows = rng.choice(65536, size=n, replace=False)
        elif kind == "run":
            starts = np.sort(rng.choice(65000, size=int(rng.integers(1, 8)),
                                        replace=False))
            lows = np.concatenate([
                np.arange(s, min(s + int(rng.integers(20, 400)), 65536))
                for s in starts.tolist()
            ])
        elif kind == "full":
            lows = np.arange(65536)
        else:  # single
            lows = rng.choice(65536, size=1)
        lows = np.unique(lows).astype(np.uint64)
        ids.append(lows + (np.uint64(key) << np.uint64(16)))
    if ids:
        bm.add_ids(np.concatenate(ids))
    return bm


def assert_ids_identical(got: np.ndarray, want: np.ndarray):
    assert got.dtype == np.uint64
    assert got.tobytes() == want.astype(np.uint64).tobytes()


# ----------------------------------------------------------------- to_ids


@pytest.mark.parametrize("seed", range(6))
def test_fragment_ids_matches_reference(seed):
    rng = np.random.default_rng(seed)
    bm = make_bitmap(rng, n_containers=int(rng.integers(1, 40)))
    flat = kernels.flatten(bm)
    assert_ids_identical(kernels.fragment_ids(flat), ref_to_ids(bm))


def test_fragment_ids_empty_and_degenerate():
    assert kernels.fragment_ids(kernels.flatten(RoaringBitmap())).size == 0
    for kind in ("full", "single", "run", "bitmap", "array"):
        rng = np.random.default_rng(hash(kind) % 2**32)
        bm = make_bitmap(rng, 1, kinds=kind)
        assert_ids_identical(
            kernels.fragment_ids(kernels.flatten(bm)), ref_to_ids(bm))


def test_flatten_key_range_subsets():
    rng = np.random.default_rng(7)
    bm = make_bitmap(rng, n_containers=30, key_span=48)
    ids = ref_to_ids(bm)
    for lo, hi in [(0, 15), (16, 31), (5, 5), (40, 200), (100, 120)]:
        flat = kernels.flatten(bm, lo, hi)
        want = ids[((ids >> np.uint64(16)) >= lo)
                   & ((ids >> np.uint64(16)) <= hi)]
        assert_ids_identical(kernels.fragment_ids(flat), want)


def test_range_ids_matches_reference():
    rng = np.random.default_rng(11)
    bm = make_bitmap(rng, n_containers=20, key_span=32)
    for start, stop in [(0, 1 << 20), (1 << 20, 3 << 20), (65536, 131072)]:
        flat = kernels.flatten(bm, start >> 16, (stop - 1) >> 16)
        assert_ids_identical(kernels.range_ids(flat, start, stop),
                             ref_range_ids(bm, start, stop))


# ----------------------------------------------------------- dense decode


@pytest.mark.parametrize("seed", range(6))
def test_dense_words32_matches_reference(seed):
    rng = np.random.default_rng(100 + seed)
    bm = make_bitmap(rng, n_containers=int(rng.integers(1, 30)), key_span=32)
    # decode in 16-container windows (a fragment row) and whole-range
    for base_key, n in [(0, 16), (16, 16), (0, 32), (3, 5)]:
        flat = kernels.flatten(bm, base_key, base_key + n - 1)
        got = kernels.dense_words32(flat, base_key, n)
        want = ref_dense_range_words32(bm, base_key << 16,
                                       (base_key + n) << 16)
        assert got.dtype == np.uint32
        assert got.tobytes() == want.tobytes()


def test_dense_words32_empty_window():
    bm = RoaringBitmap()
    flat = kernels.flatten(bm, 0, 15)
    got = kernels.dense_words32(flat, 0, 16)
    assert got.shape == (16 * 2048,)
    assert not got.any()


# --------------------------------------------------------------- popcount


@pytest.mark.parametrize("seed", range(4))
def test_popcount_matches_cardinality(seed):
    rng = np.random.default_rng(200 + seed)
    bm = make_bitmap(rng, n_containers=int(rng.integers(1, 25)))
    flat = kernels.flatten(bm)
    assert kernels.popcount(flat) == bm.count() == ref_to_ids(bm).size


# ---------------------------------------------------------------- set ops


@pytest.mark.parametrize("seed", range(6))
@pytest.mark.parametrize("op", ["and", "or", "xor", "andnot"])
def test_set_ops_match_reference(seed, op):
    rng = np.random.default_rng(300 + seed)
    # overlapping key ranges so every kind×kind pairing occurs
    a = make_bitmap(rng, n_containers=int(rng.integers(1, 20)), key_span=24)
    b = make_bitmap(rng, n_containers=int(rng.integers(1, 20)), key_span=24)
    fn = {"and": kernels.fragment_and, "or": kernels.fragment_or,
          "xor": kernels.fragment_xor, "andnot": kernels.fragment_andnot}[op]
    assert_ids_identical(fn(a, b), ref_op(a, b, op))


@pytest.mark.parametrize("op", ["and", "or", "xor", "andnot"])
def test_set_ops_empty_operands(op):
    rng = np.random.default_rng(5)
    a = make_bitmap(rng, 5)
    empty = RoaringBitmap()
    fn = {"and": kernels.fragment_and, "or": kernels.fragment_or,
          "xor": kernels.fragment_xor, "andnot": kernels.fragment_andnot}[op]
    assert_ids_identical(fn(a, empty), ref_op(a, empty, op))
    assert_ids_identical(fn(empty, a), ref_op(empty, a, op))
    assert fn(empty, empty).size == 0


def test_bitmap_bitmap_lane_stays_in_word_space():
    # two pure-bitmap operands share every key: the AND must not
    # materialize either side (set_ops counter moves, ids counter only
    # by the RESULT extraction, which is nonzero — so instead pin
    # correctness of the word lane on a crafted disjoint/overlap case)
    lows_a = np.arange(0, 30000, 2, dtype=np.uint64)
    lows_b = np.arange(0, 30000, 3, dtype=np.uint64)
    a = RoaringBitmap.from_ids(lows_a)
    b = RoaringBitmap.from_ids(lows_b)
    assert a.container(0).kind == BITMAP and b.container(0).kind == BITMAP
    assert_ids_identical(kernels.fragment_and(a, b), ref_op(a, b, "and"))
    assert_ids_identical(kernels.fragment_xor(a, b), ref_op(a, b, "xor"))


def test_galloping_intersect_lopsided():
    big = np.arange(0, 3_000_000, 3, dtype=np.uint64)
    small = np.asarray([0, 5, 9, 2_999_997, 4_000_000], np.uint64)
    got = kernels.intersect_sorted(small, big)
    want = np.intersect1d(small, big)
    assert_ids_identical(got, want)
    got = kernels.setdiff_sorted(small, big)
    want = np.setdiff1d(small, big)
    assert_ids_identical(got, want)


def test_diff_ids():
    rng = np.random.default_rng(17)
    a = make_bitmap(rng, 10, key_span=12)
    b = make_bitmap(rng, 10, key_span=12)
    only_a, only_b = kernels.diff_ids(a, b)
    assert_ids_identical(only_a, ref_op(a, b, "andnot"))
    assert_ids_identical(only_b, ref_op(b, a, "andnot"))


# ---------------------------------------------------------------- digests


@pytest.mark.parametrize("seed", range(4))
def test_digests_identical_through_kernel_ids(seed):
    rng = np.random.default_rng(400 + seed)
    bm = make_bitmap(rng, n_containers=int(rng.integers(1, 30)), key_span=400)
    flat = kernels.flatten(bm)
    assert (block_digests(kernels.fragment_ids(flat))
            == block_digests(ref_to_ids(bm)))


def test_block_slices_matches_per_block_mask():
    rng = np.random.default_rng(21)
    bm = make_bitmap(rng, n_containers=40, key_span=4000)
    ids = ref_to_ids(bm)
    blocks = sorted({int(b) for b, _ in block_digests(ids)})
    got = kernels.block_slices(ids, blocks + [10**6])
    for b in blocks:
        lo = np.uint64(b * 100) << np.uint64(20)
        hi = np.uint64((b + 1) * 100) << np.uint64(20)
        want = ids[(ids >= lo) & (ids < hi)]
        assert_ids_identical(got[b], want)
    assert got[10**6].size == 0


def test_diff_digests():
    local = [(0, "aa"), (1, "bb"), (3, "dd")]
    peer = [(0, "aa"), (1, "XX"), (2, "cc")]
    assert kernels.diff_digests(local, peer) == [1, 2]
    assert kernels.diff_digests(peer, peer) == []
    assert kernels.diff_digests([], peer) == [0, 1, 2]


# ------------------------------------------------------ snapshot fast path


@pytest.mark.parametrize("seed", range(6))
def test_snapshot_ids_matches_deserialize(seed):
    rng = np.random.default_rng(500 + seed)
    bm = make_bitmap(rng, n_containers=int(rng.integers(1, 30)))
    buf = serialize(bm)
    # append an op tail: ops_at must land exactly where deserialize says
    tail = encode_op(OP_ADD, np.asarray([1, 2, 3], np.uint64))
    ids, ops_at = kernels.snapshot_ids(buf + tail)
    want_bm, want_at = deserialize(buf + tail)
    assert ops_at == want_at
    assert_ids_identical(ids, ref_to_ids(want_bm))


def test_snapshot_ids_empty():
    ids, ops_at = kernels.snapshot_ids(serialize(RoaringBitmap()))
    assert ids.size == 0 and ids.dtype == np.uint64
    assert ops_at == 20  # header only


def test_snapshot_ids_rejects_what_deserialize_rejects():
    bm = make_bitmap(np.random.default_rng(3), 5)
    buf = serialize(bm)
    for bad in (buf[:10], buf[:-3], b"\x00" * 40):
        try:
            deserialize(bad)
            ref_raised = False
        except ValueError:
            ref_raised = True
        if ref_raised:
            with pytest.raises(ValueError):
                kernels.snapshot_ids(bad)


def test_snapshot_ids_irregular_falls_back():
    # duplicate container keys: dict semantics (last wins) — the fast
    # parser must detect and defer to the reference decoder
    bm = RoaringBitmap.from_ids(np.asarray([1, 2, 70000], np.uint64))
    buf = bytearray(serialize(bm))
    # rewrite the second descriptor's key to equal the first (key at
    # offset 20 + 16*i)
    buf[20 + 16 : 20 + 16 + 8] = buf[20 : 20 + 8]
    want, _ = deserialize(bytes(buf))
    ids, _ = kernels.snapshot_ids(bytes(buf))
    assert_ids_identical(ids, ref_to_ids(want))


# -------------------------------------------------- container directory


def _irregular(bm: RoaringBitmap, how: str) -> bytes:
    """Snapshot bytes ``deserialize`` accepts and no directory describes."""
    buf = bytearray(serialize(bm))
    n = len(bm.keys)

    def descr(i: int) -> slice:
        return slice(20 + 16 * i, 36 + 16 * i)

    if how == "duplicate_keys":
        buf[36:44] = buf[20:28]  # the second descriptor's key, the first's
    elif how == "unsorted_descriptors":
        # descriptors and payloads written last key first: each payload
        # still follows its descriptor's turn
        ends = 20 + 16 * n + np.cumsum(
            [int.from_bytes(buf[descr(i)][12:], "little") for i in range(n)])
        starts = [20 + 16 * n, *ends[:-1].tolist()]
        buf = (buf[:20]
               + b"".join(buf[descr(i)] for i in reversed(range(n)))
               + b"".join(buf[starts[i]:ends[i]] for i in reversed(range(n))))
    else:  # a bitmap payload one word short, the header and the file with it
        i = next(j for j, k in enumerate(bm.keys)
                 if bm._containers[k].kind == BITMAP)
        end = 20 + 16 * n + sum(
            int.from_bytes(buf[descr(j)][12:], "little") for j in range(i + 1))
        del buf[end - 8:end]
        buf[32 + 16 * i:36 + 16 * i] = (8184).to_bytes(4, "little")
        total = int.from_bytes(buf[12:20], "little") - 8
        buf[12:20] = total.to_bytes(8, "little")
    return bytes(buf)


@pytest.mark.parametrize("kinds", ["mixed", "array", "bitmap", "run"])
@pytest.mark.parametrize("seed", range(3))
def test_directory_of_a_snapshot_is_the_flat_view(kinds, seed):
    """Keys, kinds, cardinalities and every payload, container for
    container, without a Container made; nothing of the buffer copied."""
    rng = np.random.default_rng([40, seed])
    bm = make_bitmap(rng, n_containers=int(rng.integers(1, 40)), kinds=kinds)
    buf = serialize(bm)
    d = kernels.directory_from_snapshot(buf)
    f = kernels.flatten(bm)
    np.testing.assert_array_equal(d.keys, f.keys)
    assert d.keys.dtype == np.int64 and d.starts.size == d.keys.size + 1
    np.testing.assert_array_equal(d.kinds, f.kinds)
    np.testing.assert_array_equal(d.cards, f.cards)
    assert d.all_arrays == (f.arr_sel.size == f.n_containers)
    assert not d.payload.flags.writeable and not d.payload.flags.owndata
    for i, (kind, j) in enumerate(zip(f.kinds.tolist(), f.kind_row.tolist())):
        raw = d.payload[d.starts[i]:d.starts[i + 1]]
        if kind == ARRAY:
            want = f.arr_data[f.arr_off[j]:f.arr_off[j + 1]]
        elif kind == BITMAP:
            raw, want = raw.view("<u8"), f.bmp_words[j]
        else:
            raw = raw.reshape(-1, 2)
            want = f.run_data[f.run_off[j]:f.run_off[j + 1]]
        np.testing.assert_array_equal(raw, want)
    assert kernels.directory_from_snapshot(
        buf + encode_op(OP_ADD, np.asarray([1, 2], np.uint64))
    ).starts[-1] == d.starts[-1]


def test_directory_of_an_empty_snapshot():
    d = kernels.directory_from_snapshot(serialize(RoaringBitmap()))
    assert d.keys.size == 0 and d.starts.tolist() == [0] and d.all_arrays


@pytest.mark.parametrize("how", ["duplicate_keys", "unsorted_descriptors",
                                 "short_bitmap_payload"])
def test_irregular_snapshot_has_no_directory(how):
    bm = make_bitmap(np.random.default_rng(41), 12, kinds="bitmap")
    bad = _irregular(bm, how)
    got, _ = deserialize(bad)  # still a snapshot the reference reads
    if how == "unsorted_descriptors":
        assert got == bm
    assert kernels.directory_from_snapshot(serialize(bm)) is not None
    assert kernels.directory_from_snapshot(bad) is None


# ------------------------------------------------------- live-path parity


def test_bitmap_to_ids_now_kernel_backed():
    """RoaringBitmap.to_ids routes through the kernels and stays
    byte-identical to the reference loop."""
    rng = np.random.default_rng(42)
    bm = make_bitmap(rng, n_containers=25)
    assert_ids_identical(bm.to_ids(), ref_to_ids(bm))


def test_digest_language_unchanged():
    """The blake2b-over-ids digest itself is pinned — kernels feed it,
    never reimplement it."""
    ids = np.asarray([0, 1, (1 << 20) * 100 + 5], np.uint64)
    want = hashlib.blake2b(ids[:2].astype("<u8").tobytes(),
                           digest_size=16).hexdigest()
    assert block_digests(ids)[0] == (0, want)


# ------------------------------------------------- PROFILE cost accounting


class TestProfileContainerAccounting:
    """The batched ``row_words`` path must tally ``containers scanned
    by kind`` exactly as the retired per-container walk did: one
    ``note_containers`` call per kernel invocation whose totals equal
    a per-container recount of the row window."""

    def _fragment_with_known_row(self, tmp_path):
        from pilosa_tpu.storage.fragment import Fragment

        frag = Fragment(str(tmp_path / "f"), "i", "f", "standard", 0).open()
        cols = [
            np.asarray([5, 9, 70000], np.uint64),          # 2 array cont.
            np.arange(3 << 16, (3 << 16) + 5000,            # 1 run cont.
                      dtype=np.uint64),
        ]
        rng = np.random.default_rng(7)
        cols.append(np.unique(rng.integers(                 # 1 bitmap cont.
            5 << 16, 6 << 16, 9000).astype(np.uint64)))
        cols = np.concatenate(cols)
        frag.bulk_import(np.zeros(cols.size, np.uint64), cols)
        return frag

    def _recount_reference(self, frag, row):
        """The per-container reference tally the old path produced."""
        base_key = (row << 20) >> 16
        counts = {ARRAY: 0, BITMAP: 0, RUN: 0}
        for key in range(base_key, base_key + 16):
            c = frag.bitmap._containers.get(key)
            if c is not None and c.n:
                counts[c.kind] += 1
        return counts[ARRAY], counts[BITMAP], counts[RUN]

    def test_row_words_tally_matches_per_container_walk(self, tmp_path):
        from pilosa_tpu.utils.cost import (
            activate_cost, deactivate_cost, new_cost_context,
            set_cost_enabled,
        )

        frag = self._fragment_with_known_row(tmp_path)
        try:
            set_cost_enabled(True)
            ctx = new_cost_context("t", "i")
            tok = activate_cost(ctx)
            try:
                frag.row_words(0)
            finally:
                deactivate_cost(tok)
            got = (ctx.c_array, ctx.c_bitmap, ctx.c_run)
            assert got == self._recount_reference(frag, 0)
            # pinned absolute counts for the constructed mix — a
            # regression here means the batched path's accounting
            # drifted from one-tally-per-kernel-call
            assert got == (2, 1, 1)
            assert ctx.container_scans() == 4
        finally:
            frag.close()

    def test_row_words_tally_accumulates_per_call(self, tmp_path):
        from pilosa_tpu.utils.cost import (
            activate_cost, deactivate_cost, new_cost_context,
            set_cost_enabled,
        )

        frag = self._fragment_with_known_row(tmp_path)
        try:
            set_cost_enabled(True)
            ctx = new_cost_context("t", "i")
            tok = activate_cost(ctx)
            try:
                frag.row_words(0)
                frag.row_words(0)   # second decode tallies again
                frag.row_words(1)   # empty row: zero containers
            finally:
                deactivate_cost(tok)
            assert (ctx.c_array, ctx.c_bitmap, ctx.c_run) == (4, 2, 2)
        finally:
            frag.close()
