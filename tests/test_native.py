"""Native fastbits library tests: parity with the numpy fallback."""

import numpy as np
import pytest

from pilosa_tpu import native


requires_native = pytest.mark.skipif(
    not native.available(), reason="no C++ toolchain in environment"
)


@requires_native
def test_pack_unpack_popcount_parity():
    rng = np.random.default_rng(5)
    positions = np.unique(rng.choice(1 << 20, 50_000, replace=False)).astype(np.uint64)
    n_words = (1 << 20) // 32

    fast = native.pack_positions(positions, n_words)
    # numpy oracle
    bytes_ = np.zeros(n_words * 4, np.uint8)
    np.bitwise_or.at(
        bytes_,
        (positions >> np.uint64(3)).astype(np.int64),
        np.uint8(1) << (positions & np.uint64(7)).astype(np.uint8),
    )
    slow = bytes_.view("<u4")
    np.testing.assert_array_equal(fast, slow)

    assert native.popcount_words(fast) == positions.size
    np.testing.assert_array_equal(
        native.unpack_positions(fast, 0), positions
    )
    np.testing.assert_array_equal(
        native.unpack_positions(fast, 1 << 30), positions + (1 << 30)
    )


@requires_native
def test_runs_to_words():
    runs = np.array([[0, 5], [100, 100], [65530, 65535]], np.uint16)
    words = native.runs_to_words(runs)
    got = native.unpack_positions(words, 0).tolist()
    assert got == list(range(6)) + [100] + list(range(65530, 65536))


@requires_native
def test_empty_inputs():
    assert native.popcount_words(np.zeros(8, np.uint32)) == 0
    assert native.unpack_positions(np.zeros(8, np.uint32)).size == 0
    out = native.pack_positions(np.empty(0, np.uint64), 8)
    assert out.sum() == 0


def test_packing_api_works_with_or_without_native(monkeypatch):
    """pack_bits/unpack_bits give identical results on both paths."""
    from pilosa_tpu.ops import packing

    rng = np.random.default_rng(6)
    ids = np.unique(rng.choice(1 << 14, 1000, replace=False))
    with_native = packing.pack_bits(ids, 1 << 14)
    monkeypatch.setenv("PILOSA_TPU_NO_NATIVE", "1")
    monkeypatch.setattr(native, "_lib", None)
    without = packing.pack_bits(ids, 1 << 14)
    np.testing.assert_array_equal(with_native, without)
    np.testing.assert_array_equal(
        packing.unpack_bits(without), ids.astype(np.uint64)
    )


def test_sorted_set_ops_match_numpy():
    """union/diff_sorted_u16 (the ARRAY-container import hot path) match
    the numpy set ops they replace, including empty and disjoint edges."""
    rng = np.random.default_rng(17)
    cases = [
        (np.empty(0, np.uint16), np.empty(0, np.uint16)),
        (np.array([3], np.uint16), np.empty(0, np.uint16)),
        (np.empty(0, np.uint16), np.array([9], np.uint16)),
        (np.array([1, 2, 3], np.uint16), np.array([4, 5], np.uint16)),
        (np.array([0, 65535], np.uint16), np.array([0, 65535], np.uint16)),
    ]
    for _ in range(20):
        a = np.unique(rng.choice(1 << 16, rng.integers(0, 4000),
                                 replace=False).astype(np.uint16))
        b = np.unique(rng.choice(1 << 16, rng.integers(0, 4000),
                                 replace=False).astype(np.uint16))
        cases.append((a, b))
    for a, b in cases:
        got_u = native.union_sorted_u16(a, b)
        got_d = native.diff_sorted_u16(a, b)
        if got_u is None:  # no toolchain: numpy fallback covers it
            continue
        np.testing.assert_array_equal(got_u, np.union1d(a, b))
        np.testing.assert_array_equal(
            got_d, np.setdiff1d(a, b, assume_unique=True)
        )


@requires_native
def test_library_is_keyed_by_source_hash():
    """Only the file named after the current source's hash is ever
    loaded: a library under the old fixed name, or under another hash,
    is not what the loader looks for, whatever its mtime."""
    import os

    from pilosa_tpu.native import build

    lib_dir = os.path.dirname(build.SRC)
    planted = [os.path.join(lib_dir, "libfastbits.so"),
               os.path.join(lib_dir, "libfastbits-0000000000000000.so")]
    for path in planted:
        with open(path, "wb") as f:
            f.write(b"not a shared object")
    try:
        assert build.build() == build.lib_path()
        assert build.lib_path() not in planted
        assert native._load()._name == build.lib_path()
    finally:
        for path in planted:
            os.unlink(path)
