"""Two-tier HBM residency: demote-compress on eviction, scatter-promote
on hit (storage/residency.py; SURVEY.md §7.3 hard part #1)."""

import threading

import numpy as np
import pytest

from pilosa_tpu.shardwidth import WORDS_PER_SHARD
from pilosa_tpu.storage.residency import (
    COMPRESS_BLOCK_WORDS,
    PURGE,
    ROW_BYTES,
    DeviceRowCache,
    WriteEvent,
)


def sparse_row(rng, n_blocks_set):
    """Dense uint32[WORDS_PER_SHARD] with data in n_blocks_set blocks."""
    row = np.zeros(WORDS_PER_SHARD, np.uint32)
    total = WORDS_PER_SHARD // COMPRESS_BLOCK_WORDS
    for b in rng.choice(total, n_blocks_set, replace=False):
        lo = b * COMPRESS_BLOCK_WORDS
        row[lo : lo + COMPRESS_BLOCK_WORDS] = rng.integers(
            1, 1 << 32, COMPRESS_BLOCK_WORDS, dtype=np.uint32
        )
    return row


class CountingDecoder:
    def __init__(self, host):
        self.host = host
        self.calls = 0

    def __call__(self):
        self.calls += 1
        return self.host


def test_demote_compress_promote_roundtrip():
    rng = np.random.default_rng(7)
    # budget holds one 128 KiB row; the second insert forces demotion
    cache = DeviceRowCache(budget_bytes=200 << 10)
    a = CountingDecoder(sparse_row(rng, 3))
    b = CountingDecoder(sparse_row(rng, 2))

    cache.get_row(("a",), a)
    cache.get_row(("b",), b)  # evicts a from dense -> compressed tier
    assert cache.compressions == 1
    assert cache.compressed_bytes < ROW_BYTES // 4  # 3/32 blocks + idx

    got = np.asarray(cache.get_row(("a",), a))  # promote, no re-decode
    assert a.calls == 1
    assert cache.decompressions == 1
    np.testing.assert_array_equal(got, a.host)
    # and b was in turn demoted; its round trip is exact too
    got_b = np.asarray(cache.get_row(("b",), b))
    assert b.calls == 1
    np.testing.assert_array_equal(got_b, b.host)


def test_dense_rows_drop_instead_of_compress():
    rng = np.random.default_rng(8)
    cache = DeviceRowCache(budget_bytes=200 << 10)
    full = CountingDecoder(
        rng.integers(1, 1 << 32, WORDS_PER_SHARD, dtype=np.uint32)
    )
    other = CountingDecoder(sparse_row(rng, 1))
    cache.get_row(("full",), full)
    cache.get_row(("other",), other)
    assert cache.compressions == 0  # >50% occupancy: dropped, not kept
    assert cache.evictions == 1
    cache.get_row(("full",), full)
    assert full.calls == 2  # re-decoded from host


def test_all_zero_row_roundtrip():
    cache = DeviceRowCache(budget_bytes=200 << 10)
    zero = CountingDecoder(np.zeros(WORDS_PER_SHARD, np.uint32))
    filler = CountingDecoder(np.ones(WORDS_PER_SHARD, np.uint32))
    cache.get_row(("z",), zero)
    cache.get_row(("f",), filler)
    assert cache.compressions == 1
    got = np.asarray(cache.get_row(("z",), zero))
    assert zero.calls == 1
    assert not got.any()


def test_invalidate_hits_both_tiers():
    rng = np.random.default_rng(9)
    cache = DeviceRowCache(budget_bytes=200 << 10)
    a = CountingDecoder(sparse_row(rng, 2))
    b = CountingDecoder(sparse_row(rng, 2))
    cache.get_row(("frag", 1, "a"), a)
    cache.get_row(("frag", 1, "b"), b)  # a now compressed
    cache.invalidate_fragment(("frag", 1))
    assert len(cache) == 0 and cache.bytes_used == 0
    cache.get_row(("frag", 1, "a"), a)
    assert a.calls == 2


def test_compressed_tier_evicts_under_total_budget():
    rng = np.random.default_rng(10)
    # tiny budget: dense holds one row; compressed tier must stay under
    # total - so repeated inserts eventually drop the oldest compressed
    cache = DeviceRowCache(budget_bytes=160 << 10)
    decoders = [CountingDecoder(sparse_row(rng, 14)) for _ in range(16)]
    for i, d in enumerate(decoders):
        cache.get_row((i,), d)
    assert cache.bytes_used <= cache.budget_bytes + ROW_BYTES  # 1 dense floor
    assert cache.evictions > 0  # compressed tier did overflow


@pytest.mark.parametrize("seed", [0, 1, 2, 3, 4])
def test_randomized_roundtrip_vs_oracle(seed):
    rng = np.random.default_rng(seed)
    cache = DeviceRowCache(budget_bytes=200 << 10)
    hosts = {}
    for i in range(6):
        nb = int(rng.integers(0, 16))
        hosts[i] = sparse_row(rng, nb)
        cache.get_row((i,), CountingDecoder(hosts[i]))
    for i in rng.permutation(6):
        got = np.asarray(cache.get_row((int(i),), CountingDecoder(hosts[int(i)])))
        np.testing.assert_array_equal(got, hosts[int(i)])


def test_stacked_leaf_shapes_compress():
    """Multi-dim uint32 arrays (stacked shard leaves, BSI planes) take the
    same path."""
    rng = np.random.default_rng(11)
    cache = DeviceRowCache(budget_bytes=500 << 10)
    stacked = np.stack([sparse_row(rng, 2) for _ in range(2)])
    planes = np.zeros((2, 3, WORDS_PER_SHARD), np.uint32)
    planes[0, 1, :COMPRESS_BLOCK_WORDS] = 5
    big = CountingDecoder(
        rng.integers(1, 1 << 32, (2, WORDS_PER_SHARD), dtype=np.uint32)
    )
    cache.get_row(("s",), CountingDecoder(stacked))
    cache.get_row(("p",), CountingDecoder(planes))
    cache.get_row(("big",), big)  # forces demotions
    assert cache.compressions >= 1
    np.testing.assert_array_equal(
        np.asarray(cache.get_row(("s",), CountingDecoder(stacked))), stacked
    )
    np.testing.assert_array_equal(
        np.asarray(cache.get_row(("p",), CountingDecoder(planes))), planes
    )


def test_working_set_within_budget_stays_dense():
    """No demotion while everything fits: full-budget dense residency
    (regression guard: the two-tier split must not shrink the hot tier)."""
    rng = np.random.default_rng(12)
    cache = DeviceRowCache(budget_bytes=600 << 10)  # 4 rows fit
    decs = [CountingDecoder(sparse_row(rng, 2)) for _ in range(4)]
    for i, d in enumerate(decs):
        cache.get_row((i,), d)
    for _ in range(3):
        for i, d in enumerate(decs):
            cache.get_row((i,), d)
    assert cache.compressions == 0 and cache.evictions == 0
    assert all(d.calls == 1 for d in decs)


def test_apply_write_patches_dense_and_spares_unrelated():
    """A write routes to exactly the tagged+affected entries: the affected
    dense entry is patched in place (no eviction, no re-decode); entries
    under other tags or probed-unaffected stay untouched."""
    from pilosa_tpu.storage.residency import WriteEvent

    rng = np.random.default_rng(13)
    cache = DeviceRowCache(budget_bytes=4 << 20)
    affected = CountingDecoder(sparse_row(rng, 2))
    unrelated = CountingDecoder(sparse_row(rng, 2))
    cache.get_row(("stack", "i", "f", 1), affected)
    cache.get_row(("stack", "i", "g", 1), unrelated)

    import jax.numpy as jnp

    probed = []

    def probe(ev):
        probed.append(ev.row)
        if ev.row != 1:
            return None
        return lambda arr: arr | jnp.uint32(1)

    cache.register_updater(("stack", "i", "f", 1), ("", "i", "f"), probe)
    cache.apply_write(WriteEvent("i", "f", "standard", 0, 1))
    assert probed == [1] and cache.updates == 1
    assert len(cache) == 2 and cache.misses == 2  # nothing evicted
    got = np.asarray(cache.get_row(("stack", "i", "f", 1), affected))
    np.testing.assert_array_equal(got, affected.host | np.uint32(1))
    assert affected.calls == 1  # patched, never re-decoded
    # unaffected row: probe returns None, entry untouched
    cache.apply_write(WriteEvent("i", "f", "standard", 0, 7))
    assert cache.updates == 1
    # other tag never probed
    cache.apply_write(WriteEvent("i", "g", "standard", 0, 1))
    assert probed == [1, 7]


def test_apply_write_invalidates_compressed_copies():
    """An affected entry demoted to the compressed tier is invalidated
    (not patched); unaffected compressed entries survive the write."""
    from pilosa_tpu.storage.residency import WriteEvent

    rng = np.random.default_rng(14)
    cache = DeviceRowCache(budget_bytes=200 << 10)  # one dense row fits
    a = CountingDecoder(sparse_row(rng, 2))
    b = CountingDecoder(sparse_row(rng, 2))
    cache.get_row(("stack", "i", "f", 1), a)

    def probe_hit(ev):
        return (lambda arr: arr) if ev.row == 1 else None

    cache.register_updater(("stack", "i", "f", 1), ("", "i", "f"), probe_hit)
    cache.get_row(("stack", "i", "f", 2), b)  # demotes a to compressed
    assert cache.compressions == 1
    cache.apply_write(WriteEvent("i", "f", "standard", 0, 1))
    assert ("stack", "i", "f", 1) not in cache._compressed  # invalidated
    assert ("stack", "i", "f", 2) in cache._rows  # dense+unaffected: kept


def test_updaters_dropped_with_entries():
    from pilosa_tpu.storage.residency import WriteEvent

    rng = np.random.default_rng(15)
    cache = DeviceRowCache(budget_bytes=4 << 20)
    cache.get_row(("k",), CountingDecoder(sparse_row(rng, 2)))
    cache.register_updater(("k",), ("", "i", "f"), lambda ev: None)
    assert ("", "i", "f") in cache._tag_index
    cache.invalidate(("k",))
    assert not cache._tag_index and not cache._updaters
    # registering for a non-resident key is a no-op
    cache.register_updater(("gone",), ("", "i", "f"), lambda ev: None)
    assert not cache._updaters
    cache.apply_write(WriteEvent("i", "f", "standard", 0, 1))  # no crash


def test_touch_refreshes_lru_position():
    """touch() keeps served-from-memo leaves from looking LRU-cold:
    under pressure the UNtouched entry must be the eviction victim."""
    rng = np.random.default_rng(11)
    cache = DeviceRowCache(budget_bytes=300 << 10)  # two rows fit
    hot = CountingDecoder(sparse_row(rng, 20))
    cold = CountingDecoder(sparse_row(rng, 20))
    cache.get_row(("hot",), hot)
    cache.get_row(("cold",), cold)  # insertion order: hot is LRU-oldest
    cache.touch([("hot",), ("missing",)])  # missing keys are ignored
    gen0 = cache.generation
    cache.get_row(("new",), CountingDecoder(sparse_row(rng, 20)))  # over budget
    assert cache.generation > gen0  # eviction bumped
    cache.get_row(("hot",), hot)
    assert hot.calls == 1  # survived: touched after cold
    cache.get_row(("cold",), cold)
    assert cold.calls == 2  # evicted: it was the LRU-coldest


def test_generation_listener_weakly_held():
    """Listener mechanics: fires on a bump, dead registrants dropped,
    remove_generation_listener unregisters."""
    calls = []

    class L:
        def cb(self):
            calls.append(1)

    c1 = DeviceRowCache(budget_bytes=1 << 20)
    listener = L()
    c1.add_generation_listener(listener.cb)
    c1.get_row(("x",), CountingDecoder(sparse_row(np.random.default_rng(1), 20)))
    c1.invalidate(("x",))
    assert calls == [1]  # bump fired the listener
    c1.remove_generation_listener(listener.cb)
    c1.get_row(("x",), CountingDecoder(sparse_row(np.random.default_rng(1), 20)))
    c1.invalidate(("x",))
    assert calls == [1]  # removed: no further calls
    keeper = L()
    c1.add_generation_listener(keeper.cb)
    listener2 = L()
    c1.add_generation_listener(listener2.cb)
    del listener2
    c1.get_row(("x",), CountingDecoder(sparse_row(np.random.default_rng(1), 20)))
    c1.invalidate(("x",))
    assert calls == [1, 1]  # weakly held: dead listener dropped, live kept


def test_executor_memo_rehomes_on_cache_swap(tmp_path):
    """Executor re-home integration (executor.py _eval_operands): after
    set_global_row_cache swaps the live cache, (a) the memo is cleared
    and rebuilt against the NEW cache, (b) the listener moves — bumps on
    the OLD cache no longer clear the live memo, (c) a swap-back does
    not stack duplicate registrations."""
    from pilosa_tpu.executor import Executor
    from pilosa_tpu.storage import Holder
    from pilosa_tpu.storage import residency as res_mod

    holder = Holder(str(tmp_path / "data")).open()
    old = res_mod.global_row_cache()
    try:
        f = holder.create_index("i").create_field("f")
        f.set_bit(1, 3)
        f.set_bit(1, 99)
        ex = Executor(holder)
        c1 = DeviceRowCache(budget_bytes=8 << 20)
        res_mod.set_global_row_cache(c1)
        assert ex.execute("i", "Count(Row(f=1))") == [2]
        assert ex.execute("i", "Count(Row(f=1))") == [2]  # memo hit path
        assert ex._listened_cache is c1 and ex._operand_memo

        c2 = DeviceRowCache(budget_bytes=8 << 20)
        res_mod.set_global_row_cache(c2)
        assert ex.execute("i", "Count(Row(f=1))") == [2]
        assert ex._listened_cache is c2 and ex._operand_memo
        # (b) old-cache bumps must NOT clear the memo tracking c2
        c1.get_row(("x",), CountingDecoder(sparse_row(np.random.default_rng(1), 20)))
        c1.invalidate(("x",))
        assert ex._operand_memo, "stale cache bump cleared the live memo"
        # ...while a bump on the LIVE cache still clears it eagerly
        c2.get_row(("x",), CountingDecoder(sparse_row(np.random.default_rng(1), 20)))
        c2.invalidate(("x",))
        assert not ex._operand_memo

        # (c) swap-back: exactly one live registration per cache
        res_mod.set_global_row_cache(c1)
        assert ex.execute("i", "Count(Row(f=1))") == [2]
        assert ex._listened_cache is c1
        alive = [r for r in c1._gen_listeners if r() is not None]
        assert len(alive) == 1
        assert not [r for r in c2._gen_listeners if r() is not None]
    finally:
        res_mod.set_global_row_cache(old)
        holder.close()


# ---------------------------------------------------------------------------
# A routed write holds the lock for bookkeeping only: the patch is
# dispatched on the array the entry held, outside the lock, and swapped
# in if the entry still holds that array (DeviceRowCache._patch_routed).
# Every interleaving below is forced with Events; nothing sleeps.

WAIT = 20  # seconds; a wait that times out fails its assert

LEAF = ("stack", "", "i", "f", ("standard",), 1, 0)
TAG = ("", "i", "f")


class GatedPatch:
    """Probe whose closure ORs ``bit`` into the leaf; its first call
    announces itself and waits to be let go, later calls (a retry) run
    straight through."""

    def __init__(self, bit, row=1):
        self.bit, self.row = np.uint32(bit), row
        self.entered = threading.Event()
        self.go = threading.Event()
        self.seen = []  # the array each call was given
        self.lock_owned = []

    def on(self, cache):
        self.cache = cache
        return self

    def __call__(self, ev):
        if ev.row != self.row:
            return None

        def apply(arr):
            self.seen.append(arr)
            self.lock_owned.append(self.cache._lock.inner._is_owned())
            if len(self.seen) == 1:
                self.entered.set()
                assert self.go.wait(WAIT)
            return arr | self.bit

        return apply


def started(fn, *args):
    out = {}

    def run():
        try:
            out["value"] = fn(*args)
        except BaseException as e:  # read by the test, never swallowed
            out["error"] = e

    t = threading.Thread(target=run, daemon=True)
    t.out = out
    t.start()
    return t


def finished(t):
    t.join(WAIT)
    assert not t.is_alive()
    assert "error" not in t.out, t.out.get("error")
    return t.out.get("value")


def resident_leaf(budget=4 << 20, seed=21, key=LEAF, blocks=2):
    cache = DeviceRowCache(budget_bytes=budget)
    dec = CountingDecoder(sparse_row(np.random.default_rng(seed), blocks))
    cache.get_row(key, dec)
    return cache, dec


def test_patch_runs_outside_the_lock():
    """(a) While the patch closure runs, the writer does not own the
    cache's lock, and a hit on another key by another thread returns."""
    cache, _ = resident_leaf()
    other = CountingDecoder(sparse_row(np.random.default_rng(22), 2))
    cache.get_row(("other",), other)
    patch = GatedPatch(1).on(cache)
    cache.register_updater(LEAF, TAG, patch)
    w = started(cache.apply_write, WriteEvent("i", "f", "standard", 0, 1))
    assert patch.entered.wait(WAIT)
    hit = started(cache.get_or_build, ("other",), None, None, other)
    got = finished(hit)  # would hang here if the patch held the lock
    np.testing.assert_array_equal(np.asarray(got), other.host)
    assert other.calls == 1 and cache.updates == 0
    patch.go.set()
    finished(w)
    assert patch.lock_owned == [False]
    assert cache.updates == 1 and cache.patch_retries == 0


def test_racing_patches_of_one_leaf_retry_and_commute():
    """(b), cache level: the second writer's dispatch is held until the
    first has swapped; its patch of the stale array is thrown away and
    made again on the array the entry holds now."""
    cache, dec = resident_leaf()
    first, second = GatedPatch(1, row=1), GatedPatch(2, row=2)
    first.on(cache).go.set()
    second.on(cache)
    cache.register_updater(
        LEAF, TAG, lambda ev: first(ev) or second(ev))
    gen0 = cache.generation
    w2 = started(cache.apply_write, WriteEvent("i", "f", "standard", 0, 2))
    assert second.entered.wait(WAIT)
    cache.apply_write(WriteEvent("i", "f", "standard", 0, 1))  # swaps
    assert cache.updates == 1
    swapped = cache._rows[LEAF].arr
    second.go.set()
    finished(w2)
    assert cache.updates == 2 and cache.patch_retries == 1
    assert cache.generation == gen0 + 2  # one bump a swap, none a retry
    assert second.seen[0] is first.seen[0]  # both saw the original
    # the retry, on the first's array
    assert len(second.seen) == 2 and second.seen[1] is swapped
    got = np.asarray(cache.get_row(LEAF, dec))
    np.testing.assert_array_equal(got, dec.host | np.uint32(3))
    assert dec.calls == 1 and second.lock_owned == [False, False]


def _invalidate(cache):
    cache.invalidate(LEAF)


def _evict_to_compressed(cache):
    # one more row than the budget holds: the leaf, LRU-oldest and
    # sparse, is demoted to the compressed tier
    cache.get_row(("filler",), CountingDecoder(
        sparse_row(np.random.default_rng(23), 2)))
    assert LEAF in cache._compressed


def _evict_and_promote(cache):
    # demoted and promoted again: a dense entry, but another object,
    # built from the array the patch never reached
    _evict_to_compressed(cache)
    assert cache._lookup_locked(LEAF) is not None
    assert LEAF in cache._rows


def _demote_to_host(cache):
    assert cache.demote_field_stacks_to_host("", "i", "f") == (1, ROW_BYTES)
    assert LEAF in cache._host


def _clear(cache):
    cache.clear()


@pytest.mark.parametrize("leave", [
    _invalidate, _evict_to_compressed, _evict_and_promote,
    _demote_to_host, _clear,
], ids=lambda f: f.__name__.strip("_"))
def test_entry_that_left_the_dense_tier_is_not_resurrected(leave):
    """(c) Between dispatch and swap the entry is invalidated, evicted,
    demoted or cleared: the patched array is dropped, no copy of the
    unpatched one survives in any tier, and the next read decodes the
    row again, written bit included."""
    cache, dec = resident_leaf(budget=200 << 10)  # one dense row fits
    patch = GatedPatch(1).on(cache)
    cache.register_updater(LEAF, TAG, patch)
    dec.host = dec.host | np.uint32(1)  # the fragment holds the write
    w = started(cache.apply_write, WriteEvent("i", "f", "standard", 0, 1))
    assert patch.entered.wait(WAIT)
    leave(cache)
    patch.go.set()
    finished(w)
    for tier in (cache._rows, cache._compressed, cache._host):
        assert LEAF not in tier
    assert LEAF not in cache._updaters
    assert cache.updates == 0 and cache.patch_retries == 0
    got = np.asarray(cache.get_row(LEAF, dec))
    assert dec.calls == 2
    np.testing.assert_array_equal(got, dec.host)


def test_build_started_between_dispatch_and_swap_gets_the_event():
    """(d) The entry is dropped and a build of the same key begins while
    the patch is in flight: the writer hands its event to that build's
    buffer (and does not wait for the build), which replays it."""
    cache, dec = resident_leaf()
    patch = GatedPatch(1).on(cache)
    cache.register_updater(LEAF, TAG, patch)
    ev = WriteEvent("i", "f", "standard", 0, 1)
    w = started(cache.apply_write, ev)
    assert patch.entered.wait(WAIT)
    cache.invalidate(LEAF)
    decoding, decoded = threading.Event(), threading.Event()
    stale = dec.host.copy()  # a decode that did not see the write

    def slow_decode():
        decoding.set()
        assert decoded.wait(WAIT)
        return stale

    replayed = []

    def build_probe(e):
        replayed.append(e)
        return lambda arr: arr | np.uint32(1)

    b = started(cache.get_or_build, LEAF, TAG, lambda: build_probe,
                slow_decode)
    assert decoding.wait(WAIT)
    patch.go.set()
    finished(w)  # the writer is done while the build still decodes
    assert cache._pending_builds[LEAF] == [ev]
    assert cache.updates == 0
    decoded.set()
    got = np.asarray(finished(b))
    assert replayed == [ev]
    np.testing.assert_array_equal(got, stale | np.uint32(1))
    np.testing.assert_array_equal(
        np.asarray(cache._rows[LEAF].arr), stale | np.uint32(1))


def test_purge_invalidates_without_dispatch():
    """(e) A probe that answers PURGE drops the entry under the lock, as
    before; nothing is dispatched."""
    cache, dec = resident_leaf()
    cache.register_updater(LEAF, TAG, lambda ev: PURGE)
    cache.apply_write(WriteEvent("i", "f", "standard", 0, 1))
    assert LEAF not in cache._rows and LEAF not in cache._updaters
    assert cache.updates == 0 and cache.write_events == 1
    cache.get_row(LEAF, dec)
    assert dec.calls == 2


def test_write_during_a_build_is_buffered_not_dispatched():
    """(e) A write routed while its key is mid-build joins the build's
    buffer at once (step 1); the probe is first asked at the replay."""
    cache = DeviceRowCache(budget_bytes=4 << 20)
    host = sparse_row(np.random.default_rng(24), 2)
    decoding, decoded = threading.Event(), threading.Event()

    def slow_decode():
        decoding.set()
        assert decoded.wait(WAIT)
        return host

    asked = []

    def probe(e):
        asked.append(e)
        return lambda arr: arr | np.uint32(4)

    b = started(cache.get_or_build, LEAF, TAG, lambda: probe, slow_decode)
    assert decoding.wait(WAIT)
    ev = WriteEvent("i", "f", "standard", 0, 1)
    cache.apply_write(ev)
    assert asked == [] and cache._pending_builds[LEAF] == [ev]
    decoded.set()
    got = np.asarray(finished(b))
    assert asked == [ev]
    np.testing.assert_array_equal(got, host | np.uint32(4))


def test_failed_patch_invalidates_the_leaf():
    """A patch that raises leaves no unpatched copy behind (the fragment
    already holds the write), and the writer sees the error."""
    cache, dec = resident_leaf()

    def probe(ev):
        def apply(arr):
            raise RuntimeError("device fell over")
        return apply

    cache.register_updater(LEAF, TAG, probe)
    with pytest.raises(RuntimeError, match="fell over"):
        cache.apply_write(WriteEvent("i", "f", "standard", 0, 1))
    assert LEAF not in cache._rows and cache.updates == 0
    assert not cache._lock.inner._is_owned()
    cache.get_row(LEAF, dec)
    assert dec.calls == 2


@pytest.mark.parametrize("planes", [False, True])
def test_row_written_is_one_entry_point(planes):
    """Fragment._after_row_write's three steps in one call: the
    fragment's own row entry goes, its plane matrices go only when the
    caller says it can have any, and the event is routed."""
    rng = np.random.default_rng(25)
    cache = DeviceRowCache(budget_bytes=4 << 20)
    frag = ("", "i", "f", "bsig_f" if planes else "standard", 0)
    rows = {k: CountingDecoder(sparse_row(rng, 2)) for k in (
        frag + (1,), frag + (2,), frag + ("__planes__", 6), LEAF)}
    for k, d in rows.items():
        cache.get_row(k, d)
    cache.register_updater(
        LEAF, TAG, lambda ev: (lambda arr: arr | np.uint32(1)))
    cache.row_written(frag, WriteEvent("i", "f", frag[3], 0, 1),
                      planes=planes)
    assert frag + (1,) not in cache._rows
    assert frag + (2,) in cache._rows
    assert (frag + ("__planes__", 6) in cache._rows) is (not planes)
    assert cache.updates == 1 and cache.write_events == 1
    np.testing.assert_array_equal(
        np.asarray(cache._rows[LEAF].arr), rows[LEAF].host | np.uint32(1))


def test_patch_retries_is_exported():
    cache, _ = resident_leaf()
    cache.patch_retries = 3
    assert cache.metrics()["residency_patch_retries"] == 3
    text = cache.prometheus_lines()
    assert "pilosa_tpu_residency_patch_retries_total 3" in text
    assert "# TYPE pilosa_tpu_residency_patch_retries_total counter" in text


# ---------------------------------------------------------------- per chip
#
# The budget is bytes on the fullest chip: an entry is charged what its
# largest shard holds (residency.chip_bytes), so a leaf that the mesh
# executor shards over four chips costs a quarter of its nbytes and a
# single-device entry what it always did.


def _mesh4():
    from pilosa_tpu.parallel import make_mesh

    return make_mesh(n_devices=4)


def _placement(kind):
    """A device_put override as DistExecutor._leaf_put makes them (None:
    the cache's own single-device put) and the divisor it earns."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from pilosa_tpu.parallel.mesh import shards_sharding

    if kind == "single":
        return None, 1
    mesh = _mesh4()
    if kind == "sharded":
        sharding, div = shards_sharding(mesh), 4
    else:  # replicated: every chip holds all of it
        sharding, div = NamedSharding(mesh, P()), 1
    return (lambda host: jax.device_put(host, sharding)), div


def _leaf(rng, slots=8):
    return rng.integers(1, 1 << 32, (slots, WORDS_PER_SHARD), dtype=np.uint32)


@pytest.mark.parametrize("kind", ["single", "sharded", "replicated"])
def test_entry_is_charged_what_it_holds_on_the_fullest_chip(kind):
    from pilosa_tpu.storage.residency import chip_bytes

    put, div = _placement(kind)
    host = _leaf(np.random.default_rng(3))
    cache = DeviceRowCache(budget_bytes=64 << 20)
    arr = cache.get_row(("leaf",), lambda: host, device_put=put)
    assert chip_bytes(arr) == host.nbytes // div
    assert cache.bytes_used == host.nbytes // div
    assert chip_bytes(host) == host.nbytes  # a host array: its nbytes
    cache.invalidate(("leaf",))
    assert cache.bytes_used == 0


def test_budget_that_holds_the_per_chip_working_set_evicts_nothing():
    """Five 1 MiB leaves over four chips are 1.25 MiB a chip: they fit a
    2 MiB budget that their 5 MiB of global nbytes, the old reckoning,
    overflowed."""
    put, _ = _placement("sharded")
    rng = np.random.default_rng(4)
    cache = DeviceRowCache(budget_bytes=2 << 20)
    leaves = {("leaf", i): _leaf(rng) for i in range(5)}
    for key, host in leaves.items():
        cache.get_row(key, lambda host=host: host, device_put=put)
    assert sum(h.nbytes for h in leaves.values()) > cache.budget_bytes
    assert cache.evictions == 0 and cache.generation == 0
    assert cache.bytes_used == 5 * (1 << 20) // 4
    for key, host in leaves.items():  # all still resident: hits only
        got = cache.get_row(key, lambda: pytest.fail("re-decoded"),
                            device_put=put)
        np.testing.assert_array_equal(np.asarray(got), host)
    assert cache.misses == 5 and cache.hits == 5


def test_single_device_entries_evict_exactly_as_before():
    """One chip: chip_bytes is nbytes, so the same five leaves against
    the same budget keep two resident and drop three."""
    rng = np.random.default_rng(4)
    cache = DeviceRowCache(budget_bytes=2 << 20)
    for i in range(5):
        host = _leaf(rng)
        cache.get_row(("leaf", i), lambda host=host: host)
    assert cache.bytes_used == 2 << 20 and len(cache) == 2
    assert cache.evictions == 3


def test_patch_sharded_reassembly_keeps_the_charge():
    """A write to a mesh leaf patches the one piece that holds the slot
    and reassembles the global handle (batch._patch_sharded); the entry
    that is swapped in costs what the one it replaces did."""
    from pilosa_tpu.executor.batch import _patch_sharded
    from pilosa_tpu.storage.residency import chip_bytes

    put, _ = _placement("sharded")
    host = _leaf(np.random.default_rng(5))
    cache = DeviceRowCache(budget_bytes=64 << 20)
    arr = cache.get_row(("leaf",), lambda: host, device_put=put)
    new_row = np.full(WORDS_PER_SHARD, 7, np.uint32)

    def probe(ev):
        return lambda a: _patch_sharded(
            a, 5, lambda piece, r: piece.at[r].set(new_row))

    cache.register_updater(("leaf",), ("", "i", "f"), probe)
    cache.apply_write(WriteEvent("i", "f", "standard", 5, 0))
    patched = cache.get_row(("leaf",), lambda: pytest.fail("re-decoded"))
    assert patched is not arr and cache.updates == 1
    assert patched.sharding == arr.sharding
    assert chip_bytes(patched) == host.nbytes // 4
    assert cache.bytes_used == host.nbytes // 4
    want = host.copy()
    want[5] = new_row
    np.testing.assert_array_equal(np.asarray(patched), want)
    cache.invalidate(("leaf",))
    assert cache.bytes_used == 0


def test_metrics_report_the_per_chip_figure_and_each_chips_own():
    put4, _ = _placement("sharded")
    rng = np.random.default_rng(6)
    sharded, single = _leaf(rng), sparse_row(rng, 2)
    cache = DeviceRowCache(budget_bytes=64 << 20)
    cache.get_row(("stackm", "", "i", "f", "standard", 0), lambda: sharded,
                  device_put=put4)
    cache.get_row(("", "i", "f", "standard", 0, 3), lambda: single)
    shard = sharded.nbytes // 4
    assert cache.metrics()["residency_bytes_used"] == shard + single.nbytes
    per_chip = cache.device_bytes()
    first = str(_mesh4().devices.ravel()[0].id)
    assert per_chip == {
        str(d.id): shard + (single.nbytes if str(d.id) == first else 0)
        for d in _mesh4().devices.ravel()}
    text = cache.prometheus_lines()
    assert f"pilosa_tpu_residency_bytes_used {shard + single.nbytes}\n" in text
    assert "# TYPE pilosa_tpu_residency_device_bytes gauge\n" in text
    for d, n in per_chip.items():
        assert f'pilosa_tpu_residency_device_bytes{{device="{d}"}} {n}\n' \
            in text
    # the heat map's overlay adds up to the same figure
    per_frag, per_field = cache.residency_overlay()
    assert per_field == {("", "i", "f"): shard}
    assert per_frag == {("", "i", "f", 0): single.nbytes}


# ----------------------------------------------------------- sparse misses
#
# A miss whose decode answers a kernels.SparseRows (ISSUE 38): the set
# bits are transferred and the chip expands them; what becomes resident,
# is charged, patched and evicted is the dense leaf, as for any miss.

SPARSE_ROWS = 4  # buckets of 8,192 and 16,384 listed bits


def sparse_leaf(seed, n_bits, n_rows=SPARSE_ROWS):
    """(decode answering a fresh SparseRows, the dense leaf it stands
    for) of ``n_bits`` random bits."""
    from pilosa_tpu.roaring import kernels
    from pilosa_tpu.roaring.bitmap import RoaringBitmap

    rng = np.random.default_rng(seed)
    pos = np.sort(rng.choice(n_rows << 20, n_bits, replace=False))
    bitmaps = [(slot, RoaringBitmap.from_ids(
        (pos[pos >> 20 == slot] & ((1 << 20) - 1)).astype(np.uint64)))
        for slot in range(n_rows)]
    want = np.empty((n_rows, WORDS_PER_SHARD), np.uint32)
    kernels.dense_rows32(kernels.flatten_rows(bitmaps, 0), want)

    def decode():
        rows = kernels.sparse_rows32(
            kernels.flatten_rows(bitmaps, 0), n_rows,
            lambda shape: np.empty(shape, np.uint32))
        assert rows is not None
        return rows

    return decode, want


def counting(decode):
    calls = []

    def counted():
        calls.append(1)
        return decode()

    return counted, calls


def test_sparse_miss_counters_say_what_was_sent_and_what_was_placed():
    from pilosa_tpu.roaring import kernels

    cache = DeviceRowCache(budget_bytes=8 << 20)
    decode, want = sparse_leaf(40, 3000)
    got = np.asarray(cache.get_or_build(LEAF, TAG, lambda: lambda ev: None,
                                        decode))
    np.testing.assert_array_equal(got, want)
    m = cache.metrics()
    packed_bytes = kernels.sparse_packed_len(SPARSE_ROWS, 8192) * 4
    assert m["residency_misses"] == m["residency_sparse_misses"] == 1
    assert m["residency_miss_transfer_bytes"] == packed_bytes
    assert m["residency_miss_bytes"] == want.nbytes == m["residency_bytes_used"]
    # a dense miss beside it: transfer and placed bytes are the same
    dense = sparse_row(np.random.default_rng(41), 2)
    cache.get_row(("r", 0), lambda: dense)
    m = cache.metrics()
    assert m["residency_misses"] == 2 and m["residency_sparse_misses"] == 1
    assert m["residency_miss_transfer_bytes"] == packed_bytes + dense.nbytes
    assert m["residency_miss_bytes"] == want.nbytes + dense.nbytes
    text = cache.prometheus_lines()
    assert "pilosa_tpu_residency_sparse_misses_total 1\n" in text
    assert ("pilosa_tpu_residency_miss_transfer_bytes_total "
            f"{packed_bytes + dense.nbytes}\n") in text
    assert DeviceRowCache().metrics()["residency_sparse_misses"] == 0


def test_sparse_leafs_block_index_is_its_tiles_under_the_occupancy_rule():
    """The compressed tier's block index comes from the listed bits, by
    the rule a dense miss's scan applies: 40 bits touch at most 40 of the
    128 blocks (demoted, and promoted back bit for bit), 3,000 bits touch
    nearly all of them (dropped)."""
    cache = DeviceRowCache(budget_bytes=8 << 20)
    thin, want = sparse_leaf(42, 40)
    thick, _ = sparse_leaf(43, 3000)
    cache.get_or_build(("thin",), None, None, thin)
    cache.get_or_build(("thick",), None, None, thick)
    np.testing.assert_array_equal(
        cache._rows[("thin",)].block_idx,
        np.flatnonzero(want.reshape(-1, COMPRESS_BLOCK_WORDS).any(axis=1)))
    assert cache._rows[("thick",)].block_idx is None
    # room for the thick leaf and the thin one's 64 padded blocks
    cache.budget_bytes = want.nbytes * 3 // 2 + (64 << 10)
    cache._evict()
    assert ("thin",) in cache._compressed and cache.compressions == 1
    cache.budget_bytes = 8 << 20
    np.testing.assert_array_equal(
        np.asarray(cache.get_row(("thin",), None)), want)


def test_write_buffered_during_a_sparse_decode_lands_on_the_expanded_leaf():
    cache = DeviceRowCache(budget_bytes=8 << 20)
    decode, want = sparse_leaf(44, 2000)
    decoding, decoded = threading.Event(), threading.Event()

    def slow_decode():
        decoding.set()
        assert decoded.wait(WAIT)
        return decode()

    asked = []

    def probe(e):
        asked.append(e)
        return lambda arr: arr | np.uint32(4)

    b = started(cache.get_or_build, LEAF, TAG, lambda: probe, slow_decode)
    assert decoding.wait(WAIT)
    ev = WriteEvent("i", "f", "standard", 0, 1)
    cache.apply_write(ev)
    assert cache._pending_builds[LEAF] == [ev]
    decoded.set()
    got = np.asarray(finished(b))
    assert asked == [ev] and cache.sparse_misses == 1
    np.testing.assert_array_equal(got, want | np.uint32(4))
    np.testing.assert_array_equal(np.asarray(cache._rows[LEAF].arr), got)
    assert cache._rows[LEAF].block_idx is None  # patched: never demoted


def test_purge_redecode_and_dead_field_take_the_sparse_form():
    cache = DeviceRowCache(budget_bytes=8 << 20)
    decode, want = sparse_leaf(45, 2000)
    decode, calls = counting(decode)
    decoding, decoded = threading.Event(), threading.Event()

    def slow_decode():
        if not decoding.is_set():
            decoding.set()
            assert decoded.wait(WAIT)
        return decode()

    # a buffered event the probe cannot patch: decoded again under the
    # lock, expanded again, charged once
    b = started(cache.get_or_build, LEAF, TAG, lambda: lambda ev: PURGE,
                slow_decode)
    assert decoding.wait(WAIT)
    cache.apply_write(WriteEvent("i", "f", "standard", 0, 1))
    decoded.set()
    np.testing.assert_array_equal(np.asarray(finished(b)), want)
    assert len(calls) == 2 and cache.sparse_misses == 2
    assert cache.bytes_used == want.nbytes
    np.testing.assert_array_equal(np.asarray(cache._rows[LEAF].arr), want)
    # the field is deleted while its leaf decodes: served, not cached
    decoding.clear(), decoded.clear()
    other = ("stack", "", "i", "f", ("standard",), 2, 0)
    b = started(cache.get_or_build, other, TAG, lambda: lambda ev: None,
                slow_decode)
    assert decoding.wait(WAIT)
    cache.invalidate_tag(TAG)
    decoded.set()
    np.testing.assert_array_equal(np.asarray(finished(b)), want)
    assert other not in cache._rows and cache.bytes_used == 0
    assert cache.sparse_misses == 3


def test_every_buckets_expansion_is_compiled_with_the_first():
    """The first sparse leaf of a row count compiles the expansion of
    every bucket; leaves of two other buckets after it compile nothing."""
    from pilosa_tpu.roaring import kernels
    from pilosa_tpu.utils import tracing

    from pilosa_tpu.storage import residency

    n_rows = 16
    assert kernels.sparse_buckets(n_rows) == (8192, 16384, 32768, 65536)
    # as in a process that has expanded nothing yet
    residency._expansions_ready.clear()
    residency._expand_rows.clear_cache()
    tracing.install_compile_listener()
    cache = DeviceRowCache(budget_bytes=64 << 20)

    def compiles():  # programs made executable: compiled, or loaded
        m = tracing.device_metrics()
        return m["compiles_total"] + m["compile_cache_loads_total"]

    c0 = compiles()
    first, want = sparse_leaf(46, 5000, n_rows)
    np.testing.assert_array_equal(
        np.asarray(cache.get_or_build(("a",), None, None, first)), want)
    assert compiles() - c0 >= 4
    c1 = compiles()
    for key, n_bits, n_pad in ((("b",), 20_000, 32768),
                               (("c",), 65_536, 65536)):
        decode, want = sparse_leaf(47, n_bits, n_rows)
        rows = decode()
        assert rows.n_pad == n_pad
        np.testing.assert_array_equal(
            np.asarray(cache.get_or_build(key, None, None, lambda: rows)),
            want)
    assert compiles() == c1 and cache.sparse_misses == 3


def test_custom_device_put_never_receives_the_sparse_form(monkeypatch):
    """A leaf placed by the caller (the mesh's sharded put) is decoded
    dense: ``stacked_leaf`` asks for the sparse form only when the cache
    places the leaf itself."""
    import jax

    from pilosa_tpu.executor import batch
    from pilosa_tpu.executor.executor import _RowSpec
    from pilosa_tpu.storage import residency

    asked = []
    real = batch.host_leaf

    def host_leaf(idx, spec, block, sparse=False):
        asked.append(sparse)
        return real(idx, spec, block, sparse=sparse)

    monkeypatch.setattr(batch, "host_leaf", host_leaf)

    class NoField:
        scope, name = "", "i"

        def field(self, name):
            return None

    received = []

    def put(host):
        received.append(host)
        return jax.device_put(host)

    cache = residency.global_row_cache()
    # the process's cache: a test file this worker ran before may have
    # missed sparsely, so the counter is read as a difference
    before = cache.sparse_misses
    block = batch.ShardBlock([0, 1, 2])
    spec = _RowSpec("f", ("standard",), 1)
    batch.stacked_leaf(NoField(), spec, block, device_put=put)
    assert asked == [False] and isinstance(received[0], np.ndarray)
    assert received[0].shape == (4, WORDS_PER_SHARD)
    assert cache.sparse_misses == before
    cache.clear()
    got = batch.stacked_leaf(NoField(), spec, block)  # the cache's own put
    assert asked == [False, True] and cache.sparse_misses == before + 1
    assert not np.asarray(got).any()


def test_set_between_a_directory_decode_and_its_placement_is_replayed(
        tmp_path, monkeypatch):
    """ISSUE 40: the miss of a row leaf reads the fragments' container
    directories. A ``Set`` that lands after that decode and before the
    leaf is placed drops its fragment's directory, is buffered by the
    build and replayed on the placed leaf exactly as before: the leaf,
    and every later answer from it, holds the bit."""
    from pilosa_tpu.executor import Executor, batch
    from pilosa_tpu.roaring import kernels
    from pilosa_tpu.shardwidth import SHARD_WIDTH
    from pilosa_tpu.storage import Holder
    from pilosa_tpu.storage import residency

    holder = Holder(str(tmp_path / "data")).open()
    try:
        f = holder.create_index("i", track_existence=False).create_field("f")
        view = f.view("standard", create=True)
        for shard in range(4):
            frag = view.fragment(shard, create=True)
            frag.bulk_import([1] * 50, [i * 17 for i in range(50)])
            frag.snapshot()  # as an open of the file would leave it
            assert frag.bitmap.directory is not None
        new_col = 2 * SHARD_WIDTH + 3  # not in the stride pattern
        stats = kernels.global_kernel_stats()
        real, seen = batch.host_leaf, []

        def host_leaf_then_a_set(idx, spec, block, **kw):
            before = stats.directory_windows, stats.walked_windows
            out = real(idx, spec, block, **kw)
            if not seen and spec.field == "f":
                seen.append((stats.directory_windows - before[0],
                             stats.walked_windows - before[1], type(out)))
                f.set_bit(1, new_col)  # the decode above did not see it
            return out

        monkeypatch.setattr(batch, "host_leaf", host_leaf_then_a_set)
        ex = Executor(holder)
        cache = residency.global_row_cache()
        misses = cache.misses
        (row,) = ex.execute("i", "Row(f=1)")
        assert seen == [(4, 0, kernels.SparseRows)]
        assert new_col in row.columns().tolist()
        assert [view.fragment(s).bitmap.directory is None
                for s in range(4)] == [False, False, True, False]
        (again,) = ex.execute("i", "Row(f=1)")  # the resident leaf
        assert again.columns().tolist() == row.columns().tolist()
        assert len(row.columns()) == 4 * 50 + 1
        assert cache.misses == misses + 1
    finally:
        holder.close()
