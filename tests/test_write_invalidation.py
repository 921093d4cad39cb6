"""Write-path invalidation: one Set() patches exactly the affected shard
slot of resident stacked leaves on device instead of purging every leaf
(SURVEY.md §7.3 hard part #3; replaces the round-1 global generation
purge, which made any mixed workload re-upload its working set)."""

import sys
import threading

import numpy as np
import pytest

from pilosa_tpu.executor import Executor, batch
from pilosa_tpu.shardwidth import SHARD_WIDTH
from pilosa_tpu.storage import FieldOptions, Holder
from pilosa_tpu.storage import residency


@pytest.fixture
def env(tmp_path):
    holder = Holder(str(tmp_path / "data")).open()
    yield holder, Executor(holder)
    holder.close()


def fill(field, rows, per_row=50, shards=4, stride=17):
    for r in rows:
        for s in range(shards):
            positions = [(i * stride) % SHARD_WIDTH for i in range(per_row)]
            frag = field.view("standard", create=True).fragment(s, create=True)
            frag.bulk_import([r] * len(positions), positions)


def cache():
    return residency.global_row_cache()


class TestSetDoesNotEvictUnrelatedLeaves:
    def test_single_set_patches_in_place(self, env):
        holder, ex = env
        idx = holder.create_index("i", track_existence=False)
        f = idx.create_field("f")
        g = idx.create_field("g")
        fill(f, rows=[1, 2])
        fill(g, rows=[1])

        q = "Count(Intersect(Row(f=1), Row(f=2))) Count(Row(g=1))"
        base = ex.execute("i", q)
        resident_before = len(cache())
        misses_before = cache().misses

        # one Set into f row 1 shard 2
        pos = 3  # not in the stride pattern
        (changed,) = ex.execute("i", f"Set({2 * SHARD_WIDTH + pos}, f=1)")
        assert changed is True

        out = ex.execute("i", q)
        assert out[0] == base[0] + 0  # intersect unchanged (row 2 lacks pos)
        assert out[1] == base[1]
        # leaves were patched, not purged: same residency, zero new decodes
        assert cache().misses == misses_before
        assert len(cache()) == resident_before
        assert cache().updates >= 1

        # and the patched leaf is CORRECT: row 1 now includes the new bit
        (row1,) = ex.execute("i", "Row(f=1)")
        assert 2 * SHARD_WIDTH + pos in set(row1.columns().tolist())
        assert cache().misses == misses_before  # still no re-decode

    def test_clear_bit_patches_single_view_leaf(self, env):
        holder, ex = env
        idx = holder.create_index("i", track_existence=False)
        f = idx.create_field("f")
        fill(f, rows=[1])
        (base,) = ex.execute("i", "Count(Row(f=1))")
        misses = cache().misses
        ex.execute("i", "Clear(0, f=1)")  # position 0 is in the pattern
        (after,) = ex.execute("i", "Count(Row(f=1))")
        assert after == base - 1
        assert cache().misses == misses  # delta-patched, not re-decoded

    def test_bulk_import_patches(self, env):
        holder, ex = env
        idx = holder.create_index("i", track_existence=False)
        f = idx.create_field("f")
        fill(f, rows=[1], shards=2)
        (base,) = ex.execute("i", "Count(Row(f=1))")
        misses = cache().misses
        frag = f.view("standard").fragment(0)
        new_positions = [5, 7, 11]  # stride pattern avoids small odd primes
        before = {int(c) for c in frag.row_columns(1).tolist()}
        frag.bulk_import([1] * 3, new_positions)
        added = len(set(new_positions) - before)
        (after,) = ex.execute("i", "Count(Row(f=1))")
        assert after == base + added
        assert cache().misses == misses

    def test_bsi_write_patches_plane_leaf(self, env):
        holder, ex = env
        idx = holder.create_index("i", track_existence=False)
        f = idx.create_field("amount", FieldOptions(type="int", min=0, max=1000))
        for col, val in ((0, 10), (1, 20), (SHARD_WIDTH + 2, 30)):
            f.set_value(col, val)
        (s,) = ex.execute("i", "Sum(field=amount)")
        assert s.value == 60
        misses = cache().misses
        f.set_value(2, 40)
        (s2,) = ex.execute("i", "Sum(field=amount)")
        assert s2.value == 100
        assert cache().misses == misses  # plane leaf patched in place

    def test_write_only_invalidates_affected_compressed_leaf(self, env):
        """Presence check at the storage level: a write to field f never
        touches resident leaves of field g (different tag)."""
        holder, ex = env
        idx = holder.create_index("i", track_existence=False)
        f = idx.create_field("f")
        g = idx.create_field("g")
        fill(f, rows=[1], shards=1)
        fill(g, rows=[1], shards=1)
        ex.execute("i", "Count(Row(f=1)) Count(Row(g=1))")
        g_keys = [k for k in cache()._rows if len(k) > 3 and k[3] == "g"]
        assert g_keys
        g_arrs = [cache()._rows[k].arr for k in g_keys]
        ex.execute("i", "Set(9, f=1)")
        for k, arr in zip(g_keys, g_arrs):
            assert cache()._rows[k].arr is arr  # same device buffer


class TestDeleteRecreateSafety:
    def test_field_recreate_does_not_serve_stale_leaves(self, env):
        """Generation-free keys must not leak data across a field
        delete+recreate under the same name."""
        holder, ex = env
        idx = holder.create_index("i", track_existence=False)
        f = idx.create_field("f")
        f.set_bit(1, 10)
        (c1,) = ex.execute("i", "Count(Row(f=1))")
        assert c1 == 1
        idx.delete_field("f")
        f2 = idx.create_field("f")
        f2.set_bit(1, 20)
        (c2,) = ex.execute("i", "Count(Row(f=1))")
        assert c2 == 1
        (row,) = ex.execute("i", "Row(f=1)")
        assert row.columns().tolist() == [20]


class TestConcurrentWritePatching:
    def test_parallel_writers_do_not_lose_patches(self, env):
        """Two writers on different fragments of one field hold different
        fragment locks; the residency lock must serialize their
        read-modify-write of the shared stacked leaf (a lost patch here
        serves a missing bit forever)."""
        import threading

        holder, ex = env
        idx = holder.create_index("i", track_existence=False)
        f = idx.create_field("f")
        for s in range(2):
            f.view("standard", create=True).fragment(s, create=True)
        f.set_bit(1, 0)
        ex.execute("i", "Count(Row(f=1))")  # leaf resident

        N = 200
        barrier = threading.Barrier(2)

        def writer(shard):
            barrier.wait()
            frag = f.view("standard").fragment(shard)
            for i in range(1, N + 1):
                frag.set_bit(1, i)

        threads = [threading.Thread(target=writer, args=(s,)) for s in (0, 1)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        (count,) = ex.execute("i", "Count(Row(f=1))")
        assert count == 2 * N + 1
        (row,) = ex.execute("i", "Row(f=1)")
        want = {0} | set(range(1, N + 1)) | {SHARD_WIDTH + i for i in range(1, N + 1)}
        assert set(row.columns().tolist()) == want


class TestBufferedBuild:
    def test_write_landing_mid_decode_is_replayed(self, env):
        """A write that lands while a stacked leaf is being decoded (after
        the builder claimed the key, before the upload) must appear in the
        resulting leaf: get_or_build buffers the event and replays it as a
        patch after the upload."""
        holder, ex = env
        idx = holder.create_index("i", track_existence=False)
        f = idx.create_field("f")
        fill(f, rows=[1])

        from pilosa_tpu.executor import batch

        new_col = 2 * SHARD_WIDTH + 3  # not in the stride pattern
        fired = {"done": False}
        real_host_leaf = batch.host_leaf

        def host_leaf_with_midwrite(idx_, spec, block, **kw):
            out = real_host_leaf(idx_, spec, block, **kw)
            if not fired["done"] and spec.field == "f":
                fired["done"] = True
                # the builder has already claimed the key and registered
                # the probe; this write (which the decode above did not
                # see) must be buffered and replayed
                f.set_bit(1, new_col)
            return out

        batch.host_leaf = host_leaf_with_midwrite
        try:
            (row1,) = ex.execute("i", "Row(f=1)")
        finally:
            batch.host_leaf = real_host_leaf
        assert fired["done"]
        assert new_col in set(row1.columns().tolist())
        # the resident leaf (not just this query's result) has the bit
        (n,) = ex.execute("i", f"Count(Intersect(Row(f=1), Row(f=1)))")
        (row1b,) = ex.execute("i", "Row(f=1)")
        assert new_col in set(row1b.columns().tolist())

    def test_concurrent_builders_of_one_key_decode_once(self, env):
        """Two threads missing on the same key: the second waits for the
        first build instead of decoding the leaf twice."""
        import threading

        holder, ex = env
        idx = holder.create_index("i", track_existence=False)
        f = idx.create_field("f")
        fill(f, rows=[1])

        from pilosa_tpu.executor import batch

        decodes = []
        entered = threading.Event()
        release = threading.Event()
        real_host_leaf = batch.host_leaf

        def slow_host_leaf(idx_, spec, block, **kw):
            if spec.field == "f":
                decodes.append(1)
                if len(decodes) == 1:
                    entered.set()
                    assert release.wait(20)
            return real_host_leaf(idx_, spec, block, **kw)

        batch.host_leaf = slow_host_leaf
        results = []
        try:
            t1 = threading.Thread(
                target=lambda: results.append(ex.execute("i", "Row(f=1)"))
            )
            t1.start()
            assert entered.wait(20)
            t2 = threading.Thread(
                target=lambda: results.append(ex.execute("i", "Row(f=1)"))
            )
            t2.start()
            import time
            time.sleep(0.2)  # t2 reaches the wait on the pending build
            release.set()
            t1.join(20)
            t2.join(20)
        finally:
            batch.host_leaf = real_host_leaf
        assert len(results) == 2
        a, b = (set(r[0].columns().tolist()) for r in results)
        assert a == b
        # one build: the leaf was decoded once, not again by the second
        # thread (it waited and reused the entry)
        assert decodes == [1]


# ---------------------------------------------------------------------------
# The device patch of a write is dispatched outside the row cache's lock
# and swapped in if the leaf is unchanged (residency._patch_routed). The
# cache-level interleavings are in tests/test_residency.py; these drive
# real fragments, probes and reads.

WAIT = 20


def join_all(threads):
    for t in threads:
        t.join(WAIT)
        assert not t.is_alive()


def leaf_of(field_name, row):
    keys = [k for k in cache()._rows
            if k[0] == "stack" and k[3] == field_name and k[5] == row]
    assert len(keys) == 1
    return keys[0]


def reference_leaf(field, row, shards):
    view = field.view("standard")
    return np.stack([view.fragment(s).row_words(row) for s in shards])


class GatedOrDelta:
    """Stands in for batch._or_delta: the call that patches shard slot
    ``slot`` announces itself and waits, the first time it is made."""

    def __init__(self, slot):
        self.real = batch._or_delta
        self.slot = slot
        self.entered = threading.Event()
        self.go = threading.Event()
        self.calls = []

    def __call__(self, arr, args):
        slot = int(args[0])
        self.calls.append(slot)
        if slot == self.slot and not self.entered.is_set():
            self.entered.set()
            assert self.go.wait(WAIT)
        return self.real(arr, args)


class TestPatchOutsideTheLock:
    def test_two_writers_two_shards_one_leaf(self, env, monkeypatch):
        """(b) Writers to shards 0 and 1 of one row patch the same leaf.
        The second is held inside its dispatch until the first has
        swapped: its patch is made again on the first's array, both bits
        are in the leaf, which equals the numpy reference, and the word
        masks were built once for each write."""
        holder, ex = env
        idx = holder.create_index("i", track_existence=False)
        f = idx.create_field("f")
        fill(f, rows=[1], shards=2)
        ex.execute("i", "Count(Row(f=1))")  # leaf resident
        key = leaf_of("f", 1)
        gate = GatedOrDelta(slot=1)
        monkeypatch.setattr(batch, "_or_delta", gate)
        masks = []
        real_args = batch._delta_args
        monkeypatch.setattr(
            batch, "_delta_args",
            lambda at, p: masks.append(list(p)) or real_args(at, p))
        frags = [f.view("standard").fragment(s) for s in (0, 1)]
        retries0 = cache().patch_retries
        w1 = threading.Thread(target=frags[1].set_bit, args=(1, 3))
        w1.start()
        assert gate.entered.wait(WAIT)
        assert frags[0].set_bit(1, 5)  # swaps while w1 is in flight
        gate.go.set()
        join_all([w1])
        assert gate.calls == [1, 0, 1]
        assert masks == [[3], [5]]
        assert cache().patch_retries == retries0 + 1
        np.testing.assert_array_equal(
            np.asarray(cache()._rows[key].arr)[:2],
            reference_leaf(f, 1, (0, 1)))
        misses = cache().misses
        (row,) = ex.execute("i", "Row(f=1)")
        cols = set(row.columns().tolist())
        assert {5, SHARD_WIDTH + 3} <= cols
        assert cache().misses == misses  # served from the patched leaf

    def test_leaf_dropped_mid_patch_is_decoded_again(self, env, monkeypatch):
        """(c) The leaf is invalidated while a Set's patch is in flight:
        the patch is dropped with it and the next read decodes the
        fragment, written bit included."""
        holder, ex = env
        idx = holder.create_index("i", track_existence=False)
        f = idx.create_field("f")
        fill(f, rows=[1], shards=2)
        (base,) = ex.execute("i", "Count(Row(f=1))")
        key = leaf_of("f", 1)
        gate = GatedOrDelta(slot=0)
        monkeypatch.setattr(batch, "_or_delta", gate)
        frag = f.view("standard").fragment(0)
        w = threading.Thread(target=frag.set_bit, args=(1, 3))
        w.start()
        assert gate.entered.wait(WAIT)
        cache().invalidate(key)
        gate.go.set()
        join_all([w])
        assert key not in cache()._rows and key not in cache()._compressed
        misses = cache().misses
        (after,) = ex.execute("i", "Count(Row(f=1))")
        assert after == base + 1
        assert cache().misses == misses + 1

    def test_lock_is_free_while_a_set_patches(self, env, monkeypatch):
        """(a) A reader's leaf lookup of another field returns while a
        Set's device patch is still in flight."""
        holder, ex = env
        idx = holder.create_index("i", track_existence=False)
        f = idx.create_field("f")
        g = idx.create_field("g")
        fill(f, rows=[1], shards=1)
        fill(g, rows=[1], shards=1)
        (base_f, base_g) = ex.execute("i", "Count(Row(f=1)) Count(Row(g=1))")
        gate = GatedOrDelta(slot=0)
        monkeypatch.setattr(batch, "_or_delta", gate)
        frag = f.view("standard").fragment(0)
        w = threading.Thread(target=frag.set_bit, args=(1, 3))
        w.start()
        assert gate.entered.wait(WAIT)
        out = []
        r = threading.Thread(
            target=lambda: out.append(ex.execute("i", "Count(Row(g=1))")))
        r.start()
        join_all([r])  # would hang if the patch held the lock
        assert out == [[base_g]]
        gate.go.set()
        join_all([w])
        assert ex.execute("i", "Count(Row(f=1))") == [base_f + 1]


class TestWritersBesideReaders:
    N_WRITERS = 6
    N_READERS = 3
    OPS = 40

    def test_reads_lie_between_must_and_may(self, env):
        """(f) Writer threads Set and Clear bits of two fields, two rows
        and three shards while readers fetch the rows. A read returns
        every bit whose Set was acknowledged before it began (unless its
        Clear had begun by the time it ended) and nothing beyond the
        bits whose Set had begun by then (less those whose Clear was
        acknowledged before it began). The final state is exact, in the
        answers and in the resident leaves."""
        holder, ex = env
        idx = holder.create_index("i", track_existence=False)
        fields = {n: idx.create_field(n) for n in ("f", "g")}
        shards, rows = (0, 1, 2), (1, 2)
        base = {}
        for name, fld in fields.items():
            fill(fld, rows=rows, shards=len(shards), per_row=20)
            for r in rows:
                (row,) = ex.execute("i", f"Row({name}={r})")  # resident
                base[name, r] = set(row.columns().tolist())
        book = threading.Lock()
        log = {k: {s: set() for s in
                   ("set_begun", "set_acked", "clear_begun", "clear_acked")}
               for k in base}

        def mark(key, what, col):
            with book:
                log[key][what].add(col)

        def snapshot(key, *whats):
            with book:
                return [set(log[key][w]) for w in whats]

        def writer(n):
            rng = np.random.default_rng(100 + n)
            mine = []  # (key, col) this writer has set and not cleared
            for i in range(self.OPS):
                if mine and rng.random() < 0.3:
                    key, col = mine.pop(int(rng.integers(len(mine))))
                    mark(key, "clear_begun", col)
                    assert fields[key[0]].clear_bit(key[1], col)
                    mark(key, "clear_acked", col)
                    continue
                key = (("f", "g")[int(rng.integers(2))],
                       rows[int(rng.integers(2))])
                # a column no other writer and no earlier op touches,
                # past fill()'s pattern (20 positions under 17 * 20)
                pos = 1000 + n * self.OPS + i
                col = shards[int(rng.integers(3))] * SHARD_WIDTH + pos
                assert col not in base[key]
                mark(key, "set_begun", col)
                assert fields[key[0]].set_bit(key[1], col)
                mark(key, "set_acked", col)
                mine.append((key, col))

        done = threading.Event()
        bad = []
        errors = []

        def guarded(fn, n):
            try:
                fn(n)
            except BaseException as e:
                errors.append(e)

        def reader(n):
            rng = np.random.default_rng(200 + n)
            keys = sorted(base)
            reads = 0
            while not done.is_set() or reads < 5:
                key = keys[int(rng.integers(len(keys)))]
                acked, cleared = snapshot(key, "set_acked", "clear_acked")
                (row,) = ex.execute("i", f"Row({key[0]}={key[1]})")
                begun, clearing = snapshot(key, "set_begun", "clear_begun")
                got = set(row.columns().tolist()) - base[key]
                must, may = acked - clearing, begun - cleared
                if not must <= got <= may:
                    bad.append((key, sorted(must - got), sorted(got - may)))
                reads += 1

        old = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            readers = [threading.Thread(target=guarded, args=(reader, n))
                       for n in range(self.N_READERS)]
            writers = [threading.Thread(target=guarded, args=(writer, n))
                       for n in range(self.N_WRITERS)]
            for t in readers + writers:
                t.start()
            for t in writers:
                t.join(120)
            done.set()
            for t in readers:
                t.join(120)
        finally:
            sys.setswitchinterval(old)
        assert not any(t.is_alive() for t in readers + writers)
        assert errors == [] and bad == []
        assert cache().updates > 0
        for (name, r), before in base.items():
            (acked, cleared) = snapshot((name, r), "set_acked", "clear_acked")
            want = before | (acked - cleared)
            (row,) = ex.execute("i", f"Row({name}={r})")
            assert set(row.columns().tolist()) == want
            (n,) = ex.execute("i", f"Count(Row({name}={r}))")
            assert n == len(want)
            leaf = np.asarray(cache()._rows[leaf_of(name, r)].arr)
            np.testing.assert_array_equal(
                leaf[: len(shards)], reference_leaf(fields[name], r, shards))


def _reference_delta(words, positions, added):
    out = words.copy()
    for p in positions:
        bit = np.uint32(1) << np.uint32(p & 31)
        out[p >> 5] = out[p >> 5] | bit if added else out[p >> 5] & ~bit
    return out


@pytest.mark.parametrize("positions", [
    [5],
    [0, 1, 31, 32, 33, 64 * 32 - 1],  # shared words, word 0, three to pad
    [7, 7, 40, 40, 41],  # repeats
], ids=["one", "six", "repeats"])
@pytest.mark.parametrize("program", [
    "_or_delta", "_andnot_delta", "_or_delta_row", "_andnot_delta_row"])
def test_delta_programs_match_the_loop(program, positions):
    """The four patch programs, fed _delta_args' one packed array, do
    to one row of a leaf what the bit-by-bit loop does, and leave every
    other row alone."""
    rng = np.random.default_rng(31)
    matrix = program.endswith("_row")
    shape = (3, 4, 64) if matrix else (3, 64)
    host = rng.integers(0, 1 << 32, shape, dtype=np.uint32)
    at = (2, 1) if matrix else (2,)
    args = batch._delta_args(at, positions)
    assert args.dtype == np.uint32 and args.ndim == 1
    got = np.asarray(getattr(batch, program)(host, args))
    want = host.copy()
    want[at] = _reference_delta(host[at], positions,
                                added=program.startswith("_or"))
    np.testing.assert_array_equal(got, want)
