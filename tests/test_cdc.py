"""CDC backbone (pilosa_tpu/cdc/): WAL tail change feed, frame wire
fuzz (test_shmring.py discipline), the HTTP tail route, cluster-safe
result caching via peer tailers, stale-bounded read replicas, and
point-in-time ``restore --as-of``."""

import json
import time
import urllib.error
import urllib.request

import pytest

from pilosa_tpu.cdc.feed import (
    DURABLE_SEQ_HEADER,
    NEXT_SEQ_HEADER,
    encode_events,
    iter_frames,
)
from pilosa_tpu.storage import wal as wal_mod
from pilosa_tpu.storage.backup import backup_holder, restore_holder
from pilosa_tpu.storage.field import VIEW_STANDARD
from pilosa_tpu.storage.holder import Holder
from pilosa_tpu.storage.wal import REC_OP, REC_TOMBSTONE, TailGone

from cluster_helpers import make_cluster, req, uri


def _mk_holder(tmp_path, name="h", **kw):
    return Holder(str(tmp_path / name), **kw).open()


def _frag(holder, index="i", field="f", shard=0):
    idx = holder.index(index) or holder.create_index(index)
    fld = idx.field(field) or idx.create_field(field)
    return fld.view(VIEW_STANDARD, create=True).fragment(shard, create=True)


def _set_bits(holder, frag, cols, group=40):
    """Set row 1's ``cols`` a commit group of at most ``group`` records
    at a time. A segment rotates when a group leaves it over
    SEGMENT_MAX_BYTES, so how often it rotates follows from how the
    commit thread happens to batch: on a loaded host one group took 256
    of 300 records and the log rotated once. A barrier a batch makes
    the rotations a matter of bytes."""
    for k, col in enumerate(cols, 1):
        frag.set_bit(1, col)
        if k % group == 0:
            holder.wal.barrier()
    holder.wal.barrier()


def _wait(pred, timeout=10.0, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return
        time.sleep(0.02)
    raise AssertionError(f"timed out waiting for {msg}")


# --------------------------------------------------------- WAL tail feed


class TestWalTail:
    def test_events_in_commit_order(self, tmp_path):
        h = _mk_holder(tmp_path)
        try:
            frag = _frag(h)
            for i in range(20):
                frag.set_bit(1, i)
            h.wal.barrier()
            events, next_seq, durable = h.wal.read_tail(0)
            assert [e[0] for e in events] == list(range(1, 21))
            assert all(e[1] == REC_OP for e in events)
            assert all(e[2] == "i/f/standard/0" for e in events)
            assert next_seq == durable == 20
            # resume mid-stream: strictly after `since`
            events, next_seq, _ = h.wal.read_tail(15)
            assert [e[0] for e in events] == [16, 17, 18, 19, 20]
        finally:
            h.close()

    def test_attached_consumer_drains_to_empty(self, tmp_path):
        h = _mk_holder(tmp_path)
        try:
            frag = _frag(h)
            frag.set_bit(1, 1)
            h.wal.barrier()
            durable = h.wal.durable_seq()
            events, next_seq, d2 = h.wal.read_tail(durable)
            assert events == [] and next_seq == d2 == durable
        finally:
            h.close()

    def test_seq_past_durable_is_gone(self, tmp_path):
        """A consumer holding a cursor from a PREVIOUS process
        incarnation (seq space reset) must be told to restart, not fed
        a silently different history."""
        h = _mk_holder(tmp_path)
        try:
            with pytest.raises(TailGone):
                h.wal.read_tail(10_000)
        finally:
            h.close()

    def test_tombstones_ride_the_feed(self, tmp_path):
        h = _mk_holder(tmp_path)
        try:
            frag = _frag(h)
            frag.set_bit(1, 1)
            h.create_index("j")
            h.delete_index("j")
            h.wal.barrier()
            events, _, _ = h.wal.read_tail(0)
            tombs = [(e[2]) for e in events if e[1] == REC_TOMBSTONE]
            assert tombs == ["j/"]
        finally:
            h.close()

    def test_cursor_survives_segment_rotation(self, tmp_path,
                                              monkeypatch):
        """The cursor contract across rotation: a registered consumer
        can fall several SEGMENTS behind and still read every event in
        order — rotation must never drop feed history it pins."""
        monkeypatch.setattr(wal_mod, "SEGMENT_MAX_BYTES", 2048)
        h = _mk_holder(tmp_path)
        try:
            h.wal.register_cursor("lagger", 0)
            frag = _frag(h)
            _set_bits(h, frag, range(300))
            assert len(h.wal._segments) > 2, "rotation never happened"
            got = []
            pos = 0
            while True:
                events, next_seq, durable = h.wal.read_tail(
                    pos, max_bytes=4096)
                got.extend(e[0] for e in events)
                if next_seq <= pos:
                    break
                pos = next_seq
                h.wal.register_cursor("lagger", pos)
                if pos >= durable:
                    break
            assert got == list(range(1, 301))
        finally:
            h.close()

    def test_gc_reclaims_oldest_first_past_dropped_cursor(
            self, tmp_path, monkeypatch):
        monkeypatch.setattr(wal_mod, "SEGMENT_MAX_BYTES", 2048)
        h = _mk_holder(tmp_path)
        try:
            h.wal.register_cursor("c", 0)
            frag = _frag(h)
            _set_bits(h, frag, range(150))
            # pinned: the full feed is still readable
            events, _, _ = h.wal.read_tail(0, max_bytes=1 << 20)
            assert events and events[0][0] == 1
            h.wal.drop_cursor("c")
            _set_bits(h, frag, range(150, 300))
            assert h.wal.tail_floor() > 0, "GC never advanced the floor"
            with pytest.raises(TailGone) as ei:
                h.wal.read_tail(0)
            assert ei.value.floor == h.wal.tail_floor()
            # the still-retained suffix reads fine from the floor
            events, _, durable = h.wal.read_tail(h.wal.tail_floor(),
                                                 max_bytes=1 << 20)
            assert events and events[-1][0] == durable
        finally:
            h.close()

    def test_retention_budget_forces_lagging_cursor_off(
            self, tmp_path, monkeypatch):
        """cdc-max-retention-bytes is a hard ceiling: a stalled
        consumer cannot pin unbounded disk — the WAL force-reclaims and
        the consumer gets TailGone (-> snapshot restart) instead."""
        monkeypatch.setattr(wal_mod, "SEGMENT_MAX_BYTES", 2048)
        h = _mk_holder(tmp_path)
        try:
            h.wal.cdc_retention_bytes = 4096
            h.wal.register_cursor("stalled", 0)
            frag = _frag(h)
            _set_bits(h, frag, range(400))
            assert h.wal.metrics()["cdc_forced_reclaims_total"] > 0
            with pytest.raises(TailGone):
                h.wal.read_tail(0)
        finally:
            h.close()

    def test_tombstone_pinned_segment_survives_rotation(
            self, tmp_path, monkeypatch):
        """A segment whose only unconsumed records are tombstones is
        still feed history: GC must hold it for the lagging cursor
        exactly like op segments."""
        monkeypatch.setattr(wal_mod, "SEGMENT_MAX_BYTES", 2048)
        h = _mk_holder(tmp_path)
        try:
            h.wal.register_cursor("c", 0)
            frag = _frag(h)
            frag.set_bit(1, 1)
            h.create_index("doomed")
            h.delete_index("doomed")
            for i in range(200):
                frag.set_bit(1, i + 2)
            h.wal.barrier()
            events, _, _ = h.wal.read_tail(0, max_bytes=1 << 20)
            tombs = [e for e in events if e[1] == REC_TOMBSTONE]
            assert tombs and tombs[0][2] == "doomed/"
        finally:
            h.close()


# ------------------------------------------------------ frame wire fuzz


class TestFeedFrames:
    EVENTS = [
        (7, REC_OP, "i/f/standard/0", b"\x01" * 11),
        (8, REC_TOMBSTONE, "i/", b""),
        (9, REC_OP, "i/g/standard/3", bytes(range(40))),
    ]

    def test_roundtrip(self):
        buf = encode_events(self.EVENTS)
        assert list(iter_frames(buf)) == self.EVENTS

    def test_truncation_at_every_offset_stops_cleanly(self):
        """The shmring fuzz shape on the wire body: cut the stream at
        EVERY byte offset — the reader yields a whole-frame prefix,
        never raises, never yields a partial record."""
        buf = encode_events(self.EVENTS)
        for cut in range(len(buf)):
            got = list(iter_frames(buf[:cut]))
            assert got == self.EVENTS[: len(got)], f"cut {cut}"

    def test_corruption_in_record_bytes_stops_never_yields_garbage(self):
        """Flip one byte at every offset of the RECORD portion of the
        first frame (header, key, body — everything the producer's CRC
        or magic covers): the stream stops at or before that frame;
        any frame that does decode is byte-identical to an original."""
        buf = bytearray(encode_events(self.EVENTS))
        first_rec_end = len(encode_events(self.EVENTS[:1]))
        for off in range(8, first_rec_end):  # skip the seq prefix
            mutated = bytearray(buf)
            mutated[off] ^= 0xFF
            got = list(iter_frames(bytes(mutated)))
            for ev in got:
                assert ev in self.EVENTS, f"offset {off} yielded {ev!r}"
            assert self.EVENTS[0] not in got or mutated[off] == buf[off]


# ------------------------------------------------------- HTTP tail route


@pytest.fixture
def tail_server(tmp_path):
    from pilosa_tpu.server.api import API
    from pilosa_tpu.server.http import serve_in_thread

    holder = Holder(str(tmp_path / "data")).open()
    api = API(holder)
    server, port, _ = serve_in_thread(api)
    yield f"http://localhost:{port}", holder
    server.shutdown()
    server.server_close()
    holder.close()


def _get(url):
    r = urllib.request.Request(url, method="GET")
    with urllib.request.urlopen(r, timeout=30) as resp:
        return resp.status, dict(resp.headers), resp.read()


class TestWalTailRoute:
    def test_attach_then_poll(self, tail_server):
        base, holder = tail_server
        frag = _frag(holder)
        for i in range(5):
            frag.set_bit(1, i)
        holder.wal.barrier()
        # attach: no `since` -> empty body, cursor = durable high-water
        st, headers, body = _get(f"{base}/internal/wal/tail")
        assert st == 200 and body == b""
        durable = int(headers[DURABLE_SEQ_HEADER])
        assert int(headers[NEXT_SEQ_HEADER]) == durable == 5
        frag.set_bit(1, 99)
        holder.wal.barrier()
        st, headers, body = _get(
            f"{base}/internal/wal/tail?since={durable}")
        assert st == 200
        events = list(iter_frames(body))
        assert [(e[0], e[2]) for e in events] == [(6, "i/f/standard/0")]
        assert int(headers[NEXT_SEQ_HEADER]) == 6

    def test_cursor_registration_pins(self, tail_server):
        base, holder = tail_server
        _get(f"{base}/internal/wal/tail?cursor=it")
        assert "it" in holder.wal.cursors()

    def test_gone_is_410_with_restart_hint(self, tail_server):
        base, holder = tail_server
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(f"{base}/internal/wal/tail?since=12345")
        assert ei.value.code == 410
        detail = json.loads(ei.value.read())
        assert detail["restartFrom"] == holder.wal.durable_seq()
        assert "floor" in detail

    def test_unknown_cursor_poll_is_410(self, tail_server):
        # the cursor registry is in-memory: a poll naming a cursor the
        # producer never registered (it restarted, or force-reclaimed
        # the laggard) must 410 even when `since` still lands inside
        # the fresh seq space — the silent-gap hard edge
        base, holder = tail_server
        frag = _frag(holder)
        for i in range(5):
            frag.set_bit(1, i)
        holder.wal.barrier()
        _get(f"{base}/internal/wal/tail?cursor=it")  # attach
        st, _, _ = _get(f"{base}/internal/wal/tail?cursor=it&since=2")
        assert st == 200
        holder.wal.drop_cursor("it")  # what a producer restart does
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(f"{base}/internal/wal/tail?cursor=it&since=2")
        assert ei.value.code == 410

    def test_bad_params_are_400(self, tail_server):
        base, _ = tail_server
        for q in ("since=xyz", "max-bytes=0"):
            with pytest.raises(urllib.error.HTTPError) as ei:
                _get(f"{base}/internal/wal/tail?{q}")
            assert ei.value.code == 400, q

    def test_non_group_durability_is_501(self, tmp_path):
        from pilosa_tpu.server.api import API
        from pilosa_tpu.server.http import serve_in_thread

        holder = Holder(str(tmp_path / "d"),
                        durability_mode="flush-only").open()
        api = API(holder)
        server, port, _ = serve_in_thread(api)
        try:
            with pytest.raises(urllib.error.HTTPError) as ei:
                _get(f"http://localhost:{port}/internal/wal/tail")
            assert ei.value.code == 501
        finally:
            server.shutdown()
            server.server_close()
            holder.close()


# ------------------------------------- cluster-safe result cache (CDC)


@pytest.fixture
def _fresh_cache():
    from pilosa_tpu.serving.rescache import (
        ResultCache,
        set_global_result_cache,
    )

    yield
    set_global_result_cache(ResultCache(0))


def _query(base, index, pql):
    return req("POST", f"{base}/index/{index}/query", pql.encode())


def _seed_two_shard(servers):
    from pilosa_tpu.shardwidth import SHARD_WIDTH

    base = uri(servers[0])
    req("POST", f"{base}/index/i", {})
    req("POST", f"{base}/index/i/field/f", {})
    for s in range(4):  # spread shards so SOME are remote to node0
        _query(base, "i", f"Set({s * SHARD_WIDTH + 5}, f=1)")


class TestClusterCache:
    def test_pre_cdc_cluster_edges_refuse_with_reason(
            self, tmp_path, _fresh_cache):
        servers = make_cluster(tmp_path, 2, result_cache_bytes=8 << 20)
        try:
            _seed_two_shard(servers)
            base = uri(servers[0])
            for _ in range(3):
                assert _query(base, "i", "Count(Row(f=1))")[
                    "results"] == [4]
            snap = req("GET", f"{base}/debug/rescache")
            assert snap["refusals"].get("cluster-no-cdc", 0) > 0
            assert "cdc" not in snap  # no tailer -> no lag gauge
        finally:
            for s in servers:
                s.close()

    def test_cdc_lifts_the_refusal_and_invalidates_remote_writes(
            self, tmp_path, _fresh_cache):
        """The tentpole oracle: with tailers live, a cluster-edge
        result caches (hit on re-read) AND a write landing on the
        OTHER node invalidates it — read-your-writes holds cluster-
        wide, within the staleness the tail poll allows."""
        from pilosa_tpu.shardwidth import SHARD_WIDTH

        servers = make_cluster(tmp_path, 2, result_cache_bytes=8 << 20,
                               cdc_enabled=True, cdc_poll_interval=0.02)
        try:
            _seed_two_shard(servers)
            s0 = uri(servers[0])
            _wait(lambda: req("GET", f"{s0}/debug/rescache")
                  .get("cdc", {}).get("live"), msg="cdc live on node0")

            # `live` means the tailers are polling, not that the seed
            # writes' events have drained — a fill racing the catch-up
            # invalidations refuses on the version fence (by design,
            # counted as a fill race), so poll until a fill lands and
            # the re-read HITS instead of demanding the first fill win
            def cached_hit():
                before = req("GET", f"{s0}/debug/rescache")
                assert _query(s0, "i", "Count(Row(f=1))")[
                    "results"] == [4]
                after = req("GET", f"{s0}/debug/rescache")
                return (after["result_cache_hits_total"]
                        > before["result_cache_hits_total"])

            _wait(cached_hit,
                  msg="cluster-edge result cached despite live CDC")
            # write through the PEER: its WAL event must reach node0's
            # tailer and invalidate the cached edge result
            s1 = uri(servers[1])
            _query(s1, "i", f"Set({7 * SHARD_WIDTH + 5}, f=1)")

            def fresh():
                return _query(s0, "i",
                              "Count(Row(f=1))")["results"] == [5]

            _wait(fresh, msg="remote write to invalidate node0's cache")
            lag = req("GET", f"{s0}/debug/rescache")["cdc"]["peerLag"]
            assert len(lag) == 1  # one peer tailed
        finally:
            for s in servers:
                s.close()


# ----------------------------------------------------- read replicas


class TestFollower:
    def test_follower_serves_stale_bounded_reads(self, tmp_path,
                                                 _fresh_cache):
        from pilosa_tpu.server import Server, ServerConfig

        primary = Server(ServerConfig(
            data_dir=str(tmp_path / "p"), port=0, name="p",
            anti_entropy_interval=0, heartbeat_interval=0,
            use_mesh=False,
        )).open()
        follower = None
        try:
            pbase = uri(primary)
            req("POST", f"{pbase}/index/i", {})
            req("POST", f"{pbase}/index/i/field/f", {})
            for c in range(10):
                _query(pbase, "i", f"Set({c}, f=1)")
            follower = Server(ServerConfig(
                data_dir=str(tmp_path / "r"), port=0, name="r",
                anti_entropy_interval=0, heartbeat_interval=0,
                use_mesh=False, cdc_follow=pbase,
                cdc_poll_interval=0.02, cdc_staleness_budget=30.0,
            )).open()
            fbase = uri(follower)
            _wait(lambda: follower.api.follower.staleness_s() < 30,
                  msg="follower initial sync")
            # bulk-synced data serves
            assert _query(fbase, "i",
                          "Count(Row(f=1))")["results"] == [10]
            # post-sync writes flow through the tail
            _query(pbase, "i", "Set(99, f=1)")

            def caught_up():
                return _query(fbase, "i",
                              "Count(Row(f=1))")["results"] == [11]

            _wait(caught_up, msg="tail apply on follower")
            # followers are read replicas: writes 403
            with pytest.raises(urllib.error.HTTPError) as ei:
                _query(fbase, "i", "Set(1, f=2)")
            assert ei.value.code == 403
            # schema writes too
            with pytest.raises(urllib.error.HTTPError) as ei:
                req("POST", f"{fbase}/index/other", {})
            assert ei.value.code == 403
        finally:
            if follower is not None:
                follower.close()
            primary.close()

    def test_staleness_header_sheds_503_with_retry_after(
            self, tmp_path, _fresh_cache):
        from pilosa_tpu.server import Server, ServerConfig

        primary = Server(ServerConfig(
            data_dir=str(tmp_path / "p"), port=0, name="p",
            anti_entropy_interval=0, heartbeat_interval=0,
            use_mesh=False,
        )).open()
        follower = None
        try:
            pbase = uri(primary)
            req("POST", f"{pbase}/index/i", {})
            req("POST", f"{pbase}/index/i/field/f", {})
            _query(pbase, "i", "Set(1, f=1)")
            follower = Server(ServerConfig(
                data_dir=str(tmp_path / "r"), port=0, name="r",
                anti_entropy_interval=0, heartbeat_interval=0,
                use_mesh=False, cdc_follow=pbase,
                cdc_poll_interval=0.02, cdc_staleness_budget=30.0,
            )).open()
            fbase = uri(follower)
            _wait(lambda: follower.api.follower.staleness_s() < 30,
                  msg="follower initial sync")
            # an impossible budget: real staleness is always > 1us
            r = urllib.request.Request(
                f"{fbase}/index/i/query",
                data=b"Count(Row(f=1))", method="POST")
            r.add_header("X-Pilosa-Max-Staleness", "1us")
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(r, timeout=30)
            assert ei.value.code == 503
            assert int(ei.value.headers["Retry-After"]) >= 1
            # malformed budget is the caller's bug: 400, not a shed
            r = urllib.request.Request(
                f"{fbase}/index/i/query",
                data=b"Count(Row(f=1))", method="POST")
            r.add_header("X-Pilosa-Max-Staleness", "soon")
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(r, timeout=30)
            assert ei.value.code == 400
            # a generous budget passes on a caught-up follower
            assert _query(fbase, "i",
                          "Count(Row(f=1))")["results"] == [1]
            m = follower.api.cdc_metrics()
            assert m["cdc_follower"] == 1
            assert m["cdc_follower_staleness_seconds"] >= 0
        finally:
            if follower is not None:
                follower.close()
            primary.close()


# -------------------------------------------------- as-of restore (PIT)


class TestAsOfRestore:
    def _ledger_holder(self, tmp_path):
        h = _mk_holder(tmp_path, "src")
        frag = _frag(h)
        for i in range(10):
            frag.set_bit(1, i)
        h.wal.barrier()
        return h, frag

    def _cols(self, dst):
        h = Holder(str(dst)).open()
        try:
            frag = h.index("i").field("f").view(
                VIEW_STANDARD).fragment(0)
            return sorted(frag.row_columns(1).tolist())
        finally:
            h.close()

    def test_every_ledger_point_restores_bit_exactly(self, tmp_path):
        """The acceptance oracle: record (seq -> expected state) after
        every acked write, then EVERY recorded seq restores to exactly
        that state — adds, a clear, across two generations."""
        h, frag = self._ledger_holder(tmp_path)
        bk = tmp_path / "bk"
        try:
            backup_holder(h, str(bk))
            ledger = {}
            cols = set(range(10))
            for i in range(10, 24):
                frag.set_bit(1, i)
                cols.add(i)
                h.wal.barrier()
                ledger[h.wal.durable_seq()] = sorted(cols)
            frag.clear_bit(1, 3)
            cols.discard(3)
            h.wal.barrier()
            ledger[h.wal.durable_seq()] = sorted(cols)
            backup_holder(h, str(bk))
            for seq, want in ledger.items():
                dst = tmp_path / f"r{seq}"
                m = restore_holder(str(bk), str(dst), as_of=seq)
                assert self._cols(dst) == want, f"as_of={seq}"
                assert m["asOfSeq"] == seq
        finally:
            h.close()

    def test_boundary_as_of_needs_no_replay(self, tmp_path):
        h, _ = self._ledger_holder(tmp_path)
        bk = tmp_path / "bk"
        try:
            m1 = backup_holder(h, str(bk))
            dst = tmp_path / "r"
            m = restore_holder(str(bk), str(dst), as_of=m1["walSeq"])
            assert m["replayedOps"] == 0
            assert self._cols(dst) == list(range(10))
        finally:
            h.close()

    def test_as_of_past_latest_generation_errors(self, tmp_path):
        h, _ = self._ledger_holder(tmp_path)
        try:
            m1 = backup_holder(h, str(tmp_path / "bk"))
            with pytest.raises(ValueError, match="past the latest"):
                restore_holder(str(tmp_path / "bk"),
                               str(tmp_path / "r"),
                               as_of=m1["walSeq"] + 1)
        finally:
            h.close()

    def test_tombstone_inside_window_refuses(self, tmp_path):
        h, frag = self._ledger_holder(tmp_path)
        bk = tmp_path / "bk"
        try:
            backup_holder(h, str(bk))
            frag.set_bit(1, 50)
            h.wal.barrier()
            mid = h.wal.durable_seq()
            h.delete_index("i")
            jfrag = _frag(h, index="j")
            jfrag.set_bit(1, 1)  # gen2.walSeq lands PAST the tombstone
            h.wal.barrier()
            backup_holder(h, str(bk))
            # replaying THROUGH the deletion is refused...
            with pytest.raises(ValueError, match="deletion"):
                restore_holder(str(bk), str(tmp_path / "r1"),
                               as_of=mid + 1)
            # ...but up to just before it is fine
            restore_holder(str(bk), str(tmp_path / "r2"), as_of=mid)
            assert self._cols(tmp_path / "r2") == sorted(
                set(range(10)) | {50})
        finally:
            h.close()

    def test_generation_and_as_of_are_exclusive(self, tmp_path):
        h, _ = self._ledger_holder(tmp_path)
        try:
            m1 = backup_holder(h, str(tmp_path / "bk"))
            with pytest.raises(ValueError, match="not both"):
                restore_holder(str(tmp_path / "bk"),
                               str(tmp_path / "r"),
                               generation=1, as_of=m1["walSeq"])
        finally:
            h.close()

    def test_fragment_born_inside_window_is_synthesized(self, tmp_path):
        """First write to a brand-new shard lands between generations:
        replay must create the fragment from an empty snapshot, not
        drop the ops."""
        from pilosa_tpu.shardwidth import SHARD_WIDTH

        h, frag = self._ledger_holder(tmp_path)
        bk = tmp_path / "bk"
        try:
            backup_holder(h, str(bk))
            f2 = _frag(h, shard=3)
            f2.set_bit(4, 7)
            h.wal.barrier()
            seq = h.wal.durable_seq()
            frag.set_bit(1, 60)  # push gen2's walSeq past `seq` so the
            h.wal.barrier()      # restore goes through REPLAY, not the
            backup_holder(h, str(bk))  # generation's own content walk
            dst = tmp_path / "r"
            m = restore_holder(str(bk), str(dst), as_of=seq)
            assert m["replayedOps"] >= 1
            h2 = Holder(str(dst)).open()
            try:
                got = h2.index("i").field("f").view(
                    VIEW_STANDARD).fragment(3).row_columns(4).tolist()
                assert got == [7]
            finally:
                h2.close()
        finally:
            h.close()

    def test_backup_registers_pin_cursor(self, tmp_path):
        h, _ = self._ledger_holder(tmp_path)
        try:
            backup_holder(h, str(tmp_path / "bk"))
            names = list(h.wal.cursors())
            assert any(n.startswith("backup:") for n in names)
        finally:
            h.close()

    def test_non_grouped_wal_backups_have_no_anchor(self, tmp_path):
        h = _mk_holder(tmp_path, "src", durability_mode="flush-only")
        try:
            frag = _frag(h)
            frag.set_bit(1, 1)
            m = backup_holder(h, str(tmp_path / "bk"))
            assert m["walSeq"] is None and m["walFeed"] is None
            with pytest.raises(ValueError, match="group-durability"):
                restore_holder(str(tmp_path / "bk"),
                               str(tmp_path / "r"), as_of=1)
        finally:
            h.close()
