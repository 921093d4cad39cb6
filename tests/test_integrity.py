"""Storage integrity plane (ISSUE 10): checksum sidecars + verified
loads, quarantine at open, every-offset corruption fuzz, the background
scrubber (detection, read-repair, self-heal), ENOSPC/EIO degraded mode
with probe auto-recovery, epoch-file hardening, restore read-back
verification, and the CLI check verb."""

from __future__ import annotations

import errno
import glob
import json
import os
import threading
import time
import urllib.error

import pytest

from pilosa_tpu.storage import Holder
from pilosa_tpu.storage import integrity
from pilosa_tpu.storage.fragment import Fragment
from pilosa_tpu.storage.integrity import (
    CHECKSUM_SUFFIX,
    CorruptFragmentError,
    StorageHealth,
)
from pilosa_tpu.storage.view import VIEW_STANDARD
from pilosa_tpu.testing import faults


@pytest.fixture(autouse=True)
def _clean_disk_plane():
    yield
    faults.clear_disk()


def _mk_holder(tmp_path, name="h", **kw):
    return Holder(str(tmp_path / name), **kw).open()


def _frag(holder, index="i", field="f", shard=0):
    idx = holder.index(index) or holder.create_index(index)
    fld = idx.field(field) or idx.create_field(field)
    return fld.view(VIEW_STANDARD, create=True).fragment(shard, create=True)


def _flip(path, offset, mask=0x10):
    with open(path, "r+b") as f:
        f.seek(offset)
        b = f.read(1)
        f.seek(offset)
        f.write(bytes([b[0] ^ mask]))


def _seed_frag(holder, n=60):
    frag = _frag(holder)
    for i in range(n):
        frag.set_bit(1, i * 3)
        frag.set_bit(250, i * 5)
    holder.wal.barrier()
    frag.snapshot()
    return frag


class TestChecksumSidecar:
    def test_snapshot_writes_sidecar_matching_blocks(self, tmp_path):
        h = _mk_holder(tmp_path)
        frag = _seed_frag(h)
        sidecar = integrity.load_checksums(frag.path + CHECKSUM_SUFFIX)
        assert sidecar == list(frag.blocks())
        h.close()

    def test_clean_reopen_verifies(self, tmp_path):
        h = _mk_holder(tmp_path)
        _seed_frag(h)
        h.close()
        before = integrity.global_integrity().metrics()[
            "integrity_verified_loads_total"]
        h2 = _mk_holder(tmp_path)
        assert integrity.global_integrity().metrics()[
            "integrity_verified_loads_total"] > before
        assert _frag(h2).count_row(1) == 60
        h2.close()

    def test_torn_sidecar_reads_as_absent_not_corrupt(self, tmp_path):
        h = _mk_holder(tmp_path)
        frag = _seed_frag(h)
        with open(frag.path + CHECKSUM_SUFFIX, "r+b") as f:
            f.truncate(9)
        h.close()
        h2 = _mk_holder(tmp_path)  # skipped verify, not quarantined
        assert _frag(h2).count_row(1) == 60
        h2.close()

    def test_failed_sidecar_write_cannot_condemn_new_snapshot(
            self, tmp_path):
        """The old sidecar dies BEFORE the new snapshot publishes: a
        crash (or ENOSPC) between the rename and the new sidecar
        landing must leave NO sidecar — the next open downgrades to an
        unverified load instead of quarantining the healthy file
        against stale digests."""
        import pilosa_tpu.storage.fragment as frag_mod

        h = _mk_holder(tmp_path)
        frag = _seed_frag(h)  # snapshot 1: sidecar exists
        frag.set_bit(9, 9)

        def broken(path, blocks):
            raise OSError(28, "No space left on device", path)

        orig = frag_mod.save_checksums
        frag_mod.save_checksums = broken
        try:
            frag.snapshot()  # snapshot 2: sidecar write fails
        finally:
            frag_mod.save_checksums = orig
        assert integrity.load_checksums(
            frag.path + CHECKSUM_SUFFIX) is None  # stale one is GONE
        h.close()
        h2 = _mk_holder(tmp_path)  # unverified load, NOT quarantine
        frag2 = h2.index("i").field("f").view(VIEW_STANDARD).fragment(0)
        assert frag2 is not None and frag2.contains(9, 9)
        h2.close()

    def test_flipped_payload_byte_quarantines_at_open(self, tmp_path):
        h = _mk_holder(tmp_path)
        frag = _seed_frag(h)
        path = frag.path
        h.close()
        _flip(path, os.path.getsize(path) - 3)
        h2 = _mk_holder(tmp_path)
        view = h2.index("i").field("f").view(VIEW_STANDARD)
        assert view.fragment(0) is None  # never served
        assert not os.path.exists(path)
        assert glob.glob(path + ".quarantine-*")
        assert integrity.list_quarantined(h2.data_dir)
        h2.close()

    def test_verify_off_skips_digest_check(self, tmp_path):
        h = _mk_holder(tmp_path)
        frag = _seed_frag(h)
        path = frag.path
        h.close()
        # flip inside an array payload: structurally valid, wrong bits
        _flip(path, os.path.getsize(path) - 3)
        h2 = Holder(str(tmp_path / "h"), verify_on_load=False).open()
        assert h2.index("i").field("f").view(VIEW_STANDARD).fragment(0) \
            is not None  # the pre-PR behavior, preserved behind the knob
        h2.close()


class TestCorruptionFuzz:
    """The PR-5 torn-tail fuzz, generalized to the whole file: flip or
    truncate at EVERY offset; open must either succeed (the op tail's
    torn-tail crash model) or raise the typed CorruptFragmentError with
    the path in the message — never a raw struct/zlib/index error."""

    def _fragment_file(self, tmp_path):
        frag = Fragment(str(tmp_path / "frag"), "i", "f",
                        VIEW_STANDARD, 0).open()
        for i in range(40):
            frag.set_bit(1, i * 7)
        frag.snapshot()
        for i in range(6):  # op-log tail past the snapshot
            frag.set_bit(2, i)
        frag.close()
        with open(frag.path, "rb") as f:
            return frag.path, f.read(), list(frag.blocks())

    def _reopen(self, path, verify):
        return Fragment(path, "i", "f", VIEW_STANDARD, 0,
                        verify_on_load=verify).open()

    def test_flip_every_offset(self, tmp_path):
        path, data, blocks = self._fragment_file(tmp_path)
        integrity.save_checksums(path + CHECKSUM_SUFFIX, blocks)
        baseline_ops = 6
        for offset in range(len(data)):
            buf = bytearray(data)
            buf[offset] ^= 0x04
            with open(path, "wb") as f:
                f.write(bytes(buf))
            try:
                frag = self._reopen(path, verify=True)
            except CorruptFragmentError as e:
                assert path in str(e)
            except Exception as e:  # noqa: BLE001
                pytest.fail(f"offset {offset}: raw {type(e).__name__}: {e}")
            else:
                # survived: only the (self-CRC'd) op tail may tolerate
                # a flip, by dropping records — never by inventing ops
                assert frag.op_n <= baseline_ops

    def test_truncate_every_offset(self, tmp_path):
        path, data, blocks = self._fragment_file(tmp_path)
        integrity.save_checksums(path + CHECKSUM_SUFFIX, blocks)
        for end in range(len(data)):
            with open(path, "wb") as f:
                f.write(data[:end])
            try:
                self._reopen(path, verify=True)
            except CorruptFragmentError as e:
                assert path in str(e)
            except Exception as e:  # noqa: BLE001
                pytest.fail(f"truncate {end}: raw {type(e).__name__}: {e}")

    def test_import_roaring_garbage_is_typed(self, tmp_path):
        frag = Fragment(str(tmp_path / "frag"), "i", "f",
                        VIEW_STANDARD, 0).open()
        with pytest.raises(CorruptFragmentError):
            frag.import_roaring(b"\x75\xb1\xc4\x50garbage-after-magic")
        # still a ValueError for existing handlers
        with pytest.raises(ValueError):
            frag.import_roaring(b"\x75\xb1\xc4\x50garbage-after-magic")
        frag.close()


class TestScrubber:
    def test_detects_and_self_heals_without_replicas(self, tmp_path):
        from pilosa_tpu.parallel.scrub import Scrubber

        h = _mk_holder(tmp_path)
        frag = _seed_frag(h)
        live = frag.count_row(1)
        _flip(frag.path, 60)
        s = Scrubber(h)
        rec = s.scrub_pass()
        assert rec["corrupt"] == 1 and rec["self_healed"] == 1, rec
        assert glob.glob(frag.path + ".quarantine-*")
        # disk verifies clean now, live bits preserved
        assert s.scrub_pass()["corrupt"] == 0
        assert _frag(h).count_row(1) == live
        h.close()
        h2 = _mk_holder(tmp_path)
        assert _frag(h2).count_row(1) == live
        h2.close()

    def test_ticking_scrubber_heals_a_flip_under_a_live_server(
            self, tmp_path):
        """The background thread, not an on-demand pass: a byte flipped
        in a live fragment's snapshot is quarantined and healed by the
        next tick, and the node answers as before."""
        from tests.cluster_helpers import make_cluster, req, uri

        from pilosa_tpu.parallel.scrub import Scrubber

        (s,) = make_cluster(tmp_path, 1)
        scrubber = None
        try:
            req("POST", f"{uri(s)}/index/i", {})
            req("POST", f"{uri(s)}/index/i/field/f", {})
            cols = list(range(0, 900, 3))
            req("POST", f"{uri(s)}/index/i/field/f/import",
                {"rows": [1] * len(cols), "columns": cols})
            frag = (s.holder.index("i").field("f").view(VIEW_STANDARD)
                    .fragment(0))
            frag.snapshot()
            scrubber = s.api.scrubber = Scrubber(
                s.holder, cluster=s.api.cluster, interval_s=0.05).start()
            _flip(frag.path, 64, 0x20)
            deadline = time.monotonic() + 30
            while not (scrubber.corruptions >= 1
                       and scrubber.self_healed + scrubber.repaired >= 1):
                assert time.monotonic() < deadline, scrubber.passes
                time.sleep(0.02)
            assert glob.glob(frag.path + ".quarantine-*")
            got = req("POST", f"{uri(s)}/index/i/query",
                      b"Count(Row(f=1))")["results"][0]
            assert got == len(cols)
        finally:
            if scrubber is not None:
                scrubber.close()
            s.close()

    def test_clean_pass_touches_nothing(self, tmp_path):
        from pilosa_tpu.parallel.scrub import Scrubber

        h = _mk_holder(tmp_path)
        _seed_frag(h)
        rec = Scrubber(h).scrub_pass()
        assert rec["corrupt"] == 0 and rec["scanned"] == 1
        assert rec["bytes"] > 0
        assert not integrity.list_quarantined(h.data_dir)
        h.close()

    def test_racing_snapshot_is_not_condemned(self, tmp_path):
        """A mismatch observed unlocked must be re-derived under the
        fragment lock before quarantine acts (a snapshot swapping
        file+sidecar mid-read is a race, not rot)."""
        from pilosa_tpu.parallel.scrub import Scrubber

        h = _mk_holder(tmp_path)
        frag = _seed_frag(h)
        s = Scrubber(h)
        real = integrity.read_file
        calls = {"n": 0}

        def racy_read(path):
            calls["n"] += 1
            if calls["n"] == 1:
                # first (unlocked) read sees a flipped byte...
                data = bytearray(real(path))
                data[-3] ^= 0x40
                return bytes(data)
            return real(path)  # ...the locked re-read sees the truth

        import pilosa_tpu.storage.integrity as integrity_mod

        orig = integrity_mod.read_file
        integrity_mod.read_file = racy_read
        try:
            rec = s.scrub_pass()
        finally:
            integrity_mod.read_file = orig
        assert rec["corrupt"] == 0 and rec["scanned"] == 1, rec
        assert not glob.glob(frag.path + ".quarantine-*")
        h.close()

    def test_read_repair_via_disk_fault_plane(self, tmp_path):
        """bit-flip-on-read injection (no real file mutation) drives
        the same detect → quarantine → heal path the media-rot case
        takes, proving detection needs no lucky write pattern."""
        from pilosa_tpu.parallel.scrub import Scrubber

        h = _mk_holder(tmp_path)
        frag = _seed_frag(h)
        plane = faults.install_disk()
        plane.add("read", path=frag.path, flip_offset=70, flip_mask=0x02)
        s = Scrubber(h)
        rec = s.scrub_pass()
        # rule is unlimited: both the unlocked read and the locked
        # confirm see the flip — detection + self-heal fire
        assert rec["corrupt"] == 1 and rec["self_healed"] == 1, rec
        faults.clear_disk()
        assert s.scrub_pass()["corrupt"] == 0
        h.close()


class TestStorageDegraded:
    @pytest.fixture()
    def server(self, tmp_path):
        from tests.cluster_helpers import make_cluster

        StorageHealth.PROBE_INTERVAL_S = 0.1
        (s,) = make_cluster(tmp_path, 1)
        try:
            yield s
        finally:
            StorageHealth.PROBE_INTERVAL_S = 1.0
            faults.clear_disk()
            s.close()

    def _req(self, s, method, path, body=None):
        from tests.cluster_helpers import req, uri

        return req(method, f"{uri(s)}{path}", body)

    def test_enospc_on_wal_flips_degraded_and_recovers(self, server):
        s = server
        self._req(s, "POST", "/index/i", {})
        self._req(s, "POST", "/index/i/field/f", {})
        self._req(s, "POST", "/index/i/query", b"Set(1, f=1)")
        plane = faults.install_disk()
        rule = plane.add("fsync", path=s.holder.data_dir,
                         errno_=errno.ENOSPC)
        with pytest.raises(urllib.error.HTTPError):
            self._req(s, "POST", "/index/i/query", b"Set(2, f=1)")
        st = self._req(s, "GET", "/status")
        assert st["storageDegraded"] is True
        assert "No space left" in st["storageDegradedReason"]
        # subsequent writes shed 503 + Retry-After on the QoS path
        with pytest.raises(urllib.error.HTTPError) as err:
            self._req(s, "POST", "/index/i/query", b"Set(3, f=1)")
        assert err.value.code == 503
        assert err.value.headers.get("Retry-After")
        # schema writes shed too
        with pytest.raises(urllib.error.HTTPError) as err:
            self._req(s, "POST", "/index/j", {})
        assert err.value.code == 503
        # reads still serve
        out = self._req(s, "POST", "/index/i/query", b"Count(Row(f=1))")
        assert isinstance(out["results"][0], int)
        # gauge exported
        from tests.cluster_helpers import req, uri

        text = req("GET", f"{uri(s)}/metrics", raw=True).decode()
        assert "storage_degraded 1" in text
        # heal: drop the rule -> the probe clears the latch
        plane.remove(rule.id)
        deadline = time.time() + 10
        while (time.time() < deadline
               and self._req(s, "GET", "/status")["storageDegraded"]):
            time.sleep(0.1)
        assert self._req(s, "GET", "/status")["storageDegraded"] is False
        out = self._req(s, "POST", "/index/i/query", b"Set(4, f=1)")
        assert out["results"] == [True]
        text = req("GET", f"{uri(s)}/metrics", raw=True).decode()
        assert "storage_degraded 0" in text
        assert "storage_recoveries_total 1" in text

    def test_failed_group_never_acks_after_recovery(self, tmp_path):
        """The lost group's barrier must raise FOREVER — clearing the
        fault and committing newer groups past it must not convert the
        lost writes into late ACKs."""
        StorageHealth.PROBE_INTERVAL_S = 0.05
        h = _mk_holder(tmp_path)
        try:
            frag = _frag(h)
            frag.set_bit(1, 1)
            h.wal.barrier()
            plane = faults.install_disk()
            rule = plane.add("fsync", path=h.data_dir,
                             errno_=errno.ENOSPC)
            frag.set_bit(1, 2)
            seq_lost = h.wal.current_seq()
            with pytest.raises(OSError, match="wal commit failed"):
                h.wal.barrier(seq_lost)
            plane.remove(rule.id)
            deadline = time.time() + 5
            while h.health.degraded and time.time() < deadline:
                time.sleep(0.05)
            assert not h.health.degraded
            frag.set_bit(1, 3)  # new group commits fine
            h.wal.barrier()
            with pytest.raises(OSError, match="wal commit failed"):
                h.wal.barrier(seq_lost)  # the lost group stays lost
        finally:
            faults.clear_disk()
            StorageHealth.PROBE_INTERVAL_S = 1.0
            h.close()

    def test_snapshot_enospc_trips_health(self, tmp_path):
        StorageHealth.PROBE_INTERVAL_S = 30.0  # no auto-clear mid-test
        h = _mk_holder(tmp_path)
        try:
            frag = _seed_frag(h)
            plane = faults.install_disk()
            plane.add("fsync", path=frag.path, errno_=errno.ENOSPC,
                      count=1)
            with pytest.raises(OSError):
                frag.snapshot()
            assert h.health.degraded
            assert "snapshot" in h.health.reason
        finally:
            faults.clear_disk()
            StorageHealth.PROBE_INTERVAL_S = 1.0
            h.close()


class TestEpochFile:
    def _cluster(self, tmp_path):
        from pilosa_tpu.parallel.cluster import Cluster, Node

        holder = _mk_holder(tmp_path, "epoch-h")
        return holder, Cluster(Node("n0", "http://localhost:1"),
                               holder=holder)

    def test_garbage_epoch_file_recovers(self, tmp_path):
        holder = _mk_holder(tmp_path, "epoch-h")
        epoch_path = os.path.join(holder.data_dir, "cluster.epoch")
        with open(epoch_path, "wb") as f:
            f.write(b"\x00\xffgarbage\x13\x37")
        holder.close()
        from pilosa_tpu.parallel.cluster import Cluster, Node

        holder2 = Holder(str(tmp_path / "epoch-h")).open()
        c = Cluster(Node("n0", "http://localhost:1"), holder=holder2)
        assert c.epoch == 0
        # file re-persisted clean: the next open parses it
        with open(epoch_path) as f:
            assert int(f.read().strip()) == 0
        # gossip re-adoption still works and persists
        c.adopt_epoch(2048)
        with open(epoch_path) as f:
            assert int(f.read().strip()) == 2048
        holder2.close()

    def test_truncated_epoch_file_recovers(self, tmp_path):
        holder, c0 = self._cluster(tmp_path)
        c0.adopt_epoch(4096)
        epoch_path = os.path.join(holder.data_dir, "cluster.epoch")
        with open(epoch_path, "r+b") as f:
            f.truncate(2)  # "40": parses as a WRONG but valid int? no-
            # truncate to 2 bytes of "4096" -> "40", still an int; make
            # it truly torn instead
        with open(epoch_path, "wb") as f:
            f.write(b"40\x00\x01")
        from pilosa_tpu.parallel.cluster import Cluster, Node

        c = Cluster(Node("n0", "http://localhost:1"), holder=holder)
        assert c.epoch == 0  # torn file -> re-adopt from gossip
        holder.close()

    def test_empty_and_missing_epoch_files(self, tmp_path):
        holder, _ = self._cluster(tmp_path)
        epoch_path = os.path.join(holder.data_dir, "cluster.epoch")
        open(epoch_path, "w").close()
        from pilosa_tpu.parallel.cluster import Cluster, Node

        assert Cluster(Node("n0", "http://x:1"), holder=holder).epoch == 0
        os.unlink(epoch_path)
        assert Cluster(Node("n0", "http://x:1"), holder=holder).epoch == 0
        holder.close()


class TestRestoreVerify:
    def _seed(self, tmp_path):
        h = _mk_holder(tmp_path, "src")
        _seed_frag(h)
        from pilosa_tpu.storage.backup import backup_holder

        backup_holder(h, str(tmp_path / "bk"))
        h.close()

    def test_restore_writes_sidecars_and_verifies(self, tmp_path):
        self._seed(tmp_path)
        from pilosa_tpu.storage.backup import restore_holder

        manifest = restore_holder(str(tmp_path / "bk"),
                                  str(tmp_path / "dst"))
        assert manifest["restoredFragments"] >= 1
        frag_path = os.path.join(
            str(tmp_path / "dst"), "i", "f", "views", VIEW_STANDARD,
            "fragments", "0")
        assert integrity.load_checksums(
            frag_path + CHECKSUM_SUFFIX) is not None
        # restored dir passes a verified open
        h = Holder(str(tmp_path / "dst")).open()
        assert _frag(h).count_row(1) == 60
        h.close()

    def test_corrupt_at_rest_target_fails_restore(self, tmp_path):
        """A restore target that flips bits at rest (injected on the
        read-back seam) is caught AT RESTORE TIME by the live checksum
        verification, not at first query weeks later."""
        self._seed(tmp_path)
        from pilosa_tpu.storage.backup import restore_holder

        plane = faults.install_disk()
        plane.add("read", path=f"{tmp_path}/dst", flip_offset=66)
        with pytest.raises(ValueError, match="digest verification"):
            restore_holder(str(tmp_path / "bk"), str(tmp_path / "dst"))


class TestCLICheck:
    def test_offline_check_clean_and_corrupt(self, tmp_path, capsys):
        from pilosa_tpu.cli import main

        h = _mk_holder(tmp_path, "data")
        frag = _seed_frag(h)
        path = frag.path
        h.close()
        assert main(["check", "-d", str(tmp_path / "data")]) == 0
        out = capsys.readouterr()
        assert "ok:" in out.out
        _flip(path, os.path.getsize(path) - 3)
        assert main(["check", "-d", str(tmp_path / "data")]) == 1
        out = capsys.readouterr()
        assert "CORRUPT" in out.err and "digest mismatch" in out.err

    def test_offline_check_reports_quarantine(self, tmp_path, capsys):
        from pilosa_tpu.cli import main

        h = _mk_holder(tmp_path, "data")
        frag = _seed_frag(h)
        path = frag.path
        h.close()
        _flip(path, os.path.getsize(path) - 3)
        Holder(str(tmp_path / "data")).open().close()  # quarantines
        assert main(["check", "-d", str(tmp_path / "data")]) == 1
        assert "QUARANTINED" in capsys.readouterr().err

    def test_check_requires_target(self, capsys):
        from pilosa_tpu.cli import main

        assert main(["check"]) == 1
        assert "data-dir or --host" in capsys.readouterr().err

    def test_live_check_triggers_scrub(self, tmp_path, capsys):
        from tests.cluster_helpers import make_cluster, uri

        from pilosa_tpu.cli import main

        (s,) = make_cluster(tmp_path, 1)
        try:
            from tests.cluster_helpers import req

            req("POST", f"{uri(s)}/index/i", {})
            req("POST", f"{uri(s)}/index/i/field/f", {})
            req("POST", f"{uri(s)}/index/i/query", b"Set(5, f=1)")
            s.holder.index("i").field("f").view(VIEW_STANDARD) \
                .fragment(0).snapshot()
            assert main(["check", "--host", uri(s)]) == 0
            out = capsys.readouterr().out
            assert "live scrub" in out and "scanned=" in out
        finally:
            s.close()


class TestScrubEndpointAndMetrics:
    def test_internal_scrub_and_metrics_series(self, tmp_path):
        from tests.cluster_helpers import make_cluster, req, uri

        (s,) = make_cluster(tmp_path, 1)
        try:
            req("POST", f"{uri(s)}/index/i", {})
            req("POST", f"{uri(s)}/index/i/field/f", {})
            req("POST", f"{uri(s)}/index/i/query", b"Set(5, f=1)")
            frag = (s.holder.index("i").field("f").view(VIEW_STANDARD)
                    .fragment(0))
            frag.snapshot()
            _flip(frag.path, os.path.getsize(frag.path) - 2)
            rec = req("POST", f"{uri(s)}/internal/scrub", b"")
            assert rec["corrupt"] == 1 and rec["self_healed"] == 1
            text = req("GET", f"{uri(s)}/metrics", raw=True).decode()
            for series in ("integrity_quarantined_total",
                           "integrity_self_heals_total",
                           "scrub_passes_total", "storage_degraded"):
                assert series in text, series
            dv = req("GET", f"{uri(s)}/debug/vars")
            assert "integrity" in dv
            st = req("GET", f"{uri(s)}/status")
            assert st["storageDegraded"] is False
        finally:
            s.close()

    def test_config_knobs_roundtrip(self):
        from pilosa_tpu.server import ServerConfig

        cfg = ServerConfig.from_dict({
            "verify-on-load": "false",
            "scrub-interval": "90s",
            "scrub-max-bytes-per-sec": "1048576",
        })
        assert cfg.verify_on_load is False
        assert cfg.scrub_interval == 90.0
        assert cfg.scrub_max_bytes_per_sec == 1 << 20
        d = cfg.to_dict()
        assert d["verify-on-load"] is False
        assert d["scrub-interval"] == 90.0
        assert d["scrub-max-bytes-per-sec"] == 1 << 20
        with pytest.raises(ValueError, match="scrub-interval"):
            ServerConfig(scrub_interval=-1)


class TestReadRepair:
    def test_two_node_byte_identical_heal(self, tmp_path):
        from tests.cluster_helpers import make_cluster, req, uri

        from pilosa_tpu.parallel.scrub import Scrubber

        a, b = make_cluster(tmp_path, 2, replica_n=2)
        try:
            req("POST", f"{uri(a)}/index/i", {})
            req("POST", f"{uri(a)}/index/i/field/f", {})
            acked = []
            for col in range(0, 420, 7):
                out = req("POST", f"{uri(a)}/index/i/query",
                          f"Set({col}, f=3)".encode())
                if out["results"] == [True]:
                    acked.append(col)
            for s in (a, b):
                s.holder.index("i").field("f").view(VIEW_STANDARD) \
                    .fragment(0).snapshot()
            frag_b = (b.holder.index("i").field("f").view(VIEW_STANDARD)
                      .fragment(0))
            want = frag_b.serialize_snapshot()
            # reads are served from the node with the rotten file for
            # the whole window: every answer must be the truth
            reads, wrong = [], []
            stop = threading.Event()

            def reader():
                while not stop.is_set() or not reads:
                    got = req("POST", f"{uri(b)}/index/i/query",
                              b"Count(Row(f=3))")["results"][0]
                    reads.append(got)
                    if got != len(acked):
                        wrong.append(got)

            t = threading.Thread(target=reader)
            t.start()
            try:
                _flip(frag_b.path, 50, 0x08)
                rec = Scrubber(b.holder,
                               cluster=b.api.cluster).scrub_pass()
            finally:
                stop.set()
                t.join(30)
            assert not t.is_alive() and reads and not wrong, wrong
            assert rec["corrupt"] == 1 and rec["repaired"] == 1, rec
            healed = (b.holder.index("i").field("f").view(VIEW_STANDARD)
                      .fragment(0))
            assert healed is not None
            assert healed.serialize_snapshot() == want  # byte-identical
            with open(healed.path, "rb") as f:
                assert f.read() == want  # on disk too
            # zero lost acked writes
            got = set(req("POST", f"{uri(b)}/index/i/query",
                          b"Row(f=3)")["results"][0]["columns"])
            assert got == set(acked)
            assert glob.glob(healed.path + ".quarantine-*")
        finally:
            a.close()
            b.close()
