"""The trace reduction, on a trace recorded on the chip (cut to 600
device operations of SSB flight 2 as GroupBys on one TPU v5e, a mix that
PR 24 measured and did not ship) and on written traces whose answers are
known."""

import json
import os

import pytest

from bench_helpers import BENCH, DATA
from harness import readers, trace
from xplane_writer import xspace

MS = 1_000_000  # ns


def write(tmp_path, planes) -> str:
    p = tmp_path / "t.xplane.pb"
    p.write_bytes(xspace(planes))
    return str(p)


def test_recorded_trace_reduces_to_the_same_numbers_every_time():
    path = os.path.join(DATA, "tpu_v5e_one_chip.xplane.pb")
    with open(os.path.join(DATA, "tpu_v5e_one_chip.expected.json")) as f:
        want = json.load(f)
    for _ in range(2):
        got = trace.reduce(path, 0.0)
        assert got["devices"] == want["devices"] == 1
        assert got["window_s"] == pytest.approx(want["window_s"], rel=1e-12)
        assert got["busy_s"] == pytest.approx(want["busy_s"], rel=1e-12)
        b = trace.breakdown(got)
        assert [n for n, _ in b["device_ops"]] == \
            [n for n, _ in want["breakdown"]["device_ops"]]
        assert [s for _, s in b["device_ops"]] == pytest.approx(
            [s for _, s in want["breakdown"]["device_ops"]], rel=1e-12)
        assert [s for _, s in b["idle_gaps"]] == pytest.approx(
            [s for _, s in want["breakdown"]["idle_gaps"]], rel=1e-12)
    assert 0 < got["busy_s"] < got["window_s"]
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) == 5
    assert all(len(n) < 80 for n, _ in b["device_ops"])


def two_chip_trace():
    fusion = "%fusion.7 = u32[128,2048]{1,0:T(8,128)} fusion(u32[128,32768] %p)"
    allred = "%all-reduce.3 = s32[128]{0} all-reduce(s32[128]{0} %x)"
    gather = "%all-gather-start.1 = (u32[4]{0}, u32[16]{0}) all-gather-start(%y)"
    return [
        ("/device:TPU:0", [
            ("XLA Modules", [("jit_count", 0, 100 * MS)]),
            ("XLA Ops", [(fusion, 10 * MS, 20 * MS), (allred, 25 * MS, 10 * MS),
                         (fusion, 60 * MS, 10 * MS)])]),
        ("/device:TPU:1", [
            ("XLA Ops", [(fusion, 0, 10 * MS), (gather, 50 * MS, 30 * MS)])]),
        ("/host:CPU", [("python3", [("serve", 0, 500 * MS)])]),
    ]


def test_busy_is_the_union_of_overlapping_operations_mean_over_chips(tmp_path):
    r = trace.reduce(write(tmp_path, two_chip_trace()), 0.1)
    assert r["devices"] == 2
    # chip 0: [10,35) u [60,70) = 35 ms; chip 1: 10 + 30 = 40 ms
    assert r["busy_s_per_device"] == pytest.approx([0.035, 0.040])
    assert r["busy_s"] == pytest.approx(0.0375)
    assert r["window_s"] == pytest.approx(0.1)
    assert r["ops"]["fusion.7 u32[128,2048]"] == pytest.approx(0.020)
    assert r["idle_gaps"][0] == pytest.approx((0.035, 0.060))


def test_idle_and_operation_share_readers(tmp_path):
    r = trace.reduce(write(tmp_path, two_chip_trace()), 0.1)
    values = {}
    idle = readers.read(BENCH, "device_idle_share", {}, {}, r, values)
    assert idle == pytest.approx(62.5)
    assert readers.read(BENCH, "device_idle_share", {}, {}, None, {}) is None
    # a metric of the trace_ops kind, as a later PR's data file would state
    # it (the four-chip cell's share of time in collectives)
    os.makedirs(tmp_path / "layer_metrics")
    (tmp_path / "layer_metrics" / "collective_share.json").write_text(
        json.dumps({"reader": "trace_ops",
                    "pattern": "all-reduce|all-gather|reduce-scatter"}))
    coll = readers.read(str(tmp_path), "collective_share", {}, {}, r, values)
    assert coll == pytest.approx(100 * (0.010 + 0.030) / 2 / 0.1)
    assert readers.read(str(tmp_path), "collective_share", {}, {}, None,
                        values) is None


def test_window_is_the_devices_extent_when_that_is_longer(tmp_path):
    r = trace.reduce(write(tmp_path, two_chip_trace()), 0.05)
    assert r["window_s"] == pytest.approx(0.1)  # the modules line reaches it


def test_a_trace_with_no_device_operation_reduces_to_none(tmp_path):
    planes = [("/device:TPU:0", [("XLA Ops", [])]),
              ("/host:CPU", [("python3", [("serve", 0, 5 * MS)])])]
    assert trace.reduce(write(tmp_path, planes), 1.0) is None


def test_cpu_rehearsal_traces_fall_back_to_the_xla_threads(tmp_path):
    planes = [("/host:CPU", [("tf_XLAEigen/1", [("dot.1", 0, 2 * MS)]),
                             ("python3", [("serve", 0, 50 * MS)])])]
    r = trace.reduce(write(tmp_path, planes), 0.01)
    assert r["devices"] == 1 and r["busy_s"] == pytest.approx(0.002)


def test_short_names():
    assert trace.short_name(
        "%fusion.36 = (u32[128,12,2048]{2,0,1:T(8,128)}, u32[128,12,2048]"
        "{2,0,1:T(8,128)S(1)}) fusion(u32[128,12,32768]{2,0,1} %a), kind=kLoop"
    ) == "fusion.36 u32[128,12,2048]x2"
    assert trace.short_name("%and.14 = u32[128,32768]{1,0} and(%a, %b)") \
        == "and.14 u32[128,32768]"
    assert trace.short_name("copy.1") == "copy.1"


def test_ratio_reader_reads_deltas_and_leaves_out_what_is_missing():
    before = {"pilosa_tpu_serving_http_requests_total": 100.0,
              "pilosa_tpu_serving_waves_total": 10.0,
              "compile_cache.entries": 7.0}
    after = {"pilosa_tpu_serving_http_requests_total": 400.0,
             "pilosa_tpu_serving_waves_total": 60.0,
             "compile_cache.entries": 7.0, "gen.cpu_seconds": 3.0,
             "gen.window_seconds": 30.0}
    read = lambda name: readers.read(BENCH, name, before, after, None, {})
    assert read("wave_depth") == pytest.approx(6.0)
    assert read("compiles_in_window") == 0.0
    assert read("generator_busy_share") == pytest.approx(10.0)
    assert read("residency_hit_share") is None  # series not exported
    assert read("fsyncs_per_write") is None
    assert readers.read(BENCH, "write_ack_p50_ms", {}, {}, None,
                        {"write_ack_p50_ms": 21.5}) == 21.5
