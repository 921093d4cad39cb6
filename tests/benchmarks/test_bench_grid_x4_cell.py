"""The cells ``taxi-rides-grid-x4.cell-lookup`` and
``ssb-lineorder.brand-sweep`` (ISSUE 39): the configuration is
``taxi-rides-grid.json`` at the scale and layout of ``taxi-rides-x4``,
nothing of the schema cut; ``brand-sweep.json`` is ``brand-lookup.json``
over all 25 categories; the seven per-layer metrics are data files of
readers the harness has, and return nothing (and raise nothing) against
a program that lacks their series or their kernel, as the parent does on
a mesh; and the four-chip cell rehearsed (``--rehearse``: 8 shards on
four virtual CPU devices) is ``correct``, prints the seven when traced,
and comes out not correct under ``--control sampled``. The two
rehearsals are the ones every file of this directory shares.
"""

import collections
import json
import os
import sys

import pytest

from bench_helpers import (BENCH, CELLS, MANIFEST, ROOT, last_line,
                           load_config, load_mix, rehearsals)
from harness import datagen, readers, trace, traffic
from xplane_writer import xspace

MS = 1_000_000
X4, GRID = "taxi-rides-grid-x4.cell-lookup", "taxi-rides-grid.cell-lookup"
SWEEP = "ssb-lineorder.brand-sweep"
BOTH, MESH = [GRID, X4], [X4]
# name: (unit, better, source, layer, cells)
NEW = {
    "expand_rows_share": ("%", "lower", "device_trace", "device", BOTH),
    "residency_sparse_miss_share": ("%", "higher", "program_counter",
                                    "residency", BOTH),
    "residency_miss_transfer_kib": ("KiB/miss", "lower", "program_counter",
                                    "residency", BOTH),
    "mesh_residency_miss_ms": ("ms/miss", "lower", "program_span",
                               "residency", MESH),
    "mesh_residency_decode_ms": ("ms/miss", "lower", "program_span",
                                 "residency", MESH),
    "mesh_residency_upload_ms": ("ms/miss", "lower", "program_span",
                                 "residency", MESH),
    "mesh_residency_misses_per_read": ("misses/read", "lower",
                                       "program_counter", "residency", MESH),
}
ROW_LEAF = 512 * (datagen.SHARD_WIDTH // 8)  # 512 shard slots: 64 MiB


def spec_of(name: str) -> dict:
    with open(os.path.join(BENCH, "layer_metrics", name + ".json")) as f:
        return json.load(f)


# ------------------------------------------------- the files, as ISSUE 39


def test_configuration_is_the_grid_schema_at_the_x4_scale():
    config, base = load_config("taxi-rides-grid-x4"), load_config(
        "taxi-rides-grid")
    x4 = load_config("taxi-rides-x4")
    differ = {k for k in set(config) | set(base)
              if config.get(k) != base.get(k)}
    assert differ == {"name", "source", "deployment", "chips", "shards",
                      "rehearse_shards", "reduced_why", "assumed"}
    assert list(config) == list(base)  # the same keys in the same order
    assert (config["name"], config["index"], config["chips"], config["shards"],
            config["rehearse_shards"]) == ("taxi-rides-grid-x4", "rides", 4,
                                           512, 8)
    assert (config["chips"], config["shards"], config["rehearse_shards"]) == (
        x4["chips"], x4["shards"], x4["rehearse_shards"])
    assert config["server_knobs"] == {} and len(config["fields"]) == 10
    assert config["reduced"] == ["shards"] == list(config["reduced_why"])
    assert config["assumed"][:-1] == base["assumed"]
    assert config["assumed"][-1] == x4["assumed"][-1]
    assert "flat 1-D mesh of four chips, no knob set" in (
        config["assumed"][-1])
    entry = {c["name"]: c for c in MANIFEST["configs"]}["taxi-rides-grid-x4"]
    assert entry["source"] == config["source"] and len(entry["source"]) < 200
    assert entry["reduced"] == ["shards"]
    assert entry["file"] == "benchmarks/configs/taxi-rides-grid-x4.json"
    assert entry["source"] not in {c["source"] for c in MANIFEST["configs"]
                                   if c is not entry}
    for words in ("Pilosa's transportation example", "100 x 100",
                  "GridMapper", "2^29 rides", "four-chip host's half",
                  "v5e-8"):
        assert words in config["source"], words
    assert "ONE index sharded over" in config["deployment"]


def test_the_two_cells_are_appended_and_say_what_they_do():
    x4, sweep = CELLS[X4], CELLS[SWEEP]
    assert (x4["config"], x4["traffic"], x4["chips"]) == (
        "taxi-rides-grid-x4", "cell-lookup", 4)
    assert (sweep["config"], sweep["traffic"], sweep["chips"]) == (
        "ssb-lineorder", "brand-sweep", 1)
    for words in ("8 closed-loop clients", "mesh", "residency miss"):
        assert words in x4["why"], words
    for words in ("8 closed-loop clients", "25 categories"):
        assert words in sweep["why"], words
    assert all(len(c["why"]) <= 200 for c in (x4, sweep))
    # membership and order, not the tail: later PRs append after them
    names = list(CELLS)
    assert names.index("taxi-rides.groupby-scan") < names.index(X4) < (
        names.index(SWEEP))
    configs = [c["name"] for c in MANIFEST["configs"]]
    assert configs.index("taxi-rides-grid") < configs.index(
        "taxi-rides-grid-x4")
    # at most half the cells, rounded down, may take four chips
    four = [n for n, c in CELLS.items() if c["chips"] == 4]
    assert X4 in four and len(four) <= len(CELLS) // 2


def test_the_cold_set_is_four_times_a_chips_budget_twenty_times_over():
    """2 x 2,048 cold rows of one 512-slot leaf each: 256 GiB, 64 GiB a
    chip, against ~11.8 GiB a chip; the hot leaves (24 hours, 18 planes,
    8 years, 16 core cells) take 1,056 MiB a chip as on one chip."""
    assert 2 * 2048 * ROW_LEAF == 256 << 30
    assert (24 + 18 + 8 + 16) * ROW_LEAF // 4 == 1056 << 20
    mix, config = load_mix("cell-lookup"), load_config("taxi-rides-grid-x4")
    assert mix["preload"] is False
    assert traffic.fields_read(mix, config) == [
        "pickup_year", "pickup_hour", "total_amount_cents", "pickup_grid_id",
        "drop_grid_id"]


def test_brand_sweep_is_brand_lookup_over_every_category():
    with open(os.path.join(BENCH, "traffic", "brand-sweep.json")) as f:
        sweep = json.load(f)
    with open(os.path.join(BENCH, "traffic", "brand-lookup.json")) as f:
        lookup = json.load(f)
    assert sweep["name"] == "brand-sweep" and sweep["why"] != lookup["why"]
    assert "25 categories" in sweep["why"]
    for t in lookup["templates"].values():
        assert t["draw"]["C"] == {"row_of": "p_category", "top": 5}
        t["draw"]["C"] = {"row_of": "p_category"}
    lookup["name"], lookup["why"] = sweep["name"], sweep["why"]
    assert sweep == lookup
    assert list(sweep["templates"]) == ["q2_1", "q2_2", "q2_3"]


@pytest.mark.parametrize("seed", [3, 2_147_483_659, 4_111_222_333])
def test_every_seed_sweeps_all_25_categories(seed):
    config, mix = load_config("ssb-lineorder"), load_mix("brand-sweep")
    offsets = {"q2_1": 0, "q2_2": 20, "q2_3": 38}
    seen = collections.defaultdict(set)
    for client in traffic.clients(mix, config, config["shards"], seed, "w"):
        for _ in range(400):
            name, _pql, sem = client.next()
            category, rem = divmod(
                sem["dims"][1]["previous"] + 1 - offsets[name], 40)
            assert rem == 0
            seen[name].add(category)
            seen["region"].add(dict(sem["filter"])["s_region"])
    for name in offsets:
        assert seen[name] == set(range(25)), name
    assert seen["region"] == set(range(5))
    # by the shapes, at 64 slots and with the executor's zero rows
    # (40 -> 41, 8 -> 9, 1 -> 3): 25 x 53 matrix rows, 7 years, 26
    # planes, 25 + 5 filter rows
    rows = 25 * (41 + 9 + 3) + 7 + 26 + (25 + 5)
    assert rows * 64 * (datagen.SHARD_WIDTH // 8) == 11_104 << 20


# ------------------------------------------------- the seven metric files


@pytest.mark.parametrize("name", sorted(NEW))
def test_metric_entry_and_file(name):
    entry = {m["name"]: m for m in MANIFEST["per_layer"]}[name]
    unit, better, source, layer, cells = NEW[name]
    assert entry == {"name": name, "unit": unit, "better": better,
                     "source": source, "layer": layer, "moves": "throughput",
                     "workloads": cells}
    spec = spec_of(name)
    assert spec["reader"] == ("trace_ops" if name == "expand_rows_share"
                              else "ratio") and spec["what"]
    # nothing to read (no scrape, no trace): nothing returned, none raised
    assert readers.read(BENCH, name, {}, {}, None, {}) is None


def test_the_seven_are_appended_in_the_issues_order():
    names = [m["name"] for m in MANIFEST["per_layer"]]
    # after everything the benchmark had (PR 36's last), in the issue's
    # order; what later PRs append may follow
    assert [n for n in names if n in NEW] == list(NEW)
    assert names.index("wal_commit_ms") < names.index("expand_rows_share")
    # the one-chip namesakes keep their lists
    for m in MANIFEST["per_layer"]:
        if "mesh_" + m["name"] in NEW:
            assert m["workloads"] == [GRID]


def test_mesh_twins_read_what_their_namesakes_read():
    for twin in ("miss_ms", "decode_ms", "upload_ms", "misses_per_read"):
        a, b = spec_of("mesh_residency_" + twin), spec_of("residency_" + twin)
        assert {k: v for k, v in a.items() if k != "what"} == {
            k: v for k, v in b.items() if k != "what"}


def test_ratio_metrics_read_a_made_up_scrape():
    misses = "pilosa_tpu_residency_misses_total"
    sparse = "pilosa_tpu_residency_sparse_misses_total"
    sent = "pilosa_tpu_residency_miss_transfer_bytes_total"
    stage = "pilosa_tpu_stage_residency_{}_{}".format
    before = {misses: 100.0, sparse: 40.0, sent: 1e6, "gen.reads": 0.0}
    after = {misses: 900.0, sparse: 840.0, sent: 1e6 + 800 * 4 * 332_800,
             "gen.reads": 1300.0}
    for s, seconds in (("miss", 9.6), ("decode", 7.2), ("upload", 1.6)):
        before[stage(s, "total")] = before[stage(s, "seconds_total")] = 0.0
        after[stage(s, "total")] = 800.0
        after[stage(s, "seconds_total")] = seconds

    def read(name, b=before, a=after):
        return readers.read(BENCH, name, b, a, None, {})

    assert read("residency_sparse_miss_share") == pytest.approx(100.0)
    assert read("residency_miss_transfer_kib") == pytest.approx(1300.0)
    assert read("mesh_residency_miss_ms") == pytest.approx(12.0)
    assert read("mesh_residency_decode_ms") == pytest.approx(9.0)
    assert read("mesh_residency_upload_ms") == pytest.approx(2.0)
    assert read("mesh_residency_misses_per_read") == pytest.approx(8 / 13)
    # the parent on a mesh: every miss dense, 64 MiB handed over each
    dense = dict(after, **{sparse: 40.0, sent: 1e6 + 800 * ROW_LEAF})
    assert read("residency_sparse_miss_share", before, dense) == 0.0
    assert read("residency_miss_transfer_kib", before, dense) == 65_536.0
    # a program before PR 38 exports neither series; a window without a
    # miss has no denominator: left out both ways
    old = {k: v for k, v in after.items() if k not in (sparse, sent)}
    assert read("residency_sparse_miss_share", before, old) is None
    assert read("residency_miss_transfer_kib", before, old) is None
    assert read("residency_sparse_miss_share", after, after) is None


def test_the_program_exports_the_series_the_ratios_name():
    sys.path.insert(0, ROOT)
    from pilosa_tpu.storage.residency import DeviceRowCache
    from pilosa_tpu.utils.tracing import STAGES

    cache = DeviceRowCache()
    exported = {"pilosa_tpu_" + (f"{k}_total"
                                 if k in cache._MONOTONIC_METRICS else k)
                for k in cache.metrics()}
    exported |= {f"pilosa_tpu_stage_{s.replace('.', '_')}{suffix}"
                 for s in STAGES for suffix in ("_total", "_seconds_total")}
    exported |= {"gen.reads"}
    for name in NEW:
        spec = spec_of(name)
        if spec["reader"] == "ratio":
            assert set(spec["numerator"] + spec["denominator"]) <= exported


def test_expand_rows_share_finds_the_kernel_on_four_device_planes(tmp_path):
    """The expansion as a chip's trace names it (a custom call
    ``expand_rows`` inside ``jit_dist_expand_rows``), on every chip of
    four, beside a Count's fusion; a trace without it (the parent's on a
    mesh) reads 0 and raises nothing."""
    expand = ('%expand_rows.1 = u32[128,32768]{1,0:T(8,128)} custom-call('
              's32[4224]{0} %p, s32[65536]{0} %q), '
              'custom_call_target="tpu_custom_call"')
    fusion = "%fusion.36 = s32[128]{0} fusion(u32[128,32768] %a)"
    with_it = [(f"/device:TPU:{chip}", [("XLA Ops", [
        (expand, 0, 1 * MS), (fusion, 2 * MS, 4 * MS),
        (expand, 50 * MS, 2 * MS)])]) for chip in range(4)]
    without = [(name, [(line, [e for e in events if e[0] != expand])
                       for line, events in lines])
               for name, lines in with_it]
    assert spec_of("expand_rows_share")["pattern"] == "expand_rows"
    for planes, want in ((with_it, 3.0), (without, 0.0)):
        path = tmp_path / f"x4-{want}.xplane.pb"
        path.write_bytes(xspace(planes))
        reduced = trace.reduce(str(path), 0.1)
        assert reduced["devices"] == 4
        assert readers.read(BENCH, "expand_rows_share", {}, {}, reduced,
                            {}) == pytest.approx(want)


# ------------------------------------------------------- the cell rehearsed


@pytest.fixture(scope="module")
def both(tmp_path_factory):
    """The cell's two rehearsals, which every file of this directory
    shares (``bench_helpers.rehearsals``): run here or read from the
    worker that ran them."""
    return rehearsals(tmp_path_factory, [(X4, 0), (X4, 1)])


@pytest.fixture(scope="module")
def untraced(both):
    """The end-to-end run, with the control compared after it."""
    return both[X4, 0]


@pytest.fixture(scope="module")
def traced(both):
    return both[X4, 1]


def test_rehearsal_on_four_virtual_devices_is_correct(untraced):
    assert untraced.returncode == 0, (untraced.stderr[-3000:]
                                      + untraced.stdout[-2000:])
    line = last_line(untraced.stdout)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert line["device"] == {"platform": "cpu", "kind": "cpu", "count": 4,
                              "memory_peak_bytes": None}
    assert set(line["metrics"]) == {"throughput", "read_p50_ms",
                                    "read_p95_ms", "setup_s"}
    checks = [l for l in untraced.stdout.splitlines()
              if l.startswith("check answers.")]
    assert [l.split()[1] for l in checks] == [
        "answers.core_cell_year:", "answers.dropoff_cell_revenue:",
        "answers.dropoff_cell_year:", "answers.pickup_cell_by_hour:"]
    assert all(" wrong=0 limit=0" in l for l in checks)


def test_sampled_control_comes_out_not_correct(untraced):
    assert "control[sampled]: correct=False" in untraced.stdout
    wrong = [l for l in untraced.stdout.splitlines()
             if l.startswith("control[sampled] ") and " wrong=0 " not in l]
    assert len(wrong) == 4  # half the shards, doubled, miss every template


def test_traced_rehearsal_prints_the_seven(traced):
    assert traced.returncode == 0, (traced.stderr[-3000:]
                                    + traced.stdout[-2000:])
    line = last_line(traced.stdout)
    assert line["correct"] is True and line["failed"] == 0
    metrics = line["metrics"]
    for name, (unit, *_rest) in NEW.items():
        assert metrics[name]["unit"] == unit, name
    # every miss of the window is a grid row of array containers: placed
    # from its set bits, four lists of 2 slot rows a chip padded to the
    # smallest bucket (4 x (128 + 8,192) words = 130 KiB), not the 1 MiB
    # of an 8-slot leaf
    assert metrics["residency_sparse_miss_share"]["value"] == 100.0
    assert metrics["residency_miss_transfer_kib"]["value"] == 130.0
    # Pallas' interpreter leaves no operation of the kernel's name on the
    # CPU: the share is read, and reads nothing
    assert metrics["expand_rows_share"]["value"] == 0.0
    parts = (metrics["mesh_residency_decode_ms"]["value"]
             + metrics["mesh_residency_upload_ms"]["value"])
    assert 0 < parts <= metrics["mesh_residency_miss_ms"]["value"]
    assert 0 < metrics["mesh_residency_misses_per_read"]["value"] < 0.75
    listed = {m["name"] for m in MANIFEST["per_layer"]
              if X4 in m.get("workloads", [X4])}
    assert set(NEW) <= set(metrics) <= listed
    assert "collective_share" not in metrics  # that list is the x4 dashboard's


def test_the_sweep_rehearsed_is_correct_on_the_three_queries(tmp_path_factory):
    """``ssb-lineorder.brand-sweep``, the other cell of ISSUE 39, from the
    rehearsals every file of this directory shares."""
    p = rehearsals(tmp_path_factory, [(SWEEP, 0)])[SWEEP, 0]
    assert p.returncode == 0, p.stderr[-3000:] + p.stdout[-2000:]
    line = last_line(p.stdout)
    assert line["correct"] is True and line["failed"] == 0
    checks = [l for l in p.stdout.splitlines()
              if l.startswith("check answers.")]
    assert [l.split()[1] for l in checks] == [
        "answers.q2_1:", "answers.q2_2:", "answers.q2_3:"]
    assert all(" wrong=0 limit=0" in l for l in checks)
    assert "control[sampled]: correct=False" in p.stdout
