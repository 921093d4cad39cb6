"""The cells ``taxi-rides-grid.cell-lookup`` and ``taxi-rides.groupby-scan``
(ISSUE 34): the configuration and the two mixes are ISSUE 34's, letter for
letter; the five per-layer metrics the grid cell brings are data files of
the ``ratio`` reader over series the program exports, and return nothing
(and raise nothing) against a program that lacks them, as the parent does;
and the grid cell rehearsed (``--rehearse``: 2 shards on the CPU) is
``correct``, prints its end-to-end line, prints the five metrics when
traced, and comes out not correct under ``--control sampled``. One
rehearsal a kind, shared by the assertions. ``groupby-scan`` is rehearsed
by ``test_bench_rehearse.py`` and ``test_bench_stage_metrics.py``, which
take every cell of the manifest."""

import collections
import json
import os
import sys

import numpy as np
import pytest

from bench_helpers import (BENCH, CELLS, MANIFEST, ROOT, last_line,
                           load_config, load_mix, rehearsals)
from harness import datagen, readers, traffic
from harness.reference import Reference

GRID, SCAN = "taxi-rides-grid.cell-lookup", "taxi-rides.groupby-scan"
NEW = {"residency_miss_ms": ("ms/miss", "lower", "program_span"),
       "residency_decode_ms": ("ms/miss", "lower", "program_span"),
       "residency_upload_ms": ("ms/miss", "lower", "program_span"),
       "residency_misses_per_read": ("misses/read", "lower",
                                     "program_counter"),
       "residency_upload_mib_s": ("MiB/s", "higher", "program_counter")}
GRID_FIELD = {"type": "set",
              "geometric": {"rows": 10000, "first": 0.002, "ratio": 0.998}}
ROW_LEAF = 128 * (datagen.SHARD_WIDTH // 8)  # 128 shard slots: 16 MiB


def spec_of(name: str) -> dict:
    with open(os.path.join(BENCH, "layer_metrics", name + ".json")) as f:
        return json.load(f)


def stage_series(stage: str, suffix: str) -> str:
    return f"pilosa_tpu_stage_residency_{stage}_{suffix}"


# ------------------------------------------------- the files, as ISSUE 34


def test_configuration_is_taxi_rides_with_the_two_grid_fields():
    config, base = load_config("taxi-rides-grid"), load_config("taxi-rides")
    entry = {c["name"]: c for c in MANIFEST["configs"]}["taxi-rides-grid"]
    assert entry["reduced"] == ["shards"] == config["reduced"]
    assert entry["source"] == config["source"] != base["source"]
    for words in ("Pilosa's transportation example", "100 x 100",
                  "GridMapper"):
        assert words in config["source"], words
    assert (config["index"], config["chips"], config["shards"],
            config["rehearse_shards"]) == ("rides", 1, 128, 2)
    assert config["server_knobs"] == {}
    assert config["guarantees"] == base["guarantees"]
    assert list(config["fields"]) == list(base["fields"]) + [
        "pickup_grid_id", "drop_grid_id"]
    for name, spec in base["fields"].items():
        assert config["fields"][name] == spec, name  # frequencies unchanged
    assert config["fields"]["pickup_grid_id"] == GRID_FIELD
    assert config["fields"]["drop_grid_id"] == GRID_FIELD
    assert "its eighth of 1.1 B rides on a v5e-8" in config["deployment"]
    assumed = " ".join(config["assumed"])
    for words in ("100 x 100", "10,000 row ids", "not cut", "not fitted",
                  "independently of each other and of every other field"):
        assert words in assumed, words


def test_grid_popularity_is_what_the_file_assumes():
    """The 16 busiest cells hold 3.2 % of rides, the 2,048 busiest 98.3 %;
    the generator's 16-bit table leaves about 2,900 cells non-empty, and
    the thinnest of the 2,048 still holds about 32 rides a shard."""
    w = datagen.field_weights(GRID_FIELD)
    assert len(w) == 10_000 and w.argmax() == 0
    assert round(100 * w[:16].sum(), 1) == 3.2
    assert round(100 * w[:2048].sum(), 1) == 98.3
    cols = datagen.make_columns(load_config("taxi-rides-grid"), 3_400_000_007,
                                2, ["pickup_grid_id", "drop_grid_id"])
    for name in ("pickup_grid_id", "drop_grid_id"):
        counts = np.bincount(cols[name], minlength=10_000)
        assert 2_700 < (counts > 0).sum() < 3_100
        assert counts[:2048].min() > 0  # every drawn row holds a ride
        assert 16 <= counts[2047] / 2 <= 64
    assert (cols["pickup_grid_id"] != cols["drop_grid_id"]).mean() > 0.99


def test_both_cells_are_one_chip_and_say_what_they_do():
    grid, scan = CELLS[GRID], CELLS[SCAN]
    assert (grid["config"], grid["traffic"], grid["chips"]) == (
        "taxi-rides-grid", "cell-lookup", 1)
    assert (scan["config"], scan["traffic"], scan["chips"]) == (
        "taxi-rides", "groupby-scan", 1)
    for words in ("8 closed-loop clients", "4,096 cold 16 MiB grid rows",
                  "residency miss", "eviction"):
        assert words in grid["why"], words
    for words in ("2 closed-loop clients", "640", "low concurrency"):
        assert words in scan["why"], words
    # added after the four cells the benchmark had, which keep their
    # places; the two cells' own places are pinned, not the count: later
    # PRs append cells and configurations after them
    assert list(CELLS)[:6] == [
        "taxi-rides.dashboard", "taxi-rides.point-rw",
        "taxi-rides-x4.dashboard", "ssb-lineorder.brand-lookup", GRID, SCAN]
    assert [c["name"] for c in MANIFEST["configs"]][:4] == [
        "taxi-rides", "taxi-rides-x4", "ssb-lineorder", "taxi-rides-grid"]


def test_cell_lookup_is_the_rotation_of_four_map_clicks():
    config, mix = load_config("taxi-rides-grid"), load_mix("cell-lookup")
    assert mix["preload"] is False
    (group,) = mix["groups"]
    assert (group["clients"], group["loop"]) == (8, "closed")
    assert group["rotation"] == ["core_cell_year", "pickup_cell_by_hour",
                                 "dropoff_cell_revenue", "dropoff_cell_year"]
    year = {"row_of": "pickup_year"}
    assert mix["templates"] == {
        "core_cell_year": {
            "kind": "count",
            "filter": [["pickup_grid_id", "G"], ["pickup_year", "Y"]],
            "draw": {"G": {"row_of": "pickup_grid_id", "top": 16}, "Y": year}},
        "pickup_cell_by_hour": {
            "kind": "groupby", "dims": [{"field": "pickup_hour"}],
            "filter": [["pickup_grid_id", "G"]],
            "draw": {"G": {"row_of": "pickup_grid_id", "top": 2048}}},
        "dropoff_cell_revenue": {
            "kind": "sum", "sum": "total_amount_cents",
            "filter": [["drop_grid_id", "H"]],
            "draw": {"H": {"row_of": "drop_grid_id", "top": 2048}}},
        "dropoff_cell_year": {
            "kind": "count",
            "filter": [["drop_grid_id", "H"], ["pickup_year", "Y"]],
            "draw": {"H": {"row_of": "drop_grid_id", "top": 2048}, "Y": year}}}
    assert traffic.fields_read(mix, config) == [
        "pickup_year", "pickup_hour", "total_amount_cents", "pickup_grid_id",
        "drop_grid_id"]


def test_groupby_scan_is_the_published_group_bys_from_two_clients():
    config, mix = load_config("taxi-rides"), load_mix("groupby-scan")
    assert mix["preload"] is True
    (group,) = mix["groups"]
    assert (group["clients"], group["loop"]) == (2, "closed")
    assert group["rotation"] == ["passengers_by_year", "passengers_by_year_sum",
                                 "passengers_by_dist_in_year"]
    by_year = [{"field": "passenger_count"}, {"field": "pickup_year"}]
    assert mix["templates"] == {
        "passengers_by_year": {"kind": "groupby", "dims": by_year},
        "passengers_by_year_sum": {"kind": "groupby", "dims": by_year,
                                   "sum": "total_amount_cents"},
        "passengers_by_dist_in_year": {
            "kind": "groupby",
            "dims": [{"field": "passenger_count"}, {"field": "dist_miles"}],
            "filter": [["pickup_year", "Y"]],
            "draw": {"Y": {"row_of": "pickup_year"}}}}
    rows = {f: datagen.field_rows(config["fields"][f])
            for f in ("passenger_count", "pickup_year", "dist_miles")}
    assert rows["passenger_count"] * rows["pickup_year"] == 80
    assert rows["passenger_count"] * rows["dist_miles"] == 640
    client = traffic.Client(mix, config, 128, group, 0, 3_400_000_009, "w")
    texts = [client.next()[1] for _ in range(3)]
    assert texts[0] == "GroupBy(Rows(passenger_count), Rows(pickup_year))"
    assert texts[1] == ("GroupBy(Rows(passenger_count), Rows(pickup_year), "
                        "aggregate=Sum(field=\"total_amount_cents\"))")
    assert texts[2].startswith("GroupBy(Rows(passenger_count), "
                               "Rows(dist_miles), filter=Row(pickup_year=")


@pytest.mark.parametrize("seed", [3, 2_147_483_659, 4_111_222_333])
def test_every_seed_clicks_cold_cells_three_times_of_four(seed):
    """Whatever the seed: the core template stays among the 16 busiest
    pickup cells, the three others range over the 2,048 busiest cells of
    one grid field each, and no request names both grid fields (the
    reference tabulates a request's fields jointly)."""
    config, mix = load_config("taxi-rides-grid"), load_mix("cell-lookup")
    seen = collections.defaultdict(set)
    for client in traffic.clients(mix, config, config["shards"], seed, "w"):
        for _ in range(400):
            name, pql, sem = client.next()
            terms = dict(sem["filter"])
            assert len({"pickup_grid_id", "drop_grid_id"} & set(terms)) == 1
            (cell,) = [r for f, r in terms.items() if f.endswith("_grid_id")]
            seen[name].add(cell)
            if "pickup_year" in terms:
                seen["year"].add(terms["pickup_year"])
    assert seen["core_cell_year"] == set(range(16))
    assert seen["year"] == set(range(8))
    for name in ("pickup_cell_by_hour", "dropoff_cell_revenue",
                 "dropoff_cell_year"):
        assert len(seen[name]) > 600 and max(seen[name]) > 1900
        assert seen[name] <= set(range(2048))
    # by the shapes: 2 x 2,048 cold rows of one 128-slot leaf each against
    # the hot leaves (24 hours, 18 planes, 8 years, 16 core cells)
    assert 2 * 2048 * ROW_LEAF == 64 << 30
    assert (24 + 18 + 8 + 16) * ROW_LEAF == 1056 << 20


def test_reference_answers_the_four_templates_as_masks_do():
    """The joint tables (24 x 10,000 at most) against plain masks over
    the columns, at the rehearsal's 2 shards."""
    config, mix = load_config("taxi-rides-grid"), load_mix("cell-lookup")
    cols = datagen.make_columns(config, 3_400_000_011, 2,
                                traffic.fields_read(mix, config))
    ref = Reference(config, cols)
    (group,) = mix["groups"]
    client = traffic.Client(mix, config, 2, group, 0, 3_400_000_011, "ref")
    for _ in range(8):
        name, _pql, sem = client.next()
        mask = True
        for f, r in sem["filter"]:
            mask = mask & (cols[f] == r)
        if sem["kind"] == "count":
            want = int(mask.sum())
        elif sem["kind"] == "sum":
            want = {"value": int(cols["total_amount_cents"][mask].sum()),
                    "count": int(mask.sum())}
        else:
            counts = np.bincount(cols["pickup_hour"][mask], minlength=24)
            want = [{"group": [{"field": "pickup_hour", "rowID": h}],
                     "count": int(n)} for h, n in enumerate(counts) if n]
        assert ref.answer(sem) == want, name


# -------------------------------------------------- the five metric files


@pytest.mark.parametrize("name", sorted(NEW))
def test_metric_entry_lists_the_grid_cell_alone(name):
    entry = {m["name"]: m for m in MANIFEST["per_layer"]}[name]
    unit, better, source = NEW[name]
    assert entry == {"name": name, "unit": unit, "better": better,
                     "source": source, "layer": "residency",
                     "moves": "throughput", "workloads": [GRID]}
    spec = spec_of(name)
    assert spec["reader"] == "ratio" and spec["what"]
    # nothing to read (the parent's /metrics): nothing returned, none raised
    assert readers.read(BENCH, name, {}, {}, None, {}) is None


def test_the_five_are_appended_and_the_groupby_lists_stand():
    names = [m["name"] for m in MANIFEST["per_layer"]]
    # membership and order, not the tail: the five stand together, in
    # ISSUE 34's order, after PR 32's last; later PRs append after them
    at = names.index("residency_miss_ms")
    assert names[at:at + 5] == list(NEW) == [
        "residency_miss_ms", "residency_decode_ms", "residency_upload_ms",
        "residency_misses_per_read", "residency_upload_mib_s"]
    assert names[at - 1] == "candidates_per_level"
    # the metrics the benchmark had before them keep their lists (the
    # three GroupBy metrics' among them); one appended later may name
    # either cell
    for m in MANIFEST["per_layer"][:at]:
        assert GRID not in m.get("workloads", [])
        assert SCAN not in m.get("workloads", [])


def test_ratio_metrics_read_the_miss_path():
    for stage in ("miss", "decode", "upload"):
        assert spec_of(f"residency_{stage}_ms") == {
            "reader": "ratio", "scale": 1000,
            "numerator": [stage_series(stage, "seconds_total")],
            "denominator": [stage_series(stage, "total")],
            "what": spec_of(f"residency_{stage}_ms")["what"]}
    assert spec_of("residency_misses_per_read")["numerator"] == [
        "pilosa_tpu_residency_misses_total"]
    assert spec_of("residency_misses_per_read")["denominator"] == ["gen.reads"]
    assert spec_of("residency_upload_mib_s")["numerator"] == [
        "pilosa_tpu_residency_miss_bytes_total"]
    assert spec_of("residency_upload_mib_s")["denominator"] == [
        "gen.window_seconds"]
    assert spec_of("residency_upload_mib_s")["scale"] == 2 ** -20
    # a window of 1,200 reads and 800 misses of one row leaf: 38 ms each,
    # 30 of it decode and 7 upload
    before = {stage_series(s, k): 0.0 for s in ("miss", "decode", "upload")
              for k in ("total", "seconds_total")}
    before.update({"pilosa_tpu_residency_misses_total": 100.0,
                   "pilosa_tpu_residency_miss_bytes_total": 0.0,
                   "gen.reads": 0.0, "gen.window_seconds": 0.0})
    after = {stage_series("miss", "total"): 800.0,
             stage_series("miss", "seconds_total"): 30.4,
             stage_series("decode", "total"): 800.0,
             stage_series("decode", "seconds_total"): 24.0,
             stage_series("upload", "total"): 800.0,
             stage_series("upload", "seconds_total"): 5.6,
             "pilosa_tpu_residency_misses_total": 900.0,
             "pilosa_tpu_residency_miss_bytes_total": 800.0 * ROW_LEAF,
             "gen.reads": 1200.0, "gen.window_seconds": 30.0}

    def read(name, b=before, a=after):
        return readers.read(BENCH, name, b, a, None, {})

    assert read("residency_miss_ms") == pytest.approx(38.0)
    assert read("residency_decode_ms") == pytest.approx(30.0)
    assert read("residency_upload_ms") == pytest.approx(7.0)
    assert read("residency_misses_per_read") == pytest.approx(2 / 3)
    assert read("residency_upload_mib_s") == pytest.approx(800 * 16 / 30)
    # the parent: residency.miss and the miss counter, nothing newer
    old = {k: v for k, v in after.items()
           if "decode" not in k and "upload" not in k and "bytes" not in k}
    assert read("residency_miss_ms", before, old) == pytest.approx(38.0)
    assert read("residency_misses_per_read", before, old) \
        == pytest.approx(2 / 3)
    for name in ("residency_decode_ms", "residency_upload_ms",
                 "residency_upload_mib_s"):
        assert read(name, before, old) is None
    # a window without a miss: no denominator, left out
    assert read("residency_miss_ms", after, after) is None


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
    exported |= {"gen.reads", "gen.window_seconds"}
    for name in NEW:
        spec = spec_of(name)
        assert set(spec["numerator"] + spec["denominator"]) <= exported, name


# ------------------------------------------------------- the cell rehearsed


@pytest.fixture(scope="module")
def both(tmp_path_factory):
    """The cell's two rehearsals, which every file of this directory
    shares (``bench_helpers.rehearsals``): run here or read from the
    worker that ran them."""
    return rehearsals(tmp_path_factory, [(GRID, 0), (GRID, 1)])


@pytest.fixture(scope="module")
def untraced(both):
    """The end-to-end run, with the control compared after it."""
    return both[GRID, 0]


@pytest.fixture(scope="module")
def traced(both):
    return both[GRID, 1]


def test_rehearsal_is_correct_on_the_four_queries(untraced):
    assert untraced.returncode == 0, (untraced.stderr[-3000:]
                                      + untraced.stdout[-2000:])
    line = last_line(untraced.stdout)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    checks = [l for l in untraced.stdout.splitlines()
              if l.startswith("check answers.")]
    assert [l.split()[1] for l in checks] == [
        "answers.core_cell_year:", "answers.dropoff_cell_revenue:",
        "answers.dropoff_cell_year:", "answers.pickup_cell_by_hour:"]
    assert all(" wrong=0 limit=0" in l for l in checks)


def test_rehearsal_prints_the_end_to_end_line(untraced):
    line = last_line(untraced.stdout)
    assert set(line) == {"correct", "attempted", "failed", "metrics", "device"}
    assert set(line["metrics"]) == {"throughput", "read_p50_ms",
                                    "read_p95_ms", "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert line["device"]["platform"] == "cpu"  # never a device number


def test_sampled_control_comes_out_not_correct(untraced):
    assert "control[sampled]: correct=False" in untraced.stdout
    wrong = [l for l in untraced.stdout.splitlines()
             if l.startswith("control[sampled] ") and " wrong=0 " not in l]
    assert len(wrong) == 4  # half the shards, doubled, miss every template


def test_traced_rehearsal_prints_the_five_new_metrics(traced):
    assert traced.returncode == 0, traced.stderr[-3000:] + traced.stdout[-2000:]
    line = last_line(traced.stdout)
    assert line["correct"] is True and line["failed"] == 0
    metrics = line["metrics"]
    for name, (unit, _, _) in NEW.items():
        assert metrics[name]["unit"] == unit
        assert metrics[name]["value"] > 0
    # a miss is its decode, its upload and a little bookkeeping
    parts = (metrics["residency_decode_ms"]["value"]
             + metrics["residency_upload_ms"]["value"])
    assert 0.5 * metrics["residency_miss_ms"]["value"] < parts
    assert parts <= metrics["residency_miss_ms"]["value"]
    # three of four requests name one of 4,096 rows: at 2 shards every
    # row fits the CPU's 4 GiB and only first touches miss
    assert 0 < metrics["residency_misses_per_read"]["value"] < 0.75
    assert 0 < metrics["residency_hit_share"]["value"] < 100
    assert metrics["residency_evictions_in_window"]["value"] == 0.0
    listed = {m["name"] for m in MANIFEST["per_layer"]
              if GRID in m.get("workloads", [GRID])}
    assert set(metrics) == listed
