"""The cell ``ssb-lineorder.brand-lookup`` (ISSUE 32): the configuration
and the mix are ISSUE 32's, letter for letter; the three per-layer
metrics it brings are data files of readers that exist and read what they
say, with or without the program's new counter; and the cell rehearsed
(``--rehearse``: 2 shards on the CPU) is ``correct``, prints its
end-to-end line, prints the three metrics when traced, and comes out not
correct under ``--control sampled``. One rehearsal a kind, shared by the
assertions: one costs over a minute on the CPU."""

import collections
import json
import os
import subprocess
import sys

import pytest

from bench_helpers import (BENCH, CELLS, MANIFEST, ROOT, last_line,
                           load_config, load_mix, rehearsals)
from harness import datagen, readers, trace, traffic
from xplane_writer import xspace

CELL = "ssb-lineorder.brand-lookup"
DASHBOARDS = ["taxi-rides.dashboard", "taxi-rides-x4.dashboard"]
NEW = ("groupby_level_share", "level_programs_per_level",
       "candidates_per_level")
# template: (brands of its range, offset of the range in its category)
RANGES = {"q2_1": (40, 0), "q2_2": (8, 20), "q2_3": (1, 38)}
MS = 1_000_000  # ns


def spec_of(name: str) -> dict:
    with open(os.path.join(BENCH, "layer_metrics", name + ".json")) as f:
        return json.load(f)


# ------------------------------------------------- the files, as ISSUE 32


def test_configuration_is_ssb_lineorder_at_sf_10():
    config = load_config("ssb-lineorder")
    entry = {c["name"]: c for c in MANIFEST["configs"]}["ssb-lineorder"]
    assert entry["reduced"] == ["columns"] == config["reduced"]
    assert "SF 10 (59,986,052 rows)" in config["source"]
    assert (config["index"], config["chips"], config["shards"],
            config["rehearse_shards"]) == ("lineorder", 1, 58, 2)
    # the source's scale, whole: 58 shards are the first count that holds it
    assert 57 * datagen.SHARD_WIDTH < 59_986_052 <= 58 * datagen.SHARD_WIDTH
    assert config["server_knobs"] == {}
    assert config["guarantees"] == load_config("taxi-rides")["guarantees"]
    assert config["fields"] == {
        "p_brand1": {"type": "set", "uniform": 1000},
        "p_category": {"type": "set", "rows": 25,
                       "derived": {"field": "p_brand1", "div": 40}},
        "s_region": {"type": "set", "uniform": 5},
        "d_year": {"type": "set", "uniform": 7, "labels_from": 1992},
        "lo_revenue": {"type": "int", "min": 90000, "max": 10494950,
                       "uniform_int": [90000, 10494950]}}
    depth = (10494950 - 90000).bit_length()
    assert depth == 24  # a Sum of 26 planes: two programs at 280 candidates
    assert "one v5e chip" in config["deployment"]


def test_cell_is_one_chip_and_says_what_it_holds():
    cell = CELLS[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "ssb-lineorder", "brand-lookup", 1)
    for words in ("8 closed-loop clients", "Q2.1-Q2.3", "200 brands",
                  "24-bit Sum", "280 candidates", "2.4 GiB"):
        assert words in cell["why"], words


def test_mix_is_the_rotation_of_three_over_one_manufacturer():
    config, mix = load_config("ssb-lineorder"), load_mix("brand-lookup")
    assert mix["preload"] is False
    (group,) = mix["groups"]
    assert (group["clients"], group["loop"]) == (8, "closed")
    assert group["rotation"] == ["q2_1", "q2_2", "q2_3"]
    assert traffic.fields_read(mix, config) == list(config["fields"])
    for name, (limit, offset) in RANGES.items():
        t = mix["templates"][name]
        assert (t["kind"], t["sum"]) == ("groupby", "lo_revenue")
        assert t["dims"] == [{"field": "d_year"},
                             {"field": "p_brand1", "previous": "P",
                              "limit": limit}]
        assert t["draw"]["C"] == {"row_of": "p_category", "top": 5}
        assert t["draw"]["R"] == {"row_of": "s_region"}
        assert t["draw"]["P"] == {"affine": ["C", 40, offset - 1]}
        assert t["filter"] == ([["p_category", "C"], ["s_region", "R"]]
                               if name == "q2_1" else [["s_region", "R"]])


@pytest.mark.parametrize("seed", [3, 2_147_483_659, 4_111_222_333])
def test_every_seed_draws_the_hot_set_and_nothing_else(seed):
    """Categories 0-4 (brands 0-199) and all 5 regions, whatever the
    seed: 16 row-cache entries, 2,464 MiB at 64 slots by the shapes."""
    config, mix = load_config("ssb-lineorder"), load_mix("brand-lookup")
    seen = collections.defaultdict(set)
    for client in traffic.clients(mix, config, config["shards"], seed, "w"):
        for _ in range(150):
            name, pql, sem = client.next()
            _, brands = sem["dims"]
            limit, offset = RANGES[name]
            category, rem = divmod(brands["previous"] + 1 - offset, 40)
            assert rem == 0 and brands["limit"] == limit
            assert f"previous={brands['previous']}, limit={limit}" in pql
            seen["category"].add(category)
            seen["region"].add(dict(sem["filter"])["s_region"])
            seen[name].add(brands["previous"])
            if name == "q2_1":
                assert dict(sem["filter"])["p_category"] == category
    assert seen["category"] == set(range(5))
    assert seen["region"] == set(range(5))
    assert -1 in seen["q2_1"]  # the first category asks previous=-1
    assert {name: 7 * limit for name, (limit, _) in RANGES.items()} == {
        "q2_1": 280, "q2_2": 56, "q2_3": 7}  # 7 years x the range's brands
    # row-cache entries the window can name and their stacked rows, with
    # the executor's zero rows (batch.groupby_pad_rows: 40 -> 41, 8 -> 9,
    # 1 -> 3, 7 years stay 7, 26 planes stay 26)
    rows = 5 * (41 + 9 + 3) + 7 + 26 + (5 + 5)
    assert rows == 308 and rows * 64 * (datagen.SHARD_WIDTH // 8) == 2464 << 20


def test_written_files_hold_the_columns_bits(tmp_path):
    """``cli check -d`` finds the fragment files sound and ``cli inspect``
    counts in them exactly the columns' bits: one a column in each set
    field (array containers under the 1000 brands), and in the BSI field
    the existence row plus the ones of ``value - min``, which is what a
    plane holds (``test_bench_rehearse.py``'s case for this configuration
    counted the ones of the raw values until ISSUE 41 mended it)."""
    config, mix = load_config("ssb-lineorder"), load_mix("brand-lookup")
    fields = traffic.fields_read(mix, config)
    cols = datagen.make_columns(config, 3_200_000_051, 2, fields)
    datagen.write_data_dir(str(tmp_path), config, cols, 2, fields)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    check = subprocess.run(
        [sys.executable, "-m", "pilosa_tpu", "check", "-d", str(tmp_path)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert check.returncode == 0, check.stdout[-2000:] + check.stderr[-2000:]
    inspect = subprocess.run(
        [sys.executable, "-m", "pilosa_tpu", "inspect", "-d", str(tmp_path)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert inspect.returncode == 0
    bits = {l.split(": ", 1)[0]: int(l.split("bits=")[1].split()[0])
            for l in inspect.stdout.splitlines()}
    low = config["fields"]["lo_revenue"]["min"]
    for shard in range(2):
        for f in ("p_brand1", "p_category", "s_region", "d_year"):
            assert bits[f"lineorder/{f}/standard/{shard}"] == datagen.SHARD_WIDTH
        stored = cols["lo_revenue"][shard << 20:(shard + 1) << 20].astype(
            "int64") - low
        ones = sum(int(((stored >> i) & 1).sum()) for i in range(24))
        assert (bits[f"lineorder/lo_revenue/bsig_lo_revenue/{shard}"]
                == ones + datagen.SHARD_WIDTH)
        assert int(stored.min()) >= 0 and int(stored.max()) < 1 << 24


# ------------------------------------------------ the three metric files


@pytest.mark.parametrize("name", NEW)
def test_metric_entry_lists_the_three_cells_that_run_groupby(name):
    entry = {m["name"]: m for m in MANIFEST["per_layer"]}[name]
    assert entry["workloads"] == [CELL] + DASHBOARDS
    assert (entry["layer"], entry["moves"]) == ("device", "throughput")
    assert entry["source"] == ("device_trace" if name == "groupby_level_share"
                               else "program_counter")
    # nothing to read: nothing returned, nothing raised
    assert readers.read(BENCH, name, {}, {}, None, {}) is None


def test_ratio_metrics_read_the_groupby_block():
    levels = "pilosa_tpu_groupby_levels_total"
    programs = "pilosa_tpu_groupby_level_programs_total"
    cands = "pilosa_tpu_groupby_level_candidates_total"
    assert spec_of("level_programs_per_level") == {
        "reader": "ratio", "numerator": [programs], "denominator": [levels],
        "what": spec_of("level_programs_per_level")["what"]}
    assert spec_of("candidates_per_level")["numerator"] == [cands]
    assert spec_of("candidates_per_level")["denominator"] == [levels]
    # one rotation of the mix: 280 candidates in two programs, 56, 7
    before = {levels: 30.0, programs: 40.0, cands: 3430.0}
    after = {levels: 33.0, programs: 44.0, cands: 3773.0}
    assert readers.read(BENCH, "level_programs_per_level", before, after,
                        None, {}) == pytest.approx(4 / 3)
    assert readers.read(BENCH, "candidates_per_level", before, after,
                        None, {}) == pytest.approx(343 / 3)
    # a program from before PR 32 exports no candidates counter: left out
    del before[cands], after[cands]
    assert readers.read(BENCH, "candidates_per_level", before, after,
                        None, {}) is None
    assert readers.read(BENCH, "level_programs_per_level", before, after,
                        None, {}) == pytest.approx(4 / 3)
    # a window without a GroupBy: no denominator, left out
    assert readers.read(BENCH, "level_programs_per_level", after, after,
                        None, {}) is None


def test_the_program_exports_the_series_the_ratios_name():
    sys.path.insert(0, ROOT)
    from pilosa_tpu.utils.tracing import groupby_metrics

    exported = {f"pilosa_tpu_groupby_{k}" for k in groupby_metrics()}
    for name in ("level_programs_per_level", "candidates_per_level"):
        spec = spec_of(name)
        assert set(spec["numerator"] + spec["denominator"]) <= exported


def test_groupby_level_share_finds_the_kernel_of_a_written_plane(tmp_path):
    """The level kernel as the chip's trace names it (PR 31's ledger
    line: 26 quantities x 256 candidates), beside a readback copy."""
    level = ("%groupby_level.1 = s32[6656,128]{1,0:T(8,128)} custom-call("
             "u32[64,41,32768]{2,0,1} %p), custom_call_target=\"tpu_custom_call\"")
    small = "%groupby_level.1 = s32[208,128]{1,0:T(8,128)} custom-call(%q)"
    copy = "%copy.3 = u32[64,3,32768]{2,0,1} copy(u32[64,3,32768] %r)"
    path = tmp_path / "ssb.xplane.pb"
    path.write_bytes(xspace([("/device:TPU:0", [("XLA Ops", [
        (level, 0, 9 * MS), (copy, 10 * MS, 1 * MS), (small, 12 * MS, 1 * MS),
        (level, 20 * MS, 9 * MS)])])]))
    reduced = trace.reduce(str(path), 0.04)
    assert spec_of("groupby_level_share")["reader"] == "trace_ops"
    assert readers.read(BENCH, "groupby_level_share", {}, {}, reduced, {}) \
        == pytest.approx(100 * 19 / 40)
    assert readers.read(BENCH, "device_idle_share", {}, {}, reduced, {}) \
        == pytest.approx(50.0)


# ------------------------------------------------------- the cell rehearsed


@pytest.fixture(scope="module")
def both(tmp_path_factory):
    """The cell's two rehearsals, which every file of this directory
    shares (``bench_helpers.rehearsals``): run here or read from the
    worker that ran them."""
    return rehearsals(tmp_path_factory, [(CELL, 0), (CELL, 1)])


@pytest.fixture(scope="module")
def untraced(both):
    """The end-to-end run, with the control compared after it."""
    return both[CELL, 0]


@pytest.fixture(scope="module")
def traced(both):
    return both[CELL, 1]


def test_rehearsal_is_correct_on_the_three_queries(untraced):
    assert untraced.returncode == 0, (untraced.stderr[-3000:]
                                      + untraced.stdout[-2000:])
    line = last_line(untraced.stdout)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    checks = [l for l in untraced.stdout.splitlines()
              if l.startswith("check answers.")]
    assert [l.split()[1] for l in checks] == [
        "answers.q2_1:", "answers.q2_2:", "answers.q2_3:"]
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
    assert len(wrong) == 3  # a Sum over half the shards, doubled, is no Sum


def test_traced_rehearsal_prints_the_three_new_metrics(traced):
    assert traced.returncode == 0, traced.stderr[-3000:] + traced.stdout[-2000:]
    line = last_line(traced.stdout)
    assert line["correct"] is True and line["failed"] == 0
    metrics = line["metrics"]
    units = {m["name"]: m["unit"] for m in MANIFEST["per_layer"]}
    for name in NEW:
        assert metrics[name]["unit"] == units[name]
    # a window of whole and broken rotations: between Q2.3's and Q2.1's
    assert 1.0 < metrics["level_programs_per_level"]["value"] < 2.0
    assert 7 < metrics["candidates_per_level"]["value"] < 280
    # the CPU's trace names no operation groupby_level: 0, not left out
    assert metrics["groupby_level_share"]["value"] >= 0.0
    assert metrics["residency_evictions_in_window"]["value"] == 0.0
    assert metrics["residency_hit_share"]["value"] == 100.0
    listed = {m["name"] for m in MANIFEST["per_layer"]
              if CELL in m.get("workloads", [CELL])}
    assert set(metrics) == listed
