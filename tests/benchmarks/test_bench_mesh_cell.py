"""The four-chip cell ``taxi-rides-x4.dashboard`` and the three per-layer
metrics it brings (``collective_share``, ``reduce_bytes_per_dispatch``,
``residency_evictions_in_window``): their data files name readers that
exist and read what they say, a traced rehearsal prints the two ``ratio``
metrics, and, below the harness, the mesh executor on four devices
answers the mix's four templates as the plain reference does, under a
row-cache budget that only holds the working set when it is counted per
chip."""

import json
import os

import numpy as np
import pytest

from bench_helpers import (BENCH, CELLS, MANIFEST, last_line, load_config,
                           load_mix, rehearse)
from harness import datagen, readers, trace, traffic
from harness.reference import Reference
from xplane_writer import xspace

CELL = "taxi-rides-x4.dashboard"
NEW = ("collective_share", "reduce_bytes_per_dispatch",
       "residency_evictions_in_window")
MS = 1_000_000  # ns


def spec_of(name: str) -> dict:
    with open(os.path.join(BENCH, "layer_metrics", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", NEW)
def test_metric_file_names_a_reader_that_exists(name):
    spec = spec_of(name)
    entry = {m["name"]: m for m in MANIFEST["per_layer"]}[name]
    # a reader that exists returns a number or nothing, never raises
    assert readers.read(BENCH, name, {}, {}, None, {}) is None
    if name == "residency_evictions_in_window":
        assert spec["reader"] == "ratio" and "denominator" not in spec
        assert "workloads" not in entry  # every cell exports the series
        series = spec["numerator"][0]
        assert readers.read(BENCH, name, {series: 3.0}, {series: 3.0}, None,
                            {}) == 0.0
        assert readers.read(BENCH, name, {series: 3.0}, {series: 8.0}, None,
                            {}) == 5.0
    else:
        assert entry["workloads"] == [CELL] and CELLS[CELL]["chips"] == 4
        assert entry["layer"] == "mesh reduction"
    if name == "reduce_bytes_per_dispatch":
        num, den = spec["numerator"][0], spec["denominator"][0]
        before, after = {num: 1000.0, den: 10.0}, {num: 9000.0, den: 12.0}
        assert readers.read(BENCH, name, before, after, None, {}) == 4000.0
        # a one-chip server dispatches no mesh program: left out
        assert readers.read(BENCH, name, before, before, None, {}) is None


def test_collective_share_finds_the_all_reduce_of_a_written_plane(tmp_path):
    fusion = "%fusion.36 = u32[128,12,2048]{2,0,1} fusion(u32[128,32768] %p)"
    allred = "%all-reduce.3 = s32[2,120]{1,0} all-reduce(s32[2,120]{1,0} %x)"
    planes = [(f"/device:TPU:{chip}", [("XLA Ops", [
        (fusion, 0, 40 * MS), (allred, 40 * MS, 2 * MS),
        (fusion, 50 * MS, 40 * MS)])]) for chip in range(4)]
    path = tmp_path / "x4.xplane.pb"
    path.write_bytes(xspace(planes))
    reduced = trace.reduce(str(path), 0.1)
    assert reduced["devices"] == 4
    assert spec_of("collective_share")["reader"] == "trace_ops"
    assert readers.read(BENCH, "collective_share", {}, {}, reduced, {}) \
        == pytest.approx(2.0)
    assert readers.read(BENCH, "device_idle_share", {}, {}, reduced, {}) \
        == pytest.approx(18.0)


def test_traced_rehearsal_prints_the_ratio_metrics():
    p = rehearse(CELL, trace=1, seed=2_900_000_011)
    assert p.returncode == 0, p.stderr[-3000:] + p.stdout[-2000:]
    line = last_line(p.stdout)
    assert line["correct"] is True and line["failed"] == 0
    assert line["device"]["count"] == 4
    metrics = line["metrics"]
    assert metrics["reduce_bytes_per_dispatch"]["value"] > 0
    assert metrics["reduce_bytes_per_dispatch"]["unit"] == "bytes"
    assert metrics["residency_evictions_in_window"] == {"value": 0.0,
                                                        "unit": "count"}
    assert metrics["residency_hit_share"]["value"] == 100.0
    # every metric the manifest gives the cell, save the device trace's
    # where the CPU backend records no collective of that name
    listed = {m["name"] for m in MANIFEST["per_layer"]
              if CELL in m.get("workloads", [CELL])}
    assert listed - set(metrics) <= {"collective_share"}


N_SHARDS = 8  # the configuration's rehearse_shards: two a device


@pytest.fixture(scope="module")
def deployment(tmp_path_factory):
    """The cell's data at 8 shards, as the harness writes it, opened by a
    holder under the mesh executor on a flat mesh of four devices."""
    from pilosa_tpu.parallel import DistExecutor, make_mesh
    from pilosa_tpu.storage import Holder

    config, mix = load_config("taxi-rides-x4"), load_mix("dashboard")
    assert config["rehearse_shards"] == N_SHARDS
    fields = traffic.fields_read(mix, config)
    columns = datagen.make_columns(config, 2_900_000_033, N_SHARDS, fields)
    data_dir = str(tmp_path_factory.mktemp("x4") / "data")
    os.makedirs(data_dir)
    datagen.write_data_dir(data_dir, config, columns, N_SHARDS, fields)
    holder = Holder(data_dir).open()
    dist = DistExecutor(holder, make_mesh(n_devices=4))
    yield config, mix, columns, dist
    holder.close()


def test_mesh_executor_answers_the_mix_as_the_reference_does(deployment):
    """Preloaded as the harness preloads, the mix keeps 68 stacked rows
    resident: 24 filter leaves (8 years, 16 distances), the matrices of
    cab_type (3 rows padded to 4), passenger_count (10) and pickup_month
    (12), and the 18 planes of total_amount_cents. At 8 shards that is
    68 MiB over all chips and 17 MiB a chip; the budget, 32 MiB, lies
    between, as the default 4 GiB lies between 4.25 GiB and 1.06 GiB at
    512 shards. Nothing is evicted and every answer of every template
    equals the reference's."""
    from pilosa_tpu.executor.result import result_to_json
    from pilosa_tpu.storage import residency

    config, mix, columns, dist = deployment
    ref = Reference(config, columns)
    leaf = N_SHARDS * residency.ROW_BYTES  # one stacked row, all chips
    budget = 32 << 20
    cache = residency.DeviceRowCache(budget_bytes=budget)
    old = residency.global_row_cache()
    residency.set_global_row_cache(cache)
    try:
        index = config["index"]
        for f, r in traffic.preload_rows(mix, config):
            (got,) = dist.execute(index, f"Count(Row({f}={r}))")
            assert got == ref.count([(f, r)])
        (group,) = mix["groups"]
        compared = {name: 0 for name in group["rotation"]}
        for k in range(group["clients"]):
            client = traffic.Client(mix, config, N_SHARDS, group, k,
                                    2_900_000_033, "mesh")
            for _ in range(len(group["rotation"])):
                name, pql, sem = client.next()
                (got,) = dist.execute(index, pql)
                assert result_to_json(got) == ref.answer(sem), pql
                compared[name] += 1
        assert all(n == group["clients"] for n in compared.values())
        m = cache.metrics()
        assert m["residency_evictions"] == 0 and cache.generation == 0
        assert m["residency_misses"] == m["residency_entries"] == 28
        # what the old reckoning charged does not fit the budget
        global_bytes = sum(int(e.arr.nbytes) for e in cache._rows.values())
        assert global_bytes == 68 * leaf > budget
        assert m["residency_bytes_used"] == 68 * leaf // 4 < budget
        assert cache.device_bytes() == {
            str(d.id): 68 * leaf // 4 for d in dist.mesh.devices.ravel()}
    finally:
        residency.set_global_row_cache(old)
        cache.clear()


def test_the_sampled_control_fails_this_cell_too(deployment):
    """Answers from half the shards, doubled, are not the reference's:
    the cell's limit 0 lies between the mesh executor's answers and an
    approximation's."""
    config, mix, columns, dist = deployment
    half = {f: v.reshape(N_SHARDS, -1)[::2].reshape(-1)
            for f, v in columns.items()}
    ref, ref_half = Reference(config, columns), Reference(config, half)
    sem = {"kind": "topn", "field": "cab_type",
           "filter": [("pickup_year", 3)]}
    exact = ref.answer(sem)
    doubled = [{**p, "count": 2 * p["count"]} for p in ref_half.answer(sem)]
    assert doubled != exact
    assert np.sum([p["count"] for p in exact]) > 0
