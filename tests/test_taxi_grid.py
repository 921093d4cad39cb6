"""The taxi example's grid-id fields (ISSUE 34) below the harness: a toy
``rides`` index (3 shards, so one zero slot of 4; two sparse 10,000-id
grid fields with a steep fall-off, a 24-row hour field, an 8-row year
field, a 16-bit amount) through ``API.query``, every answer compared with
plain numpy over the columns written here: nothing of the benchmark's
harness, nothing of the program.

The four query shapes are the cell's (``benchmarks/traffic/
cell-lookup.json``). They run under a ``DeviceRowCache`` whose budget
holds the hot stacks (the hour matrix, the amount's planes, the year
rows) and about a dozen grid rows beside them, as the chip's budget holds
685 of the cell's 4,096: so while the answers are checked the cache
misses, evicts rows too dense to compress, demotes the sparse ones to
their non-zero blocks on the device and promotes those back by scatter,
in one thread and from 8 at once. The counter and the two stages ISSUE 34
adds (``residency_miss_bytes``, ``residency.decode``,
``residency.upload``) are read around the queries.
"""

import threading

import numpy as np
import pytest

from pilosa_tpu.executor import batch
from pilosa_tpu.roaring import kernels
from pilosa_tpu.server.api import API
from pilosa_tpu.shardwidth import SHARD_WIDTH
from pilosa_tpu.storage import Holder, residency
from pilosa_tpu.utils.tracing import stage_metrics

INDEX = "rides"
N_SHARDS, SLOTS = 3, 4          # a ShardBlock pads 3 shards to 4 slots
PER_SHARD = 30_000
GRID_IDS, HOURS, YEARS = 10_000, 24, 8
AMOUNT = (0, 65_535)            # 16 bits: 18 planes
CELLS = 64                      # grid rows the queries name: 0..63
ROW_LEAF = SLOTS * residency.ROW_BYTES
# the hot stacks and thirteen grid rows beside them
BUDGET = (HOURS + 18 + YEARS + 13) * ROW_LEAF


@pytest.fixture(scope="module")
def columns():
    """One ride a column. A cell's share of the rides falls by a tenth
    from one id to the next: cell 0 holds ~3,000 rides a shard (every
    4 KiB block of its row non-zero: too dense to compress), cell 50
    ~15 (a few blocks: demoted, not dropped), cells past ~90 none."""
    rng = np.random.default_rng(34)
    col = np.concatenate([
        np.sort(rng.choice(SHARD_WIDTH, PER_SHARD, replace=False))
        + shard * SHARD_WIDTH for shard in range(N_SHARDS)])
    n = col.size
    return {
        "column": col,
        "pickup_grid_id": np.minimum(rng.geometric(0.1, n) - 1, GRID_IDS - 1),
        "drop_grid_id": np.minimum(rng.geometric(0.1, n) - 1, GRID_IDS - 1),
        "pickup_hour": rng.integers(0, HOURS, n),
        "pickup_year": rng.integers(0, YEARS, n),
        "total_amount_cents": rng.integers(AMOUNT[0], AMOUNT[1] + 1, n),
    }


@pytest.fixture(scope="module")
def api(tmp_path_factory, columns):
    holder = Holder(str(tmp_path_factory.mktemp("grid") / "data")).open()
    api = API(holder)
    api.create_index(INDEX)
    for name in ("pickup_grid_id", "drop_grid_id", "pickup_hour",
                 "pickup_year"):
        api.create_field(INDEX, name)
        api.import_bits(INDEX, name, columns[name], columns["column"])
    api.create_field(INDEX, "total_amount_cents",
                     {"type": "int", "min": AMOUNT[0], "max": AMOUNT[1]})
    api.import_values(INDEX, "total_amount_cents", columns["column"],
                      columns["total_amount_cents"])
    yield api
    holder.close()


@pytest.fixture
def small_cache():
    """The process's row cache replaced by one of BUDGET bytes."""
    cache = residency.DeviceRowCache(budget_bytes=BUDGET)
    old = residency.global_row_cache()
    residency.set_global_row_cache(cache)
    yield cache
    residency.set_global_row_cache(old)
    cache.clear()


# ------------------------------------------ the four shapes, and numpy's


def core_cell_year(c, g, y):
    keep = (c["pickup_grid_id"] == g) & (c["pickup_year"] == y)
    return (f"Count(Intersect(Row(pickup_grid_id={g}), Row(pickup_year={y})))",
            int(keep.sum()))


def pickup_cell_by_hour(c, g, _y):
    counts = np.bincount(c["pickup_hour"][c["pickup_grid_id"] == g],
                         minlength=HOURS)
    return (f"GroupBy(Rows(pickup_hour), filter=Row(pickup_grid_id={g}))",
            [{"group": [{"field": "pickup_hour", "rowID": h}], "count": int(n)}
             for h, n in enumerate(counts) if n])


def dropoff_cell_revenue(c, h, _y):
    keep = c["drop_grid_id"] == h
    return (f'Sum(Row(drop_grid_id={h}), field="total_amount_cents")',
            {"value": int(c["total_amount_cents"][keep].sum()),
             "count": int(keep.sum())})


def dropoff_cell_year(c, h, y):
    keep = (c["drop_grid_id"] == h) & (c["pickup_year"] == y)
    return (f"Count(Intersect(Row(drop_grid_id={h}), Row(pickup_year={y})))",
            int(keep.sum()))


SHAPES = (core_cell_year, pickup_cell_by_hour, dropoff_cell_revenue,
          dropoff_cell_year)


def requests(columns, seed: int, n: int) -> list:
    """``n`` rotations of the four shapes, the cell drawn among CELLS
    (core_cell_year among the 4 busiest) and the year among YEARS."""
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        for shape in SHAPES:
            cell = int(rng.integers(0, 4 if shape is core_cell_year else CELLS))
            out.append(shape(columns, cell, int(rng.integers(0, YEARS))))
    return out


def moved(cache, before: dict) -> dict:
    after = cache.metrics()
    return {k: after[k] - before[k] for k in (
        "residency_hits", "residency_misses", "residency_evictions",
        "residency_compressions", "residency_decompressions",
        "residency_miss_bytes", "residency_miss_transfer_bytes",
        "residency_sparse_misses")}


def stages_entered(before: dict) -> dict:
    after = stage_metrics()
    return {k: after[k] - before[k] for k in after}


# ---------------------------------------------------------------- the tests


def test_toy_rows_are_dense_and_sparse_as_the_docstring_says(columns):
    per_shard = np.bincount(columns["pickup_grid_id"],
                            minlength=GRID_IDS) / N_SHARDS
    assert per_shard[0] > 2_500 and 5 < per_shard[50] < 40
    assert per_shard[CELLS - 1] > 0 and per_shard[200:].sum() == 0
    assert BUDGET < (HOURS + 18 + YEARS + 2 * CELLS) * ROW_LEAF


def test_every_answer_is_numpys_while_the_cache_thrashes(api, columns,
                                                          small_cache):
    """One thread, 60 rotations: 240 answers, exact integers; the cache
    meanwhile misses, drops, demotes and promotes."""
    m0, s0 = small_cache.metrics(), stage_metrics()
    for pql, want in requests(columns, 3_400_000_001, 60):
        (got,) = api.query(INDEX, pql)["results"]
        assert got == want, pql
    d = moved(small_cache, m0)
    assert d["residency_misses"] > 60 and d["residency_hits"] > 0
    assert d["residency_evictions"] > 0       # too dense: dropped
    assert d["residency_compressions"] > 0    # sparse: demoted on the device
    assert d["residency_decompressions"] > 0  # and promoted by scatter
    assert small_cache.bytes_used <= BUDGET
    assert small_cache.generation > 0  # the executor's operand memo cleared
    # a miss is one decode and one upload, and the bytes it placed are
    # counted: every row leaf is SLOTS x 128 KiB, the stacks multiples
    s = stages_entered(s0)
    assert (s["residency_decode_total"] == s["residency_upload_total"]
            == s["residency_miss_total"] == d["residency_misses"])
    assert (0 < s["residency_decode_seconds_total"]
            + s["residency_upload_seconds_total"]
            <= s["residency_miss_seconds_total"])
    assert d["residency_miss_bytes"] % ROW_LEAF == 0
    assert d["residency_miss_bytes"] >= d["residency_misses"] * ROW_LEAF


def test_a_row_leaf_miss_places_its_dense_bytes(api, columns, small_cache):
    """A Count of one cold grid row against one year row: two misses of
    one row leaf each, 2 x SLOTS x 128 KiB placed; asked again, none."""
    pql, want = core_cell_year(columns, 2, 5)
    m0 = small_cache.metrics()
    assert api.query(INDEX, pql)["results"] == [want]
    d = moved(small_cache, m0)
    assert (d["residency_misses"], d["residency_miss_bytes"]) == (
        2, 2 * ROW_LEAF)
    m0, s0 = small_cache.metrics(), stage_metrics()
    assert api.query(INDEX, pql)["results"] == [want]
    assert moved(small_cache, m0)["residency_miss_bytes"] == 0
    s = stages_entered(s0)  # a hit enters neither new stage
    assert s["residency_decode_total"] == s["residency_upload_total"] == 0


def test_a_row_leaf_miss_is_one_decode_of_the_whole_leaf(api, columns,
                                                        small_cache):
    """The two row leaves of a cold Count are decoded in one kernel call
    each (``batch.host_leaf``), whatever the shard count: the host-path
    counters rise by the misses, not by misses x N_SHARDS as the per-shard
    ``row_words`` stack raised them, and each places its SLOTS x 128 KiB.
    Both rows are array containers only (ISSUE 38): they travel as their
    set bits, no dense image is decoded, and what the chip expands them
    to is what the Count reads."""
    stats = kernels.global_kernel_stats()
    pql, want = dropoff_cell_year(columns, 3, 6)
    m0, k0 = small_cache.metrics(), dict(stats.metrics())
    assert api.query(INDEX, pql)["results"] == [want]
    d = moved(small_cache, m0)
    k = {name: v - k0[name] for name, v in stats.metrics().items()}
    assert d["residency_misses"] == 2 < 2 * N_SHARDS
    assert d["residency_miss_bytes"] == 2 * ROW_LEAF
    assert d["residency_sparse_misses"] == 2
    assert d["residency_miss_transfer_bytes"] < 2 * ROW_LEAF // 4
    assert k["hostpath_dense_decodes_total"] == 0
    assert k["hostpath_kernel_calls_total"] == 2
    assert k["hostpath_containers_flattened_total"] > 2 * N_SHARDS
    k0 = dict(stats.metrics())  # a hit decodes nothing
    assert api.query(INDEX, pql)["results"] == [want]
    assert stats.metrics() == k0


def test_eight_threads_at_once_get_numpys_answers(api, columns, small_cache):
    """8 threads x 12 rotations over the same small cache: builders of
    one key wait for each other, evictions land between another thread's
    lookup and its program, and every answer is still exact."""
    work = [requests(columns, 3_400_000_100 + k, 12) for k in range(8)]
    wrong: list = []
    start = threading.Barrier(8)

    def client(reqs) -> None:
        try:
            start.wait()
            for pql, want in reqs:
                (got,) = api.query(INDEX, pql)["results"]
                if got != want:
                    wrong.append((pql, got, want))
        except BaseException as e:  # shown by the assert below
            wrong.append(repr(e))

    m0 = small_cache.metrics()
    threads = [threading.Thread(target=client, args=(w,)) for w in work]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not wrong, wrong[:3]
    d = moved(small_cache, m0)
    assert d["residency_misses"] > 0 and d["residency_evictions"] > 0
    assert d["residency_compressions"] > 0
    assert d["residency_decompressions"] > 0
    assert d["residency_miss_bytes"] >= d["residency_misses"] * ROW_LEAF


def test_a_staging_array_goes_out_again_only_when_nobody_holds_it(monkeypatch):
    """ShardBlock.stack decodes a miss into a pooled host array: the same
    memory serves the next miss once the last one's array is dropped, never
    while anything (a caller, JAX's transfer or alias) still refers to it;
    padding slots read zero whatever the array held before."""
    monkeypatch.setattr(batch, "_staging", {})
    monkeypatch.setattr(batch, "_staging_bytes", 0)
    block = batch.ShardBlock([0, 1, 2])
    assert block.padded == SLOTS
    first = block.stack(lambda s: np.full(8, s + 1, np.uint32), inner=(8,))
    assert first.tolist() == [[1] * 8, [2] * 8, [3] * 8, [0] * 8]
    first[3] = 9  # what a recycled array may hold in its padding slot
    held = block.stack(lambda s: np.full(8, 7, np.uint32), inner=(8,))
    assert held is not first  # ``first`` is still referred to
    address = first.ctypes.data
    del first
    again = block.stack(lambda s: np.full(8, s + 4, np.uint32), inner=(8,))
    assert again.ctypes.data == address
    assert again.tolist() == [[4] * 8, [5] * 8, [6] * 8, [0] * 8]
    assert held.tolist() == [[7] * 8] * 3 + [[0] * 8]
    # a stack the pool has no room for is built and forgotten
    monkeypatch.setattr(batch, "STAGING_POOL_BYTES", batch._staging_bytes)
    big = block.stack(lambda s: np.zeros(16, np.uint32), inner=(16,))
    assert big.shape == (SLOTS, 16) and batch._staging[(SLOTS, 16)] == []
