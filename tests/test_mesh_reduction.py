"""The mesh reduction (parallel/dist.py): psum / pmax over the shards axis.

Two contracts, gated here:

* bit-exactness — every reduce kind on a mesh of 1, 2, 4 and 8 devices
  returns byte-identical results to the single-device Executor,
  including non-divisible shard counts (padded slots);
* the byte count — every reducing dispatch adds 1 to ``dispatches`` and
  the bytes of a ring all-reduce of its packed lanes to ``dense_bytes``
  and to ``actual_bytes`` (the benchmark's reduce_bytes_per_dispatch
  divides the latter by the former).
"""


import numpy as np
import pytest

from pilosa_tpu.executor import Executor
from pilosa_tpu.executor.result import result_to_json
from pilosa_tpu.parallel import DistExecutor, dist, make_mesh
from pilosa_tpu.shardwidth import SHARD_WIDTH
from pilosa_tpu.storage import FieldOptions, Holder
from pilosa_tpu.utils import cost as cost_mod

N_SHARDS = 13  # deliberately not a multiple of any mesh size

MESH_SIZES = [1, 2, 4, 8]

# one query per reduce kind _note_reduce tells apart, and the lanes its
# packed result has on this holder where they do not depend on the
# candidates (count: the two split channels; min / max: best and the
# count's two)
KIND_QUERIES = {
    "count": ("Count(Row(f=1))", 2),
    "row": ("Union(Row(f=2), Row(g=3))", 0),
    "bsisum": ("Sum(Row(f=1), field=fare)", None),
    "min": ("Min(field=fare)", 3),
    "max": ("Max(field=fare)", 3),
    "countrows": ("TopN(f, n=2)", None),
    "groupby": ("GroupBy(Rows(f), aggregate=Sum(field=fare))", None),
}


@pytest.fixture(scope="module")
def holder(tmp_path_factory):
    holder = Holder(str(tmp_path_factory.mktemp("mesh") / "data")).open()
    idx = holder.create_index("big")
    f = idx.create_field("f")
    g = idx.create_field("g")
    fare = idx.create_field("fare",
                            FieldOptions(type="int", min=-5, max=1000))
    rng = np.random.default_rng(11)
    all_cols = []
    for shard in range(N_SHARDS):
        base = shard * SHARD_WIDTH
        cols = np.sort(rng.choice(SHARD_WIDTH, 150, replace=False)) + base
        f.view("standard", create=True).fragment(
            shard, create=True
        ).bulk_import(np.repeat([1, 2], 75), cols % SHARD_WIDTH)
        for c in cols[::5]:
            g.set_bit(3, int(c))
        for c in cols[:15]:
            fare.set_value(int(c), int(rng.integers(-5, 1000)))
        all_cols.extend(cols.tolist())
    idx.mark_columns_exist(all_cols)
    yield holder
    holder.close()


@pytest.fixture(scope="module")
def executors(holder):
    """One DistExecutor per mesh size, shared across tests so compiled
    programs amortize over the whole module."""
    return {n: DistExecutor(holder, make_mesh(n)) for n in MESH_SIZES}


@pytest.fixture(scope="module")
def base(holder):
    return Executor(holder)


class TestPaddedShardParity:
    """Satellite: DistExecutor vs single-device results at non-divisible
    shard counts x mesh sizes, all reduce kinds — byte-identical JSON."""

    @pytest.mark.parametrize("n_devices", MESH_SIZES,
                             ids=[f"{n}dev" for n in MESH_SIZES])
    def test_all_kinds_all_shard_counts(self, n_devices, base, executors):
        dist_ex = executors[n_devices]
        for k in (1, 5, N_SHARDS):
            shards = list(range(k))
            for pql, _ in KIND_QUERIES.values():
                (want,) = base.execute("big", pql, shards=shards)
                (got,) = dist_ex.execute("big", pql, shards=shards)
                assert result_to_json(got) == result_to_json(want), (
                    f"mesh={n_devices} shards={k} {pql}"
                )


class TestWireAccounting:
    def test_byte_model(self):
        # count on 8 devices: the ring moves 2*(8-1) times the two int32
        # split channels
        assert dist.dense_reduce_bytes(8, 2) == 112

    @pytest.mark.parametrize("kind", list(KIND_QUERIES))
    @pytest.mark.parametrize("n_devices", [2, 4], ids=["2dev", "4dev"])
    def test_flat_mesh_is_passthrough(self, executors, n_devices, kind):
        """What reduce_bytes_per_dispatch divides: each reducing
        dispatch is 1 of ``dispatches`` and the same bytes of
        ``dense_bytes`` and ``actual_bytes``, whole ring passes of int32
        lanes; a Row stays sharded and is no dispatch."""
        pql, lanes = KIND_QUERIES[kind]
        stats = dist.global_reduce_stats()
        stats.reset()
        executors[n_devices].execute("big", pql)
        snap = stats.snapshot()
        assert set(snap) == {"dispatches", "dense_bytes", "actual_bytes"}
        assert snap["actual_bytes"] == snap["dense_bytes"]
        a_lane = dist.dense_reduce_bytes(n_devices, 1)
        assert snap["dense_bytes"] % a_lane == 0
        if kind == "row":
            assert snap == {"dispatches": 0, "dense_bytes": 0,
                            "actual_bytes": 0}
        else:
            assert snap["dispatches"] >= 1 and snap["dense_bytes"] > 0
        if lanes:
            assert snap["dense_bytes"] == (
                snap["dispatches"] * lanes * a_lane)

    def test_profile_reduce_bytes(self, executors):
        """reduceBytes rides the PROFILE tree + context totals of a
        request a mesh executor serves: the same two figures."""
        prof = cost_mod.QueryProfile("big", "Count(Row(f=1))")
        ctx = cost_mod.new_cost_context("t", "big", profile=prof)
        tok = cost_mod.activate_cost(ctx)
        try:
            executors[8].execute("big", "Count(Row(f=1))")
        finally:
            cost_mod.deactivate_cost(tok)
        assert ctx.totals()["reduceBytes"] == {
            "denseEquiv": dist.dense_reduce_bytes(8, 2),
            "actual": dist.dense_reduce_bytes(8, 2)}
