"""Hierarchical reduction plane (parallel/reduction.py + the 2-D mesh).

Two contracts, gated here:

* bit-exactness — every reduce kind on every mesh factorization returns
  byte-identical results to the single-device Executor, including
  non-divisible shard counts (padded slots);
* the wire model — dense-equivalent vs actual reduction-lane bytes are
  recorded per dispatch, actual is smaller on hierarchical meshes, and
  Row/TopN shapes clear the ≥4x bar the ROADMAP target needs.
"""


import numpy as np
import pytest

from pilosa_tpu.executor import Executor
from pilosa_tpu.executor.result import result_to_json
from pilosa_tpu.parallel import DistExecutor, make_mesh, mesh_groups
from pilosa_tpu.parallel import reduction
from pilosa_tpu.shardwidth import SHARD_WIDTH, WORDS_PER_SHARD
from pilosa_tpu.storage import FieldOptions, Holder
from pilosa_tpu.utils import cost as cost_mod

N_SHARDS = 13  # deliberately not a multiple of any mesh size

# mesh sizes 1/2/4/8 including 2-D groups x shards factorizations
MESH_CONFIGS = [(1, None), (2, None), (2, 2), (4, 2), (8, 2), (8, 4)]

# one query per reduce kind: count, row, bsisum, min, max,
# countrows (TopN), groupby + aggregate
KIND_QUERIES = [
    "Count(Row(f=1))",
    "Union(Row(f=2), Row(g=3))",
    "Sum(Row(f=1), field=fare)",
    "Min(field=fare)",
    "Max(field=fare)",
    "TopN(f, n=2)",
    "GroupBy(Rows(f), aggregate=Sum(field=fare))",
]


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
    """One DistExecutor per mesh config, shared across tests so compiled
    programs amortize over the whole module."""
    return {
        cfg: DistExecutor(holder, make_mesh(cfg[0], groups=cfg[1]))
        for cfg in MESH_CONFIGS
    }


@pytest.fixture(scope="module")
def base(holder):
    return Executor(holder)


class TestPaddedShardParity:
    """Satellite: DistExecutor vs single-device results at non-divisible
    shard counts x mesh sizes, all reduce kinds — byte-identical JSON."""

    @pytest.mark.parametrize("cfg", MESH_CONFIGS,
                             ids=[f"{n}dev-g{g or 1}" for n, g in MESH_CONFIGS])
    def test_all_kinds_all_shard_counts(self, cfg, base, executors):
        dist = executors[cfg]
        for k in (1, 5, N_SHARDS):
            shards = list(range(k))
            for pql in KIND_QUERIES:
                (want,) = base.execute("big", pql, shards=shards)
                (got,) = dist.execute("big", pql, shards=shards)
                assert result_to_json(got) == result_to_json(want), (
                    f"mesh={cfg} shards={k} {pql}"
                )

    def test_hier_mesh_shape(self, executors):
        assert mesh_groups(executors[(8, 2)].mesh) == (2, 4)
        assert mesh_groups(executors[(8, 4)].mesh) == (4, 2)
        assert mesh_groups(executors[(2, None)].mesh) is None
        with pytest.raises(ValueError):
            make_mesh(8, groups=3)


class TestWireAccounting:
    def test_lane_dtype_bounds(self):
        assert reduction.lane_dtype_bytes(0) == 1
        assert reduction.lane_dtype_bytes(255) == 1
        assert reduction.lane_dtype_bytes(256) == 2
        assert reduction.lane_dtype_bytes(0xFFFF) == 2
        assert reduction.lane_dtype_bytes(0x10000) == 4

    def test_byte_model(self):
        # count on an 8-device 2x4 mesh, 16 padded slots: the flat ring
        # moves 2*(8-1)*2*4 bytes; the inter-group hop moves
        # G*(G-1)*(lo int32 + hi uint16)
        assert reduction.dense_reduce_bytes(8, 2) == 112
        inter, intra = reduction.hier_reduce_bytes("count", 2, 2, 4, 8)
        assert inter == 2 * 1 * (4 + 2)
        assert intra == 2 * 2 * 3 * 2 * 4

    def test_row_frames_roundtrip(self):
        rng = np.random.default_rng(3)
        host = np.zeros((4, WORDS_PER_SHARD), np.uint32)
        host[1, rng.integers(0, WORDS_PER_SHARD, 300)] = 0x80000001
        host[2, :7] = 0xFFFFFFFF
        frames, nbytes = reduction.encode_row_frames(host)
        assert nbytes < host.nbytes
        back = reduction.decode_row_frames(frames, host.shape)
        np.testing.assert_array_equal(back, host)

    def test_flat_mesh_is_passthrough(self, executors):
        stats = reduction.global_reduce_stats()
        stats.reset()
        executors[(2, None)].execute("big", "Count(Row(f=1))")
        snap = stats.snapshot()
        assert snap["dispatches"] >= 1
        assert snap["hier_dispatches"] == 0
        assert snap["actual_bytes"] == snap["dense_bytes"]
        assert snap["row_gathers"] == 0

    def test_hier_row_topn_4x(self, executors):
        """Row and TopN shapes move >=4x fewer reduction-lane bytes than
        the dense equivalent on the hierarchical mesh."""
        dist = executors[(8, 2)]
        stats = reduction.global_reduce_stats()
        stats.reset()
        dist.execute("big", "Union(Row(f=2), Row(g=3))")
        dist.execute("big", "TopN(f, n=2)")
        snap = stats.snapshot()
        assert snap["row_gathers"] >= 1
        assert snap["row_dense_bytes"] >= 4 * snap["row_actual_bytes"]
        assert snap["hier_dispatches"] >= 1
        assert snap["dense_bytes"] >= 4 * snap["actual_bytes"]

    def test_profile_reduce_bytes(self, executors):
        """reduceBytes rides the PROFILE tree + context totals when the
        hierarchical plane is engaged."""
        prof = cost_mod.QueryProfile("big", "Count(Row(f=1))")
        ctx = cost_mod.new_cost_context("t", "big", profile=prof)
        tok = cost_mod.activate_cost(ctx)
        try:
            executors[(8, 2)].execute("big", "Count(Row(f=1))")
        finally:
            cost_mod.deactivate_cost(tok)
        totals = ctx.totals()
        assert totals["reduceBytes"]["denseEquiv"] > \
            totals["reduceBytes"]["actual"] > 0


class TestQuantizedRanking:
    """Satellite: the EQuARX-style 8-bit candidate-ranking lane
    (`topn-quantized-ranking`). Contracts pinned here:

    * final TopN/GroupBy results are byte-identical to the lossless
      lane on every mesh factorization and shard count (the window
      widening provably covers any rank perturbation, and the window
      is recounted exactly);
    * the numpy property bound — per-row quantization error never
      exceeds the transmitted per-block bound, and the widened window
      always contains the exact top-n;
    * the quantized wire counters flow through ReduceStats (and from
      there to /metrics as dist_reduce_quantized_*).
    """

    # a ranking-heavy field: 64 rows with distinct global counts so the
    # quantized lane has real rank structure to perturb
    @pytest.fixture(scope="class")
    def qholder(self, tmp_path_factory):
        holder = Holder(str(tmp_path_factory.mktemp("meshq") / "data")).open()
        idx = holder.create_index("rank")
        many = idx.create_field("many")
        few = idx.create_field("few")
        cols = []
        for shard in range(N_SHARDS):
            base = shard * SHARD_WIDTH
            c = 0
            for r in range(64):
                # row r gets 2+r bits per shard: every row's global
                # count is distinct, so the ranking has real structure
                # and the widened window can actually shrink
                for _ in range(2 + r):
                    col = base + (c * 97) % SHARD_WIDTH
                    many.set_bit(r, col)
                    cols.append(col)
                    c += 1
            few.set_bit(1, base)
            few.set_bit(2, base + 5)
        idx.mark_columns_exist(cols)
        yield holder
        holder.close()

    @pytest.fixture(scope="class")
    def qbase(self, qholder):
        return Executor(qholder)

    QUANT_QUERIES = [
        "TopN(many, n=3)",
        "TopN(many, n=8)",
        "TopN(many, n=5, threshold=40)",
        "TopN(few, n=2)",
        "GroupBy(Rows(few))",
    ]

    # 1-D flat (lossless pass-through), 2x2, 4x2 — the ISSUE's matrix
    QUANT_CONFIGS = [(2, None), (4, 2), (8, 2)]

    @pytest.mark.parametrize(
        "cfg", QUANT_CONFIGS,
        ids=[f"{n}dev-g{g or 1}" for n, g in QUANT_CONFIGS])
    def test_final_results_byte_identical(self, cfg, qholder, qbase):
        """verify_quantized re-runs the lossless recount in-process and
        raises on ANY divergence, so this also certifies the window."""
        dist = DistExecutor(qholder, make_mesh(cfg[0], groups=cfg[1]),
                            quantized_ranking=True, verify_quantized=True)
        for k in (1, 5, N_SHARDS):  # incl. non-divisible
            shards = list(range(k))
            for pql in self.QUANT_QUERIES:
                (want,) = qbase.execute("rank", pql, shards=shards)
                (got,) = dist.execute("rank", pql, shards=shards)
                assert result_to_json(got) == result_to_json(want), (
                    f"mesh={cfg} shards={k} {pql}"
                )

    def test_error_bound_and_window_coverage_property(self):
        """Pure-numpy property sweep of the device lane's math: the
        per-row reconstruction error never exceeds the transmitted
        per-block bound (so the bound IS a valid window widening), and
        the widened window always contains the exact top-n."""
        rng = np.random.default_rng(5)
        B = reduction.QUANT_BLOCK
        for _ in range(25):
            n_rows = int(rng.integers(1, 700))
            groups = int(rng.integers(1, 5))
            exact_parts = rng.integers(
                0, 1 << int(rng.integers(4, 22)), size=(groups, n_rows))
            nb = reduction.quant_blocks(n_rows)
            padded = np.zeros((groups, nb * B), np.int64)
            padded[:, :n_rows] = exact_parts
            blocks = padded.reshape(groups, nb, B)
            # the device program, re-derived: integer max-scale,
            # deterministic round-to-nearest, 8-bit payload
            s = np.maximum((blocks.max(axis=2) + 254) // 255, 1)
            q = (blocks + (s[:, :, None] >> 1)) // s[:, :, None]
            assert q.max() <= 255
            approx = (q * s[:, :, None]).reshape(
                groups, -1)[:, :n_rows].sum(axis=0)
            err_blocks = np.where(s > 1, (s + 1) >> 1, 0).sum(axis=0)
            err = np.repeat(err_blocks, B)[:n_rows]
            exact = exact_parts.sum(axis=0)
            assert np.all(np.abs(approx - exact) <= err)
            if exact_parts.max() <= 255:
                # sub-byte blocks quantize exactly: zero budget spent
                assert np.all(err == 0) and np.all(approx == exact)
            n = int(rng.integers(1, min(16, n_rows) + 1))
            widx = set(
                np.asarray(
                    reduction.quant_topn_window(approx, err, n)).tolist())
            top = sorted(range(n_rows), key=lambda r: (-exact[r], r))[:n]
            assert set(top) <= widx

    def test_quantized_wire_counters(self, qholder):
        """Production mode (no verify recount): the quantized lane's
        actual inter-group bytes beat the modeled lossless bytes, and
        the window shrinks the exact recount below the candidate set."""
        dist = DistExecutor(qholder, make_mesh(4, groups=2),
                            quantized_ranking=True)
        dist.execute("rank", "TopN(many, n=3)")  # warm the programs
        stats = reduction.global_reduce_stats()
        stats.reset()
        dist.execute("rank", "TopN(many, n=3)")
        snap = stats.snapshot()
        assert snap["quantized_dispatches"] >= 1
        assert 0 < snap["quantized_actual_bytes"] \
            < snap["quantized_lossless_bytes"]
        assert 0 < snap["quantized_window_rows"] \
            < snap["quantized_candidate_rows"]

    def test_pruned_groupby_quantized_levels(self, qholder, qbase,
                                             monkeypatch):
        """Force the prefix-pruning GroupBy strategy: non-final levels
        ride the quantized lane (survival gating on approx+err upper
        bounds never drops a true survivor), the final level is always
        lossless — results byte-identical."""
        import pilosa_tpu.executor.executor as ex_mod

        monkeypatch.setattr(ex_mod, "GROUPBY_DENSE_MAX_PROGRAMS", 0)
        dist = DistExecutor(qholder, make_mesh(4, groups=2),
                            quantized_ranking=True, verify_quantized=True)
        pql = "GroupBy(Rows(many), Rows(few))"
        (want,) = qbase.execute("rank", pql)
        (got,) = dist.execute("rank", pql)
        assert result_to_json(got) == result_to_json(want)
