"""Multi-chip execution tests on the 8-device virtual CPU mesh.

The TPU analog of the reference's in-process cluster fixture
(test.MustRunCluster — SURVEY.md §4): real multi-device SPMD execution
without TPU hardware. Every result is cross-checked against the
single-device Executor on the same holder.
"""

import numpy as np
import pytest

from pilosa_tpu.executor import Executor
from pilosa_tpu.parallel import DistExecutor, make_mesh
from pilosa_tpu.shardwidth import SHARD_WIDTH
from pilosa_tpu.storage import FieldOptions, Holder

N_SHARDS = 13  # deliberately not a multiple of the 8-device mesh


@pytest.fixture(scope="module")
def mesh():
    return make_mesh()


@pytest.fixture
def env(tmp_path, mesh):
    holder = Holder(str(tmp_path / "data")).open()
    idx = holder.create_index("big")
    f = idx.create_field("f")
    g = idx.create_field("g")
    fare = idx.create_field("fare", FieldOptions(type="int", min=-5, max=1000))
    rng = np.random.default_rng(7)
    all_cols = []
    for shard in range(N_SHARDS):
        base = shard * SHARD_WIDTH
        cols = np.sort(rng.choice(SHARD_WIDTH, 200, replace=False)) + base
        f.view("standard", create=True).fragment(shard, create=True).bulk_import(
            np.repeat([1, 2], 100), cols % SHARD_WIDTH
        )
        for c in cols[::5]:
            g.set_bit(3, int(c))
        for c in cols[:20]:
            fare.set_value(int(c), int(rng.integers(-5, 1000)))
        all_cols.extend(cols.tolist())
    idx.mark_columns_exist(all_cols)
    yield holder, Executor(holder), DistExecutor(holder, mesh)
    holder.close()


def both(env, pql):
    holder, base, dist = env
    (r1,) = base.execute("big", pql)
    (r2,) = dist.execute("big", pql)
    return r1, r2


class TestDistMatchesSingle:
    def test_count(self, env):
        r1, r2 = both(env, "Count(Row(f=1))")
        assert r1 == r2 > 0

    def test_count_intersect(self, env):
        r1, r2 = both(env, "Count(Intersect(Row(f=1), Row(g=3)))")
        assert r1 == r2 > 0

    def test_row_segments(self, env):
        r1, r2 = both(env, "Union(Row(f=2), Row(g=3))")
        assert sorted(r1.segments) == sorted(r2.segments)
        np.testing.assert_array_equal(r1.columns(), r2.columns())

    def test_not_all(self, env):
        r1, r2 = both(env, "Not(Row(f=1))")
        np.testing.assert_array_equal(r1.columns(), r2.columns())
        r1, r2 = both(env, "Count(All())")
        assert r1 == r2

    def test_complex_tree(self, env):
        pql = "Count(Difference(Union(Row(f=1), Row(f=2)), Intersect(Row(g=3), All())))"
        r1, r2 = both(env, pql)
        assert r1 == r2

    def test_sum(self, env):
        r1, r2 = both(env, 'Sum(field="fare")')
        assert (r1.value, r1.count) == (r2.value, r2.count)
        assert r2.count > 0

    def test_sum_filtered(self, env):
        r1, r2 = both(env, 'Sum(Row(fare > 100), field="fare")')
        assert (r1.value, r1.count) == (r2.value, r2.count)

    def test_min_max(self, env):
        for call in ('Min(field="fare")', 'Max(field="fare")'):
            r1, r2 = both(env, call)
            assert (r1.value, r1.count) == (r2.value, r2.count), call

    def test_range_compare(self, env):
        for op in ("<", "<=", ">", ">=", "==", "!="):
            r1, r2 = both(env, f"Range(fare {op} 500)")
            np.testing.assert_array_equal(r1.columns(), r2.columns())

    def test_topn(self, env):
        r1, r2 = both(env, "TopN(f, n=3)")
        assert [(p.id, p.count) for p in r1] == [(p.id, p.count) for p in r2]

    def test_topn_filtered(self, env):
        r1, r2 = both(env, "TopN(f, Row(g=3), n=2)")
        assert [(p.id, p.count) for p in r1] == [(p.id, p.count) for p in r2]

    def test_shift(self, env):
        r1, r2 = both(env, "Shift(Row(f=1), n=7)")
        np.testing.assert_array_equal(r1.columns(), r2.columns())


class TestDistConsistency:
    def test_write_invalidates_stacked_cache(self, env):
        holder, base, dist = env
        (before,) = dist.execute("big", "Count(Row(f=1))")
        holder.index("big").field("f").set_bit(1, 5 * SHARD_WIDTH + 999_999)
        (after,) = dist.execute("big", "Count(Row(f=1))")
        assert after == before + 1

    def test_empty_shard_padding(self, env, mesh):
        """Shard count not divisible by mesh size: padded slots contribute 0."""
        holder, base, dist = env
        (r1,) = base.execute("big", "Count(Row(f=2))")
        (r2,) = dist.execute("big", "Count(Row(f=2))")
        assert r1 == r2

    def test_mesh_subset(self, env):
        holder, base, dist = env
        import jax

        small = make_mesh(n_devices=3)
        dist3 = DistExecutor(holder, small)
        (r1,) = base.execute("big", "Count(Row(f=1))")
        (r3,) = dist3.execute("big", "Count(Row(f=1))")
        assert r1 == r3


class TestDistGroupBy:
    def groups_json(self, res):
        return [g.to_json() for g in res]

    def test_groupby_matches_single(self, env):
        r1, r2 = both(env, "GroupBy(Rows(f), Rows(g))")
        assert self.groups_json(r1) == self.groups_json(r2)
        assert r1  # non-empty

    def test_groupby_with_filter(self, env):
        r1, r2 = both(env, "GroupBy(Rows(f), Rows(g), filter=Row(fare > 100))")
        assert self.groups_json(r1) == self.groups_json(r2)

    def test_groupby_aggregate_sum(self, env):
        r1, r2 = both(env, 'GroupBy(Rows(f), aggregate=Sum(field="fare"))')
        assert self.groups_json(r1) == self.groups_json(r2)
        assert any(g.sum is not None for g in r2)

    def test_groupby_aggregate_sum_with_filter(self, env):
        r1, r2 = both(
            env,
            'GroupBy(Rows(f), Rows(g), filter=Row(fare > 0), aggregate=Sum(field="fare"))',
        )
        assert self.groups_json(r1) == self.groups_json(r2)

    @pytest.mark.parametrize("pql", [
        "GroupBy(Rows(f), Rows(g))",
        'GroupBy(Rows(f), filter=Row(fare > 100), aggregate=Sum(field="fare"))',
    ])
    def test_level_operands_reach_the_mesh_as_one_replicated_array(
            self, env, pql):
        """A level's candidate indices and scalars are one packed array
        placed on every chip at once (stage device.replicate); the base
        executor places the same array on its device (device.upload)."""
        from pilosa_tpu.utils.tracing import stage_metrics

        def entered():
            m = stage_metrics()
            return np.array([m["device_upload_total"],
                             m["device_replicate_total"]])

        holder, base, dist = env
        # the env's executors are shared by the module's tests: what an
        # earlier one placed is found again and enters neither stage
        base._placed_operands.clear()
        dist._placed_operands.clear()
        t0 = entered()
        (r1,) = base.execute("big", pql)
        t1 = entered()
        (r2,) = dist.execute("big", pql)
        t2 = entered()
        assert self.groups_json(r1) == self.groups_json(r2) and r2
        assert (t1 - t0).tolist() == [1, 0]
        assert (t2 - t1).tolist() == [0, 1]
        # the same level again: the array is where it was placed
        (held,) = dist._placed_operands.values()
        assert held.sharding.is_fully_replicated
        (r3,) = base.execute("big", pql)
        (r4,) = dist.execute("big", pql)
        assert (entered() - t2).tolist() == [0, 0]
        assert self.groups_json(r3) == self.groups_json(r4) \
            == self.groups_json(r2)
        assert list(dist._placed_operands.values())[0] is held

    def test_packed_operand_layout(self, env, mesh):
        holder, base, dist = env
        cand = np.array([[1, 4], [2, 5], [3, 6]])
        packed = dist._groupby_operand_put((7, 9))(cand)
        assert packed.dtype == np.int32
        assert packed.sharding.is_fully_replicated
        assert packed.sharding.device_set == set(mesh.devices.ravel())
        assert np.asarray(packed).tolist() == [1, 2, 3, 4, 5, 6, 7, 9]
        # the same one array on the base executor's one device
        local = base._groupby_operand_put((7, 9))(cand)
        assert len(local.sharding.device_set) == 1
        assert np.asarray(local).tolist() == [1, 2, 3, 4, 5, 6, 7, 9]
        from pilosa_tpu.executor import batch

        idxs, scalars = batch.unpack_groupby_operand(np.asarray(local), 2, 2)
        assert idxs.tolist() == [[1, 2, 3], [4, 5, 6]]
        assert [int(x) for x in scalars] == [7, 9]

    def test_groupby_limit(self, env):
        r1, r2 = both(env, "GroupBy(Rows(f), Rows(g), limit=1)")
        assert self.groups_json(r1) == self.groups_json(r2)
        assert len(r2) == 1

    def test_groupby_having_and_topn_threshold(self, env):
        """Round-4 PQL edges on the mesh path: having filters merged
        groups and threshold floors the exact recount, both matching the
        single-device executor."""
        r1, r2 = both(env, "GroupBy(Rows(f), Rows(g), having=Condition(count > 0))")
        assert self.groups_json(r1) == self.groups_json(r2) and r2
        base_counts = {g.count for g in r2}
        floor = sorted(base_counts)[len(base_counts) // 2]  # drop some
        r1, r2 = both(
            env, f"GroupBy(Rows(f), Rows(g), having=Condition(count >= {floor}))"
        )
        assert self.groups_json(r1) == self.groups_json(r2)
        assert all(g.count >= floor for g in r2)
        r1, r2 = both(env, "TopN(f, n=10, threshold=2)")
        assert [(p.id, p.count) for p in r1] == [(p.id, p.count) for p in r2]

    def test_groupby_level_pruning_path(self, env, monkeypatch):
        """Force the per-dimension prefix-pruning strategy (cross-product
        'too big' for a single level) and check it matches the dense path."""
        import pilosa_tpu.executor.executor as ex_mod

        monkeypatch.setattr(ex_mod, "GROUPBY_DENSE_MAX_PROGRAMS", 0)
        r1, r2 = both(env, "GroupBy(Rows(f), Rows(g))")
        assert self.groups_json(r1) == self.groups_json(r2)

    def test_groupby_tiny_chunk_budget(self, env, monkeypatch):
        """A candidate bound of one, so that every level runs one
        candidate a program, must still produce identical results (chunk
        concat + unpack)."""
        from pilosa_tpu.executor import batch as batch_mod

        monkeypatch.setattr(batch_mod, "groupby_chunk_groups",
                            lambda n_planes: 1)
        r1, r2 = both(
            env,
            'GroupBy(Rows(f), Rows(g), aggregate=Sum(field="fare"))',
        )
        assert self.groups_json(r1) == self.groups_json(r2)


class TestDistWritePatching:
    def test_write_patches_sharded_leaf_in_place(self, env):
        """A Set() between two mesh queries scatter-patches the
        NamedSharding-resident stacked leaf — no re-decode, no eviction
        (SURVEY.md §7.3 hard part #3 on the SPMD path)."""
        from pilosa_tpu.storage import residency

        holder, base, dist = env
        (c1,) = dist.execute("big", "Count(Row(f=1))")
        cache = residency.global_row_cache()
        misses = cache.misses
        new_col = 2 * SHARD_WIDTH + 3  # not in the rng pattern? ensure:
        idx = holder.index("big")
        frag = idx.field("f").view("standard").fragment(2)
        delta = 0 if frag.contains(1, 3) else 1
        dist.execute("big", f"Set({new_col}, f=1)")
        (c2,) = dist.execute("big", "Count(Row(f=1))")
        assert c2 == c1 + delta
        assert cache.misses == misses  # patched in place, not re-decoded
        assert cache.updates >= 1
        (r_base,) = base.execute("big", "Row(f=1)")
        (r_dist,) = dist.execute("big", "Row(f=1)")
        assert r_base.columns().tolist() == r_dist.columns().tolist()
        assert new_col in set(r_dist.columns().tolist())


class TestDistMicrobatch:
    """Executor.submit on the mesh path: pipelined same-shape reductions
    coalesce into micro-batched SPMD dispatches (one shard_map program of
    B queries), matching the single-device executor's results — the
    serving-path behavior, not just correctness-demo eager dispatch."""

    def test_submit_count_microbatch_coalesces_on_mesh(self, env):
        holder, base, dist = env
        dispatches = []
        orig = dist._program_batched

        def counting(structure, rk, lr, ns, nq):
            dispatches.append(nq)
            return orig(structure, rk, lr, ns, nq)

        dist._program_batched = counting
        try:
            pqls = [
                f"Count(Intersect(Row(f={1 + (i % 2)}), Row(g=3)))"
                for i in range(32)
            ]
            want = [base.execute("big", p)[0] for p in pqls]
            defs = [dist.submit("big", p)[0] for p in pqls]
            got = [d.result() for d in defs]
        finally:
            dist._program_batched = orig
        assert got == want
        # 32 same-shape queries / microbatch_max=16 → exactly 2 dispatches
        assert sum(dispatches) == 32
        assert len(dispatches) == -(-32 // dist.microbatch_max)

    def test_submit_partial_group_flushes_on_resolve(self, env):
        holder, base, dist = env
        pqls = ["Count(Row(f=1))", "Count(Row(f=2))", "Count(Row(g=3))"]
        want = [base.execute("big", p)[0] for p in pqls]
        defs = [dist.submit("big", p)[0] for p in pqls]
        assert dist._pending  # 3 < microbatch_max: group still pending
        assert [d.result() for d in defs] == want
        assert not dist._pending

    def test_submit_bsi_aggregates_microbatch_on_mesh(self, env):
        holder, base, dist = env
        pqls = [
            'Sum(field="fare")',
            'Sum(Row(f=1), field="fare")',
            'Min(field="fare")',
            'Max(field="fare")',
        ]
        want = [base.execute("big", p)[0] for p in pqls]
        defs = [dist.submit("big", p)[0] for p in pqls]
        assert [d.result() for d in defs] == want
