"""The GroupBy level program (ISSUE 28): one Pallas kernel body for the
local and the mesh builder, which reads each operand row once and keeps
every candidate's accumulators on-chip. Here it runs through Pallas'
interpreter on the CPU; every count is compared with a numpy oracle over
the same words.
"""

import json
import os
import re

import numpy as np
import pytest

from cluster_helpers import req, uri
from pilosa_tpu.executor import Executor, batch
from pilosa_tpu.executor.executor import _groupby_level_unpack
from pilosa_tpu.parallel import DistExecutor, make_mesh
from pilosa_tpu.shardwidth import WORDS_PER_SHARD
from pilosa_tpu.storage import Holder
from pilosa_tpu.utils.tracing import groupby_metrics

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N_SHARDS = 3          # in 4 slots on one device, 8 on the mesh
DEPTH = 16
BASE = -50            # the Sum's field has min != 0
DIM_ROWS = (10, 12, 3)


class _AggField:
    """What _groupby_level_enqueue reads of the aggregate's field."""

    class options:
        bit_depth = DEPTH
        base = BASE


@pytest.fixture(scope="module")
def words():
    """Host words of every operand, shard-major: dimension matrices,
    two filter rows and the Sum's planes (exists, sign, 16 bits)."""
    rng = np.random.default_rng(28)

    def draw(*shape):
        return (rng.integers(0, 1 << 32, shape, dtype=np.uint32)
                & rng.integers(0, 1 << 32, shape, dtype=np.uint32))

    return {
        "dims": [draw(N_SHARDS, n, WORDS_PER_SHARD) for n in DIM_ROWS],
        "filt": [draw(N_SHARDS, WORDS_PER_SHARD) for _ in range(2)],
        "planes": draw(N_SHARDS, DEPTH + 2, WORDS_PER_SHARD),
    }


@pytest.fixture(scope="module")
def executors(tmp_path_factory):
    holder = Holder(str(tmp_path_factory.mktemp("gbl") / "data")).open()
    yield {
        "local": Executor(holder),
        "mesh": DistExecutor(holder, make_mesh()),
        "mesh-2": DistExecutor(holder, make_mesh(2)),
    }
    holder.close()


def _popcount(x) -> int:
    return int(np.bitwise_count(x).sum())


def oracle(words, n_dims, filt, cand, with_sum):
    """Counts (and Sums) of each candidate, straight from the words."""
    counts, sums = [], []
    if with_sum:
        planes = words["planes"]
        exists = planes[:, 0]
    for c in cand:
        m = words["dims"][0][:, c[0]]
        for d in range(1, n_dims):
            m = m & words["dims"][d][:, c[d]]
        if filt is not None:
            m = m & filt
        counts.append(_popcount(m))
        if with_sum:
            gm = m & exists
            total = BASE * _popcount(gm)
            for b in range(DEPTH):
                total += _popcount(planes[:, 2 + b] & gm) << b
            sums.append(total)
    return counts, sums


def level_sums(agg, n):
    """Each candidate's Sum from its aggregate partials."""
    n_g, plane_counts = agg
    return [sum(int(v) << b for b, v in enumerate(plane_counts[:, j]))
            + BASE * int(n_g[j]) for j in range(n)]


def shifted(row, n):
    """Shift(row, n) within each shard: bits move up by n columns."""
    bits = np.unpackbits(row.view(np.uint8), axis=-1, bitorder="little")
    out = np.zeros_like(bits)
    out[:, n:] = bits[:, :-n]
    return np.packbits(out, axis=-1, bitorder="little").view(np.uint32)


def cross(*sizes):
    grids = np.meshgrid(*[np.arange(n) for n in sizes], indexing="ij")
    return np.stack([g.ravel() for g in grids], axis=1).astype(np.int32)


# executor._index_cross of the prefixes (2,), (5,), (9,) and 12 rows
SURVIVORS_3X12 = cross(10, 12).reshape(10, 12, 2)[[2, 5, 9]].reshape(-1, 2)

# name: (gathered dims, filter, Sum, candidates, candidate bound or None)
CASES = {
    "1dim-nofilter-count-c1": (1, None, False, cross(10)[3:4], None),
    "1dim-leaf-sum16-c10": (1, "leaf", True, cross(10), None),
    "2dims-intersect-count-c120": (2, "and", False, cross(10, 12), None),
    # 37 candidates in programs of 16: a non-power-of-two above the bound
    "2dims-leaf-sum16-c37-above-bound": (
        2, "leaf", True, cross(10, 12)[5:42], 16),
    # what a pruned level sends: no cross product, an index repeated
    "3dims-nofilter-count-pruned-list": (
        3, None, False,
        np.array([[0, 0, 0], [9, 11, 2], [4, 7, 1], [4, 7, 1], [4, 0, 2],
                  [1, 1, 1], [9, 0, 0], [0, 11, 2], [3, 3, 0], [3, 3, 1]],
                 np.int32), None),
    "3dims-intersect-sum16-c10": (3, "and", True, cross(10, 12, 3)[100:110],
                                  None),
    # a filter the kernel cannot take tile by tile: XLA evaluates it once
    "2dims-shift-count-c120": (2, "shift", False, cross(10, 12), None),
    # the bound forced to one candidate a program: concat + unpack
    "1dim-leaf-sum16-bound1": (1, "leaf", True, cross(10)[:5], 1),
    # dimensions too large to hold a tile of: rows copied in by index
    "2dims-leaf-count-c120-last-paged": (2, "leaf", False, cross(10, 12),
                                         None),
    "3dims-intersect-sum16-all-paged": (
        3, "and", True, cross(10, 12, 3)[200:209], None),
    "2dims-nofilter-count-pruned-first-paged": (
        2, None, False,
        np.array([[4, 7], [4, 7], [9, 0], [0, 11], [4, 1]], np.int32), None),
    # ISSUE 43: a paged row tile is copied only where the index changes.
    # Prefix-major, as a pruned level sends its candidates: three
    # survivors of the first dimension, each with the second's 12 rows
    "2dims-leaf-count-3x12-both-paged": (2, "leaf", False, SURVIVORS_3X12,
                                         None),
    # the first index alternates (every candidate copies, into the half
    # the candidate before last read), the second lasts two candidates
    "2dims-nofilter-count-alternating-both-paged": (
        2, None, False,
        np.array([[4, 7], [9, 7], [4, 2], [9, 2], [4, 7], [9, 7], [4, 7]],
                 np.int32), None),
    # one index for the whole program: candidate 0's is the only copy
    "2dims-leaf-count-constant-first-paged": (
        2, "leaf", False, cross(10, 12)[48:60], None),
    "1dim-nofilter-count-c1-paged": (1, None, False, cross(10)[3:4], None),
    # runs of 12 over programs of 16: a run goes on in the next program,
    # which starts with a copy
    "2dims-leaf-count-3x12-above-bound-both-paged": (
        2, "leaf", False, SURVIVORS_3X12, 16),
    "2dims-leaf-sum16-3x12-first-paged": (2, "leaf", True, SURVIVORS_3X12,
                                          None),
}
# the tile plan these cases force (word tile, who is paged): at the
# test's row counts every dimension would be resident
PAGED = {
    "2dims-leaf-count-c120-last-paged": (8192, (False, True)),
    "3dims-intersect-sum16-all-paged": (2048, (True, True, True)),
    "2dims-nofilter-count-pruned-first-paged": (32768, (True, False)),
    "2dims-leaf-count-3x12-both-paged": (8192, (True, True)),
    "2dims-nofilter-count-alternating-both-paged": (16384, (True, True)),
    "2dims-leaf-count-constant-first-paged": (8192, (True, False)),
    "1dim-nofilter-count-c1-paged": (32768, (True,)),
    "2dims-leaf-count-3x12-above-bound-both-paged": (16384, (True, True)),
    "2dims-leaf-sum16-3x12-first-paged": (4096, (True, False)),
}
# name: (row tiles the candidates name, row tiles the kernel copies), a
# grid step and summed over the level's programs
PAGED_ROWS = {
    "2dims-leaf-count-c120-last-paged": (120, 120),
    "3dims-intersect-sum16-all-paged": (27, 1 + 4 + 9),
    "2dims-nofilter-count-pruned-first-paged": (5, 4),
    "2dims-leaf-count-3x12-both-paged": (72, 3 + 36),
    "2dims-nofilter-count-alternating-both-paged": (14, 7 + 3),
    "2dims-leaf-count-constant-first-paged": (12, 1),
    "1dim-nofilter-count-c1-paged": (1, 1),
    # programs of 16, 16 and 4 candidates: first indices 2, 1 and 1 runs
    "2dims-leaf-count-3x12-above-bound-both-paged": (72, 2 + 2 + 1 + 36),
    "2dims-leaf-sum16-3x12-first-paged": (36, 3),
}
FILTERS = {
    None: (None, 0, ()),
    "leaf": (("leaf", 0), 1, ()),
    "and": (("and", ("leaf", 0), ("leaf", 1)), 2, ()),
    "shift": (("shift", ("leaf", 0), 0), 1, (7,)),
}


def run_level(ex, words, case, monkeypatch):
    n_dims, filt_kind, with_sum, cand, bound = CASES[case]
    if bound is not None:
        monkeypatch.setattr(batch, "groupby_chunk_groups",
                            lambda n_planes: bound)
    if case in PAGED:
        monkeypatch.setattr(batch, "groupby_tile_plan",
                            lambda *shapes: PAGED[case])
    structure, n_filt, scalars = FILTERS[filt_kind]
    block = ex._shard_block(list(range(N_SHARDS)))
    import jax.numpy as jnp

    put = ex._leaf_put(block) or jnp.asarray

    def slots(host):
        out = np.zeros((block.padded,) + host.shape[1:], np.uint32)
        out[:N_SHARDS] = host
        return put(out)

    packed, layout = ex._groupby_level_enqueue(
        block, [slots(f) for f in words["filt"][:n_filt]], structure,
        list(scalars), [slots(d) for d in words["dims"][:n_dims]], cand,
        slots(words["planes"]) if with_sum else None,
        _AggField if with_sum else None,
    )
    got = _groupby_level_unpack(
        np.asarray(packed), layout, cand.shape[0], with_sum,
        DEPTH if with_sum else 0)
    host_filt = {None: None, "leaf": words["filt"][0],
                 "and": words["filt"][0] & words["filt"][1],
                 "shift": shifted(words["filt"][0], 7)}[filt_kind]
    want = oracle(words, n_dims, host_filt, cand, with_sum)
    return got, want, layout


@pytest.mark.parametrize("case", list(CASES))
@pytest.mark.parametrize("builder", ["local", "mesh"])
def test_level_program_matches_numpy(executors, words, builder, case,
                                     monkeypatch):
    before = groupby_metrics()
    (counts, agg), (want_counts, want_sums), layout = run_level(
        executors[builder], words, case, monkeypatch)
    after = groupby_metrics()
    assert tuple(after[k] - before[k] for k in (
        "paged_row_visits_total", "paged_row_copies_total")) == (
            PAGED_ROWS.get(case, (0, 0)))
    assert counts.tolist() == want_counts
    assert any(want_counts)
    if CASES[case][2]:
        assert level_sums(agg, len(want_sums)) == want_sums
    if CASES[case][4] is not None:
        assert len(layout) > 1   # chunked: concat + unpack covered


@pytest.mark.parametrize("case", [
    "2dims-intersect-count-c120", "3dims-nofilter-count-pruned-list",
    "2dims-leaf-count-3x12-both-paged",
    "2dims-nofilter-count-alternating-both-paged",
    "2dims-leaf-count-constant-first-paged", "1dim-nofilter-count-c1-paged",
    "2dims-leaf-count-3x12-above-bound-both-paged",
    "2dims-leaf-sum16-3x12-first-paged"])
def test_level_program_on_a_mesh_of_two_slots_a_device(executors, words,
                                                       case, monkeypatch):
    """The three shards are one slot a device on the mesh of eight; on
    two devices each holds two slots (the kernel's grid walks both
    before the psum), and the counts and sums are the same."""
    (counts, agg), (want, want_sums), _ = run_level(
        executors["mesh-2"], words, case, monkeypatch)
    assert counts.tolist() == want
    if CASES[case][2]:
        assert level_sums(agg, len(want_sums)) == want_sums


# levels no case above sends: candidates, who is paged, candidates a
# program, (row tiles named, copied)
OTHER_PAGED_ROWS = {
    # 10 surviving cities x 250: the first index changes 10 times
    "q3_2-second-level": (
        np.stack([np.repeat(np.arange(10) * 25, 250),
                  np.tile(np.arange(250), 10)], axis=1).astype(np.int32),
        (True, True), 8192, (5000, 2510)),
    "nothing-paged": (cross(10, 12), (False, False), 8192, (0, 0)),
    "no-candidate": (cross(10, 12)[:0], (True, True), 8192, (0, 0)),
}


@pytest.mark.parametrize("shape", list(PAGED_ROWS) + list(OTHER_PAGED_ROWS))
def test_paged_row_counters_count_the_runs_of_an_index(shape):
    """``groupby_paged_row_visits_total`` and ``_copies_total`` from the
    candidates alone: a copy a run of equal index in a paged column of
    a program's candidates."""
    if shape in PAGED_ROWS:
        cand, bound = CASES[shape][3:5]
        level = (cand, PAGED[shape][1], bound or 8192, PAGED_ROWS[shape])
    else:
        level = OTHER_PAGED_ROWS[shape]
    assert batch.groupby_paged_rows(*level[:3]) == level[3]


@pytest.mark.parametrize("builder", ["local", "mesh"])
def test_groupby_over_row_counts_the_executor_pads(tmp_path, builder,
                                                   monkeypatch):
    """An 8-row and a 2-row dimension and a Sum over 8 planes (depth 6):
    the row counts XLA would re-lay. The executor pads each matrix with
    zero rows (groupby_pad_rows); groups and sums are those of the
    columns."""
    from pilosa_tpu.shardwidth import SHARD_WIDTH
    from pilosa_tpu.storage import FieldOptions

    holder = Holder(str(tmp_path / "data")).open()
    idx = holder.create_index("i")
    f, g = idx.create_field("f"), idx.create_field("g")
    v = idx.create_field("v", FieldOptions(type="int", min=0, max=63))
    assert v.options.bit_depth == 6
    rng = np.random.default_rng(8)
    cols = np.concatenate([
        np.sort(rng.choice(SHARD_WIDTH, 60, replace=False))
        + shard * SHARD_WIDTH for shard in range(N_SHARDS)])
    f_rows = rng.integers(0, 8, cols.size)
    g_rows = rng.integers(0, 2, cols.size)
    values = rng.integers(0, 64, cols.size)
    want: dict = {}
    for c, fr, gr, val in zip(cols.tolist(), f_rows, g_rows, values):
        f.set_bit(int(fr), c)
        g.set_bit(int(gr), c)
        v.set_value(c, int(val))
        n, total = want.get((int(fr), int(gr)), (0, 0))
        want[(int(fr), int(gr))] = (n + 1, total + int(val))
    idx.mark_columns_exist(cols.tolist())
    ex = (Executor(holder) if builder == "local"
          else DistExecutor(holder, make_mesh()))
    matrix_rows = []

    def spy(build):
        def built(*args, **kwargs):
            out = build(*args, **kwargs)
            if out.ndim == 3:
                matrix_rows.append(out.shape[1])
            return out
        return built

    monkeypatch.setattr(batch, "stacked_matrix", spy(batch.stacked_matrix))
    monkeypatch.setattr(batch, "stacked_leaf", spy(batch.stacked_leaf))
    try:
        (groups,) = ex.execute(
            "i", 'GroupBy(Rows(f), Rows(g), aggregate=Sum(field="v"))')
        got = {tuple(fr["rowID"] for fr in gc.group): (gc.count, gc.sum)
               for gc in groups}
        assert got == want and len(want) > 8
        assert matrix_rows == [8 + 1, 2 + 1, 8 + 1]   # f, g, v's planes
    finally:
        holder.close()


# ------------------------------------------------------- the chunking rule


def programs_a_level(candidates: int, n_planes: int) -> int:
    return -(-candidates // batch.groupby_chunk_groups(n_planes))


@pytest.mark.parametrize("candidates,n_planes", [
    (10, 16 + 2),   # q2_passengers_sum, 128 slots
    (120, 0),       # q3 / q4, 128 slots; and each chip's 128 of 512
])
def test_one_program_a_level_at_the_cells_shapes(candidates, n_planes):
    # the rule reads static shapes, and the slot count is not one of them
    assert programs_a_level(candidates, n_planes) == 1


def test_candidate_bound_comes_from_the_accumulator_block():
    assert batch.groupby_chunk_groups(0) >= 4096
    assert batch.groupby_chunk_groups(16 + 2) >= 256
    for n_planes in (0, 16 + 2, 62 + 2):
        chunk = batch.groupby_chunk_groups(n_planes)
        assert chunk & (chunk - 1) == 0
        assert (chunk * max(n_planes, 1) * 128 * 4
                <= batch.GROUPBY_VMEM_BYTES // 8)
    assert not hasattr(batch, "GROUPBY_MASK_BUDGET_BYTES")


# dimension rows, filter leaves + planes: the tile's words, who is paged
TILE_PLANS = {
    "q3": ((10, 12), 1, 8192, (False, False)),
    "q4": ((10, 12), 2, 8192, (False, False)),
    "q2": ((10,), 1 + 18, 4096, (False,)),
    "one-row": ((3,), 0, WORDS_PER_SHARD, (False,)),
    "two-64-row-fields": ((65, 65), 1, 1024, (False, False)),
    # the 1000 brands would leave a tile of 128 words: paged, and the
    # seven years stay resident
    "brand-lookup": ((7, 1001), 1 + 18, 4096, (False, True)),
    "10k-rows": ((10001,), 0, WORDS_PER_SHARD, (True,)),
    "both-large": ((5001, 3001), 1, WORDS_PER_SHARD, (True, True)),
}


@pytest.mark.parametrize("plan", list(TILE_PLANS))
def test_tile_plan_fits_the_allowance_whatever_the_rows(plan):
    dim_rows, other_rows, want_tw, want_paged = TILE_PLANS[plan]
    tw, paged = batch.groupby_tile_plan(dim_rows, other_rows, 8,
                                        WORDS_PER_SHARD)
    assert (tw, paged) == (want_tw, want_paged)
    assert WORDS_PER_SHARD % tw == 0 and tw % 128 == 0
    held = other_rows + sum(1 if p else n for n, p in zip(dim_rows, paged))
    assert 2 * held * 8 * tw * 4 <= 3 * batch.GROUPBY_VMEM_BYTES // 8


@pytest.mark.parametrize("rows", [1, 2, 3, 8, 10, 12, 16, 40, 1000])
def test_a_dimension_is_padded_off_the_row_counts_xla_re_lays(rows):
    padded = rows + batch.groupby_pad_rows(rows)
    assert padded > 2 and padded % 8 != 0
    assert padded - rows <= 2 and (padded == rows) == (
        rows > 2 and rows % 8 != 0)


# ------------------------------------------------------- the served path


def _metric(text: str, name: str) -> float:
    m = re.search(rf"^pilosa_tpu_{name} (\S+)$", text, re.M)
    assert m, name
    return float(m.group(1))


def test_dashboard_templates_dispatch_one_program_a_groupby(tmp_path):
    """The four templates of the dashboard mix over HTTP at rehearsal
    scale: TopN dispatches no GroupBy program, each GroupBy exactly one,
    read from the /metrics series PERF.md quotes."""
    import sys

    sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
    from harness import traffic
    from pilosa_tpu.server import Server, ServerConfig

    with open(traffic.mix_path(os.path.join(ROOT, "benchmarks"),
                               "dashboard")) as f:
        mix = json.load(f)
    s = Server(ServerConfig(
        data_dir=str(tmp_path / "node"), port=0, name="t",
        anti_entropy_interval=0, heartbeat_interval=0,
    )).open()
    try:
        base = uri(s)
        req("POST", f"{base}/index/rides", {})
        rng = np.random.default_rng(5)
        cols = np.arange(0, 2 * (1 << 20), 997)
        for field, n_rows in (("cab_type", 3), ("passenger_count", 10),
                              ("pickup_month", 12), ("pickup_year", 7),
                              ("dist_miles", 16)):
            req("POST", f"{base}/index/rides/field/{field}", {})
            req("POST", f"{base}/index/rides/field/{field}/import",
                {"rows": rng.integers(0, n_rows, cols.size).tolist(),
                 "columns": cols.tolist()})
        req("POST", f"{base}/index/rides/field/total_amount_cents",
            {"options": {"type": "int", "min": 0, "max": 65535}})
        req("POST", f"{base}/index/rides/field/total_amount_cents/import-value",
            {"columns": cols.tolist(),
             "values": rng.integers(0, 65536, cols.size).tolist()})

        def scrape():
            text = req("GET", f"{base}/metrics", raw=True)
            text = text.decode() if isinstance(text, bytes) else text
            return {n: _metric(text, n) for n in (
                "groupby_levels_total", "groupby_level_programs_total",
                "stage_device_dispatch_total", "stage_http_query_total")}

        want_programs = {"q1_cabs": 0, "q2_passengers_sum": 1,
                         "q3_passengers_months": 1,
                         "q4_passengers_months_dist": 1}
        for name in mix["groups"][0]["rotation"]:
            t = mix["templates"][name]
            sem = dict(t, filter=[(f, 2) for f, _ in t.get("filter", ())])
            before = scrape()
            out = req("POST", f"{base}/index/rides/query",
                      traffic.render(sem).encode())
            assert out["results"][0], name
            after = scrape()
            delta = {k: after[k] - before[k] for k in after}
            assert delta["groupby_level_programs_total"] == \
                want_programs[name], name
            assert delta["groupby_levels_total"] == want_programs[name]
            if want_programs[name]:
                assert delta["stage_device_dispatch_total"] == 1, name
    finally:
        s.close()


# ------------------------------------- compiled for the chip, without one
#
# The TPU's compiler is installed here and compiles for a described v5e
# (on-chip-measurement guide, section 2). What interpret mode cannot
# show: that Mosaic takes the kernel at the cells' real shapes, and that
# the [n, S, W] view the kernel is handed is a bitcast of the resident
# matrix (no re-laid copy of a dimension matrix in HBM, temp bytes 0).


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler here, or its lock is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


# filter leaves, structure, dimension rows, planes, padded candidates,
# shard slots a chip
CELL_LEVELS = {
    "q2_passengers_sum": (1, ("leaf", 0), (10,), DEPTH + 2, 16, 128),
    "q3_passengers_months": (1, ("leaf", 0), (10, 12), 0, 128, 128),
    "q4_passengers_months_dist": (
        2, ("and", ("leaf", 0), ("leaf", 1)), (10, 12), 0, 128, 128),
    # q-flight's Q3.2 (58 shards in 64 slots), both cities paged: its
    # second level's 2,500 candidates, where a row tile is copied only
    # when its index changes (ISSUE 43), and a program of its last
    # level's 600 with lo_revenue's 26 planes
    "q3_2_cities": (
        2, ("and", ("leaf", 0), ("leaf", 1)), (250, 250), 0, 4096, 64),
    "q3_2_cities_years_sum": (
        2, ("and", ("leaf", 0), ("leaf", 1)), (250, 250, 6), 24 + 2, 256,
        64),
}
# the same, then shard slots: row counts no cell has, each matrix as the
# executor pads it
OTHER_LEVELS = {
    **{f"{n}-row-dimension": (0, None, (n + batch.groupby_pad_rows(n), 12),
                              0, 128, 128) for n in (1, 2, 8, 40)},
    "16-planes": (1, ("leaf", 0), (10,), 16, 16, 128),
    "8-planes-two-64-row-fields": (
        1, ("leaf", 0), (64 + batch.groupby_pad_rows(64),) * 2, 8, 256, 128),
    # paged: the rows stay in HBM, whatever their count
    "10001-rows": (0, None, (10001,), 0, 8192, 8),
    "7-years-1000-brands-sum": (
        1, ("leaf", 0), (7, 1000 + batch.groupby_pad_rows(1000)), DEPTH + 2,
        256, 8),
}


def _level_args(slots, level, sharding_of):
    import jax
    import jax.numpy as jnp

    n_filt, _, dims, n_planes, c_pad = level[:5]

    def sds(shape, dtype, sharded=True):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=sharding_of(sharded))

    return (
        [sds((slots, WORDS_PER_SHARD), jnp.uint32)] * n_filt
        + [sds((slots, n, WORDS_PER_SHARD), jnp.uint32) for n in dims]
        + ([sds((slots, n_planes + batch.groupby_pad_rows(n_planes),
                 WORDS_PER_SHARD), jnp.uint32)] if n_planes else [])
        + [sds((len(dims) * c_pad,), jnp.int32, sharded=False)]
    )


def _assert_reads_rows_in_place(compiled):
    text = compiled.as_text()
    assert 'custom_call_target="tpu_custom_call"' in text
    # no operand (the words are the program's only u32) is re-laid
    assert not re.search(r"= u32\[[^=]*? (copy|transpose)\(", text)
    assert compiled.memory_analysis().temp_size_in_bytes == 0


def _compile_for_one_chip(topo, level, slots, monkeypatch):
    from jax.sharding import SingleDeviceSharding

    monkeypatch.setattr(batch, "_pallas_interpret", lambda: False)
    monkeypatch.setattr(batch, "_LOCAL_JIT_CACHE", {})
    one_chip = SingleDeviceSharding(topo.devices[0])
    n_filt, structure, dims, n_planes = level[:4]
    fn = batch.local_groupby_level_fn(structure, n_filt, 0, len(dims),
                                      n_planes)
    return fn.lower(*_level_args(slots, level, lambda _: one_chip)).compile()


@pytest.mark.parametrize("level", list(CELL_LEVELS))
def test_cell_level_compiles_for_one_v5e_chip(topo, level, monkeypatch):
    cell = CELL_LEVELS[level]
    assert any(batch.groupby_tile_plan(
        cell[2], cell[0] + cell[3], 8, WORDS_PER_SHARD)[1]) == (
            level.startswith("q3_2"))
    _assert_reads_rows_in_place(
        _compile_for_one_chip(topo, cell, cell[5], monkeypatch))


@pytest.mark.parametrize("level", list(OTHER_LEVELS))
def test_any_row_count_compiles_for_one_v5e_chip(topo, level, monkeypatch):
    """No row count has XLA re-lay a matrix for the kernel, and none
    outgrows VMEM: what Pallas' interpreter, which has none, cannot
    show."""
    _assert_reads_rows_in_place(_compile_for_one_chip(
        topo, OTHER_LEVELS[level], OTHER_LEVELS[level][5], monkeypatch))


@pytest.mark.parametrize("level", list(CELL_LEVELS))
def test_cell_level_compiles_for_the_four_chip_mesh(topo, level,
                                                    monkeypatch):
    from jax.sharding import Mesh, NamedSharding
    from pilosa_tpu.parallel import dist
    from pilosa_tpu.parallel.mesh import SHARDS_AXIS, replicated, shards_spec

    monkeypatch.setattr(batch, "_pallas_interpret", lambda: False)
    monkeypatch.setattr(dist, "_DIST_JIT_CACHE", {})
    mesh = Mesh(np.asarray(topo.devices), (SHARDS_AXIS,))
    n_filt, structure, dims, n_planes, _, slots = CELL_LEVELS[level]
    # on the chip the flat mesh keeps its varying-axes check
    fn = dist._dist_groupby_level_fn(mesh, structure, n_filt, 0, len(dims),
                                     n_planes)

    def sharding_of(sharded):
        return (NamedSharding(mesh, shards_spec(mesh)) if sharded
                else replicated(mesh))

    compiled = fn.lower(
        *_level_args(mesh.size * slots, CELL_LEVELS[level],
                     sharding_of)).compile()
    _assert_reads_rows_in_place(compiled)
    assert "all-reduce" in compiled.as_text()


# The expansion of a sparse residency miss (ISSUE 38) is the tree's other
# Pallas kernel; it is compiled here because one file describes the chip
# (a second file could go to another worker, whose fixture would skip).

@pytest.mark.parametrize("n_rows", [128, 8, 2])
def test_sparse_miss_expansion_compiles_for_one_v5e_chip(topo, n_rows,
                                                         monkeypatch, request):
    """Every bucket of the cells' 128-slot leaves, and of two leaves
    smaller than a block of eight slot rows: copies from HBM into scalar
    memory, strided reads of the scratch, no temporary beside the leaf
    (what Pallas' interpreter cannot show)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from pilosa_tpu.roaring import kernels
    from pilosa_tpu.storage import residency

    monkeypatch.setattr(residency, "pallas_interpret", lambda: False)
    # a trace of these shapes made for the CPU by an earlier test of this
    # process holds the interpreter's call, and a later test must not
    # find the chip's: both ways the jit's cache is emptied
    residency._expand_rows.clear_cache()
    request.addfinalizer(residency._expand_rows.clear_cache)
    one_chip = SingleDeviceSharding(topo.devices[0])
    buckets = kernels.sparse_buckets(n_rows)
    assert buckets[0] == 8192 and buckets[-1] == n_rows * 4096
    for n_pad in buckets:
        packed = jax.ShapeDtypeStruct(
            (kernels.sparse_packed_len(n_rows, n_pad),), jnp.uint32,
            sharding=one_chip)
        compiled = residency._expand_rows.lower(
            packed, n_rows=n_rows, n_pad=n_pad).compile()
        assert 'custom_call_target="tpu_custom_call"' in compiled.as_text()
        assert compiled.memory_analysis().temp_size_in_bytes == 0
        assert compiled.memory_analysis().output_size_in_bytes == (
            n_rows * WORDS_PER_SHARD * 4)


@pytest.mark.parametrize("n_rows", [512, 8])
def test_sparse_miss_expansion_compiles_for_the_four_chip_mesh(
        topo, n_rows, monkeypatch):
    """ISSUE 39: the mesh program of every bucket of a 512-slot leaf (128
    slot rows a chip: the one-chip cell's buckets) and of the rehearsal's
    8-slot leaf. Every chip runs the kernel on its own share: the packed
    lists come in split over the shard axis, the leaf goes out split by
    slot rows, and nothing crosses the mesh."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from pilosa_tpu.parallel import dist
    from pilosa_tpu.parallel.mesh import SHARDS_AXIS
    from pilosa_tpu.roaring import kernels
    from pilosa_tpu.storage import residency

    monkeypatch.setattr(residency, "pallas_interpret", lambda: False)
    mesh = Mesh(np.asarray(topo.devices), (SHARDS_AXIS,))
    monkeypatch.setattr(dist, "_DIST_JIT_CACHE", {})
    sharded = NamedSharding(mesh, P(SHARDS_AXIS))
    rows = n_rows // mesh.size
    buckets = kernels.sparse_buckets(rows)
    assert buckets == kernels.sparse_buckets(128) if n_rows == 512 else (
        buckets == (8192,))
    for n_pad in buckets:
        packed = jax.ShapeDtypeStruct(
            (mesh.size * kernels.sparse_packed_len(rows, n_pad),),
            jnp.uint32, sharding=sharded)
        compiled = dist._dist_expand_fn(mesh, n_rows, n_pad).lower(
            packed).compile()
        text = compiled.as_text()
        assert 'custom_call_target="tpu_custom_call"' in text
        assert "jit_dist_expand_rows" in text
        for collective in ("all-reduce", "all-gather", "all-to-all",
                           "collective-permute", "reduce-scatter"):
            assert collective not in text
        assert compiled.memory_analysis().temp_size_in_bytes == 0
        # a chip's share of the leaf
        assert compiled.memory_analysis().output_size_in_bytes == (
            rows * WORDS_PER_SHARD * 4)
        assert compiled.output_shardings.is_equivalent_to(sharded, 2)
