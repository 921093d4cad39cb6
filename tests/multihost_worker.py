"""Multi-host worker: one process of a 2-process CPU-backend cluster.

Launched by tests/test_multihost.py with JAX_PLATFORMS=cpu and 4 virtual
devices per process. Each process opens an identical holder, joins the
global mesh via initialize_distributed, and drives the SAME query
sequence through a DistExecutor (the SPMD contract: every host executes
every query; each host decodes and uploads ONLY the shard slots its
devices own — ShardAssignment.local_slots). Results are replicated
scalars, asserted against a host oracle computed from the same
deterministic data.

Usage: python multihost_worker.py <coordinator_port> <process_id>
"""

import sys
import tempfile

COORD_PORT, PROC_ID = int(sys.argv[1]), int(sys.argv[2])

import jax  # noqa: E402

from pilosa_tpu.parallel.mesh import initialize_distributed  # noqa: E402

initialize_distributed(
    coordinator=f"127.0.0.1:{COORD_PORT}", num_processes=2,
    process_id=PROC_ID,
)
assert jax.process_count() == 2, jax.process_count()
assert len(jax.devices()) == 8, jax.devices()

from pilosa_tpu.parallel.dist import DistExecutor  # noqa: E402
from pilosa_tpu.parallel.mesh import make_mesh  # noqa: E402
from pilosa_tpu.shardwidth import SHARD_WIDTH  # noqa: E402
from pilosa_tpu.storage import FieldOptions, Holder  # noqa: E402

N_SHARDS = 8


def build(holder):
    """Deterministic dataset spanning N_SHARDS shards; returns the
    python-set oracle {row: set(cols)} and {col: value}."""
    idx = holder.create_index("repos", track_existence=False)
    f = idx.create_field("f")
    rows = {1: set(), 2: set(), 3: set()}
    for shard in range(N_SHARDS):
        base = shard * SHARD_WIDTH
        for k in range(40):
            rows[1].add(base + 7 * k)
            if k % 2 == 0:
                rows[2].add(base + 7 * k)
            if k < 30:  # distinct row sizes: TopN ordering is exact
                rows[3].add(base + 11 * k + 1)
    for row, cols in rows.items():
        for c in sorted(cols):
            f.set_bit(row, c)
    v = idx.create_field("v", FieldOptions(type="int", min=0, max=1000))
    values = {}
    for shard in range(N_SHARDS):
        base = shard * SHARD_WIDTH
        for k in range(10):
            values[base + 13 * k] = (shard * 31 + k * 7) % 1000
    for c, val in values.items():
        v.set_value(c, val)
    return rows, values


with tempfile.TemporaryDirectory() as tmp:
    holder = Holder(tmp).open()
    rows, values = build(holder)
    ex = DistExecutor(holder, make_mesh())

    got = ex.execute("repos", "Count(Intersect(Row(f=1), Row(f=2)))")[0]
    want = len(rows[1] & rows[2])
    assert got == want, (got, want)

    got = ex.execute("repos", "Count(Union(Row(f=1), Row(f=3)))")[0]
    want = len(rows[1] | rows[3])
    assert got == want, (got, want)

    (s,) = ex.execute("repos", 'Sum(field="v")')
    assert (s.value, s.count) == (sum(values.values()), len(values)), s

    # TopN: phase-1 candidate counts via cross-host countrows psum,
    # phase-2 exact recount — row sizes are distinct by construction
    (pairs,) = ex.execute("repos", "TopN(f, n=2)")
    sizes = sorted(((len(c), r) for r, c in rows.items()), reverse=True)
    got = [(p.id, p.count) for p in pairs]
    want = [(r, n) for n, r in sizes[:2]]
    assert got == want, (got, want)

    # GroupBy over one dimension, cross-host reduced
    (groups,) = ex.execute("repos", "GroupBy(Rows(f))")
    got = {g.group[0]["rowID"]: g.count for g in groups}
    assert got == {r: len(c) for r, c in rows.items()}, got

    # write-through: the contract is that a shard's write is applied on
    # (at least) the process owning that shard's slot; here both
    # replicated holders apply it, which covers the owner. Resident
    # sharded leaves are PATCHED per addressable piece
    # (batch._patch_sharded, a single-device scatter + handle reassembly,
    # no collective) — asserted via residency counters: the write must
    # bump `updates` and the re-query must re-decode nothing.
    from pilosa_tpu.storage import residency  # noqa: E402

    cache = residency.global_row_cache()
    misses_before = cache.misses
    updates_before = cache.updates
    new_col = 5 * SHARD_WIDTH + 997  # shard 5: process 1's half
    holder.index("repos").field("f").set_bit(1, new_col)
    holder.index("repos").field("f").set_bit(2, new_col)
    if PROC_ID == 1:  # shard 5's slot is addressable on process 1 only
        assert cache.updates >= updates_before + 2, (
            "multi-host write did not patch resident leaves in place",
            updates_before, cache.updates,
        )
    else:  # non-owner: nothing local to patch, and nothing purged
        assert cache.updates == updates_before, (
            updates_before, cache.updates,
        )
    got = ex.execute("repos", "Count(Intersect(Row(f=1), Row(f=2)))")[0]
    want = len((rows[1] | {new_col}) & (rows[2] | {new_col}))
    assert got == want, (got, want)
    assert cache.misses == misses_before, (
        "write purged resident leaves: re-query re-decoded",
        misses_before, cache.misses,
    )

    holder.close()

print(f"MULTIHOST_WORKER_{PROC_ID}_OK", flush=True)
