"""Benchmark: the north-star metric through the REAL framework path.

Headline number: ``Count(Intersect(Row(a=k), Row(b=j)))`` — the exact
BASELINE.json op — executed end-to-end by ``Executor.submit``: PQL parse
→ expression compile → residency-cached stacked leaves in HBM → micro-
batched fused programs (8 queries per dispatch) → pipelined readback —
at 1B columns per query (1024 shards), with the dataset built through
the storage tree (holder → field → view → fragment bulk_import). Also
measured and printed: the raw fused-kernel ceiling (the same
bitwise+popcount with zero framework around it) and the executor/kernel
ratio.

Method notes:
- The device holds 2·K_ROWS distinct 1B-column stacked leaves (2 GiB)
  via the residency LRU, and the executor path cycles through all
  K_ROWS² row pairs, so every query streams real data from HBM.
- Dispatch is pipelined (Executor.submit): enqueue all iterations, then
  force completion by resolving the LAST Deferred (single-device streams
  are ordered). The one blocking final readback is amortized over ITERS;
  a trivial blocking round trip is reported beside it as rtt_floor_ms.
- best-of-trials to damp host scheduling noise.
- Runs on a TPU only: on any other platform it exits non-zero instead of
  timing XLA's CPU backend under a device metric's name.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N,
   "platform": ..., "device_kind": ..., "device_count": N, ...}

vs_baseline compares against a single-CPU-node reference executing the
same logical op with numpy (np.bitwise_and + np.bitwise_count) on this
machine — the reference repo publishes no numbers and its mount is empty
(BASELINE.md), so the CPU baseline is measured, not quoted.
"""

from __future__ import annotations

import argparse
import itertools
import json
import sys
import tempfile
import time

import numpy as np

METRIC_NAME = "pql_intersect_count_cols_per_sec_1B"
METRIC_UNIT = "columns/sec/chip"

N_COLS = 1 << 30  # one billion columns per query
K_ROWS = 8  # distinct rows per field (2 GiB HBM in stacked leaves)

# Roofline reference: HBM bandwidth per chip, keyed by the device_kind JAX
# reports. Count(Intersect(a, b)) streams both operands from HBM once —
# 2 × n_cols/8 = n_cols/4 bytes per query — and writes back O(1), so
# frac_hbm_peak ≈ how close the path runs to the bandwidth bound (2 loads
# per AND+popcount: firmly memory-bound, roofline is the right ceiling).
# A kind that is not in the table is an error, never a default.
HBM_PEAK_BYTES_PER_SEC = {
    # Google Cloud documentation, "TPU v5e": 16 GB HBM2e, 819 GB/s
    "TPU v5 lite": 819e9,
}
BITS_PER_ROW_SHARD = 512  # set bits per (row, shard); throughput is
                          # density-independent (dense words on device)
KERNEL_ITERS = 256
EXEC_ITERS = 2048  # = 8 × KERNEL_ITERS: the kernel computes all K_ROWS
                   # row-queries per call, so equal-depth loops would
                   # amortize the final readback 8× better per COLUMN on
                   # the kernel side and the executor/kernel ratio would
                   # mostly measure that artifact. 8:1 equalizes the
                   # readback's share per column.
TRIALS = 8  # best-of: the host shares its cores, so a trial's wall wanders


# ------------------------------------------------------------ raw kernel path


def _make_rows(k: int, n_words: int, seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return rng.integers(0, 1 << 32, size=(k, n_words), dtype=np.uint32)


def bench_kernel(a_host: np.ndarray, b_host: np.ndarray):
    """Ceiling: the fused intersect+count kernel with no framework around
    it, pipelined. Returns (dt_per_call, counts)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    @jax.jit
    def batch_intersect_count(a, b):
        return jnp.sum(lax.population_count(a & b).astype(jnp.uint32), axis=1)

    a = jax.device_put(a_host)
    b = jax.device_put(b_host)
    jax.block_until_ready((a, b))

    ref = np.asarray(batch_intersect_count(a, b))  # compile

    best = float("inf")
    for _ in range(TRIALS):
        t0 = time.perf_counter()
        out = None
        for _ in range(KERNEL_ITERS):
            out = batch_intersect_count(a, b)
        np.asarray(out)  # stream-ordered: last done => all done
        best = min(best, (time.perf_counter() - t0) / KERNEL_ITERS)
    return best, ref


def bench_cpu_reference(a: np.ndarray, b: np.ndarray, iters: int = 3) -> tuple[float, np.ndarray]:
    """Single-node CPU doing the same logical work (numpy vectorized and
    cache-blocked — generous to the baseline: the Go reference walks
    roaring containers per shard)."""
    k, n_words = a.shape

    def run() -> np.ndarray:
        out = np.zeros(k, np.uint64)
        chunk = 1 << 22
        for i in range(0, n_words, chunk):
            out += np.bitwise_count(a[:, i : i + chunk] & b[:, i : i + chunk]).sum(
                axis=1, dtype=np.uint64
            )
        return out

    ref = run().astype(np.uint32)
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        run()
        best = min(best, time.perf_counter() - t0)
    return best, ref


# -------------------------------------------------------------- executor path


def build_holder(tmp: str, n_shards: int):
    """The benchmark dataset through the real write path: K_ROWS rows in
    each of fields a/b, one bulk_import per (field, shard)."""
    from pilosa_tpu.shardwidth import SHARD_WIDTH
    from pilosa_tpu.storage import Holder
    from pilosa_tpu.storage.view import VIEW_STANDARD

    holder = Holder(tmp).open()
    idx = holder.create_index("bench")
    rng = np.random.default_rng(7)
    rows = np.repeat(
        np.arange(1, K_ROWS + 1, dtype=np.uint64), BITS_PER_ROW_SHARD
    )
    for fname in ("a", "b"):
        f = idx.create_field(fname)
        view = f.view(VIEW_STANDARD, create=True)
        for shard in range(n_shards):
            cols = rng.integers(0, SHARD_WIDTH, rows.size, dtype=np.uint64)
            view.fragment(shard, create=True).bulk_import(rows, cols)
    return holder, idx


def _combo(g: int) -> tuple[int, int]:
    """Query-pair schedule: walk all K_ROWS² row pairs, so the stream
    reads all 2·K_ROWS resident leaves rather than one pair."""
    c = g % (K_ROWS * K_ROWS)
    return 1 + c // K_ROWS, 1 + c % K_ROWS


def oracle_count(idx, k: int, j: int, n_shards: int) -> int:
    from pilosa_tpu.storage.view import VIEW_STANDARD

    fa = idx.field("a").view(VIEW_STANDARD)
    fb = idx.field("b").view(VIEW_STANDARD)
    total = 0
    for shard in range(n_shards):
        aw = fa.fragment(shard).row_words(k)
        bw = fb.fragment(shard).row_words(j)
        total += int(np.bitwise_count(aw & bw).sum())
    return total


def bench_executor(holder, idx, n_shards: int):
    """Sustained throughput of the full query path, pipelined via
    Executor.submit. Returns (dt_per_query, microbatch, ok)."""
    from pilosa_tpu.executor import Executor

    ex = Executor(holder)

    def pql(k: int, j: int) -> str:
        return f"Count(Intersect(Row(a={k}), Row(b={j})))"

    # warm: decode + upload every row's stacked leaf, compile the B=1
    # program (sync path) and the micro-batched program (one full flush)
    for k in range(1, K_ROWS + 1):
        ex.execute("bench", pql(k, k))
    g = itertools.count(0)
    warm = [ex.submit("bench", pql(*_combo(next(g))))[0]
            for _ in range(ex.microbatch_max)]
    warm[-1].result()

    best = float("inf")
    for _ in range(TRIALS):
        t0 = time.perf_counter()
        d = None
        for _ in range(EXEC_ITERS):
            d = ex.submit("bench", pql(*_combo(next(g))))[0]
        d.result()  # stream-ordered: last done => all done
        best = min(best, (time.perf_counter() - t0) / EXEC_ITERS)

    # correctness against the host oracle on fresh combos (outside timing)
    ok = True
    for _ in range(3):
        k, j = _combo(next(g))
        got = ex.execute("bench", pql(k, j))[0]
        ok = ok and got == oracle_count(idx, k, j, n_shards)
    return best, ex.microbatch_max, ok


def rtt_floor_ms() -> float:
    """Median wall time of a trivial blocking device round trip — the
    share of each trial spent on the single final readback."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x, s: jnp.sum(x) + s)
    x = jax.device_put(np.zeros(8, np.int32))
    samples = []
    for i in range(8):
        t0 = time.perf_counter()
        int(f(x, i))
        samples.append(time.perf_counter() - t0)
    return round(float(np.median(samples)) * 1e3, 1)


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--shards", type=int, default=N_COLS >> 20,
                        help="shards per query (default: 1024 = 1B cols)")
    args = parser.parse_args()
    n_shards = args.shards
    n_cols = n_shards << 20
    n_words = n_cols // 32

    from pilosa_tpu.utils import compile_cache

    compile_cache.configure()
    import jax

    devices = jax.devices()
    platform, kind = devices[0].platform, devices[0].device_kind
    if platform != "tpu":
        sys.exit(f"bench.py measures a TPU; JAX found platform {platform!r} "
                 f"({kind!r}). Nothing was measured.")
    if kind not in HBM_PEAK_BYTES_PER_SEC:
        sys.exit(f"bench.py has no HBM peak for device_kind {kind!r}; add it "
                 "to HBM_PEAK_BYTES_PER_SEC with its source.")
    hbm_peak = HBM_PEAK_BYTES_PER_SEC[kind]
    a = _make_rows(K_ROWS, n_words, seed=1)
    b = _make_rows(K_ROWS, n_words, seed=2)
    kernel_dt, kernel_ref = bench_kernel(a, b)
    cpu_dt, cpu_ref = bench_cpu_reference(a, b)
    if not np.array_equal(kernel_ref, cpu_ref):
        raise AssertionError(f"kernel mismatch tpu={kernel_ref} cpu={cpu_ref}")
    del a, b

    with tempfile.TemporaryDirectory() as tmp:
        holder, idx = build_holder(tmp, n_shards)
        exec_dt, microbatch, ok = bench_executor(holder, idx, n_shards)
        holder.close()
    if not ok:
        raise AssertionError("executor result mismatch vs host oracle")

    exec_cols_per_sec = n_cols / exec_dt
    kernel_cols_per_sec = K_ROWS * n_cols / kernel_dt
    cpu_dt_per_col = cpu_dt / (K_ROWS * n_cols)
    # each column costs 2 bits = 1/4 byte of HBM traffic (both operands)
    exec_hbm = exec_cols_per_sec / 4
    kernel_hbm = kernel_cols_per_sec / 4
    print(
        json.dumps(
            {
                "metric": METRIC_NAME,
                "value": round(exec_cols_per_sec, 1),
                "unit": METRIC_UNIT,
                "vs_baseline": round(cpu_dt_per_col * exec_cols_per_sec, 2),
                "kernel_cols_per_sec": round(kernel_cols_per_sec, 1),
                "executor_vs_kernel": round(
                    exec_cols_per_sec / kernel_cols_per_sec, 3
                ),
                "hbm_bytes_per_sec": round(exec_hbm, 1),
                "kernel_hbm_bytes_per_sec": round(kernel_hbm, 1),
                "frac_hbm_peak": round(exec_hbm / hbm_peak, 3),
                "frac_hbm_peak_kernel": round(kernel_hbm / hbm_peak, 3),
                "platform": platform,
                "device_kind": kind,
                "device_count": len(devices),
                "kernel": "xla",
                "path": "executor.submit",
                "microbatch": microbatch,
                "iters": EXEC_ITERS,
                "rtt_floor_ms": rtt_floor_ms(),
            }
        )
    )


if __name__ == "__main__":
    main()
