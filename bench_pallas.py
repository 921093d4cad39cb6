"""Same-run Pallas-vs-XLA comparison for the fused intersect-count op.

Settles with data whether a hand-written Pallas kernel beats the XLA
kernel whenever the latter sits below ~0.8 of the HBM roofline. This
harness measures, in ONE process on the chip (a host that shares its
cores makes runs wander — only same-run ratios mean anything):

  1. the XLA fused kernel (the bench.py ceiling op, plus a salt operand):
     per-row sum(popcount(a & (b ^ salt))) over uint32[R, W];
  2. a Pallas grid kernel for the same op at several VMEM block sizes
     (R-row operand blocks, grid over the word axis, accumulating
     per-row partial counts in the revisited output block).

Timing is INTERLEAVED: each trial runs one pipelined pass of every
variant back-to-back, so all variants sample the same seconds of host
noise; best-of-TRIALS per variant.

History: the round-2 measurement (README "Kernel strategy") found
parity and the Pallas path was retired; re-run this harness when the op
or toolchain changes.

Prints one JSON line per variant; correctness is asserted against the
XLA reference counts before any timing is reported. A variant that fails
to compile or counts wrong prints an error line, the remaining variants
still compare, and the process exits non-zero.

Operands are generated ON DEVICE (jax.random.bits) rather than uploaded:
two device-side PRNG programs instead of a 2 GiB host→device transfer.
Correctness gating is two-level: the XLA kernel's counts are pinned
against numpy at a small shape (1 MiB slice readback), and every Pallas
variant must match the XLA kernel's counts at the full shape.
"""

from __future__ import annotations

import json
import sys
import time

import numpy as np


def _stage(msg: str) -> None:
    """Progress marker on stderr."""
    print(f"[bench_pallas +{time.monotonic() - _T0:.1f}s] {msg}",
          file=sys.stderr, flush=True)


_T0 = time.monotonic()

R = 8
N_COLS = 1 << 30
W = N_COLS // 32  # 2^25 words per row
ITERS = 64
TRIALS = 6


def pallas_intersect_count(block_w: int, rows: int = R, words: int = W,
                           interpret: bool = False):
    """Pallas grid kernel for per-row sum(popcount(a & (b ^ salt))).
    ``interpret=True`` runs the kernel logic on any backend (the CI test
    pins it against a numpy oracle without TPU hardware)."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def kernel(salt_ref, a_ref, b_ref, out_ref):
        w = pl.program_id(0)
        s = salt_ref[0]
        x = a_ref[:] & (b_ref[:] ^ s)
        c = jnp.sum(lax.population_count(x).astype(jnp.int32), axis=1,
                    keepdims=True)

        @pl.when(w == 0)
        def _():
            out_ref[:] = c

        @pl.when(w != 0)
        def _():
            out_ref[:] = out_ref[:] + c

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(words // block_w,),
        in_specs=[
            pl.BlockSpec((rows, block_w), lambda w, s: (0, w)),
            pl.BlockSpec((rows, block_w), lambda w, s: (0, w)),
        ],
        out_specs=pl.BlockSpec((rows, 1), lambda w, s: (0, 0)),
    )
    return jax.jit(
        lambda a, b, salt: pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct((rows, 1), jnp.int32),
            grid_spec=grid_spec,
            interpret=interpret,
        )(salt, a, b)
    )


class Variant:
    """One kernel variant: compile + correctness-gate up front, then the
    harness interleaves timing passes round-robin across variants so
    every variant samples the SAME seconds of host noise."""

    def __init__(self, fn, name, wrap):
        self.fn, self.name, self.wrap = fn, name, wrap
        self.salt = 0
        self.best = float("inf")
        self.ok = False

    def compile_and_gate(self, a, b, expect=None):
        """Compile + reference counts (BEFORE any timing is reported — a
        wrong variant prints an error line and no numbers). The remaining
        variants still compare; main() exits non-zero for the failure."""
        try:
            ref = np.asarray(self.fn(a, b, self.wrap(self.salt)))
        except Exception as e:  # noqa: BLE001 — report and keep comparing
            print(json.dumps({
                "variant": self.name, "error": f"{type(e).__name__}: {e}"
            }), flush=True)
            return None
        if expect is not None and not np.array_equal(
            ref.ravel().astype(np.int64), expect.astype(np.int64)
        ):
            print(json.dumps({
                "variant": self.name,
                "error":
                    f"wrong counts: {ref.ravel().tolist()} != {expect.tolist()}",
            }), flush=True)
            return None
        self.salt += 1
        self.ok = True
        return ref.ravel()

    def timed_pass(self, a, b):
        """One pipelined pass of ITERS calls; keeps the best per-call dt.
        cols_per_sec counts all R row-queries per call, the same unit as
        bench.py's kernel_cols_per_sec (K_ROWS · n_cols / dt)."""
        t0 = time.perf_counter()
        out = None
        for _ in range(ITERS):
            out = self.fn(a, b, self.wrap(self.salt))
            self.salt += 1
        np.asarray(out)  # stream-ordered: last done => all done
        self.best = min(self.best, (time.perf_counter() - t0) / ITERS)

    def report(self, hbm_peak: float) -> None:
        rate = R * N_COLS / self.best
        print(json.dumps({
            "variant": self.name, "cols_per_sec": round(rate, 1),
            "hbm_bytes_per_sec": round(rate / 4, 1),
            "frac_hbm_peak": round((rate / 4) / hbm_peak, 3),
            "iters": ITERS, "trials": TRIALS, "schedule": "interleaved",
        }), flush=True)


def main() -> None:
    import jax
    import jax.numpy as jnp
    from jax import lax, random

    from bench import HBM_PEAK_BYTES_PER_SEC

    kind = jax.devices()[0].device_kind
    if kind not in HBM_PEAK_BYTES_PER_SEC:
        sys.exit(f"bench_pallas.py has no HBM peak for device_kind {kind!r} "
                 "(bench.HBM_PEAK_BYTES_PER_SEC)")
    hbm_peak = HBM_PEAK_BYTES_PER_SEC[kind]
    _stage("generating operands on device")
    bits = jax.jit(lambda k: random.bits(k, (R, W), jnp.uint32))
    a = bits(random.key(1))
    b = bits(random.key(2))
    jax.block_until_ready((a, b))

    @jax.jit
    def xla_kernel(a, b, salt):
        return jnp.sum(
            lax.population_count(a & (b ^ salt)).astype(jnp.uint32), axis=1
        )

    # small-shape numpy gate: the same fused op on a 1 MiB slice readback
    # pins the XLA kernel against the host before the full-shape ratios
    # (full operands never leave the device).
    _stage("small-shape numpy correctness gate")
    w_small = 1 << 15
    a_s = np.asarray(a[:, :w_small])
    b_s = np.asarray(b[:, :w_small])
    got = np.asarray(xla_kernel(a[:, :w_small], b[:, :w_small],
                                jnp.uint32(5)))
    want = np.bitwise_count(a_s & (b_s ^ np.uint32(5))).sum(
        axis=1, dtype=np.uint64
    )
    if not np.array_equal(got.astype(np.uint64), want):
        print(json.dumps({"variant": "xla_small_gate",
                          "error": f"{got.tolist()} != {want.tolist()}"}),
              flush=True)
        sys.exit(1)

    scalar = lambda s: jnp.uint32(s)  # noqa: E731
    vec1 = lambda s: np.full(1, s, np.uint32)  # noqa: E731

    variants = [Variant(xla_kernel, "xla", scalar)]
    # 2^17 words is the largest block that compiles on a v5e: 2^18 asks
    # for 32 MiB of scoped VMEM against a 16 MiB limit
    for bw in (1 << 15, 1 << 16, 1 << 17):
        variants.append(
            Variant(pallas_intersect_count(bw), f"pallas_bw{bw}", vec1)
        )

    _stage("compiling + gating variants")
    ref = variants[0].compile_and_gate(a, b)
    # ref=None (xla failed to compile) leaves the Pallas variants ungated
    # against it; they still time, and the run still fails below
    for v in variants[1:]:
        v.compile_and_gate(a, b, expect=ref)
    live = [v for v in variants if v.ok]
    for t in range(TRIALS):
        _stage(f"interleaved trial {t + 1}/{TRIALS} "
               f"({', '.join(v.name for v in live)})")
        for v in live:
            v.timed_pass(a, b)
    for v in live:
        v.report(hbm_peak)
    failed = [v.name for v in variants if not v.ok]
    if failed:
        sys.exit(f"bench_pallas: variants failed: {', '.join(failed)}")


if __name__ == "__main__":
    main()
