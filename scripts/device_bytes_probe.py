#!/usr/bin/env python
"""How many bytes does each device hold, and how high did they peak?

chip_smoke.py stays off JAX and so cannot ask the devices; this probe is
the one process that holds them. It boots a default-knob ``Server``
in-process, loads chip_smoke's data set over HTTP, runs chip_smoke's
queries, burst and write against its numpy reference, then reads — from
inside the owning process, through public JAX calls only — what each
device holds: the shards of ``jax.live_arrays()`` per device, and each
device's ``memory_stats()`` (bytes in use, peak, limit).

On a multi-device host it exits non-zero unless the server chose the
mesh executor by itself and every device holds a share of the live bytes
(within 2x of an even split).

    python scripts/device_bytes_probe.py [--shards 1024] [--seed 0]

Prints one JSON line.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import chip_smoke  # noqa: E402  (data set, reference, HTTP client, phases)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shards", type=int, default=1024)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    from pilosa_tpu.utils import compile_cache

    compile_cache.configure()
    import jax

    from pilosa_tpu.parallel.dist import DistExecutor
    from pilosa_tpu.server import Server, ServerConfig

    devices = jax.devices()
    ref = chip_smoke.Reference(args.seed, args.shards)
    with tempfile.TemporaryDirectory(prefix="bytes_probe_") as tmp:
        server = Server(ServerConfig(data_dir=tmp, bind="127.0.0.1",
                                     port=0)).open()
        try:
            local = server.api.executor.local
            if isinstance(local, DistExecutor) != (len(devices) > 1):
                sys.exit(f"{len(devices)} device(s) but the server chose "
                         f"{type(local).__name__}")
            chip_smoke.load(server.port, ref)
            with chip_smoke.Client(server.port) as c:
                chip_smoke.serial_queries(
                    c, ref, chip_smoke.padded_shards(args.shards,
                                                     len(devices)))
                chip_smoke.burst(server.port, c, ref)
                chip_smoke.write_and_read_back(c, ref)
                resident = int(chip_smoke.metric(
                    c.metrics(), "pilosa_tpu_residency_bytes_used"))
            live = {d.id: 0 for d in devices}
            for arr in jax.live_arrays():
                for shard in arr.addressable_shards:
                    live[shard.device.id] += shard.data.nbytes
            stats = {}
            for d in devices:
                mem = d.memory_stats() or {}  # None on the CPU backend
                stats[d.id] = {k: mem.get(k) for k in (
                    "bytes_in_use", "peak_bytes_in_use", "bytes_limit")}
        finally:
            server.close()
    even = sum(live.values()) / len(devices)
    ok = all(even / 2 <= b <= even * 2 for b in live.values())
    print(json.dumps({
        "ok": ok,
        "device": {"platform": devices[0].platform,
                   "kind": devices[0].device_kind, "count": len(devices)},
        "executor": type(local).__name__,
        "shards": args.shards,
        "residency_bytes_used": resident,
        "live_array_bytes_by_device": live,
        "memory_stats_by_device": stats,
    }), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
