"""The one server child: ``python -m pilosa_tpu server`` and, after its
clean exit, the device memory peaks written where the harness reads them.

The program exposes no device memory figure over HTTP and only the
process that holds the chips can ask JAX for one, so this wrapper runs
the program's own entry point unchanged (same arguments, default knobs,
same SIGTERM handling) and then writes ``memory_stats()`` of every local
device to the file named by ``BENCH_MEMORY_STATS``.
"""

import json
import os
import sys


def main() -> int:
    from pilosa_tpu.cli import main as cli_main

    rc = cli_main(["server"] + sys.argv[1:])
    out = os.environ.get("BENCH_MEMORY_STATS")
    if out and not rc:
        import jax

        stats = [d.memory_stats() or {} for d in jax.local_devices()]
        with open(out, "w") as f:
            json.dump([{k: v for k, v in s.items()
                        if isinstance(v, (int, float))} for s in stats], f)
    return rc


if __name__ == "__main__":
    sys.exit(main())
