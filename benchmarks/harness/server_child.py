"""The one server child: ``python -m pilosa_tpu server`` and, after its
clean exit, the device memory peaks written where the harness reads them.

The program exposes no device memory figure over HTTP and only the
process that holds the chips can ask JAX for one, so this wrapper runs
the program's own entry point unchanged (same arguments, default knobs,
same SIGTERM handling) and then writes ``memory_stats()`` of every local
device to the file named by ``BENCH_MEMORY_STATS``.

The harness starts this process as the leader of a session of its own
(``serving.ServerProc``), so a signal sent to the harness's process
group no longer reaches it. A harness that is killed outright must not
leave a server holding the chip: the kernel sends this process SIGTERM,
the clean close, when its parent is gone.
"""

import ctypes
import json
import os
import signal
import sys

PR_SET_PDEATHSIG = 1  # <linux/prctl.h>


def stop_with_the_harness() -> None:
    harness = os.environ.get("BENCH_HARNESS_PID")
    if not harness:
        return
    libc = ctypes.CDLL(None, use_errno=True)
    libc.prctl.argtypes = [ctypes.c_int] + [ctypes.c_ulong] * 4
    libc.prctl.restype = ctypes.c_int
    if libc.prctl(PR_SET_PDEATHSIG, int(signal.SIGTERM), 0, 0, 0):
        raise OSError(ctypes.get_errno(), "prctl(PR_SET_PDEATHSIG)")
    if os.getppid() != int(harness):
        sys.exit("the harness was gone before its server child started")


def main() -> int:
    stop_with_the_harness()
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
