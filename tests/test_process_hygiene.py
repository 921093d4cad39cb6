"""One process per chip: which entry points stay off JAX.

A chip belongs to one process. The multi-process serving tier, the chip
smoke and every offline CLI command rest on these modules and commands
never initialising a JAX backend (a process that does would take, or
hang on, a chip some other process owns)."""

import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str, *argv: str) -> subprocess.CompletedProcess:
    return subprocess.run(
        [sys.executable, "-c", code, *argv], cwd=ROOT,
        capture_output=True, text=True, timeout=300,
    )


def test_cli_and_worker_modules_do_not_import_jax():
    proc = _run(
        "import sys\n"
        "import pilosa_tpu.cli, pilosa_tpu.serving.worker\n"
        "import pilosa_tpu.native, pilosa_tpu.wire\n"
        "import pilosa_tpu.utils.compile_cache\n"
        "assert pilosa_tpu.native.available() in (True, False)\n"
        "assert pilosa_tpu.wire.available() in (True, False)\n"
        "leaked = sorted(m for m in sys.modules"
        " if m == 'jax' or m.startswith('jax.'))\n"
        "assert not leaked, leaked[:5]\n"
    )
    assert proc.returncode == 0, proc.stderr[-2000:]


# runs the real CLI entry in-process, then asks JAX (which a command may
# have imported) whether any backend was ever initialised
_OFFLINE = (
    "import sys\n"
    "from pilosa_tpu.cli import main\n"
    "rc = main(sys.argv[1:])\n"
    "assert rc == 0, rc\n"
    "if 'jax' in sys.modules:\n"
    "    from jax._src import xla_bridge\n"
    "    assert not xla_bridge.backends_are_initialized(), sys.argv[1]\n"
)


def test_offline_cli_commands_leave_the_backends_alone(tmp_path):
    data = str(tmp_path / "data")
    csv = tmp_path / "bits.csv"
    csv.write_text("1,10\n1,1048586\n2,20\n")
    stdout = {}
    for argv in (
        ["import", "-d", data, "-i", "i", "-f", "f", "--create", str(csv)],
        ["export", "-d", data, "-i", "i", "-f", "f"],
        ["check", "-d", data],
    ):
        proc = _run(_OFFLINE, *argv)
        assert proc.returncode == 0, (argv, proc.stdout, proc.stderr[-2000:])
        stdout[argv[0]] = proc.stdout
    assert sorted(stdout["export"].split()) == ["1,10", "1,1048586", "2,20"]
