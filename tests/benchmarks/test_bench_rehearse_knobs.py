"""What a rehearsal has that a chip run has not (ISSUE 41): a work
directory of its own, so that the files that rehearse one cell in
different xdist workers stop removing each other's
``benchmarks/.work/<cell>/``. One that was killed (a test's timeout,
tier-1's limit) leaves its directory behind; the cell's next rehearsal
removes it. The rest of the run is driven in this process, as
``test_bench_rehearse.py`` drives it to break the timed path."""

import os
import shutil
import subprocess
import sys

from bench_helpers import BENCH, CELLS, import_run, last_line, load_mix

CELL = "ssb-lineorder.brand-lookup"


def test_a_chip_run_keeps_todays_path_and_a_rehearsal_its_own():
    run = import_run()
    work = os.path.join(BENCH, ".work")
    for cell in CELLS:
        assert run.work_dir(cell, False) == os.path.join(work, cell)
        assert run.work_dir(cell, True) == os.path.join(
            work, f"{cell}.{os.getpid()}")


def test_a_rehearsal_works_in_its_own_directory_and_clears_the_dead_ones(
        capsys, monkeypatch):
    """While it runs, a rehearsal's work files are in the directory of
    this process, which is gone when it ends; a directory whose process
    is gone goes at its start, a live process's and another cell's stay.
    It sends from the mix's own 8 clients: nothing caps a rehearsal."""
    run = import_run()
    gone = subprocess.Popen([sys.executable, "-c", "pass"])
    gone.wait()
    work = os.path.join(BENCH, ".work")
    dead = os.path.join(work, f"{CELL}.{gone.pid}")
    alive = os.path.join(work, f"{CELL}.{os.getppid()}")
    other = os.path.join(work, f"not-a.cell.{gone.pid}")
    for d in (dead, alive, other):
        os.makedirs(os.path.join(d, "data"), exist_ok=True)
    real = run.loadgen.run
    sent_from, work_dirs = [], set()

    def counting(port, index, clients, **kw):
        sent_from.append((len(clients), kw.get("seconds")))
        work_dirs.update(os.listdir(work))
        return real(port, index, clients, **kw)

    monkeypatch.setattr(run.loadgen, "run", counting)
    try:
        rc = run.main(["--workload", CELL, "--seed", "4100000011",
                       "--seconds", "2.5", "--trace", "0", "--rehearse"])
        out = capsys.readouterr()
        assert rc == 0, out.err[-3000:]
        line = last_line(out.out)
        assert line["correct"] is True and line["failed"] == 0
        assert not os.path.exists(dead)
        assert os.path.isdir(alive) and os.path.isdir(other)
    finally:
        for d in (alive, other):
            shutil.rmtree(d, ignore_errors=True)
    # sample: 1 client; ladder: 1, 2, 4, 8 for each of 3 templates; then
    # the warm passes and the window, all from the mix's 8
    assert load_mix("brand-lookup")["groups"][0]["clients"] == 8
    counts = [n for n, _ in sent_from]
    assert counts[0] == 1 and counts[1:13] == [1, 2, 4, 8] * 3
    assert set(counts[13:]) == {8} and sent_from[-1] == (8, 2.5)
    checks = [l for l in out.out.splitlines() if l.startswith("check ")]
    assert len(checks) == 3 and all(" wrong=0 limit=0" in l for l in checks)
    mine = f"{CELL}.{os.getpid()}"
    # no rehearsal, this one or another worker's, uses the chip run's path
    assert mine in work_dirs and CELL not in work_dirs
    assert os.path.basename(dead) not in work_dirs
    assert not os.path.exists(os.path.join(work, mine))
