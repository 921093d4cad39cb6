"""Shared by the benchmark's tests: where things are, one way to run a
rehearsal (``benchmarks/run.py --rehearse``: the whole harness on the
CPU at 2 shards, one server child), and the two rehearsals of each
shipped cell that every file of this directory shares (``rehearsals``)."""

from __future__ import annotations

import fcntl
import importlib.util
import json
import os
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmarks")
RUN = os.path.join(BENCH, "run.py")
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

# BENCH_MANIFEST: a manifest other than the shipped one, for the one test
# that runs the pins of these files against a copy with entries appended
# (test_bench_pins.py); nothing else sets it
with open(os.environ.get("BENCH_MANIFEST")
          or os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
CELLS = {w["name"]: w for w in MANIFEST["workloads"]}
CONTROL = {name: ("lost-write" if cell["traffic"] == "point-rw" else "sampled")
           for name, cell in CELLS.items()}


def load_config(name: str) -> dict:
    files = {c["name"]: c["file"] for c in MANIFEST["configs"]}
    with open(os.path.join(ROOT, files[name])) as f:
        return json.load(f)


def load_mix(name: str) -> dict:
    from harness import traffic

    return traffic.load_mix(traffic.mix_path(BENCH, name))


# Kinds of field, template, filter term and draw that no shipped mix uses
# yet (they are what the SSB flights in PERF.md's Open questions need, and
# a later PR can only add data files), exercised by the tests on a toy of
# their own. The last three templates are in the forms of SSB's Q1.1 (a Sum
# under two range terms and a row), Q3.3 (a GroupBy under two ``in`` terms,
# on its own dimension and on another field) and Q4.1 (a GroupBy under two
# rows and an ``in``).
TOY_CONFIG = {
    "name": "toy", "index": "toy", "shards": 2, "fields": {
        "brand": {"type": "set", "uniform": 80},
        "category": {"type": "set", "rows": 10,
                     "derived": {"field": "brand", "div": 8}},
        "region": {"type": "set", "uniform": 5},
        "year": {"type": "set", "uniform": 7},
        "city": {"type": "set", "uniform": 20},
        "nation": {"type": "set", "rows": 5,
                   "derived": {"field": "city", "div": 4}},
        "revenue": {"type": "int", "min": 0, "max": 5000,
                    "uniform_int": [100, 5000]},
        "quantity": {"type": "int", "min": 0, "max": 50,
                     "uniform_int": [1, 50]},
        "discount": {"type": "int", "min": 0, "max": 10,
                     "uniform_int": [0, 10]}}}
_BY_YEAR_AND_BRAND = {"kind": "groupby", "sum": "revenue"}
TOY_MIX = {
    "name": "toy-flight", "preload": False,
    "groups": [{"name": "analysts", "clients": 4, "loop": "closed",
                "rotation": ["category_page", "brand_span", "one_brand",
                             "discounted_revenue", "city_pair",
                             "nation_by_year"]}],
    "templates": {
        "discounted_revenue": {
            "kind": "sum", "sum": "revenue",
            "filter": [["year", "Y"],
                       ["discount", {"between": ["LO", "HI"]}],
                       ["quantity", {"lt": 25}]],
            "draw": {"Y": {"row_of": "year"}, "LO": {"int": [1, 5]},
                     "HI": {"affine": ["LO", 1, 2]}}},
        "city_pair": {
            "kind": "groupby", "sum": "revenue",
            "dims": [{"field": "city"}, {"field": "year", "limit": 6}],
            "filter": [["city", {"in": ["A", "B"]}],
                       ["region", {"in": ["R", "S"]}]],
            "draw": {"A": {"row_of": "city", "span": 5},
                     "B": {"affine": ["A", 1, 4]},
                     "R": {"row_of": "region", "span": 2},
                     "S": {"affine": ["R", 1, 1]}}},
        "nation_by_year": {
            "kind": "groupby", "sum": "revenue",
            "dims": [{"field": "year"}, {"field": "nation"}],
            "filter": [["region", "R"], ["category", "C"],
                       ["brand", {"in": ["B", "B2"]}]],
            "draw": {"R": {"row_of": "region"}, "C": {"row_of": "category"},
                     "B": {"affine": ["C", 8, 0]},
                     "B2": {"affine": ["C", 8, 5]}}},
        "category_page": dict(
            _BY_YEAR_AND_BRAND,
            dims=[{"field": "year"},
                  {"field": "brand", "previous": "P", "limit": 8}],
            filter=[["category", "C"], ["region", "R"]],
            draw={"C": {"row_of": "category"}, "R": {"row_of": "region"},
                  "P": {"affine": ["C", 8, -1]}}),
        "brand_span": dict(
            _BY_YEAR_AND_BRAND,
            dims=[{"field": "year"},
                  {"field": "brand", "previous": "P", "limit": 4}],
            filter=[["region", "R"]],
            draw={"B": {"row_of": "brand", "span": 4},
                  "R": {"row_of": "region"}, "P": {"affine": ["B", 1, -1]}}),
        "one_brand": {
            "kind": "sum", "sum": "revenue",
            "filter": [["brand", "B"], ["region", "R"]],
            "draw": {"B": {"row_of": "brand"}, "R": {"row_of": "region"}}}}}
TOY_CELL = {"name": "toy.toy-flight", "config": "toy", "traffic": "toy-flight"}


def config_and_mix(cell: dict) -> tuple[dict, dict]:
    if cell is TOY_CELL:
        return TOY_CONFIG, TOY_MIX
    return load_config(cell["config"]), load_mix(cell["traffic"])


SHARED_SEED = 2_600_000_011


def rehearse(workload: str, *extra: str, seed: int = SHARED_SEED,
             seconds: float = 3.0, trace: int = 0, env: dict | None = None):
    """One rehearsal as a subprocess; returns the CompletedProcess. A file
    that rehearses a shipped cell with no arguments of its own takes the
    run from ``rehearsals`` instead."""
    return subprocess.run(
        [sys.executable, RUN, "--workload", workload, "--seed", str(seed),
         "--seconds", str(seconds), "--trace", str(trace), "--rehearse",
         *extra],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)


# ------------------------------------------- the rehearsals every file shares
#
# A rehearsal costs 25 to 150 s of CPU, and tier-1 rehearses a cell in two
# kinds only, whoever asks: untraced with the cell's control compared after
# it, and traced. Both take SHARED_SEED and 3 seconds. The finished run
# (standard output, standard error, return code) is kept in a directory of
# the session under pytest's base temp, one entry a kind, made under a file
# lock: the second xdist worker to ask waits for the first and reads what
# it left. Each runs with a compile cache of its own inside its entry, so
# ``compiles_in_window`` and the warm-up's last pass count its own
# compilations whatever the other workers compile.

KINDS = (0, 1)  # --trace; the control follows from it


def shared_dir(tmp_path_factory) -> str:
    base = tmp_path_factory.getbasetemp()
    if os.environ.get("PYTEST_XDIST_WORKER"):
        base = base.parent  # a worker's base is <session>/popen-gwN
    path = os.path.join(str(base), "bench-rehearsals")
    os.makedirs(path, exist_ok=True)
    return path


def shared_key(cell: str, trace: int) -> str:
    """``<cell>.t<trace>.<control>.<seed>``: a cell has these two and no
    third, which is what holds tier-1 to two rehearsals a cell."""
    if cell not in CELLS or trace not in KINDS:
        raise ValueError(f"no shared rehearsal of {cell!r} with trace "
                         f"{trace!r}: a shipped cell, untraced with its "
                         "control or traced")
    control = "none" if trace else CONTROL[cell]
    return f"{cell}.t{trace}.{control}.{SHARED_SEED}"


def _kept(entry: str):
    try:
        with open(os.path.join(entry, "returncode")) as f:
            rc = int(f.read())
    except (OSError, ValueError):
        return None  # not run yet, or its producer was killed half way
    with open(os.path.join(entry, "stdout")) as f:
        out = f.read()
    with open(os.path.join(entry, "stderr")) as f:
        err = f.read()
    return subprocess.CompletedProcess([RUN, entry], rc, out, err)


def _produce(entry: str, cell: str, trace: int):
    shutil.rmtree(entry, ignore_errors=True)
    os.makedirs(entry)
    extra = () if trace else ("--control", CONTROL[cell])
    env = dict(os.environ,
               JAX_COMPILATION_CACHE_DIR=os.path.join(entry, "jax_cache"))
    try:
        p = rehearse(cell, *extra, trace=trace, env=env)
    except subprocess.TimeoutExpired as e:  # kept too: every reader fails
        out, err = ((t or b"").decode(errors="replace")
                    for t in (e.stdout, e.stderr))
        p = subprocess.CompletedProcess(
            e.cmd, 124, out, f"{err}\ntimed out after {e.timeout} s")
    for name, text in (("stdout", p.stdout), ("stderr", p.stderr),
                       ("returncode", str(p.returncode))):  # the code last
        with open(os.path.join(entry, name), "w") as f:
            f.write(text)
    shutil.rmtree(env["JAX_COMPILATION_CACHE_DIR"], ignore_errors=True)
    return p


def _take(top: str, cell: str, trace: int, wait: bool):
    """The kept run, made here if nobody has made it; None where another
    process is making it and ``wait`` is false."""
    entry = os.path.join(top, shared_key(cell, trace))
    with open(entry + ".lock", "w") as lock:
        try:
            fcntl.flock(lock, fcntl.LOCK_EX | (0 if wait else fcntl.LOCK_NB))
        except BlockingIOError:
            return None
        return _kept(entry) or _produce(entry, cell, trace)


def rehearsals(tmp_path_factory, wanted) -> dict:
    """``{(cell, trace): CompletedProcess}`` for every pair of ``wanted``.
    What no process has started is run here, in the order given; what
    another process is running is waited for only when nothing else is
    left to run, so that files which want the same runs make different
    ones meanwhile."""
    top = shared_dir(tmp_path_factory)
    runs: dict = {}
    pending = list(dict.fromkeys(wanted))
    while pending:
        for key in pending:
            run = _take(top, *key, wait=False)
            if run is not None:
                runs[key] = run
        if not any(key in runs for key in pending):
            runs[pending[0]] = _take(top, *pending[0], wait=True)
        pending = [key for key in pending if key not in runs]
    return runs


def last_line(stdout: str) -> dict:
    return json.loads(stdout.strip().splitlines()[-1])


def import_run():
    """``benchmarks/run.py`` as a module, for tests that break the timed
    path underneath it."""
    spec = importlib.util.spec_from_file_location("bench_run", RUN)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
