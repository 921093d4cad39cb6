#!/usr/bin/env python3
"""One run of one benchmark cell: the served HTTP path on the chip.

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its traffic mix and its per-layer metrics
are data (``BENCHMARK.json``, ``benchmarks/configs``, ``benchmarks/
traffic``, ``benchmarks/layer_metrics``); see ``benchmarks/README.md``.
This process never imports JAX: the one server child holds the chips.

Steps: (1) the cell's data from the seed, written as fragment files;
(2) one server child, default knobs unless the configuration's
``server_knobs`` names a documented deployment setting; (3) ``/info``
must list only TPU devices of a kind in ``peaks.json``, as many as the
cell asks for;
(4) the rows the traffic names made resident, a sample of every
template answered, and the mix itself run at its own and at lower
concurrency until a pass adds no entry to the compile cache; (5) the
measured window; (6) every acknowledged write read back; (7) SIGTERM,
which must exit 0; (8) every kept answer compared with the numpy
reference, computed while the server makes its clean close; (9) the
result line. ``setup_s`` is (1) to (4).

``--rehearse`` runs the same code on the CPU at the configuration's
``rehearse_shards`` and stamps its line ``cpu``; it is for tests and
never yields a device number. Without it, no TPU is an error. A
rehearsal keeps its work files in a directory of its own
(``.work/<cell>.<pid>``: several may rehearse one cell at once).
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import numpy as np  # noqa: E402

from harness import datagen, loadgen, readers, trace, traffic  # noqa: E402
from harness.reference import Reference  # noqa: E402
from harness.serving import Conn, HarnessError, ServerProc  # noqa: E402

SAMPLE_PER_TEMPLATE = 8
WARM_BURST_ROUNDS = 3
WARM_PASS_S = 2.0
WARM_MAX_PASSES = 10
TRACE_S = 5.0
READBACK_BATCH = 50


def log(msg: str) -> None:
    print(f"[bench {time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr,
          flush=True)


def die(msg: str) -> "NoReturn":  # noqa: F821
    print(f"benchmarks/run.py: {msg}", file=sys.stderr, flush=True)
    sys.exit(2)


def load_cell(workload: str):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        die(f"no workload {workload!r} in BENCHMARK.json "
            f"(have {sorted(cells)})")
    cell = cells[workload]
    files = {c["name"]: c["file"] for c in manifest["configs"]}
    with open(os.path.join(ROOT, files[cell["config"]])) as f:
        config = json.load(f)
    mix = traffic.load_mix(traffic.mix_path(HERE, cell["traffic"]))
    if config["chips"] != cell["chips"]:
        die(f"cell {workload} asks for {cell['chips']} chips, its "
            f"configuration for {config['chips']}")
    return manifest, cell, config, mix


def work_dir(workload: str, rehearse: bool) -> str:
    """Where a run keeps its data dir and logs, removed at its start and
    its end. A rehearsal's is its own (the pid): tests rehearse one cell
    from several processes at once."""
    return os.path.join(HERE, ".work", workload + (
        f".{os.getpid()}" if rehearse else ""))


def remove_orphans(workload: str) -> None:
    """The work directories that killed rehearsals of this cell left
    behind: ``.work/<cell>.<pid>`` whose process is gone."""
    top = os.path.join(HERE, ".work")
    for name in os.listdir(top) if os.path.isdir(top) else ():
        cell, _, pid = name.rpartition(".")
        if cell != workload or not pid.isdigit():
            continue
        try:
            os.kill(int(pid), 0)
        except ProcessLookupError:
            shutil.rmtree(os.path.join(top, name), ignore_errors=True)
        except OSError:
            pass  # another user's process: alive


def compile_cache_dir() -> str:
    # the program's rule (pilosa_tpu/utils/compile_cache.py): where
    # JAX_COMPILATION_CACHE_DIR says, else <checkout>/.jax_cache
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_cache")


def cache_entries() -> int:
    d = compile_cache_dir()
    if not os.path.isdir(d):
        return 0
    return sum(1 for name in os.listdir(d) if name.endswith("-cache"))


def check_devices(conn: Conn, cell: dict, rehearse: bool) -> dict:
    devices = conn.get_json("/info").get("devices") or []
    if not devices:
        raise HarnessError("/info lists no devices")
    platforms = {d.get("platform") for d in devices}
    kinds = {d.get("kind") for d in devices}
    if not rehearse:
        with open(os.path.join(HERE, "peaks.json")) as f:
            peaks = json.load(f)
        if platforms != {"tpu"}:
            raise HarnessError(f"the server's devices are {devices!r}: this "
                               "benchmark measures TPUs and found none")
        if len(kinds) != 1 or not kinds <= set(peaks):
            raise HarnessError(f"device kinds {sorted(kinds)} are not in "
                               "benchmarks/peaks.json")
        if len(devices) != cell["chips"]:
            raise HarnessError(f"cell needs {cell['chips']} chips, the "
                               f"server sees {len(devices)}")
    return {"platform": devices[0]["platform"], "kind": devices[0]["kind"],
            "count": len(devices)}


# ------------------------------------------------------------------ set-up


def preload(port: int, index: str, rows: list, n_conns: int = 8) -> None:
    """Count every row the traffic can name once, so its dense form is on
    the device before the window (a restart's cost, not a request's)."""
    chunks = [rows[i::n_conns] for i in range(n_conns)]
    errors: list = []

    def work(chunk) -> None:
        try:
            with Conn(port) as c:
                for f, r in chunk:
                    c.query(index, f"Count(Row({f}={r}))")
        except BaseException as e:  # re-raised on the caller
            errors.append(e)

    threads = [threading.Thread(target=work, args=(ch,)) for ch in chunks if ch]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        raise errors[0]


def sample_templates(port, index, mix, config, n_shards, seed) -> list:
    """At least SAMPLE_PER_TEMPLATE requests of every template, one at a
    time on one connection, answers kept for the reference."""
    records = []
    for gi, group in enumerate(mix["groups"]):
        client = traffic.Client(mix, config, n_shards, group, 0, seed,
                                f"sample{gi}")
        n = SAMPLE_PER_TEMPLATE * len(group["rotation"])
        records += loadgen.run(port, index, [client],
                               requests_each=n).records
    return records


def warm_up(port, index, mix, config, n_shards, seed) -> tuple[list, int]:
    """Every shape the window can send, compiled or read from the cache
    before it opens. Wave sizes choose the micro-batch programs (a wave's
    requests of one shape are padded to a power of two), so first every
    template is sent in bursts of 1, 2, 4, ... simultaneous requests up
    to its group's client count; then the mix itself runs at the cell's
    own concurrency, pass after pass, until a pass adds no entry to the
    compile cache."""
    records: list = []
    n_pass = 0
    for group in mix["groups"]:
        for name in dict.fromkeys(group["rotation"]):
            only = dict(group, rotation=[name])
            k = 1
            while k <= group["clients"]:
                n_pass += 1
                burst = [traffic.Client(mix, config, n_shards, only, i, seed,
                                        f"burst{n_pass}") for i in range(k)]
                records.extend(loadgen.run(
                    port, index, burst, requests_each=WARM_BURST_ROUNDS,
                    keep_bodies=False).records)
                k *= 2
    for rnd in range(WARM_MAX_PASSES):
        before = cache_entries()
        n_pass += 1
        cl = traffic.clients(mix, config, n_shards, seed, f"warm{n_pass}")
        records.extend(loadgen.run(port, index, cl, seconds=WARM_PASS_S,
                                   keep_bodies=False).records)
        if rnd and cache_entries() == before:
            return records, n_pass
    raise HarnessError(f"warm-up still compiles after {n_pass} passes")


# ------------------------------------------------------------ after window


def read_back(port: int, index: str, writes: list) -> tuple[dict, dict]:
    """Through the served path: for every write its column's rows in that
    field (``Rows(f, column=c)``), and for every touched row its count
    (``Count(Row(f=r))``). Returns ({(f, col): rows}, {(f, r): count})."""
    col_rows: dict = {}
    row_counts: dict = {}
    cols = sorted({(w.sem["field"], w.sem["column"]) for w in writes})
    rows = sorted({(w.sem["field"], w.sem["row"]) for w in writes})
    with Conn(port) as c:
        for lo in range(0, len(cols), READBACK_BATCH):
            batch = cols[lo:lo + READBACK_BATCH]
            res = c.query(index, " ".join(
                f"Rows({f}, column={col})" for f, col in batch))
            for key, r in zip(batch, res):
                col_rows[key] = r["rows"] if isinstance(r, dict) else r
        for lo in range(0, len(rows), READBACK_BATCH):
            batch = rows[lo:lo + READBACK_BATCH]
            res = c.query(index, " ".join(
                f"Count(Row({f}={r}))" for f, r in batch))
            row_counts.update(zip(batch, res))
    return col_rows, row_counts


class Checks:
    """Every number compared, printed beside its limit."""

    def __init__(self, label: str = "check"):
        self.ok = True
        self.label = label

    def expect(self, name: str, compared: int, wrong: int,
               example: str = "", at_least: int = 1) -> None:
        line = (f"{self.label} {name}: compared={compared} wrong={wrong} "
                f"limit=0")
        if wrong:
            self.ok = False
            line += f" first: {example[:400]}"
        if compared < at_least:
            self.ok = False
            line += f" (needs at least {at_least} to compare)"
        print(line, flush=True)


def verify(ref: Reference, has_writes: bool, answers: list, writes: list,
           col_rows: dict, row_counts: dict, checks: Checks,
           templates: list) -> None:
    """``answers`` is [(record, result)] for every read that was kept;
    a read that ran beside writes is held between what it must and what
    it may have seen, every other answer to equality."""
    by_template: dict = {name: [] for name in templates}
    for r, got in answers:
        by_template.setdefault(r.template, []).append((r, got))
    for name, recs in sorted(by_template.items()):
        wrong, example = 0, ""
        for r, got in recs:
            if r.sem["kind"] == "count" and has_writes:
                terms = [tuple(t) for t in r.sem["filter"]]
                lo = ref.count(terms, acked_before=r.t_sent)
                hi = ref.count(terms, sent_before=r.t_done)
                good = isinstance(got, int) and lo <= got <= hi
                want = [lo, hi]
            else:
                want = ref.answer(r.sem)
                good = got == want
            if not good:
                wrong += 1
                example = example or (f"{traffic.render(r.sem)} got "
                                      f"{json.dumps(got)[:150]} want "
                                      f"{json.dumps(want)[:150]}")
        checks.expect(f"answers.{name}", len(recs), wrong, example,
                      at_least=SAMPLE_PER_TEMPLATE)
    if writes:
        acked = [w for w in writes if w.ok]
        wrong, example = 0, ""
        for w in acked:
            f, row, col = w.sem["field"], w.sem["row"], w.sem["column"]
            if row not in col_rows.get((f, col), ()):
                wrong += 1
                example = example or (f"Set({col}, {f}={row}) acknowledged, "
                                      f"Rows({f}, column={col}) = "
                                      f"{col_rows.get((f, col))}")
        checks.expect("writes.acknowledged_bits_read_back", len(acked), wrong,
                      example)
        wrong, example = 0, ""
        for (f, row), got in sorted(row_counts.items()):
            lo = ref.row_count(f, row, acked_only=True)
            hi = ref.row_count(f, row, acked_only=False)
            if not lo <= got <= hi:
                wrong += 1
                example = example or (f"Count(Row({f}={row})) = {got}, "
                                      f"reference [{lo}, {hi}]")
        checks.expect("writes.touched_row_counts", len(row_counts), wrong,
                      example)


# ----------------------------------------------------------------- control

CONTROLS = ("sampled", "lost-write")


def _doubled(answer):
    if isinstance(answer, int):
        return 2 * answer
    if isinstance(answer, dict):
        return {k: (2 * v if k in ("count", "sum", "value") else v)
                for k, v in answer.items()}
    return [_doubled(item) for item in answer]


def control(kind: str, config: dict, columns: dict, n_shards: int,
            answers: list, writes: list, col_rows: dict, row_counts: dict):
    """The reference in the program's place with one stated guarantee
    broken, the step that would tempt a later PR; the comparison has to
    call it not correct.

    ``sampled``    answers are exact no more: every read is answered from
                   the even-numbered shards alone and doubled.
    ``lost-write`` an acknowledged write is not durable: the middle
                   acknowledged write is missing from what is read back.
    """
    if kind == "sampled":
        half = {f: v.reshape(n_shards, -1)[::2].reshape(-1)
                for f, v in columns.items()}
        ref_half = Reference(config, half)
        answers = [(r, _doubled(ref_half.answer(r.sem))) for r, _ in answers]
    else:
        acked = [w for w in writes if w.ok]
        if not acked:
            raise HarnessError("control lost-write needs a cell with writes")
        w = acked[len(acked) // 2]
        f, row, col = w.sem["field"], w.sem["row"], w.sem["column"]
        col_rows = dict(col_rows)
        col_rows[(f, col)] = [r for r in col_rows[(f, col)] if r != row]
        if int(columns[f][col]) != row:
            row_counts = dict(row_counts)
            row_counts[(f, row)] -= 1
    return answers, col_rows, row_counts


# -------------------------------------------------------------------- main


def percentile(values: list, q: float) -> float:
    return float(np.percentile(np.asarray(values, np.float64), q))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU, the configuration's rehearse_shards; for "
                         "tests, never a device number")
    ap.add_argument("--control", choices=CONTROLS,
                    help="also compare the reference with one guarantee "
                         "broken; it must come out not correct (exit 3 if "
                         "it does not)")
    ap.add_argument("--keep-work", action="store_true",
                    help="leave the data dir and logs in benchmarks/.work")
    args = ap.parse_args(argv)

    manifest, cell, config, mix = load_cell(args.workload)
    if not os.path.isdir(os.path.join(ROOT, "pilosa_tpu")):
        die("no pilosa_tpu package beside benchmarks/: nothing to measure")
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if not args.rehearse and platforms and "tpu" not in platforms.split(","):
        die(f"JAX_PLATFORMS={platforms!r}: this benchmark measures TPUs. "
            "Nothing was loaded or measured (--rehearse runs the CPU "
            "rehearsal the tests use).")

    env_extra = {}
    if args.rehearse:
        env_extra["JAX_PLATFORMS"] = "cpu"
        flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
                 if "xla_force_host_platform_device_count" not in f]
        flags.append("--xla_force_host_platform_device_count="
                     f"{cell['chips']}")
        env_extra["XLA_FLAGS"] = " ".join(flags)
        remove_orphans(args.workload)
    n_shards = config["rehearse_shards"] if args.rehearse else config["shards"]
    index = config["index"]
    work = work_dir(args.workload, args.rehearse)
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    data_dir = os.path.join(work, "data")
    os.makedirs(data_dir)

    server = None
    try:
        # ------------------------------------------------ (1)-(4) set-up
        t_setup = time.monotonic()
        fields = traffic.fields_read(mix, config)
        columns = datagen.make_columns(config, args.seed, n_shards, fields)
        log(f"columns for {fields} at {n_shards} shards: "
            f"{time.monotonic() - t_setup:.1f} s")
        t = time.monotonic()
        n_bytes = datagen.write_data_dir(data_dir, config, columns, n_shards,
                                         fields)
        log(f"wrote {n_bytes / 2**20:.0f} MiB of fragments in "
            f"{time.monotonic() - t:.1f} s")
        t = time.monotonic()
        knobs = config.get("server_knobs", {})
        server = ServerProc(ROOT, data_dir, os.path.join(work, "server.log"),
                            knobs, env_extra)
        server.wait_ready()
        with Conn(server.port) as c:
            device = check_devices(c, cell, args.rehearse)
        log(f"server up on {device} in {time.monotonic() - t:.1f} s, "
            f"server_knobs {json.dumps(knobs, sort_keys=True)}")
        t = time.monotonic()
        if mix.get("preload"):
            preload(server.port, index, traffic.preload_rows(mix, config))
            log(f"preload: {time.monotonic() - t:.1f} s")
        t = time.monotonic()
        sampled = sample_templates(server.port, index, mix, config, n_shards,
                                   args.seed)
        warm, n_pass = warm_up(server.port, index, mix, config, n_shards,
                               args.seed)
        log(f"sample + {n_pass} warm passes: {time.monotonic() - t:.1f} s")
        setup_s = time.monotonic() - t_setup

        # ---------------------------------------------------- (5) window
        clients = traffic.clients(mix, config, n_shards, args.seed, "window")
        traced: dict = {}
        tracer = None
        if args.trace:
            trace_s = min(TRACE_S, args.seconds / 2)

            def capture() -> None:
                time.sleep(max(0.0, (args.seconds - trace_s) / 2))
                try:
                    with Conn(server.port) as tc:
                        status, body = tc.request(
                            "POST", f"/debug/trace-device?secs={trace_s}")
                    if status == 200:
                        traced.update(json.loads(body))
                except (OSError, ValueError) as e:
                    log(f"trace capture failed: {e}")

            tracer = threading.Thread(target=capture, daemon=True)
        with Conn(server.port) as c:
            before = c.metrics()
            before["compile_cache.entries"] = cache_entries()
            if tracer:
                tracer.start()
            win = loadgen.run(server.port, index, clients,
                              seconds=args.seconds)
            after = c.metrics()
            after["compile_cache.entries"] = cache_entries()
        if tracer:
            tracer.join()
        log(f"window: {len(win.records)} requests")

        # ------------------------------------- (6) read back, (7) SIGTERM
        everything = sampled + warm + win.records
        writes = [r for r in everything if r.sem["kind"] in traffic.WRITE_KINDS]
        col_rows, row_counts = ({}, {})
        t = time.monotonic()
        if writes:
            col_rows, row_counts = read_back(server.port, index, writes)
            log(f"read back {len(writes)} writes: "
                f"{time.monotonic() - t:.1f} s")
        t_stop = time.monotonic()
        server.terminate()

        # ---------- (8) the reference, while the server makes its close
        t = time.monotonic()
        checks = Checks()
        answers = [(r, json.loads(r.body)["results"][0])
                   for r in sampled + win.records
                   if r.sem["kind"] not in traffic.WRITE_KINDS
                   and r.body is not None]
        read_templates = [name for name, tp in mix["templates"].items()
                          if tp["kind"] not in traffic.WRITE_KINDS]
        ref = Reference(config, columns)
        for w in writes:
            ref.note_write(w.sem["field"], w.sem["row"], w.sem["column"],
                           w.t_sent, w.t_done if w.ok else None)
        verify(ref, bool(writes), answers, writes, col_rows, row_counts,
               checks, read_templates)
        control_held = True
        if args.control:
            broken = Checks(f"control[{args.control}]")
            c_answers, c_cols, c_rows = control(
                args.control, config, columns, n_shards, answers, writes,
                col_rows, row_counts)
            verify(ref, bool(writes), c_answers, writes, c_cols, c_rows,
                   broken, read_templates)
            control_held = not broken.ok
            print(f"control[{args.control}]: correct={broken.ok}, and it "
                  f"must be false", flush=True)
        log(f"reference: {time.monotonic() - t:.1f} s")
        rc = server.wait_stopped()
        log(f"SIGTERM to exit {rc}: {time.monotonic() - t_stop:.1f} s")
        memory_peak = server.memory_peak_bytes()
        srv, server = server, None
        if rc != 0:
            raise HarnessError(f"server exit code {rc} after SIGTERM\n"
                               f"{srv.log_tail()}")

        # ------------------------------------------------- (9) metrics
        in_window = win.records
        ok = [r for r in in_window if r.ok]
        done = [r for r in ok if r.t_done <= win.t_end]
        read_ms = [(r.t_done - r.t_sent) * 1e3 for r in ok
                   if r.sem["kind"] not in traffic.WRITE_KINDS]
        write_ms = [(r.t_done - r.t_sent) * 1e3 for r in ok
                    if r.sem["kind"] in traffic.WRITE_KINDS]
        values = {"throughput": len(done) / args.seconds, "setup_s": setup_s}
        if read_ms:
            values["read_p50_ms"] = percentile(read_ms, 50)
            values["read_p95_ms"] = percentile(read_ms, 95)
        if write_ms:
            values["write_ack_p50_ms"] = percentile(write_ms, 50)
            values["write_ack_p95_ms"] = percentile(write_ms, 95)
        by_template: dict = {}
        for r in ok:
            by_template.setdefault(r.template, []).append(
                (r.t_done - r.t_sent) * 1e3)
        for name, ms in sorted(by_template.items()):
            print(f"window {name}: n={len(ms)} " + " ".join(
                f"p{q}={percentile(ms, q):.1f}" for q in (50, 90, 95, 99))
                + " ms", flush=True)
        with open(os.path.join(work, "latencies.json"), "w") as f:
            json.dump(by_template, f)
        print(f"window: requests={len(in_window)} reads={len(read_ms)} "
              f"acknowledged_writes={len(write_ms)} "
              f"failed={len(in_window) - len(ok)}", flush=True)

        reduced = None
        if args.trace and traced.get("logDir"):
            path = trace.newest_xplane(traced["logDir"])
            if path:
                reduced = trace.reduce(path, trace_s)
        gen = {
            "gen.cpu_seconds": win.cpu_seconds,
            "gen.window_seconds": args.seconds,
            "gen.requests": float(len(ok)),
            "gen.reads": float(len(read_ms)),
            "gen.acknowledged_writes": float(len(write_ms)),
        }
        after.update(gen)
        before.update({k: 0.0 for k in gen})

        def wanted(metric: dict) -> bool:
            return args.workload in metric.get(
                "workloads", [args.workload])

        metrics = {}
        if args.trace:
            for m in manifest["per_layer"]:
                if not wanted(m):
                    continue
                v = readers.read(HERE, m["name"], before, after, reduced,
                                 values)
                if v is not None:
                    metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        else:
            for m in manifest["end_to_end"]:
                if wanted(m) and m["name"] in values:
                    metrics[m["name"]] = {"value": values[m["name"]],
                                          "unit": m["unit"]}
        device["memory_peak_bytes"] = memory_peak
        line = {"correct": checks.ok, "attempted": len(in_window),
                "failed": len(in_window) - len(ok), "metrics": metrics,
                "device": device}
        if args.trace:
            if reduced is None and not args.rehearse:
                raise HarnessError("traced run: no operation ran on a device "
                                   "inside the traced span")
            if reduced is not None:
                device["busy_s"] = reduced["busy_s"]
                device["window_s"] = reduced["window_s"]
                line["breakdown"] = trace.breakdown(reduced)
        print(json.dumps(line), flush=True)
        return 0 if control_held else 3
    except HarnessError as e:
        print(f"benchmarks/run.py: {e}", file=sys.stderr, flush=True)
        return 1
    finally:
        if server is not None:
            server.kill()
        if not args.keep_work:
            shutil.rmtree(work, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
