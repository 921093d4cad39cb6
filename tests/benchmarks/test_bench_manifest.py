"""BENCHMARK.json against the contract's mechanical rules, and against
the files it names."""

import fnmatch
import json
import os
import re
import sys

import pytest

from bench_helpers import BENCH, MANIFEST, ROOT, load_config

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}

# What a configuration's ``server_knobs`` may never set, whatever its
# source says: the settings that state a guarantee, the device's budget or
# the executor. A configuration's speed is not bought with a weaker
# promise, and ``guarantees.durability_mode == "group"`` keeps its meaning.
KNOBS_DENIED = {
    "durability-mode": "durability: what a 200 on a write means",
    "group-commit-max-ms": "durability: how long an acknowledgement may "
                           "wait for its group's fsync",
    "group-commit-max-ops": "durability: the size of a commit group",
    "replica-n": "replication: how many copies hold an acknowledged write",
    "verify-on-load": "integrity: snapshots checked against their "
                      "checksums at open",
    "device-budget-bytes": "the device's budget: what fits the chip",
    "use-mesh": "the executor: mesh or one device, chosen by the server",
    "cdc-staleness-budget": "consistency: how stale a follower's read "
                            "may be",
    "cdc-follow": "consistency: a follower answers for its primary",
    "qos-*": "availability: which requests are shed or cut short",
    "tls": "transport: the TLS table",
    "certificate": "transport: the TLS table",
    "key": "transport: the TLS table",
    "skip-verify": "transport: the TLS table",
}


def documented_knobs() -> set:
    """The keys of the server's own documented configuration
    (``pilosa_tpu/cli.py`` ``_DEFAULT_TOML``), commented-out ones too.
    The tests may import the program; the harness may not import JAX."""
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from pilosa_tpu.cli import _DEFAULT_TOML

    return set(re.findall(r"^(?:# )?([a-z][a-z0-9-]*) = ", _DEFAULT_TOML,
                          re.M))


def knobs_keep_to_the_rule(body: dict) -> None:
    """``server_knobs`` is empty, or every key of it (a) is a documented
    setting of the server, (b) states no guarantee, budget or executor,
    and (c) has its reason in ``knobs_why`` (which document and section
    describe the deployment shape) and its name in ``deployment``."""
    knobs = body["server_knobs"]
    if not knobs:
        assert not body.get("knobs_why"), "a reason for no knob"
        return
    documented = documented_knobs()
    for key in knobs:
        assert key in documented, (
            f"server_knobs names {key!r}, which the server's documented "
            "configuration (pilosa_tpu/cli.py _DEFAULT_TOML) does not")
        for pattern, touches in KNOBS_DENIED.items():
            assert not fnmatch.fnmatchcase(key, pattern), (
                f"server_knobs may not set {key!r}: it would touch "
                f"{touches}")
        assert key in body["deployment"], (
            f"the configuration's deployment text does not name {key!r}")
    why = body.get("knobs_why")
    assert isinstance(why, dict) and set(why) == set(knobs), (
        "knobs_why has to give every key of server_knobs, and no other, "
        "the document and section that describe the deployment shape")
    for key, reason in why.items():
        assert re.search(r"\S+\.md\b.*\S", reason), (
            f"knobs_why[{key!r}] names no document and section")


def test_exactly_the_contracts_keys():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= MANIFEST["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 << 10


def test_command_and_paths_stay_inside_the_benchmark():
    assert MANIFEST["command"] == ["python3", "benchmarks/run.py"]
    for p in MANIFEST["paths"]:
        assert os.path.isdir(os.path.join(ROOT, p))
        assert not p.startswith("/") and ".." not in p.split("/")


@pytest.mark.parametrize("cfg", MANIFEST["configs"], ids=lambda c: c["name"])
def test_configuration_entry(cfg):
    assert set(cfg) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(cfg["name"])
    assert 1 <= len(cfg["source"]) <= 200 and 1 <= len(cfg["why"]) <= 200
    assert cfg["file"].startswith("benchmarks/configs/")
    with open(os.path.join(ROOT, cfg["file"])) as f:
        body = json.load(f)
    assert body["name"] == cfg["name"]
    assert body["source"] == cfg["source"]
    assert body["reduced"] == cfg["reduced"] and len(cfg["reduced"]) <= 16
    assert all(NAME.match(k) for k in cfg["reduced"])
    assert set(body["reduced_why"]) == set(cfg["reduced"])
    # the guarantees are part of the result: stated in the file
    assert body["guarantees"]["durability_mode"] == "group"
    knobs_keep_to_the_rule(body)
    assert any(w["config"] == cfg["name"] for w in MANIFEST["workloads"])


MP = {"server_knobs": {"serving-workers": 2},
      "knobs_why": {"serving-workers": "docs/OPERATIONS.md, \"Deployment "
                    "shapes: single-process vs multi-process serving\""},
      "deployment": "one node, serving-workers = 2 in front of the one "
                    "process that owns the chip"}


def toy(**changes) -> dict:
    body = dict(load_config("taxi-rides"), **MP)
    body.update(changes)
    return {k: v for k, v in body.items() if v is not None}


@pytest.mark.parametrize("body", [
    load_config("taxi-rides"), toy(),
    toy(server_knobs={"serving-workers": 4, "ring-slots": 4096},
        knobs_why={"serving-workers": MP["knobs_why"]["serving-workers"],
                   "ring-slots": "docs/OPERATIONS.md, \"Deployment shapes\""},
        deployment="serving-workers = 4, ring-slots = 4096"),
], ids=["no-knob", "serving-workers", "two-knobs"])
def test_a_documented_deployment_setting_is_admitted(body):
    knobs_keep_to_the_rule(body)


@pytest.mark.parametrize("body,says", [
    (toy(knobs_why=None), "knobs_why"),
    (toy(knobs_why={}), "knobs_why"),
    (toy(knobs_why=dict(MP["knobs_why"], port="docs/OPERATIONS.md, x")),
     "knobs_why"),
    (toy(knobs_why={"serving-workers": "it is faster"}), "document"),
    (toy(deployment="one node, as Pilosa's example runs it"),
     "deployment text"),
    (toy(server_knobs={}), "a reason for no knob"),
    (toy(server_knobs={"serving-threads": 2},
         knobs_why={"serving-threads": "docs/OPERATIONS.md, \"x\""},
         deployment="serving-threads"), "documented"),
], ids=["no-why", "empty-why", "why-of-another-key", "why-names-no-document",
        "deployment-does-not-name-it", "why-without-a-knob", "undocumented"])
def test_a_knob_outside_the_rule_is_refused(body, says):
    with pytest.raises(AssertionError, match=says):
        knobs_keep_to_the_rule(body)


DENIED_KEYS = [
    "durability-mode", "group-commit-max-ms", "group-commit-max-ops",
    "replica-n", "verify-on-load", "device-budget-bytes", "use-mesh",
    "cdc-staleness-budget", "cdc-follow", "qos-max-inflight",
    "qos-tenant-inflight", "qos-default-deadline", "qos-hedge-delay",
    "qos-hedge-budget", "qos-breaker-threshold", "qos-breaker-cooldown",
    "certificate", "key", "skip-verify"]


@pytest.mark.parametrize("key", DENIED_KEYS)
def test_a_knob_that_states_a_guarantee_is_refused_by_name(key):
    """Documented, explained and named in the deployment text, and still
    refused: the message says which promise the key would touch."""
    assert key in documented_knobs()
    touches = next(t for p, t in KNOBS_DENIED.items()
                   if fnmatch.fnmatchcase(key, p))
    body = toy(server_knobs={key: 1},
               knobs_why={key: "docs/OPERATIONS.md, \"Write-path "
                               "durability\""},
               deployment=f"one node with {key} = 1")
    with pytest.raises(AssertionError) as e:
        knobs_keep_to_the_rule(body)
    assert key in str(e.value) and touches.split(":")[0] in str(e.value)


def test_the_cases_above_are_every_documented_key_the_list_denies():
    """A knob the server gains under a denied pattern, or loses, shows
    here and not as a hole in the rule."""
    documented = documented_knobs()
    assert {"serving-workers", "ring-slots", "ring-slot-bytes"} <= documented
    denied = {key for key in documented
              if any(fnmatch.fnmatchcase(key, p) for p in KNOBS_DENIED)}
    assert denied == set(DENIED_KEYS)


@pytest.mark.parametrize("cell", MANIFEST["workloads"], ids=lambda c: c["name"])
def test_cell_entry(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
    assert cell["chips"] in (1, 4) and 1 <= len(cell["why"]) <= 200
    assert cell["config"] in {c["name"] for c in MANIFEST["configs"]}
    assert os.path.isfile(os.path.join(BENCH, "traffic",
                                       cell["traffic"] + ".json"))


def test_cells_are_unique_and_few_take_four_chips():
    cells = MANIFEST["workloads"]
    pairs = [(c["config"], c["traffic"]) for c in cells]
    assert len(set(pairs)) == len(pairs)
    four = [c["name"] for c in cells if c["chips"] == 4]
    assert len(four) <= max(1, len(cells) // 2)


@pytest.mark.parametrize("m", MANIFEST["end_to_end"], ids=lambda m: m["name"])
def test_end_to_end_metric(m):
    assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                      "source"}
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher")
    assert m["source"] in ("host_clock", "device_trace")
    assert 0.01 <= m["bound"] <= 0.25


@pytest.mark.parametrize("m", MANIFEST["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metric_is_a_data_file_of_a_known_reader(m):
    assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                      "layer", "moves"}
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["source"] in SOURCES and m["better"] in ("lower", "higher")
    e2e = {e["name"]: e for e in MANIFEST["end_to_end"]}
    assert m["moves"] in e2e
    cells = {c["name"] for c in MANIFEST["workloads"]}
    mine = set(m.get("workloads", cells))
    assert mine <= cells
    # every cell that reads it reports the end-to-end metric it moves
    assert mine <= set(e2e[m["moves"]].get("workloads", cells))
    with open(os.path.join(BENCH, "layer_metrics", m["name"] + ".json")) as f:
        spec = json.load(f)
    assert spec["reader"] in ("ratio", "trace_idle", "trace_ops", "end_to_end")


def test_names_are_unique_and_setup_is_reported():
    metrics = [m["name"] for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]]
    assert len(set(metrics)) == len(metrics)
    setup = [m for m in MANIFEST["end_to_end"] if m["name"] == "setup_s"]
    assert len(setup) == 1 and "workloads" not in setup[0]
    assert setup[0]["bound"] <= 0.25


def test_peaks_table_names_its_source():
    with open(os.path.join(BENCH, "peaks.json")) as f:
        peaks = json.load(f)
    assert "TPU v5e" in peaks["source"]
    assert peaks["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9


def test_every_file_under_paths_is_named_from_a_names_characters():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    listed = os.popen(f"cd {ROOT} && git ls-files -co --exclude-standard "
                      + " ".join(MANIFEST["paths"])).read().split()
    assert listed and all(ok.match(p) for p in listed)
