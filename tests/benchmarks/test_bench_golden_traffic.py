"""Today's traffic, held: for every cell of ``BENCHMARK.json`` and three
seeds, a hash of the first 200 (template, PQL) pairs of every client, as
the generator of 9a8c9f6 (before ISSUE 41 taught it new filter terms and
an integer draw) sent them. A shipped mix must send the same text in the
same order for the same seed whatever the generator learns next: the
ledger's numbers for a cell are numbers of that traffic.

``data/traffic_golden.json`` was written by this file's ``digests`` run
on the parent's ``harness/traffic.py`` (``python
tests/benchmarks/test_bench_golden_traffic.py`` rewrites it: only a PR
that means to change a shipped mix's traffic does that, and says so)."""

import hashlib
import json
import os

import pytest

from bench_helpers import BENCH, CELLS, DATA, config_and_mix
from harness import traffic

GOLDEN = os.path.join(DATA, "traffic_golden.json")
SEEDS = (1, 41, 3_200_000_029)
PAIRS_PER_CLIENT = 200


def digests(cell: dict, seed: int) -> list[str]:
    """One digest a client, in the cell's client order."""
    config, mix = config_and_mix(cell)
    out = []
    for client in traffic.clients(mix, config, config["shards"], seed,
                                  "window"):
        h = hashlib.sha256()
        for _ in range(PAIRS_PER_CLIENT):
            name, pql, _sem = client.next()
            h.update(f"{name}\t{pql}\n".encode())
        out.append(h.hexdigest()[:16])
    return out


def golden() -> dict:
    if not os.path.exists(GOLDEN):  # only while this file rewrites it
        return {"cells": {}}
    with open(GOLDEN) as f:
        return json.load(f)


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("name", sorted(golden()["cells"]))
def test_shipped_mix_sends_what_it_sent_at_the_parent(name, seed):
    assert name in CELLS, "a cell of the golden file left the manifest"
    assert digests(CELLS[name], seed) == golden()["cells"][name][str(seed)]


def test_golden_holds_every_mix_a_cell_names():
    held = {CELLS[name]["traffic"] for name in golden()["cells"]
            if name in CELLS}
    shipped = {f[:-len(".json")]
               for f in os.listdir(os.path.join(BENCH, "traffic"))}
    assert golden()["pairs_per_client"] == PAIRS_PER_CLIENT
    assert golden()["seeds"] == list(SEEDS)
    # a mix added after the golden was taken is not in it (a later PR adds
    # files and edits none); every mix it was taken from is still shipped
    assert held <= shipped and len(held) >= 6


if __name__ == "__main__":
    body = {"taken_at": "9a8c9f6 (harness/traffic.py as PR 40 left it)",
            "seeds": list(SEEDS), "pairs_per_client": PAIRS_PER_CLIENT,
            "cells": {name: {str(s): digests(cell, s) for s in SEEDS}
                      for name, cell in sorted(CELLS.items())}}
    with open(GOLDEN, "w") as f:
        json.dump(body, f, indent=1)
        f.write("\n")
