"""The one general traffic generator: a mix is a data file.

A mix (``benchmarks/traffic/<mix>.json``) names groups of closed-loop
clients, each with a fixed rotation of templates; client ``k`` of the
cell starts at offset ``k`` of its group's rotation, so every window of
every seed holds the templates in the same proportions. A template is
a semantic form (kind, fields, filter, dimensions) plus the constants
to draw; the seed draws only those constants, among constants of equal
cost. The PQL text is rendered from the semantic form here, and the
reference answers the same form, so a template cannot say one thing to
the server and another to the reference.

Template kinds: ``count`` (Count of a Row or an Intersect of Rows),
``sum`` (Sum of an int field under such a filter), ``topn`` (TopN of a
field under a filter), ``groupby`` (GroupBy over Rows dimensions,
optional filter and Sum aggregate), ``set`` (one Set).

Filter terms (``"filter": [term, ...]``, one term a plain ``Row`` or a
``Union`` of them, several an ``Intersect``); every constant may be a
drawn variable:
  [F, r]                     Row(F=r)
  [F, {"in": [a, b]}]        Union(Row(F=a), Row(F=b)); one value, Row(F=a)
  [I, {"lt": v}]             Row(I < v) of int field I
  [I, {"between": [lo, hi]}] Row(I >< [lo, hi]), both ends inside

Draw kinds (``"draw": {name: spec}``, evaluated in file order):
  {"row_of": F}              a row id of field F, uniform
  {"row_of": F, "top": K}    among F's K commonest rows (config weights)
  {"row_of": F, "span": L}   a row id that leaves L rows from it on
  {"row_of_var": V}          a row id of the field that variable V named
  {"fields": [F, ...]}       distinct fields; the name "f,g" binds two
  {"column": true}           a column of the loaded index, uniform
  {"affine": [V, a, b]}      a * V + b
  {"int": [lo, hi]}          an integer, uniform, both ends inside
"""

from __future__ import annotations

import json
import os
import random
import zlib

import numpy as np

from harness.datagen import SHARD_WIDTH, field_rows, field_weights

WRITE_KINDS = ("set",)


def load_mix(path: str) -> dict:
    with open(path) as f:
        mix = json.load(f)
    for g in mix["groups"]:
        for t in g["rotation"]:
            if t not in mix["templates"]:
                raise ValueError(f"{path}: group {g['name']} rotates over "
                                 f"unknown template {t!r}")
    return mix


def _names(template: dict, config: dict):
    """Every literal field name a template can touch."""
    fields = config["fields"]
    for draw in template.get("draw", {}).values():
        if "row_of" in draw:
            yield draw["row_of"]
        for f in draw.get("fields", ()):
            yield f
    for key in ("field", "sum"):
        if template.get(key) in fields:
            yield template[key]
    for f, _ in template.get("filter", ()):
        if f in fields:
            yield f
    for d in template.get("dims", ()):
        yield d["field"]


def fields_read(mix: dict, config: dict) -> list[str]:
    """The fields the data writer has to materialise for this mix."""
    seen = {f for t in mix["templates"].values() for f in _names(t, config)}
    return [f for f in config["fields"] if f in seen]


def preload_rows(mix: dict, config: dict) -> list[tuple[str, int]]:
    """Every (set field, row) a filter constant can name, drawn or
    written out in an ``in`` list: the rows set-up makes resident before
    the window when the mix asks for it."""
    rows = set()
    for t in mix["templates"].values():
        if t["kind"] in WRITE_KINDS:
            continue
        for f, spec in t.get("filter", ()):
            if isinstance(spec, dict) and f in config["fields"]:
                rows.update((f, r) for r in spec.get("in", ())
                            if isinstance(r, int))
        for draw in t.get("draw", {}).values():
            if "row_of" in draw:
                rows.update((draw["row_of"], r)
                            for r in _domain(draw, config))
            elif "fields" in draw:
                for f in draw["fields"]:
                    rows.update((f, r) for r in range(
                        field_rows(config["fields"][f])))
    return sorted(rows)


def _domain(draw: dict, config: dict) -> list[int]:
    spec = config["fields"][draw["row_of"]]
    n = field_rows(spec)
    if "top" in draw:
        order = np.argsort(-field_weights(spec), kind="stable")
        return sorted(order[:draw["top"]].tolist())
    return list(range(n - draw.get("span", 1) + 1))


class Client:
    """The request stream of one closed-loop client: deterministic in
    (seed, stream, mix, client index)."""

    def __init__(self, mix: dict, config: dict, n_shards: int, group: dict,
                 k: int, seed: int, stream: str):
        self.mix, self.config = mix, config
        self.n_columns = n_shards * SHARD_WIDTH
        self.group = group
        self.k = k
        self.i = 0
        self.rng = random.Random(zlib.crc32(
            f"{seed}/{stream}/{mix['name']}/{k}".encode()) ^ (seed << 1))
        # the rows each row_of draw may name, worked out once
        self._domains = {
            (t, name): _domain(draw, config)
            for t, tp in mix["templates"].items()
            for name, draw in tp.get("draw", {}).items() if "row_of" in draw}

    def _draw(self, tname: str) -> dict:
        env: dict = {}
        for name, draw in self.mix["templates"][tname].get("draw", {}).items():
            if "row_of" in draw:
                env[name] = self.rng.choice(self._domains[tname, name])
            elif "row_of_var" in draw:
                spec = self.config["fields"][env[draw["row_of_var"]]]
                env[name] = self.rng.randrange(field_rows(spec))
            elif "fields" in draw:
                names = name.split(",")
                for n, f in zip(names, self.rng.sample(draw["fields"],
                                                       len(names))):
                    env[n] = f
            elif "column" in draw:
                env[name] = self.rng.randrange(self.n_columns)
            elif "affine" in draw:
                v, a, b = draw["affine"]
                env[name] = a * env[v] + b
            elif "int" in draw:
                env[name] = self.rng.randint(*draw["int"])
            else:
                raise ValueError(f"unknown draw {draw!r}")
        return env

    def next(self) -> tuple[str, str, dict]:
        """(template name, PQL text, semantic form with constants)."""
        rotation = self.group["rotation"]
        name = rotation[(self.k + self.i) % len(rotation)]
        self.i += 1
        t = self.mix["templates"][name]
        env = self._draw(name)
        val = lambda x: _bound(x, env)
        sem = {"kind": t["kind"]}
        if "filter" in t:
            sem["filter"] = [(val(f), val(r)) for f, r in t["filter"]]
        if t["kind"] == "topn":
            sem["field"] = t["field"]
        elif t["kind"] == "sum":
            sem["sum"] = t["sum"]
        elif t["kind"] == "groupby":
            sem["dims"] = [{k: val(v) for k, v in d.items()}
                           for d in t["dims"]]
            sem["sum"] = t.get("sum")
        elif t["kind"] == "set":
            sem.update(field=val(t["field"]), row=val(t["row"]),
                       column=val(t["column"]))
        return name, render(sem), sem


def _bound(x, env: dict):
    """A template's value as written, the variables it names replaced by
    what was drawn for them, inside a filter term too."""
    if isinstance(x, str):
        return env.get(x, x)
    if isinstance(x, dict):
        return {op: _bound(v, env) for op, v in x.items()}
    if isinstance(x, list):
        return [_bound(v, env) for v in x]
    return x


def render_term(field: str, spec) -> str:
    """PQL text of one filter term (the forms the module's text lists)."""
    if not isinstance(spec, dict):
        return f"Row({field}={spec})"
    (op, v), = spec.items()
    if op == "in":
        rows = [f"Row({field}={r})" for r in v]
        if not rows:
            raise ValueError(f"filter term on {field} names no row")
        return rows[0] if len(rows) == 1 else f"Union({', '.join(rows)})"
    if op == "between":
        return f"Row({field} >< [{v[0]}, {v[1]}])"
    if op == "lt":
        return f"Row({field} < {v})"
    raise ValueError(f"unknown filter term {spec!r} on {field}")


def render(sem: dict) -> str:
    """PQL text of a semantic form."""
    kind = sem["kind"]
    if kind == "set":
        return f"Set({sem['column']}, {sem['field']}={sem['row']})"
    rows = [render_term(f, r) for f, r in sem.get("filter", ())]
    filt = (rows[0] if len(rows) == 1
            else f"Intersect({', '.join(rows)})" if rows else None)
    if kind == "count":
        return f"Count({filt})"
    if kind == "sum":
        return f"Sum({filt}, field=\"{sem['sum']}\")"
    if kind == "topn":
        return (f"TopN({sem['field']}, {filt})" if filt
                else f"TopN({sem['field']})")
    if kind == "groupby":
        parts = []
        for d in sem["dims"]:
            args = [d["field"]]
            if d.get("previous") is not None:
                args.append(f"previous={d['previous']}")
            if d.get("limit"):
                args.append(f"limit={d['limit']}")
            parts.append(f"Rows({', '.join(args)})")
        if filt:
            parts.append(f"filter={filt}")
        if sem.get("sum"):
            parts.append(f"aggregate=Sum(field=\"{sem['sum']}\")")
        return f"GroupBy({', '.join(parts)})"
    raise ValueError(f"unknown template kind {kind!r}")


def clients(mix: dict, config: dict, n_shards: int, seed: int,
            stream: str) -> list[Client]:
    """The cell's clients, numbered across groups."""
    out = []
    for g in mix["groups"]:
        for _ in range(g["clients"]):
            out.append(Client(mix, config, n_shards, g, len(out), seed,
                              stream))
    return out


def mix_path(root: str, name: str) -> str:
    return os.path.join(root, "traffic", f"{name}.json")
