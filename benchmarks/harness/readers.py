"""Per-layer metrics: a metric is a data file naming one of these readers.

``benchmarks/layer_metrics/<name>.json`` holds ``{"reader": kind, ...}``:

``ratio``      scale * sum(delta of numerator series) / sum(delta of
               denominator series) over the window. A series is a name
               on ``/metrics`` (unlabelled), ``vars.<group>.<name>`` from
               ``/debug/vars``, ``compile_cache.entries`` (files in the
               compile cache), or one of the generator's own counts:
               ``gen.cpu_seconds``, ``gen.window_seconds``,
               ``gen.requests``, ``gen.reads``, ``gen.acknowledged_writes``.
               Without a denominator it is the plain delta.
``trace_idle`` 100 * (1 - device-busy seconds / traced seconds), the mean
               over the chips, from the traced span.
``trace_ops``  100 * seconds in device operations whose XLA name matches
               ``pattern`` / traced seconds, the mean over the chips.
``end_to_end`` the run's own value of an end-to-end quantity by name (a
               statistic kept beside the one the bound is on).

A reader that finds nothing to read (a series the server does not
export, a zero denominator, no trace) returns None and the harness
leaves the metric out of the line.
"""

from __future__ import annotations

import json
import os

from harness import trace


def read(root: str, name: str, before: dict, after: dict,
         reduced: dict | None, values: dict) -> float | None:
    with open(os.path.join(root, "layer_metrics", f"{name}.json")) as f:
        spec = json.load(f)
    kind = spec["reader"]
    scale = float(spec.get("scale", 1.0))
    if kind == "ratio":
        num = _delta(spec["numerator"], before, after)
        if num is None:
            return None
        if "denominator" not in spec:
            return scale * num
        den = _delta(spec["denominator"], before, after)
        return scale * num / den if den else None
    if kind == "trace_idle":
        if not reduced or not reduced["window_s"]:
            return None
        return 100.0 * (1.0 - reduced["busy_s"] / reduced["window_s"])
    if kind == "trace_ops":
        if not reduced or not reduced["window_s"]:
            return None
        return (100.0 * trace.op_seconds(reduced, spec["pattern"])
                / reduced["window_s"])
    if kind == "end_to_end":
        return values.get(spec["name"])
    raise ValueError(f"layer metric {name}: unknown reader {kind!r}")


def _delta(series: list, before: dict, after: dict) -> float | None:
    if any(s not in after for s in series):
        return None
    return sum(after[s] - before.get(s, 0.0) for s in series)
