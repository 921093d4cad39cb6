"""Reduction of a profiler trace (``.xplane.pb``) to device numbers.

Read with ``jaxlib._profile_data`` alone, so the harness' parent stays
off JAX. A device is a plane named ``/device:TPU:<n>``; its operations
are the events of its ``XLA Ops`` line. On the CPU (rehearsals and the
tests' recorded trace) the XLA thread-pool lines of ``/host:CPU`` stand
in, one "device" for all of them, so the arithmetic below is exercised;
a number from such a trace is never a device metric.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
CPU_PLANE = "/host:CPU"
CPU_LINE = re.compile(r"^tf_XLA|^XLA")
TOP_N = 10
GAPS_N = 5


def newest_xplane(log_dir: str) -> str | None:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(paths, key=os.path.getmtime) if paths else None


_HLO = re.compile(r"^%?([\w.\-]+) = (\(?)([a-z0-9]+\[[0-9,]*\])")


def short_name(text: str) -> str:
    """An operation's XLA name with the shape of its result, from the HLO
    text the TPU's trace carries as the event name: ``fusion.36
    u32[128,12,2048]x16`` (x16: a tuple of sixteen)."""
    m = _HLO.match(text)
    if not m:
        return text[:80]
    name, is_tuple, shape = m.groups()
    if is_tuple:
        head = text.split(") ", 1)[0]
        shape += f"x{head.count('[')}"
    return f"{name} {shape}"


def _device_events(path: str):
    """Per device, its operations as (start_s, end_s, name), and the
    extent of those planes' events, first start to last end."""
    from jaxlib import _profile_data

    data = _profile_data.ProfileData.from_file(path)

    def collect(plane, is_ops, every_line: bool):
        ops, first, last = [], float("inf"), 0.0
        for line in plane.lines:
            if not (every_line or is_ops(line.name)):
                continue  # the host's Python lines hold millions of events
            keep = is_ops(line.name)
            for e in line.events:
                end = e.start_ns + e.duration_ns
                first, last = min(first, e.start_ns), max(last, end)
                if keep:
                    ops.append((e.start_ns * 1e-9, end * 1e-9,
                                short_name(e.name)))
        return ops, first, last

    tpu = [collect(p, lambda n: n == OPS_LINE, True)
           for p in data.planes if DEVICE_PLANE.match(p.name)]
    if not tpu:
        tpu = [collect(p, lambda n: bool(CPU_LINE.match(n)), False)
               for p in data.planes if p.name == CPU_PLANE]
    if not tpu:
        return [], 0.0
    first = min(t[1] for t in tpu)
    last = max(t[2] for t in tpu)
    return [t[0] for t in tpu], max(0.0, last - first) * 1e-9


def _union(events) -> list[tuple[float, float]]:
    """Merged busy intervals of one device."""
    merged: list[list[float]] = []
    for start, end, _ in sorted(events):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [(a, b) for a, b in merged]


def reduce(path: str, asked_s: float) -> dict | None:
    """Busy seconds (mean over devices), time per operation name (mean
    over devices), the longest idle gaps of the first device, and the
    traced span. The span is the ``asked_s`` seconds the server held the
    capture open or, where the device's own events reach further (the
    profiler records while it starts and stops too), their extent: a
    device that idles at the edges leaves no event to mark them. Returns
    None when no operation ran on any device."""
    devices, extent_s = _device_events(path)
    devices = [d for d in devices if d]
    window_s = max(float(asked_s), extent_s)
    if not devices:
        return None
    n = len(devices)
    busy = []
    ops: dict[str, float] = {}
    gaps: list[tuple[float, float]] = []
    for events in devices:
        merged = _union(events)
        busy.append(sum(b - a for a, b in merged))
        for start, end, name in events:
            ops[name] = ops.get(name, 0.0) + (end - start) / n
        if len(devices) == 1 or not gaps:
            gaps = [(b0, a1) for (_, b0), (a1, _) in zip(merged, merged[1:])]
    gaps.sort(key=lambda g: g[0] - g[1])
    return {
        "devices": n,
        "window_s": window_s,
        "busy_s": sum(busy) / n,
        "busy_s_per_device": busy,
        "ops": ops,
        "idle_gaps": [(a, b) for a, b in gaps[:GAPS_N]],
    }


def breakdown(reduced: dict) -> dict:
    """The contract's ``breakdown``: the operations that took most device
    time, and the longest idle gaps by what the host was doing in them,
    which reads ``unknown`` until the program records host spans."""
    top = sorted(reduced["ops"].items(), key=lambda kv: -kv[1])[:TOP_N]
    return {
        "device_ops": [[name, seconds] for name, seconds in top],
        "idle_gaps": [["unknown", b - a] for a, b in reduced["idle_gaps"]],
    }


def op_seconds(reduced: dict, pattern: str) -> float:
    """Seconds (mean over devices) in operations whose name matches."""
    rx = re.compile(pattern)
    return sum(s for name, s in reduced["ops"].items() if rx.search(name))
