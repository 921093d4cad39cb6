"""A minimal writer of the profiler's ``.xplane.pb`` (XSpace) format, so
the trace reduction can be tested on traces whose answers are known.

Only the fields the reduction reads are written: planes with a name,
lines with a name and a start, events with a name (through the plane's
event metadata), an offset and a duration. Field numbers are those of
tsl/profiler/protobuf/xplane.proto.
"""

from __future__ import annotations


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _int(field: int, value: int) -> bytes:
    return _varint(field << 3) + _varint(value)


def _bytes(field: int, data: bytes) -> bytes:
    return _varint(field << 3 | 2) + _varint(len(data)) + data


def xspace(planes: list) -> bytes:
    """``planes`` is [(plane name, [(line name, [(event name, start_ns,
    duration_ns)])])]; returns the serialized XSpace."""
    out = b""
    for pid, (plane_name, lines) in enumerate(planes, 1):
        names: dict[str, int] = {}
        body = _int(1, pid) + _bytes(2, plane_name.encode())
        for lid, (line_name, events) in enumerate(lines, 1):
            line = _int(1, lid) + _bytes(2, line_name.encode())
            line += _int(3, 0)  # timestamp_ns: offsets count from 0
            for name, start_ns, duration_ns in events:
                mid = names.setdefault(name, len(names) + 1)
                event = (_int(1, mid) + _int(2, start_ns * 1000)
                         + _int(3, duration_ns * 1000))
                line += _bytes(4, event)
            body += _bytes(3, line)
        for name, mid in names.items():
            meta = _int(1, mid) + _bytes(2, name.encode())
            body += _bytes(4, _int(1, mid) + _bytes(2, meta))
        out += _bytes(1, body)
    return out
