"""Protobuf wire format (generated on demand via protoc).

``pb2()`` returns the generated module, compiling internal.proto on first
use; returns None when protoc or the protobuf runtime is unavailable, in
which case the HTTP layer serves JSON only (content negotiation degrades
gracefully).

The generated ``internal_pb2.py`` leads with a stamp line carrying the
hash of the ``internal.proto`` it was generated from. A file without the
current source's stamp — an old checkout's, or one copied in with a fresh
mtime — is regenerated, never imported.
"""

from __future__ import annotations

import hashlib
import os
import shutil
import subprocess
import tempfile

_DIR = os.path.dirname(os.path.abspath(__file__))
_PROTO = os.path.join(_DIR, "internal.proto")
_GEN = os.path.join(_DIR, "internal_pb2.py")

_pb2 = None
_tried = False


def _stamp() -> bytes:
    with open(_PROTO, "rb") as f:
        return b"# source-sha256: %s\n" % hashlib.sha256(
            f.read()).hexdigest().encode()


def _is_current(stamp: bytes) -> bool:
    try:
        with open(_GEN, "rb") as f:
            return f.readline() == stamp
    except FileNotFoundError:
        return False


def _generate(stamp: bytes) -> bool:
    protoc = shutil.which("protoc")
    if protoc is None:
        return False
    with tempfile.TemporaryDirectory() as out:
        try:
            subprocess.run(
                [protoc, f"--python_out={out}", f"--proto_path={_DIR}",
                 "internal.proto"],
                check=True, capture_output=True, timeout=60,
            )
        except (subprocess.CalledProcessError, subprocess.TimeoutExpired):
            return False
        with open(os.path.join(out, "internal_pb2.py"), "rb") as f:
            body = f.read()
    tmp = f"{_GEN}.{os.getpid()}.tmp"  # same directory: atomic replace
    with open(tmp, "wb") as f:
        f.write(stamp + body)
    os.replace(tmp, _GEN)
    return True


def pb2():
    global _pb2, _tried
    if _pb2 is not None or _tried:
        return _pb2
    _tried = True
    try:
        import google.protobuf  # noqa: F401
    except ImportError:
        return None
    stamp = _stamp()
    if not _is_current(stamp) and not _generate(stamp):
        return None
    from pilosa_tpu.wire import internal_pb2

    _pb2 = internal_pb2
    return _pb2


def available() -> bool:
    return pb2() is not None
