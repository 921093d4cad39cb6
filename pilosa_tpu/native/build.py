"""Build the fastbits native library (g++, no external deps).

The artefact is named after a hash of its source and build flags, so a
library built from other source — an old checkout's, or one copied in
with a fresh mtime — is never the file this module looks for.
"""

from __future__ import annotations

import hashlib
import os
import shutil
import subprocess

_DIR = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(_DIR, "fastbits.cpp")
_FLAGS = ["-O3", "-fPIC", "-shared"]


def lib_path() -> str:
    """The only library file the current source may be loaded from."""
    h = hashlib.sha256(" ".join(_FLAGS).encode())
    with open(SRC, "rb") as f:
        h.update(f.read())
    return os.path.join(_DIR, f"libfastbits-{h.hexdigest()[:16]}.so")


def build(force: bool = False) -> str | None:
    """Compile the library if needed; returns the .so path or None when no
    toolchain is available (callers fall back to numpy)."""
    lib = lib_path()
    if not force and os.path.exists(lib):
        return lib
    gxx = shutil.which("g++") or shutil.which("clang++")
    if gxx is None:
        return None
    tmp = f"{lib[:-3]}.{os.getpid()}.tmp.so"  # *.so: stays gitignored
    try:
        subprocess.run([gxx, *_FLAGS, "-o", tmp, SRC], check=True,
                       capture_output=True, timeout=120)
    except (subprocess.CalledProcessError, subprocess.TimeoutExpired):
        if os.path.exists(tmp):
            os.unlink(tmp)
        return None
    os.replace(tmp, lib)
    return lib


if __name__ == "__main__":
    path = build(force=True)
    print(path or "build failed / no compiler")
