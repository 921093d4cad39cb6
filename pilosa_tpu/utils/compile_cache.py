"""Persistent XLA compile cache placement.

Every entry point that will touch a device calls ``configure()`` before
its first compile (``cli.cmd_server``, ``__graft_entry__.py``). The
directory is part of JAX's cache key, so it must be the same path on
every start: ``JAX_COMPILATION_CACHE_DIR`` when
the operator set it (JAX reads that itself — nothing is set in code),
else ``<checkout>/.jax_cache`` beside the package. Never a temp name, a
pid, a port, the data dir or the time.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def cache_dir() -> str:
    """Where compiled programs persist for this checkout and environment
    (imports no JAX — callers that only report the path stay off it)."""
    return os.environ.get(ENV_VAR) or DEFAULT_DIR


def named_jit(name: str, fn, **jit_kwargs):
    """``jax.jit(fn)`` under a stable program name: XLA calls the module
    ``jit_<name>``, which is what a profiler capture's ``XLA Modules``
    line, a compile log and the lowered text show. Every program of the
    served path goes through here so none of them reads ``jit_body``.
    The name is part of the persistent cache's key."""
    import jax

    fn.__name__ = fn.__qualname__ = name
    return jax.jit(fn, **jit_kwargs)


def pallas_interpret() -> bool:
    """Off the TPU a Pallas kernel's body runs through Pallas'
    interpreter (the ``interpret`` argument of its ``pallas_call``)."""
    import jax

    return jax.default_backend() != "tpu"


def configure() -> str:
    """Point JAX's persistent compile cache at ``cache_dir()`` and cache
    every program, however quickly it compiled: the default 1 s
    threshold would leave the small patch/scatter programs uncached and
    make "a restart compiles nothing" depend on compile-time noise."""
    import jax

    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return cache_dir()
