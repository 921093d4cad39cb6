"""Test configuration: run JAX on an 8-device virtual CPU mesh.

Mirrors the reference's test strategy of exercising multi-node logic in one
process without a real cluster (test.MustRunCluster — SURVEY.md §4): we
exercise multi-chip sharding logic without TPUs by forcing 8 host CPU
devices.

The environment pins the CPU for every subprocess a test spawns; this
process pins it through jax.config as well, which also holds if a pytest
plugin imported jax before this file ran. Tests never reach for an
accelerator.
"""

import os

xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax

jax.config.update("jax_platforms", "cpu")

import pytest


@pytest.fixture(autouse=True)
def _fresh_global_row_cache():
    """Isolate the process-global device residency cache per test: leaves
    are keyed by (index, field, ...) names, which recur across tests that
    forget to close their holder."""
    from pilosa_tpu.storage import residency

    residency.global_row_cache().clear()
    yield
