"""The row cache's default budget follows the chip (ISSUE 34): with
``device-budget-bytes`` unset or 0 a server's budget is three quarters of
the smallest ``memory_stats()["bytes_limit"]`` over its local devices,
4 GiB where the backend reports none (the CPU these tests run on), and a
non-zero knob still wins. No device here has a memory limit, so the
devices are stand-ins that answer ``memory_stats()`` and nothing else."""

import pytest

from cluster_helpers import req, uri
from pilosa_tpu.storage import residency

V5E_LIMIT = 16_909_336_576  # what a chip might report: 15.75 GiB


class FakeDevice:
    def __init__(self, stats):
        self._stats = stats

    def memory_stats(self):
        return self._stats


@pytest.fixture
def own_cache():
    """A row cache of this test's own in the process global's place: an
    open server re-sizes the global cache."""
    old = residency.global_row_cache()
    cache = residency.DeviceRowCache()
    residency.set_global_row_cache(cache)
    yield cache
    residency.set_global_row_cache(old)


def open_server(tmp_path, monkeypatch, devices, **config):
    from pilosa_tpu.server import Server, ServerConfig

    server = Server(ServerConfig(
        data_dir=str(tmp_path / "node"), port=0, name="t",
        anti_entropy_interval=0, heartbeat_interval=0, **config))
    with monkeypatch.context() as m:  # the stand-ins answer open() alone
        if devices is not None:
            m.setattr(residency.jax, "local_devices", lambda: devices)
        return server.open()


def test_three_quarters_of_the_chips_limit():
    chip = FakeDevice({"bytes_limit": V5E_LIMIT, "bytes_in_use": 123})
    assert residency.default_budget_bytes([chip]) == V5E_LIMIT * 3 // 4
    assert residency.default_budget_bytes([chip]) == 12_682_002_432  # 11.8 GiB


@pytest.mark.parametrize("stats", [None, {}, {"bytes_in_use": 5},
                                   {"bytes_limit": 0}],
                         ids=["no-stats", "empty", "no-limit", "zero-limit"])
def test_four_gib_where_the_backend_gives_no_limit(stats):
    assert residency.DEFAULT_BUDGET_BYTES == 4 << 30
    assert residency.default_budget_bytes([FakeDevice(stats)]) == 4 << 30
    # one chip of a mesh that cannot say: the fallback, not a guess
    assert residency.default_budget_bytes(
        [FakeDevice({"bytes_limit": V5E_LIMIT}), FakeDevice(stats)]) == 4 << 30


def test_the_smallest_chip_of_a_mesh_decides():
    mesh = [FakeDevice({"bytes_limit": n})
            for n in (V5E_LIMIT, V5E_LIMIT - (1 << 30), V5E_LIMIT, V5E_LIMIT)]
    assert residency.default_budget_bytes(mesh) == (
        (V5E_LIMIT - (1 << 30)) * 3 // 4)


def test_the_cpu_and_no_device_at_all_fall_back():
    assert residency.default_budget_bytes() == 4 << 30  # jax.local_devices()
    assert residency.default_budget_bytes([]) == 4 << 30


@pytest.mark.parametrize("knob", [None, 0], ids=["unset", "zero"])
def test_a_server_without_the_knob_measures_its_chips(
        tmp_path, monkeypatch, own_cache, knob):
    chips = [FakeDevice({"bytes_limit": V5E_LIMIT}) for _ in range(4)]
    s = open_server(tmp_path, monkeypatch, chips, device_budget_bytes=knob)
    try:
        assert residency.global_row_cache() is own_cache
        assert own_cache.budget_bytes == V5E_LIMIT * 3 // 4
        # /metrics and /debug/vars show the derived value, as an exact int
        text = req("GET", f"{uri(s)}/metrics", raw=True).decode()
        assert (f"pilosa_tpu_residency_budget_bytes {V5E_LIMIT * 3 // 4}\n"
                in text)
        dv = req("GET", f"{uri(s)}/debug/vars")
        assert dv["residency"]["residency_budget_bytes"] == V5E_LIMIT * 3 // 4
        assert dv["residency"]["residency_miss_bytes"] == 0
        assert "pilosa_tpu_residency_miss_bytes_total 0\n" in text
    finally:
        s.close()


def test_a_server_on_the_cpu_keeps_four_gib(tmp_path, monkeypatch, own_cache):
    own_cache.budget_bytes = 1  # whatever it was, open() measures again
    s = open_server(tmp_path, monkeypatch, None)
    try:
        assert own_cache.budget_bytes == 4 << 30
    finally:
        s.close()


def test_a_non_zero_knob_wins(tmp_path, monkeypatch, own_cache):
    chips = [FakeDevice({"bytes_limit": V5E_LIMIT})]
    s = open_server(tmp_path, monkeypatch, chips,
                    device_budget_bytes=123_456_789)
    try:
        cache = residency.global_row_cache()
        assert cache is not own_cache  # a cache of the size asked for
        assert cache.budget_bytes == 123_456_789
        text = req("GET", f"{uri(s)}/metrics", raw=True).decode()
        assert "pilosa_tpu_residency_budget_bytes 123456789\n" in text
    finally:
        s.close()
