"""/debug/memory `device.allocator`: `fullest_bytes_in_use` and
`fullest_peak_bytes` are the maximum over `jax.local_devices()` of what the
two numbers beside them read from device 0. A mesh index spreads one shard
over every chip, and the fullest one is what limits."""

import jax
import pytest

from weaviate_tpu.monitoring import memory
from weaviate_tpu.monitoring.metrics import noop_metrics


class _Dev:
    def __init__(self, stats):
        self._stats = stats

    def memory_stats(self):
        return self._stats


@pytest.fixture
def ledger():
    led = memory.configure(memory.MemoryLedger(metrics=noop_metrics()))
    yield led
    memory.configure(None)


def _allocator(ledger, monkeypatch, per_device):
    monkeypatch.setattr(jax, "local_devices",
                        lambda: [_Dev(s) for s in per_device])
    return ledger.summary()["device"].get("allocator")


def test_on_one_device_the_fullest_is_device_zero(ledger, monkeypatch):
    a = _allocator(ledger, monkeypatch,
                   [{"bytes_in_use": 300, "peak_bytes_in_use": 900}])
    assert a["allocator_bytes_in_use"] == a["fullest_bytes_in_use"] == 300
    assert a["allocator_peak_bytes"] == a["fullest_peak_bytes"] == 900


def test_on_four_devices_the_fullest_is_the_maximum(ledger, monkeypatch):
    a = _allocator(ledger, monkeypatch, [
        {"bytes_in_use": 300, "peak_bytes_in_use": 900},
        {"bytes_in_use": 310, "peak_bytes_in_use": 700},
        {"bytes_in_use": 290, "peak_bytes_in_use": 1500},
        {"bytes_in_use": 305, "peak_bytes_in_use": 800}])
    assert a["allocator_bytes_in_use"] == 300       # device 0, as before
    assert a["allocator_peak_bytes"] == 900
    assert a["fullest_bytes_in_use"] == 310
    assert a["fullest_peak_bytes"] == 1500


@pytest.mark.parametrize("per_device", [
    [None, None, None, None],                        # the CPU backend
    [{"bytes_limit": 1}],                            # no bytes_in_use
])
def test_a_backend_that_reports_nothing_has_no_allocator_block(
        ledger, monkeypatch, per_device):
    assert _allocator(ledger, monkeypatch, per_device) is None


def test_without_a_peak_only_the_fullest_in_use_is_given(ledger, monkeypatch):
    a = _allocator(ledger, monkeypatch,
                   [{"bytes_in_use": 5}, {"bytes_in_use": 9}])
    assert a["fullest_bytes_in_use"] == 9
    assert "allocator_peak_bytes" not in a and "fullest_peak_bytes" not in a
