"""bench.py measures the accelerator: with none it stops, unless the caller
asked for a CPU rehearsal; and its MFU/bandwidth denominators come from a
table keyed by the device JAX reports, where an unknown device is an error."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench  # noqa: E402


def test_no_accelerator_stops_the_benchmark(monkeypatch):
    """JAX came up on the CPU and nobody asked for that: refuse to measure
    the host under a device metric's name."""
    monkeypatch.delenv("JAX_PLATFORMS")
    with pytest.raises(SystemExit, match="no accelerator"):
        bench.require_accelerator()


def test_caller_set_cpu_is_a_rehearsal(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    bench.require_accelerator()  # returns: a rehearsal, stamped by platform
    assert bench.device_platform() == "cpu"


def test_unknown_device_kind_has_no_peaks():
    """The CPU is not in the peaks table — and neither is any chip nobody
    looked up: no silent v5e denominators."""
    with pytest.raises(RuntimeError, match="no peak specs"):
        bench.device_peaks()
    assert bench.DEVICE_PEAKS["TPU v5 lite"] == {"bf16_flops": 197e12, "hbm_gbs": 819.0}
