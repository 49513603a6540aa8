"""Test set-up of the benchmark harness: the CPU with 8 virtual devices,
set before JAX is first imported, as ``tests/conftest.py`` does."""

import os
import sys

if "jax" not in sys.modules:
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()

import pytest  # noqa: E402

from chip.testkit import STREAM, X4, shrink, stream_cell, x4_cell  # noqa: E402,E501


@pytest.fixture
def tiny(monkeypatch):
    """``tiny(name)`` resolves a cell of ``BENCHMARK.json`` (or the
    four-chip batch cell, :data:`X4`) and shrinks it, or builds the
    tests' monitor cell, :data:`STREAM`; the table of peaks answers for
    the CPU as for a v5e chip, and the reference runs in this process."""
    from chip import run, trace
    real = trace.peaks
    monkeypatch.setattr(trace, "peaks", lambda kind: real("TPU v5 lite"))

    def make(name: str) -> dict:
        if name == STREAM:
            cell = stream_cell()
            monkeypatch.setattr(cell["driver"], "REFERENCE_WORKERS", 1)
            return cell
        if name == X4:
            return shrink(x4_cell())
        return shrink(run.resolve(run.load_benchmark(), name))
    return make
