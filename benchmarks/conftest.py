"""Test set-up of the benchmarks: each test's traced window writes its
profile under the test's own temporary directory, not under the
checkout's shared ``.bench_trace``, so that tests run in parallel
processes never read or delete each other's profiles."""

import pytest


@pytest.fixture(autouse=True)
def private_trace_dir(monkeypatch, tmp_path):
    from chip import run
    monkeypatch.setattr(run, "TRACE_DIR", tmp_path / "bench_trace")
