"""A census step that cannot be traced, lowered or compiled surfaces at
once as :class:`StepCompileError` — never retried, failed over, or
carried forward as a degraded monitor window — while a device runtime
error from a step that does compile still takes the retry path."""

import jax
import numpy as np
import pytest

from repro.core import (
    CensusEngine, StepCompileError, TriadMonitor, default_mesh,
    monitor_stream, scale_free_digraph)
from repro.core import census as census_mod
from repro.core import engine as eng


class Boom(RuntimeError):
    """What the stand-in compiler raises."""


def _graph():
    return scale_free_digraph(n=150, avg_degree=4, exponent=2.2,
                              mutual_p=0.3, seed=5)


@pytest.fixture
def refuse_steps(monkeypatch):
    """Make every census step raise :class:`Boom` while it is traced;
    the jit caches are cleared so no step compiled earlier in this
    process is reused."""
    def refuse():
        def boom(*args, **kwargs):
            raise Boom("step refused at trace time")
        monkeypatch.setattr(census_mod, "classify_items", boom)
        jax.clear_caches()
    yield refuse
    jax.clear_caches()


def _assert_compile_error(excinfo):
    err = excinfo.value
    assert isinstance(err.__cause__, Boom)
    assert "'jnp'" in str(err) and "int32[" in str(err)


@pytest.mark.parametrize("emit", ["device", "host"])
def test_run_surfaces_compile_error(refuse_steps, emit):
    refuse_steps()
    engine = CensusEngine()
    with pytest.raises(StepCompileError) as excinfo:
        engine.run(_graph(), max_items=512, emit=emit)
    _assert_compile_error(excinfo)


@pytest.mark.parametrize("emit", ["device", "host"])
def test_partitioned_async_run_surfaces_compile_error(refuse_steps, emit):
    """The async path's retry/failover loop re-raises a compile failure
    at once instead of retiring every device into a FaultError."""
    refuse_steps()
    engine = CensusEngine(default_mesh(4), partition=True)
    with pytest.raises(StepCompileError) as excinfo:
        engine.run(_graph(), max_items=2048, emit=emit)
    _assert_compile_error(excinfo)
    st = engine.stats
    assert (st.retries, st.failovers, st.retired_devices) == (0, 0, [])


@pytest.mark.parametrize("partition", [False, True])
def test_session_surfaces_compile_error(refuse_steps, partition):
    refuse_steps()
    mesh = default_mesh(2) if partition else None
    session = CensusEngine(mesh, partition=partition).session(
        _graph(), max_items=512)
    with pytest.raises(StepCompileError) as excinfo:
        session.census()
    _assert_compile_error(excinfo)
    assert session.retries == 0


def test_monitor_does_not_degrade_on_compile_error(refuse_steps):
    """A window whose step cannot compile raises out of ``observe``; it
    is not recorded as a degraded window carrying the last census."""
    rng = np.random.default_rng(0)
    src, dst, n = monitor_stream(rng, 30, 200, 200, 1200)
    mon = TriadMonitor(n, window=1000, stride=100, orient="degree",
                       max_items=1024)
    assert mon.observe(src[:1000], dst[:1000]).shape == (1, 16)
    refuse_steps()
    with pytest.raises(StepCompileError) as excinfo:
        mon.observe(src[1000:], dst[1000:])
    _assert_compile_error(excinfo)
    assert mon.degraded == []
    assert mon._session.retries == 0
    assert len(mon.censuses) == 1


class _FailsOnceAtRuntime:
    """Wraps a jitted step: the first call raises as a device would at
    run time, while lowering and compiling still succeed."""

    def __init__(self, step):
        self.step = step
        self.calls = 0
        self.__name__ = step.__name__

    def __call__(self, *args):
        self.calls += 1
        if self.calls == 1:
            raise RuntimeError("device lost the dispatch")
        return self.step(*args)

    def lower(self, *args):
        return self.step.lower(*args)

    def _cache_size(self):
        return self.step._cache_size()


def test_runtime_error_is_still_retried(monkeypatch):
    g = _graph()
    mesh = default_mesh(2)
    want = CensusEngine().run(g, max_items=512)
    flaky = _FailsOnceAtRuntime(eng._desc_megastep)
    monkeypatch.setattr(eng, "_desc_megastep", flaky)
    engine = CensusEngine(mesh, partition=True, retry_backoff=0.0)
    got = engine.run(g, max_items=1024)
    assert np.array_equal(got, want)
    assert engine.stats.retries == 1
    assert engine.stats.failovers == 0
