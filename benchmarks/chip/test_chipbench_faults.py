"""The check that decides ``correct`` against a timed path broken
underneath: each fault a cell can have is planted in the program, the
rest of a run goes as on the chip (the look for a chip skipped), and
``correct`` must come out false."""

import numpy as np
import pytest

from chip.testkit import STREAM, measure


def _alter_answer(monkeypatch):
    """One count moved from one class to another where the census is
    assembled (it still sums to C(n, 3))."""
    import repro.core.engine as engine
    real = engine.assemble_counts

    def altered(*args, **kw):
        c = np.array(real(*args, **kw))
        c[0] -= 1
        c[3] += 1
        return c
    monkeypatch.setattr(engine, "assemble_counts", altered)


def _alter_delta(monkeypatch):
    import repro.core.engine as engine
    real = engine.combine

    def altered(*args, **kw):
        c = np.array(real(*args, **kw))
        c[1] -= 1
        c[5] += 1
        return c
    monkeypatch.setattr(engine, "combine", altered)


def _drop_half_the_chunks(monkeypatch):
    """Every other chunk's partials never reach the census."""
    import repro.core.engine as engine
    real = engine._land_desc_partials
    calls = [0]

    def half(fut, hist_acc, inter_acc, chunk_items):
        calls[0] += 1
        if calls[0] % 2:
            return real(fut, hist_acc, inter_acc, chunk_items)
        return real(fut, hist_acc.copy(), inter_acc.copy(), [])
    monkeypatch.setattr(engine, "_land_desc_partials", half)


def _unchanged_state(monkeypatch):
    """A slide that returns the session's census without moving it."""
    from repro.core.engine import EngineSession

    def stale(self, *args, **kw):
        return self._census.copy()
    monkeypatch.setattr(EngineSession, "update", stale)


def _no_exchange(monkeypatch):
    """Only the first chip's partials reach the host merge."""
    import jax.numpy as jnp

    import repro.core.engine as engine
    real = engine._launch

    def local(step, backend, *args):
        out = real(step, backend, *args)
        first = next(iter(args[0].devices())).id
        if first != 0:
            return tuple(jnp.zeros_like(x) for x in out)
        return out
    monkeypatch.setattr(engine, "_launch", local)


FAULTS = [
    ("patents-batch", _alter_answer),
    ("patents-batch", _drop_half_the_chunks),
    (STREAM, _unchanged_state),
    (STREAM, _alter_delta),
    ("patents-batch-x4", _alter_answer),
    ("patents-batch-x4", _no_exchange),
]


@pytest.mark.parametrize("cell,fault", FAULTS,
                         ids=[f"{c}-{f.__name__.strip('_')}"
                              for c, f in FAULTS])
def test_a_broken_timed_path_is_not_correct(tiny, monkeypatch, cell, fault):
    shrunk = tiny(cell)
    fault(monkeypatch)
    line, _ = measure(shrunk)
    assert line["correct"] is False
    assert line["checks"]["census_gap"]["value"] > 0


@pytest.mark.parametrize("cell", ["patents-batch", STREAM,
                                  "patents-batch-x4"])
def test_the_sound_path_is_correct(tiny, cell):
    line, _ = measure(tiny(cell))
    assert line["correct"] is True
    assert line["checks"]["census_gap"]["value"] == 0
