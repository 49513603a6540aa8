"""What every driver of a timed window shares, and the loader that finds
drivers and metric readers by name.

A traffic mix is a data file (``traffic/<mix>.json``) whose ``driver``
key names a driver file, ``drivers/<driver>.py``; the rest of the mix is
that driver's parameters.  A driver exposes

* ``run(config, traffic, seed, seconds, devices, clock, spans, t_start,
  profile=None, **kw)``: set up, measure for ``seconds``, compare every
  census it produced with the plain reference, and return the run's
  record (a dict) that the metric readers (``metrics/<name>.py``) read;
* ``control_inputs(config, traffic, seed, count)``: the arcs ``(src,
  dst, n)`` of the first ``count`` censuses a run compares, for the
  control (``control.py``).

A new shape of traffic is a new mix file, and where no driver fits, a
new driver file: no existing file changes.
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import sys
from pathlib import Path


def load(path: Path):
    """The module in the file ``path``, loaded under a name of its own
    (a driver or a metric reader)."""
    name = "chip_" + path.parent.name + "_" + path.stem.replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


class Clock:
    """Counts the programs that are compiled or loaded from the
    persistent cache, so that a compile inside the window shows."""

    def __init__(self):
        self.compiles = 0
        self.loads = 0

    def install(self) -> None:
        import jax

        def on_duration(event, duration, **_kw):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1

        def on_event(event, **_kw):
            if event == "/jax/compilation_cache/cache_hits":
                self.loads += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def count(self) -> int:
        return self.compiles + self.loads


class Spans:
    """Host spans around the calls into the program, written into the
    profiler's trace when the run is traced, and nothing otherwise."""

    def __init__(self, traced: bool):
        self.traced = traced
        self._open = None

    def __call__(self, name: str):
        if not self.traced:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def mark(self, name: str) -> None:
        """Close the previous ``name`` span and open the next one; a
        progress callback calls this once per landed chunk."""
        if not self.traced:
            return
        self.close_mark()
        self._open = self(name)
        self._open.__enter__()

    def close_mark(self) -> None:
        if self._open is not None:
            self._open.__exit__(None, None, None)
            self._open = None


def stats_dict(stats) -> dict:
    """An ``EngineStats`` as plain numbers, with its derived fields."""
    if stats is None:
        return {}
    out = {k: v for k, v in dataclasses.asdict(stats).items()
           if isinstance(v, (int, float, str, bool, type(None)))}
    out["plan_host_seconds"] = stats.plan_host_seconds
    out["shard_max_over_mean"] = stats.shard_max_over_mean
    return out


class WarmedUp(Exception):
    """Raised by :func:`stop_after_first_chunk` to end a warm-up census."""


def stop_after_first_chunk(*_args):
    """A progress callback that ends a census after its first chunk."""
    raise WarmedUp


def drain(devices) -> None:
    """Wait until every device has finished what was sent to it."""
    import jax
    import jax.numpy as jnp
    for a in jax.live_arrays():
        a.block_until_ready()
    for d in devices:
        (jax.device_put(jnp.int32(1), d) + 1).block_until_ready()


def peak_bytes(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)
