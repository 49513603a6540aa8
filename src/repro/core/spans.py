"""Host spans of the census engine, on the profiler's clock.

``with span("chunk.land", stats, "host_land_seconds", census=3, chunk=1):``
does two things:

* it opens a ``jax.profiler.TraceAnnotation`` with that name and those
  ids, so a traced run shows the engine's host phases on the same clock
  as the device's operations (nothing is recorded when no trace is
  being taken);
* when ``bucket`` is given, it adds the span's wall time to that counter
  of ``stats`` (an :class:`~repro.core.engine.EngineStats` field, or a
  session's running accumulator), whether or not a trace is taken.

The span's own duration is left in ``seconds`` for a phase whose counter
is set once its stats exist.  Spans of one census share its ``census``
id; per-chunk spans add ``chunk`` (and ``shard`` where there are several).
"""

from __future__ import annotations

import threading
import time

import jax

#: buckets are also summed from the partitioned run's producer threads
_BUCKET_LOCK = threading.Lock()


class span:
    """Context manager: a named host span, optionally timed into
    ``stats.<bucket>`` (see the module docstring)."""

    __slots__ = ("_annotation", "_stats", "_bucket", "_t0", "seconds")

    def __init__(self, name: str, stats=None, bucket: str | None = None,
                 **ids):
        self._annotation = jax.profiler.TraceAnnotation(name, **ids)
        self._stats = stats
        self._bucket = bucket
        self.seconds = 0.0

    def __enter__(self) -> "span":
        self._annotation.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.seconds = time.perf_counter() - self._t0
        self._annotation.__exit__(exc_type, exc, tb)
        if self._bucket is not None:
            with _BUCKET_LOCK:
                setattr(self._stats, self._bucket,
                        getattr(self._stats, self._bucket) + self.seconds)
