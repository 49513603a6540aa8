"""Reduction of a profiler trace to device busy time, idle share and a
breakdown of where the time went.

* Busy time of a device is the union of the intervals in which an XLA
  operation ran on it, clipped to the traced window (the harness's own
  ``window`` span).  Idle share is ``1 - busy / window``.
* An idle gap is a stretch of the window with no operation on the
  device.  Each gap is labelled by what the host was doing: the
  innermost harness span (``census``, ``chunk``, ``slide``, ``alarm``)
  that covers the gap's midpoint, or ``none``.
* The peaks of the chip come from ``peaks.json``, keyed by
  ``device_kind``; a kind that is not in the table is an error.
"""

from __future__ import annotations

import glob
import json
import os
import re
from pathlib import Path

#: host spans the harness writes around the calls into the program
SPANS = ("census", "chunk", "slide", "alarm")
WINDOW_SPAN = "window"
#: the profiler line that holds one event per executed XLA operation
OPS_LINE = "XLA Ops"
TOP = 10


class UnknownDevice(KeyError):
    """The chip is not in the table of peaks."""


def peaks(device_kind: str, path: str | os.PathLike | None = None) -> dict:
    """Published peaks of ``device_kind`` from ``peaks.json``."""
    path = Path(path) if path else Path(__file__).with_name("peaks.json")
    table = json.loads(path.read_text())
    if device_kind not in table:
        raise UnknownDevice(
            f"no peaks for device kind {device_kind!r}; known: "
            f"{sorted(table)}")
    return table[device_kind]


def union(intervals) -> list[tuple[float, float]]:
    """Sorted, disjoint union of ``(start, end)`` intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def gaps(busy, lo: float, hi: float) -> list[tuple[float, float]]:
    """Stretches of ``[lo, hi]`` that the disjoint ``busy`` leaves."""
    out, at = [], lo
    for s, e in clip(busy, lo, hi):
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return out


def label(t: float, spans) -> str:
    """Innermost host span ``(name, start, end)`` that covers ``t``."""
    best = None
    for name, s, e in spans:
        if s <= t <= e and (best is None or e - s < best[2] - best[1]):
            best = (name, s, e)
    return best[0] if best else "none"


def op_name(text: str) -> str:
    """An XLA operation's event name without its layouts and backend
    attributes: ``%fusion.26 = s32[4194304] fusion(s32[968550] ...)``."""
    text = re.sub(r"\{[^{}]*\}", "", text)
    return text.split(", kind=")[0].split(", calls=")[0]


def _device_id(plane_name: str) -> int | None:
    head, _, tail = plane_name.rpartition(":")
    if not head.startswith("/device:") or not tail.isdigit():
        return None
    return int(tail)


def events(profile) -> tuple[dict, list, tuple | None]:
    """Pull from a ``jax.profiler.ProfileData`` the per-device operation
    events ``{device_id: [(name, start_ns, end_ns)]}``, the harness's
    host spans ``[(name, start_ns, end_ns)]`` and the window span."""
    ops: dict[int, list] = {}
    spans, window = [], None
    for plane in profile.planes:
        dev = _device_id(plane.name)
        for line in plane.lines:
            if dev is not None and line.name == OPS_LINE:
                ops.setdefault(dev, []).extend(
                    (op_name(e.name), e.start_ns,
                     e.start_ns + e.duration_ns)
                    for e in line.events)
            elif plane.name.startswith("/host:"):
                for e in line.events:
                    if e.name == WINDOW_SPAN and window is None:
                        window = (e.start_ns, e.start_ns + e.duration_ns)
                    elif e.name in SPANS:
                        spans.append((e.name, e.start_ns,
                                      e.start_ns + e.duration_ns))
    return ops, spans, window


def reduce(profile, device_ids) -> dict:
    """Busy time, idle share and breakdown of the traced window, over
    the devices ``device_ids`` (the chips the run used)."""
    ops, spans, window = events(profile)
    if window is None:
        raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
    lo, hi = window
    busy_ns, op_ns, all_gaps = [], {}, []
    for d in device_ids:
        evs = [(n, s, e) for n, s, e in ops.get(d, ())
               if e > lo and s < hi]
        busy = union(clip([(s, e) for _, s, e in evs], lo, hi))
        busy_ns.append(sum(e - s for s, e in busy))
        for name, s, e in evs:
            op_ns[name] = op_ns.get(name, 0.0) + min(e, hi) - max(s, lo)
        tag = f"tpu{d}:" if len(device_ids) > 1 else ""
        all_gaps.extend([tag + label((s + e) / 2, spans), (e - s) / 1e9]
                        for s, e in gaps(busy, lo, hi))
    window_s = (hi - lo) / 1e9
    busy_s = sum(busy_ns) / len(busy_ns) / 1e9 if busy_ns else 0.0
    ndev = max(len(device_ids), 1)
    top_ops = sorted(op_ns.items(), key=lambda kv: -kv[1])[:TOP]
    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "busy_s_per_device": [b / 1e9 for b in busy_ns],
        "idle_pct": (100.0 * (1.0 - busy_s / window_s)
                     if window_s and busy_s > 0 else None),
        "op_events": sum(len(ops.get(d, ())) for d in device_ids),
        "device_ops": [[n, t / 1e9 / ndev] for n, t in top_ops],
        "idle_gaps": sorted(all_gaps, key=lambda g: -g[1])[:TOP],
    }


def load(trace_dir: str | os.PathLike):
    """The ``ProfileData`` of the newest ``.xplane.pb`` under a
    ``jax.profiler.trace`` directory."""
    from jax.profiler import ProfileData
    found = sorted(glob.glob(os.path.join(str(trace_dir), "**",
                                          "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return ProfileData.from_file(found[-1])
