#!/usr/bin/env python3
"""Where a traced census spends its time, by the program's own names: the
device seconds of each named stage of the census step, the self time of
each of the engine's host spans, and the idle gaps labelled by the
innermost span, harness or program, that covers them.

    python3 benchmarks/chip/scopes.py --workload patents-batch \\
        --seed 7 --seconds 20 --out scopes.json

runs one traced window of a cell as ``run.py --trace 1`` does and
prints one JSON object (also written to ``--out``).  As a module,
:func:`reduce` turns a recorded trace into the same numbers.  The run
compiles its programs in the process, with the persistent compilation
cache off: a TPU program loaded from that cache carries no ``tf_op``
in the trace, so every operation would read ``unscoped``.

* A device operation belongs to the first stage name (:data:`STAGES`,
  the ``jax.named_scope`` names of ``repro.core.census``) among the
  components of its ``tf_op`` path, such as
  ``jit(_desc_step_impl)/expand/closed_call/gather``; components like
  ``jit(...)`` or ``shard_map`` are skipped.  Device time that matches
  no stage is ``unscoped``, so the stages and ``unscoped`` add up to the
  busy time of ``trace.reduce``.
* A program span's self time is its duration inside the traced window
  less what the program spans nested in it on the same thread cover,
  summed over threads.
* ``tf_op`` is a stat of the operations' event metadata, which
  ``jax.profiler.ProfileData`` does not expose.  The trace is read
  through the XPlane protobuf module that ships inside the installed
  ``tensorflow`` package, loaded by its file path with
  ``google.protobuf`` alone: TensorFlow itself is never imported.
"""

from __future__ import annotations

import argparse
import functools
import gzip
import heapq
import importlib.util
import json
import os
import shutil
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
#: where this script's traced window writes its profile, deleted once read
TRACE_DIR = HERE.parents[1] / ".bench_trace_scopes"
#: the engine's host spans (``repro.core.spans``)
PROGRAM_SPANS = ("census.plan", "census.partition", "census.upload",
                 "chunk.emit", "chunk.dispatch", "chunk.land",
                 "pipeline.stall", "census.assemble")
#: the named stages of the census step, in the order they are matched
STAGES = ("expand", "classify", "keep", "reduce")
UNSCOPED = "unscoped"


@functools.cache
def xplane_pb2():
    """The XPlane protobuf module of the installed ``tensorflow``
    package, loaded from its file without importing TensorFlow."""
    spec = importlib.util.find_spec("tensorflow")
    if spec is None or spec.origin is None:
        raise ModuleNotFoundError(
            "no tensorflow package: its xplane_pb2 reads the trace's "
            "operation metadata")
    path = (Path(spec.origin).parent / "tsl" / "profiler" / "protobuf"
            / "xplane_pb2.py")
    mod_spec = importlib.util.spec_from_file_location("chip_xplane_pb2",
                                                      path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def load(source) -> object:
    """An ``XSpace`` from the serialized bytes of a trace, or from a
    ``.xplane.pb`` (optionally gzipped) file."""
    if isinstance(source, (str, os.PathLike)):
        source = Path(source).read_bytes()
    if source[:2] == b"\x1f\x8b":
        source = gzip.decompress(source)
    space = xplane_pb2().XSpace()
    space.ParseFromString(source)
    return space


def stage_of(tf_op: str) -> str:
    """The first stage among the components of an operation's path."""
    for part in tf_op.split("/"):
        if part.rstrip(":") in STAGES:
            return part.rstrip(":")
    return UNSCOPED


def _stat_str(plane, names: dict, stats, key: str) -> str:
    for st in stats:
        if names.get(st.metadata_id) == key:
            if st.str_value:
                return st.str_value
            if st.ref_value:
                return plane.stat_metadata[st.ref_value].name
    return ""


def _ids(names: dict, stats) -> dict:
    out = {}
    for st in stats:
        kind = st.WhichOneof("value")
        if kind in ("int64_value", "uint64_value"):
            out[names.get(st.metadata_id, "?")] = int(getattr(st, kind))
    return out


def events(space) -> tuple[dict, list, tuple | None]:
    """Per-device operations ``{device_id: [(stage, start_ns, end_ns)]}``,
    host spans ``[(name, thread, start_ns, end_ns, ids)]`` of the program
    and the harness, and the window span — in the nanoseconds
    ``ProfileData`` gives, so that busy time matches ``trace.reduce``."""
    from chip import trace
    ops: dict[int, list] = {}
    spans, window = [], None
    wanted = set(PROGRAM_SPANS) | set(trace.SPANS)
    for plane in space.planes:
        dev = trace._device_id(plane.name)
        host = plane.name.startswith("/host:")
        names = {k: v.name for k, v in plane.stat_metadata.items()}
        for line in plane.lines:
            if dev is not None and line.name == trace.OPS_LINE:
                stage = {}
                for e in line.events:
                    if e.metadata_id not in stage:
                        md = plane.event_metadata[e.metadata_id]
                        stage[e.metadata_id] = stage_of(
                            _stat_str(plane, names, md.stats, "tf_op"))
                    s = line.timestamp_ns + e.offset_ps // 1000
                    ops.setdefault(dev, []).append(
                        (stage[e.metadata_id], s, s + e.duration_ps // 1000))
            elif host:
                for e in line.events:
                    name = plane.event_metadata[e.metadata_id].name
                    s = line.timestamp_ns + e.offset_ps // 1000
                    end = s + e.duration_ps // 1000
                    if name == trace.WINDOW_SPAN and window is None:
                        window = (s, end)
                    elif name in wanted:
                        spans.append((name, (plane.id, line.id), s, end,
                                      _ids(names, e.stats)))
    return ops, spans, window


def scope_seconds(ops, lo: float, hi: float) -> dict:
    """Device seconds per stage inside ``[lo, hi]``.  Time that several
    operations cover counts once, for the innermost: the one that
    started last (a ``while`` or ``conditional`` of the megastep spans
    the fusions it runs, which carry the stage names)."""
    out = dict.fromkeys(STAGES + (UNSCOPED,), 0.0)
    ops = sorted((s, e, stage) for stage, s, e in ops
                 if min(e, hi) > max(s, lo))
    bounds = sorted({min(max(t, lo), hi) for s, e, _ in ops
                     for t in (s, e)})
    active, i = [], 0                   # heap of (-start, end, stage)
    for a, b in zip(bounds, bounds[1:]):
        while i < len(ops) and ops[i][0] <= a:
            heapq.heappush(active, (-ops[i][0], ops[i][1], ops[i][2]))
            i += 1
        while active and active[0][1] <= a:
            heapq.heappop(active)
        if active:
            out[active[0][2]] += (b - a) / 1e9
    return out


def self_seconds(spans, lo: float, hi: float) -> dict:
    """Self time of each program span inside ``[lo, hi]``: its clipped
    duration less what the program spans nested in it on the same
    thread cover, summed over threads."""
    out = dict.fromkeys(PROGRAM_SPANS, 0.0)
    by_thread: dict = {}
    for name, thread, s, e, _ids in spans:
        if name in out and min(e, hi) > max(s, lo):
            by_thread.setdefault(thread, []).append(
                (max(s, lo), min(e, hi), name))
    for items in by_thread.values():
        stack = []                      # [end, name, covered by children]
        for s, e, name in sorted(items, key=lambda x: (x[0], -x[1])):
            while stack and stack[-1][0] <= s:
                _close(stack, out)
            if stack:
                stack[-1][2] += e - s
            stack.append([e, name, 0.0, s])
        while stack:
            _close(stack, out)
    return {k: v / 1e9 for k, v in out.items()}


def _close(stack, out) -> None:
    end, name, covered, start = stack.pop()
    out[name] += (end - start) - covered


def label(t: float, spans) -> str:
    """Innermost span, harness or program, that covers ``t``."""
    best = None
    for name, _thread, s, e, _ids in spans:
        if s <= t <= e and (best is None or e - s < best[1]):
            best = (name, e - s)
    return best[0] if best else "none"


def reduce(space, device_ids) -> dict:
    """``scope_s`` (device seconds per stage, averaged over the chips),
    ``spans_s`` (self time of each program span, summed over threads),
    the longest program spans with their ids, and the idle gaps
    labelled by the innermost span that covers them."""
    from chip import trace
    ops, spans, window = events(space)
    if window is None:
        raise ValueError(f"no {trace.WINDOW_SPAN!r} span in the trace")
    lo, hi = window
    ndev = max(len(device_ids), 1)
    scope = dict.fromkeys(STAGES + (UNSCOPED,), 0.0)
    all_gaps = []
    for d in device_ids:
        evs = ops.get(d, ())
        for k, v in scope_seconds(evs, lo, hi).items():
            scope[k] += v / ndev
        busy = trace.union(trace.clip([(s, e) for _, s, e in evs], lo, hi))
        tag = f"tpu{d}:" if len(device_ids) > 1 else ""
        all_gaps.extend([tag + label((s + e) / 2, spans), (e - s) / 1e9]
                        for s, e in trace.gaps(busy, lo, hi))
    longest = sorted((sp for sp in spans if sp[0] in PROGRAM_SPANS),
                     key=lambda sp: sp[2] - sp[3])[:trace.TOP]
    return {
        "window_s": (hi - lo) / 1e9,
        "scope_s": scope,
        "spans_s": self_seconds(spans, lo, hi),
        "longest_spans": [[n, (e - s) / 1e9, ids]
                          for n, _t, s, e, ids in longest],
        "idle_gaps": sorted(all_gaps, key=lambda g: -g[1])[:trace.TOP],
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", help="also write the result to this file")
    args = ap.parse_args(argv)
    t_start = time.perf_counter()
    # the harness as the package ``chip``, never its modules by bare name
    sys.path[:] = [p for p in sys.path
                   if os.path.abspath(p or ".") != str(HERE)]
    sys.path[:0] = [str(HERE.parents[1] / "src"), str(HERE.parent)]
    import glob

    import jax

    from chip import drive, run, trace
    cell = run.resolve(run.load_benchmark(), args.workload)
    jax.config.update("jax_enable_compilation_cache", False)
    devices = run.chips(cell["chips"])
    clock = drive.Clock()
    clock.install()
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2

    def profile():
        return jax.profiler.trace(str(TRACE_DIR), profiler_options=opts)
    record = cell["driver"].run(cell["config"], cell["traffic"], args.seed,
                                args.seconds, devices, clock,
                                drive.Spans(True), t_start, profile=profile)
    found = sorted(glob.glob(str(TRACE_DIR / "**" / "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    raw = Path(found[-1]).read_bytes()
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    ids = [d.id for d in devices]
    from jax.profiler import ProfileData
    busy = trace.reduce(ProfileData.from_serialized_xspace(raw), ids)
    out = reduce(load(raw), ids)
    done = record["attempted"] - record["failed"]
    stats = [c["stats"] for c in record["censuses"]]
    out.update(
        workload=args.workload, seed=args.seed,
        device=devices[0].device_kind, chips=len(devices),
        correct=all(c["value"] <= c["limit"]
                    for c in record["checks"].values()),
        censuses=done, census_s=record["window_s"] / max(done, 1),
        busy_s=busy["busy_s"], idle_pct=busy["idle_pct"],
        device_ops=busy["device_ops"],
        host_counters_s={k: sum(st.get(k, 0.0) for st in stats)
                         / max(len(stats), 1)
                         for k in ("host_pair_seconds",
                                   "host_emit_seconds",
                                   "host_partition_seconds",
                                   "host_land_seconds")},
        landed_s=[c["landed_s"] for c in record["censuses"]])
    text = json.dumps(out, default=float)
    if args.out:
        Path(args.out).write_text(text)
    print(text, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
