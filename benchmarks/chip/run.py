#!/usr/bin/env python3
"""Run one benchmark cell once on the chips of this machine.

    python3 benchmarks/chip/run.py --workload patents-batch --seed 7 \\
        --seconds 30 --trace 0

The cell, its configuration, traffic mix and metrics are found by name
from ``BENCHMARK.json`` at the root of the checkout:

* ``configs/<config>.json``: the deployment (graph or monitored network,
  engine settings);
* ``traffic/<mix>.json``: the traffic mix, whose ``driver`` key names
  the driver of the window, ``drivers/<driver>.py`` (see ``drive.py``);
* ``metrics/<metric>.py``: one reader per metric, ``read(record)``,
  which returns the number or ``None`` where it finds nothing to read.

The run generates its inputs from ``--seed``, warms up the shapes the
cell uses, measures for ``--seconds``, then compares every census it
produced with the plain reference.  With ``--trace 1`` the window runs
under the profiler and the per-layer metrics are printed, otherwise the
end-to-end ones.  The last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device``
(``breakdown`` when traced) and, last, ``checks``: each number compared
with the reference beside its limit.  The same checks are the last
lines of standard error.

It refuses to run anywhere but on a TPU with as many chips as the cell
asks for: a non-zero exit and no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
#: where a traced run's profile is written, and deleted once read
TRACE_DIR = ROOT / ".bench_trace"


class NoChip(RuntimeError):
    """This machine cannot run the cell."""


def _paths() -> None:
    """Import the harness as the package ``chip`` and the program from
    ``src`` (never this directory's own modules under bare names)."""
    here = str(HERE)
    sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != here]
    for p in (str(ROOT / "src"), str(HERE.parent)):
        if p not in sys.path:
            sys.path.insert(0, p)


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def resolve(bench: dict, workload: str, root: Path = ROOT,
            here: Path = HERE) -> dict:
    """Everything one cell needs, by name: its configuration, traffic
    mix, the driver the mix names, and the readers of the metrics it
    reports."""
    from chip import drive
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; one of {sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / configs[cell["config"]]["file"])
                        .read_text())
    traffic = json.loads((here / "traffic" / f"{cell['traffic']}.json")
                         .read_text())

    # an end-to-end metric without ``workloads`` is every cell's; a
    # per-layer one is every cell's that reports the metric it moves
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    moved = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if (workload in m["workloads"] if "workloads" in m
                 else m["moves"] in moved)]

    def reader(m):
        return m, drive.load(here / "metrics" / f"{m['name']}.py").read
    return {
        "name": workload, "chips": int(cell["chips"]),
        "config": config, "traffic": traffic,
        "driver": drive.load(here / "drivers"
                             / f"{traffic['driver']}.py"),
        "end_to_end": [reader(m) for m in e2e],
        "per_layer": [reader(m) for m in layer],
    }


def chips(count: int):
    """The first ``count`` TPU chips, or :class:`NoChip`."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found {devices[0].platform!r}")
    if len(devices) < count:
        raise NoChip(f"the cell needs {count} chips; found {len(devices)}")
    return devices[:count]


def enable_cache() -> str:
    """The program's persistent compilation cache
    (``<checkout>/.jax_cache``, or where ``JAX_COMPILATION_CACHE_DIR``
    says), keeping every program, however quick to compile or small."""
    import jax

    from repro.compile_cache import enable_compile_cache
    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def measure(cell: dict, seed: int, seconds: float, traced: bool,
            devices, t_start: float, **driver_kw) -> dict:
    """Run the cell's window and return its result line (a dict) and
    the record the metrics were read from."""
    from chip import drive, trace
    clock = drive.Clock()
    clock.install()
    spans = drive.Spans(traced)
    profile = None
    if traced:
        import jax
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2

        def profile():
            return jax.profiler.trace(str(TRACE_DIR), profiler_options=opts)
    record = cell["driver"].run(cell["config"], cell["traffic"], seed, seconds, devices,
                 clock, spans, t_start, profile=profile, **driver_kw)
    kind = devices[0].device_kind
    record["peaks"] = trace.peaks(kind)
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices),
              "memory_peak_bytes": record["memory_peak_bytes"]}
    if traced:
        record["trace"] = trace.reduce(trace.load(TRACE_DIR),
                                       [d.id for d in devices])
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        device["busy_s"] = record["trace"]["busy_s"]
        device["window_s"] = record["trace"]["window_s"]
    metrics = {}
    for spec, read in cell["per_layer" if traced else "end_to_end"]:
        value = read(record)
        if value is not None:
            metrics[spec["name"]] = {"value": value, "unit": spec["unit"]}
    checks = record["checks"]
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    line = {"correct": correct, "attempted": record["attempted"],
            "failed": record["failed"], "metrics": metrics,
            "device": device}
    if traced:
        line["breakdown"] = {"device_ops": record["trace"]["device_ops"],
                             "idle_gaps": record["trace"]["idle_gaps"]}
    line["checks"] = checks
    return line, record


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--record", help="also write the full run record "
                    "(every census or slide) to this JSON file")
    args = ap.parse_args(argv)
    _paths()
    cell = resolve(load_benchmark(), args.workload)
    enable_cache()
    try:
        devices = chips(cell["chips"])
    except (NoChip, RuntimeError) as exc:
        print(f"run.py: {exc}", file=sys.stderr)
        return 2
    print(f"devices: {len(devices)} x {devices[0].device_kind}",
          file=sys.stderr, flush=True)
    line, record = measure(cell, args.seed, args.seconds,
                           bool(args.trace), devices, T_START)
    print(f"set-up compiles or cache loads: {record['setup_compiles']}; "
          f"in the window: {record['window_compiles']}; "
          f"censuses checked: {record['checked']}; reference "
          f"{record['reference_s']:.3f} s", file=sys.stderr, flush=True)
    if record.get("errors"):
        print(f"errors: {record['errors']}", file=sys.stderr)
    if args.record:
        Path(args.record).write_text(json.dumps(record, default=float))
    for name, c in line["checks"].items():
        print(f"check {name} = {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
