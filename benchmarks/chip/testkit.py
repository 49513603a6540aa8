"""Helpers of the harness's tests: cells cut to a size the CPU runs in
a moment, and one run of such a cell on the CPU's devices."""

import json
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
#: the four-chip batch cell: its configuration and reader are committed,
#: the cell is not in ``BENCHMARK.json`` until it is proven on the chip
X4 = "patents-batch-x4"
#: the monitor cell of the tests: the stream driver has no cell in
#: ``BENCHMARK.json`` until a monitored network with a public source is
#: found for it
STREAM = "monitor-tiny"
#: the stream driver's metrics: end-to-end, then per-layer
STREAM_METRICS = (
    [("monitor_edges_per_s", "edges/s"), ("slide_p95_ms", "ms"),
     ("setup_s", "s")],
    [("host_plan_ms.monitor", "ms"), ("slide_work_ratio.monitor", "ratio"),
     ("step_device_ms.monitor", "ms"), ("device_idle_pct.monitor", "%")])


def x4_cell() -> dict:
    """``patents-batch`` on four chips, with the sharded configuration
    ``configs/cit-patents-16-x4.json`` and the shard-balance reader."""
    from chip import drive, run
    cell = run.resolve(run.load_benchmark(), "patents-batch")
    cell.update(name=X4, chips=4, config=json.loads(
        (HERE / "configs" / "cit-patents-16-x4.json").read_text()))
    name = "shard_max_over_mean.batch"
    cell["per_layer"].append(({"name": name, "unit": "ratio"}, drive.load(
        HERE / "metrics" / f"{name}.py").read))
    return cell


def stream_cell() -> dict:
    """A monitor cell at a size the CPU runs in a moment: a window of
    1,200 edges over 420 hosts sliding by 60, on a backbone of 900 arcs
    with every second slot an ephemeral flow."""
    from chip import drive
    config = {"name": STREAM, "n_servers": 120, "n_peers": 300,
              "window": 1200, "stride": 60, "history": 3, "threshold": 3.0,
              "engine": {"backend": "jnp", "emit": "device",
                         "orient": "degree", "incremental": True,
                         "index": True, "max_items_per_chip": 1 << 11}}
    traffic = {"driver": "stream", "backbone_arcs": 900,
               "backbone_every": 2, "max_slides_per_s": 20000,
               "structure_seed": 0}

    def readers(metrics):
        return [({"name": name, "unit": unit},
                 drive.load(HERE / "metrics" / f"{name}.py").read)
                for name, unit in metrics]
    return {"name": STREAM, "chips": 1, "config": config,
            "traffic": traffic,
            "driver": drive.load(HERE / "drivers" / "stream.py"),
            "end_to_end": readers(STREAM_METRICS[0]),
            "per_layer": readers(STREAM_METRICS[1])}


def shrink(cell: dict) -> dict:
    """A resolved batch cell cut to a size the CPU runs in well under a
    second: the same driver, engine settings and checks."""
    config = cell["config"]
    config["n"] = 2000
    config["arcs"] = 8752
    config["engine"]["max_items_per_chip"] = 1 << 11
    return cell


def measure(cell: dict, seconds: float = 0.3, traced: bool = False,
            seed: int = 2 ** 33 + 1, **kw):
    """One run of a shrunk cell on the CPU's first devices."""
    import jax

    from chip import run
    devices = jax.devices()[:cell["chips"]]
    return run.measure(cell, seed, seconds, traced, devices,
                       time.perf_counter(), **kw)
