"""The harness at a tiny size on the CPU: cells resolve to their files,
the result line has the contracted keys, failures count, new cells come
from new files alone, and the CLI refuses any machine without a TPU."""

import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest
from chip.testkit import STREAM, STREAM_METRICS, X4, measure

from chip import run

BENCH = run.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_benchmark_json_keeps_to_its_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["benchmarks/chip"]
    assert 1 <= BENCH["run_seconds"] <= 51
    names = [c["name"] for c in BENCH["configs"]] + CELLS + [
        m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert all(NAME.match(n) for n in names)
    assert len(names) == len(set(names))
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    assert all(0.01 <= m["bound"] <= 0.25 for m in e2e.values())
    assert all(m["moves"] in e2e for m in BENCH["per_layer"])
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(CELLS) // 2)


@pytest.mark.parametrize("name", CELLS)
def test_every_cell_resolves_to_its_files(name):
    cell = run.resolve(BENCH, name)
    assert callable(cell["driver"].run)
    assert callable(cell["driver"].control_inputs)
    e2e = [m["name"] for m, _ in cell["end_to_end"]]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell["per_layer"]
    for _, read in cell["end_to_end"] + cell["per_layer"]:
        assert callable(read)
    spec = next(c for c in BENCH["configs"]
                if c["name"] == next(w for w in BENCH["workloads"]
                                     if w["name"] == name)["config"])
    for key in spec["reduced"]:
        assert key in cell["config"]


@pytest.mark.parametrize("name", CELLS + [X4, STREAM])
@pytest.mark.parametrize("traced", [False, True])
def test_result_line_has_the_contracted_keys(tiny, name, traced):
    cell = tiny(name)
    line, record = measure(cell, traced=traced)
    keys = ["correct", "attempted", "failed", "metrics", "device"]
    keys += ["breakdown"] if traced else []
    assert list(line) == keys + ["checks"]
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    assert record["window_compiles"] == 0
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert line["device"]["count"] == cell["chips"]
    if traced:
        assert {"busy_s", "window_s"} <= set(line["device"])
        assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    else:
        assert set(line["metrics"]) == {m["name"]
                                        for m, _ in cell["end_to_end"]}
    for spec, _ in cell["end_to_end" if not traced else "per_layer"]:
        if spec["name"] in line["metrics"]:
            assert line["metrics"][spec["name"]]["unit"] == spec["unit"]
    assert json.loads(json.dumps(line)) == line


def test_degraded_monitor_window_counts_as_failed(tiny):
    from repro.core import Fault, FaultPlan
    cell = tiny(STREAM)
    # three dispatches in a row, well inside the window, fail: past the
    # retry budget, the monitor carries a census forward as degraded
    plan = FaultPlan(faults=[Fault("dispatch", "error", device=0,
                                   occurrence=30 + i) for i in range(3)])
    line, record = measure(cell, monitor_kw={
        "faults": plan, "max_retries": 2, "retry_backoff": 0.0})
    assert line["failed"] >= 1
    assert line["correct"] is False
    assert line["checks"]["failed"]["value"] == line["failed"]


def test_a_census_that_raises_counts_as_failed(tiny):
    from repro.core import CensusEngine

    class Flaky(CensusEngine):
        calls = 0

        def run(self, g, **kw):
            Flaky.calls += 1
            if Flaky.calls == 3:
                raise RuntimeError("device lost")
            return super().run(g, **kw)

    cell = tiny("patents-batch")
    line, _ = measure(cell, engine=Flaky(backend="jnp"))
    assert line["failed"] == 1 and line["correct"] is False


def _copy(tmp_path: Path) -> Path:
    root = Path(run.ROOT)
    shutil.copytree(root / "benchmarks" / "chip",
                    tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(root / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    return tmp_path


def _add(root: Path, files: dict, entries: dict) -> dict:
    """Add ``files`` (relative path: text) under the copy's harness and
    ``entries`` (key: list) to its ``BENCHMARK.json``; check that no
    file there changed, and return the new ``BENCHMARK.json``."""
    here = root / "benchmarks" / "chip"
    before = {p: p.read_bytes() for p in here.rglob("*") if p.is_file()}
    for rel, text in files.items():
        assert not (here / rel).exists()
        (here / rel).write_text(text)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    for key, items in entries.items():
        bench[key] += items
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    after = {p: p.read_bytes() for p in before}
    assert after == before
    return bench


def _batch_files():
    config = json.loads((Path(run.HERE) / "configs" / "cit-patents-16.json")
                        .read_text())
    config.update(name="patents-small", n=1500, arcs=6564)
    config["engine"]["max_items_per_chip"] = 1 << 11
    files = {
        "configs/patents-small.json": json.dumps(config),
        "traffic/batch-again.json": json.dumps({"driver": "batch"}),
        "metrics/censuses_per_min.py":
            "def read(record):\n"
            "    return 60 * record['attempted'] / record['window_s']\n"}
    entries = {
        "configs": [{"name": "patents-small",
                     "source": "https://snap.stanford.edu/data/cit-Patents.html",
                     "file": "benchmarks/chip/configs/patents-small.json",
                     "reduced": ["n", "arcs"], "why": "a small graph"}],
        "workloads": [{"name": "patents-small-batch",
                       "config": "patents-small", "traffic": "batch-again",
                       "chips": 1, "why": "a small graph"}],
        "per_layer": [{"name": "censuses_per_min", "unit": "1/min",
                       "better": "higher", "source": "host_clock",
                       "layer": "entry", "moves": "census_s",
                       "workloads": ["patents-small-batch"]}]}
    return "patents-small-batch", "censuses_per_min", files, entries


def _stream_files():
    """A monitor cell: its configuration, mix and metrics are data and
    readers the harness already has; only their entries are new."""
    from chip.testkit import stream_cell
    tiny = stream_cell()
    config = dict(tiny["config"], name="monitor-small")
    files = {"configs/monitor-small.json": json.dumps(config),
             "traffic/sparse.json": json.dumps(
                 dict(tiny["traffic"], backbone_every=None, eph_every=5))}
    cell = "monitor-small-sparse"
    e2e, layer = STREAM_METRICS
    entries = {
        "configs": [{"name": "monitor-small",
                     "source": "https://arxiv.org/abs/1209.6308",
                     "file": "benchmarks/chip/configs/monitor-small.json",
                     "reduced": [], "why": "a small monitored network"}],
        "workloads": [{"name": cell, "config": "monitor-small",
                       "traffic": "sparse", "chips": 1,
                       "why": "a sparse stream"}],
        "end_to_end": [{"name": name, "unit": unit, "better": "lower",
                        "bound": 0.05, "source": "host_clock",
                        "workloads": [cell]}
                       for name, unit in e2e if name != "setup_s"],
        "per_layer": [{"name": name, "unit": unit, "better": "lower",
                       "source": "program_counter", "layer": "monitor",
                       "moves": "monitor_edges_per_s",
                       "workloads": [cell]} for name, unit in layer]}
    return cell, "slide_work_ratio.monitor", files, entries


@pytest.mark.parametrize("kind", ["batch", "stream"])
def test_new_config_mix_and_metric_come_from_new_files(tiny, tmp_path,
                                                       kind):
    """A later cell adds files and entries and edits no file."""
    root = _copy(tmp_path)
    here = root / "benchmarks" / "chip"
    cell_name, metric, files, entries = (
        _batch_files() if kind == "batch" else _stream_files())
    bench = _add(root, files, entries)
    cell = run.resolve(bench, cell_name, root=root, here=here)
    assert metric in [m["name"] for m, _ in cell["per_layer"]]
    if kind == "stream":
        cell["driver"].REFERENCE_WORKERS = 1
    line, _ = measure(cell, traced=True)
    assert line["correct"] is True
    assert line["metrics"][metric]["value"] > 0


#: a whole new driver: one census per call of the engine on a graph the
#: mix describes, closed loop, compared with the reference
NEW_DRIVER = """
import time

import numpy as np

from chip import drive, reference


def control_inputs(config, traffic, seed, count):
    rng = np.random.default_rng(seed)
    n, m = traffic["n"], traffic["m"]
    return [(rng.integers(0, n, m), rng.integers(0, n, m), n)]


def run(config, traffic, seed, seconds, devices, clock, spans, t_start,
        profile=None):
    from repro.core import CensusEngine, from_edges
    (src, dst, n), = control_inputs(config, traffic, seed, 1)
    g = from_edges(src, dst, n=n)
    engine = CensusEngine(backend=config["engine"]["backend"])
    engine.run(g)
    record = {"driver": "single", "chips": len(devices), "censuses": [],
              "setup_compiles": clock.count()}
    t0 = time.perf_counter()
    record["setup_s"] = t0 - t_start
    c0 = clock.count()
    got = [engine.run(g)]
    record["window_s"] = time.perf_counter() - t0
    record["window_compiles"] = clock.count() - c0
    record["memory_peak_bytes"] = drive.peak_bytes(devices)
    want = reference.census(src, dst, n)
    gap = max(reference.gap(c, want) for c in got)
    record.update(attempted=len(got), failed=0, errors=[], checked=1,
                  reference_s=0.0, checks={
                      "census_gap": {"value": gap, "limit": 0},
                      "failed": {"value": 0, "limit": 0}})
    return record
"""


def test_a_new_driver_comes_from_a_new_file(tiny, tmp_path):
    """Traffic that no driver fits brings its driver as a new file."""
    root = _copy(tmp_path)
    here = root / "benchmarks" / "chip"
    bench = _add(root, {
        "drivers/single.py": NEW_DRIVER,
        "traffic/single.json": json.dumps(
            {"driver": "single", "n": 300, "m": 1500}),
        "metrics/window_ms.single.py":
            "def read(record):\n"
            "    if record['driver'] != 'single':\n"
            "        return None\n"
            "    return 1e3 * record['window_s']\n"}, {
        "workloads": [{"name": "patents-single", "config": "cit-patents-16",
                       "traffic": "single", "chips": 1,
                       "why": "one census a window"}],
        "end_to_end": [{"name": "window_ms.single", "unit": "ms",
                        "better": "lower", "bound": 0.05,
                        "source": "host_clock",
                        "workloads": ["patents-single"]}]})
    cell = run.resolve(bench, "patents-single", root=root, here=here)
    assert [m["name"] for m, _ in cell["end_to_end"]] == [
        "census_s", "setup_s", "window_ms.single"]
    line, _ = measure(cell)
    assert line["correct"] is True and line["attempted"] == 1
    assert line["metrics"]["window_ms.single"]["value"] > 0
    assert "census_s" not in line["metrics"]


def _cli(cwd, *extra, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "patents-batch", "--seed", "3", "--seconds", "1", "--trace", "0",
         *extra], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_cli_refuses_a_machine_without_a_tpu():
    done = _cli(run.ROOT)
    assert done.returncode != 0
    assert done.stdout.strip() == ""
    assert "needs a TPU" in done.stderr


def test_cli_fails_without_the_program(tmp_path):
    """A checkout of the benchmark alone has no system to measure."""
    root = _copy(tmp_path)
    done = _cli(root, env_extra={"PYTHONPATH": ""})
    assert done.returncode != 0
    assert done.stdout.strip() == ""
