"""Which cell reports which metric, and one more fault of the monitor's
timed path: a metric that lists its cells reads a number on each of them
and nothing elsewhere, and a slide that loses half of its recount is not
correct."""

import numpy as np
import pytest
from chip.testkit import STREAM, measure

from chip import drive, run

BENCH = run.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_metrics_list_cells_that_report_what_they_move():
    """A ``workloads`` list names cells of the file; a per-layer metric's
    cells each report the end-to-end metric it moves; every cell reports
    ``setup_s`` and one end-to-end metric more."""
    e2e = BENCH["end_to_end"]
    reported = {w: {m["name"] for m in e2e
                    if w in m.get("workloads", [w])} for w in CELLS}
    for m in e2e + BENCH["per_layer"]:
        assert set(m.get("workloads", CELLS)) <= set(CELLS), m["name"]
    for m in BENCH["per_layer"]:
        assert all(m["moves"] in reported[w]
                   for w in m.get("workloads", CELLS)), m["name"]
    assert all("setup_s" in r and len(r) >= 2 for r in reported.values())


@pytest.mark.parametrize("name", CELLS)
def test_metrics_read_only_on_their_cells(tiny, name):
    """Every metric that lists its cells reads a number on each of them
    and nothing on any other cell."""
    _, record = measure(tiny(name), traced=True)
    # the CPU's trace holds no device operations: half the window busy
    record["trace"].update(busy_s=record["trace"]["window_s"] / 2,
                           idle_pct=50.0)
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        if "workloads" in m:
            read = drive.load(run.HERE / "metrics" / f"{m['name']}.py").read
            got = read(record)
            assert (got is not None) == (name in m["workloads"]), m["name"]


def _drop_half_the_slide_chunks(monkeypatch):
    """Every other dispatch of a session lands as zeros: half of a
    slide's recount never reaches its census."""
    import repro.core.engine as engine
    real = engine._land_retrying_session
    calls = [0]

    def half(*args, **kw):
        hist, inter = real(*args, **kw)
        calls[0] += 1
        if calls[0] % 2:
            return hist, inter
        return np.zeros_like(hist), np.zeros_like(inter)
    monkeypatch.setattr(engine, "_land_retrying_session", half)


def test_a_slide_missing_half_its_recount_is_not_correct(tiny, monkeypatch):
    shrunk = tiny(STREAM)
    _drop_half_the_slide_chunks(monkeypatch)
    line, _ = measure(shrunk)
    assert line["correct"] is False
    assert line["checks"]["census_gap"]["value"] > 0
