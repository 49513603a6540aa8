"""The Graph500 Kronecker cell: its generator follows the specification's
loop, its configuration records what the generator draws, its driver is
the batch driver's timed loop on another graph, its two lane metrics
read the program's counters, and one wrong count of one census makes
the run not correct."""

import json
import math
from pathlib import Path

import numpy as np
import pytest
from chip.testkit import STREAM, X4, measure

from chip import control, drive, gen, kronecker, reference, run, work

CELL = "graph500-s13-batch"
HERE = Path(run.HERE)
LANES = ("lane_keep_pct.batch", "pad_lane_pct.batch")


def _config() -> dict:
    return json.loads((HERE / "configs" / "graph500-s13.json").read_text())


@pytest.mark.parametrize("scale,tuples,seed", [(6, 1000, 0), (10, 16384, 1),
                                               (13, 4096, 2 ** 33 + 5)])
def test_tuples_are_deterministic_per_seed(scale, tuples, seed):
    src, dst = kronecker.kronecker_tuples(scale, tuples, seed)
    assert len(src) == len(dst) == tuples
    assert src.min() >= 0 and dst.min() >= 0
    assert max(src.max(), dst.max()) < 2 ** scale
    again = kronecker.kronecker_tuples(scale, tuples, seed)
    assert (again[0] == src).all() and (again[1] == dst).all()
    other = kronecker.kronecker_tuples(scale, tuples, seed + 1)
    assert (other[0] != src).any()


@pytest.mark.parametrize("bit", [0, 9])
def test_quadrant_shares_match_the_initiator(bit):
    """Each bit level picks a quadrant of the adjacency matrix with the
    initiator's probabilities: within 3 sigma at 2**17 tuples."""
    tuples = 1 << 17
    src, dst = kronecker.kronecker_tuples(10, tuples, 3)
    row, col = (src >> bit) & 1, (dst >> bit) & 1
    for name, (r, c) in zip("ABCD", [(0, 0), (0, 1), (1, 0), (1, 1)]):
        p = kronecker.INITIATOR[name]
        share = np.mean((row == r) & (col == c))
        assert abs(share - p) < 3 * math.sqrt(p * (1 - p) / tuples), name


def test_the_configuration_records_what_the_generator_draws():
    """The cheap half of ``realized`` (no pruning count, no triangles)."""
    config = _config()
    n = config["n"]
    assert n == 2 ** config["scale"]
    assert config["arcs"] == config["edgefactor"] * n
    assert config["initiator"] == kronecker.INITIATOR
    src, dst = kronecker.config_arcs(config)
    pkey, pcode = reference.dyads(src, dst, n)
    deg = np.bincount(np.concatenate([pkey // n, pkey % n]), minlength=n)
    got = {"tuples": len(src), "self_loops": int((src == dst).sum()),
           "pairs": len(pkey), "mutual_pairs": int((pcode == 3).sum()),
           "max_degree": int(deg.max()),
           "preprune_items": work.adjacency_entries(src, dst, n)}
    assert got == {k: config["realized"][k] for k in got}


def test_the_shrunk_cell_draws_a_1024_id_rmat_graph(tiny):
    cell = tiny(CELL)
    config = cell["config"]
    assert (config["n"], config["arcs"]) == (2000, 8752)
    assert kronecker.scale_of(config["n"]) == 10
    raw = kronecker.config_arcs(config)
    assert max(raw[0].max(), raw[1].max()) < 1024
    src, dst = cell["driver"].graph_arcs(config, 2 ** 33 + 1)
    assert len(src) == 8752
    assert len(np.unique(np.concatenate([src, dst]))) <= 1024
    again = gen.relabel(*raw, 2000, 2 ** 33 + 1)
    assert (again[0] == src).all() and (again[1] == dst).all()


def test_the_cell_runs_the_batch_loop_on_its_own_graph(tiny):
    cell = tiny(CELL)
    assert Path(cell["driver"].__file__).name == "graph500.py"
    patents = tiny("patents-batch")
    assert patents["driver"].graph_arcs is not cell["driver"].graph_arcs
    # a citation cell resolved after this one still draws citations
    fresh = drive.load(HERE / "drivers" / "batch.py")
    src, _ = fresh.graph_arcs(patents["config"], 5)
    assert len(src) == patents["config"]["arcs"]
    assert (fresh.graph_arcs.__module__
            != cell["driver"].graph_arcs.__module__)
    line, record = measure(cell)
    _, want = measure(patents)
    assert line["correct"] is True
    assert set(record) == set(want)
    assert set(record["censuses"][0]) == set(want["censuses"][0])
    assert set(record["censuses"][0]["stats"]) == set(
        want["censuses"][0]["stats"])


def test_a_census_with_the_300_count_off_by_one_is_not_correct(
        tiny, monkeypatch):
    import repro.core.engine as engine
    real = engine.assemble_counts
    calls = [0]

    def off(*args, **kw):
        c = np.array(real(*args, **kw))
        calls[0] += 1
        if calls[0] == 1:
            c[0] -= 1
            c[15] += 1
        return c
    monkeypatch.setattr(engine, "assemble_counts", off)
    line, _ = measure(tiny(CELL))
    assert line["correct"] is False
    assert line["checks"]["census_gap"]["value"] == 1


def test_the_control_fails_the_check(tiny):
    """The float32 reference, in place of the census, reads a gap."""
    assert min(control.control_gaps(tiny(CELL), 11)) > 0


def _readers():
    return {name: drive.load(HERE / "metrics" / f"{name}.py").read
            for name in LANES}


def test_lane_metrics_read_the_counters():
    stats = [{"items": 60, "preprune_items": 100, "chunks": 2,
              "chunk_shape": 64},
             {"items": 30, "preprune_items": 100, "chunks": 2,
              "chunk_shape": 64}]
    record = {"driver": "batch", "chips": 1,
              "censuses": [{"stats": st} for st in stats]}
    keep, pad = (_readers()[name] for name in LANES)
    assert keep(record) == pytest.approx(45.0)
    assert pad(record) == pytest.approx(100.0 * 56 / 256)
    # a program that does not count its pre-prune lanes reads nothing
    for st in stats:
        del st["preprune_items"]
    assert keep(record) is None and pad(record) is None


def test_lane_metrics_read_nothing_on_four_chips_or_a_stream(tiny):
    _, four = measure(tiny(X4))
    assert four["censuses"][0]["stats"]["preprune_items"] > 0
    _, stream = measure(tiny(STREAM))
    for read in _readers().values():
        assert read(four) is None and read(stream) is None
