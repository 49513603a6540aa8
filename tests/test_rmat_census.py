"""Exact censuses of directed R-MAT (Graph500 Kronecker) graphs.

The inputs are raw generator tuples at SCALE 8 to 10 and edgefactor 16:
self-loops, repeated tuples, hub rows of hundreds of entries and
thousands of mutual pairs, with every one of the 16 triad types present.
Every engine path is held to two independent references, the serial
Batagelj-Mrvar census (``core/census_ref.py``) and the benchmark's
numpy wedge census (``benchmarks/chip/reference.py``), exactly.
"""

import sys
from pathlib import Path

import numpy as np
import pytest

from repro.core import (
    CensusEngine, census_batagelj_mrvar, default_mesh, from_edges,
    pair_space)

_BENCHMARKS = str(Path(__file__).resolve().parents[1] / "benchmarks")
if _BENCHMARKS not in sys.path:
    sys.path.append(_BENCHMARKS)

from chip import kronecker, reference  # noqa: E402

#: (scale, seed) of each input graph
GRAPHS = [(8, 0), (9, 1), (10, 2)]
EDGEFACTOR = 16


@pytest.fixture(scope="module", params=GRAPHS,
                ids=lambda p: f"s{p[0]}-{p[1]}")
def rmat(request):
    """One raw R-MAT tuple list, its graph and its census."""
    scale, seed = request.param
    n = 1 << scale
    src, dst = kronecker.kronecker_tuples(scale, EDGEFACTOR * n, seed)
    g = from_edges(src, dst, n=n)
    return {"src": src, "dst": dst, "n": n, "g": g,
            "want": reference.census(src, dst, n)}


def test_inputs_are_raw_tuples_with_every_triad_type(rmat):
    src, dst, n = rmat["src"], rmat["dst"], rmat["n"]
    assert len(src) == EDGEFACTOR * n
    assert (src == dst).any()
    assert len(np.unique(src * n + dst)) < len(src)
    _, code = reference.dyads(src, dst, n)
    assert (code == 3).sum() > 100
    assert (rmat["want"] > 0).all()


def test_the_references_agree(rmat):
    assert (census_batagelj_mrvar(rmat["g"]) == rmat["want"]).all()


def _check_stats(stats, space):
    assert stats.preprune_items == space.num_items_preprune
    assert stats.search_iters == space.search_iters
    assert stats.items == space.num_items_postprune()


@pytest.mark.parametrize("orient", ["none", "degree"])
@pytest.mark.parametrize("emit", ["device", "host"])
def test_engine_matches_the_references(rmat, emit, orient):
    engine = CensusEngine(backend="jnp", emit=emit)
    got = engine.run(rmat["g"], orient=orient, max_items=1 << 12)
    assert got.dtype == np.int64
    assert reference.gap(got, rmat["want"]) == 0
    _check_stats(engine.stats, pair_space(rmat["g"], orient=orient))


@pytest.mark.parametrize("emit", ["device", "host"])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_a_hub_pair_spans_three_windows(seed, emit):
    """A budget a third of the heaviest pair's lanes, at SCALE 8 (some
    2,000 dispatches): that pair's lanes fall in at least three
    windows, and the census does not move."""
    n = 1 << 8
    src, dst = kronecker.kronecker_tuples(8, EDGEFACTOR * n, seed)
    g = from_edges(src, dst, n=n)
    space = pair_space(g, orient="degree")
    heaviest = int(space.counts.max())
    max_items = heaviest // 3
    assert heaviest >= 3 * max_items
    engine = CensusEngine(backend="jnp", emit=emit)
    got = engine.run(g, orient="degree", max_items=max_items)
    assert reference.gap(got, reference.census(src, dst, n)) == 0
    assert engine.stats.chunks >= space.num_items_preprune // max_items
    _check_stats(engine.stats, space)


@pytest.mark.parametrize("emit", ["device", "host"])
def test_partitioned_on_four_devices_matches(rmat, emit):
    """Four virtual devices, the graph sharded: the same census, the
    pre-prune lanes summed over the shards."""
    engine = CensusEngine(mesh=default_mesh(4), backend="jnp", emit=emit,
                          partition=True)
    got = engine.run(rmat["g"], orient="degree", max_items=1 << 14)
    assert reference.gap(got, rmat["want"]) == 0
    assert engine.stats.partitioned
    _check_stats(engine.stats, pair_space(rmat["g"], orient="degree"))
