"""The citation graph generator: the arc count is met exactly, the arcs
are a simple graph with no mutual pair, and the shape it reports agrees
with networkx."""

import json
from pathlib import Path

import networkx as nx
import numpy as np
import pytest

from chip import gen

HERE = Path(gen.__file__).parent


@pytest.mark.parametrize("n,arcs,seed", [(500, 2188, 0), (3000, 13128, 1),
                                         (2000, 8752, 2 ** 33 + 5)])
def test_citation_arcs_meet_the_count_and_stay_simple(n, arcs, seed):
    src, dst = gen.citation_arcs(n, arcs, 3.126, seed)
    assert len(src) == len(dst) == arcs
    assert not (src == dst).any()
    key = src * n + dst
    assert len(np.unique(key)) == arcs
    assert not np.isin(dst * n + src, key).any()
    again = gen.citation_arcs(n, arcs, 3.126, seed)
    assert (again[0] == src).all() and (again[1] == dst).all()


def test_outdegrees_sum_to_the_arcs():
    rng = np.random.default_rng(3)
    deg = gen.powerlaw_outdegrees(10_000, 3.126, 43_761, rng)
    assert deg.sum() == 43_761
    assert deg.min() >= 0 and deg.max() < 10_000


def test_relabel_keeps_the_shape():
    src, dst = gen.citation_arcs(800, 3500, 3.126, 4)
    a, b = gen.relabel(src, dst, 800, 9)
    assert gen.shape(a, b, 800) == gen.shape(src, dst, 800)


@pytest.mark.parametrize("seed", [0, 1])
def test_shape_agrees_with_networkx(seed):
    rng = np.random.default_rng(seed)
    n = 60
    src, dst = rng.integers(0, n, 400), rng.integers(0, n, 400)
    got = gen.shape(src, dst, n, block=7)
    g = nx.DiGraph()
    g.add_nodes_from(range(n))
    g.add_edges_from((int(a), int(b)) for a, b in zip(src, dst) if a != b)
    u = g.to_undirected()
    assert got["arcs"] == g.number_of_edges()
    assert got["max_out_degree"] == max(d for _, d in g.out_degree())
    assert got["max_in_degree"] == max(d for _, d in g.in_degree())
    assert got["triangles"] == sum(nx.triangles(u).values()) // 3
    assert got["avg_clustering"] == pytest.approx(nx.average_clustering(u))


def test_configs_record_their_realized_arcs():
    """What a configuration says it realizes is what the generator
    draws (the count alone: the full shape takes seconds)."""
    for name in ("cit-patents-16", "cit-patents-16-x4"):
        config = json.loads((HERE / "configs" / f"{name}.json").read_text())
        assert config["realized"]["arcs"] == config["arcs"]
        deg = gen.powerlaw_outdegrees(
            config["n"], config["exponent"], config["arcs"],
            np.random.default_rng(config["structure_seed"]))
        assert int(deg.max()) == config["realized"]["max_out_degree"]
