"""The plain reference census against independent oracles, and its
control: the same census carried in float32 must fail the check."""

import networkx as nx
import numpy as np
import pytest

from chip import reference


def _random(n, m, mutual, seed):
    rng = np.random.default_rng(seed)
    src, dst = rng.integers(0, n, m), rng.integers(0, n, m)
    back = rng.random(m) < mutual
    return (np.concatenate([src, dst[back]]),
            np.concatenate([dst, src[back]]))


def _networkx(src, dst, n):
    g = nx.DiGraph()
    g.add_nodes_from(range(n))
    g.add_edges_from((int(a), int(b)) for a, b in zip(src, dst) if a != b)
    got = nx.triadic_census(g)
    return np.array([got[name] for name in reference.NAMES])


@pytest.mark.parametrize("seed", range(6))
@pytest.mark.parametrize("mutual", [0.0, 0.4])
def test_census_matches_networkx(seed, mutual):
    n = 5 + 7 * seed
    src, dst = _random(n, 3 * n, mutual, seed)
    assert (reference.census(src, dst, n) == _networkx(src, dst, n)).all()


def test_census_matches_program_bruteforce():
    from repro.core import census_bruteforce, from_edges
    src, dst = _random(40, 150, 0.3, 7)
    want = census_bruteforce(from_edges(src, dst, n=40))
    assert (reference.census(src, dst, 40) == want).all()


def test_blocks_do_not_change_the_census(monkeypatch):
    src, dst = _random(60, 400, 0.2, 3)
    whole = reference.census(src, dst, 60)
    monkeypatch.setattr(reference, "BLOCK_WEDGES", 5)
    assert (reference.census(src, dst, 60) == whole).all()


def test_class_table_covers_every_class():
    assert sorted(set(reference.CLASS_OF_CODE.tolist())) == list(range(16))
    assert reference.CLASS_OF_CODE[0] == 0


def test_empty_and_loop_only_graphs():
    assert reference.census([], [], 5).tolist() == [10] + [0] * 15
    assert reference.census([1, 2], [1, 2], 4).tolist() == [4] + [0] * 15


@pytest.mark.parametrize("seed", [11, 12, 13])
def test_control_fails_the_check(seed):
    """At counts past 2**24 float32 cannot hold the census exactly: the
    control reads a gap above the check's limit of 0."""
    from chip import gen
    src, dst = gen.citation_arcs(3000, 13128, 3.126, seed)
    want = reference.census(src, dst, 3000)
    control = reference.census(src, dst, 3000, dtype=np.float32)
    assert reference.gap(want, want) == 0
    assert reference.gap(control, want) > 0
