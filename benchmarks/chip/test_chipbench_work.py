"""The census's byte count, from the graph alone."""

import itertools

import numpy as np
import pytest

from chip import gen, work


def _brute(src, dst, n):
    adj = [set() for _ in range(n)]
    for a, b in zip(src.tolist(), dst.tolist()):
        if a != b:
            adj[a].add(b)
            adj[b].add(a)
    return sum(len(adj[u]) + len(adj[v])
               for u, v in itertools.combinations(range(n), 2)
               if v in adj[u])


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_entries_match_brute_force_over_pairs(seed):
    rng = np.random.default_rng(seed)
    src, dst = rng.integers(0, 150, 600), rng.integers(0, 150, 600)
    back = rng.random(600) < 0.3           # mutual arcs, loops, repeats
    src, dst = (np.concatenate([src, dst[back]]),
                np.concatenate([dst, src[back]]))
    assert work.adjacency_entries(src, dst, 150) == _brute(src, dst, 150)
    assert work.census_bytes(src, dst, 150) == 8 * _brute(src, dst, 150)


def test_count_is_the_program_s_unpruned_walk():
    from repro.core import from_edges, pair_space
    src, dst = gen.citation_arcs(3000, 13128, 3.126, 5)
    g = from_edges(src, dst, n=3000)
    assert work.adjacency_entries(src, dst, 3000) \
        == pair_space(g, orient="none").num_items_preprune


def test_count_does_not_follow_orient_or_chunking():
    """The engine's item counts move with ``orient`` and ``max_items``;
    the work charged for the census does not."""
    from repro.core import CensusEngine, from_edges
    src, dst = gen.citation_arcs(2000, 8752, 3.126, 9)
    g = from_edges(src, dst, n=2000)
    charged = work.census_bytes(src, dst, 2000)
    items, censuses = set(), []
    for orient, max_items in [("none", None), ("degree", None),
                              ("degree", 1 << 11), ("none", 1 << 12)]:
        engine = CensusEngine()
        censuses.append(engine.run(g, orient=orient, max_items=max_items))
        items.add((engine.stats.items, engine.stats.chunks))
        assert work.census_bytes(src, dst, 2000) == charged
    assert len(items) > 1
    assert all((c == censuses[0]).all() for c in censuses)
