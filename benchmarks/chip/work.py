"""Work of one exact triad census, counted from the graph alone.

The census visits every connected pair ``u < v`` and walks the union of
the two adjacency lists: each entry of either list is read once (a
4-byte packed neighbour word) and its relation to the other endpoint is
looked up once (another 4-byte word).  So a census moves at least

    bytes = 8 * sum over connected pairs u < v of (deg u + deg v)
          = 8 * sum over vertices v of deg(v) ** 2

where ``deg`` counts undirected neighbours.  The count is a property of
the graph: it does not change with the program's orientation, emission,
pruning or chunking, so every implementation is charged for the same
work.  Pruning that skips entries only makes the charge easier to beat.
"""

from __future__ import annotations

import numpy as np

from chip.reference import dyads

#: bytes per adjacency entry visited: the entry and one lookup
BYTES_PER_ENTRY = 8


def adjacency_entries(src, dst, n: int) -> int:
    """Sum over connected pairs of the two endpoints' degrees."""
    pkey, _ = dyads(src, dst, n)
    deg = np.bincount(np.concatenate([pkey // n, pkey % n]),
                      minlength=n).astype(np.int64)
    return int((deg * deg).sum())


def census_bytes(src, dst, n: int) -> int:
    """Bytes one census of the graph must move (see the module doc)."""
    return BYTES_PER_ENTRY * adjacency_entries(src, dst, n)
