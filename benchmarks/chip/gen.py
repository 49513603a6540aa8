"""Graph inputs of the benchmark, kept apart from the program so that a
change to the program cannot move the yardstick.

:func:`citation_arcs` draws a citation graph shaped like SNAP
cit-Patents: a bounded power-law out-degree law, uniform targets, no
self-citations, no repeated and no mutual citations, and exactly the
arc count asked for.  It starts from the out-degree draw of
``repro.core.generators.scale_free_digraph`` but rounds and redraws so
that the count is met.

    python3 benchmarks/chip/gen.py benchmarks/chip/configs/cit-patents-16.json

prints the realized shape of a configuration's graph (arcs, largest
degrees, triangles, average clustering), the numbers its ``realized``
entry records.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

import numpy as np


def powerlaw_outdegrees(n: int, exponent: float, arcs: int,
                        rng: np.random.Generator,
                        max_degree: int | None = None) -> np.ndarray:
    """Bounded discrete power-law sample, scaled so that it sums to
    exactly ``arcs``: the scaled degrees are rounded down and the
    remainder goes one arc each to the vertices with the largest
    fractional parts, ties broken at random."""
    if max_degree is None:
        max_degree = max(4, int(np.sqrt(n) * 4))
    ks = np.arange(1, max_degree + 1, dtype=np.float64)
    pmf = ks ** (-exponent)
    pmf /= pmf.sum()
    deg = rng.choice(ks.astype(np.int64), size=n, p=pmf)
    want = deg * (arcs / deg.sum())
    out = np.floor(want).astype(np.int64)
    order = np.lexsort((rng.random(n), out - want))
    out[order[:arcs - int(out.sum())]] += 1
    if out.max() >= n:
        raise ValueError(f"an out-degree of {out.max()} needs more than "
                         f"{n} vertices")
    return out


def _unfit(src: np.ndarray, dst: np.ndarray, n: int) -> np.ndarray:
    """Arcs to draw again: self-loops, repeats after the first, and the
    arc from the larger id of each mutual pair."""
    key = src * n + dst
    order = np.argsort(key, kind="stable")
    repeat = np.zeros(len(key), bool)
    repeat[order[1:]] = key[order[1:]] == key[order[:-1]]
    mutual = (src > dst) & np.isin(dst * n + src, key)
    return (src == dst) | repeat | mutual


def citation_arcs(n: int, arcs: int, exponent: float,
                  seed: int) -> tuple[np.ndarray, np.ndarray]:
    """Arcs ``(src, dst)`` of a citation graph: ``arcs`` distinct arcs
    over ``n`` vertices, power-law out-degrees, targets drawn uniformly
    and drawn again until no arc is a self-loop, a repeat or one half of
    a mutual pair."""
    rng = np.random.default_rng(seed)
    outdeg = powerlaw_outdegrees(n, exponent, arcs, rng)
    src = np.repeat(np.arange(n, dtype=np.int64), outdeg)
    dst = rng.integers(0, n, arcs)
    while (bad := _unfit(src, dst, n)).any():
        dst[bad] = rng.integers(0, n, int(bad.sum()))
    return src, dst


def relabel(src, dst, n: int, seed: int):
    """The same graph or stream with its vertex ids permuted by
    ``seed``: every seed gives the same sizes, degrees and arrival
    order, on other vertices."""
    perm = np.random.default_rng(seed).permutation(n)
    return perm[src], perm[dst]


def shape(src, dst, n: int, block: int = 1 << 15) -> dict:
    """Arcs, largest degrees, triangles and average clustering (over
    every vertex, zero below degree 2, as networkx's
    ``average_clustering``) of the undirected graph under the arcs."""
    from scipy import sparse
    src, dst = np.asarray(src), np.asarray(dst)
    keep = src != dst
    a = sparse.coo_matrix((np.ones(int(keep.sum()), np.int64),
                           (src[keep], dst[keep])), shape=(n, n)).tocsr()
    a.data[:] = 1
    und = ((a + a.T) > 0).astype(np.int64).tocsr()
    deg = np.diff(und.indptr)
    tri = np.zeros(n, np.int64)
    for lo in range(0, n, block):
        rows = und[lo:lo + block]
        tri[lo:lo + block] = np.asarray(
            (rows @ und).multiply(rows).sum(axis=1)).ravel() // 2
    pairs = deg * (deg - 1)
    cc = np.where(pairs > 0, 2 * tri / np.maximum(pairs, 1), 0.0)
    return {"arcs": int(a.nnz),
            "max_out_degree": int(np.diff(a.indptr).max()),
            "max_in_degree": int(np.bincount(a.indices, minlength=n).max()),
            "triangles": int(tri.sum() // 3),
            "avg_clustering": float(cc.mean())}


def main(argv=None) -> int:
    for path in (argv if argv is not None else sys.argv[1:]):
        config = json.loads(Path(path).read_text())
        src, dst = citation_arcs(config["n"], config["arcs"],
                                 config["exponent"],
                                 config["structure_seed"])
        print(json.dumps({"config": config["name"],
                          **shape(src, dst, config["n"])}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
