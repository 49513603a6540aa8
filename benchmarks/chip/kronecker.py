"""Graph500 Kronecker graphs, read as directed R-MAT graphs, kept apart
from the program so that a change to the program cannot move the
yardstick.

:func:`kronecker_tuples` follows the generation loop of the Graph500
specification ("Kernel 0", graph500.org): for each of ``scale`` bits
the row bit is set with probability ``1 - (A + B)``, then the column bit
with probability ``1 - C / (C + D)`` if the row bit is set, else
``1 - A / (A + B)``, so that the four quadrants of the adjacency matrix
are chosen with probabilities A, B, C and D.  Each tuple ``(start,
end)`` is one arc, as R-MAT (Chakrabarti, Zhan and Faloutsos, SDM 2004)
defines the generator.  Self-loops and repeated tuples are kept, as the
specification keeps them; the census drops them.  The specification's
random vertex permutation is left to the caller (``gen.relabel``).

    python3 benchmarks/chip/kronecker.py benchmarks/chip/configs/graph500-s13.json

prints the realized shape of a configuration's graph: ``gen.shape``'s
numbers, the connected and mutual pairs, the largest undirected degree,
and the lanes of one census before and after the program's pruning.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

import numpy as np

#: the specification's initiator probabilities
INITIATOR = {"A": 0.57, "B": 0.19, "C": 0.19, "D": 0.05}


def kronecker_tuples(scale: int, tuples: int, seed: int,
                     initiator: dict = INITIATOR
                     ) -> tuple[np.ndarray, np.ndarray]:
    """``tuples`` raw R-MAT tuples ``(start, end)`` over ``[0,
    2**scale)``, self-loops and repeats included, drawn from ``seed``."""
    a, b, c, d = (initiator[k] for k in "ABCD")
    ab, c_norm, a_norm = a + b, c / (c + d), a / (a + b)
    rng = np.random.default_rng(seed)
    start = np.zeros(tuples, np.int64)
    end = np.zeros(tuples, np.int64)
    for bit in range(scale):
        row = rng.random(tuples) > ab
        col = rng.random(tuples) > np.where(row, c_norm, a_norm)
        start |= row.astype(np.int64) << bit
        end |= col.astype(np.int64) << bit
    return start, end


def scale_of(n: int) -> int:
    """The largest ``scale`` whose ``2**scale`` ids fit in ``n``."""
    return int(n).bit_length() - 1


def config_arcs(config: dict) -> tuple[np.ndarray, np.ndarray]:
    """The configured graph's raw tuples, before any relabelling:
    ``arcs`` tuples at ``scale_of(n)``, from ``structure_seed``."""
    return kronecker_tuples(scale_of(config["n"]), config["arcs"],
                            config["structure_seed"], config["initiator"])


def realized(src, dst, n: int) -> dict:
    """The numbers a configuration's ``realized`` entry records.  The
    pre-prune lanes are the harness's own count (``work.py``); the
    post-prune items are the program's count under ``orient="degree"``
    (``repro.core.pair_space``)."""
    from chip import gen, reference, work
    from repro.core import from_edges, pair_space
    pkey, pcode = reference.dyads(src, dst, n)
    deg = np.bincount(np.concatenate([pkey // n, pkey % n]), minlength=n)
    space = pair_space(from_edges(src, dst, n=n), orient="degree")
    return {**gen.shape(src, dst, n),
            "tuples": int(len(src)),
            "self_loops": int((np.asarray(src) == np.asarray(dst)).sum()),
            "pairs": int(len(pkey)),
            "mutual_pairs": int((pcode == 3).sum()),
            "max_degree": int(deg.max()),
            "search_iters": max(1, int(np.ceil(np.log2(deg.max() + 1)))),
            "preprune_items": work.adjacency_entries(src, dst, n),
            "postprune_items_degree": space.num_items_postprune()}


def main(argv=None) -> int:
    here = Path(__file__).resolve().parent
    # the harness as the package ``chip``, never its modules under bare
    # names (``chip/trace.py`` would shadow the standard library's)
    sys.path[:] = [p for p in sys.path if Path(p or ".").resolve() != here]
    sys.path[:0] = [str(here.parents[1] / "src"), str(here.parent)]
    for path in (argv if argv is not None else sys.argv[1:]):
        config = json.loads(Path(path).read_text())
        src, dst = config_arcs(config)
        print(json.dumps({"config": config["name"],
                          **realized(src, dst, config["n"])}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
