"""Whole exact censuses of a Graph500 Kronecker graph through
``CensusEngine.run``, back to back, closed loop, until the window's time
is up: the batch driver's timed loop, on another graph.

The configuration gives the graph (``n``, ``arcs``, ``initiator``,
``structure_seed``: :func:`chip.kronecker.config_arcs`, at the largest
scale whose ids fit in ``n``) and the engine settings.  The mix takes no
parameters.

The loop, the warm-up, the reference check and the record are
``drivers/batch.py``'s own: this driver loads a private copy of that
module and gives the copy this driver's :func:`graph_arcs`.  The batch
module that a citation cell loads is never the patched one.
"""

from __future__ import annotations

import importlib.util
from pathlib import Path

from chip import gen, kronecker


def graph_arcs(config: dict, seed: int):
    """The configured graph's arcs: its raw tuples drawn once from the
    configuration's ``structure_seed``, its vertex ids permuted by
    ``seed`` (the specification's label permutation), so that every seed
    asks for the same work."""
    src, dst = kronecker.config_arcs(config)
    return gen.relabel(src, dst, config["n"], seed)


def _batch():
    """``drivers/batch.py`` loaded under a name of its own, reading this
    driver's graph."""
    path = Path(__file__).resolve().parent / "batch.py"
    spec = importlib.util.spec_from_file_location(
        "chip_drivers_graph500_batch", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    mod.graph_arcs = graph_arcs
    return mod


_BATCH = _batch()
run = _BATCH.run


def control_inputs(config: dict, traffic: dict, seed: int, count: int):
    src, dst = graph_arcs(config, seed)
    return [(src, dst, config["n"])]
