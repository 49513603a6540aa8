"""Whole exact censuses of one graph through ``CensusEngine.run``, back
to back, closed loop, until the window's time is up; the census running
at the deadline finishes and counts.

The configuration gives the graph (``n``, ``arcs``, ``exponent``,
``structure_seed``: :func:`chip.gen.citation_arcs`) and the engine
settings.  The mix takes no parameters.
"""

from __future__ import annotations

import contextlib
import gc
import time

import numpy as np

from chip import drive, gen, reference, work


def graph_arcs(config: dict, seed: int):
    """The configured graph's arcs: its structure drawn from the
    configuration's ``structure_seed``, its vertex ids permuted by
    ``seed``, so that every seed asks for the same work."""
    src, dst = gen.citation_arcs(config["n"], config["arcs"],
                                 config["exponent"],
                                 config["structure_seed"])
    return gen.relabel(src, dst, config["n"], seed)


def control_inputs(config: dict, traffic: dict, seed: int, count: int):
    src, dst = graph_arcs(config, seed)
    return [(src, dst, config["n"])]


def engine_for(config: dict, devices):
    """The ``CensusEngine`` the configuration deploys on ``devices``."""
    from jax.sharding import Mesh

    from repro.core import CensusEngine
    eng = config["engine"]
    kw = {"backend": eng["backend"], "emit": eng["emit"]}
    if len(devices) > 1:
        kw["mesh"] = Mesh(np.asarray(devices), ("d",))
        kw["partition"] = bool(eng.get("partition", False))
    return CensusEngine(**kw)


def run(config: dict, traffic: dict, seed: int, seconds: float, devices,
        clock: drive.Clock, spans: drive.Spans, t_start: float,
        engine=None, profile=None) -> dict:
    from repro.core import from_edges
    n = config["n"]
    t_in = time.perf_counter()
    src, dst = graph_arcs(config, seed)
    g = from_edges(src, dst, n=n)
    engine = engine if engine is not None else engine_for(config, devices)
    t_warm = time.perf_counter()
    eng = config["engine"]
    run_kw = {"orient": eng["orient"],
              "max_items": eng["max_items_per_chip"] * len(devices)}
    # warm-up: the census's own shapes, compiled and run for one chunk
    try:
        engine.run(g, progress=drive.stop_after_first_chunk, **run_kw)
    except drive.WarmedUp:
        pass
    drive.drain(devices)
    record = {"driver": "batch", "chips": len(devices), "censuses": [],
              "setup_compiles": clock.count(),
              "setup_parts": {"start_s": t_in - t_start,
                              "inputs_s": t_warm - t_in,
                              "warm_up_s": time.perf_counter() - t_warm}}

    landed = []

    def progress(*_args):
        landed.append(time.perf_counter())
        spans.mark("chunk")

    results, raised = [], []
    with profile() if profile else contextlib.nullcontext():
        with spans("window"):
            t0 = time.perf_counter()
            record["setup_s"] = t0 - t_start
            c0 = clock.count()
            deadline = t0 + seconds
            while True:
                t = time.perf_counter()
                landed.clear()
                try:
                    with spans("census"):
                        c = engine.run(g, progress=progress, **run_kw)
                        spans.close_mark()
                    results.append(np.asarray(c, np.int64))
                except Exception as exc:       # a census that raised
                    spans.close_mark()
                    raised.append(repr(exc))
                # host seconds from the census's start to each chunk
                # landing, so that a slow census shows where it lost time
                record["censuses"].append(
                    {"seconds": time.perf_counter() - t,
                     "landed_s": [x - t for x in landed],
                     "stats": drive.stats_dict(engine.stats)})
                if time.perf_counter() >= deadline:
                    break
            record["window_s"] = time.perf_counter() - t0
            record["window_compiles"] = clock.count() - c0
    record["memory_peak_bytes"] = drive.peak_bytes(devices)
    del engine, g
    gc.collect()
    record["attempted"] = len(record["censuses"])
    record["failed"] = len(raised)
    record["errors"] = raised[:3]
    t = time.perf_counter()
    want = reference.census(src, dst, n)
    gaps = [reference.gap(c, want) for c in results]
    record["reference_s"] = time.perf_counter() - t
    record["work_bytes"] = work.census_bytes(src, dst, n)
    record["checks"] = {
        "census_gap": {"value": max(gaps, default=0), "limit": 0},
        "failed": {"value": len(raised), "limit": 0},
    }
    record["checked"] = len(gaps)
    return record
