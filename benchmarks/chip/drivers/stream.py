"""A ``TriadMonitor`` fed one stride of stream edges per ``observe``
call, closed loop, each call followed by ``alarms()``, until the
window's time is up.

The configuration gives the monitored network (``n_servers``,
``n_peers``), the window, stride, alarm history and threshold, and the
engine settings.  The mix gives the stream (:func:`monitor_stream`:
``backbone_arcs``, ``backbone_every`` or ``eph_every``,
``structure_seed``) and ``max_slides_per_s``, the most slides a second
the generated stream has room for; a run that outruns it fails.

Every window the run produced is compared with the plain reference, up
to :data:`MAX_CHECKED_WINDOWS`, then a sample drawn from the seed.
"""

from __future__ import annotations

import contextlib
import gc
import math
import multiprocessing
import time
from concurrent.futures import ProcessPoolExecutor

import numpy as np

from chip import drive, gen, reference

#: most windows of one monitor run compared with the reference; a run
#: that produced more compares a sample drawn from the seed (first and
#: last window always in it)
MAX_CHECKED_WINDOWS = 512
#: worker processes of the reference (numpy only, never JAX)
REFERENCE_WORKERS = 8


class StreamExhausted(RuntimeError):
    """The window outran the stream generated for it."""


def monitor_stream(rng, n_servers, n_peers, backbone_arcs, length,
                   backbone_every=2, eph_every=None):
    """Edge stream of a monitored network: a fixed backbone of
    ``backbone_arcs`` server-to-server arcs cycled through the stream,
    interleaved with ephemeral peer-to-peer flows drawn afresh.
    ``backbone_every=k`` makes every k-th slot a backbone arc;
    ``eph_every=k`` makes every k-th slot ephemeral and the rest
    backbone.  Returns ``(src, dst, n)`` over ``n = n_servers + n_peers``
    vertices, servers first."""
    n = n_servers + n_peers
    bs = rng.integers(0, n_servers, backbone_arcs)
    bd = (bs + 1 + rng.integers(0, n_servers - 1, backbone_arcs)) \
        % n_servers
    src = np.empty(length, np.int64)
    dst = np.empty(length, np.int64)
    slots = np.arange(length)
    if eph_every is not None:
        bb = slots % eph_every != 0
        idx = (np.cumsum(bb) - 1)[bb] % backbone_arcs
    else:
        bb = slots % backbone_every == 0
        idx = (slots[bb] // backbone_every) % backbone_arcs
    src[bb], dst[bb] = bs[idx], bd[idx]
    n_peer_slots = int((~bb).sum())
    src[~bb] = n_servers + rng.integers(0, n_peers, n_peer_slots)
    dst[~bb] = n_servers + rng.integers(0, n_peers, n_peer_slots)
    return src, dst, n


def stream_length(config: dict, traffic: dict, seconds: float) -> int:
    """Edges generated for a run: the first window, the set-up slide,
    and ``max_slides_per_s`` slides for every second of the window."""
    slides = math.ceil(traffic["max_slides_per_s"] * seconds) + 1
    return config["window"] + config["stride"] * slides


def stream_edges(config: dict, traffic: dict, seed: int, length: int):
    """The mix's edge stream: drawn from the mix's ``structure_seed``,
    its vertex ids permuted by ``seed``, so that every seed brings the
    same arrivals."""
    src, dst, n = monitor_stream(
        np.random.default_rng(traffic["structure_seed"]),
        config["n_servers"], config["n_peers"], traffic["backbone_arcs"],
        length, backbone_every=traffic.get("backbone_every", 2),
        eph_every=traffic.get("eph_every"))
    src, dst = gen.relabel(src, dst, n, seed)
    return src, dst, n


def check_windows(censuses, src, dst, n: int, window: int, stride: int,
                  seed: int) -> tuple:
    """Widest gap between each window's census and the reference, over
    every window (or a sample of :data:`MAX_CHECKED_WINDOWS` drawn from
    the seed, the first and last always in it); returns the gap and the
    windows checked."""
    k = len(censuses)
    ids = np.arange(k)
    if k > MAX_CHECKED_WINDOWS:
        rng = np.random.default_rng([seed, 1])
        mid = rng.choice(np.arange(1, k - 1), MAX_CHECKED_WINDOWS - 2,
                         replace=False)
        ids = np.sort(np.concatenate([[0, k - 1], mid]))
    jobs = [(src[i * stride:i * stride + window],
             dst[i * stride:i * stride + window], n) for i in ids]
    workers = min(REFERENCE_WORKERS, len(jobs))
    if workers > 1:
        ctx = multiprocessing.get_context("spawn")
        with ProcessPoolExecutor(workers,
                                 mp_context=ctx) as pool:
            wants = list(pool.map(reference.census_job, jobs))
    else:
        wants = [reference.census_job(j) for j in jobs]
    gap = max((reference.gap(censuses[i], w) for i, w in zip(ids, wants)),
              default=0)
    return gap, len(ids)


def control_inputs(config: dict, traffic: dict, seed: int, count: int):
    W, S = config["window"], config["stride"]
    src, dst, n = stream_edges(config, traffic, seed, W + S * count)
    return [(src[k * S:k * S + W], dst[k * S:k * S + W], n)
            for k in range(count)]


def monitor_for(config: dict, n: int, devices, **extra):
    from repro.core import TriadMonitor
    eng = config["engine"]
    kw = dict(window=config["window"], stride=config["stride"],
              history=config["history"], threshold=config["threshold"],
              backend=eng["backend"], emit=eng["emit"],
              orient=eng["orient"], incremental=eng["incremental"],
              index=eng["index"], max_items=eng["max_items_per_chip"])
    if len(devices) > 1:
        from jax.sharding import Mesh
        kw["mesh"] = Mesh(np.asarray(devices), ("d",))
        kw["partition"] = bool(eng.get("partition", False))
    kw.update(extra)
    return TriadMonitor(n, **kw)


def run(config: dict, traffic: dict, seed: int, seconds: float,
               devices, clock: drive.Clock, spans: drive.Spans, t_start: float,
               monitor_kw=None, profile=None) -> dict:
    W, S = config["window"], config["stride"]
    t_in = time.perf_counter()
    length = stream_length(config, traffic, seconds)
    src, dst, n = stream_edges(config, traffic, seed, length)
    mon = monitor_for(config, n, devices, **(monitor_kw or {}))
    t_warm = time.perf_counter()
    # set-up: the first full window, which the traffic needs anyway,
    # and one slide
    mon.observe(src[:W], dst[:W])
    mon.alarms()
    pos = W
    mon.observe(src[pos:pos + S], dst[pos:pos + S])
    mon.alarms()
    pos += S
    drive.drain(devices)
    record = {"driver": "stream", "chips": len(devices), "slides": [],
              "stride": S, "setup_compiles": clock.count(),
              "setup_parts": {"start_s": t_in - t_start,
                              "inputs_s": t_warm - t_in,
                              "warm_up_s": time.perf_counter() - t_warm}}
    raised = []
    first = len(mon.censuses)
    with profile() if profile else contextlib.nullcontext():
        with spans("window"):
            t0 = time.perf_counter()
            record["setup_s"] = t0 - t_start
            c0 = clock.count()
            deadline = t0 + seconds
            while True:
                if pos + S > length:
                    raise StreamExhausted(
                        f"the window outran the {length}-edge stream "
                        f"after {len(record['slides'])} slides")
                t = time.perf_counter()
                try:
                    with spans("slide"):
                        mon.observe(src[pos:pos + S], dst[pos:pos + S])
                    with spans("alarm"):
                        mon.alarms()
                except Exception as exc:       # the monitor died
                    raised.append(repr(exc))
                    break
                finally:
                    pos += S
                st = mon.window_stats[-1] if mon.window_stats else None
                record["slides"].append(
                    {"seconds": time.perf_counter() - t,
                     "stats": drive.stats_dict(st)})
                if time.perf_counter() >= deadline:
                    break
            record["window_s"] = time.perf_counter() - t0
            record["window_compiles"] = clock.count() - c0
    record["memory_peak_bytes"] = drive.peak_bytes(devices)
    censuses = mon.censuses
    degraded = [d["window"] for d in mon.degraded if d["window"] >= first]
    del mon
    gc.collect()
    record["attempted"] = len(record["slides"]) + len(raised)
    record["failed"] = len(degraded) + len(raised)
    record["errors"] = raised[:3]
    t = time.perf_counter()
    gap, checked = check_windows(censuses, src, dst, n, W, S, seed)
    record["reference_s"] = time.perf_counter() - t
    record["checks"] = {
        "census_gap": {"value": gap, "limit": 0},
        "failed": {"value": record["failed"], "limit": 0},
    }
    record["checked"] = checked
    return record
