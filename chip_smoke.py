#!/usr/bin/env python3
"""Bring-up check of the triad census on a TPU, at the paper's
citation-graph scale, through the entry points a user calls.

    python chip_smoke.py [--seed S]          # one chip: batch + monitor
    python chip_smoke.py --four-chips        # partitioned census, 2x2 host

Phases, all in this one process:

* **batch** — a cit-Patents-sized graph (SNAP cit-Patents: 3,774,768
  vertices, 16,518,948 arcs; out-degree exponent 3.126, no mutual arcs)
  counted by ``CensusEngine(backend="jnp").run`` in streamed 4M-item
  chunks under ``orient="degree"``.  The census must sum to C(n, 3), the
  device's kept-item count must equal the host's post-prune count, and
  host emission (a different device program) must give the same census
  bit for bit.  Exactness: a seeded 20,000-vertex patents graph through
  the same engine must equal the serial Batagelj–Mrvar census.
* **monitor** — ``TriadMonitor`` over the backbone-plus-ephemeral edge
  stream (20,000 servers, 50,000 peers, 150,000 backbone arcs): a
  200,000-edge window sliding 8 times by 10,000 edges.  Every
  delta-updated window must equal the full recompute, with no retries,
  failovers or degraded windows.
* **four-chips** (``--four-chips`` only, instead of the above) — the
  batch graph partitioned over a 4-chip mesh, 1D and ``(2, 2)``, each
  bit-identical to the one-chip census in the same process, with every
  chip's memory printed.

Each phase prints its wall time, compile time, compile count and the
device's peak memory: set-up facts, not benchmark numbers.  The last
line of stdout is ``{"ok": true, "device": {...}}`` only when every
check passed on a TPU; anything else exits non-zero without it.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh  # noqa: E402

from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.core import (  # noqa: E402
    PAPER_WORKLOADS, CensusEngine, TriadMonitor, census_batagelj_mrvar,
    monitor_stream, pair_space, paper_workload, partition_graph,
    partition_graph_2d, scale_free_digraph)

#: SNAP cit-Patents: vertices and average out-degree (16,518,948 arcs)
PATENTS_N = 3_774_768
PATENTS_AVG_DEGREE = 4.376
#: pre-prune work items per dispatch: ~250 MB of per-item temporaries
#: next to the ~350 MB resident graph, and 101 chunks for the batch graph
MAX_ITEMS = 1 << 22
#: exactness graph: small enough for the serial reference (~1 min)
EXACT_N = 20_000
EXACT_AVG_DEGREE = 3.0
#: monitor stream: the backbone-dominated regime (1 ephemeral slot in 50)
N_SERVERS, N_PEERS, BACKBONE_ARCS, EPH_EVERY = 20_000, 50_000, 150_000, 50
WINDOW, STRIDE, SLIDES = 200_000, 10_000, 8
MONITOR_MAX_ITEMS = 1 << 20


class CheckFailed(AssertionError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise CheckFailed(what)
    print(f"  ok: {what}", flush=True)


_compile_seconds: list = []


def _on_event(event: str, duration: float, **_kw) -> None:
    if event == "/jax/core/compile/backend_compile_duration":
        _compile_seconds.append(duration)


class Phase:
    """Prints a phase's wall time, compile time and count, and the peak
    device memory so far, when the phase ends."""

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        print(f"[{self.name}] start", flush=True)
        self.t0 = time.perf_counter()
        self.c0 = len(_compile_seconds)
        return self

    def __exit__(self, exc_type, exc, tb):
        wall = time.perf_counter() - self.t0
        new = _compile_seconds[self.c0:]
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in jax.local_devices()]
        status = "done" if exc_type is None else "FAILED"
        print(f"[{self.name}] {status} wall_s={wall} "
              f"compile_s={sum(new)} compiles={len(new)} "
              f"peak_bytes_in_use={peaks}", flush=True)
        return False


def patents_graph(seed: int):
    # Uniform targets, not the preferential default: at this size the
    # static-Zipf attachment gives one vertex an in-degree of ~828,000,
    # a pair space of ~6.2e11 post-prune items, a hub the real citation
    # graph does not have.  Uniform targets keep the out-degree law and
    # give ~15.5M arcs with a maximum degree near 4,100.
    return scale_free_digraph(
        n=PATENTS_N, avg_degree=PATENTS_AVG_DEGREE,
        exponent=PAPER_WORKLOADS["patents"]["exponent"], mutual_p=0.0,
        preferential=False, seed=seed)


def batch_phase(seed: int) -> None:
    with Phase("batch.graph"):
        g = patents_graph(seed)
        space = pair_space(g, orient="degree")
        kept_host = space.num_items_postprune()
        print(f"  n={g.n} arcs={g.num_arcs} pairs={space.num_pairs} "
              f"max_degree={space.max_degree} "
              f"preprune_items={space.num_items_preprune} "
              f"postprune_items={kept_host}", flush=True)
    engine = CensusEngine(backend="jnp")
    with Phase("batch.device_emit"):
        c_dev = engine.run(g, orient="degree", emit="device",
                           max_items=MAX_ITEMS)
        print(f"  {engine.stats.summary()}", flush=True)
        check(int(c_dev.sum()) == math.comb(g.n, 3),
              "batch census sums to C(n, 3)")
        check(engine.stats.items == kept_host,
              f"device kept {engine.stats.items} items == host "
              f"post-prune count {kept_host}")
    with Phase("batch.host_emit"):
        c_host = engine.run(g, orient="degree", emit="host",
                            max_items=MAX_ITEMS)
        print(f"  {engine.stats.summary()}", flush=True)
        check(np.array_equal(c_dev, c_host),
              "emit='host' census bit-identical to emit='device'")
    with Phase("batch.exact"):
        small = paper_workload("patents", n=EXACT_N,
                               avg_degree=EXACT_AVG_DEGREE, seed=seed)
        got = engine.run(small, orient="degree", max_items=MAX_ITEMS)
        print(f"  {engine.stats.summary()}", flush=True)
        want = census_batagelj_mrvar(small)
        check(np.array_equal(got, want),
              f"{EXACT_N}-vertex census bit-identical to the serial "
              f"Batagelj-Mrvar reference")


def monitor_phase(seed: int) -> None:
    rng = np.random.default_rng(seed)
    length = WINDOW + SLIDES * STRIDE
    src, dst, n = monitor_stream(rng, N_SERVERS, N_PEERS, BACKBONE_ARCS,
                                 length, eph_every=EPH_EVERY)
    monitors = {}
    for incremental in (True, False):
        name = "incremental" if incremental else "full"
        with Phase(f"monitor.{name}"):
            mon = TriadMonitor(n, window=WINDOW, stride=STRIDE,
                               orient="degree", incremental=incremental,
                               index=True, max_items=MONITOR_MAX_ITEMS)
            mon.observe(src, dst)
            stats = mon.window_stats
            print(f"  windows={len(stats)} items="
                  f"{[s.items for s in stats if s is not None]}",
                  flush=True)
            check(len(mon.censuses) == SLIDES + 1,
                  f"{SLIDES + 1} windows emitted")
            check(mon.degraded == [], "no degraded windows")
            check(all(s is not None and s.retries == 0
                      and s.failovers == 0 for s in stats),
                  "no retries or failovers")
            check(all(int(c.sum()) == math.comb(n, 3)
                      for c in mon.censuses),
                  "every window census sums to C(n, 3)")
        monitors[name] = mon
    check(np.array_equal(monitors["incremental"].censuses,
                         monitors["full"].censuses),
          "every delta-updated window bit-identical to its full recompute")


def four_chip_phase(seed: int) -> None:
    devices = jax.devices()[:4]
    mesh = Mesh(np.asarray(devices), ("d",))
    with Phase("four.graph"):
        g = patents_graph(seed)
        space = pair_space(g, orient="degree")
    # the host partitions the pair space (minutes of numpy) in worker
    # threads while the chips count the one-chip reference census
    with ThreadPoolExecutor(max_workers=2) as pool:
        parts = {
            "partition_1d": pool.submit(partition_graph, num_shards=4,
                                        space=space),
            "partition_2x2": pool.submit(partition_graph_2d, space=space,
                                         mesh_shape=(2, 2))}
        with Phase("four.one_chip"):
            c_one = CensusEngine(backend="jnp").run(
                g, orient="degree", max_items=MAX_ITEMS)
        engines = {"partition_1d": CensusEngine(mesh, partition=True),
                   "partition_2x2": CensusEngine(mesh,
                                                 partition_2d=(2, 2))}
        for name, engine in engines.items():
            with Phase(f"four.{name}"):
                part = parts[name].result()
                # the per-device budget is MAX_ITEMS, as on one chip
                c = engine.run(g, part=part, max_items=4 * MAX_ITEMS)
                print(f"  {engine.stats.summary()}", flush=True)
                peaks = []
                for d, dev in enumerate(devices):
                    ms = dev.memory_stats() or {}
                    peaks.append(ms.get("peak_bytes_in_use", 0))
                    print(f"  device {d} ({dev}): shard_bytes="
                          f"{part.stats.shard_bytes[d]} bytes_in_use="
                          f"{ms.get('bytes_in_use')} peak_bytes_in_use="
                          f"{peaks[-1]}", flush=True)
                check(all(peak >= part.stats.shard_bytes[d]
                          for d, peak in enumerate(peaks)),
                      "every chip's peak memory holds its own shard")
                check(np.array_equal(c, c_one),
                      f"{name} census bit-identical to the one-chip "
                      f"census")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of every generated graph and stream")
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the partitioned census on a 4-chip "
                         "mesh, against the one-chip census")
    args = ap.parse_args(argv)

    enable_compile_cache()
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {platform!r}",
              file=sys.stderr)
        return 1
    if args.four_chips and len(devices) < 4:
        print(f"chip_smoke: --four-chips needs 4 chips, found "
              f"{len(devices)}", file=sys.stderr)
        return 1
    print(f"devices: {len(devices)} x {devices[0].device_kind} "
          f"jax {jax.__version__}", flush=True)
    jax.monitoring.register_event_duration_secs_listener(_on_event)

    t0 = time.perf_counter()
    if args.four_chips:
        four_chip_phase(args.seed)
    else:
        batch_phase(args.seed)
        monitor_phase(args.seed)
    print(f"total wall_s={time.perf_counter() - t0}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
