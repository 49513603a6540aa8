"""Distributed triad census: the public partition + mesh API.

Two distribution regimes, both ending in the paper's single merge of
per-processor private census vectors:

* **Replicated** (the default): every device holds the whole CSR and the
  flat work items are sharded across the mesh.  Right for graphs that fit
  one device's memory anyway — zero partitioning overhead, perfect item
  balance by construction.
* **Partitioned** (``partition=True`` / :func:`partition_graph`): the
  pair space is LPT-split into one private shard per device
  (:mod:`repro.core.partition`), each device holds only its shard's
  relabeled local subgraph — O(E_shard + halo) resident bytes instead of
  O(E) — and walks its own descriptor stream inside the compile-once
  collective step; the private histograms meet in one ``psum``.
  Bit-identical to the replicated and single-device paths for every
  backend, orient and emit mode.
* **2D partitioned** (``partition_2d=(P, V)`` /
  :func:`partition_graph_2d`): the mesh is read as ``pair_shards ×
  vertex_slices``.  The pair axis keeps the 1D LPT assignment; each pair
  shard's witness range is then split across ``V`` contiguous vertex
  slices, so tile ``(s, j)`` holds only the slice of each endpoint row
  whose neighbor ids fall in its vertex range — the *halo* (replicated
  adjacency entries) shrinks from the 1D level at ``P·V`` shards to the
  1D level at ``P`` shards, spread over ``V`` devices.  Per-tile item
  sub-ranges partition each pair's global item space exactly, so the
  merged census stays bit-identical to the 1D and reference paths.

What lives here is the public surface:

* :func:`partition_graph` / :class:`GraphPartition` /
  :class:`PartitionStats` / :func:`shard_report` — the partition layer,
  usable standalone (inspect balance and residency before committing to
  a mesh shape).
* :func:`default_mesh` — flat mesh over all (or the first ``k``) local
  devices.
* :func:`triad_census_distributed` — exact census of a prebuilt
  (monolithic, replicated) plan across a mesh.
* :func:`triad_census_graph` — plan + count in one call, streaming
  (``max_items``), emission (``emit``) and partitioning (``partition``)
  knobs included.

Dispatch lives in :class:`repro.core.engine.CensusEngine`, shared with
the single-device driver.
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh

from repro.core.digraph import CompactDigraph
from repro.core.partition import (
    GraphPartition, GraphPartition2D, LocalShard, PartitionStats,
    extract_shard, graph_bytes, lpt_assign, lpt_assign_heap,
    partition_graph, partition_graph_2d, replicated_graph_bytes,
    vertex_slices)
from repro.core.planner import CensusPlan

__all__ = [
    "GraphPartition", "GraphPartition2D", "LocalShard", "PartitionStats",
    "default_mesh", "extract_shard", "graph_bytes", "lpt_assign",
    "lpt_assign_heap", "partition_graph", "partition_graph_2d",
    "replicated_graph_bytes", "shard_report", "triad_census_distributed",
    "triad_census_graph", "vertex_slices",
]


def default_mesh(num_devices: int | None = None) -> Mesh:
    """Flat mesh over all local devices, or the first ``num_devices`` of
    them (sub-meshes are how the shard-count invariance suites sweep
    1/2/4/8 shards on one host)."""
    devs = jax.devices()
    if num_devices is None:
        n = len(devs)
        return jax.make_mesh((n,), ("devices",),
                             axis_types=(jax.sharding.AxisType.Auto,))
    k = int(num_devices)
    if not 1 <= k <= len(devs):
        raise ValueError(
            f"num_devices must be in [1, {len(devs)}], got {k}")
    return Mesh(np.asarray(devs[:k]), ("devices",))


def shard_report(part: GraphPartition | GraphPartition2D,
                 stats=None) -> str:
    """Human-readable per-shard balance + residency table of a
    :func:`partition_graph` or :func:`partition_graph_2d` result (2D
    partitions label each row with its ``(pair_shard, vertex_slice)``
    tile coordinate and add a resident-entry replication line).

    Pass the run's :class:`~repro.core.engine.EngineStats` as ``stats``
    to append a fault-tolerance section when anything went wrong:
    retried windows, producer watchdog restarts, retired devices whose
    queues failed over to the survivors, and checkpoint-resumed windows.
    """
    text = part.stats.report()
    if stats is None:
        return text
    fired = (getattr(stats, "retries", 0)
             or getattr(stats, "failovers", 0)
             or getattr(stats, "watchdog_fires", 0)
             or getattr(stats, "retired_devices", [])
             or getattr(stats, "resumed_windows", 0))
    if not fired:
        return text
    lines = ["", "fault tolerance:"]
    if stats.retired_devices:
        lines.append(f"  retired devices : {sorted(stats.retired_devices)}"
                     " (queues drained by survivors)")
    lines.append(f"  retries         : {stats.retries}")
    lines.append(f"  failovers       : {stats.failovers}")
    lines.append(f"  watchdog fires  : {stats.watchdog_fires}")
    if stats.resumed_windows:
        lines.append(f"  resumed windows : {stats.resumed_windows}"
                     " (skipped via checkpoint)")
    return text + "\n".join(lines)


def triad_census_distributed(plan: CensusPlan, mesh: Mesh | None = None,
                             backend: str = "jnp") -> np.ndarray:
    """Exact 16-type census computed across all devices of ``mesh``
    (replicated graph, sharded items — prebuilt plans carry global
    coordinates; partition from the graph via :func:`triad_census_graph`
    instead)."""
    from repro.core.engine import CensusEngine
    if mesh is None:
        mesh = default_mesh()
    return CensusEngine(mesh=mesh, backend=backend).run_plan(plan)


def triad_census_graph(g: CompactDigraph, mesh: Mesh | None = None,
                       backend: str = "jnp", orient: str = "none",
                       max_items: int | None = None,
                       progress=None,
                       emit: str | None = None,
                       partition: bool = False,
                       partition_2d: tuple[int, int] | None = None
                       ) -> np.ndarray:
    """Convenience: plan + distribute + count in one call.

    ``max_items=None`` reproduces the historical one-dispatch schedule;
    an integer budget streams the plan in O(max_items) host memory.
    ``emit`` picks the work-item path (default ``"device"``: descriptor
    upload + in-kernel pair→item expansion; ``"host"``: packed-item
    upload).  ``partition=True`` shards the GRAPH across the mesh — each
    device holds only its pair shard's local subgraph and walks its own
    stream (:mod:`repro.core.partition`) with no inter-shard barrier.
    ``partition_2d=(P, V)`` upgrades the partitioned path to the 2D
    pair×vertex decomposition — ``P·V`` must equal the mesh's device
    count — sharding each pair shard's adjacency halo across ``V``
    vertex slices.  Bit-identical on every combination.
    """
    from repro.core.engine import CensusEngine
    if mesh is None:
        mesh = default_mesh()
    engine = CensusEngine(mesh=mesh, backend=backend,
                          partition=partition, partition_2d=partition_2d)
    return engine.run(g, max_items=max_items, orient=orient,
                      progress=progress, emit=emit)
