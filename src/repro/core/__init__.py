"""Parallel triad census — the paper's contribution, TPU-native in JAX.

Public API::

    g = from_edges(src, dst, n)                 # paper Fig 7 structure
    plan = build_plan(g, pad_to=num_devices)    # manhattan-collapse plan
    census = triad_census(plan)                 # single device
    census = triad_census_distributed(plan, mesh)   # sharded + psum

    # out-of-core: never materialize the O(W) plan — stream bounded chunks
    # (backend="jnp", the default, is the one that runs on a TPU)
    engine = CensusEngine(mesh)
    census = engine.run(g, max_items=10_000_000)
    engine.stats.summary()                      # chunks, peak plan bytes

    # resident sliding-window session: upload once, recount by edge delta
    session = engine.session(g)
    c0 = session.census()
    c1 = session.update(add_src, add_dst, del_src, del_dst)

    # partitioned: shard the GRAPH, not just the items — each device
    # holds only its pair shard's local subgraph (O(E_shard + halo))
    part = partition_graph(g, num_shards=8); print(shard_report(part))
    engine = CensusEngine(mesh, partition=True)
    census = engine.run(g)            # bit-identical, private shards
    session = engine.session(g)       # deltas dispatch owning shards only
    # each shard drains its own stream: no inter-shard barrier, so
    # walltime tracks the MEAN shard, not the max

    # 2D pair×vertex: keep the LPT pair axis, slice each shard's
    # witness range across V vertex slices — the adjacency halo shards
    # too, not just the pairs
    part = partition_graph_2d(g, mesh_shape=(4, 2))
    engine = CensusEngine(mesh, partition_2d=(4, 2))
    census = engine.run(g)            # still bit-identical
"""

from repro.core.digraph import (
    CompactDigraph, GraphDelta, apply_delta, canonical_pairs, from_edges,
    from_dense, from_pairs, to_dense)
from repro.core.planner import (
    CensusPlan, DescriptorWindow, PairSpace, base_for_pairs, build_plan,
    descriptor_window, emit_items, emit_items_for_pairs,
    iter_descriptor_windows, pack_items, pair_space, unpack_items)
from repro.core.plan_stream import (
    PlanChunk, PlanChunker, ProducerStalledError, ShardSchedule,
    ShardStreamPipeline, WindowBatcher, iter_plan_chunks)
from repro.core.faults import (
    Fault, FaultError, FaultInjector, FaultPlan, InjectedFault)
from repro.core.planner import PlanOverflowError
from repro.core.census import (
    triad_census, assemble_census, census_partials_desc_batch,
    check_backend)
from repro.core.engine import (
    CensusEngine, EMIT_MODES, EngineSession, EngineStats,
    PartitionedEngineSession, PartitionedEngineSession2D, StepCompileError)
from repro.core.incremental import (
    affected_pair_ids, subset_contribution, subset_descriptor_windows,
    verify_delta_closure)
from repro.core.pair_index import IndexCorruptionError, PairSpaceIndex
from repro.core.partition import (
    GraphPartition, GraphPartition2D, LocalShard, PartitionStats,
    extract_shard, lpt_assign, lpt_assign_heap, partition_graph,
    partition_graph_2d, replicated_graph_bytes, vertex_slices)
from repro.core.distributed import (
    shard_report, triad_census_distributed, triad_census_graph,
    default_mesh)
from repro.core.census_ref import (
    census_bruteforce, census_batagelj_mrvar, census_dict)
from repro.core.tricode import (
    TRIAD_NAMES, TRICODE_TO_CLASS, FOLD_64_TO_16, NUM_CLASSES)
from repro.core.generators import (
    scale_free_digraph, paper_workload, erdos_renyi_digraph, monitor_stream,
    PAPER_WORKLOADS)
from repro.core.temporal import (
    TriadMonitor, SECURITY_PATTERNS, SECURITY_PATTERN_INDICES)

__all__ = [
    "CompactDigraph", "GraphDelta", "apply_delta", "canonical_pairs",
    "from_edges", "from_dense", "from_pairs", "to_dense",
    "CensusPlan", "DescriptorWindow", "PairSpace", "base_for_pairs",
    "build_plan", "descriptor_window", "emit_items",
    "emit_items_for_pairs", "iter_descriptor_windows", "pack_items",
    "pair_space", "unpack_items",
    "PlanChunk", "PlanChunker", "ProducerStalledError", "ShardSchedule",
    "ShardStreamPipeline", "WindowBatcher", "iter_plan_chunks",
    "Fault", "FaultError", "FaultInjector", "FaultPlan", "InjectedFault",
    "PlanOverflowError",
    "CensusEngine", "EMIT_MODES", "EngineSession",
    "EngineStats", "PartitionedEngineSession",
    "PartitionedEngineSession2D", "StepCompileError",
    "affected_pair_ids", "subset_contribution",
    "subset_descriptor_windows", "verify_delta_closure",
    "IndexCorruptionError", "PairSpaceIndex",
    "GraphPartition", "GraphPartition2D", "LocalShard", "PartitionStats",
    "extract_shard", "lpt_assign", "lpt_assign_heap", "partition_graph",
    "partition_graph_2d", "replicated_graph_bytes", "vertex_slices",
    "shard_report",
    "triad_census", "assemble_census", "census_partials_desc_batch",
    "check_backend",
    "triad_census_distributed", "triad_census_graph", "default_mesh",
    "census_bruteforce", "census_batagelj_mrvar", "census_dict",
    "TRIAD_NAMES", "TRICODE_TO_CLASS", "FOLD_64_TO_16", "NUM_CLASSES",
    "scale_free_digraph", "paper_workload", "erdos_renyi_digraph",
    "monitor_stream",
    "PAPER_WORKLOADS", "TriadMonitor", "SECURITY_PATTERNS",
    "SECURITY_PATTERN_INDICES",
]
