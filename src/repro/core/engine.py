"""Streaming census engine: unified multi-chunk execution, all backends.

:class:`CensusEngine` is the single owner of device dispatch for the triad
census.  It subsumes what used to be two parallel drivers (the
single-device path in :mod:`repro.core.census` and the sharded path in
:mod:`repro.core.distributed` — both are now thin wrappers over it) and
adds the out-of-core mode that the monolithic drivers could not express:

* **Monolithic** (``max_items=None``): one plan, one dispatch — exactly
  the historical behavior, for plans that fit.
* **Streamed** (``max_items=N``): the plan is never materialized whole.
  :class:`repro.core.plan_stream.PlanChunker` slices the pre-prune item
  space into bounded chunks; the engine uploads the chunk-invariant graph
  and pair arrays once, runs one jitted fixed-shape partials step per
  chunk (every chunk is padded to the same ``chunk_shape``, so the step
  compiles exactly once), overlaps the host-side generation + upload of
  chunk k+1 with the device compute of chunk k, and accumulates the
  ``hist64``/``inter`` partials in int64 on the host.  Peak plan memory is O(max_items) instead of O(W).

Orthogonally, ``emit`` picks how chunks reach the device:

* ``emit="device"`` (default): the host ships each chunk as ONE packed
  buffer of O(pairs) descriptors + anchors
  (:class:`repro.core.planner.DescriptorWindow`); the device step maps
  every flat item index back to its pair via an anchored constant-depth
  lower-bound search, derives slot/side arithmetically against the
  resident CSR, and applies the pruning predicate in-kernel — no item is
  ever materialized on the host, and per-chunk host→device plan traffic
  drops from O(max_items) to O(pairs-per-chunk)
  (``EngineStats.plan_upload_bytes``).
* ``emit="host"``: the original path — emit, prune, pack and upload the
  O(W) item words in numpy.  Kept as the oracle (bit-identical censuses
  by construction: every plan-pruned item is provably a zero
  contribution of the classification masks) and for prebuilt plans.

Partials are perfectly mergeable across chunks (integer histogram sums and
additive closed-form bases), so the streamed census is bit-identical to
the monolithic dispatch for every backend (``jnp``, ``pallas``,
``pallas-fused``), both orient modes, and any chunk size — enforced by
``tests/test_streaming.py``.

For *repeated* censuses of an evolving graph (the temporal monitor's
sliding windows), :meth:`CensusEngine.session` opens a resident-graph
:class:`EngineSession`: the CSR + pair arrays live on device in
fixed-capacity buffers, every dispatch reuses one jitted fixed-shape chunk
step (search depth pinned to ``ceil(log2 n)`` so no graph revision ever
recompiles it), and edge deltas are applied incrementally — only the
*affected pairs* (endpoint row changed) are re-counted, old partials
subtracted and new ones added, bit-identical to a from-scratch census
(:mod:`repro.core.incremental`).

Orthogonally to all of the above, ``partition=True`` shards the GRAPH
instead of replicating it (:mod:`repro.core.partition`): the pair space
is LPT-split into one private shard per mesh device, each device holds
only its shard's order-preservingly relabeled local subgraph
(O(E_shard + halo) resident bytes instead of O(E)) and walks its own
descriptor/item stream as single-device dispatches against its own
committed shard buffers, with no inter-shard barrier: the host merges
the per-window partials in int64.  Full runs and
:class:`PartitionedEngineSession` share that discipline; a session's
delta updates touch only the shards owning affected pairs.
"""

from __future__ import annotations

import functools
import itertools
import json
import os
import time
import zlib
from collections import deque
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.census import (
    assemble_census, assemble_counts, census_partials_desc_batch,
    check_backend, desc_partials_fn, partials_fn)
from repro.core.digraph import CompactDigraph, GraphDelta, apply_delta
from repro.core.faults import FaultError, FaultPlan, poison_result
from repro.core.incremental import (
    affected_pair_ids, combine, contribution_counts,
    subset_descriptor_windows)
from repro.core.pair_index import PairSpaceIndex
from repro.core.partition import (
    extract_shard, partition_graph, partition_graph_2d,
    range_postprune_pair_counts, slice_pair_terms,
    replicated_graph_bytes,
    stacked_device_arrays)
from repro.core.planner import (
    DESC_BYTES, DESC_SEARCH_ITERS, CensusPlan, PlanOverflowError,
    base_for_pairs,
    build_plan, emit_items, emit_items_for_pairs, global_bases,
    iter_descriptor_windows, max_pairs_per_window, num_desc_anchors,
    pad_and_pack, pair_space, postprune_pair_counts)
from repro.core.plan_stream import (
    PlanChunker, ShardSchedule, ShardStreamPipeline, WindowBatcher)
from repro.core.spans import span

#: work-item emission modes: ``device`` streams O(pairs) descriptors and
#: expands pairs→items in-kernel (the default); ``host`` materializes and
#: uploads every packed item in numpy (the original path, kept as the
#: oracle and for prebuilt monolithic plans)
EMIT_MODES = ("device", "host")

#: per-shard produced-window queue depth of the async host pipeline
#: (2 == double-buffering: one window in flight, one pre-built behind it)
PIPELINE_DEPTH = 2

#: default cap K on the descriptor windows one async megastep dispatch
#: consumes (``lax.scan`` over the stacked window batch): Python dispatch
#: cost is paid once per up-to-K windows; the live batch size adapts
#: between 1 and this cap from stall/backlog feedback
#: (:class:`repro.core.plan_stream.WindowBatcher`)
MAX_WINDOWS_PER_DISPATCH = 8


def _chunk_step_impl(indptr, packed, pair_u, pair_v, pair_code,
                     item_sp, item_pv, mesh, search_iters, backend):
    """One fixed-shape partials dispatch: ``(hist64, inter)`` int32.

    ``mesh=None`` runs single-device; otherwise the items are shard_mapped
    over every mesh axis with replicated graph/pair arrays and a final
    psum — the paper's privatized census vectors, one collective at the
    end.
    """
    partials = partials_fn(backend, search_iters)
    if mesh is None:
        return partials(indptr, packed, pair_u, pair_v, pair_code,
                        item_sp, item_pv)

    axes = mesh.axis_names

    def shard_fn(ip, pk, pu, pv, pc, wsp, wpv):
        hist64, inter = partials(ip, pk, pu, pv, pc, wsp, wpv)
        return jax.lax.psum(hist64, axes), jax.lax.psum(inter, axes)

    item_spec = P(axes)       # work items sharded over every mesh axis
    rep = P()                 # graph + pair arrays replicated
    fn = shard_map(
        shard_fn, mesh=mesh,
        in_specs=(rep, rep, rep, rep, rep, item_spec, item_spec),
        out_specs=(rep, rep),
        # pallas_call has no replication rule; keep the check on the
        # pure-XLA path where it still can catch a missing psum
        check_vma=(backend == "jnp"))
    return fn(indptr, packed, pair_u, pair_v, pair_code, item_sp, item_pv)


_STATIC = ("mesh", "search_iters", "backend")
_chunk_step = functools.partial(
    jax.jit, static_argnames=_STATIC)(_chunk_step_impl)


def _platform(mesh=None) -> str:
    """The platform the work runs on: the mesh's device platform when
    sharded, the default backend when single-device."""
    return (mesh.devices.flat[0].platform if mesh is not None
            else jax.default_backend())


def _desc_step_impl(indptr, packed, pair_u, pair_v, pair_code,
                    desc_words, idx, mesh, search_iters, desc_iters,
                    backend, orient, prune_self):
    """One fixed-shape device-emission dispatch: ``(hist64, inter3)``.

    ``desc_words`` is the window's single packed int32 buffer
    (:meth:`repro.core.planner.DescriptorWindow.device_words` — one
    upload per chunk instead of four); ``idx`` is the resident flat
    item-index array (created on device once per run/session, sharded
    over the mesh when distributed) — everything else is replicated.  No
    buffers are donated: the per-chunk upload is the O(pairs) descriptor
    buffer, small enough that HBM aliasing buys nothing.
    """
    num_anchors = num_desc_anchors(idx.shape[0])
    num_descs = (desc_words.shape[0] - 1 - num_anchors) // 3
    partials = desc_partials_fn(backend, search_iters, desc_iters,
                                orient, prune_self)

    def run(ip, pk, pu, pv, pc, words, ix):
        nv = words[:1]
        dp = words[1:1 + num_descs]
        dc = words[1 + num_descs:1 + 2 * num_descs]
        dw = words[1 + 2 * num_descs:1 + 3 * num_descs]
        an = words[1 + 3 * num_descs:]
        return partials(ip, pk, pu, pv, pc, dp, dc, dw, an, nv, ix)

    if mesh is None:
        return run(indptr, packed, pair_u, pair_v, pair_code,
                   desc_words, idx)

    axes = mesh.axis_names

    def shard_fn(*args):
        hist64, inter = run(*args)
        return jax.lax.psum(hist64, axes), jax.lax.psum(inter, axes)

    rep = P()                 # graph + pair + descriptor arrays replicated
    fn = shard_map(
        shard_fn, mesh=mesh,
        in_specs=(rep, rep, rep, rep, rep, rep,
                  P(axes)),   # only the item-index space is sharded
        out_specs=(rep, rep),
        check_vma=(backend == "jnp"))
    return fn(indptr, packed, pair_u, pair_v, pair_code, desc_words, idx)


_desc_step = functools.partial(
    jax.jit, static_argnames=(
        "mesh", "search_iters", "desc_iters", "backend", "orient",
        "prune_self"))(_desc_step_impl)


def _desc_megastep_impl(indptr, packed, pair_u, pair_v, pair_code,
                        words_batch, idx, search_iters, desc_iters,
                        backend, orient, prune_self):
    """K-window async megastep: one single-device dispatch scans a
    fixed-shape ``(K, words)`` batch of stacked descriptor windows
    (:func:`repro.core.census.census_partials_desc_batch`) and returns
    the per-window partials stacked — ``(hist64s (K, 64),
    inter3s (K, 3))`` int32, merged on the host in int64.  The batch
    shape is the ``max_windows_per_dispatch`` cap regardless of how many
    real windows landed (padding rows mask to exact zeros), so the step
    compiles once per device no matter how the adaptive K schedule
    moves."""
    return census_partials_desc_batch(
        indptr, packed, pair_u, pair_v, pair_code, words_batch, idx,
        search_iters, desc_iters, orient, prune_self, backend=backend)


_desc_megastep = functools.partial(
    jax.jit, static_argnames=(
        "search_iters", "desc_iters", "backend", "orient",
        "prune_self"))(_desc_megastep_impl)


def _jit_cache_size(step) -> int:
    """Compile counter of a jitted step (the ``step_compiles`` stat)."""
    return step._cache_size()


class StepCompileError(RuntimeError):
    """A census step failed to trace, lower or compile.

    The compiler refuses the same program on every attempt and every
    device, so this is raised at once: never retried, failed over, or
    carried forward as a degraded monitor window.  The message names the
    step, the backend and the argument shapes; the compiler's own
    exception is the ``__cause__``."""


def _describe_args(args) -> str:
    return ", ".join(f"{a.dtype}{list(a.shape)}" if hasattr(a, "shape")
                     else repr(a) for a in args)


def _launch(step, backend: str, *args):
    """Call the jitted ``step`` on ``args``.  A failure is classified by
    lowering and compiling the same call again: if that fails too, the
    step cannot be built and :class:`StepCompileError` is raised; if it
    succeeds, the failure was a device runtime error and propagates
    unchanged to the caller's retry discipline.  The re-lowering runs
    only on the failure path and reuses the compile the call made."""
    try:
        return step(*args)
    except Exception as exc:
        try:
            step.lower(*args).compile()
        except Exception:
            raise StepCompileError(
                f"{step.__name__} failed to compile for backend "
                f"{backend!r} with arguments ({_describe_args(args)})"
            ) from exc
        raise


#: bytes per packed work item (two int32 words)
ITEM_BYTES = 8


def _desc_capacity(chunk_shape: int, need: int) -> int:
    """Session descriptor capacity for a ``chunk_shape``-lane dispatch:
    2x headroom over the densest full-stream window (sparser
    affected-pair subsets span more pairs per item), capped at the
    structural bound of ``chunk_shape/2 + 1`` pairs per window — every
    pair spans >= 2 pre-prune items.  Overflowing windows shrink their
    item span instead (:func:`repro.core.planner
    .iter_descriptor_windows`), so this is never a recompile vector."""
    return min(chunk_shape // 2 + 1, max(64, 2 * need))


def _guard_chunk_shape(chunk_shape: int) -> int:
    if chunk_shape >= 2**31:
        raise PlanOverflowError(
            f"chunk_shape {chunk_shape} exceeds int32 item indexing and "
            f"would silently wrap the per-window int32 accumulator "
            f"lanes; pass a smaller max_items budget (< 2**31)")
    return chunk_shape


def _validate_partials(hist, inter) -> None:
    """Landing-time sanity check on fetched device partials: census
    histogram and intersection lanes are counts and can never go
    negative.  A corrupted (poisoned) result fails here, turning silent
    wrong answers into a retryable :class:`FaultError`."""
    if (hist < 0).any() or (inter < 0).any():
        raise FaultError(
            "device returned corrupted census partials (negative "
            "counts); retrying the window")


def _land_desc_partials(fut, hist_acc: np.ndarray, inter_acc: np.ndarray,
                        chunk_items: list) -> int:
    """Accumulate one descriptor-step result in place — hist64 into
    ``hist_acc``, the two intersection lanes into ``inter_acc`` — and
    record/return lane 2, the chunk's device-counted valid items (the
    one place that knows the ``inter3`` layout)."""
    hist_acc += np.asarray(fut[0], dtype=np.int64)
    inter3 = np.asarray(fut[1], dtype=np.int64)
    inter_acc += inter3[:2]
    num = int(inter3[2])
    chunk_items.append(num)
    return num


@dataclass
class EngineStats:
    """Execution stats of the last :class:`CensusEngine` run.

    ``peak_plan_bytes`` is the per-dispatch item-lane footprint at packed
    -item width (``ITEM_BYTES * chunk_shape`` — the streaming ceiling the
    ``max_items`` knob tunes, comparable across emit modes; under
    ``emit="device"`` nothing item-shaped is HOST-resident, and the bytes
    actually uploaded per chunk are ``plan_upload_bytes``);
    ``monolithic_plan_bytes`` is what a single dispatch of the same work
    would have shipped.  ``step_compiles`` counts fresh compilations of
    the per-chunk step during the run — 0 or 1 for a streamed run, never
    one per chunk (fixed chunk shapes).
    """

    backend: str
    ndev: int
    orient: str
    streamed: bool
    max_items: int | None
    chunks: int
    chunk_shape: int           #: padded items per dispatch
    items: int                 #: total valid work items processed
    chunk_items: list[int] = field(default_factory=list)
    #: valid lanes dispatched before the prune mask (the pre-prune item
    #: space, summed over shards when partitioned; under host emission
    #: what the host pruned before upload): ``items`` over it is the
    #: share of lanes kept, and ``chunks * chunk_shape`` less it the
    #: padding of an un-partitioned run.  0 where not recorded
    preprune_items: int = 0
    #: dependent search rounds per lane in the classify step
    #: (``ceil(log2(max_degree + 1))``).  0 where not recorded
    search_iters: int = 0
    peak_plan_bytes: int = 0
    monolithic_plan_bytes: int = 0
    step_compiles: int = 0
    #: session-mode extras: valid items a full recompute of the current
    #: graph would process (== ``items`` for non-incremental runs), and
    #: the number of affected pairs an incremental update re-counted
    full_items: int = 0
    affected_pairs: int = 0
    #: work-item emission mode of the run ("host" or "device")
    emit: str = "host"
    #: fixed per-dispatch descriptor-array length (device emission only)
    desc_shape: int = 0
    #: *physical per-device* host→device plan bytes shipped per dispatch:
    #: the packed item words under host emission (divided across the mesh
    #: when the item arrays are sharded), the descriptor window (+ 4-byte
    #: valid count) under device emission (replicated on every device
    #: un-partitioned, one private window per device partitioned) — the
    #: traffic the emit knob trades
    plan_upload_bytes: int = 0
    #: jitted-step compilations forced by session capacity growth (graph
    #: buffers regrown past their padded device shapes), counted apart
    #: from ``step_compiles`` so the compile-once contract stays auditable
    capacity_recompiles: int = 0
    #: True when the run sharded the GRAPH (each device held only its
    #: pair shard's local subgraph), not just the work items
    partitioned: bool = False
    #: (pair_shards, vertex_slices) of a 2D-partitioned run; None when
    #: un-partitioned or 1D (device d serves tile (d // V, d % V))
    partition_shape: tuple | None = None
    #: per-shard post-prune work items owned (partitioned runs: the LPT
    #: balance record; per-update dispatch record for sessions)
    shard_items: list[int] = field(default_factory=list)
    #: per-device resident graph + pair bytes: the max shard footprint
    #: when partitioned, the full replicated footprint otherwise
    graph_resident_bytes: int = 0
    #: what replication would have made ``graph_resident_bytes`` — equal
    #: to it on un-partitioned runs, ≥ it (the byte-reduction numerator)
    #: on partitioned ones
    graph_replicated_bytes: int = 0
    #: per-shard REAL dispatch steps (windows carrying pre-prune items)
    shard_steps: list[int] = field(default_factory=list)
    #: async consumer stalls: moments every produced-window queue was
    #: empty and the host had to wait on a producer (pipeline-bound)
    stall_steps: int = 0
    #: per-shard produced-window queue depth of the async host pipeline
    pipeline_depth: int = 0
    #: TOTAL host→device plan bytes attributed to REAL windows over the
    #: whole run, summed across devices and dispatches
    #: (``plan_upload_bytes`` is the per-window unit).  Padding that was
    #: physically shipped but masked — megabatch rows past the real
    #: window count — is reported separately as ``plan_pad_bytes_total``
    #: instead of silently inflating the per-shard numbers
    plan_upload_bytes_total: int = 0
    #: masked-padding plan bytes physically shipped (see above); the
    #: run's physical upload is the sum of both totals
    plan_pad_bytes_total: int = 0
    #: device dispatches issued for the run's windows: one megastep
    #: dispatch consumes up to ``dispatch_batch_limit`` windows
    dispatches_total: int = 0
    #: real windows per dispatch, mean and max over the run — the
    #: dispatch-amortization record (adapts toward
    #: ``dispatch_batch_limit``)
    windows_per_dispatch_mean: float = 0.0
    windows_per_dispatch_max: int = 0
    #: the megabatch cap K in effect (``max_windows_per_dispatch``;
    #: 1 == no window batching, 0 == not a partitioned run)
    dispatch_batch_limit: int = 0
    #: fault-tolerance record: window dispatches re-attempted after a
    #: transient failure (injected or real), devices retired to the
    #: survivors, watchdog-restarted producers, and the retired device
    #: ids — all zero/empty on a fault-free run
    retries: int = 0
    failovers: int = 0
    watchdog_fires: int = 0
    retired_devices: list = field(default_factory=list)
    #: windows restored from a checkpoint journal instead of re-executed
    resumed_windows: int = 0
    #: host walltime of the run, split by phase (each the sum of its
    #: :func:`repro.core.spans.span` durations): pair-space maintenance
    #: (full ``pair_space`` rebuild and closed-form bases, or the
    #: delta-incremental index edit + affected-pair discovery when
    #: ``indexed``), the ``apply_delta`` CSR/pair-code diff, host-side
    #: work emission (item materialization / descriptor-window
    #: construction, device wait excluded; summed over the partitioned
    #: run's producer threads), LPT partitioning + shard extraction, and
    #: landing (the host blocked on a device result, then its int64 merge)
    host_pair_seconds: float = 0.0
    host_merge_seconds: float = 0.0
    host_emit_seconds: float = 0.0
    host_partition_seconds: float = 0.0
    host_land_seconds: float = 0.0
    #: True when the run's pair space came from the session's persistent
    #: :class:`~repro.core.pair_index.PairSpaceIndex` instead of a full
    #: O(P) rebuild
    indexed: bool = False

    @property
    def plan_host_seconds(self) -> float:
        """Total host planning walltime (sum of the three phase buckets)."""
        return (self.host_pair_seconds + self.host_merge_seconds
                + self.host_emit_seconds)

    @property
    def shard_max_over_mean(self) -> float:
        """Shard work imbalance (1.0 == perfectly balanced shards)."""
        if not self.shard_items or not sum(self.shard_items):
            return 1.0
        mean = sum(self.shard_items) / len(self.shard_items)
        return max(self.shard_items) / mean

    @property
    def chunk_max_over_mean(self) -> float:
        """Streamed-schedule imbalance (1.0 == perfectly even chunks)."""
        if not self.chunk_items or not sum(self.chunk_items):
            return 1.0
        mean = sum(self.chunk_items) / len(self.chunk_items)
        return max(self.chunk_items) / mean

    def summary(self) -> str:
        mode = (f"streamed max_items={self.max_items}" if self.streamed
                else "monolithic")
        part = ""
        if self.partitioned:
            mesh2d = (f" mesh={self.partition_shape[0]}"
                      f"x{self.partition_shape[1]}"
                      if self.partition_shape else "")
            part = (f" partitioned{mesh2d} "
                    f"shards={len(self.shard_items)} "
                    f"shard_max_over_mean={self.shard_max_over_mean:.3f} "
                    f"graph_bytes={self.graph_resident_bytes}"
                    f"/{self.graph_replicated_bytes}"
                    f" stalls={self.stall_steps} "
                    f"depth={self.pipeline_depth} "
                    f"dispatches={self.dispatches_total} "
                    f"win/disp={self.windows_per_dispatch_mean:.2f}"
                    f"/{self.windows_per_dispatch_max}"
                    f"(cap {self.dispatch_batch_limit})")
        if (self.retries or self.failovers or self.watchdog_fires
                or self.resumed_windows):
            part += (f" faults[retries={self.retries} "
                     f"failovers={self.failovers} "
                     f"retired={self.retired_devices} "
                     f"watchdog_fires={self.watchdog_fires} "
                     f"resumed={self.resumed_windows}]")
        if self.plan_host_seconds:
            part += (f" host[pair={self.host_pair_seconds * 1e3:.2f}ms"
                     f" merge={self.host_merge_seconds * 1e3:.2f}ms"
                     f" emit={self.host_emit_seconds * 1e3:.2f}ms"
                     f" partition={self.host_partition_seconds * 1e3:.2f}ms"
                     f" land={self.host_land_seconds * 1e3:.2f}ms"
                     f"{' indexed' if self.indexed else ''}]")
        return (f"{self.backend} [{mode} emit={self.emit}] "
                f"chunks={self.chunks} items={self.items} "
                f"peak_plan_bytes={self.peak_plan_bytes} "
                f"(monolithic {self.monolithic_plan_bytes}) "
                f"plan_upload_bytes={self.plan_upload_bytes} "
                f"chunk_max_over_mean={self.chunk_max_over_mean:.3f} "
                f"step_compiles={self.step_compiles}" + part)


class _CheckpointJournal:
    """JSONL window journal for :meth:`CensusEngine.run(checkpoint=)`.

    Line 0 is the run fingerprint (graph + schedule identity); every
    further line records one landed dispatch: the shard, the explicit
    window ids it covered, the dispatch's summed int64 partials, and
    the per-window valid item counts.  Landings are flushed
    line-by-line, so a run killed mid-stream leaves a valid prefix.

    Resume correctness rests on the property the async machinery already
    proved: the host merge is an integer sum over independent windows,
    so restoring the journaled partials and *skipping exactly the
    journaled window ids* reproduces the uninterrupted census
    bit-identically — regardless of the order landings happened to
    reach the journal (retried windows can land out of per-shard
    order, hence explicit ids instead of prefix counts).
    """

    VERSION = 1

    def __init__(self, path: str, fingerprint: dict, ndev: int):
        self.path = path
        self.fingerprint = fingerprint
        #: per-shard set of yielded-window ids already landed
        self.done: list = [set() for _ in range(ndev)]
        self.hist = np.zeros(64, np.int64)
        self.inter = np.zeros(2, np.int64)
        self.chunk_items: list = []
        self.shard_items = [0] * ndev
        self.windows = 0
        self._f = None
        if os.path.exists(path):
            self._load(ndev)
        self._f = open(path, "a" if self.windows or self._header_ok
                       else "w")
        if not self._header_ok:
            self._f.write(json.dumps({"v": self.VERSION,
                                      **fingerprint}) + "\n")
            self._f.flush()

    _header_ok = False

    @staticmethod
    def graph_fingerprint(space, *, emit: str, ndev: int,
                          max_items) -> dict:
        return {
            "n": int(space.n), "pairs": int(space.num_pairs),
            "preprune": int(space.num_items_preprune),
            "packed_crc": int(zlib.crc32(
                np.ascontiguousarray(space.packed).tobytes())),
            "orient": space.orient, "prune_self": bool(space.prune_self),
            "emit": emit, "ndev": int(ndev),
            "max_items": None if max_items is None else int(max_items),
        }

    def _load(self, ndev: int) -> None:
        with open(self.path) as f:
            lines = [ln for ln in f.read().splitlines() if ln.strip()]
        if not lines:
            return
        head = json.loads(lines[0])
        want = {"v": self.VERSION, **self.fingerprint}
        if head != want:
            raise FaultError(
                f"checkpoint {self.path!r} was written by a different "
                f"run (header {head} != {want}); delete it or pass a "
                f"fresh path")
        self._header_ok = True
        for ln in lines[1:]:
            try:
                rec = json.loads(ln)
            except json.JSONDecodeError:
                break                      # torn final line from a kill
            s = int(rec["s"])
            ids = {int(x) for x in rec["ids"]}
            if ids & self.done[s]:
                continue                   # duplicate landing — ignore
            self.done[s] |= ids
            self.hist += np.asarray(rec["hist"], dtype=np.int64)
            self.inter += np.asarray(rec["inter"], dtype=np.int64)
            self.chunk_items.extend(int(x) for x in rec["items"])
            self.shard_items[s] += int(sum(rec["items"]))
            self.windows += len(ids)

    def record(self, s: int, ids, hist, inter, items) -> None:
        self._f.write(json.dumps({
            "s": int(s), "ids": [int(x) for x in ids],
            "hist": [int(x) for x in hist],
            "inter": [int(x) for x in inter],
            "items": [int(x) for x in items]}) + "\n")
        self._f.flush()

    def close(self) -> None:
        if self._f is not None:
            self._f.close()
            self._f = None


class CensusEngine:
    """Owns mesh + backend dispatch for monolithic and streamed censuses.

    ``mesh=None`` executes on the default device; a :class:`Mesh` shards
    every chunk's items across all mesh axes.  ``partition=True``
    additionally shards the GRAPH: the pair space is LPT-split into one
    private shard per mesh device (:mod:`repro.core.partition`), each
    device holds only its shard's relabeled local subgraph and walks its
    own descriptor/item stream through single-device compile-once steps,
    and the host merges the private histograms in int64 — per-device
    resident graph bytes drop from O(E) to O(E_shard + halo), with
    bit-identical censuses.  Replication (the default) remains right for
    graphs small enough to fit every device anyway — partitioning spends
    host-side extraction work to shrink device residency.  After each
    ``run`` / ``run_plan`` the execution record is available as
    :attr:`stats`.
    """

    def __init__(self, mesh: Mesh | None = None, backend: str = "jnp",
                 emit: str = "device", partition: bool = False,
                 pipeline_depth: int = PIPELINE_DEPTH,
                 max_windows_per_dispatch: int =
                 MAX_WINDOWS_PER_DISPATCH,
                 partition_2d: tuple | None = None,
                 max_retries: int = 2, retry_backoff: float = 0.01,
                 watchdog_timeout: float | None = None,
                 faults: FaultPlan | None = None):
        check_backend(backend, _platform(mesh))
        if emit not in EMIT_MODES:
            raise ValueError(
                f"unknown emit mode {emit!r}; one of {EMIT_MODES}")
        if partition_2d is not None:
            partition = True          # a 2D mesh factorization implies it
            partition_2d = (int(partition_2d[0]), int(partition_2d[1]))
            if partition_2d[0] < 1 or partition_2d[1] < 1:
                raise ValueError(
                    f"partition_2d must be >= (1, 1), got {partition_2d}")
        if partition:
            if mesh is None:
                raise ValueError("partition=True requires a mesh")
            if mesh.devices.ndim != 1:
                raise ValueError(
                    "partitioned execution shards over a 1-D mesh; got "
                    f"shape {mesh.devices.shape}")
            ndev = int(np.prod(mesh.devices.shape))
            if (partition_2d is not None
                    and partition_2d[0] * partition_2d[1] != ndev):
                raise ValueError(
                    f"partition_2d {partition_2d} needs "
                    f"{partition_2d[0] * partition_2d[1]} devices; the "
                    f"mesh has {ndev}")
        if pipeline_depth < 1:
            raise ValueError(
                f"pipeline_depth must be >= 1, got {pipeline_depth}")
        if max_windows_per_dispatch < 1:
            raise ValueError(
                "max_windows_per_dispatch must be >= 1, got "
                f"{max_windows_per_dispatch}")
        if max_retries < 0:
            raise ValueError(
                f"max_retries must be >= 0, got {max_retries}")
        if retry_backoff < 0:
            raise ValueError(
                f"retry_backoff must be >= 0, got {retry_backoff}")
        if watchdog_timeout is not None and watchdog_timeout <= 0:
            raise ValueError(
                f"watchdog_timeout must be > 0, got {watchdog_timeout}")
        self.mesh = mesh
        self.backend = backend
        self.emit = emit
        self.partition = partition
        #: (pair_shards, vertex_slices) factorization of the 1-D mesh;
        #: device d serves tile (d // V, d % V).  None == 1D partition.
        self.partition_2d = partition_2d
        #: per-shard produced-window queue depth of the async host
        #: pipeline (:class:`repro.core.plan_stream.ShardStreamPipeline`)
        self.pipeline_depth = int(pipeline_depth)
        #: cap K on the windows one async megastep dispatch may consume
        self.max_windows_per_dispatch = int(max_windows_per_dispatch)
        #: fault-tolerance knobs: per-window re-dispatch budget with
        #: exponential ``retry_backoff`` sleeps, producer-stall watchdog
        #: (None == off), and an optional deterministic
        #: :class:`repro.core.faults.FaultPlan` to inject against
        self.max_retries = int(max_retries)
        self.retry_backoff = float(retry_backoff)
        self.watchdog_timeout = (None if watchdog_timeout is None
                                 else float(watchdog_timeout))
        self.faults = faults
        self.stats: EngineStats | None = None
        #: ids of the censuses (runs, session censuses and updates) this
        #: engine starts: every host span of one census carries its id
        self._census_ids = itertools.count(1)

    @property
    def ndev(self) -> int:
        return 1 if self.mesh is None else int(
            np.prod(self.mesh.devices.shape))

    # ------------------------------------------------------------- helpers
    def _shardings(self):
        """(replicated, item-sharded) NamedShardings, or (None, None)."""
        if self.mesh is None:
            return None, None
        return (NamedSharding(self.mesh, P()),
                NamedSharding(self.mesh, P(self.mesh.axis_names)))

    def _put(self, a, sharding):
        arr = jnp.asarray(a)
        return arr if sharding is None else jax.device_put(arr, sharding)

    def _mono_stats(self, plan: CensusPlan,
                    max_items: int | None = None) -> EngineStats:
        wp = int(plan.item_sp.shape[0])
        gbytes = 4 * (plan.indptr.shape[0] + plan.packed.shape[0]
                      + 3 * plan.num_pairs)
        return EngineStats(
            backend=self.backend, ndev=self.ndev, orient=plan.orient,
            streamed=False, max_items=max_items,
            chunks=1 if plan.num_items else 0, chunk_shape=wp,
            items=plan.num_items,
            chunk_items=[plan.num_items] if plan.num_items else [],
            peak_plan_bytes=ITEM_BYTES * wp,
            monolithic_plan_bytes=ITEM_BYTES * wp,
            emit="host",
            # items are sharded over the mesh: physical per-device bytes
            plan_upload_bytes=ITEM_BYTES * wp // self.ndev,
            graph_resident_bytes=gbytes, graph_replicated_bytes=gbytes)

    # ------------------------------------------------------------- running
    def run_plan(self, plan: CensusPlan) -> np.ndarray:
        """Exact 16-type census from a prebuilt (monolithic) plan."""
        if self.partition:
            raise ValueError(
                "prebuilt plans are replicated; partitioned execution "
                "plans from the graph — use run()/session()")
        wp = int(plan.item_sp.shape[0])
        if self.mesh is not None and wp % self.ndev != 0:
            raise ValueError(
                f"plan padded to {wp} items, not a multiple of "
                f"{self.ndev} devices; build with pad_to=num_devices")
        self.stats = self._mono_stats(plan)
        if plan.num_pairs == 0 or plan.num_items == 0:
            # zero-work plans (incl. pairs whose items were all pruned)
            # resolve entirely from the host closed forms — the device is
            # never dispatched on zero-length item arrays
            return assemble_census(plan, np.zeros(64, np.int64),
                                   np.zeros(2, np.int64))
        rep, item_sh = self._shardings()
        step = _chunk_step
        cache0 = _jit_cache_size(step)
        hist64, inter = _launch(
            step, self.backend,
            self._put(plan.indptr, rep), self._put(plan.packed, rep),
            self._put(plan.pair_u, rep), self._put(plan.pair_v, rep),
            self._put(plan.pair_code, rep),
            self._put(plan.item_sp, item_sh),
            self._put(plan.item_pv, item_sh),
            self.mesh, plan.search_iters, self.backend)
        census = assemble_census(plan, np.asarray(hist64),
                                 np.asarray(inter))
        self.stats.step_compiles = _jit_cache_size(step) - cache0
        return census

    def run(self, g: CompactDigraph, *, max_items: int | None = None,
            orient: str = "none", prune_self: bool = True,
            progress=None, emit: str | None = None, part=None,
            checkpoint: str | None = None) -> np.ndarray:
        """Plan + count ``g`` end to end.

        ``max_items=None`` covers the whole item space in one dispatch;
        an integer budget streams bounded chunks instead (O(max_items)).
        ``emit`` (default: the engine's mode) picks the work-item path:
        ``"device"`` ships O(pairs) descriptors per chunk and expands
        pairs→items in-kernel; ``"host"`` materializes, packs and uploads
        every O(W) item in numpy (the oracle).  Both are bit-identical on
        every backend and orient mode.
        ``progress(chunk_index, num_chunks, chunk_valid_items)`` is called
        per chunk — at dispatch under host emission, when the chunk's
        device-counted valid items land under device emission.

        Partitioned engines additionally accept ``part`` — a prebuilt
        :class:`repro.core.partition.GraphPartition` over
        ``num_shards == ndev`` shards, overriding the internal LPT
        (``orient``/``prune_self`` are then taken from its space).

        ``checkpoint`` (partitioned runs only) journals every
        landed window to the given JSONL path; a later ``run`` (or
        :meth:`resume`) against an existing journal restores the
        journaled partials, skips the completed windows, and reproduces
        the uninterrupted census bit-identically.
        """
        emit = self.emit if emit is None else emit
        if emit not in EMIT_MODES:
            raise ValueError(
                f"unknown emit mode {emit!r}; one of {EMIT_MODES}")
        if part is not None and not self.partition:
            raise ValueError(
                "a prebuilt partition requires partition=True")
        if checkpoint is not None and not self.partition:
            raise ValueError(
                "checkpoint/resume is supported on partitioned runs "
                "(partition=True)")
        census = next(self._census_ids)
        if self.partition:
            return self._run_partitioned(g, max_items=max_items,
                                         orient=orient,
                                         prune_self=prune_self,
                                         progress=progress, emit=emit,
                                         part=part,
                                         checkpoint=checkpoint,
                                         census=census)
        if emit == "host" and max_items is None:
            plan = build_plan(g, pad_to=self.ndev, orient=orient,
                              prune_self=prune_self)
            return self.run_plan(plan)
        with span("census.plan", census=census) as plan:
            chunker = PlanChunker(g, max_items, orient=orient,
                                  pad_to=self.ndev, prune_self=prune_self)
        if emit == "device":
            return self._run_stream_desc(chunker, progress,
                                         max_items=max_items,
                                         census=census,
                                         plan_seconds=plan.seconds)
        return self._run_stream(chunker, progress, census=census,
                                plan_seconds=plan.seconds)

    def resume(self, g: CompactDigraph, checkpoint: str,
               **kwargs) -> np.ndarray:
        """Resume a checkpointed partitioned run: requires the
        journal to exist (use :meth:`run` with ``checkpoint=`` to start
        one), restores its landed windows, and completes the rest —
        bit-identical to the uninterrupted run."""
        if not os.path.exists(checkpoint):
            raise FileNotFoundError(
                f"no checkpoint journal at {checkpoint!r}; start the "
                f"run with run(..., checkpoint=path) first")
        return self.run(g, checkpoint=checkpoint, **kwargs)

    @staticmethod
    def compact_checkpoint(checkpoint: str) -> dict:
        """Fold an append-only checkpoint journal into its minimal form.

        A long checkpointed run appends one JSONL record per landed
        dispatch, so the journal grows with the window count even though
        resume only needs the *sums*.  Compaction rewrites the file as
        the fingerprint header plus ONE merged record per shard (summed
        partials, unioned window ids, concatenated per-window item
        counts) — the landing merge is an integer sum over independent
        windows, so :meth:`resume` restores the compacted journal to the
        exact state the full journal would have produced, and keeps
        appending new landings after it (``_load`` is additive per
        record; both forms read identically).

        Duplicate landings and a torn final line are dropped the same
        way loading drops them.  The rewrite is atomic (temp file +
        ``os.replace``), so a kill mid-compaction leaves the original
        journal intact.  Returns ``{"records", "compacted", "bytes",
        "compacted_bytes"}``.
        """
        if not os.path.exists(checkpoint):
            raise FileNotFoundError(
                f"no checkpoint journal at {checkpoint!r}")
        old_bytes = os.path.getsize(checkpoint)
        with open(checkpoint) as f:
            lines = [ln for ln in f.read().splitlines() if ln.strip()]
        if not lines:
            raise FaultError(
                f"checkpoint {checkpoint!r} is empty — nothing to "
                f"compact")
        head = json.loads(lines[0])
        if head.get("v") != _CheckpointJournal.VERSION:
            raise FaultError(
                f"checkpoint {checkpoint!r} has unknown version "
                f"{head.get('v')!r}")
        # replay the records exactly the way _load does (skip duplicate
        # landings and the torn tail), but keep the sums per shard
        merged: dict = {}
        records = 0
        for ln in lines[1:]:
            try:
                rec = json.loads(ln)
            except json.JSONDecodeError:
                break
            records += 1
            s = int(rec["s"])
            m = merged.setdefault(s, {
                "ids": set(), "hist": np.zeros(64, np.int64),
                "inter": np.zeros(2, np.int64), "items": []})
            ids = {int(x) for x in rec["ids"]}
            if ids & m["ids"]:
                continue
            m["ids"] |= ids
            m["hist"] += np.asarray(rec["hist"], dtype=np.int64)
            m["inter"] += np.asarray(rec["inter"], dtype=np.int64)
            m["items"].extend(int(x) for x in rec["items"])
        tmp = checkpoint + ".compact.tmp"
        with open(tmp, "w") as f:
            f.write(json.dumps(head) + "\n")
            for s in sorted(merged):
                m = merged[s]
                f.write(json.dumps({
                    "s": s, "ids": sorted(m["ids"]),
                    "hist": [int(x) for x in m["hist"]],
                    "inter": [int(x) for x in m["inter"]],
                    "items": m["items"]}) + "\n")
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, checkpoint)
        return {"records": records, "compacted": len(merged),
                "bytes": old_bytes,
                "compacted_bytes": os.path.getsize(checkpoint)}

    def session(self, g: CompactDigraph, *, orient: str = "none",
                prune_self: bool = True, max_items: int | None = None,
                emit: str | None = None,
                auto_rebalance_threshold: float | None = None,
                index: bool = True):
        """Open a resident-graph session on ``g`` for repeated / sliding-
        window censuses (see :class:`EngineSession`; a partitioned engine
        opens a :class:`PartitionedEngineSession`, whose delta updates
        dispatch only the shards owning touched pairs).
        ``auto_rebalance_threshold`` (partitioned only) re-shards the
        session with a fresh LPT whenever churn pushes the load
        ``max/mean`` past it (see
        :meth:`PartitionedEngineSession.rebalance`).  ``index`` keeps a
        persistent :class:`~repro.core.pair_index.PairSpaceIndex` so
        warm ``update()`` calls edit the pair space in O(delta · log P)
        instead of rebuilding it in O(P); ``index=False`` is the
        rebuild-from-scratch oracle path (bit-identical either way)."""
        if self.partition:
            if self.partition_2d is not None:
                return PartitionedEngineSession2D(
                    self, g, mesh_shape=self.partition_2d,
                    orient=orient, prune_self=prune_self,
                    max_items=max_items, emit=emit,
                    auto_rebalance_threshold=auto_rebalance_threshold,
                    index=index)
            return PartitionedEngineSession(
                self, g, orient=orient, prune_self=prune_self,
                max_items=max_items, emit=emit,
                auto_rebalance_threshold=auto_rebalance_threshold,
                index=index)
        if auto_rebalance_threshold is not None:
            raise ValueError(
                "auto_rebalance_threshold requires partition=True")
        return EngineSession(self, g, orient=orient,
                             prune_self=prune_self,
                             max_items=max_items, emit=emit, index=index)

    def _run_stream(self, chunker: PlanChunker, progress, *, census: int,
                    plan_seconds: float) -> np.ndarray:
        space = chunker.space
        gbytes = replicated_graph_bytes(space)
        self.stats = st = EngineStats(
            backend=self.backend, ndev=self.ndev, orient=space.orient,
            streamed=True, max_items=chunker.max_items,
            chunks=chunker.num_chunks, chunk_shape=chunker.chunk_shape,
            items=0, peak_plan_bytes=ITEM_BYTES * chunker.chunk_shape,
            preprune_items=space.num_items_preprune,
            search_iters=space.search_iters,
            emit="host",
            # item arrays are sharded over the mesh (chunk_shape is a
            # multiple of ndev): physical per-device upload bytes
            plan_upload_bytes=ITEM_BYTES * chunker.chunk_shape
            // self.ndev,
            graph_resident_bytes=gbytes, graph_replicated_bytes=gbytes,
            host_pair_seconds=plan_seconds)
        if chunker.num_chunks == 0:
            return assemble_counts(space.n, 0, 0, np.zeros(64, np.int64),
                                   np.zeros(2, np.int64))

        rep, item_sh = self._shardings()
        # chunk-invariant graph + pair arrays: uploaded once, reused by
        # every chunk step (replicated across the mesh when sharded)
        with span("census.upload", census=census):
            graph_dev = tuple(self._put(a, rep)
                              for a in chunker.device_arrays())

        hist_acc = np.zeros(64, np.int64)
        inter_acc = np.zeros(2, np.int64)
        base_asym = base_mut = 0
        chunk_items: list[int] = []
        step = _chunk_step
        cache0 = _jit_cache_size(step)
        pending = None

        def land(fut, k):
            with span("chunk.land", st, "host_land_seconds",
                      census=census, chunk=k):
                np.add(hist_acc, np.asarray(fut[0], dtype=np.int64),
                       out=hist_acc)
                np.add(inter_acc, np.asarray(fut[1], dtype=np.int64),
                       out=inter_acc)

        for k in range(chunker.num_chunks):
            with span("chunk.emit", st, "host_emit_seconds",
                      census=census, chunk=k):
                chunk = chunker.chunk(k)
            base_asym += chunk.base_asym
            base_mut += chunk.base_mut
            chunk_items.append(chunk.num_items)
            if progress is not None:
                progress(chunk.index, chunker.num_chunks, chunk.num_items)
            if chunk.num_items == 0:
                # fully-pruned chunk: its bases are credited above, the
                # all-invalid items contribute nothing — skip the dispatch
                # (mirrors the monolithic zero-work short-circuit)
                continue
            # upload + dispatch chunk k while chunk k-1 still computes
            # (dispatch is async; we only block when accumulating k-1)
            with span("chunk.dispatch", census=census, chunk=k):
                sp_dev = self._put(chunk.item_sp, item_sh)
                pv_dev = self._put(chunk.item_pv, item_sh)
                fut = _launch(step, self.backend, *graph_dev, sp_dev,
                              pv_dev, self.mesh, space.search_iters,
                              self.backend)
            if pending is not None:
                land(*pending)
            pending = fut, k
        if pending is not None:
            land(*pending)

        st.step_compiles = _jit_cache_size(step) - cache0
        st.chunk_items = chunk_items
        st.items = int(sum(chunk_items))
        mono_wp = -(-st.items // self.ndev) * self.ndev
        st.monolithic_plan_bytes = ITEM_BYTES * mono_wp
        with span("census.assemble", census=census):
            return assemble_counts(space.n, base_asym, base_mut,
                                   hist_acc, inter_acc)

    def _run_stream_desc(self, chunker: PlanChunker, progress, *,
                         max_items: int | None, census: int,
                         plan_seconds: float) -> np.ndarray:
        """Device-emission stream: per chunk the host ships the O(pairs)
        descriptor window; the device expands pairs→items in-kernel
        against the resident flat-index array.  Bit-identical to
        :meth:`_run_stream` — the expanded pre-prune items carry the
        plan-time pruning as an in-kernel mask, and every masked item is
        provably a zero contribution (see
        :func:`repro.core.census.prune_keep_mask`)."""
        space = chunker.space
        # the descriptor buffer is replicated on every device: the padded
        # window IS the physical per-device upload
        upload = (DESC_BYTES * chunker.desc_shape
                  + 4 * chunker.num_anchors + 4)
        gbytes = replicated_graph_bytes(space)
        self.stats = st = EngineStats(
            backend=self.backend, ndev=self.ndev, orient=space.orient,
            streamed=max_items is not None, max_items=max_items,
            chunks=chunker.num_chunks, chunk_shape=chunker.chunk_shape,
            items=0, peak_plan_bytes=ITEM_BYTES * chunker.chunk_shape,
            preprune_items=space.num_items_preprune,
            search_iters=space.search_iters,
            emit="device", desc_shape=chunker.desc_shape,
            plan_upload_bytes=upload,
            graph_resident_bytes=gbytes, graph_replicated_bytes=gbytes,
            host_pair_seconds=plan_seconds)
        if chunker.num_chunks == 0:
            return assemble_counts(space.n, 0, 0, np.zeros(64, np.int64),
                                   np.zeros(2, np.int64))

        rep, item_sh = self._shardings()
        with span("census.upload", census=census):
            graph_dev = tuple(self._put(a, rep)
                              for a in chunker.device_arrays())
            # the flat item-index space: created on device once, reused
            # by every chunk (this is the array the mesh shards — there
            # are no item arrays left to shard)
            idx_dev = self._put(
                jnp.arange(chunker.chunk_shape, dtype=jnp.int32), item_sh)

        hist_acc = np.zeros(64, np.int64)
        inter_acc = np.zeros(2, np.int64)
        base_asym = base_mut = 0
        chunk_items: list[int] = []
        cache0 = _jit_cache_size(_desc_step)
        pending = None

        def land(fut, k):
            with span("chunk.land", st, "host_land_seconds",
                      census=census, chunk=k):
                num = _land_desc_partials(fut, hist_acc, inter_acc,
                                          chunk_items)
            if progress is not None:
                progress(k, chunker.num_chunks, num)

        for k in range(chunker.num_chunks):
            with span("chunk.emit", st, "host_emit_seconds",
                      census=census, chunk=k):
                ba, bm = chunker.bases(k)
                base_asym += ba
                base_mut += bm
                words = chunker.descriptors(k).device_words()
            with span("chunk.dispatch", census=census, chunk=k):
                fut = _launch(_desc_step, self.backend, *graph_dev,
                              self._put(words, rep), idx_dev, self.mesh,
                              space.search_iters, chunker.desc_iters,
                              self.backend, space.orient,
                              space.prune_self)
            if pending is not None:
                land(pending, k - 1)
            pending = fut
        if pending is not None:
            land(pending, chunker.num_chunks - 1)

        st.step_compiles = _jit_cache_size(_desc_step) - cache0
        st.chunk_items = chunk_items
        st.items = int(sum(chunk_items))
        mono_wp = -(-st.items // self.ndev) * self.ndev
        st.monolithic_plan_bytes = ITEM_BYTES * mono_wp
        with span("census.assemble", census=census):
            return assemble_counts(space.n, base_asym, base_mut,
                                   hist_acc, inter_acc)

    def _run_partitioned(self, g: CompactDigraph, *,
                         max_items: int | None, orient: str,
                         prune_self: bool, progress, emit: str,
                         census: int, part=None,
                         checkpoint: str | None = None) -> np.ndarray:
        """Partitioned plan + count: LPT-shard the pair space (or take a
        prebuilt ``part``), extract one local subgraph per mesh device,
        and let every device drain its PRIVATE chunk queue
        (:class:`repro.core.plan_stream.ShardSchedule`) with no
        inter-shard barrier.  Each device holds only ITS shard's
        relabeled CSR + pair arrays and receives only its own descriptor
        windows (``emit="device"``) or packed item windows
        (``emit="host"``), dispatched as independent single-device steps
        against per-device-committed shard buffers — the
        :class:`PartitionedEngineSession` dispatch discipline applied to
        the full run.  A shard with 3 chunks is done after 3 dispatches
        while a 12-chunk shard keeps going, so walltime tracks the MEAN
        shard cost, not the max.

        The host side is pipelined by a
        :class:`repro.core.plan_stream.ShardStreamPipeline`: one
        background producer per non-empty shard packs descriptor
        windows / emits item batches ``pipeline_depth`` windows ahead
        into its private queue, so window k+1's generation + upload
        overlaps window k's compute (zero-window shards never get a
        producer or a rotation slot); dispatches are async (futures)
        with a bounded in-flight deque of ``2 * ndev``, keeping host +
        device plan memory O(ndev · chunk_shape).

        Under ``emit="device"`` each dispatch is a **megastep**: the
        producer coalesces up to K descriptor windows into one
        fixed-shape ``(cap, words)`` batch
        (:class:`repro.core.plan_stream.WindowBatcher`) and the device
        scans them inside one compiled step
        (:func:`_desc_megastep`), so Python dispatch cost — the
        per-shard streams' Achilles' heel on fast devices with tiny
        windows — is paid once per K windows.  K adapts live between 1
        and ``max_windows_per_dispatch``: consumer stalls shrink it
        (producer-bound: smaller batches keep the pipeline full),
        producer backlog grows it (dispatch-bound: amortize more).
        ``emit="host"`` keeps one window per dispatch as the oracle.

        Partials merge on the host in int64 — integer sums, so any
        landing order is bit-identical to the replicated and
        single-device paths for every backend, orient and emit (the
        relabeling is order-preserving and the pair partition is exact).

        **Fault tolerance** rides on the same property: windows are
        independent and the merge is order-invariant, so any window can
        be re-dispatched (after a transient error or a corrupted
        result) or re-routed to a surviving device (after its home
        device is retired) without changing a single census bit.  Every
        dispatch is retried up to ``max_retries`` with exponential
        backoff; a device that exhausts the budget (or hits a
        persistent injected fault) is retired and its shards' host
        arrays are re-uploaded to a survivor, whose already-compiled
        step drains the remaining queue; stalled producers are
        restarted by the pipeline watchdog; and ``checkpoint=`` journals
        every landed window so a killed run resumes to the exact same
        census.  An optional :class:`repro.core.faults.FaultPlan`
        injects deterministic failures at the producer / upload /
        dispatch boundaries to exercise all of it.
        """
        plan_seconds = 0.0
        if part is None:
            with span("census.plan", census=census) as plan:
                space = pair_space(g, orient=orient, prune_self=prune_self)
            plan_seconds = plan.seconds
        elif part.num_shards != self.ndev:
            raise ValueError(
                f"prebuilt partition has {part.num_shards} shards for "
                f"{self.ndev} devices")
        elif (self.partition_2d is not None
              and getattr(part, "mesh_shape", None) != self.partition_2d):
            raise ValueError(
                f"prebuilt partition mesh "
                f"{getattr(part, 'mesh_shape', None)} does not match "
                f"partition_2d={self.partition_2d}")
        with span("census.partition", census=census) as partition:
            if part is None:
                part = (partition_graph_2d(space=space,
                                           mesh_shape=self.partition_2d)
                        if self.partition_2d is not None
                        else partition_graph(num_shards=self.ndev,
                                             space=space))
            sched = ShardSchedule([sh.space for sh in part.shards],
                                  max_items, self.ndev,
                                  mesh_shape=getattr(part, "mesh_shape",
                                                     None))
        upload = (4 * (1 + 3 * sched.desc_shape + sched.num_anchors)
                  if emit == "device"
                  else ITEM_BYTES * sched.chunk_shape)
        space = part.space
        ndev = self.ndev
        total_windows = sched.total_windows
        # effective megabatch capacity: never pad past the longest
        # shard's queue — a schedule whose every shard has s windows can
        # fill at most s rows per batch, so a larger buffer would only
        # upload dead zero rows (the scan already skips their compute)
        cap = (max(1, min(self.max_windows_per_dispatch,
                          max(sched.shard_steps, default=0)))
               if emit == "device" else 1)
        self.stats = st = EngineStats(
            backend=self.backend, ndev=ndev, orient=space.orient,
            streamed=max_items is not None, max_items=max_items,
            chunks=0, chunk_shape=sched.chunk_shape, items=0,
            # the schedule-wide lane footprint (all devices)
            peak_plan_bytes=ITEM_BYTES * sched.chunk_shape * ndev,
            emit=emit,
            desc_shape=sched.desc_shape if emit == "device" else 0,
            plan_upload_bytes=upload, partitioned=True,
            partition_shape=getattr(part, "mesh_shape", None),
            shard_items=list(part.stats.shard_items),
            graph_resident_bytes=part.stats.max_shard_bytes,
            graph_replicated_bytes=part.stats.replicated_bytes,
            shard_steps=[0] * ndev,
            pipeline_depth=self.pipeline_depth,
            dispatch_batch_limit=cap,
            host_pair_seconds=plan_seconds,
            host_partition_seconds=partition.seconds,
            preprune_items=sum(sh.space.num_items_preprune
                               for sh in part.shards),
            search_iters=space.search_iters)
        with span("census.plan", st, "host_pair_seconds", census=census):
            base_asym, base_mut = global_bases(space)
        if total_windows == 0:
            return assemble_counts(space.n, base_asym, base_mut,
                                   np.zeros(64, np.int64),
                                   np.zeros(2, np.int64))

        injector = (self.faults.injector()
                    if self.faults is not None else None)
        journal = None
        done = None
        if checkpoint is not None:
            fp = _CheckpointJournal.graph_fingerprint(
                space, emit=emit, ndev=ndev, max_items=max_items)
            journal = _CheckpointJournal(checkpoint, fp, ndev)
            done = journal.done

        devices = list(self.mesh.devices.flat)
        # per-device commit of each shard's padded local arrays (common
        # shapes across shards, so ONE compiled single-device step serves
        # every shard's every window); the host copies in ``arrs`` stay
        # alive as the failover re-upload source
        with span("census.partition", st, "host_partition_seconds",
                  census=census):
            arrs = stacked_device_arrays(part.shards)
        with span("census.upload", census=census):
            dev = [tuple(jax.device_put(a[s], devices[s]) for a in arrs)
                   for s in range(ndev)]
            idx = ([jax.device_put(np.arange(sched.chunk_shape,
                                             dtype=np.int32), d)
                    for d in devices] if emit == "device" else None)
        #: shard → device currently serving it (failover re-routes)
        home = list(range(ndev))
        retired: set = set()
        # drained-shard short-circuit: a shard with zero windows never
        # gets a producer thread or a consumer rotation slot
        batcher = None
        if emit == "device":
            step = _desc_megastep
            batcher = WindowBatcher(
                cap, 1 + 3 * sched.desc_shape + sched.num_anchors)
            # remaining window ids per shard in yield order — lets the
            # consumer recover each pulled window's id (FIFO queues
            # preserve producer order) for the checkpoint journal
            order = [[k for k in range(sched.steps_for(s))
                      if done is None or k not in done[s]]
                     for s in range(ndev)]
            live = [s for s in range(ndev) if order[s]]

            def make_source(s, skip=0):
                def gen():
                    for j, k in enumerate(order[s]):
                        if j < skip:
                            continue
                        if injector is not None:
                            injector.fire("producer", shard=s)
                        with span("chunk.emit", st, "host_emit_seconds",
                                  census=census, chunk=k, shard=s):
                            words = sched.descriptors(s, k).device_words()
                        yield words
                return gen()
        else:
            step = _chunk_step
            order = None
            live = [s for s in range(ndev) if sched.steps_for(s) > 0]

            def make_source(s, skip=0):
                def gen():
                    emitted = 0
                    for k in range(sched.steps_for(s)):
                        if done is not None and k in done[s]:
                            continue
                        with span("chunk.emit", st, "host_emit_seconds",
                                  census=census, chunk=k, shard=s):
                            sp, pv, num = sched.shard_step_items(s, k)
                        if num == 0:
                            # fully-pruned window: zero contribution by
                            # construction — never dispatched
                            continue
                        emitted += 1
                        if emitted <= skip:
                            continue
                        if injector is not None:
                            injector.fire("producer", shard=s)
                        yield k, sp, pv, num
                return gen()

        cache0 = _jit_cache_size(step)
        hist_acc = np.zeros(64, np.int64)
        inter_acc = np.zeros(2, np.int64)
        chunk_items: list[int] = []
        if journal is not None and journal.windows:
            np.add(hist_acc, journal.hist, out=hist_acc)
            np.add(inter_acc, journal.inter, out=inter_acc)
            chunk_items.extend(journal.chunk_items)
            self.stats.resumed_windows = journal.windows
        shard_steps = [0] * ndev
        pos = [0] * ndev
        dispatches = 0
        win_max = 0
        pad_windows = 0
        landed = [self.stats.resumed_windows]

        def retire(d_id: int, cause) -> None:
            """Fail device ``d_id`` over to the survivors: every shard
            homed on it is re-uploaded (from the host copies) onto a
            surviving device, whose already-compiled step drains the
            rest of the queue.  The merge is untouched, so the census
            stays bit-identical."""
            if d_id in retired:
                return
            retired.add(d_id)
            survivors = [x for x in range(ndev) if x not in retired]
            if not survivors:
                raise FaultError(
                    "every device has been retired; cannot complete "
                    "the census") from cause
            st.failovers += 1
            st.retired_devices.append(d_id)
            for s2 in range(ndev):
                if home[s2] == d_id:
                    r = survivors[s2 % len(survivors)]
                    home[s2] = r
                    dev[s2] = tuple(
                        jax.device_put(a[s2], devices[r]) for a in arrs)

        def do_dispatch(s: int, window):
            """One dispatch attempt of ``window`` on shard ``s``'s home
            device; returns (future, poisoned)."""
            d_id = home[s]
            d = devices[d_id]
            if injector is not None:
                injector.fire("upload", shard=s, device=d_id)
            if emit == "device":
                buf, _x = window
                buf_d = jax.device_put(buf, d)
                if injector is not None:
                    injector.fire("dispatch", shard=s, device=d_id)
                fut = _launch(step, self.backend, *dev[s], buf_d,
                              idx[d_id], space.search_iters,
                              sched.desc_iters, self.backend,
                              space.orient, space.prune_self)
            else:
                _wid, sp, pv, _num = window
                sp_d = jax.device_put(sp, d)
                pv_d = jax.device_put(pv, d)
                if injector is not None:
                    injector.fire("dispatch", shard=s, device=d_id)
                fut = _launch(step, self.backend, *dev[s], sp_d, pv_d,
                              None, space.search_iters, self.backend)
            poisoned = (injector.take_poison()
                        if injector is not None else False)
            return fut, poisoned

        def dispatch_retrying(s: int, window, attempts: int = 0):
            """Dispatch with the retry/failover discipline: transient
            failures back off and retry on the same device up to
            ``max_retries``; a dead device (persistent fault) or an
            exhausted budget retires the device and re-routes.  A step
            that cannot compile is raised at once."""
            while True:
                d_id = home[s]
                try:
                    fut, poisoned = do_dispatch(s, window)
                    return fut, poisoned, attempts
                except StepCompileError:
                    raise
                except Exception as exc:
                    dead = ((injector is not None
                             and injector.device_is_dead(d_id))
                            or getattr(getattr(exc, "fault", None),
                                       "persistent", False))
                    if dead:
                        retire(d_id, exc)
                        attempts = 0
                        continue
                    attempts += 1
                    st.retries += 1
                    if attempts > self.max_retries:
                        # budget exhausted: treat the device as failed
                        # and drain its queue on the survivors
                        retire(d_id, exc)
                        attempts = 0
                        continue
                    time.sleep(self.retry_backoff
                               * 2 ** (attempts - 1))

        def land(job) -> None:
            s, window, ids, fut, x, attempts, poisoned = job
            with span("chunk.land", st, "host_land_seconds",
                      census=census, chunk=ids[0], shard=s):
                hsum, isum, nums = fetch(s, window, fut, x, attempts,
                                         poisoned)
                np.add(hist_acc, hsum, out=hist_acc)
                np.add(inter_acc, isum, out=inter_acc)
            if journal is not None:
                journal.record(s, ids, hsum, isum, nums)
            for num in nums:
                chunk_items.append(num)
                if progress is not None:
                    progress(landed[0], total_windows, num)
                landed[0] += 1

        def fetch(s, window, fut, x, attempts, poisoned):
            """One dispatch's validated partials, summed through int64:
            ``(hist64, inter2, per-window valid items)``."""
            while True:
                try:
                    if emit == "device":
                        # megastep: per-window int32 partials stacked
                        # (cap, ·); summing the first x rows through
                        # int64 is bit-identical to landing x
                        # single-window dispatches
                        hist64s = np.asarray(fut[0], dtype=np.int64)
                        inter3s = np.asarray(fut[1], dtype=np.int64)
                        if poisoned:
                            hist64s, inter3s = poison_result(hist64s,
                                                             inter3s)
                        _validate_partials(hist64s[:x], inter3s[:x])
                        return (hist64s[:x].sum(axis=0),
                                inter3s[:x, :2].sum(axis=0),
                                [int(inter3s[i, 2]) for i in range(x)])
                    h = np.asarray(fut[0], dtype=np.int64)
                    it2 = np.asarray(fut[1], dtype=np.int64)
                    if poisoned:
                        h, it2 = poison_result(h, it2)
                    _validate_partials(h, it2)
                    return h, it2, [x]
                except Exception as exc:
                    # fetch/validation failure: re-dispatch the SAME
                    # window (same-device retry, then failover) — the
                    # merge is order-invariant, so the late landing is
                    # bit-identical
                    attempts += 1
                    st.retries += 1
                    if attempts > self.max_retries:
                        retire(home[s], exc)
                        attempts = 0
                    else:
                        time.sleep(self.retry_backoff
                                   * 2 ** (attempts - 1))
                    fut, poisoned, attempts = dispatch_retrying(
                        s, window, attempts)

        def restart(slot: int, skip: int):
            return make_source(live[slot], skip)

        pipeline = ShardStreamPipeline(
            [make_source(s) for s in live], depth=self.pipeline_depth,
            batch=batcher, restart=restart,
            watchdog=self.watchdog_timeout,
            max_retries=self.max_retries, backoff=self.retry_backoff,
            census=census)
        pending: deque = deque()
        limit = 2 * ndev
        try:
            with pipeline:
                for slot, window in pipeline:
                    s = live[slot]
                    if emit == "device":
                        _buf, x = window
                        ids = order[s][pos[s]:pos[s] + x]
                        pos[s] += x
                        shard_steps[s] += x
                        win_max = max(win_max, x)
                        pad_windows += cap - x
                    else:
                        wid, _sp, _pv, x = window
                        ids = [wid]
                        shard_steps[s] += 1
                        win_max = max(win_max, 1)
                    with span("chunk.dispatch", census=census,
                              chunk=ids[0], shard=s):
                        fut, poisoned, attempts = dispatch_retrying(
                            s, window)
                    dispatches += 1
                    pending.append(
                        (s, window, ids, fut, x, attempts, poisoned))
                    if len(pending) > limit:
                        land(pending.popleft())
                while pending:
                    land(pending.popleft())
        finally:
            if journal is not None:
                journal.close()

        st.step_compiles = _jit_cache_size(step) - cache0
        st.chunk_items = chunk_items
        st.chunks = len(chunk_items)
        st.items = int(sum(chunk_items))
        st.shard_steps = shard_steps
        st.stall_steps = pipeline.stalls
        st.retries += pipeline.producer_retries
        st.watchdog_fires = pipeline.watchdog_fires
        st.dispatches_total = dispatches
        st.windows_per_dispatch_max = win_max
        st.windows_per_dispatch_mean = (
            sum(shard_steps) / dispatches if dispatches else 0.0)
        st.plan_upload_bytes_total = upload * sum(shard_steps)
        st.plan_pad_bytes_total = upload * pad_windows
        mono_wp = -(-st.items // ndev) * ndev
        st.monolithic_plan_bytes = ITEM_BYTES * mono_wp
        with span("census.assemble", census=census):
            return assemble_counts(space.n, base_asym, base_mut,
                                   hist_acc, inter_acc)


def _pad_i32(a: np.ndarray, cap: int) -> np.ndarray:
    """Zero-pad an int32 array to a fixed capacity (device shape)."""
    out = np.zeros(cap, dtype=np.int32)
    out[:a.shape[0]] = a
    return out


def _emit_spans(it, session, **ids):
    """Yield ``it``'s items, each built inside a ``chunk.emit`` span
    timed into the session's emit counter: the host-side plan/window
    construction cost of a lazy emission stream, excluding the
    consumer's device-wait time."""
    it = iter(it)
    for k in itertools.count():
        with span("chunk.emit", session, "_t_emit",
                  census=session._census_id, chunk=k, **ids):
            item = next(it, None)
        if item is None:
            return
        yield item


def _split_capacity_compiles(session, chunk_items: list, compiles: int
                             ) -> tuple[int, int]:
    """(capacity_recompiles, step_compiles) attribution shared by both
    session kinds: the first dispatches after the resident buffers regrew
    charge any fresh compile to the capacity growth, not the step."""
    if session._capacity_grew and chunk_items:
        session._capacity_grew = False
        return compiles, 0
    return 0, compiles


def _dispatch_retrying_session(session, thunk):
    """Session-side dispatch retry: call ``thunk`` (upload + step launch,
    with the session's fault-injection hooks inside) under the engine's
    retry budget with exponential backoff.  Sessions retry on the same
    device only — failover is an engine-run discipline — so a persistent
    fault surfaces to the caller once the budget is spent (the temporal
    monitor turns that into a degraded window instead of dying)."""
    engine = session.engine
    attempts = 0
    while True:
        try:
            return thunk()
        except FaultError:
            if attempts >= engine.max_retries:
                raise
            attempts += 1
            session.retries += 1
            time.sleep(engine.retry_backoff * 2 ** (attempts - 1))


def _land_retrying_session(session, fut, poisoned, redo):
    """Session-side landing: fetch + validate one dispatch result,
    re-dispatching the same window via ``redo`` on failure (fetch error
    or corrupted partials), up to the engine's retry budget.  Returns
    the validated ``(hist64, inter)`` int64 arrays — the caller
    accumulates them, so nothing is ever double-counted."""
    engine = session.engine
    attempts = 0
    while True:
        try:
            hist = np.asarray(fut[0], dtype=np.int64)
            inter = np.asarray(fut[1], dtype=np.int64)
            if poisoned:
                hist, inter = poison_result(hist, inter)
            _validate_partials(hist, inter)
            return hist, inter
        except Exception:
            if redo is None or attempts >= engine.max_retries:
                raise
            attempts += 1
            session.retries += 1
            time.sleep(engine.retry_backoff * 2 ** (attempts - 1))
            fut, poisoned = redo()


def _session_graph_crc(g: CompactDigraph) -> int:
    return int(zlib.crc32(np.ascontiguousarray(g.packed).tobytes()))


def _save_session_checkpoint(session, path: str) -> None:
    """Persist a session's running census + graph fingerprint so a new
    session over the same graph can continue warm updates without
    recomputing the baseline (both session kinds share this format)."""
    if session._census is None:
        raise RuntimeError(
            "no census to checkpoint: call census() first")
    with open(path, "w") as f:
        json.dump({
            "v": 1, "kind": "session", "n": int(session.n),
            "orient": session.orient,
            "prune_self": bool(session.prune_self),
            "packed_crc": _session_graph_crc(session._g),
            "census": [int(x) for x in session._census]}, f)
        f.write("\n")


def _load_session_checkpoint(session, path: str) -> np.ndarray:
    """Restore a running census saved by :func:`_save_session_checkpoint`
    into a session whose RESIDENT graph matches the checkpoint's
    fingerprint; :meth:`update` then continues exactly where the saved
    session left off (bit-identical — the census never depended on which
    process computed it)."""
    with open(path) as f:
        rec = json.load(f)
    want = {"v": 1, "kind": "session", "n": int(session.n),
            "orient": session.orient,
            "prune_self": bool(session.prune_self),
            "packed_crc": _session_graph_crc(session._g)}
    got = {k: rec.get(k) for k in want}
    if got != want:
        raise FaultError(
            f"session checkpoint {path!r} does not match the resident "
            f"graph/session ({got} != {want})")
    session._census = np.asarray(rec["census"], dtype=np.int64)
    return session._census.copy()


class EngineSession:
    """Resident-graph census session: upload once, recount by delta.

    The graph-shaped device arrays (CSR ``indptr``/``packed`` + pair
    arrays) are uploaded once per graph revision into fixed-capacity
    zero-padded buffers (grown geometrically, so revisions of similar size
    reuse the same compiled step), items are dispatched in fixed
    ``chunk_shape`` slices through the engine's compile-once chunk step,
    and the binary-search depth is pinned to ``ceil(log2 n)`` — an upper
    bound for every possible row — so no future window can force a
    recompilation.  The padding is inert by construction: items only
    reference real slots/pairs, and the search stays inside real row
    bounds.

    Two ways to move the session forward:

    * :meth:`set_graph` + :meth:`census` — full recompute of a new graph
      (the tumbling-window path; still benefits from the resident arrays
      and the compile-once step).
    * :meth:`update` — apply an edge delta via
      :func:`repro.core.digraph.apply_delta` and recount only the
      *affected pairs* (see :mod:`repro.core.incremental`):
      ``C_new = C_old + contrib(A, G_new) − contrib(A, G_old)``,
      bit-identical to a from-scratch census of the edited graph.

    ``max_items`` bounds the padded items per dispatch (device-memory
    knob, default: one chunk sized to the initial graph's pre-prune item
    space); full censuses emit per-slice so host plan memory is
    O(chunk_shape), and subset recounts are O(subset items).  After every
    operation :attr:`stats` (also mirrored to ``engine.stats``) records
    the dispatch schedule, including ``full_items`` — what a from-scratch
    recompute would have processed — and ``affected_pairs``.

    Under ``emit="device"`` (the default) nothing above changes
    semantically, but per dispatch the host uploads ONE packed
    descriptor buffer (O(pairs-in-window) words) instead of the packed
    items, and a delta update uploads only the touched pairs'
    descriptors.  The descriptor capacity and anchor geometry are fixed
    at session open — windows that would overflow shrink their item span
    instead — so device emission adds no recompile vector;
    graph-capacity growth remains the only one and is counted apart as
    ``stats.capacity_recompiles``.
    """

    def __init__(self, engine: CensusEngine, g: CompactDigraph, *,
                 orient: str = "none", prune_self: bool = True,
                 max_items: int | None = None, emit: str | None = None,
                 index: bool = True):
        if max_items is not None and max_items < 1:
            raise ValueError(f"max_items must be >= 1, got {max_items}")
        emit = engine.emit if emit is None else emit
        if emit not in EMIT_MODES:
            raise ValueError(
                f"unknown emit mode {emit!r}; one of {EMIT_MODES}")
        self.engine = engine
        self.orient = orient
        self.prune_self = prune_self
        self.emit = emit
        self.n = g.n
        self.max_items = max_items
        #: delta-incremental host planning: keep a persistent
        #: :class:`PairSpaceIndex` and edit it per update instead of
        #: rebuilding the O(P) pair space (False == rebuild oracle)
        self.use_index = bool(index)
        self._pair_index: PairSpaceIndex | None = None
        #: host-phase counters of the operation in progress (its spans'
        #: buckets), handed to its :class:`EngineStats` and reset
        self._t_pair = self._t_merge = self._t_emit = 0.0
        #: id of the census or update in progress, carried by its spans
        self._census_id = next(engine._census_ids)
        #: pinned unrolled-search depth: any row has < n entries, so this
        #: upper bound keeps the jitted step valid for every graph revision
        self.search_iters = max(1, int(np.ceil(np.log2(max(g.n, 2)))))
        self._rep, self._item_sh = engine._shardings()
        self._step = _chunk_step
        self._cap_entries = 0
        self._cap_pairs = 0
        self._capacity_grew = False
        self.chunk_shape: int | None = None
        self.desc_shape: int | None = None
        self._census: np.ndarray | None = None
        self.last_delta: GraphDelta | None = None
        self.stats: EngineStats | None = None
        #: injected-fault runtime shared across this session's dispatches
        #: (occurrence counters persist across census()/update() calls)
        self._injector = (engine.faults.injector()
                          if engine.faults is not None else None)
        #: dispatches re-attempted after a fault, across the session's life
        self.retries = 0
        self._closed = False
        self._install(g)
        if self.emit == "device":
            self._init_device_emission()

    # ---------------------------------------------------------- lifecycle
    def close(self) -> None:
        """Release the resident device buffers.  Idempotent; the session
        is unusable afterwards."""
        self._dev = None
        if hasattr(self, "_idx"):
            self._idx = None
        self._closed = True

    def __enter__(self) -> "EngineSession":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.close()

    def _check_open(self) -> None:
        if self._closed:
            raise RuntimeError("session is closed")

    # ------------------------------------------------------- checkpointing
    def save_checkpoint(self, path: str) -> None:
        """Persist the running census + graph fingerprint (JSON) so a new
        session over the same graph resumes warm updates via
        :meth:`load_checkpoint` without recomputing the baseline."""
        _save_session_checkpoint(self, path)

    def load_checkpoint(self, path: str) -> np.ndarray:
        """Adopt a census saved by :meth:`save_checkpoint`; the resident
        graph must match the checkpoint's fingerprint.  Returns the
        restored census; subsequent :meth:`update` calls continue
        bit-identically from it."""
        return _load_session_checkpoint(self, path)

    # ------------------------------------------------------------ state
    @property
    def graph(self) -> CompactDigraph:
        return self._g

    @property
    def space(self):
        return self._space

    @property
    def counts(self) -> np.ndarray | None:
        """The session's running census C_k (None until :meth:`census`)."""
        return None if self._census is None else self._census.copy()

    @staticmethod
    def _grown(cap: int, need: int) -> int:
        cap = max(cap, 256)
        while cap < need:
            cap *= 2
        return cap

    def _init_device_emission(self) -> None:
        """Fix the session's descriptor geometry: the per-dispatch
        descriptor capacity (:func:`_desc_capacity`), the matching pinned
        lower-bound depth, and the resident flat-index array the windows
        expand against — none of which any graph revision or delta can
        ever force to recompile."""
        space = self._space
        cs = self.chunk_shape
        self.desc_shape = _desc_capacity(
            cs, max_pairs_per_window(space.offsets, cs))
        self.desc_iters = DESC_SEARCH_ITERS
        self.num_anchors = num_desc_anchors(cs)
        self._idx = self.engine._put(
            jnp.arange(cs, dtype=jnp.int32), self._item_sh)

    def _install(self, g: CompactDigraph, space=None) -> None:
        """Make ``g`` the resident graph: rebuild the pair space (or
        adopt the prebuilt ``space`` an index edit produced) and
        (re)upload the padded device arrays."""
        self._g = g
        if space is None:
            with span("census.plan", self, "_t_pair",
                      census=self._census_id):
                if self.use_index:
                    self._pair_index = PairSpaceIndex(
                        g, orient=self.orient, prune_self=self.prune_self)
                    space = self._pair_index.space
                else:
                    space = pair_space(g, orient=self.orient,
                                       prune_self=self.prune_self)
        self._space = space
        self._full_items: int | None = None   # lazy per-install stat
        if self.chunk_shape is None:
            budget = (self.max_items if self.max_items is not None
                      else max(space.num_items_preprune, 1))
            self.chunk_shape = _guard_chunk_shape(
                -(-max(int(budget), 1)
                  // self.engine.ndev) * self.engine.ndev)
        prev_caps = (self._cap_entries, self._cap_pairs)
        self._cap_entries = self._grown(self._cap_entries,
                                        space.packed.shape[0])
        self._cap_pairs = self._grown(self._cap_pairs, space.num_pairs)
        if prev_caps != (0, 0) and \
                prev_caps != (self._cap_entries, self._cap_pairs):
            # the padded device shapes changed: the next dispatch's fresh
            # compile (if any) is a capacity recompile, not a step compile
            self._capacity_grew = True
        put = self.engine._put
        self._dev = (
            put(space.indptr.astype(np.int32), self._rep),
            put(_pad_i32(space.packed, self._cap_entries), self._rep),
            put(_pad_i32(space.pair_u.astype(np.int32),
                         self._cap_pairs), self._rep),
            put(_pad_i32(space.pair_v.astype(np.int32),
                         self._cap_pairs), self._rep),
            put(_pad_i32(space.pair_code, self._cap_pairs), self._rep),
        )

    def set_graph(self, g: CompactDigraph) -> None:
        """Replace the resident graph wholesale (no delta bookkeeping).
        Invalidates the running census until :meth:`census` recomputes."""
        if g.n != self.n:
            raise ValueError(f"session is pinned to n={self.n}, got {g.n}")
        self._census_id = next(self.engine._census_ids)
        self._install(g)
        self._census = None
        self.last_delta = None

    # ---------------------------------------------------------- running
    def _run_batches(self, batches
                     ) -> tuple[np.ndarray, np.ndarray, list[int]]:
        """Dispatch item batches (each with at most ``chunk_shape``
        items) in fixed-shape chunks against the resident device graph;
        accumulate int64 partials on the host, overlapping batch k+1's
        emission + upload with batch k's compute.  Fully-pruned batches
        are skipped without a dispatch."""
        hist_acc = np.zeros(64, np.int64)
        inter_acc = np.zeros(2, np.int64)
        chunk_items: list[int] = []
        pending = None

        def land(job):
            fut, poisoned, dispatch = job
            hist, inter = _land_retrying_session(
                self, fut, poisoned,
                lambda: _dispatch_retrying_session(self, dispatch))
            np.add(hist_acc, hist, out=hist_acc)
            np.add(inter_acc, inter, out=inter_acc)

        for item_pair, item_slot, item_side in batches:
            num = int(item_pair.shape[0])
            if num == 0:
                continue
            item_sp, item_pv = pad_and_pack(
                item_pair, item_slot, item_side, self.chunk_shape)

            def dispatch(item_sp=item_sp, item_pv=item_pv):
                inj = self._injector
                if inj is not None:
                    inj.fire("upload", shard=0, device=0)
                sp_dev = self.engine._put(item_sp, self._item_sh)
                pv_dev = self.engine._put(item_pv, self._item_sh)
                if inj is not None:
                    inj.fire("dispatch", shard=0, device=0)
                backend = self.engine.backend
                fut = _launch(self._step, backend, *self._dev, sp_dev,
                              pv_dev, self.engine.mesh, self.search_iters,
                              backend)
                poisoned = inj.take_poison() if inj is not None else False
                return fut, poisoned

            fut, poisoned = _dispatch_retrying_session(self, dispatch)
            if pending is not None:
                land(pending)
            pending = (fut, poisoned, dispatch)
            chunk_items.append(num)
        if pending is not None:
            land(pending)
        return hist_acc, inter_acc, chunk_items

    def _run_desc_batches(self, windows
                          ) -> tuple[np.ndarray, np.ndarray, list[int]]:
        """Device-emission twin of :meth:`_run_batches`: dispatch
        descriptor windows against the resident graph + flat-index
        arrays, overlapping window k+1's (tiny) descriptor build + upload
        with window k's compute.  Valid-item counts come back from the
        device (``inter`` lane 2), so the stats stay comparable with host
        emission without materializing a single item."""
        hist_acc = np.zeros(64, np.int64)
        inter_acc = np.zeros(2, np.int64)
        chunk_items: list[int] = []
        put = self.engine._put
        pending = None

        def land(job):
            fut, poisoned, dispatch = job
            hist, inter3 = _land_retrying_session(
                self, fut, poisoned,
                lambda: _dispatch_retrying_session(self, dispatch))
            np.add(hist_acc, hist, out=hist_acc)
            np.add(inter_acc, inter3[:2], out=inter_acc)
            chunk_items.append(int(inter3[2]))

        for win in windows:
            if win.num_preprune == 0:
                continue

            def dispatch(win=win):
                inj = self._injector
                if inj is not None:
                    inj.fire("upload", shard=0, device=0)
                words = put(win.device_words(), self._rep)
                if inj is not None:
                    inj.fire("dispatch", shard=0, device=0)
                backend = self.engine.backend
                fut = _launch(_desc_step, backend, *self._dev, words,
                              self._idx, self.engine.mesh,
                              self.search_iters, self.desc_iters, backend,
                              self.orient, self.prune_self)
                poisoned = inj.take_poison() if inj is not None else False
                return fut, poisoned

            fut, poisoned = _dispatch_retrying_session(self, dispatch)
            if pending is not None:
                land(pending)
            pending = (fut, poisoned, dispatch)
        if pending is not None:
            land(pending)
        return hist_acc, inter_acc, chunk_items

    def _slices(self, item_pair, item_slot, item_side):
        """Yield materialized items in ``chunk_shape``-sized batches."""
        cs = self.chunk_shape
        for lo in range(0, int(item_pair.shape[0]), cs):
            yield (item_pair[lo:lo + cs], item_slot[lo:lo + cs],
                   item_side[lo:lo + cs])

    def _subset(self, pair_ids: np.ndarray
                ) -> tuple[np.ndarray, int, list[int]]:
        """Contribution of a pair subset of the RESIDENT graph.  Host
        memory is O(subset items) under host emission and O(subset pairs)
        under device emission — bounded by the affected neighborhoods in
        the incremental path, not by the graph's full W."""
        base_asym, base_mut = base_for_pairs(self._space, pair_ids)
        if self.emit == "device":
            ids = np.asarray(pair_ids, dtype=np.int64).ravel()
            hist, inter, chunk_items = self._run_desc_batches(_emit_spans(
                subset_descriptor_windows(self._space, ids,
                                          self.chunk_shape,
                                          self.desc_shape,
                                          self.num_anchors), self))
            return (contribution_counts(base_asym, base_mut, hist, inter),
                    int(sum(chunk_items)), chunk_items)
        with span("chunk.emit", self, "_t_emit", census=self._census_id):
            items = emit_items_for_pairs(self._space, pair_ids)
        num_items = int(items[0].shape[0])
        if num_items == 0:
            return (contribution_counts(base_asym, base_mut,
                                        np.zeros(64, np.int64),
                                        np.zeros(2, np.int64)), 0, [])
        hist, inter, chunk_items = self._run_batches(self._slices(*items))
        return (contribution_counts(base_asym, base_mut, hist, inter),
                num_items, chunk_items)

    def _postprune_items(self) -> int:
        """Full-recompute item count of the resident graph, computed at
        most once per graph revision.  The index's maintained per-pair
        cost vector answers it with an O(P) sum; the rebuild oracle pays
        the O(m + P log m) degree-orient closed-form scan instead."""
        if self._full_items is None:
            if self.use_index and self._pair_index is not None:
                self._full_items = int(self._pair_index.costs.sum())
            else:
                self._full_items = self._space.num_items_postprune()
        return self._full_items

    def _cache_size(self) -> int:
        """Compile counter of the jitted step this session dispatches
        through (the descriptor step under device emission)."""
        return _jit_cache_size(
            _desc_step if self.emit == "device" else self._step)

    def _set_stats(self, chunk_items: list[int], items: int,
                   full_items: int, affected_pairs: int,
                   compiles: int) -> None:
        ndev = self.engine.ndev
        capacity_recompiles, compiles = _split_capacity_compiles(
            self, chunk_items, compiles)
        gbytes = replicated_graph_bytes(self._space)
        self.stats = EngineStats(
            backend=self.engine.backend, ndev=ndev, orient=self.orient,
            streamed=True, max_items=self.max_items,
            chunks=len(chunk_items), chunk_shape=self.chunk_shape,
            items=items, chunk_items=chunk_items,
            peak_plan_bytes=ITEM_BYTES * self.chunk_shape,
            monolithic_plan_bytes=ITEM_BYTES
            * (-(-full_items // ndev) * ndev),
            step_compiles=compiles,
            full_items=full_items, affected_pairs=affected_pairs,
            emit=self.emit,
            desc_shape=self.desc_shape or 0,
            # physical per-device plan bytes: descriptor windows are
            # replicated, item arrays sharded over the mesh
            plan_upload_bytes=(
                DESC_BYTES * self.desc_shape + 4 * self.num_anchors + 4
                if self.emit == "device"
                else ITEM_BYTES * self.chunk_shape // ndev),
            capacity_recompiles=capacity_recompiles,
            retries=self.retries,
            graph_resident_bytes=gbytes, graph_replicated_bytes=gbytes,
            host_pair_seconds=self._t_pair,
            host_merge_seconds=self._t_merge,
            host_emit_seconds=self._t_emit, indexed=self.use_index)
        self._t_pair = self._t_merge = self._t_emit = 0.0
        self.engine.stats = self.stats

    def census(self) -> np.ndarray:
        """Full census of the resident graph; (re)bases the session's
        running C_k that :meth:`update` moves forward.  Under host
        emission items are emitted per pre-prune slice of ``chunk_shape``
        (host plan memory O(chunk_shape), never O(W)); under device
        emission only descriptor windows are built — O(pairs-per-window)
        host memory and upload."""
        self._check_open()
        self._census_id = next(self.engine._census_ids)
        space = self._space
        cache0 = self._cache_size()
        w0 = space.num_items_preprune
        cs = self.chunk_shape
        if self.emit == "device":
            hist, inter, chunk_items = self._run_desc_batches(_emit_spans(
                iter_descriptor_windows(space.offsets, cs,
                                        self.desc_shape,
                                        self.num_anchors), self))
        else:
            hist, inter, chunk_items = self._run_batches(_emit_spans(
                (emit_items(space, lo, min(lo + cs, w0))
                 for lo in range(0, w0, cs)), self))
        base_asym, base_mut = global_bases(space)
        self._census = assemble_counts(self.n, base_asym, base_mut,
                                       hist, inter)
        num_items = int(sum(chunk_items))
        self._full_items = num_items      # the full census just counted it
        self._set_stats(chunk_items, num_items, num_items,
                        space.num_pairs,
                        self._cache_size() - cache0)
        return self._census.copy()

    def update(self, add_src=None, add_dst=None,
               del_src=None, del_dst=None) -> np.ndarray:
        """Apply an edge delta and return the edited graph's census,
        recounting only the affected pairs — bit-identical to a
        from-scratch census of the new graph on any backend."""
        self._check_open()
        if self._census is None:
            raise RuntimeError(
                "no baseline census: call census() before update()")
        self._census_id = next(self.engine._census_ids)
        cache0 = self._cache_size()
        with span("delta.merge", self, "_t_merge", census=self._census_id):
            g_new, delta = apply_delta(self._g, add_src, add_dst,
                                       del_src, del_dst)
        self.last_delta = delta
        if delta.num_changed == 0:
            # nothing changed: no recount, no descriptor/item upload, no
            # device dispatch — the running census is already the answer
            self._set_stats([], 0, self._postprune_items(), 0,
                            self._cache_size() - cache0)
            return self._census.copy()

        with span("census.plan", self, "_t_pair", census=self._census_id):
            aff_old = (self._pair_index.affected_pair_ids(delta.touched)
                       if self.use_index
                       else affected_pair_ids(self._space, delta.touched))
        contrib_old, items_old, chunks_old = self._subset(aff_old)
        if self.use_index:
            # edit the persistent index into the new graph's pair space
            # (O(delta · log P + affected)) instead of rebuilding O(P)
            with span("census.plan", self, "_t_pair",
                      census=self._census_id):
                space_new = self._pair_index.apply(delta, g_new)
            self._install(g_new, space=space_new)
        else:
            self._install(g_new)
        with span("census.plan", self, "_t_pair", census=self._census_id):
            aff_new = (self._pair_index.affected_pair_ids(delta.touched)
                       if self.use_index
                       else affected_pair_ids(self._space, delta.touched))
        contrib_new, items_new, chunks_new = self._subset(aff_new)
        self._census = combine(self._census, contrib_old, contrib_new,
                               self.n)
        self._set_stats(chunks_old + chunks_new, items_old + items_new,
                        self._postprune_items(),
                        int(aff_old.shape[0] + aff_new.shape[0]),
                        self._cache_size() - cache0)
        return self._census.copy()


class PartitionedEngineSession:
    """Partition-resident census session: each shard lives on its device,
    delta updates dispatch only the shards owning touched pairs.

    On open the graph's pair space is LPT-split into one private shard
    per mesh device (:mod:`repro.core.partition`); each shard's relabeled
    local CSR + pair arrays are uploaded once into fixed-capacity buffers
    committed to THAT device (capacities are common across shards and
    grown geometrically, so one compiled single-device step serves every
    shard and every graph revision — the binary-search depth is pinned to
    ``ceil(log2 n)`` exactly like :class:`EngineSession`).  Per-shard
    dispatches are independent and asynchronous, so devices overlap
    naturally; partials are merged on the host (the paper's 64 private
    census vectors, merged once).

    :meth:`update` applies an edge delta and routes the recount by
    ownership: the *affected pairs* (endpoint row changed) are looked up
    in each shard's sorted key set, only the owning shards re-count their
    slices (old contribution against the still-resident arrays, then new
    contribution after only those shards re-extract + re-upload), and
    **untouched shards dispatch nothing** — no descriptor/item upload, no
    device work, their resident subgraphs provably unchanged.  Pairs that
    appear in the delta are assigned to a shard already owning one of
    their endpoints' pairs (locality), else to the lightest shard.
    Bit-identical to a from-scratch census of the edited graph on every
    backend, orient and emit mode.

    Sustained churn drifts the locality-routed loads away from the LPT
    optimum (the spill cap bounds the drift at ~1.25x mean, but never
    restores balance).  :meth:`rebalance` re-sharding — a fresh LPT over
    the CURRENT pair space with every shard re-extracted + re-uploaded,
    like :meth:`set_graph` but keeping the running census valid (counts
    never depend on ownership) — restores ≈LPT balance;
    ``auto_rebalance_threshold`` triggers it automatically at the end of
    any :meth:`update` that leaves ``load_max_over_mean`` above the
    threshold (``rebalances`` counts the triggers).
    """

    def __init__(self, engine: CensusEngine, g: CompactDigraph, *,
                 orient: str = "none", prune_self: bool = True,
                 max_items: int | None = None, emit: str | None = None,
                 auto_rebalance_threshold: float | None = None,
                 index: bool = True):
        if max_items is not None and max_items < 1:
            raise ValueError(f"max_items must be >= 1, got {max_items}")
        if auto_rebalance_threshold is not None \
                and auto_rebalance_threshold < 1.0:
            raise ValueError(
                "auto_rebalance_threshold must be >= 1.0, got "
                f"{auto_rebalance_threshold}")
        emit = engine.emit if emit is None else emit
        if emit not in EMIT_MODES:
            raise ValueError(
                f"unknown emit mode {emit!r}; one of {EMIT_MODES}")
        self.auto_rebalance_threshold = (
            None if auto_rebalance_threshold is None
            else float(auto_rebalance_threshold))
        self.rebalances = 0
        self.engine = engine
        self.orient = orient
        self.prune_self = prune_self
        self.emit = emit
        self.n = g.n
        self.max_items = max_items
        self.ndev = engine.ndev
        self._devices = list(engine.mesh.devices.flat)
        #: pinned unrolled-search depth (see :class:`EngineSession`)
        self.search_iters = max(1, int(np.ceil(np.log2(max(g.n, 2)))))
        self._step = _chunk_step
        self._cap_n = self._cap_entries = self._cap_pairs = 0
        self._capacity_grew = False
        self.chunk_shape: int | None = None
        self.desc_shape: int | None = None
        self._census: np.ndarray | None = None
        self.last_delta: GraphDelta | None = None
        self.stats: EngineStats | None = None
        #: injected-fault runtime shared across this session's dispatches
        self._injector = (engine.faults.injector()
                          if engine.faults is not None else None)
        #: dispatches re-attempted after a fault, across the session's life
        self.retries = 0
        self._closed = False
        #: delta-incremental host planning (see :class:`EngineSession`)
        self.use_index = bool(index)
        self._pair_index: PairSpaceIndex | None = None
        #: host-phase counters and census id (see :class:`EngineSession`)
        self._t_pair = self._t_merge = self._t_emit = 0.0
        self._census_id = next(engine._census_ids)
        self._install_full(g)

    # ---------------------------------------------------------- lifecycle
    def close(self) -> None:
        """Release every shard's resident device buffers.  Idempotent;
        the session is unusable afterwards."""
        self._dev = [None] * self.ndev
        if hasattr(self, "_idx"):
            self._idx = None
        self._closed = True

    def __enter__(self) -> "PartitionedEngineSession":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.close()

    def _check_open(self) -> None:
        if self._closed:
            raise RuntimeError("session is closed")

    # ------------------------------------------------------- checkpointing
    def save_checkpoint(self, path: str) -> None:
        """Persist the running census + graph fingerprint (JSON); a new
        session over the same graph warm-resumes updates via
        :meth:`load_checkpoint` without recomputing the baseline.  The
        census never depends on the partition, so the restoring session
        may shard (1D/2D) however it likes."""
        _save_session_checkpoint(self, path)

    def load_checkpoint(self, path: str) -> np.ndarray:
        """Adopt a census saved by :meth:`save_checkpoint` (the resident
        graph must match its fingerprint); :meth:`update` continues
        bit-identically from it."""
        return _load_session_checkpoint(self, path)

    # ------------------------------------------------------------ state
    @property
    def graph(self) -> CompactDigraph:
        return self._g

    @property
    def space(self):
        """The GLOBAL pair space of the resident graph."""
        return self._space

    @property
    def shards(self):
        return list(self._shards)

    @property
    def counts(self) -> np.ndarray | None:
        return None if self._census is None else self._census.copy()

    def _install_full(self, g: CompactDigraph) -> None:
        """(Re)partition ``g`` from scratch and make every shard
        device-resident (session open and :meth:`set_graph`)."""
        self._g = g
        with span("census.plan", self, "_t_pair", census=self._census_id):
            if self.use_index:
                self._pair_index = PairSpaceIndex(
                    g, orient=self.orient, prune_self=self.prune_self)
                space = self._pair_index.space
            else:
                space = pair_space(g, orient=self.orient,
                                   prune_self=self.prune_self)
        self._space = space
        self._full_items: int | None = None
        part = self._make_partition(space)
        self._shards = list(part.shards)
        self._keys = [sh.keys for sh in self._shards]
        self._set_ownership(part)
        if self.chunk_shape is None:
            budget = (self.max_items if self.max_items is not None
                      else max(space.num_items_preprune, 1))
            self.chunk_shape = _guard_chunk_shape(
                -(-max(int(budget), 1) // self.ndev))
        if self.emit == "device" and self.desc_shape is None:
            cs = self.chunk_shape
            self.desc_shape = _desc_capacity(
                cs, max(max_pairs_per_window(sh.space.offsets, cs)
                        for sh in self._shards))
            self.desc_iters = DESC_SEARCH_ITERS
            self.num_anchors = num_desc_anchors(cs)
            self._idx = [
                jax.device_put(np.arange(cs, dtype=np.int32), d)
                for d in self._devices]
        self._dev: list = [None] * self.ndev
        self._upload_shards(range(self.ndev))

    # ----------------------------------------------- ownership hooks
    # The 2D session (:class:`PartitionedEngineSession2D`) overrides
    # these four: there a device holds a TILE (pair shard × vertex
    # slice) while ownership/load bookkeeping stays per pair shard.
    def _make_partition(self, space):
        """Partition ``space`` into the device-resident shard list."""
        return partition_graph(num_shards=self.ndev, space=space)

    def _set_ownership(self, part) -> None:
        """Record ownership/load bookkeeping from a fresh partition."""
        self._load = [sh.items for sh in self._shards]

    def _tile_shard(self, s: int) -> int:
        """Device/tile index → owning pair shard (identity in 1D)."""
        return s

    def _ownership(self) -> list:
        """Per pair shard sorted global key arrays (the reassignment
        target of :meth:`update`); the per-device dispatch key sets in
        1D, the per-shard sets distinct from ``_keys`` in 2D."""
        return self._keys

    def _upload_shards(self, shard_ids) -> None:
        """(Re)upload the listed shards' padded local arrays onto their
        devices; a capacity growth changes every shard's padded shapes,
        so it forces a full re-upload (and is accounted as a capacity
        recompile, never a step compile)."""
        need_n = max(max(sh.graph.indptr.shape[0]
                         for sh in self._shards), 2)
        need_e = max(max(sh.graph.packed.shape[0]
                         for sh in self._shards), 1)
        need_p = max(max(sh.num_pairs for sh in self._shards), 1)
        prev = (self._cap_n, self._cap_entries, self._cap_pairs)
        self._cap_n = EngineSession._grown(self._cap_n, need_n)
        self._cap_entries = EngineSession._grown(self._cap_entries,
                                                 need_e)
        self._cap_pairs = EngineSession._grown(self._cap_pairs, need_p)
        caps = (self._cap_n, self._cap_entries, self._cap_pairs)
        if prev != caps:
            if prev != (0, 0, 0):
                self._capacity_grew = True
            shard_ids = range(self.ndev)
        for s in shard_ids:
            sh = self._shards[s]
            ip = np.zeros(self._cap_n, dtype=np.int32)
            l = sh.graph.indptr.shape[0]
            ip[:l] = sh.graph.indptr
            ip[l:] = sh.graph.indptr[-1]      # phantom empty rows
            dev = self._devices[s]
            self._dev[s] = tuple(
                jax.device_put(a, dev) for a in (
                    ip, _pad_i32(sh.graph.packed, self._cap_entries),
                    _pad_i32(sh.space.pair_u.astype(np.int32),
                             self._cap_pairs),
                    _pad_i32(sh.space.pair_v.astype(np.int32),
                             self._cap_pairs),
                    _pad_i32(sh.space.pair_code, self._cap_pairs)))

    def set_graph(self, g: CompactDigraph) -> None:
        """Replace the resident graph wholesale: fresh LPT partition,
        every shard re-extracted + re-uploaded.  Invalidates the running
        census until :meth:`census` recomputes."""
        if g.n != self.n:
            raise ValueError(f"session is pinned to n={self.n}, got {g.n}")
        self._census_id = next(self.engine._census_ids)
        self._install_full(g)
        self._census = None
        self.last_delta = None

    @property
    def load_max_over_mean(self) -> float:
        """Current shard load imbalance (post-prune items; 1.0 ==
        perfectly balanced) — the quantity ``auto_rebalance_threshold``
        is compared against after every update."""
        total = sum(self._load)
        if not total:
            return 1.0
        return max(self._load) / (total / len(self._load))

    def rebalance(self) -> None:
        """Re-shard the CURRENT resident graph with a fresh LPT (the
        :meth:`set_graph` ownership reset without the graph change):
        every shard re-extracts + re-uploads, restoring ≈LPT balance
        after churn has drifted the locality-routed loads.  The running
        census — and the pair space — are untouched: the census never
        depends on which shard owns a pair, so no recount is needed and
        :meth:`update` continues bit-identically from here."""
        part = self._make_partition(self._space)
        self._shards = list(part.shards)
        self._keys = [sh.keys for sh in self._shards]
        self._set_ownership(part)
        self._upload_shards(range(self.ndev))
        self.rebalances += 1

    def _maybe_rebalance(self) -> None:
        if self.auto_rebalance_threshold is not None and \
                self.load_max_over_mean > self.auto_rebalance_threshold:
            self.rebalance()

    # ---------------------------------------------------------- running
    def _dispatch_desc(self, s: int, win):
        """One descriptor window against shard ``s``'s resident arrays,
        on shard ``s``'s device (single-device step, async).  Fires the
        session's fault-injection hooks around the upload and the step
        launch; returns ``(fut, poisoned)``."""
        inj = self._injector
        if inj is not None:
            inj.fire("upload", shard=s, device=s)
        words = jax.device_put(win.device_words(), self._devices[s])
        if inj is not None:
            inj.fire("dispatch", shard=s, device=s)
        backend = self.engine.backend
        fut = _launch(_desc_step, backend, *self._dev[s], words,
                      self._idx[s], None, self.search_iters,
                      self.desc_iters, backend, self.orient,
                      self.prune_self)
        return fut, (inj.take_poison() if inj is not None else False)

    def _dispatch_items(self, s: int, item_pair, item_slot, item_side):
        """One packed-item window against shard ``s``'s resident arrays
        (host emission), on shard ``s``'s device; returns
        ``(fut, poisoned)`` like :meth:`_dispatch_desc`."""
        item_sp, item_pv = pad_and_pack(item_pair, item_slot, item_side,
                                        self.chunk_shape)
        dev = self._devices[s]
        inj = self._injector
        if inj is not None:
            inj.fire("upload", shard=s, device=s)
        sp_dev = jax.device_put(item_sp, dev)
        pv_dev = jax.device_put(item_pv, dev)
        if inj is not None:
            inj.fire("dispatch", shard=s, device=s)
        backend = self.engine.backend
        fut = _launch(self._step, backend, *self._dev[s], sp_dev, pv_dev,
                      None, self.search_iters, backend)
        return fut, (inj.take_poison() if inj is not None else False)

    def _shard_jobs(self, s: int, pair_ids=None):
        """Yield shard ``s``'s dispatch jobs: its full stream
        (``pair_ids=None``) or an arbitrary local pair subset.  Each job
        is ``(fut, poisoned, redo, num_or_None)`` — ``redo`` re-dispatches
        the same window (the landing-side retry handle), ``num`` is the
        item count under host emission and ``None`` under device emission
        (counts come back from the device).  Dispatch-time faults are
        retried here under the engine's budget."""
        sp = self._shards[s].space
        cs = self.chunk_shape
        if self.emit == "device":
            wins = _emit_spans(
                iter_descriptor_windows(sp.offsets, cs,
                                        self.desc_shape,
                                        self.num_anchors)
                if pair_ids is None else
                subset_descriptor_windows(sp, pair_ids, cs,
                                          self.desc_shape,
                                          self.num_anchors), self, shard=s)
            for win in wins:
                if win.num_preprune == 0:
                    continue

                def redo(win=win, s=s):
                    return _dispatch_retrying_session(
                        self, lambda: self._dispatch_desc(s, win))

                fut, poisoned = redo()
                yield fut, poisoned, redo, None
            return
        if pair_ids is None:
            w0 = sp.num_items_preprune
            batches = _emit_spans((emit_items(sp, lo, min(lo + cs, w0))
                                   for lo in range(0, w0, cs)), self,
                                  shard=s)
        else:
            with span("chunk.emit", self, "_t_emit",
                      census=self._census_id, shard=s):
                items = emit_items_for_pairs(sp, pair_ids)
            batches = (
                (items[0][lo:lo + cs], items[1][lo:lo + cs],
                 items[2][lo:lo + cs])
                for lo in range(0, max(int(items[0].shape[0]), 1), cs))
        for batch in batches:
            num = int(batch[0].shape[0])
            if num == 0:
                continue

            def redo(batch=batch, s=s):
                return _dispatch_retrying_session(
                    self, lambda: self._dispatch_items(s, *batch))

            fut, poisoned = redo()
            yield fut, poisoned, redo, num

    def _job_stream(self, s: int, pair_ids=None):
        """Shard ``s``'s jobs tagged with their shard id (a bound helper,
        so per-shard generators never share a loop variable)."""
        for fut, poisoned, redo, num in self._shard_jobs(s, pair_ids):
            yield s, fut, poisoned, redo, num

    def _land(self, futs, hist_acc, inter_acc, chunk_items, shard_items):
        """Accumulate ``(shard, fut, poisoned, redo, num_or_None)``
        results, re-dispatching through ``redo`` on fetch failures or
        corrupted partials (the landing half of the session retry)."""
        for s, fut, poisoned, redo, num in futs:
            hist, inter = _land_retrying_session(self, fut, poisoned,
                                                 redo)
            if num is None:
                inter_acc += inter[:2]
                num = int(inter[2])
            else:
                inter_acc += inter
            hist_acc += hist
            chunk_items.append(num)
            shard_items[s] += num

    def _drain(self, streams, hist_acc, inter_acc, chunk_items,
               shard_items) -> None:
        """Pull per-shard job streams round-robin (every device gets fed
        each cycle) with a bounded in-flight window: at most
        ``2 * ndev`` dispatches — and their chunk-shaped buffers — are
        pending at once, so host and device memory stay
        O(ndev · chunk_shape), never O(W) (the memory contract
        ``max_items`` promises, matching :class:`EngineSession`'s
        depth-1 pipelining)."""
        limit = 2 * self.ndev
        pending: deque = deque()
        active = list(streams)
        while active:
            alive = []
            for it in active:
                job = next(it, None)
                if job is None:
                    continue
                alive.append(it)
                pending.append(job)
                if len(pending) > limit:
                    self._land([pending.popleft()], hist_acc, inter_acc,
                               chunk_items, shard_items)
            active = alive
        self._land(pending, hist_acc, inter_acc, chunk_items,
                   shard_items)

    def _cache_size(self) -> int:
        return _jit_cache_size(
            _desc_step if self.emit == "device" else self._step)

    def _postprune_items(self) -> int:
        if self._full_items is None:
            if self.use_index and self._pair_index is not None:
                self._full_items = int(self._pair_index.costs.sum())
            else:
                self._full_items = self._space.num_items_postprune()
        return self._full_items

    def _set_stats(self, chunk_items, shard_items, items, full_items,
                   affected_pairs, compiles) -> None:
        capacity_recompiles, compiles = _split_capacity_compiles(
            self, chunk_items, compiles)
        self.stats = EngineStats(
            backend=self.engine.backend, ndev=self.ndev,
            orient=self.orient, streamed=True, max_items=self.max_items,
            chunks=len(chunk_items), chunk_shape=self.chunk_shape,
            items=items, chunk_items=chunk_items,
            peak_plan_bytes=ITEM_BYTES * self.chunk_shape,
            monolithic_plan_bytes=ITEM_BYTES
            * (-(-full_items // self.ndev) * self.ndev),
            step_compiles=compiles,
            full_items=full_items, affected_pairs=affected_pairs,
            emit=self.emit, desc_shape=self.desc_shape or 0,
            plan_upload_bytes=(
                DESC_BYTES * self.desc_shape + 4 * self.num_anchors + 4
                if self.emit == "device"
                else ITEM_BYTES * self.chunk_shape),
            capacity_recompiles=capacity_recompiles,
            retries=self.retries,
            partitioned=True,
            partition_shape=getattr(self, "mesh_shape", None),
            shard_items=shard_items,
            graph_resident_bytes=max(sh.resident_bytes
                                     for sh in self._shards),
            graph_replicated_bytes=replicated_graph_bytes(self._space),
            host_pair_seconds=self._t_pair,
            host_merge_seconds=self._t_merge,
            host_emit_seconds=self._t_emit, indexed=self.use_index)
        self._t_pair = self._t_merge = self._t_emit = 0.0
        self.engine.stats = self.stats

    def census(self) -> np.ndarray:
        """Full census of the resident graph: every shard walks its own
        stream on its own device, partials merge on the host.  (Re)bases
        the running C_k that :meth:`update` moves forward."""
        self._check_open()
        self._census_id = next(self.engine._census_ids)
        cache0 = self._cache_size()
        hist_acc = np.zeros(64, np.int64)
        inter_acc = np.zeros(2, np.int64)
        chunk_items: list[int] = []
        shard_items = [0] * self.ndev
        self._drain([self._job_stream(s) for s in range(self.ndev)],
                    hist_acc, inter_acc, chunk_items, shard_items)
        base_asym, base_mut = global_bases(self._space)
        self._census = assemble_counts(self.n, base_asym, base_mut,
                                       hist_acc, inter_acc)
        items = int(sum(chunk_items))
        self._full_items = items
        self._set_stats(chunk_items, shard_items, items, items,
                        self._space.num_pairs,
                        self._cache_size() - cache0)
        return self._census.copy()

    def _recount(self, aff_keys, chunk_items, shard_items,
                 touched_owner=None, touched=None):
        """Contribution of the affected pairs, recounted shard by shard
        on the CURRENT resident arrays; shards owning none of them are
        never dispatched.  Returns (contribution, dirty shard ids)."""
        base_asym = base_mut = 0
        streams = []
        dirty = []
        for s in range(self.ndev):
            loc = np.nonzero(np.isin(self._keys[s], aff_keys,
                                     assume_unique=True))[0]
            if loc.size == 0:
                continue
            dirty.append(s)
            sh = self._shards[s]
            if touched_owner is not None:
                # remember which shard owns each touched vertex's pairs —
                # appeared pairs are assigned for locality from this map
                gids = sh.pair_ids[loc]
                for u in np.intersect1d(
                        np.concatenate([self._space.pair_u[gids],
                                        self._space.pair_v[gids]]),
                        touched).tolist():
                    touched_owner.setdefault(int(u),
                                             self._tile_shard(s))
            ba, bm = base_for_pairs(sh.space, loc)
            base_asym += ba
            base_mut += bm
            streams.append(self._job_stream(s, loc))
        hist = np.zeros(64, np.int64)
        inter = np.zeros(2, np.int64)
        self._drain(streams, hist, inter, chunk_items, shard_items)
        return contribution_counts(base_asym, base_mut, hist, inter), \
            dirty

    def _refresh_shards(self, dirty, space_new, key_all_new,
                        costs_new=None) -> None:
        """Re-extract + re-upload the dirty pair shards against the new
        space; untouched shards keep their device buffers verbatim.
        ``costs_new`` is the per-pair post-prune cost vector — the
        maintained one from the session's index when available, else one
        global scan shared by every dirty shard's refresh (extract_shard
        would otherwise recount it per shard)."""
        if costs_new is None:
            costs_new = postprune_pair_counts(space_new)
        for s in dirty:
            ids = np.searchsorted(key_all_new, self._keys[s])
            self._shards[s] = extract_shard(space_new, ids, index=s,
                                            costs=costs_new)
            self._load[s] = self._shards[s].items
        self._upload_shards(dirty)

    def update(self, add_src=None, add_dst=None,
               del_src=None, del_dst=None) -> np.ndarray:
        """Apply an edge delta and return the edited graph's census.

        Only the shards owning affected pairs recount (old contribution
        on their still-resident arrays, new contribution after refresh);
        every other shard keeps its device buffers untouched and
        dispatches nothing.  Bit-identical to a from-scratch census."""
        self._check_open()
        if self._census is None:
            raise RuntimeError(
                "no baseline census: call census() before update()")
        self._census_id = next(self.engine._census_ids)
        cache0 = self._cache_size()
        with span("delta.merge", self, "_t_merge", census=self._census_id):
            g_new, delta = apply_delta(self._g, add_src, add_dst,
                                       del_src, del_dst)
        self.last_delta = delta
        if delta.num_changed == 0:
            self._set_stats([], [0] * self.ndev, 0,
                            self._postprune_items(), 0,
                            self._cache_size() - cache0)
            return self._census.copy()

        n = self.n
        space_old = self._space
        with span("census.plan", self, "_t_pair", census=self._census_id):
            if self.use_index:
                aff_old = self._pair_index.affected_pair_ids(delta.touched)
            else:
                aff_old = affected_pair_ids(space_old, delta.touched)
            aff_keys_old = (space_old.pair_u * n
                            + space_old.pair_v)[aff_old]
        chunk_items: list[int] = []
        shard_items = [0] * self.ndev
        touched_owner: dict[int, int] = {}
        contrib_old, dirty_old = self._recount(
            aff_keys_old, chunk_items, shard_items,
            touched_owner=touched_owner, touched=delta.touched)

        # ---- reassign ownership and refresh only the dirty shards
        self._g = g_new
        with span("census.plan", self, "_t_pair", census=self._census_id):
            if self.use_index:
                # edit the persistent index into the new pair space
                # (O(delta · log P + affected)) instead of rebuilding
                # O(P); its maintained keys/costs also feed the owner
                # routing and the dirty-shard refresh below
                space_new = self._pair_index.apply(delta, g_new)
                key_all_new = self._pair_index.keys
                costs_new = self._pair_index.costs
            else:
                space_new = pair_space(g_new, orient=self.orient,
                                       prune_self=self.prune_self)
                key_all_new = space_new.pair_u * n + space_new.pair_v
                costs_new = None
        self._space = space_new
        self._full_items = None
        dkeys = delta.pair_lo * n + delta.pair_hi
        vanished = dkeys[delta.new_code == 0]
        appeared = dkeys[delta.old_code == 0]
        okeys = self._ownership()
        # dirty is tracked per PAIR SHARD (== per device in 1D; a 2D
        # shard refreshes all of its vertex-slice tiles together so the
        # designated base-term slice stays consistent within the shard)
        dirty = {self._tile_shard(t) for t in dirty_old}
        if vanished.size:
            for s in sorted(dirty):  # vanished pairs were affected-old
                okeys[s] = np.setdiff1d(okeys[s], vanished,
                                        assume_unique=True)
        if appeared.size:
            pending: dict[int, list[int]] = {}
            # locality first — an appeared pair joins the shard already
            # owning its endpoints' pairs — but only while that shard is
            # within 1.25x of the mean load; past it, spill to the
            # lightest shard so sustained churn cannot concentrate the
            # whole pair space onto one device
            cap = 1.25 * (sum(self._load) / len(self._load)) + 1.0
            for k in appeared.tolist():
                u, v = divmod(k, n)
                s = touched_owner.get(u, touched_owner.get(v))
                if s is None or self._load[s] > cap:
                    s = int(np.argmin(self._load))
                touched_owner.setdefault(u, s)
                touched_owner.setdefault(v, s)
                idx = int(np.searchsorted(key_all_new, k))
                self._load[s] += int(space_new.counts[idx])
                pending.setdefault(s, []).append(k)
            for s, ks in pending.items():
                okeys[s] = np.union1d(okeys[s],
                                      np.asarray(ks, np.int64))
                dirty.add(s)
        self._refresh_shards(sorted(dirty), space_new, key_all_new,
                             costs_new)

        # ---- new-side recount (owners of every affected new pair are,
        # by construction, in the refreshed dirty set)
        with span("census.plan", self, "_t_pair", census=self._census_id):
            if self.use_index:
                aff_new = self._pair_index.affected_pair_ids(delta.touched)
            else:
                aff_new = affected_pair_ids(space_new, delta.touched)
            aff_keys_new = key_all_new[aff_new]
        contrib_new, _ = self._recount(
            aff_keys_new, chunk_items, shard_items)
        self._census = combine(self._census, contrib_old, contrib_new,
                               self.n)
        self._set_stats(chunk_items, shard_items,
                        int(sum(chunk_items)),
                        self._postprune_items(),
                        int(aff_old.shape[0] + aff_new.shape[0]),
                        self._cache_size() - cache0)
        self._maybe_rebalance()
        return self._census.copy()


class PartitionedEngineSession2D(PartitionedEngineSession):
    """2D-partition-resident session: device = tile (pair shard × vertex
    slice), ownership = pair shard.

    Every device-facing mechanism of :class:`PartitionedEngineSession`
    — fixed-capacity grow-once buffers, async per-tile dispatch, the
    bounded-in-flight drain, host int64 merge — runs verbatim over the
    flat tile list (tile ``(s, j)`` at device ``s * V + j``).  What the
    second axis changes is *bookkeeping*: a pair belongs to one pair
    shard (``_ownership`` tracks per-shard key sets), its items split
    across that shard's ``V`` tiles by witness vertex range, and its
    closed-form base term is credited to one designated tile
    (:func:`repro.core.partition.slice_pair_terms`) so per-tile bases
    stay subset-additive.

    :meth:`update` routes deltas to ``(owner shard, touched slices)``:
    affected pairs recount only on the tiles whose vertex slice actually
    holds some of their items (a tile without them never appears in the
    key lookup, so it dispatches nothing), and a dirty shard re-extracts
    all of its slice tiles together against the session's pinned vertex
    bounds, keeping each pair's designated-slice term consistent within
    the shard.  Bit-identical to the 1D and unpartitioned sessions on
    every backend, orient and emit mode.
    """

    def __init__(self, engine: CensusEngine, g: CompactDigraph, *,
                 mesh_shape: tuple, **kwargs):
        mesh_shape = (int(mesh_shape[0]), int(mesh_shape[1]))
        if mesh_shape[0] * mesh_shape[1] != engine.ndev:
            raise ValueError(
                f"mesh_shape {mesh_shape} needs "
                f"{mesh_shape[0] * mesh_shape[1]} devices; the engine "
                f"has {engine.ndev}")
        self.mesh_shape = mesh_shape
        super().__init__(engine, g, **kwargs)

    def _make_partition(self, space):
        return partition_graph_2d(space=space,
                                  mesh_shape=self.mesh_shape)

    def _set_ownership(self, part) -> None:
        num_shards, num_slices = self.mesh_shape
        self._vertex_bounds = np.asarray(part.vertex_bounds,
                                         dtype=np.int64)
        space = part.space
        key_all = (space.pair_u.astype(np.int64) * space.n
                   + space.pair_v)
        self._shard_keys = [np.sort(key_all[part.owner == s])
                            for s in range(num_shards)]
        self._load = [sum(self._shards[s * num_slices + j].items
                          for j in range(num_slices))
                      for s in range(num_shards)]

    def _tile_shard(self, s: int) -> int:
        return s // self.mesh_shape[1]

    def _ownership(self) -> list:
        return self._shard_keys

    def _refresh_shards(self, dirty, space_new, key_all_new,
                        costs_new=None) -> None:
        """Re-extract every vertex-slice tile of each dirty pair shard
        against the session's pinned slice bounds (one shard's tiles are
        a unit: the designated base-term slice of any of its pairs must
        agree across them), then re-upload just those tiles.
        ``costs_new`` (the 1D session's maintained global cost vector) is
        ignored: tile costs are range-restricted per vertex slice, so
        they are recomputed here — the index still supplies the space
        itself, which is where the rebuild time went."""
        num_slices = self.mesh_shape[1]
        bounds = self._vertex_bounds
        terms = slice_pair_terms(space_new, bounds)
        slice_costs = [range_postprune_pair_counts(
            space_new, int(bounds[j]), int(bounds[j + 1]))
            for j in range(num_slices)]
        tiles = []
        for s in dirty:
            ids = np.searchsorted(key_all_new, self._shard_keys[s])
            load = 0
            for j in range(num_slices):
                t = s * num_slices + j
                sh = extract_shard(
                    space_new, ids, index=t, costs=slice_costs[j],
                    vertex_range=(int(bounds[j]), int(bounds[j + 1])),
                    pair_term=terms[j])
                self._shards[t] = sh
                self._keys[t] = sh.keys
                load += sh.items
                tiles.append(t)
            self._load[s] = load
        self._upload_shards(tiles)
