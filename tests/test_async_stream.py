"""Async per-shard streams: no inter-shard barrier, pipelined host side.

The tentpole contract:

* **Bit-identity** — ``schedule="async"`` (independent per-device
  dispatches, host int64 merge) equals ``schedule="lockstep"`` (the
  collective psum oracle) equals the reference census, across
  1/2/4/8-device meshes × both orients × both emit modes, on balanced,
  skewed and empty-shard partitions.  Integer sums make the merge order
  unobservable.
* **No cross-shard synchronization** — the async path never enters the
  collective lock-step primitives (``_part_desc_step`` /
  ``_part_chunk_step``); each window is a single-device dispatch.
* **Skew** — a shard with 4× everyone else's chunk queue finishes late
  WITHOUT holding the other shards' queues: total dispatches equal the
  sum of real windows, not ``ndev × max``.
* **Stats** — per-shard step counts, stall/idle counters, pipeline depth
  and per-shard upload attribution are exact under both schedules.
"""

import numpy as np
import pytest

from repro.core import (
    CensusEngine, ShardStreamPipeline, census_batagelj_mrvar,
    default_mesh, lpt_assign_heap, pair_space, partition_graph,
    scale_free_digraph, triad_census_graph)
from repro.core.plan_stream import ShardSchedule


def pl_graph(n=100, deg=5, seed=7):
    return scale_free_digraph(n=n, avg_degree=deg, exponent=2.2,
                              mutual_p=0.3, seed=seed)


def skewed_partition(g, num_shards, factor=4.0, orient="none"):
    """Deliberately imbalanced partition: shard 0 gets the heaviest
    pairs until it holds ``factor``× each other shard's pre-prune items
    (and therefore ~``factor``× the chunk-queue length); the rest are
    LPT-balanced across shards 1..ns-1."""
    space = pair_space(g, orient=orient)
    costs = space.counts.astype(np.int64)     # pre-prune items per pair
    order = np.argsort(-costs, kind="stable")
    total = int(costs.sum())
    target0 = total * factor / (factor + (num_shards - 1))
    csum = np.cumsum(costs[order])
    k = int(np.searchsorted(csum, target0)) + 1
    owner = np.empty(space.num_pairs, np.int64)
    owner[order[:k]] = 0
    rest = order[k:]
    owner[rest] = 1 + lpt_assign_heap(costs[rest], num_shards - 1)
    return partition_graph(num_shards=num_shards, space=space,
                           owner=owner)


# ----------------------------------------------------------- pipeline


class TestShardStreamPipeline:
    def test_yields_every_window_tagged_with_shard(self):
        srcs = [iter([10, 11]), iter([20]), iter([30, 31, 32])]
        pipe = ShardStreamPipeline(srcs, depth=2)
        got = sorted(pipe)
        pipe.close()
        assert got == [(0, 10), (0, 11), (1, 20), (2, 30), (2, 31),
                       (2, 32)]

    def test_empty_sources(self):
        pipe = ShardStreamPipeline([iter([]), iter([1]), iter([])])
        assert sorted(pipe) == [(1, 1)]
        pipe.close()

    def test_skewed_sources_no_barrier(self):
        """A 1-window shard ends after its window; the 8-window shard
        keeps streaming — consumption order can interleave but never
        waits for the long shard to finish a 'step'."""
        pipe = ShardStreamPipeline(
            [iter(range(8)), iter([100])], depth=2)
        got = list(pipe)
        pipe.close()
        assert got.count((1, 100)) == 1
        assert [w for s, w in got if s == 0] == list(range(8))

    def test_producer_exception_reraises_in_consumer(self):
        def bad():
            yield 1
            raise RuntimeError("producer blew up")

        pipe = ShardStreamPipeline([bad(), iter([2])])
        with pytest.raises(RuntimeError, match="blew up"):
            for _ in pipe:
                pass
        pipe.close()

    def test_slow_producer_counts_stalls(self):
        import time

        def slow():
            for i in range(3):
                time.sleep(0.05)
                yield i

        pipe = ShardStreamPipeline([slow()], depth=2)
        assert [w for _, w in pipe] == [0, 1, 2]
        assert pipe.stalls >= 1
        pipe.close()

    def test_close_is_idempotent_and_unblocks_producers(self):
        pipe = ShardStreamPipeline([iter(range(10_000))], depth=1)
        next(iter(pipe))
        pipe.close()
        pipe.close()
        assert all(not t.is_alive() for t in pipe._threads)

    def test_depth_validation(self):
        with pytest.raises(ValueError, match="depth"):
            ShardStreamPipeline([iter([])], depth=0)


# ----------------------------------------------------- shard schedule


class TestPerShardSchedule:
    def test_steps_for_and_totals(self):
        g = pl_graph(n=90, seed=3)
        part = skewed_partition(g, 4)
        sched = ShardSchedule([sh.space for sh in part.shards], 200, 4)
        steps = sched.shard_steps
        assert steps == [sched.steps_for(s) for s in range(4)]
        assert sched.num_steps == max(steps)
        assert sched.total_windows == sum(steps)
        # the skew helper really skews the queue lengths
        assert steps[0] >= 3 * max(steps[1:])

    def test_shard_step_items_tile_the_shard(self):
        g = pl_graph(n=60, seed=9)
        part = partition_graph(g, 3)
        sched = ShardSchedule([sh.space for sh in part.shards], 100, 3)
        for s in range(3):
            total = 0
            for k in range(sched.steps_for(s)):
                sp, pv, num = sched.shard_step_items(s, k)
                assert sp.shape == (sched.chunk_shape,)
                total += num
            assert total == part.shards[s].items


# -------------------------------------------------------- bit-identity


class TestAsyncBitIdentity:
    @pytest.mark.parametrize("num_devices", [1, 2, 4, 8])
    @pytest.mark.parametrize("orient", ["none", "degree"])
    @pytest.mark.parametrize("emit", ["device", "host"])
    def test_async_equals_lockstep_and_reference(self, num_devices,
                                                 orient, emit):
        g = pl_graph(n=70, seed=5)
        want = census_batagelj_mrvar(g)
        got = {}
        for sched in ("async", "lockstep"):
            engine = CensusEngine(mesh=default_mesh(num_devices),
                                  backend="jnp", partition=True,
                                  emit=emit, schedule=sched)
            got[sched] = engine.run(g, max_items=120, orient=orient)
        np.testing.assert_array_equal(got["async"], want)
        np.testing.assert_array_equal(got["async"], got["lockstep"])

    @pytest.mark.parametrize("emit", ["device", "host"])
    def test_skewed_partition_bit_identical(self, emit):
        g = pl_graph(n=90, seed=11)
        want = census_batagelj_mrvar(g)
        part = skewed_partition(g, 4)
        for sched in ("async", "lockstep"):
            engine = CensusEngine(mesh=default_mesh(4), backend="jnp",
                                  partition=True, emit=emit,
                                  schedule=sched)
            got = engine.run(g, max_items=200, part=part)
            np.testing.assert_array_equal(got, want)
        # async dispatched only the real windows: Σ steps, not ndev×max
        st = engine.stats          # lockstep (last): padded idle steps
        assert st.idle_steps > 0

    def test_empty_shards_both_schedules(self):
        g = pl_graph(n=50, seed=13)
        want = census_batagelj_mrvar(g)
        space = pair_space(g)
        owner = np.zeros(space.num_pairs, np.int64)   # all pairs → 0
        part = partition_graph(num_shards=4, space=space, owner=owner)
        for sched in ("async", "lockstep"):
            engine = CensusEngine(mesh=default_mesh(4), backend="jnp",
                                  partition=True, schedule=sched)
            got = engine.run(g, max_items=150, part=part)
            np.testing.assert_array_equal(got, want)
            assert engine.stats.shard_steps[1:] == [0, 0, 0]

    @pytest.mark.parametrize("backend", ["pallas", "pallas-fused"])
    def test_async_backends(self, backend):
        g = pl_graph(n=40, deg=4, seed=8)
        want = census_batagelj_mrvar(g)
        engine = CensusEngine(mesh=default_mesh(4), backend=backend,
                              partition=True, schedule="async")
        np.testing.assert_array_equal(engine.run(g), want)
        np.testing.assert_array_equal(engine.run(g, max_items=80), want)

    def test_monolithic_schedule_async(self):
        """max_items=None still works: one window per shard."""
        g = pl_graph(n=60, seed=19)
        got = triad_census_graph(g, mesh=default_mesh(4),
                                 partition=True, schedule="async")
        np.testing.assert_array_equal(got, census_batagelj_mrvar(g))

    def test_schedule_validation(self):
        with pytest.raises(ValueError, match="schedule"):
            CensusEngine(mesh=default_mesh(2), partition=True,
                         schedule="bogus")
        engine = CensusEngine(mesh=default_mesh(2), partition=True)
        with pytest.raises(ValueError, match="schedule"):
            engine.run(pl_graph(n=20), schedule="bogus")

    def test_prebuilt_part_validation(self):
        g = pl_graph(n=30, seed=1)
        part = partition_graph(g, 2)
        with pytest.raises(ValueError, match="partition=True"):
            CensusEngine(mesh=default_mesh(2), backend="jnp").run(
                g, part=part)
        with pytest.raises(ValueError, match="shards"):
            CensusEngine(mesh=default_mesh(4), backend="jnp",
                         partition=True).run(g, part=part)


# ------------------------------------------------------ no-sync proof


class TestNoCrossShardSync:
    @pytest.mark.parametrize("emit", ["device", "host"])
    def test_async_never_enters_collective_step(self, emit, monkeypatch):
        """The lock-step path's collective primitives are the ONLY
        cross-shard synchronization points; poisoning them proves the
        async schedule never synchronizes shards between chunk steps."""
        import repro.core.engine as engine_mod

        def poison(*a, **k):
            raise AssertionError("async schedule entered the "
                                 "collective lock-step primitive")

        monkeypatch.setattr(engine_mod, "_part_desc_step", poison)
        monkeypatch.setattr(engine_mod, "_part_chunk_step", poison)
        g = pl_graph(n=70, seed=23)
        engine = CensusEngine(mesh=default_mesh(4), backend="jnp",
                              partition=True, emit=emit,
                              schedule="async")
        got = engine.run(g, max_items=150)
        np.testing.assert_array_equal(got, census_batagelj_mrvar(g))

    def test_lockstep_does_use_collective_step(self, monkeypatch):
        """Control for the poison test: the oracle path DOES go through
        the collective primitive."""
        import repro.core.engine as engine_mod
        calls = []
        real = engine_mod._part_desc_step

        def spy(*a, **k):
            calls.append(1)
            return real(*a, **k)

        spy._cache_size = real._cache_size
        monkeypatch.setattr(engine_mod, "_part_desc_step", spy)
        g = pl_graph(n=40, seed=23)
        engine = CensusEngine(mesh=default_mesh(4), backend="jnp",
                              partition=True, emit="device",
                              schedule="lockstep")
        engine.run(g, max_items=150)
        assert calls


# -------------------------------------------------------------- stats


class TestAsyncStats:
    def test_lockstep_vs_async_stats_regression(self):
        """Satellite: upload/step attribution under async.  Same census,
        same items, same per-shard step counts; both schedules attribute
        upload to REAL windows, with padding split into a separate
        counter (lock-step burns whole idle collective steps; async pads
        only ragged megabatch tails)."""
        g = pl_graph(n=90, seed=11)
        part = skewed_partition(g, 4)
        st = {}
        census = {}
        for sched in ("async", "lockstep"):
            engine = CensusEngine(mesh=default_mesh(4), backend="jnp",
                                  partition=True, emit="device",
                                  schedule=sched)
            census[sched] = engine.run(g, max_items=200, part=part)
            st[sched] = engine.stats
        a, l = st["async"], st["lockstep"]
        np.testing.assert_array_equal(census["async"],
                                      census["lockstep"])
        assert a.items == l.items > 0
        assert a.schedule == "async" and l.schedule == "lockstep"
        # identical queues, so identical per-shard step counts
        assert a.shard_steps == l.shard_steps
        sched_obj = ShardSchedule(
            [sh.space for sh in part.shards], 200, 4)
        assert a.shard_steps == sched_obj.shard_steps
        # async dispatches exactly the real windows; lock-step burns
        # whole collective steps on exhausted shards
        assert a.chunks == sum(a.shard_steps)
        assert a.idle_steps == 0
        assert l.idle_steps == 4 * max(l.shard_steps) \
            - sum(l.shard_steps) > 0
        # upload attribution: both schedules charge upload for REAL
        # windows only; lock-step's padded idle steps land in the pad
        # counter instead of inflating the upload total
        assert a.plan_upload_bytes_total == \
            a.plan_upload_bytes * sum(a.shard_steps)
        assert l.plan_upload_bytes_total == \
            l.plan_upload_bytes * sum(l.shard_steps)
        assert a.plan_upload_bytes_total == l.plan_upload_bytes_total
        assert l.plan_pad_bytes_total == \
            l.plan_upload_bytes * l.idle_steps > 0
        # async pad obeys the megabatch identity: cap × dispatches
        # minus real windows, all ragged-tail slots
        assert a.plan_pad_bytes_total == a.plan_upload_bytes * \
            (a.dispatch_batch_limit * a.dispatches_total
             - sum(a.shard_steps))
        # pipeline surface
        assert a.pipeline_depth == 2
        assert a.stall_steps >= 0
        assert "async" in a.summary() and "lockstep" in l.summary()
        # comparable lane footprint records
        assert a.peak_plan_bytes == l.peak_plan_bytes

    def test_async_compiles_once_per_device_not_per_step(self):
        """The stacked common-shape shard buffers mean one compiled step
        per DEVICE serves that shard's every window (jit keys on device
        placement, so the floor is ndev, never O(steps))."""
        g = pl_graph(n=90, seed=21)
        engine = CensusEngine(mesh=default_mesh(4), backend="jnp",
                              partition=True, schedule="async")
        engine.run(g, max_items=64)
        assert engine.stats.chunks >= 8
        assert engine.stats.step_compiles <= 4

    def test_host_emit_skips_fully_pruned_windows(self):
        """Host emission never dispatches a zero-valid window: chunks
        counts only real dispatches."""
        g = pl_graph(n=60, seed=29)
        engine = CensusEngine(mesh=default_mesh(4), backend="jnp",
                              partition=True, emit="host",
                              schedule="async")
        engine.run(g, max_items=100)
        st = engine.stats
        assert st.chunks == len(st.chunk_items)
        assert all(n > 0 for n in st.chunk_items)
