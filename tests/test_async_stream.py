"""Async per-shard streams: no inter-shard barrier, pipelined host side.

The tentpole contract:

* **Bit-identity** — the partitioned run (independent per-device
  dispatches, host int64 merge) equals the Batagelj–Mrvar reference and
  the single-device census, across 1/2/4/8-device meshes × both orients
  × both emit modes, on balanced, skewed and empty-shard partitions.
  Integer sums make the merge order unobservable.
* **No cross-shard synchronization** — every step a partitioned run
  launches takes arrays committed to one device; no input spans the
  mesh, so no collective can run.
* **Skew** — a shard with 4× everyone else's chunk queue finishes late
  WITHOUT holding the other shards' queues: total dispatches equal the
  sum of real windows, not ``ndev × max``.
* **Stats** — per-shard step counts, stall counters, pipeline depth and
  per-shard upload attribution are exact.
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
    def test_partitioned_equals_single_device_and_reference(
            self, num_devices, orient, emit):
        g = pl_graph(n=70, seed=5)
        want = census_batagelj_mrvar(g)
        engine = CensusEngine(mesh=default_mesh(num_devices),
                              backend="jnp", partition=True, emit=emit)
        got = engine.run(g, max_items=120, orient=orient)
        np.testing.assert_array_equal(got, want)
        single = CensusEngine(backend="jnp", emit=emit)
        np.testing.assert_array_equal(
            single.run(g, max_items=120, orient=orient), got)

    @pytest.mark.parametrize("emit", ["device", "host"])
    def test_skewed_partition_bit_identical(self, emit):
        g = pl_graph(n=90, seed=11)
        want = census_batagelj_mrvar(g)
        part = skewed_partition(g, 4)
        engine = CensusEngine(mesh=default_mesh(4), backend="jnp",
                              partition=True, emit=emit)
        got = engine.run(g, max_items=200, part=part)
        np.testing.assert_array_equal(got, want)
        np.testing.assert_array_equal(
            CensusEngine(backend="jnp", emit=emit).run(g, max_items=200),
            got)
        # only the real windows ran: Σ steps, not ndev × the longest
        st = engine.stats
        assert st.chunks == sum(st.shard_steps) \
            < 4 * max(st.shard_steps)

    def test_empty_shards_both_schedules(self):
        g = pl_graph(n=50, seed=13)
        want = census_batagelj_mrvar(g)
        space = pair_space(g)
        owner = np.zeros(space.num_pairs, np.int64)   # all pairs → 0
        part = partition_graph(num_shards=4, space=space, owner=owner)
        engine = CensusEngine(mesh=default_mesh(4), backend="jnp",
                              partition=True)
        got = engine.run(g, max_items=150, part=part)
        np.testing.assert_array_equal(got, want)
        np.testing.assert_array_equal(
            CensusEngine(backend="jnp").run(g, max_items=150), got)
        assert engine.stats.shard_steps[1:] == [0, 0, 0]

    @pytest.mark.parametrize("backend", ["pallas", "pallas-fused"])
    def test_async_backends(self, backend):
        g = pl_graph(n=40, deg=4, seed=8)
        want = census_batagelj_mrvar(g)
        engine = CensusEngine(mesh=default_mesh(4), backend=backend,
                              partition=True)
        np.testing.assert_array_equal(engine.run(g), want)
        np.testing.assert_array_equal(engine.run(g, max_items=80), want)

    def test_monolithic_schedule_async(self):
        """max_items=None still works: one window per shard."""
        g = pl_graph(n=60, seed=19)
        got = triad_census_graph(g, mesh=default_mesh(4),
                                 partition=True)
        np.testing.assert_array_equal(got, census_batagelj_mrvar(g))

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


def single_device_launches(monkeypatch):
    """Spy on ``_launch``: record, for every step a run launches, the
    device sets of its array arguments."""
    import jax

    import repro.core.engine as engine_mod
    seen = []
    real = engine_mod._launch

    def spy(step, backend, *args):
        seen.append([a.devices() for a in args
                     if isinstance(a, jax.Array)])
        return real(step, backend, *args)

    monkeypatch.setattr(engine_mod, "_launch", spy)
    return seen


class TestNoCrossShardSync:
    @pytest.mark.parametrize("emit", ["device", "host"])
    def test_async_never_enters_collective_step(self, emit, monkeypatch):
        """Every launched step takes arrays committed to one device, the
        same one for all of a launch's arguments: no input spans the
        mesh, so no step can synchronize shards."""
        seen = single_device_launches(monkeypatch)
        g = pl_graph(n=70, seed=23)
        engine = CensusEngine(mesh=default_mesh(4), backend="jnp",
                              partition=True, emit=emit)
        got = engine.run(g, max_items=150)
        np.testing.assert_array_equal(got, census_batagelj_mrvar(g))
        assert len(seen) == engine.stats.dispatches_total > 4
        for devs in seen:
            assert devs and all(len(d) == 1 for d in devs)
            assert len(set().union(*devs)) == 1
        # the launches cover more than one device
        assert len(set().union(*(d for devs in seen for d in devs))) > 1


# -------------------------------------------------------------- stats


class TestAsyncStats:
    def test_upload_and_step_stats_regression(self):
        """Upload/step attribution of a partitioned run: per-shard step
        counts match the shard schedule, upload is charged to REAL
        windows only, and the padding of ragged megabatch tails goes to
        a separate counter."""
        g = pl_graph(n=90, seed=11)
        part = skewed_partition(g, 4)
        engine = CensusEngine(mesh=default_mesh(4), backend="jnp",
                              partition=True, emit="device")
        census = engine.run(g, max_items=200, part=part)
        a = engine.stats
        np.testing.assert_array_equal(census, census_batagelj_mrvar(g))
        assert a.items > 0
        sched_obj = ShardSchedule(
            [sh.space for sh in part.shards], 200, 4)
        assert a.shard_steps == sched_obj.shard_steps
        # exactly the real windows are dispatched
        assert a.chunks == sum(a.shard_steps)
        # upload attribution: charged for REAL windows only
        assert a.plan_upload_bytes_total == \
            a.plan_upload_bytes * sum(a.shard_steps)
        # pad obeys the megabatch identity: cap × dispatches minus real
        # windows, all ragged-tail slots
        assert a.plan_pad_bytes_total == a.plan_upload_bytes * \
            (a.dispatch_batch_limit * a.dispatches_total
             - sum(a.shard_steps))
        # pipeline surface
        assert a.pipeline_depth == 2
        assert a.stall_steps >= 0
        assert "partitioned" in a.summary()
        # the schedule-wide lane footprint covers every device
        assert a.peak_plan_bytes == 8 * sched_obj.chunk_shape * 4

    def test_async_compiles_once_per_device_not_per_step(self):
        """The stacked common-shape shard buffers mean one compiled step
        per DEVICE serves that shard's every window (jit keys on device
        placement, so the floor is ndev, never O(steps))."""
        g = pl_graph(n=90, seed=21)
        engine = CensusEngine(mesh=default_mesh(4), backend="jnp",
                              partition=True)
        engine.run(g, max_items=64)
        assert engine.stats.chunks >= 8
        assert engine.stats.step_compiles <= 4

    def test_host_emit_skips_fully_pruned_windows(self):
        """Host emission never dispatches a zero-valid window: chunks
        counts only real dispatches."""
        g = pl_graph(n=60, seed=29)
        engine = CensusEngine(mesh=default_mesh(4), backend="jnp",
                              partition=True, emit="host")
        engine.run(g, max_items=100)
        st = engine.stats
        assert st.chunks == len(st.chunk_items)
        assert all(n > 0 for n in st.chunk_items)
