"""Device-side megastep: scan K descriptor windows per dispatch.

The tentpole contract:

* **Bit-identity** — a partitioned run with any
  ``max_windows_per_dispatch`` K equals the single-device census and
  the reference census, across 1/2/4/8-device meshes × both
  orients × both emit modes × K∈{1,2,8}.  The megastep returns per-
  window STACKED int32 partials and the host sums them in int64, so a
  K-window scan is bit-identical to K single-window dispatches.
* **Dispatch amortization** — at an equal window budget, K=8 issues at
  most half the device dispatches of K=1 (the whole point: Python
  dispatch cost is paid once per K windows).
* **Compile-once** — the megabatch buffer is fixed ``(cap, words)``
  shape with zero-padded masked rows, so the jitted megastep compiles
  once per device no matter how the adaptive K schedule moves.
* **Adaptive K** — consumer stalls shrink K (producer-bound), producer
  backlog grows K (dispatch-bound), monotonically within [1, cap].
* **Short-circuit** — zero-window shards never get a producer thread
  or a rotation slot, and every megastep takes arrays committed to one
  device: no cross-shard collective can run.
"""

import time

import numpy as np
import pytest

from repro.core import (
    CensusEngine, ShardStreamPipeline, TriadMonitor, WindowBatcher,
    census_batagelj_mrvar, default_mesh, lpt_assign_heap, pair_space,
    partition_graph, scale_free_digraph)
from repro.core.plan_stream import ShardSchedule


def pl_graph(n=100, deg=5, seed=7):
    return scale_free_digraph(n=n, avg_degree=deg, exponent=2.2,
                              mutual_p=0.3, seed=seed)


def skewed_partition(g, num_shards, factor=4.0, orient="none"):
    """Shard 0 holds ``factor``× each other shard's pre-prune items;
    the rest are LPT-balanced across shards 1..ns-1."""
    space = pair_space(g, orient=orient)
    costs = space.counts.astype(np.int64)
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


def rows_of(n, words=3):
    """n distinct nonzero int32 window rows (leading word > 0, as real
    ``device_words`` always have ``num_preprune >= 1``)."""
    return [np.full(words, i + 1, dtype=np.int32) for i in range(n)]


# ------------------------------------------------------ WindowBatcher


class TestWindowBatcher:
    def test_validation(self):
        with pytest.raises(ValueError):
            WindowBatcher(0, 4)
        with pytest.raises(ValueError):
            WindowBatcher(4, 0)

    def test_start_defaults_to_cap_and_clamps(self):
        assert WindowBatcher(8, 4).k == 8
        assert WindowBatcher(8, 4, start=3).k == 3
        assert WindowBatcher(8, 4, start=99).k == 8
        assert WindowBatcher(8, 4, start=0).k == 1

    def test_shrink_grow_monotone_within_bounds(self):
        b = WindowBatcher(8, 4)
        ks = []
        for _ in range(5):
            b.shrink()
            ks.append(b.k)
        assert ks == [4, 2, 1, 1, 1]      # halves, floors at 1
        ks = []
        for _ in range(5):
            b.grow()
            ks.append(b.k)
        assert ks == [2, 4, 8, 8, 8]      # doubles, caps at cap

    def test_wrap_coalesces_fixed_shape_with_zero_pad(self):
        b = WindowBatcher(4, 3)
        batches = list(b.wrap(rows_of(6)))
        assert len(batches) == 2
        full, real = batches[0]
        assert full.shape == (4, 3) and full.dtype == np.int32
        assert real == 4
        np.testing.assert_array_equal(full, np.stack(rows_of(6)[:4]))
        tail, real = batches[1]
        assert tail.shape == (4, 3)       # shape never depends on fill
        assert real == 2
        np.testing.assert_array_equal(tail[:2], np.stack(rows_of(6)[4:]))
        # padding rows are all-zero → num_preprune word 0 → masked out
        np.testing.assert_array_equal(tail[2:], 0)

    def test_wrap_k_larger_than_stream(self):
        b = WindowBatcher(8, 3)
        batches = list(b.wrap(rows_of(3)))
        assert len(batches) == 1
        buf, real = batches[0]
        assert buf.shape == (8, 3) and real == 3
        np.testing.assert_array_equal(buf[3:], 0)

    def test_wrap_empty_source(self):
        assert list(WindowBatcher(4, 3).wrap([])) == []

    def test_wrap_snapshots_current_k_per_batch(self):
        b = WindowBatcher(8, 3, start=2)
        gen = b.wrap(rows_of(10))
        _, real = next(gen)
        assert real == 2                  # filled at k=2
        b.grow()                          # adaptive move between batches
        _, real = next(gen)
        assert real == 4                  # next batch sees k=4


# --------------------------------------- adaptive feedback in the pipe


class TestAdaptiveK:
    def test_consumer_stall_shrinks_k(self):
        """Slow producer + fast consumer: once at least one batch has
        been consumed, each stall halves k."""
        b = WindowBatcher(8, 2, start=4)

        def slow():
            for i in range(12):
                time.sleep(0.03)
                yield np.array([1, i], np.int32)

        pipe = ShardStreamPipeline([slow()], depth=2, batch=b)
        got = sum(real for _, (_, real) in pipe)
        pipe.close()
        assert got == 12                  # every window lands exactly once
        assert pipe.stalls > 0
        assert b.k < 4

    def test_producer_backlog_grows_k(self):
        """Fast producer + slow consumer on a depth-1 queue: puts block,
        k doubles toward cap."""
        b = WindowBatcher(8, 2, start=1)

        def fast():
            for i in range(12):
                yield np.array([1, i], np.int32)

        pipe = ShardStreamPipeline([fast()], depth=1, batch=b)
        got = 0
        for _, (_, real) in pipe:
            time.sleep(0.08)              # device busy: consumer behind
            got += real
        pipe.close()
        assert got == 12
        assert b.k > 1

    def test_startup_latency_is_not_starvation(self):
        """The very first stall (nothing consumed yet) must NOT shrink
        k — producer warm-up is not a bottleneck signal."""
        b = WindowBatcher(8, 2)

        def warmup():
            time.sleep(0.08)              # consumer stalls before row 0
            for i in range(4):
                yield np.array([1, i], np.int32)

        pipe = ShardStreamPipeline([warmup()], depth=2, batch=b)
        got = sum(real for _, (_, real) in pipe)
        pipe.close()
        assert got == 4
        assert pipe.stalls >= 1
        assert b.k == 8                   # grace: no shrink before use


# -------------------------------------------------------- bit-identity


class TestMegastepBitIdentity:
    @pytest.mark.parametrize("orient", ["none", "degree"])
    @pytest.mark.parametrize("cap", [1, 2, 8])
    def test_k_matrix_vs_single_device_and_reference(self, cap, orient):
        g = pl_graph(n=70, seed=13)
        want = census_batagelj_mrvar(g)
        part = skewed_partition(g, 4, orient=orient)
        single = CensusEngine(backend="jnp", emit="device")
        np.testing.assert_array_equal(
            single.run(g, max_items=120, orient=orient), want)
        eng = CensusEngine(mesh=default_mesh(4), backend="jnp",
                           partition=True, emit="device",
                           max_windows_per_dispatch=cap)
        got = eng.run(g, max_items=120, part=part)
        np.testing.assert_array_equal(got, want)
        st = eng.stats
        assert st.dispatch_batch_limit == cap
        assert 1 <= st.windows_per_dispatch_max <= cap
        # every window of the shard schedule, in at most as many
        # dispatches
        sched = ShardSchedule([sh.space for sh in part.shards], 120, 4)
        assert st.shard_steps == sched.shard_steps
        assert st.dispatches_total <= sum(st.shard_steps)

    @pytest.mark.parametrize("ndev", [1, 2, 8])
    def test_device_count_sweep(self, ndev):
        g = pl_graph(n=60, seed=5)
        want = census_batagelj_mrvar(g)
        eng = CensusEngine(mesh=default_mesh(ndev), backend="jnp",
                           partition=True,
                           max_windows_per_dispatch=8)
        np.testing.assert_array_equal(eng.run(g, max_items=100), want)

    @pytest.mark.parametrize("backend", ["pallas", "pallas-fused"])
    def test_pallas_backends_through_scan(self, backend):
        g = pl_graph(n=40, deg=4, seed=8)
        want = census_batagelj_mrvar(g)
        eng = CensusEngine(mesh=default_mesh(4), backend=backend,
                           partition=True,
                           max_windows_per_dispatch=4)
        np.testing.assert_array_equal(eng.run(g, max_items=80), want)

    def test_host_emit_stays_single_window_oracle(self):
        """``emit="host"`` ignores the megastep: cap is pinned to 1 so
        the PR 6 one-window-per-dispatch path stays the oracle."""
        g = pl_graph(n=60, seed=29)
        eng = CensusEngine(mesh=default_mesh(4), backend="jnp",
                           partition=True, emit="host",
                           max_windows_per_dispatch=8)
        np.testing.assert_array_equal(eng.run(g, max_items=100),
                                      census_batagelj_mrvar(g))
        st = eng.stats
        assert st.dispatch_batch_limit == 1
        assert st.windows_per_dispatch_max == 1
        assert st.dispatches_total == st.chunks


# --------------------------------------------------------------- stats


class TestMegastepStats:
    def test_ragged_tail_pad_identity(self):
        """Windows not divisible by K: the tail batch pads, and the pad
        bytes obey cap × dispatches − real windows exactly."""
        g = pl_graph(n=70, seed=13)
        part = skewed_partition(g, 4)
        eng = CensusEngine(mesh=default_mesh(4), backend="jnp",
                           partition=True,
                           max_windows_per_dispatch=8)
        eng.run(g, max_items=120, part=part)
        st = eng.stats
        windows = sum(st.shard_steps)
        assert st.chunks == windows == len(st.chunk_items)
        assert st.dispatches_total < windows
        assert st.plan_upload_bytes_total == \
            st.plan_upload_bytes * windows
        assert st.plan_pad_bytes_total == st.plan_upload_bytes * \
            (st.dispatch_batch_limit * st.dispatches_total - windows)
        assert st.plan_pad_bytes_total > 0     # ragged tails exist
        assert st.windows_per_dispatch_mean == \
            pytest.approx(windows / st.dispatches_total)
        assert "win/disp" in st.summary()
        assert f"dispatches={st.dispatches_total}" in st.summary()

    def test_k_exceeds_total_windows(self):
        """cap far above any shard's window count: the engine clamps
        the effective batch capacity to the longest shard queue, so
        short schedules never upload dead pad rows — one dispatch per
        shard, zero pad bytes."""
        g = pl_graph(n=40, deg=3, seed=2)
        eng = CensusEngine(mesh=default_mesh(4), backend="jnp",
                           partition=True,
                           max_windows_per_dispatch=64)
        got = eng.run(g)                  # unstreamed: 1 window/shard
        np.testing.assert_array_equal(got, census_batagelj_mrvar(g))
        st = eng.stats
        assert st.dispatches_total == \
            sum(1 for t in st.shard_steps if t > 0)
        assert st.dispatch_batch_limit == max(st.shard_steps) == 1
        assert st.plan_pad_bytes_total == 0

    def test_dispatch_reduction_at_equal_window_budget(self):
        """The headline: same windows, ≥2× fewer dispatches at K=8."""
        g = pl_graph(n=90, seed=11)
        part = skewed_partition(g, 4)
        disp = {}
        for cap in (1, 8):
            eng = CensusEngine(mesh=default_mesh(4), backend="jnp",
                               partition=True,
                               max_windows_per_dispatch=cap)
            eng.run(g, max_items=100, part=part)
            disp[cap] = eng.stats.dispatches_total
            if cap == 1:
                windows = sum(eng.stats.shard_steps)
            else:
                assert sum(eng.stats.shard_steps) == windows
        assert disp[8] * 2 <= disp[1]

    def test_compiles_once_per_device_across_k_schedule(self):
        """Fixed (cap, words) megabatch shape: one compiled step per
        device regardless of how many windows each batch really holds,
        and a second run recompiles nothing."""
        g = pl_graph(n=90, seed=21)
        eng = CensusEngine(mesh=default_mesh(4), backend="jnp",
                           partition=True,
                           max_windows_per_dispatch=8)
        eng.run(g, max_items=64)
        assert eng.stats.dispatches_total >= 4
        assert eng.stats.step_compiles <= 4
        eng.run(g, max_items=64)          # warm cache
        assert eng.stats.step_compiles == 0

    def test_ctor_validation(self):
        with pytest.raises(ValueError):
            CensusEngine(mesh=default_mesh(2), partition=True,
                         pipeline_depth=0)
        with pytest.raises(ValueError):
            CensusEngine(mesh=default_mesh(2), partition=True,
                         max_windows_per_dispatch=0)

    def test_pipeline_depth_configurable_and_surfaced(self):
        g = pl_graph(n=50, seed=4)
        eng = CensusEngine(mesh=default_mesh(2), backend="jnp",
                           partition=True,
                           pipeline_depth=3)
        np.testing.assert_array_equal(eng.run(g, max_items=80),
                                      census_batagelj_mrvar(g))
        assert eng.pipeline_depth == 3
        assert eng.stats.pipeline_depth == 3

    def test_triad_monitor_forwards_knobs(self):
        mon = TriadMonitor(50, window=40, mesh=default_mesh(2),
                           partition=True, pipeline_depth=3,
                           max_windows_per_dispatch=4)
        assert mon.engine.pipeline_depth == 3
        assert mon.engine.max_windows_per_dispatch == 4


# ------------------------------------- short-circuit + no collectives


class TestShortCircuitAndIsolation:
    def test_empty_shards_never_enter_rotation(self, monkeypatch):
        """All pairs on shard 0 of a 4-device mesh: the pipeline is
        built with ONE source, not four — drained/empty shards are
        short-circuited out before any thread or queue exists."""
        import repro.core.engine as engine_mod
        seen = []
        real = engine_mod.ShardStreamPipeline

        class Spy(real):
            def __init__(self, sources, **kw):
                sources = list(sources)
                seen.append(len(sources))
                super().__init__(sources, **kw)

        monkeypatch.setattr(engine_mod, "ShardStreamPipeline", Spy)
        g = pl_graph(n=60, seed=17)
        space = pair_space(g, orient="none")
        part = partition_graph(
            num_shards=4, space=space,
            owner=np.zeros(space.num_pairs, np.int64))
        eng = CensusEngine(mesh=default_mesh(4), backend="jnp",
                           partition=True,
                           max_windows_per_dispatch=8)
        got = eng.run(g, max_items=100, part=part)
        np.testing.assert_array_equal(got, census_batagelj_mrvar(g))
        assert seen == [1]
        st = eng.stats
        assert st.shard_steps[0] > 0
        assert all(t == 0 for t in st.shard_steps[1:])

    @pytest.mark.parametrize("cap", [2, 8])
    def test_megastep_never_enters_collectives(self, cap, monkeypatch):
        """Spy on ``_launch``: every megastep takes arrays committed to
        one device — single-device dispatches + host merge only."""
        import jax

        import repro.core.engine as engine_mod
        seen = []
        real = engine_mod._launch

        def spy(step, backend, *args):
            seen.append((step, {d for a in args
                                if isinstance(a, jax.Array)
                                for d in a.devices()}))
            return real(step, backend, *args)

        monkeypatch.setattr(engine_mod, "_launch", spy)
        g = pl_graph(n=70, seed=13)
        eng = CensusEngine(mesh=default_mesh(4), backend="jnp",
                           partition=True,
                           max_windows_per_dispatch=cap)
        got = eng.run(g, max_items=120,
                      part=skewed_partition(g, 4))
        np.testing.assert_array_equal(got, census_batagelj_mrvar(g))
        assert len(seen) == eng.stats.dispatches_total
        assert all(step is engine_mod._desc_megastep and len(devs) == 1
                   for step, devs in seen)
