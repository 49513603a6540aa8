"""Fault-tolerant shard streams: inject, retry, fail over, resume —
and stay bit-identical.

The tentpole contract:

* **Bit-identity under faults** — because the host int64 merge is
  order-invariant and windows are independent, ANY window may be
  retried, re-routed to a surviving device, or re-counted after a
  resume without changing a single census lane.  Seeded fault plans
  (producer errors, dispatch errors, slow devices, poisoned results,
  mid-run device retirements) across 1/2/4/8-device meshes × orients ×
  emit modes must reproduce the fault-free census exactly.
* **Accounting** — every recovery action is visible:
  ``EngineStats.retries/failovers/watchdog_fires/retired_devices``.
* **Checkpoint/resume** — a run killed mid-stream resumes from its
  journal to the exact same census, skipping completed windows.
* **Sessions** — the resident sessions retry transient faults on the
  same device and reject poisoned partials; context managers reap the
  device buffers on exceptions.
* **Guard rails** — int32-overflow plans fail loudly at plan time
  (:class:`PlanOverflowError`), and the ingestion edge rejects ragged /
  non-finite / out-of-range input before it reaches the CSR editors.
"""

import os
import threading
import time

import numpy as np
import pytest

from repro.core import (
    CensusEngine, Fault, FaultError, FaultPlan, InjectedFault,
    PlanChunker, PlanOverflowError, ProducerStalledError,
    ShardStreamPipeline, TriadMonitor, default_mesh, from_edges,
    partition_graph, scale_free_digraph, shard_report)
from repro.core.faults import FaultInjector, poison_result
from repro.core.plan_stream import ShardSchedule


def pl_graph(n=120, deg=4, seed=3):
    return scale_free_digraph(n=n, avg_degree=deg, exponent=2.2,
                              mutual_p=0.3, seed=seed)


# ------------------------------------------------------------ fault plans


class TestFaultPlan:
    def test_seeded_is_deterministic(self):
        a = FaultPlan.seeded(11, 8, producer_errors=2, dispatch_errors=2,
                             retire_devices=1, delays=1, poisons=1)
        b = FaultPlan.seeded(11, 8, producer_errors=2, dispatch_errors=2,
                             retire_devices=1, delays=1, poisons=1)
        assert a.faults == b.faults
        c = FaultPlan.seeded(12, 8, producer_errors=2, dispatch_errors=2,
                             retire_devices=1, delays=1, poisons=1)
        assert a.faults != c.faults

    def test_retirements_spare_device_zero(self):
        for seed in range(20):
            plan = FaultPlan.seeded(seed, 8, retire_devices=3)
            retired = {f.device for f in plan.faults if f.persistent}
            assert 0 not in retired and len(retired) == 3

    def test_fault_validation(self):
        with pytest.raises(ValueError, match="site"):
            Fault("nowhere")
        with pytest.raises(ValueError, match="kind"):
            Fault("dispatch", "explode")
        with pytest.raises(ValueError, match="persistent"):
            Fault("producer", "error", persistent=True)

    def test_injector_occurrence_matching(self):
        inj = FaultPlan(faults=[
            Fault("dispatch", "error", device=1, occurrence=1)]).injector()
        inj.fire("dispatch", shard=1, device=1)        # occurrence 0: ok
        with pytest.raises(InjectedFault):
            inj.fire("dispatch", shard=1, device=1)    # occurrence 1
        inj.fire("dispatch", shard=1, device=1)        # transient: gone
        inj.fire("dispatch", shard=0, device=0)        # other stream: ok

    def test_persistent_fault_kills_the_device(self):
        inj = FaultPlan(faults=[
            Fault("dispatch", "error", device=2, occurrence=0,
                  persistent=True)]).injector()
        with pytest.raises(InjectedFault):
            inj.fire("dispatch", shard=2, device=2)
        assert inj.device_is_dead(2)
        with pytest.raises(InjectedFault):   # every later op fails too
            inj.fire("upload", shard=5, device=2)
        inj.fire("dispatch", shard=3, device=3)   # survivors unaffected

    def test_poison_is_taken_once(self):
        inj = FaultPlan(faults=[
            Fault("dispatch", "poison", occurrence=0)]).injector()
        inj.fire("dispatch", shard=0, device=0)
        assert inj.take_poison()
        assert not inj.take_poison()

    def test_poison_result_fails_validation(self):
        hist = np.arange(64, dtype=np.int64)
        inter = np.array([3, 4, 5], dtype=np.int64)
        ph, pi = poison_result(hist, inter)
        assert (ph < 0).all()
        from repro.core.engine import _validate_partials
        with pytest.raises(FaultError):
            _validate_partials(ph, pi)
        _validate_partials(hist, inter)   # clean partials pass


# ---------------------------------------------------- pipeline robustness


class TestPipelineRecovery:
    def test_producer_error_restarts_from_skip(self):
        """A producer that dies mid-stream is restarted with the count of
        windows already delivered; nothing is lost or duplicated."""
        attempts = {"n": 0}

        def flaky(skip=0):
            attempts["n"] += 1
            for k in range(skip, 6):
                if k == 3 and attempts["n"] == 1:
                    raise RuntimeError("flake")
                yield k

        pipe = ShardStreamPipeline(
            [flaky()], restart=lambda slot, skip: flaky(skip),
            backoff=0.0)
        got = [w for _, w in pipe]
        pipe.close()
        assert got == list(range(6))
        assert pipe.producer_retries == 1

    def test_producer_error_without_restart_propagates(self):
        def dead():
            yield 0
            raise RuntimeError("no recovery")

        pipe = ShardStreamPipeline([dead()])
        with pytest.raises(RuntimeError, match="no recovery"):
            list(pipe)
        pipe.close()

    def test_retry_budget_exhaustion_propagates(self):
        def always(skip=0):
            raise RuntimeError("permafail")
            yield  # pragma: no cover

        pipe = ShardStreamPipeline(
            [always()], restart=lambda slot, skip: always(skip),
            max_retries=2, backoff=0.0)
        with pytest.raises(RuntimeError, match="permafail"):
            list(pipe)
        pipe.close()
        assert pipe.producer_retries == 2

    def test_watchdog_restarts_hung_producer(self):
        """A producer that hangs (no put, queue empty) past the watchdog
        timeout is cancelled and regenerated from its skip count."""
        hang = threading.Event()

        def hung(skip=0):
            for k in range(skip, 4):
                if k == 2 and not hang.is_set():
                    hang.set()
                    time.sleep(30)       # never finishes in time
                yield k

        pipe = ShardStreamPipeline(
            [hung()], restart=lambda slot, skip: hung(skip),
            watchdog=0.3, backoff=0.0)
        got = [w for _, w in pipe]
        pipe.close()
        assert got == list(range(4))
        assert pipe.watchdog_fires >= 1

    def test_watchdog_exhaustion_raises_stalled(self):
        def hung(skip=0):
            time.sleep(30)
            yield 0  # pragma: no cover

        pipe = ShardStreamPipeline(
            [hung()], restart=lambda slot, skip: hung(skip),
            watchdog=0.2, max_retries=1, backoff=0.0)
        with pytest.raises(ProducerStalledError):
            list(pipe)
        pipe.close()

    def test_context_manager_reaps_threads(self):
        def slow():
            for k in range(1000):
                yield k

        with ShardStreamPipeline([slow(), slow()], depth=2) as pipe:
            next(iter(pipe))
            threads = list(pipe._threads)
        for t in threads:
            t.join(timeout=5)
            assert not t.is_alive()

    def test_context_manager_reaps_on_exception(self):
        def src():
            yield from range(100)

        try:
            with ShardStreamPipeline([src()]) as pipe:
                raise KeyboardInterrupt
        except KeyboardInterrupt:
            pass
        for t in pipe._threads:
            t.join(timeout=5)
            assert not t.is_alive()


# -------------------------------------------------- engine runs, faulted


@pytest.fixture(scope="module")
def g():
    return pl_graph()


@pytest.fixture(scope="module")
def reference(g):
    """Fault-free reference censuses keyed by orient."""
    eng = CensusEngine()
    return {orient: eng.run(g, orient=orient)
            for orient in ("none", "degree")}


class TestFaultedRunsBitIdentical:
    @pytest.mark.parametrize("ndev", [1, 2, 4, 8])
    @pytest.mark.parametrize("emit", ["device", "host"])
    def test_transient_faults_all_meshes(self, g, reference, ndev, emit):
        plan = FaultPlan.seeded(
            31 + ndev, ndev, producer_errors=1, dispatch_errors=1,
            retire_devices=1 if ndev > 1 else 0)
        eng = CensusEngine(mesh=default_mesh(ndev), partition=True,
                           faults=plan,
                           retry_backoff=0.0)
        got = eng.run(g, max_items=900, emit=emit)
        assert (got == reference["none"]).all()
        st = eng.stats
        assert st.retries >= 1
        if ndev > 1:
            assert st.failovers >= 1 and st.retired_devices

    @pytest.mark.parametrize("orient", ["none", "degree"])
    def test_orients_with_retirement(self, g, reference, orient):
        plan = FaultPlan.seeded(5, 8, producer_errors=1,
                                dispatch_errors=2, retire_devices=1)
        eng = CensusEngine(mesh=default_mesh(8), partition=True,
                           faults=plan,
                           retry_backoff=0.0)
        got = eng.run(g, max_items=900, orient=orient)
        assert (got == reference[orient]).all()
        assert eng.stats.failovers >= 1

    def test_slow_device_and_poison(self, g, reference):
        plan = FaultPlan.seeded(9, 4, producer_errors=0,
                                dispatch_errors=0, delays=2, poisons=2,
                                delay_seconds=0.05)
        eng = CensusEngine(mesh=default_mesh(4), partition=True,
                          faults=plan,
                          retry_backoff=0.0)
        got = eng.run(g, max_items=900)
        assert (got == reference["none"]).all()
        assert eng.stats.retries >= 1   # each poison forces a re-dispatch

    def test_every_device_retired_raises(self, g):
        plan = FaultPlan(faults=[
            Fault("dispatch", "error", device=d, occurrence=0,
                  persistent=True) for d in range(2)])
        eng = CensusEngine(mesh=default_mesh(2), partition=True,
                          faults=plan,
                          retry_backoff=0.0)
        with pytest.raises(FaultError, match="every device"):
            eng.run(g, max_items=900)

    def test_shard_report_failure_section(self, g):
        plan = FaultPlan.seeded(5, 8, producer_errors=1,
                                dispatch_errors=2, retire_devices=1)
        eng = CensusEngine(mesh=default_mesh(8), partition=True,
                          faults=plan,
                          retry_backoff=0.0)
        eng.run(g, max_items=900)
        part = partition_graph(g, num_shards=8)
        text = shard_report(part, stats=eng.stats)
        assert "fault tolerance:" in text
        assert "retired devices" in text and "failovers" in text
        assert "fault tolerance:" not in shard_report(part)


# ----------------------------------------------------- checkpoint/resume


class _Killer:
    """Progress callback that raises after ``at`` landed windows."""

    def __init__(self, at):
        self.at = at
        self.seen = 0

    def __call__(self, done, total, num=None):
        self.seen += 1
        if self.seen == self.at:
            raise KeyboardInterrupt


class TestCheckpointResume:
    @pytest.mark.parametrize("emit", ["device", "host"])
    def test_resume_equals_uninterrupted(self, tmp_path, g, reference,
                                         emit):
        ck = str(tmp_path / "run.ckpt")
        eng = CensusEngine(mesh=default_mesh(4), partition=True)
        with pytest.raises(KeyboardInterrupt):
            eng.run(g, max_items=900, emit=emit, checkpoint=ck,
                    progress=_Killer(4))
        assert os.path.getsize(ck) > 0
        got = eng.resume(g, ck, max_items=900, emit=emit)
        assert (got == reference["none"]).all()
        assert eng.stats.resumed_windows >= 1

    def test_resume_under_further_faults(self, tmp_path, g, reference):
        """Kill a run, then resume it WITH a fault plan that retires a
        device — the journal windows stay skipped, the remainder fails
        over, and the census is still exact."""
        ck = str(tmp_path / "run.ckpt")
        eng = CensusEngine(mesh=default_mesh(4), partition=True)
        with pytest.raises(KeyboardInterrupt):
            eng.run(g, max_items=900, checkpoint=ck, progress=_Killer(3))
        plan = FaultPlan.seeded(2, 4, producer_errors=0,
                                dispatch_errors=1, retire_devices=1)
        eng2 = CensusEngine(mesh=default_mesh(4), partition=True,
                           faults=plan,
                           retry_backoff=0.0)
        got = eng2.resume(g, ck, max_items=900)
        assert (got == reference["none"]).all()
        assert eng2.stats.resumed_windows >= 1
        assert eng2.stats.failovers >= 1

    def test_completed_checkpoint_dispatches_nothing(self, tmp_path, g,
                                                     reference):
        ck = str(tmp_path / "run.ckpt")
        eng = CensusEngine(mesh=default_mesh(4), partition=True)
        want = eng.run(g, max_items=900, checkpoint=ck)
        assert (want == reference["none"]).all()
        windows = eng.stats.resumed_windows + sum(eng.stats.shard_steps)
        got = eng.resume(g, ck, max_items=900)
        assert (got == want).all()
        assert eng.stats.resumed_windows == windows
        assert sum(eng.stats.shard_steps) == 0

    def test_fingerprint_mismatch_rejected(self, tmp_path, g):
        ck = str(tmp_path / "run.ckpt")
        eng = CensusEngine(mesh=default_mesh(4), partition=True)
        eng.run(g, max_items=900, checkpoint=ck)
        other = pl_graph(seed=99)
        with pytest.raises(FaultError, match="different run"):
            eng.resume(other, ck, max_items=900)

    def test_checkpoint_requires_async_partitioned(self, tmp_path, g):
        eng = CensusEngine(mesh=default_mesh(4))
        with pytest.raises(ValueError, match="checkpoint"):
            eng.run(g, max_items=900,
                    checkpoint=str(tmp_path / "x.ckpt"))

    def test_resume_missing_file_raises(self, g):
        eng = CensusEngine(mesh=default_mesh(4), partition=True)
        with pytest.raises(FileNotFoundError):
            eng.resume(g, "/nonexistent/run.ckpt", max_items=900)

    def test_compact_checkpoint_resumes_identically(self, tmp_path, g,
                                                    reference):
        """Kill a run mid-stream, fold its journal, and resume from the
        compacted form — same census, smaller file, and a second
        compaction after completion leaves one record per shard."""
        ck = str(tmp_path / "run.ckpt")
        eng = CensusEngine(mesh=default_mesh(4), partition=True)
        with pytest.raises(KeyboardInterrupt):
            eng.run(g, max_items=900, checkpoint=ck, progress=_Killer(4))
        info = CensusEngine.compact_checkpoint(ck)
        assert info["records"] >= info["compacted"] >= 1
        assert info["compacted_bytes"] == os.path.getsize(ck)
        assert info["compacted_bytes"] <= info["bytes"]
        eng2 = CensusEngine(mesh=default_mesh(4), partition=True)
        got = eng2.resume(g, ck, max_items=900)
        assert (got == reference["none"]).all()
        assert eng2.stats.resumed_windows >= 1
        # the completed journal (compacted snapshot + appended tail)
        # compacts again and then resumes with zero dispatches
        info2 = CensusEngine.compact_checkpoint(ck)
        assert info2["compacted"] >= info["compacted"]
        got2 = eng2.resume(g, ck, max_items=900)
        assert (got2 == reference["none"]).all()
        assert sum(eng2.stats.shard_steps) == 0

    def test_compact_checkpoint_rejects_bad_journals(self, tmp_path):
        with pytest.raises(FileNotFoundError):
            CensusEngine.compact_checkpoint(
                str(tmp_path / "missing.ckpt"))
        empty = tmp_path / "empty.ckpt"
        empty.write_text("")
        with pytest.raises(FaultError, match="empty"):
            CensusEngine.compact_checkpoint(str(empty))
        bad = tmp_path / "bad.ckpt"
        bad.write_text('{"v": 99}\n')
        with pytest.raises(FaultError, match="version"):
            CensusEngine.compact_checkpoint(str(bad))


# ----------------------------------------------------------- sessions


class TestSessionFaults:
    @pytest.mark.parametrize("partition", [False, True])
    def test_session_retries_transient_faults(self, g, reference,
                                              partition):
        plan = FaultPlan(faults=[
            Fault("dispatch", "error", occurrence=1),
            Fault("dispatch", "poison", occurrence=3),
            Fault("upload", "error", occurrence=5)])
        eng = CensusEngine(mesh=default_mesh(4), partition=partition,
                          faults=plan, retry_backoff=0.0)
        with eng.session(g, max_items=900) as s:
            got = s.census()
            assert (got == reference["none"]).all()
            assert s.retries >= 2
            assert s.stats.retries == s.retries

    @pytest.mark.parametrize("partition", [False, True])
    def test_session_budget_exhaustion_raises(self, g, partition):
        plan = FaultPlan(faults=[
            Fault("dispatch", "error", occurrence=2 + i)
            for i in range(4)])
        eng = CensusEngine(mesh=default_mesh(4), partition=partition,
                          faults=plan, max_retries=2, retry_backoff=0.0)
        with eng.session(g, max_items=900) as s:
            with pytest.raises(FaultError):
                s.census()

    @pytest.mark.parametrize("partition", [False, True])
    def test_context_manager_closes(self, g, partition):
        eng = CensusEngine(mesh=default_mesh(4), partition=partition)
        with eng.session(g, max_items=900) as s:
            s.census()
        with pytest.raises(RuntimeError, match="closed"):
            s.census()
        with pytest.raises(RuntimeError, match="closed"):
            s.update([0], [1])
        s.close()     # idempotent

    @pytest.mark.parametrize("partition", [False, True])
    def test_checkpoint_warm_resume(self, tmp_path, g, reference,
                                    partition):
        """A census checkpointed from one session warm-resumes updates in
        a fresh session bit-identically to a never-interrupted one."""
        ck = str(tmp_path / "sess.ckpt")
        eng = CensusEngine(mesh=default_mesh(4), partition=partition)
        with eng.session(g, max_items=900) as s:
            s.census()
            s.save_checkpoint(ck)
        with eng.session(g, max_items=900) as warm:
            assert (warm.load_checkpoint(ck) == reference["none"]).all()
            c_warm = warm.update([0, 1, 2], [3, 4, 5])
        with eng.session(g, max_items=900) as cold:
            cold.census()
            c_cold = cold.update([0, 1, 2], [3, 4, 5])
        assert (c_warm == c_cold).all()

    def test_checkpoint_mismatch_rejected(self, tmp_path, g):
        ck = str(tmp_path / "sess.ckpt")
        eng = CensusEngine(mesh=default_mesh(4))
        with eng.session(g, max_items=900) as s:
            s.census()
            s.save_checkpoint(ck)
        with eng.session(pl_graph(seed=99), max_items=900) as other:
            with pytest.raises(FaultError, match="does not match"):
                other.load_checkpoint(ck)

    def test_checkpoint_without_census_raises(self, tmp_path, g):
        eng = CensusEngine(mesh=default_mesh(4))
        with eng.session(g, max_items=900) as s:
            with pytest.raises(RuntimeError, match="census"):
                s.save_checkpoint(str(tmp_path / "x.ckpt"))


# ------------------------------------------------------------ monitor


class TestMonitorDegradation:
    def _stream(self, seed=0, n=120, batch=150, batches=8):
        rng = np.random.default_rng(seed)
        return [(rng.integers(0, n, batch), rng.integers(0, n, batch))
                for _ in range(batches)]

    def test_monitor_survives_budget_exhaustion(self):
        plan = FaultPlan(faults=[
            Fault("dispatch", "error", device=0, occurrence=6 + i)
            for i in range(3)])
        mon = TriadMonitor(120, window=300, stride=150, history=3,
                           faults=plan, max_retries=2, retry_backoff=0.0)
        ref = TriadMonitor(120, window=300, stride=150, history=3)
        for src, dst in self._stream():
            mon.observe(src, dst)
        for src, dst in self._stream():
            ref.observe(src, dst)
        assert len(mon.degraded) >= 1
        deg = {d["window"] for d in mon.degraded}
        A, B = mon.censuses, ref.censuses
        assert A.shape == B.shape
        for t in range(A.shape[0]):
            if t in deg:     # carried forward from the previous window
                assert (A[t] == A[t - 1]).all()
            else:            # recomputed in full: bit-identical again
                assert (A[t] == B[t]).all()
        assert mon.window_stats[min(deg)] is None

    def test_monitor_transparent_retries(self):
        plan = FaultPlan(faults=[Fault("dispatch", "error", occurrence=2)])
        mon = TriadMonitor(120, window=300, stride=150, history=3,
                           faults=plan, retry_backoff=0.0)
        ref = TriadMonitor(120, window=300, stride=150, history=3)
        for src, dst in self._stream():
            mon.observe(src, dst)
        for src, dst in self._stream():
            ref.observe(src, dst)
        assert not mon.degraded
        assert (mon.censuses == ref.censuses).all()
        assert mon._session.retries >= 1


class TestIngestionValidation:
    def test_monitor_rejects_ragged(self):
        mon = TriadMonitor(10, window=4)
        with pytest.raises(ValueError, match="ragged"):
            mon.observe(np.array([[0, 1], [2]], dtype=object), [1, 2])

    def test_monitor_rejects_out_of_range(self):
        mon = TriadMonitor(10, window=4)
        with pytest.raises(ValueError, match="out of range"):
            mon.observe([0, 99], [1, 2])

    def test_monitor_rejects_bad_timestamps(self):
        mon = TriadMonitor(10, window=4)
        with pytest.raises(ValueError, match="NaN"):
            mon.observe([0, 1], [1, 2], t=[1.0, float("nan")])
        with pytest.raises(ValueError, match="negative"):
            mon.observe([0, 1], [1, 2], t=[-1.0, 2.0])
        with pytest.raises(ValueError, match="mismatch"):
            mon.observe([0, 1], [1, 2], t=[1.0])
        mon.observe([0, 1], [1, 2], t=[1.0, 2.0])
        with pytest.raises(ValueError, match="regressed"):
            mon.observe([0, 1], [1, 2], t=[0.5, 3.0])

    def test_clean_arcs_actionable_errors(self):
        with pytest.raises(ValueError, match="ragged"):
            from_edges(np.array([[0, 1], [2]], dtype=object), [1, 2])
        with pytest.raises(ValueError, match="non-finite"):
            from_edges([0.0, float("nan")], [1.0, 2.0], n=4)
        with pytest.raises(ValueError, match=r"out of range \[0, 4\)"):
            from_edges([0, 9], [1, 2], n=4)
        with pytest.raises(ValueError, match="mismatch: 2 != 3"):
            from_edges([0, 1], [1, 2, 3])

    def test_apply_delta_validates(self):
        from repro.core import apply_delta
        g = pl_graph(n=20)
        with pytest.raises(ValueError, match="out of range"):
            apply_delta(g, [0, 99], [1, 2])
        with pytest.raises(ValueError, match="non-finite"):
            apply_delta(g, None, None, [float("inf")], [1.0])


# ----------------------------------------------------- overflow guards


class TestPlanOverflowGuard:
    def test_is_a_value_error(self):
        assert issubclass(PlanOverflowError, ValueError)

    def test_chunker_rejects_near_2_31_window(self):
        from types import SimpleNamespace
        big = SimpleNamespace(num_items_preprune=2**31 + 5)
        with pytest.raises(PlanOverflowError, match="int32"):
            PlanChunker(None, 2**31, space=big)
        # a budget under the lane limit is fine at construction time
        small = SimpleNamespace(num_items_preprune=2**31 + 5)
        try:
            PlanChunker(None, 2**20, space=small)
        except PlanOverflowError:      # pragma: no cover
            pytest.fail("sub-limit budget must not raise")
        except Exception:
            pass   # later attrs of the fake space may be missing

    def test_shard_schedule_rejects_near_2_31_window(self):
        from types import SimpleNamespace
        big = SimpleNamespace(num_items_preprune=2**31 + 7)
        with pytest.raises(PlanOverflowError, match="int32"):
            ShardSchedule([big], None, 1)

    def test_engine_guard(self):
        from repro.core.engine import _guard_chunk_shape
        with pytest.raises(PlanOverflowError, match="int32"):
            _guard_chunk_shape(2**31)
        assert _guard_chunk_shape(2**31 - 1) == 2**31 - 1
