"""Host spans and stage scopes of the census engine.

* :func:`repro.core.spans.span` adds its duration to its counter, from
  any number of threads, and writes a profiler span carrying its ids.
* ``CensusEngine.run`` times every host phase through it: pair space,
  emission and landing on every path, partitioning on the partitioned
  ones, with counts still bit-identical to the reference.
* The census step's stages carry their ``jax.named_scope`` names.
"""

import glob
import os
import re
import sys
import threading
import time
import types
import warnings

import jax
import numpy as np
import pytest

from repro.core import (
    CensusEngine, ShardStreamPipeline, census_batagelj_mrvar, default_mesh,
    scale_free_digraph)
from repro.core import engine as eng
from repro.core.plan_stream import PlanChunker
from repro.core.spans import span

#: the engine's host spans on the paths of ``CensusEngine.run``
RUN_SPANS = {"census.plan", "census.upload", "chunk.emit",
             "chunk.dispatch", "chunk.land", "census.assemble"}
STAGES = ("expand", "classify", "keep", "reduce")


def graph(n=80, seed=3):
    return scale_free_digraph(n=n, avg_degree=5, exponent=2.2,
                              mutual_p=0.3, seed=seed)


def traced(fn, tmp_path):
    """Run ``fn`` under the profiler; return its result and the host
    events ``[(name, start_ns, end_ns, {id: value})]`` of the trace."""
    from jax.profiler import ProfileData
    with jax.profiler.trace(str(tmp_path)):
        out = fn()
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    evs = []
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in ProfileData.from_file(path).planes:
            for line in plane.lines:
                evs.extend((e.name, e.start_ns, e.end_ns, dict(e.stats))
                           for e in line.events)
    return out, evs


class TestSpan:
    def test_bucket_sums_the_span_durations(self):
        stats = types.SimpleNamespace(host_land_seconds=0.25)
        seen = []
        for pause in (0.01, 0.02):
            with span("chunk.land", stats, "host_land_seconds",
                      census=1, chunk=0) as sp:
                time.sleep(pause)
            assert sp.seconds >= pause
            seen.append(sp.seconds)
        assert stats.host_land_seconds == 0.25 + seen[0] + seen[1]

    def test_without_bucket_only_times(self):
        stats = types.SimpleNamespace(host_emit_seconds=0.0)
        with span("chunk.emit", census=1) as sp:
            time.sleep(0.005)
        assert sp.seconds >= 0.005 and stats.host_emit_seconds == 0.0

    def test_counts_and_reraises_when_the_phase_raises(self):
        stats = types.SimpleNamespace(host_emit_seconds=0.0)
        with pytest.raises(KeyError):
            with span("chunk.emit", stats, "host_emit_seconds") as sp:
                raise KeyError("boom")
        assert stats.host_emit_seconds == sp.seconds > 0

    def test_bucket_loses_no_update_across_threads(self):
        stats = types.SimpleNamespace(host_emit_seconds=0.0)
        per_thread = [[] for _ in range(16)]

        def work(out):
            for _ in range(300):
                with span("chunk.emit", stats, "host_emit_seconds") as sp:
                    pass
                out.append(sp.seconds)

        old = sys.getswitchinterval()
        sys.setswitchinterval(1e-6)
        try:
            threads = [threading.Thread(target=work, args=(out,))
                       for out in per_thread]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=30)
        finally:
            sys.setswitchinterval(old)
        assert not any(t.is_alive() for t in threads)
        want = sum(sum(out) for out in per_thread)
        assert stats.host_emit_seconds == pytest.approx(want, rel=1e-9)

    def test_trace_carries_name_and_ids(self, tmp_path):
        def fn():
            with span("chunk.land", census=7, chunk=3, shard=1):
                time.sleep(0.001)
        _, evs = traced(fn, tmp_path)
        got = [ids for name, _s, _e, ids in evs if name == "chunk.land"]
        assert got == [{"census": 7, "chunk": 3, "shard": 1}]


#: engine settings of every streamed path of ``run``; the partitioned
#: ones run on a mesh of four devices
PATHS = {
    "stream-device": {},
    "stream-host": {"emit": "host"},
    "async-device": {"partition": True},
    "async-host": {"partition": True, "emit": "host"},
}


def engine_for(path):
    kw = PATHS[path]
    mesh = default_mesh(4) if kw.get("partition") else None
    return CensusEngine(backend="jnp", mesh=mesh, **kw)


@pytest.mark.parametrize("path", PATHS)
def test_run_sets_the_host_counters(path):
    g = graph()
    engine = engine_for(path)
    got = engine.run(g, max_items=256, orient="degree")
    np.testing.assert_array_equal(got, census_batagelj_mrvar(g))
    st = engine.stats
    assert st.chunks > 1
    assert st.host_pair_seconds > 0
    assert st.host_emit_seconds > 0
    assert st.host_land_seconds > 0
    assert (st.host_partition_seconds > 0) == st.partitioned
    assert st.host_merge_seconds == 0.0


@pytest.mark.parametrize("path", ["stream-device", "async-device"])
def test_run_spans_share_the_census_id(tmp_path, path):
    g = graph()
    engine = engine_for(path)
    engine.run(g, max_items=256, orient="degree")          # compile
    _, evs = traced(lambda: engine.run(g, max_items=256, orient="degree"),
                    tmp_path)
    mine = [(n, s, e, ids) for n, s, e, ids in evs
            if n.startswith(("census.", "chunk.", "pipeline."))]
    names = {n for n, *_ in mine}
    assert RUN_SPANS <= names
    assert ("census.partition" in names) == engine.stats.partitioned
    assert {ids["census"] for *_, ids in mine} == {2}
    assert all("chunk" in ids for n, *_, ids in mine
               if n.startswith("chunk."))
    st = engine.stats
    lands = [(e - s) / 1e9 for n, s, e, _ in mine if n == "chunk.land"]
    assert len(lands) == (st.dispatches_total if st.partitioned
                          else st.chunks)
    # the counter sums the same spans on the host's clock
    assert st.host_land_seconds == pytest.approx(sum(lands), abs=2e-3)


def test_session_spans_carry_one_census_id_per_operation(tmp_path):
    g = graph()
    session = CensusEngine(backend="jnp").session(g, max_items=256)
    session.census()

    def update():
        return session.update(add_src=[0, 1], add_dst=[5, 9])
    _, evs = traced(update, tmp_path)
    ids = {ids.get("census") for n, _s, _e, ids in evs
           if n in ("delta.merge", "census.plan", "chunk.emit")}
    assert len(ids) == 1 and None not in ids
    st = session.stats
    assert st.host_merge_seconds > 0 and st.host_pair_seconds > 0


def test_pipeline_stall_is_a_span(tmp_path):
    def slow():
        for i in range(3):
            time.sleep(0.02)
            yield i

    def drain():
        pipe = ShardStreamPipeline([slow()], depth=2, census=5)
        with pipe:
            return [w for _s, w in pipe], pipe.stalls
    (got, stalls), evs = traced(drain, tmp_path)
    assert got == [0, 1, 2] and stalls >= 1
    spans = [ids for n, _s, _e, ids in evs if n == "pipeline.stall"]
    assert len(spans) == stalls
    assert all(ids == {"census": 5, "shard": 0} for ids in spans)


def test_census_step_stages_are_named():
    rng = np.random.default_rng(0)
    from repro.core import from_edges
    g = from_edges(rng.integers(0, 50, 300), rng.integers(0, 50, 300), n=50)
    ch = PlanChunker(g, 256, orient="degree")
    sp = ch.space
    args = [np.asarray(a) for a in ch.device_arrays()] + [
        ch.descriptors(0).device_words(),
        np.arange(ch.chunk_shape, dtype=np.int32)]
    text = eng._desc_step.lower(
        *args, mesh=None, search_iters=sp.search_iters,
        desc_iters=ch.desc_iters, backend="jnp", orient=sp.orient,
        prune_self=sp.prune_self).compile().as_text()
    scopes = set(re.findall(r'op_name="jit\(_desc_step_impl\)/(\w+)/',
                            text))
    assert set(STAGES) <= scopes
