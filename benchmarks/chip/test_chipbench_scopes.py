"""Where a traced census spends its time by the program's own names
(``scopes.py``), and the readers of the engine's host counters.

The recorded traces come from a TPU v5e chip: ``trace_small`` and
``trace_spans`` are ``record_trace.py``'s, from a program without and
with spans and stage scopes; ``trace_megastep`` is one census of the
``cit-patents-16-x4`` graph through ``CensusEngine(partition=True)`` on
a one-chip mesh, whose megastep runs its fusions inside a ``while`` and
a ``conditional`` that the trace records as operations too."""

import gzip
from pathlib import Path

import pytest

from chip import drive, scopes, trace

DATA = Path(__file__).with_name("testdata")
METRICS = Path(__file__).with_name("metrics")


def _profile(name):
    from jax.profiler import ProfileData
    return ProfileData.from_serialized_xspace(
        gzip.decompress((DATA / name).read_bytes()))


@pytest.fixture(scope="module", params=["trace_small.xplane.pb.gz",
                                        "trace_spans.xplane.pb.gz",
                                        "trace_megastep.xplane.pb.gz"])
def recorded(request):
    name = request.param
    return name, scopes.load(DATA / name), trace.reduce(_profile(name), [0])


def test_stages_and_unscoped_add_up_to_busy_time(recorded):
    _name, space, base = recorded
    got = scopes.reduce(space, [0])
    assert set(got["scope_s"]) == set(scopes.STAGES) | {scopes.UNSCOPED}
    assert sum(got["scope_s"].values()) == pytest.approx(base["busy_s"],
                                                         rel=1e-9)
    assert got["window_s"] == pytest.approx(base["window_s"])


def test_a_program_without_scopes_reads_all_unscoped():
    got = scopes.reduce(scopes.load(DATA / "trace_small.xplane.pb.gz"), [0])
    assert all(v == 0 for k, v in got["scope_s"].items()
               if k != scopes.UNSCOPED)
    assert all(v == 0 for v in got["spans_s"].values())
    assert {n for n, _ in got["idle_gaps"]} <= set(trace.SPANS)


def test_scoped_trace_names_its_stages_spans_and_gaps():
    space = scopes.load(DATA / "trace_spans.xplane.pb.gz")
    got = scopes.reduce(space, [0])
    stages = {k for k, v in got["scope_s"].items() if v > 0}
    assert {"expand", "classify", "reduce"} <= stages
    spans = {k for k, v in got["spans_s"].items() if v > 0}
    assert {"census.plan", "census.upload", "chunk.emit", "chunk.dispatch",
            "chunk.land", "census.assemble"} <= spans
    # the program's spans sit inside the harness's census span
    census = [e - s for n, _t, s, e, _ in scopes.events(space)[1]
              if n == "census"]
    assert sum(got["spans_s"].values()) <= sum(census) / 1e9
    labels = {n for n, _ in got["idle_gaps"]}
    assert labels & set(scopes.PROGRAM_SPANS)
    name, seconds, ids = got["longest_spans"][0]
    assert name in scopes.PROGRAM_SPANS and seconds > 0
    assert "census" in ids


def test_megastep_fusions_count_inside_their_loop():
    got = scopes.reduce(scopes.load(DATA / "trace_megastep.xplane.pb.gz"),
                        [0])
    scope = got["scope_s"]
    busy = sum(scope.values())
    assert scope["expand"] + scope["classify"] + scope["reduce"] > 0.99 * busy
    assert got["spans_s"]["census.partition"] > 0


def test_stage_of_takes_the_first_stage_in_the_path():
    assert scopes.stage_of("jit(_desc_step_impl)/expand/closed_call/"
                           "gather") == "expand"
    assert scopes.stage_of("jit(f)/shard_map/classify/while/body/keep/"
                           "and:") == "classify"
    assert scopes.stage_of("jit(_desc_megastep_impl)/while/body/reduce:") \
        == "reduce"
    assert scopes.stage_of("gather") == scopes.UNSCOPED
    assert scopes.stage_of("jit(expanded)/keeper/x") == scopes.UNSCOPED
    assert scopes.stage_of("") == scopes.UNSCOPED


def test_overlapping_operations_count_once_for_the_innermost():
    ops = [("expand", 0, 10), ("classify", 5, 20), ("keep", 30, 40),
           ("reduce", 35, 38), ("unscoped", -5, 2),
           # a loop around two staged fusions, as the megastep's scan
           ("unscoped", 50, 60), ("expand", 51, 55), ("classify", 55, 58)]
    got = {k: v * 1e9 for k, v in scopes.scope_seconds(ops, 0, 59).items()}
    # each instant goes to the operation that started last
    assert got == pytest.approx({"unscoped": 1 + 1, "expand": 5 + 4,
                                 "classify": 15 + 3, "keep": 5 + 2,
                                 "reduce": 3})
    busy = trace.union(trace.clip([(s, e) for _, s, e in ops], 0, 59))
    assert sum(got.values()) == pytest.approx(
        sum(e - s for s, e in busy))


def test_self_time_subtracts_nested_program_spans_per_thread():
    spans = [("chunk.land", "a", 0, 100, {}),
             ("chunk.emit", "a", 10, 30, {}),
             ("chunk.dispatch", "a", 12, 20, {}),
             ("census", "a", 0, 200, {}),        # a harness span: ignored
             ("chunk.emit", "b", 5, 50, {}),     # another thread
             ("chunk.land", "a", 150, 260, {})]  # clipped by the window
    got = {k: v * 1e9 for k, v in
           scopes.self_seconds(spans, 0, 200).items()}
    assert got["chunk.land"] == pytest.approx(80 + 50)
    assert got["chunk.emit"] == pytest.approx(12 + 45)
    assert got["chunk.dispatch"] == pytest.approx(8)
    assert got["census.plan"] == 0
    assert scopes.label(15, spans) == "chunk.dispatch"
    assert scopes.label(199, spans) == "chunk.land"
    assert scopes.label(300, spans) == "none"


def _record(stats, driver="batch"):
    return {"driver": driver,
            "censuses": [{"stats": st} for st in stats]}


@pytest.mark.parametrize("metric,stats,want", [
    ("host_plan_s.batch",
     [{"host_pair_seconds": 0.04, "host_emit_seconds": 0.01},
      {"host_pair_seconds": 0.06, "host_emit_seconds": 0.03}], 0.07),
    ("host_land_s.batch",
     [{"host_land_seconds": 9.5}, {"host_land_seconds": 9.7}], 9.6),
    ("host_partition_s.batch",
     [{"partitioned": True, "host_partition_seconds": 2.0},
      {"partitioned": True, "host_partition_seconds": 3.0}], 2.5),
])
def test_host_counter_readers_mean_over_the_censuses(metric, stats, want):
    read = drive.load(METRICS / f"{metric}.py").read
    assert read(_record(stats)) == pytest.approx(want)
    assert read(_record(stats, driver="stream")) is None


@pytest.mark.parametrize("metric,stats", [
    # a program that does not time its host phases (the counters absent,
    # or the planning ones never set) reads nothing
    ("host_plan_s.batch", [{"host_pair_seconds": 0.0,
                            "host_emit_seconds": 0.0}]),
    ("host_land_s.batch", [{"host_pair_seconds": 0.0}]),
    ("host_partition_s.batch", [{"partitioned": True}]),
    # a census on one chip is not partitioned
    ("host_partition_s.batch", [{"partitioned": False,
                                 "host_partition_seconds": 0.0}]),
    ("host_plan_s.batch", []),
])
def test_host_counter_readers_find_nothing_to_read(metric, stats):
    read = drive.load(METRICS / f"{metric}.py").read
    assert read(_record(stats)) is None
