"""The reduction from a profiler trace to busy time, idle share and
labelled gaps, on a trace of a few census chunks recorded on a TPU v5e
chip by ``record_trace.py``."""

import gzip
from pathlib import Path

import pytest

from chip import trace

TRACE = Path(__file__).with_name("testdata") / "trace_small.xplane.pb.gz"


@pytest.fixture(scope="module")
def profile():
    from jax.profiler import ProfileData
    return ProfileData.from_serialized_xspace(
        gzip.decompress(TRACE.read_bytes()))


def _sweep_busy(intervals, lo, hi):
    """Busy time by a sweep over interval ends, independent of
    :func:`trace.union`."""
    edges = sorted([(max(s, lo), 1) for s, e in intervals if e > lo
                    and s < hi] + [(min(e, hi), -1) for s, e in intervals
                                   if e > lo and s < hi])
    busy, depth, at = 0.0, 0, None
    for t, step in edges:
        if depth > 0:
            busy += t - at
        depth += step
        at = t
    return busy


def test_busy_is_the_union_of_device_intervals(profile):
    ops, _spans, (lo, hi) = trace.events(profile)
    got = trace.reduce(profile, [0])
    want = _sweep_busy([(s, e) for _, s, e in ops[0]], lo, hi) / 1e9
    assert got["busy_s"] == pytest.approx(want, rel=1e-12)
    assert 0 < got["busy_s"] <= got["window_s"]
    assert got["op_events"] == len(ops[0])


def test_idle_is_one_minus_busy_over_window(profile):
    got = trace.reduce(profile, [0])
    assert got["idle_pct"] == pytest.approx(
        100 * (1 - got["busy_s"] / got["window_s"]))
    gaps_s = sum(e - s for s, e in trace.gaps(
        trace.union([(a, b) for _, a, b in trace.events(profile)[0][0]]),
        *trace.events(profile)[2]))
    assert gaps_s / 1e9 == pytest.approx(got["window_s"] - got["busy_s"])


def test_gaps_carry_the_host_spans(profile):
    got = trace.reduce(profile, [0])
    labels = {name for name, _ in got["idle_gaps"]}
    assert labels and labels <= set(trace.SPANS)
    assert "chunk" in labels
    assert len(got["idle_gaps"]) <= trace.TOP
    assert len(got["device_ops"]) == trace.TOP
    assert all(" = " in name and "{" not in name
               for name, _ in got["device_ops"])


def test_a_device_without_operations_reads_nothing(profile):
    got = trace.reduce(profile, [3])
    assert got["busy_s"] == 0 and got["idle_pct"] is None


def test_union_gaps_and_labels():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 8), (9, 9)]) == \
        [(0, 3), (5, 8)]
    assert trace.gaps([(0, 3), (5, 8)], -1, 10) == \
        [(-1, 0), (3, 5), (8, 10)]
    assert trace.gaps([(-5, -2), (0, 3), (12, 15)], -1, 10) == \
        [(-1, 0), (3, 10)]
    spans = [("census", 0, 10), ("chunk", 2, 4)]
    assert trace.label(3, spans) == "chunk"
    assert trace.label(6, spans) == "census"
    assert trace.label(11, spans) == "none"


def test_peaks_known_and_unknown_kinds():
    v5e = trace.peaks("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["int8_ops_per_s"] == 393e12
    with pytest.raises(trace.UnknownDevice):
        trace.peaks("TPU v9 imaginary")
