"""Temporal monitoring: sliding-window censuses, incremental parity,
alarm behavior, input validation, and proportion/alarm caching."""

import numpy as np
import pytest

from repro.core import (
    SECURITY_PATTERN_INDICES, SECURITY_PATTERNS, TRIAD_NAMES, TriadMonitor,
    build_plan, from_edges, monitor_stream, triad_census)


def direct_census(src, dst, n, lo, hi, backend="jnp", orient="none"):
    g = from_edges(src[lo:hi], dst[lo:hi], n=n)
    return triad_census(build_plan(g, orient=orient), backend=backend)


def stream(seed, n, length, zipf=1.6, mutual_p=0.3):
    rng = np.random.default_rng(seed)
    src = (rng.zipf(zipf, length) - 1) % n
    dst = rng.integers(0, n, length)
    back = rng.random(length) < mutual_p
    src = np.where(back, dst, src)
    return src.astype(np.int64), dst.astype(np.int64)


# ------------------------------------------------------------ window parity


class TestWindowParity:
    @pytest.mark.parametrize("incremental", [True, False])
    def test_sliding_windows_match_direct_census(self, incremental):
        n, W, S = 100, 400, 100
        src, dst = stream(0, n, 1600)
        mon = TriadMonitor(n, window=W, stride=S, history=2,
                           incremental=incremental)
        out = []
        # ragged batches: windowing must not depend on batch boundaries
        for lo, hi in ((0, 250), (250, 900), (900, 901), (901, 1600)):
            out.extend(mon.observe(src[lo:hi], dst[lo:hi]))
        starts = range(0, 1600 - W + 1, S)
        assert len(out) == len(list(starts))
        for census, lo in zip(out, starts):
            np.testing.assert_array_equal(
                census, direct_census(src, dst, n, lo, lo + W),
                err_msg=f"window at {lo}")

    @pytest.mark.parametrize("backend", ["jnp", "pallas", "pallas-fused"])
    @pytest.mark.parametrize("orient", ["none", "degree"])
    def test_incremental_bit_identical_all_backends(self, backend, orient):
        """Acceptance: incremental window updates == full per-window
        recompute across all 3 backends x both orients."""
        n, W, S = 60, 150, 50
        src, dst = stream(1, n, 450)
        censuses = {}
        for incremental in (True, False):
            mon = TriadMonitor(n, window=W, stride=S, history=2,
                               backend=backend, orient=orient,
                               incremental=incremental)
            mon.observe(src, dst)
            censuses[incremental] = mon.censuses
        np.testing.assert_array_equal(censuses[True], censuses[False])
        np.testing.assert_array_equal(
            censuses[True][-1],
            direct_census(src, dst, n, 450 - W, 450,
                          backend=backend, orient=orient))

    def test_tumbling_equals_stride_eq_window(self):
        n, W = 80, 300
        src, dst = stream(2, n, 900)
        default = TriadMonitor(n, window=W)           # stride defaults to W
        explicit = TriadMonitor(n, window=W, stride=W)
        out_d = default.observe(src, dst)
        out_e = explicit.observe(src, dst)
        assert out_d.shape == (3, 16)
        np.testing.assert_array_equal(out_d, out_e)
        for k in range(3):
            np.testing.assert_array_equal(
                out_d[k], direct_census(src, dst, n, k * W, (k + 1) * W))

    def test_duplicate_and_self_loop_edges_collapse(self):
        n = 10
        src = np.array([1, 1, 1, 2, 3, 3])
        dst = np.array([2, 2, 1, 1, 4, 4])
        mon = TriadMonitor(n, window=6)
        out = mon.observe(src, dst)
        np.testing.assert_array_equal(
            out[0], direct_census(src, dst, n, 0, 6))

    def test_incremental_processes_fewer_items(self):
        n, W, S = 4000, 800, 80         # 10% stride on a sparse stream
        rng = np.random.default_rng(3)
        src = rng.integers(0, n, 2400)
        dst = rng.integers(0, n, 2400)
        mon = TriadMonitor(n, window=W, stride=S, history=2,
                           incremental=True, max_items=1024)
        mon.observe(src, dst)
        slid = mon.window_stats[1:]
        assert slid and all(s.items < s.full_items for s in slid)

    @pytest.mark.parametrize("backend", ["jnp", "pallas-fused"])
    def test_incremental_halves_items(self, backend):
        """Sliding a service-backbone stream at a 10% stride: the
        incremental windows equal full recomputes, count at least 2x
        fewer items, and compile the session step at most once."""
        rng = np.random.default_rng(0)
        window = 1500
        src, dst, n = monitor_stream(rng, 40, 1500, 300, 5 * window)
        mons = {}
        for incremental in (True, False):
            mons[incremental] = TriadMonitor(
                n, window=window, stride=window // 10, history=5,
                backend=backend, incremental=incremental, max_items=2048)
            mons[incremental].observe(src, dst)
        np.testing.assert_array_equal(mons[True].censuses,
                                      mons[False].censuses)
        slid = mons[True].window_stats[1:]
        items = sum(s.items for s in slid)
        full_items = sum(s.full_items for s in slid)
        assert full_items >= 2 * items > 0
        assert sum(s.step_compiles for s in mons[True].window_stats) <= 1

    @pytest.mark.parametrize("orient", ["none", "degree"])
    def test_indexed_monitor_matches_rebuild(self, orient):
        """Warm slides through the persistent pair-space index give the
        censuses and post-prune item totals of the per-window rebuild
        (``index=False``) on a backbone-dominated stream."""
        rng = np.random.default_rng(0)
        window, stride = 4000, 200
        src, dst, n = monitor_stream(rng, 200, 500, 1500,
                                     window + 8 * stride, eph_every=50)
        mons = {}
        for index in (True, False):
            mons[index] = TriadMonitor(
                n, window=window, stride=stride, history=5,
                orient=orient, max_items=2048, index=index)
            mons[index].observe(src, dst)
        np.testing.assert_array_equal(mons[True].censuses,
                                      mons[False].censuses)
        slid = {ix: mons[ix].window_stats[1:] for ix in mons}
        assert len(slid[True]) == 8
        assert all(s.indexed for s in slid[True])
        assert [s.full_items for s in slid[True]] == \
            [s.full_items for s in slid[False]]


# ------------------------------------------------------------ observe input


class TestObserveValidation:
    def test_empty_batch_raises(self):
        mon = TriadMonitor(10, window=5)
        with pytest.raises(ValueError, match="empty"):
            mon.observe([], [])

    def test_length_mismatch_raises(self):
        mon = TriadMonitor(10, window=5)
        with pytest.raises(ValueError, match="mismatch"):
            mon.observe([1, 2], [3])

    def test_out_of_range_raises(self):
        mon = TriadMonitor(10, window=5)
        with pytest.raises(ValueError, match="range"):
            mon.observe([1], [10])
        with pytest.raises(ValueError, match="range"):
            mon.observe([-1], [2])

    def test_2d_input_is_raveled(self):
        n = 12
        src = np.array([[1, 2], [3, 4]])
        dst = np.array([[5, 6], [7, 8]])
        mon = TriadMonitor(n, window=4)
        out = mon.observe(src, dst)
        np.testing.assert_array_equal(
            out[0], direct_census(src.ravel(), dst.ravel(), n, 0, 4))

    def test_rejects_bad_config(self):
        with pytest.raises(ValueError):
            TriadMonitor(0)
        with pytest.raises(ValueError):
            TriadMonitor(5, window=0)
        with pytest.raises(ValueError):
            TriadMonitor(5, window=10, stride=11)
        with pytest.raises(ValueError):
            TriadMonitor(5, window=10, stride=0)
        with pytest.raises(ValueError):
            TriadMonitor(5, window=10, history=0)

    def test_legacy_positional_signature(self):
        """(n_nodes, window, history, threshold) positionally — the seed's
        dataclass field order; stride is keyword-only."""
        mon = TriadMonitor(50, 100, 5, 2.5)
        assert (mon.window, mon.history, mon.threshold) == (100, 5, 2.5)
        assert mon.stride == mon.window          # tumbling default
        with pytest.raises(TypeError):
            TriadMonitor(50, 100, 5, 2.5, 10)    # no 5th positional

    def test_partial_window_emits_nothing(self):
        mon = TriadMonitor(10, window=100)
        out = mon.observe([1, 2], [3, 4])
        assert out.shape == (0, 16) and mon.censuses.shape == (0, 16)


# ------------------------------------------------------------ alarms


def scan_burst_stream(rng, n_hosts, per_window, n_windows, attack_windows,
                      n_targets=120):
    """The network_monitor example scenario: zipf background + injected
    port-scan bursts (021D fan-out) in the attack windows."""
    chunks_s, chunks_d = [], []
    for w in range(n_windows):
        k = per_window - (n_targets if w in attack_windows else 0)
        src = (rng.zipf(1.5, k) - 1) % n_hosts
        dst = rng.integers(0, n_hosts, k)
        back = rng.random(k) < 0.3
        src = np.concatenate([src[~back], dst[back]])
        dst = np.concatenate([dst[~back], src[:back.sum()]])
        if w in attack_windows:
            scanner = int(rng.integers(0, n_hosts))
            targets = rng.choice(n_hosts, size=n_targets, replace=False)
            src = np.concatenate([src, np.full(n_targets, scanner)])
            dst = np.concatenate([dst, targets])
        chunks_s.append(src[:per_window])
        chunks_d.append(dst[:per_window])
    return np.concatenate(chunks_s), np.concatenate(chunks_d)


class TestAlarms:
    def test_pattern_indices_match_names(self):
        for pattern, types in SECURITY_PATTERNS.items():
            np.testing.assert_array_equal(
                SECURITY_PATTERN_INDICES[pattern],
                [TRIAD_NAMES.index(t) for t in types])

    def test_scan_burst_fires_scanning_alarm(self):
        rng = np.random.default_rng(0)
        n_hosts, per_window = 200, 600
        attack = {14, 15}
        src, dst = scan_burst_stream(rng, n_hosts, per_window, 17, attack)
        mon = TriadMonitor(n_hosts, window=per_window, history=8,
                           threshold=4.0)
        mon.observe(src, dst)
        alarms = mon.alarms()
        flagged = {a["window"] for a in alarms
                   if a["pattern"] == "scanning"}
        assert attack <= flagged, (attack, alarms)
        false_pos = flagged - attack
        assert len(false_pos) <= 1, alarms

    def test_robust_baseline_survives_poisoned_history(self):
        """Median/MAD baseline: a minority of poisoned (attack-like)
        history windows must not suppress detection of the next attack
        (a mean/std baseline would absorb them)."""
        clean = np.zeros(16, np.int64)
        clean[1] = 900
        clean[3] = 10                    # steady small 021D share
        poisoned = clean.copy()
        poisoned[3] = 450                # attack-sized 021D share
        mon = TriadMonitor(10, window=5, history=8, threshold=4.0)
        for _ in range(6):
            mon.record(clean)
        for _ in range(2):
            mon.record(poisoned)         # minority poison in the baseline
        mon.record(poisoned)             # the attack window itself
        alarms = [a for a in mon.alarms()
                  if a["pattern"] == "scanning" and a["window"] == 8]
        assert alarms, mon.alarms()
        # and a fully clean window after the attack stays quiet
        mon.record(clean)
        assert not [a for a in mon.alarms() if a["window"] == 9]

    def test_alarm_cache_is_incremental_and_stable(self):
        rng = np.random.default_rng(4)
        n_hosts, per_window = 150, 400
        src, dst = scan_burst_stream(rng, n_hosts, per_window, 14, {11})
        fresh = TriadMonitor(n_hosts, window=per_window, history=6,
                             threshold=4.0)
        cached = TriadMonitor(n_hosts, window=per_window, history=6,
                              threshold=4.0)
        half = 7 * per_window
        cached.observe(src[:half], dst[:half])
        first = cached.alarms()
        assert cached.alarms() == first          # idempotent
        cached.observe(src[half:], dst[half:])
        fresh.observe(src, dst)
        assert cached.alarms() == fresh.alarms() # cache == full rescan

    def test_threshold_is_retunable_after_caching(self):
        """Scores are cached threshold-free: loosening the threshold after
        alarms() ran must surface alarms in already-evaluated windows."""
        rng = np.random.default_rng(6)
        n_hosts, per_window = 150, 400
        src, dst = scan_burst_stream(rng, n_hosts, per_window, 14, {11})
        mon = TriadMonitor(n_hosts, window=per_window, history=6,
                           threshold=1e9)
        mon.observe(src, dst)
        assert mon.alarms() == []                # nothing passes 1e9
        mon.threshold = 4.0
        fresh = TriadMonitor(n_hosts, window=per_window, history=6,
                             threshold=4.0)
        fresh.observe(src, dst)
        assert mon.alarms() == fresh.alarms() != []

    def test_proportions_cached_per_window(self):
        mon = TriadMonitor(10, window=5, history=2)
        c = np.zeros(16, np.int64)
        c[1], c[3] = 50, 25
        mon.record(c)
        props = mon.proportions()
        assert props.shape == (1, 16)
        np.testing.assert_allclose(props[0], c / 75.0)
        assert mon.proportions().shape == (0, 16) or True  # no mutation
        mon.record(c)
        assert mon.proportions().shape == (2, 16)
