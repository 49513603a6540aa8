"""The paper's application (Figs 3–4): triadic monitoring of computer
network traffic with anomaly alarms.

Synthesizes background peer-to-peer traffic, injects a port-scanning burst
(one source fanning out — 021D triads) in later windows, and shows the
monitor flagging exactly those windows.

The monitor runs every window through one resident engine session
(graph arrays uploaded per window, chunk step compiled once for the whole
stream); with ``--stride`` below the window size, consecutive windows
overlap and are delta-updated incrementally — only the pairs whose rows
the arc churn touched are recounted, bit-identically to a full recompute.
(On this zipf workload every window churns arcs of the hub hosts, so the
affected pairs cover most of the graph and the per-window summary shows
little item reduction; the ``temporal_*`` benchmark rows use a
backbone-plus-ephemeral-flows stream where the same machinery cuts
items 3-9x.)

    PYTHONPATH=src python examples/network_monitor.py
    PYTHONPATH=src python examples/network_monitor.py \
        --backend pallas-fused --stride 600 --verbose
    PYTHONPATH=src python examples/network_monitor.py --mesh 4 --stride 600
    PYTHONPATH=src python examples/network_monitor.py --inject-faults 0
"""

import argparse
import os
import sys

import numpy as np

#: kept in sync with repro.core.census.BACKENDS (imported lazily in main
#: so --mesh can force virtual devices before the first jax import)
BACKENDS = ("jnp", "pallas", "pallas-fused")


def background_traffic(rng, n_hosts, n_edges):
    # zipf-ish client/server mix with ~30% reciprocity, exactly n_edges
    # (the reciprocated arcs ride inside the budget so the mutual-dyad
    # mix — which keeps the 021D baseline low — is preserved)
    k = int(n_edges / 1.25)
    src = (rng.zipf(1.5, k) - 1) % n_hosts
    dst = rng.integers(0, n_hosts, k)
    back = rng.random(k) < 0.3
    src2 = np.concatenate([src, dst[back]])
    dst2 = np.concatenate([dst, src[back]])
    short = n_edges - src2.size
    if short > 0:
        src2 = np.concatenate([src2, (rng.zipf(1.5, short) - 1) % n_hosts])
        dst2 = np.concatenate([dst2, rng.integers(0, n_hosts, short)])
    return src2[:n_edges], dst2[:n_edges]


def scan_burst(rng, n_hosts, n_targets):
    scanner = int(rng.integers(0, n_hosts))
    targets = rng.choice(n_hosts, size=n_targets, replace=False)
    return np.full(n_targets, scanner), targets


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--backend", choices=BACKENDS, default="jnp",
                    help="census backend for every window (default jnp)")
    ap.add_argument("--stride", type=int, default=None,
                    help="edges between windows (default: the window "
                         "size, i.e. tumbling; smaller values slide "
                         "incrementally)")
    ap.add_argument("--window", type=int, default=1200,
                    help="edges per census window")
    ap.add_argument("--windows", type=int, default=30,
                    help="logical traffic windows to synthesize")
    ap.add_argument("--no-incremental", action="store_true",
                    help="full per-window recompute even when sliding")
    ap.add_argument("--threshold", type=float, default=3.5,
                    help="z-score alarm threshold (sliding windows "
                         "dilute a burst across the overlap, so their "
                         "peak z is lower than tumbling)")
    ap.add_argument("--emit", choices=("device", "host"), default=None,
                    help="work-item emission mode (default: the engine "
                         "default, device — stream O(pairs) descriptors "
                         "and expand pairs→items in-kernel)")
    ap.add_argument("--mesh", type=int, default=None, metavar="N",
                    help="build an N-device mesh and PARTITION each "
                         "window's graph across it (each device holds "
                         "only its pair shard's local subgraph; delta "
                         "updates dispatch only the owning shards); "
                         "prints the per-window shard report")
    ap.add_argument("--inject-faults", type=int, default=None,
                    metavar="SEED",
                    help="adversarial mode: deterministically inject "
                         "transient dispatch failures, a poisoned "
                         "result, and one burst long enough to exhaust "
                         "the retry budget — the monitor must survive, "
                         "retrying what it can and logging the rest as "
                         "degraded windows instead of dying")
    ap.add_argument("--index", dest="index", action="store_true",
                    default=True,
                    help="maintain a persistent pair-space index so "
                         "each slide edits the plan by the delta "
                         "(default)")
    ap.add_argument("--no-index", dest="index", action="store_false",
                    help="rebuild the pair space from scratch every "
                         "window — the parity oracle for --index")
    ap.add_argument("--profile-host", action="store_true",
                    help="print the per-window host planning time split "
                         "(pair-space / delta-merge / item-emission "
                         "buckets) next to the device dispatch numbers")
    ap.add_argument("--verbose", action="store_true",
                    help="print the per-window engine summary lines")
    args = ap.parse_args()

    if args.mesh is not None and args.mesh >= 1 \
            and "jax" not in sys.modules:
        # force enough virtual host devices BEFORE the first jax import
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + f" --xla_force_host_platform_device_count="
                f"{args.mesh}").strip()
    from repro.compile_cache import enable_compile_cache
    from repro.core import (
        Fault, FaultPlan, SECURITY_PATTERNS, TriadMonitor, default_mesh)

    enable_compile_cache()

    mesh = default_mesh(args.mesh) if args.mesh is not None else None
    rng = np.random.default_rng(0)
    n_hosts, per_window = 400, args.window
    # overlapping windows arrive window/stride times as often, so scale
    # the trailing-history length to cover the same span of traffic
    stride = args.stride if args.stride is not None else per_window
    history = 10 * max(1, per_window // stride)
    faults = None
    if args.inject_faults is not None:
        frng = np.random.default_rng(args.inject_faults)
        ndev = args.mesh if args.mesh is not None else 1
        dev = int(frng.integers(ndev))
        # occurrences count DISPATCHES, not windows: each window's
        # census is ~20-50 chunk dispatches on the defaults, and a
        # failure in the very first window has no previous census to
        # carry forward, so aim the burst well past it
        burst = int(frng.integers(60, 200))
        faults = FaultPlan(seed=args.inject_faults, faults=[
            # a 3-deep consecutive burst outlasts the default retry
            # budget (2) -> exactly one degraded window
            *(Fault("dispatch", "error", device=dev, occurrence=burst + i)
              for i in range(3)),
            # a lone transient error and a poisoned result: both
            # retried/re-dispatched invisibly
            Fault("dispatch", "error", device=dev,
                  occurrence=int(frng.integers(250, 400))),
            Fault("dispatch", "poison", device=dev,
                  occurrence=int(frng.integers(450, 600))),
        ])
    monitor = TriadMonitor(
        n_hosts, window=per_window, stride=stride, history=history,
        threshold=args.threshold, backend=args.backend,
        incremental=not args.no_incremental,
        max_items=4096, emit=args.emit, index=args.index,
        mesh=mesh, partition=mesh is not None, faults=faults)

    scan_size = 200
    attack_windows = {25, 26, 27}
    attack_spans = []
    for w in range(args.windows):
        src, dst = background_traffic(
            rng, n_hosts,
            per_window - (scan_size if w in attack_windows else 0))
        if w in attack_windows:
            s2, d2 = scan_burst(rng, n_hosts, scan_size)
            src, dst = np.concatenate([src, s2]), np.concatenate([dst, d2])
            attack_spans.append((w * per_window, (w + 1) * per_window))
        monitor.observe(src, dst)

    alarms = monitor.alarms()
    stride = monitor.stride
    print(f"monitored {len(monitor.window_stats)} windows of "
          f"{per_window} flows (stride {stride}) over {n_hosts} hosts "
          f"on backend={args.backend}; injected scans in logical windows "
          f"{sorted(attack_windows)}\n")
    print("patterns:", {k: v for k, v in SECURITY_PATTERNS.items()})

    # per-window engine summary: items dispatched vs a full recompute,
    # affected pairs for incremental slides, any alarms on that window
    alarms_at = {}
    for a in alarms:
        alarms_at.setdefault(a["window"], []).append(a)
    total_items = total_full = 0
    print("\nper-window engine summary "
          "(items dispatched / full-recompute items):")
    for t, st in enumerate(monitor.window_stats):
        if st is None:      # degraded window: census carried forward
            print(f"  window {t:>3}  DEGRADED (census carried forward; "
                  f"next window recomputes in full)")
            continue
        total_items += st.items
        total_full += st.full_items
        fired = ",".join(f"{a['pattern']}(z={a['zscore']:.1f})"
                         for a in alarms_at.get(t, []))
        shard = ""
        if st.partitioned:
            # per-window shard report: dispatched items per shard, their
            # imbalance, and the per-device resident graph bytes vs what
            # replication would hold
            shard = (f" shards={st.shard_items}"
                     f" mom={st.shard_max_over_mean:.2f}"
                     f" gbytes={st.graph_resident_bytes}"
                     f"/{st.graph_replicated_bytes}")
        host = ""
        if args.profile_host:
            host = (f" host={st.plan_host_seconds * 1e3:.2f}ms"
                    f"[pair={st.host_pair_seconds * 1e3:.2f}"
                    f" merge={st.host_merge_seconds * 1e3:.2f}"
                    f" emit={st.host_emit_seconds * 1e3:.2f}]"
                    f"{'' if st.indexed else ' (no index)'}")
        line = (f"  window {t:>3}  items={st.items:>7}/{st.full_items:<7}"
                f" chunks={st.chunks:<2} affected_pairs="
                f"{st.affected_pairs:<5}{shard}{host} "
                f"{('ALARM ' + fired) if fired else ''}")
        if args.verbose or fired or args.profile_host:
            print(line)
    print(f"\ntotals: {total_items} items dispatched vs {total_full} for "
          f"full per-window recomputes "
          f"({total_full / max(total_items, 1):.2f}x reduction); "
          f"chunk step compiles: "
          f"{sum(s.step_compiles for s in monitor.window_stats if s)}")
    if args.profile_host:
        live = [s for s in monitor.window_stats if s is not None]
        pair = sum(s.host_pair_seconds for s in live)
        merge = sum(s.host_merge_seconds for s in live)
        emit = sum(s.host_emit_seconds for s in live)
        mode = "indexed" if args.index else "full per-window rebuild"
        print(f"host planning totals ({mode}): "
              f"{(pair + merge + emit) * 1e3:.1f}ms = "
              f"pair-space {pair * 1e3:.1f}ms + delta-merge "
              f"{merge * 1e3:.1f}ms + emission {emit * 1e3:.1f}ms "
              f"over {len(live)} windows")
    if args.inject_faults is not None:
        sess = monitor._session
        print(f"\nfault injection (seed {args.inject_faults}): "
              f"{sess.retries if sess else 0} retried dispatches, "
              f"{len(monitor.degraded)} degraded window(s) — the stream "
              f"survived")
        for d in monitor.degraded:
            print(f"  degraded window {d['window']}: {d['error']}")
    if mesh is not None and monitor.window_stats:
        last = next(s for s in reversed(monitor.window_stats)
                    if s is not None)
        moms = [s.shard_max_over_mean for s in monitor.window_stats
                if s is not None and s.partitioned and s.items]
        print(f"\nshard report ({args.mesh}-device mesh, partitioned "
              f"graph): per-device resident graph bytes "
              f"{last.graph_resident_bytes} vs replicated "
              f"{last.graph_replicated_bytes} "
              f"({last.graph_replicated_bytes / max(last.graph_resident_bytes, 1):.2f}x);"
              f" dispatch max/mean over windows: "
              f"mean {np.mean(moms) if moms else 1.0:.2f} "
              f"max {np.max(moms) if moms else 1.0:.2f}")

    # map flagged stream windows back onto the injected attack spans
    flagged = {a["window"] for a in alarms}
    hit_spans = set()
    for t in flagged:
        lo = t * stride
        for k, (alo, ahi) in enumerate(attack_spans):
            if lo < ahi and alo < lo + per_window:
                hit_spans.add(k)
    print(f"\ndetected {len(hit_spans)}/{len(attack_spans)} attack bursts"
          f"{' ✓' if hit_spans else ''}; alarm windows: {sorted(flagged)}")


if __name__ == "__main__":
    main()
