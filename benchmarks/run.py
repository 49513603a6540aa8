"""Benchmark harness — one entry per paper table/figure (census half) plus
LM substrate micro-benchmarks. Prints ``name,us_per_call,derived`` CSV;
``--json PATH`` additionally writes the rows as machine-readable JSON so
the perf trajectory is tracked across PRs.

Run: ``PYTHONPATH=src python -m benchmarks.run [--quick]``
"""

from __future__ import annotations

import argparse
import json
import os
import sys


#: every bench workload seeds its generators from this; recorded per
#: JSON row so cross-PR comparisons only match rows with identical
#: inputs
BENCH_SEED = 0


def write_json(path: str, rows: list) -> None:
    """Persist the benchmark rows as a ``BENCH_*.json``-style file: one
    object per row (name, us_per_call, derived, backend, jax_version,
    seed)."""
    import jax
    backend = jax.default_backend()
    payload = [
        {"name": name, "us_per_call": round(us, 3), "derived": derived,
         "backend": backend, "jax_version": jax.__version__,
         "seed": BENCH_SEED}
        for name, us, derived in rows
    ]
    with open(path, "w") as f:
        json.dump(payload, f, indent=1)
        f.write("\n")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="census benchmarks only")
    ap.add_argument("--smoke", action="store_true",
                    help="fast census smoke subset (CI regression gate)")
    ap.add_argument("--streaming-smoke", action="store_true",
                    help="streamed-vs-monolithic parity gate: tiny graph, "
                         "a max_items budget forcing >= 4 chunks")
    ap.add_argument("--temporal-smoke", action="store_true",
                    help="incremental-vs-full sliding-window gate: "
                         "bit-identity plus >= 2x item reduction at a "
                         "10%% stride")
    ap.add_argument("--emit-smoke", action="store_true",
                    help="device-vs-host emission gate: bit-identical "
                         "censuses (full + incremental) with >= 4x fewer "
                         "host-to-device plan bytes per chunk")
    ap.add_argument("--partition-smoke", action="store_true",
                    help="partitioned-execution gate: bit-identity vs "
                         "the single-device path on an 8-virtual-host "
                         "mesh, shard imbalance <= 1.2, >= 2x per-device "
                         "graph-byte reduction")
    ap.add_argument("--2d-smoke", dest="twod_smoke", action="store_true",
                    help="2D pair×vertex decomposition gate: bit-"
                         "identity 1D vs 2D vs reference on an 8-"
                         "virtual-device mesh ((4,2) and (2,4), both "
                         "emits, both orients, async + lockstep, "
                         "incremental session), >= 1.5x further halo "
                         "(resident adjacency entry) cut over 1D at "
                         "(4,2) and >= 2x at (2,4) on the power-law "
                         "workload")
    ap.add_argument("--mega-smoke", action="store_true",
                    help="megastep gate: in the tiny-window dispatch-"
                         "bound regime, K-window batched dispatches "
                         "must stay bit-identical, issue >= 2x fewer "
                         "dispatches than one-window async, and hold "
                         "within 1.15x of lock-step walltime")
    ap.add_argument("--fault-smoke", action="store_true",
                    help="fault-tolerance gate: a seeded plan (producer "
                         "error + transient dispatch error + one device "
                         "retirement) on an 8-virtual-device mesh must "
                         "finish bit-identical with >= 1 recorded "
                         "failover; an armed-but-idle engine must stay "
                         "within 1.05x of plain async; a run killed "
                         "mid-stream must checkpoint-resume to the "
                         "exact same census")
    ap.add_argument("--incr-host-smoke", action="store_true",
                    help="delta-incremental host-planner gate: warm "
                         "sliding-window updates with the persistent "
                         "pair-space index must be bit-identical to the "
                         "per-window rebuild oracle (censuses AND "
                         "post-prune item totals), >= 1.5x faster in "
                         "walltime and >= 1.3x in the pair-space host "
                         "phase at a 5%% stride on the backbone-"
                         "dominated degree-oriented workload")
    ap.add_argument("--async-smoke", action="store_true",
                    help="async-schedule gate: on a synthetic 4x-skewed "
                         "8-shard partition, async per-shard streams "
                         "must be bit-identical to the lock-step "
                         "oracle, >= 1.5x faster, and within 1.25x of "
                         "the balanced mean-shard ideal")
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="also write the rows as machine-readable JSON "
                         "(name, us_per_call, derived, backend), e.g. "
                         "BENCH_census.json")
    args = ap.parse_args()

    # the partition rows (part_shard{1,4,8} and --partition-smoke) need a
    # multi-device mesh; force 8 virtual host devices BEFORE the first
    # jax import, exactly like tests/conftest.py (single-device rows
    # still execute on one device — the virtual split only adds
    # addressable devices)
    if "jax" not in sys.modules:
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8"
            ).strip()

    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()

    rows: list = []
    from benchmarks import census_bench
    if args.fault_smoke:
        census_bench.fault_smoke(rows)
    elif args.twod_smoke:
        census_bench.twod_smoke(rows)
    elif args.mega_smoke:
        census_bench.mega_smoke(rows)
    elif args.incr_host_smoke:
        census_bench.incr_host_smoke(rows)
    elif args.async_smoke:
        census_bench.async_smoke(rows)
    elif args.partition_smoke:
        census_bench.partition_smoke(rows)
    elif args.emit_smoke:
        census_bench.emit_smoke(rows)
    elif args.temporal_smoke:
        census_bench.temporal_smoke(rows)
    elif args.streaming_smoke:
        census_bench.streaming_smoke(rows)
    elif args.smoke:
        census_bench.run_smoke(rows)
    else:
        census_bench.run(rows)
        if not args.quick:
            from benchmarks import lm_bench
            lm_bench.run(rows)

    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.3f},{derived}")
    sys.stdout.flush()
    if args.json:
        write_json(args.json, rows)


if __name__ == "__main__":
    main()
