"""End-to-end driver: the paper's scaling experiment on re-synthesized
workloads (patents / orkut / webgraph analogues), distributed over every
local device with the paper's privatized-histogram reduction — followed by
the out-of-core streaming demo: a workload whose monolithic flat plan
exceeds the (stand-in) host plan-memory budget by >8x, completed by the
chunked CensusEngine under that budget.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python examples/census_scaling.py
"""

import time

import jax
import numpy as np

from repro.core import (
    CensusEngine, PAPER_WORKLOADS, build_plan, census_batagelj_mrvar,
    census_dict, default_mesh, pair_space, paper_workload,
    triad_census_distributed)
from repro.analysis.report import streaming_section
from repro.compile_cache import enable_compile_cache

SIZES = {"patents": (30_000, 3.0), "orkut": (5_000, 40.0),
         "webgraph": (15_000, 15.0)}

#: stand-in for the host plan-memory ceiling: on a real billion-edge run
#: this is the RAM that the monolithic O(W) item arrays would blow past;
#: here it is sized so the demo workload's full plan exceeds it >= 8x
PLAN_BUDGET_BYTES = 12 << 20

#: workload for the streaming demo — its monolithic packed-item plan is
#: ~130 MB, > 8x PLAN_BUDGET_BYTES: it "does not fit" under the budget
#: and only completes in streaming mode
STREAM_SIZE = ("webgraph", 6_000, 10.0)


def streaming_demo(mesh):
    name, n, deg = STREAM_SIZE
    g = paper_workload(name, n=n, avg_degree=deg, seed=0)
    w_pre = pair_space(g).num_items_preprune
    mono_bytes = 8 * w_pre
    max_items = PLAN_BUDGET_BYTES // 8     # 8 packed bytes per item
    print(f"== streaming  ({name} n={n} avg_deg={deg})")
    print(f"   monolithic plan: ~{mono_bytes / 1e6:.0f} MB of packed "
          f"items — {mono_bytes / PLAN_BUDGET_BYTES:.1f}x over the "
          f"{PLAN_BUDGET_BYTES / 1e6:.0f} MB plan budget; "
          "streaming instead")
    engine = CensusEngine(mesh=mesh, backend="jnp")
    t0 = time.perf_counter()
    census = engine.run(g, max_items=max_items,
                        progress=lambda k, total, items: print(
                            f"   chunk {k + 1}/{total}: {items} items",
                            end="\r"))
    dt = time.perf_counter() - t0
    st = engine.stats
    print(f"\n   streamed census: {dt:.3f}s, {st.chunks} chunks, "
          f"peak plan bytes {st.peak_plan_bytes / 1e6:.1f} MB "
          f"(vs {st.monolithic_plan_bytes / 1e6:.0f} MB monolithic), "
          f"step compiles: {st.step_compiles}")
    # parity on a reduced same-family graph (oracle is slow python)
    g_small = paper_workload(name, n=1200, avg_degree=8.0, seed=0)
    eng2 = CensusEngine(mesh=mesh, backend="jnp")
    assert (eng2.run(g_small, max_items=max(max_items // 64, 1)) ==
            census_batagelj_mrvar(g_small)).all()
    print("   reduced-graph streamed census == serial B&M oracle ✓")
    d = census_dict(census)
    print("   top connected triads: "
          + ", ".join(f"{k}={v}" for k, v in
                      sorted(d.items(), key=lambda kv: -kv[1])[1:5]))
    print()
    print(streaming_section(st))


def main():
    enable_compile_cache()
    mesh = default_mesh()
    ndev = len(jax.devices())
    print(f"devices: {ndev}  (mesh {mesh.axis_names})\n")

    for name, meta in PAPER_WORKLOADS.items():
        n, deg = SIZES[name]
        g = paper_workload(name, n=n, avg_degree=deg, seed=0)
        plan = build_plan(g, pad_to=ndev)
        st = plan.balance_stats(ndev)
        t0 = time.perf_counter()
        census = triad_census_distributed(plan, mesh=mesh)
        dt = time.perf_counter() - t0
        # serial reference (the paper's Fig-5 algorithm) on a reduced
        # same-family graph (the python oracle is O(items) in slow loops)
        g_small = paper_workload(name, n=min(g.n, 1500),
                                 avg_degree=min(deg, 8.0), seed=0)
        t1 = time.perf_counter()
        ref = census_batagelj_mrvar(g_small)
        dt_ref = time.perf_counter() - t1
        assert (triad_census_distributed(
            build_plan(g_small, pad_to=ndev), mesh=mesh) == ref).all()
        d = census_dict(census)
        print(f"== {name}  (outdeg exponent target "
              f"{meta['exponent']})")
        print(f"   n={g.n} arcs={g.num_arcs} work_items={plan.num_items}")
        print(f"   distributed census: {dt:.3f}s "
              f"({plan.num_items / dt:.3g} items/s, incl. compile on "
              f"first call); serial B&M oracle (reduced graph): "
              f"{dt_ref:.3f}s, equal ✓")
        print(f"   balance (max/mean work): flat plan "
              f"{st['flat_max_over_mean']:.4f} vs naive pair split "
              f"{st['pair_max_over_mean']:.2f}")
        print(f"   top connected triads: "
              + ", ".join(f"{k}={v}" for k, v in
                          sorted(d.items(), key=lambda kv: -kv[1])[1:5]))
        for shards in (64, 256, 512):
            p = build_plan(g, pad_to=shards)
            s = p.balance_stats(shards)
            print(f"   modeled speedup @{shards} shards: "
                  f"{shards / s['flat_max_over_mean']:.1f}x")
        print()

    streaming_demo(mesh)


if __name__ == "__main__":
    main()
