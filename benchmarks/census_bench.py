"""Census benchmarks mapping to the paper's tables/figures.

* fig6  — outdegree power-law distributions of the three re-synthesized
          workloads (patents / orkut / webgraph analogues).
* fig9  — utilization analogue: work-balance of the flat plan vs a naive
          pair-partitioned plan (the paper's CPU-utilization story).
* fig10/11/13 — strong-scaling analogue per workload: measured single-
          device throughput + modeled speedup from per-shard work shares
          (exact for a bandwidth-bound vector workload), up to 512 shards.
* table_census — exact 16-type censuses, validated against serial
          Batagelj-Mrvar.

CPU-host caveat (documented in EXPERIMENTS.md): this container has one
physical core, so wall-clock multi-device speedups are not observable;
the scaling columns report the work-partition model the paper's speedup
figures measure on real hardware, plus measured items/second throughput.
"""

from __future__ import annotations

import time

import numpy as np

from repro.core import (
    PAPER_WORKLOADS, build_plan, census_batagelj_mrvar, census_dict,
    paper_workload, triad_census)
from repro.core.generators import measured_exponent, monitor_stream

#: scaled-down workload sizes (nodes, avg outdegree) — shaped like the
#: paper's patents (sparse, steep tail) / orkut (dense social) / webgraph
WORKLOAD_SIZES = {
    "patents": (30_000, 3.0),     # W ~  77M work items
    "orkut": (5_000, 40.0),       # W ~ 100M
    "webgraph": (15_000, 15.0),   # W ~ 118M
}


def _timeit(fn, *args, reps=3, **kw):
    fn(*args, **kw)                      # warmup / compile
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        out = fn(*args, **kw)
        ts.append(time.perf_counter() - t0)
    return min(ts), out


def fig6_degree_distributions(rows: list):
    for name in PAPER_WORKLOADS:
        n, deg = WORKLOAD_SIZES[name]
        g = paper_workload(name, n=n, avg_degree=deg, seed=0)
        exp = measured_exponent(g)
        rows.append((f"fig6_{name}_exponent", exp * 1e6,
                     f"target={PAPER_WORKLOADS[name]['exponent']}"))


def fig9_balance(rows: list):
    g = paper_workload("orkut", *WORKLOAD_SIZES["orkut"], seed=1)
    plan = build_plan(g, pad_to=64)
    st = plan.balance_stats(64)
    rows.append(("fig9_flat_max_over_mean",
                 st["flat_max_over_mean"] * 1e6, "flat plan, 64 shards"))
    rows.append(("fig9_pair_max_over_mean",
                 st["pair_max_over_mean"] * 1e6,
                 "naive pair partitioning"))


def scaling_fig(rows: list, name: str, fig: str):
    n, deg = WORKLOAD_SIZES[name]
    g = paper_workload(name, n=n, avg_degree=deg, seed=0)
    plan = build_plan(g)
    dt, census = _timeit(triad_census, plan)
    items_per_s = plan.num_items / dt
    rows.append((f"{fig}_{name}_census", dt * 1e6,
                 f"items={plan.num_items};items_per_s={items_per_s:.3g}"))
    # modeled strong scaling from per-shard work shares (paper's speedup)
    for shards in (8, 64, 256, 512):
        p = build_plan(g, pad_to=shards)
        st = p.balance_stats(shards)
        speedup = shards / st["flat_max_over_mean"]
        rows.append((f"{fig}_{name}_speedup_{shards}",
                     speedup * 1e6, "modeled from work shares"))


def table_census(rows: list):
    """Exact censuses; the (slow, serial-python) Batagelj-Mrvar oracle
    runs on a reduced graph of the same family — full-size equality is
    covered by the JAX-vs-oracle test suite."""
    for name in PAPER_WORKLOADS:
        n, deg = WORKLOAD_SIZES[name]
        g_small = paper_workload(name, n=min(n, 2000),
                                 avg_degree=min(deg, 10.0), seed=0)
        assert (triad_census(build_plan(g_small)) ==
                census_batagelj_mrvar(g_small)).all(), name
        g = paper_workload(name, n=n, avg_degree=deg, seed=0)
        c = triad_census(build_plan(g))
        d = census_dict(c)
        top = sorted(d.items(), key=lambda kv: -kv[1])[1:4]
        rows.append((f"table_census_{name}_ok", 1.0,
                     ";".join(f"{k}={v}" for k, v in top)))


def om_scaling(rows: list):
    """Batagelj–Mrvar's O(m) claim: census time ~ linear in work items
    (Σ deg(u)+deg(v) over edges) at fixed degree structure."""
    from repro.core import scale_free_digraph
    pts = []
    for n in (10_000, 20_000, 40_000, 80_000):
        g = scale_free_digraph(n=n, avg_degree=6, exponent=2.3,
                               mutual_p=0.3, preferential=False, seed=0)
        plan = build_plan(g)
        dt, _ = _timeit(triad_census, plan)
        pts.append((plan.num_items, dt))
        rows.append((f"fig_om_n{n}", dt * 1e6,
                     f"items={plan.num_items};"
                     f"ns_per_item={dt / plan.num_items * 1e9:.1f}"))
    # linearity check: per-item time ratio largest/smallest graph
    per = [t / w for w, t in pts]
    rows.append(("fig_om_linearity_ratio",
                 max(per) / min(per) * 1e6,
                 "~1.0 == linear in work items"))


def kernel_throughput(rows: list):
    import jax.numpy as jnp
    from repro.kernels import tricode_histogram_ref
    rng = np.random.default_rng(0)
    w = 1 << 20
    from repro.kernels.tricode_hist import tricode_histogram_kernel
    tri = jnp.asarray(rng.integers(0, 64, w), jnp.int32)
    mask = jnp.ones(w, bool)
    # hoist the jnp.where masking out of BOTH timed paths: each consumes
    # the identical pre-masked array (w is already a BLOCK_ITEMS multiple),
    # so neither side smuggles masking/padding cost into its timing
    masked = jnp.where(mask, tri, 64).block_until_ready()
    dt_ref, _ = _timeit(
        lambda: tricode_histogram_ref(masked).block_until_ready())
    dt_k, _ = _timeit(lambda: tricode_histogram_kernel(
        masked, interpret=True).block_until_ready())
    rows.append(("kernel_tricode_hist_jnp", dt_ref * 1e6,
                 f"{w / dt_ref:.3g} items/s"))
    rows.append(("kernel_tricode_hist_pallas_interp", dt_k * 1e6,
                 "interpret-mode (CPU correctness harness)"))


#: reduced sizes for the fused-kernel columns: interpret mode re-simulates
#: every grid step on the CPU host, so the full WORKLOAD_SIZES are too slow
FUSED_SIZES = {
    "patents": (3_000, 3.0),
    "orkut": (800, 20.0),
    "webgraph": (1_500, 8.0),
}


def fused_vs_reference(rows: list):
    """Fused single-pass kernel vs the jnp reference path, plus the
    degree-oriented planning work reduction (see EXPERIMENTS.md)."""
    for name in PAPER_WORKLOADS:
        n, deg = FUSED_SIZES[name]
        g = paper_workload(name, n=n, avg_degree=deg, seed=0)
        plan = build_plan(g)
        plan_deg = build_plan(g, orient="degree")
        dt_ref, c_ref = _timeit(triad_census, plan, backend="jnp")
        dt_fused, c_fused = _timeit(triad_census, plan,
                                    backend="pallas-fused")
        # explicit raise (not assert): this parity check is the regression
        # gate benchmarks/check.sh relies on, and must survive python -O
        if not (c_ref == c_fused).all():
            raise AssertionError(f"fused census mismatch on {name}")
        rows.append((f"fused_{name}_jnp", dt_ref * 1e6,
                     f"items_per_s={plan.num_items / dt_ref:.3g}"))
        rows.append((f"fused_{name}_pallas_fused_interp", dt_fused * 1e6,
                     f"items_per_s={plan.num_items / dt_fused:.3g};"
                     "interpret-mode (CPU correctness harness)"))
        # degree-oriented planning: same census, fewer work items
        dt_deg, c_deg = _timeit(triad_census, plan_deg,
                                backend="pallas-fused")
        if not (c_ref == c_deg).all():
            raise AssertionError(
                f"degree-oriented census mismatch on {name}")
        rows.append((f"fused_{name}_degree_oriented", dt_deg * 1e6,
                     f"items={plan_deg.num_items} vs {plan.num_items} "
                     f"({plan_deg.num_items / plan.num_items:.2%} of "
                     "default plan)"))


def streaming_vs_monolithic(rows: list):
    """Tentpole rows: streamed (chunked out-of-core) vs monolithic census.

    The monolithic path materializes the whole O(W) plan and ships it in
    one dispatch; streaming caps the per-dispatch packed-item bytes at
    ``8 * max_items`` and accumulates per-chunk partials.  The sweep
    includes a budget the monolithic plan exceeds by >= 8x, and asserts
    bit-identical censuses plus a compile-once chunk step.
    """
    from repro.core import CensusEngine, pair_space

    g = paper_workload("webgraph", n=6_000, avg_degree=10.0, seed=0)
    w_pre = pair_space(g).num_items_preprune
    mono = CensusEngine(backend="jnp")
    dt_mono, c_mono = _timeit(mono.run, g)
    rows.append(("stream_monolithic", dt_mono * 1e6,
                 f"plan_bytes={mono.stats.peak_plan_bytes};"
                 f"items={mono.stats.items}"))
    for frac in (8, 32):
        engine = CensusEngine(backend="jnp")
        max_items = -(-w_pre // frac)
        # compile-once gate on the FIRST (un-warmed) run: a per-chunk
        # recompilation regression compiles one entry per chunk here,
        # before _timeit's warmup can mask it in the cache
        c = engine.run(g, max_items=max_items)
        compiles_first = engine.stats.step_compiles
        if compiles_first > 1:
            raise AssertionError(
                f"per-chunk recompilation: {compiles_first} "
                f"compiles for {engine.stats.chunks} chunks")
        dt, c = _timeit(engine.run, g, max_items=max_items)
        st = engine.stats
        if not (c == c_mono).all():
            raise AssertionError(f"streamed census mismatch at 1/{frac}")
        # the 1/32 budget demonstrates a workload whose monolithic plan
        # is >= 8x the chunk budget (pruning keeps the 1/8 run near ~7x)
        if frac >= 32 and st.monolithic_plan_bytes < 8 * st.peak_plan_bytes:
            raise AssertionError(
                f"budget not demonstrated: monolithic "
                f"{st.monolithic_plan_bytes} < 8x peak "
                f"{st.peak_plan_bytes}")
        rows.append((f"stream_budget_1_{frac}", dt * 1e6,
                     f"chunks={st.chunks};"
                     f"peak_plan_bytes={st.peak_plan_bytes};"
                     f"monolithic_bytes={st.monolithic_plan_bytes};"
                     f"chunk_max_over_mean={st.chunk_max_over_mean:.3f};"
                     f"step_compiles={compiles_first}"))


def streaming_smoke(rows: list):
    """CI gate (benchmarks/check.sh): tiny graph, a max_items budget that
    forces >= 4 chunks (with intra-pair splits), parity-checked against
    the monolithic census on the jnp and pallas-fused backends."""
    from repro.core import CensusEngine, pair_space

    g = paper_workload("orkut", n=400, avg_degree=12.0, seed=0)
    want = triad_census(build_plan(g))
    w_pre = pair_space(g).num_items_preprune
    max_items = max(w_pre // 6, 1)
    for backend in ("jnp", "pallas-fused"):
        engine = CensusEngine(backend=backend)
        # first run is un-warmed: per-chunk recompilation shows up here
        got = engine.run(g, max_items=max_items)
        compiles_first = engine.stats.step_compiles
        if compiles_first > 1:
            raise AssertionError(
                f"per-chunk recompilation on {backend}: "
                f"{compiles_first} compiles for "
                f"{engine.stats.chunks} chunks")
        dt, got = _timeit(engine.run, g, max_items=max_items)
        st = engine.stats
        if not (got == want).all():
            raise AssertionError(f"streamed {backend} != monolithic")
        if st.chunks < 4:
            raise AssertionError(f"smoke too coarse: {st.chunks} chunks")
        rows.append((f"stream_smoke_{backend}", dt * 1e6,
                     f"chunks={st.chunks};items={st.items};"
                     f"peak_plan_bytes={st.peak_plan_bytes};"
                     f"step_compiles={compiles_first};parity=ok"))


def device_emission(rows: list):
    """Tentpole rows: host vs device work-item emission.

    ``emit="host"`` (the PR 3 baseline) materializes, packs and uploads
    every O(W) work item per chunk; ``emit="device"`` ships O(pairs)
    descriptors and expands pairs→items in-kernel.  Same chunk schedule,
    bit-identical censuses (asserted in-row), and the per-chunk
    host→device plan bytes shrink by the mean items-per-pair factor.
    """
    from repro.core import CensusEngine, pair_space

    g = paper_workload("webgraph", n=6_000, avg_degree=10.0, seed=0)
    w_pre = pair_space(g).num_items_preprune
    max_items = -(-w_pre // 32)
    res = {}
    for emit in ("host", "device"):
        engine = CensusEngine(backend="jnp", emit=emit)
        dt, c = _timeit(engine.run, g, max_items=max_items)
        res[emit] = (dt, c, engine.stats)
        st = engine.stats
        rows.append((f"emit_stream_{emit}", dt * 1e6,
                     f"chunks={st.chunks};items={st.items};"
                     f"plan_upload_bytes_per_chunk={st.plan_upload_bytes}"))
    if not (res["host"][1] == res["device"][1]).all():
        raise AssertionError("device-emit census != host-emit census")
    ratio = (res["host"][2].plan_upload_bytes
             / res["device"][2].plan_upload_bytes)
    rows.append(("emit_upload_reduction", ratio * 1e6,
                 "host/device plan bytes per chunk (same schedule)"))

    # warm incremental-update walltime: resident sessions on the
    # monitoring workload, timed over a fixed reciprocal delta after
    # warmup — the row the device-emission path must improve
    rng = np.random.default_rng(0)
    window = 4000
    src, dst, n = monitor_stream(rng, 80, 3000, 800, 2 * window)
    from repro.core import from_edges
    g = from_edges(src[:window], dst[:window], n=n)
    # reciprocal delta: arcs of the NEXT window absent from g (so add
    # followed by delete restores g exactly — set semantics)
    base = src[:window] * n + dst[:window]
    cand_s, cand_d = src[window:], dst[window:]
    fresh = ~np.isin(cand_s * n + cand_d, base) & (cand_s != cand_d)
    d_src, d_dst = cand_s[fresh][:400], cand_d[fresh][:400]
    dts = {}
    for emit in ("host", "device"):
        session = CensusEngine(backend="jnp", emit=emit).session(
            g, max_items=4096)
        want = session.census()

        def cycle():
            session.update(d_src, d_dst)
            return session.update(del_src=d_src, del_dst=d_dst)

        dt, back = _timeit(cycle)
        dts[emit] = dt / 2                 # one update per half-cycle
        if not (back == want).all():
            raise AssertionError(f"emit={emit}: reciprocal updates "
                                 "did not restore the census")
        st = session.stats
        rows.append((f"emit_incr_update_{emit}", dts[emit] * 1e6,
                     f"affected_pairs={st.affected_pairs};"
                     f"items={st.items};"
                     f"plan_upload_bytes_per_chunk={st.plan_upload_bytes}"))
    rows.append(("emit_incr_update_speedup",
                 dts["host"] / max(dts["device"], 1e-9) * 1e6,
                 "host-emission walltime / device-emission walltime, "
                 "warm incremental update"))


def emit_smoke(rows: list):
    """CI gate (benchmarks/check.sh --emit-smoke): device-emission
    censuses must be bit-identical to host emission on the jnp and
    pallas-fused backends — full streamed runs (>= 4 chunks, matching
    per-chunk valid-item counts) and incremental session updates — with
    >= 4x fewer host→device plan bytes per chunk on both paths."""
    from repro.core import CensusEngine, pair_space

    g = paper_workload("orkut", n=400, avg_degree=12.0, seed=0)
    w_pre = pair_space(g).num_items_preprune
    max_items = max(w_pre // 6, 1)
    rng = np.random.default_rng(1)
    add = (rng.integers(0, 400, 60), rng.integers(0, 400, 60))
    rem = (rng.integers(0, 400, 60), rng.integers(0, 400, 60))
    for backend in ("jnp", "pallas-fused"):
        orients = ("none", "degree") if backend == "jnp" else ("none",)
        for orient in orients:
            t0 = time.perf_counter()
            # full streamed parity + per-chunk upload reduction
            eng = {}
            census = {}
            for emit in ("host", "device"):
                eng[emit] = CensusEngine(backend=backend, emit=emit)
                census[emit] = eng[emit].run(g, max_items=max_items,
                                             orient=orient)
            if not (census["host"] == census["device"]).all():
                raise AssertionError(
                    f"{backend}/{orient}: device-emit != host-emit")
            st_h, st_d = eng["host"].stats, eng["device"].stats
            if st_h.chunks < 4:
                raise AssertionError(f"smoke too coarse: {st_h.chunks}")
            if st_d.chunk_items != st_h.chunk_items:
                raise AssertionError(
                    f"{backend}/{orient}: device-counted valid items "
                    f"diverge from the host plan")
            if st_h.plan_upload_bytes < 4 * st_d.plan_upload_bytes:
                raise AssertionError(
                    f"{backend}/{orient}: full-run upload reduction "
                    f"{st_h.plan_upload_bytes}/{st_d.plan_upload_bytes} "
                    "< 4x")
            # incremental session parity + upload reduction
            ses = {e: CensusEngine(backend=backend, emit=e).session(
                g, orient=orient, max_items=max_items)
                for e in ("host", "device")}
            if not (ses["host"].census() == ses["device"].census()).all():
                raise AssertionError(
                    f"{backend}/{orient}: session census diverges")
            got_h = ses["host"].update(*add, *rem)
            got_d = ses["device"].update(*add, *rem)
            if not (got_h == got_d).all():
                raise AssertionError(
                    f"{backend}/{orient}: incremental update diverges")
            ib_h = ses["host"].stats.plan_upload_bytes
            ib_d = ses["device"].stats.plan_upload_bytes
            if ib_h < 4 * ib_d:
                raise AssertionError(
                    f"{backend}/{orient}: incremental upload reduction "
                    f"{ib_h}/{ib_d} < 4x")
            dt = time.perf_counter() - t0
            rows.append((f"emit_smoke_{backend}_{orient}", dt * 1e6,
                         f"chunks={st_h.chunks};"
                         f"full_bytes={st_h.plan_upload_bytes}v"
                         f"{st_d.plan_upload_bytes};"
                         f"incr_bytes={ib_h}v{ib_d};parity=ok"))


def partitioned_scaling(rows: list):
    """Tentpole rows ``part_shard{1,4,8}``: partitioned multi-device
    execution of the power-law workload — each device holds only its pair
    shard's local subgraph and walks its own descriptor stream — vs the
    replicated mesh baseline.  Asserts bit-identical censuses in-row and
    reports the per-device resident graph bytes, the byte reduction over
    replication, and the LPT shard imbalance (target ≤ 1.2)."""
    import jax

    from repro.core import CensusEngine, default_mesh

    if len(jax.devices()) < 8:
        rows.append(("part_shard_skipped", 0.0,
                     f"needs 8 devices, have {len(jax.devices())}"))
        return
    g = paper_workload("patents", n=20_000, avg_degree=3.0, seed=0)
    repl = CensusEngine(mesh=default_mesh(8), backend="jnp")
    dt_repl, want = _timeit(repl.run, g)
    rows.append(("part_replicated8", dt_repl * 1e6,
                 f"graph_bytes={repl.stats.graph_resident_bytes};"
                 f"items={repl.stats.items}"))
    for shards in (1, 4, 8):
        engine = CensusEngine(mesh=default_mesh(shards), backend="jnp",
                              partition=True, schedule="lockstep")
        got = engine.run(g)
        if not (got == want).all():
            raise AssertionError(
                f"partitioned census mismatch at {shards} shards")
        dt, _ = _timeit(engine.run, g)
        st = engine.stats
        rows.append((
            f"part_shard{shards}", dt * 1e6,
            f"graph_bytes={st.graph_resident_bytes};"
            f"replicated={st.graph_replicated_bytes};"
            f"reduction="
            f"{st.graph_replicated_bytes / max(st.graph_resident_bytes, 1):.2f}x;"
            f"shard_max_over_mean={st.shard_max_over_mean:.3f}"))
    # async per-shard streams on the same workload: no inter-shard
    # barrier, per-shard chunk queues drained independently.  Pinned to
    # one window per dispatch so the row stays comparable with its
    # pre-megastep history; part_mega_shard{4,8} below carries the
    # batched dispatches.
    for shards in (4, 8):
        engine = CensusEngine(mesh=default_mesh(shards), backend="jnp",
                              partition=True, schedule="async",
                              max_windows_per_dispatch=1)
        got = engine.run(g)
        if not (got == want).all():
            raise AssertionError(
                f"async partitioned census mismatch at {shards} shards")
        dt, _ = _timeit(engine.run, g)
        st = engine.stats
        rows.append((
            f"part_async_shard{shards}", dt * 1e6,
            f"windows={sum(st.shard_steps)};"
            f"stalls={st.stall_steps};"
            f"pipeline_depth={st.pipeline_depth};"
            f"upload_bytes={st.plan_upload_bytes_total};"
            f"shard_max_over_mean={st.shard_max_over_mean:.3f}"))
    # megastep: same async schedule, up to 8 windows scanned per
    # compiled dispatch — the Python dispatch cost is paid once per K.
    # Streamed (1M-item windows) so each shard has a multi-window queue
    # to batch; the unstreamed rows above have one window per shard,
    # where the engine clamps the batch capacity back to 1.
    for shards in (4, 8):
        engine = CensusEngine(mesh=default_mesh(shards), backend="jnp",
                              partition=True, schedule="async")
        got = engine.run(g, max_items=1_048_576)
        if not (got == want).all():
            raise AssertionError(
                f"megastep partitioned census mismatch at {shards} shards")
        dt, _ = _timeit(engine.run, g, max_items=1_048_576)
        st = engine.stats
        rows.append((
            f"part_mega_shard{shards}", dt * 1e6,
            f"windows={sum(st.shard_steps)};"
            f"dispatches={st.dispatches_total};"
            f"win_per_disp={st.windows_per_dispatch_mean:.2f}/"
            f"{st.windows_per_dispatch_max};"
            f"cap={st.dispatch_batch_limit};"
            f"pad_bytes={st.plan_pad_bytes_total};"
            f"stalls={st.stall_steps}"))
    # 2D pair×vertex meshes on the same workload: the pair axis keeps
    # the LPT assignment, the vertex axis slices each shard's adjacency
    # halo.  halo = max per-device resident adjacency entries (the
    # replicated CSR words the decomposition shards); 1D at 8 devices is
    # the reference point.
    from repro.core import partition_graph, partition_graph_2d
    halo_1d = max(partition_graph(g, num_shards=8).stats.shard_entries)
    for mesh_shape in ((4, 2), (2, 4)):
        p, v = mesh_shape
        engine = CensusEngine(mesh=default_mesh(8), backend="jnp",
                              partition_2d=mesh_shape, schedule="async")
        got = engine.run(g)
        if not (got == want).all():
            raise AssertionError(
                f"2D partitioned census mismatch at {mesh_shape}")
        dt, _ = _timeit(engine.run, g)
        st = engine.stats
        part2 = partition_graph_2d(g, mesh_shape=mesh_shape)
        halo = max(part2.stats.shard_entries)
        rows.append((
            f"part_2d_shard{p}x{v}", dt * 1e6,
            f"graph_bytes={st.graph_resident_bytes};"
            f"halo_entries={halo};"
            f"halo_cut_vs_1d8={halo_1d / max(halo, 1):.2f}x;"
            f"entry_replication={part2.stats.entry_replication:.2f};"
            f"shard_max_over_mean={st.shard_max_over_mean:.3f}"))


def _skewed_partition(space, num_shards: int, frac: float):
    """Deliberately imbalanced partition: shard 0 takes the heaviest
    pairs up to ``frac`` of the total pre-prune work (so its chunk queue
    is ``frac * num_shards``× the mean); the rest LPT-balance across the
    remaining shards."""
    from repro.core import lpt_assign_heap, partition_graph

    costs = space.counts.astype(np.int64)
    order = np.argsort(-costs, kind="stable")
    csum = np.cumsum(costs[order])
    k = int(np.searchsorted(csum, int(costs.sum() * frac))) + 1
    owner = np.empty(space.num_pairs, np.int64)
    owner[order[:k]] = 0
    rest = order[k:]
    owner[rest] = 1 + lpt_assign_heap(costs[rest], num_shards - 1)
    return partition_graph(num_shards=num_shards, space=space,
                           owner=owner)


def async_smoke(rows: list):
    """CI gate (benchmarks/check.sh --async-smoke): on a synthetic
    4×-skewed 8-shard partition (the heaviest shard's chunk queue ≥ 4×
    the mean) the async schedule must

    * stay bit-identical to the lock-step oracle AND the single-device
      census,
    * run ≥ 1.5× faster than lock-step (which burns ndev × max-shard
      collective steps, padded windows included), and
    * land within 1.25× of the mean-shard ideal — the same async engine
      on a balanced LPT partition of the same graph (same per-window
      dispatch cost, so the ratio isolates the skew penalty the barrier
      drop is supposed to erase).
    """
    import jax

    from repro.core import (CensusEngine, default_mesh, pair_space,
                            partition_graph, scale_free_digraph)
    from repro.core.plan_stream import ShardSchedule

    if len(jax.devices()) < 8:
        raise AssertionError(
            f"async smoke needs 8 devices, have {len(jax.devices())} "
            "(run via benchmarks/run.py, which forces them)")
    g = scale_free_digraph(1500, 8.0, 2.1, seed=0)
    space = pair_space(g)
    want = CensusEngine(backend="jnp").run(g)
    max_items = 16_384
    part_skew = _skewed_partition(space, 8, 0.52)
    part_bal = partition_graph(num_shards=8, space=space)
    sched = ShardSchedule([sh.space for sh in part_skew.shards],
                          max_items, 8)
    steps = sched.shard_steps
    skew = max(steps) / (sum(steps) / len(steps))
    if skew < 4.0:
        raise AssertionError(
            f"synthetic skew too mild: heaviest/mean {skew:.2f} < 4")
    mesh = default_mesh(8)

    def run_once(schedule, part):
        # pinned to one window per dispatch: this gate measures the PR 6
        # barrier drop (skew vs mean-shard pacing) and its thresholds
        # were calibrated there; the K-window megastep shifts the
        # critical path from dispatch to per-shard compute and has its
        # own gate (mega_smoke)
        engine = CensusEngine(mesh=mesh, backend="jnp",
                              partition=True, schedule=schedule,
                              max_windows_per_dispatch=1)
        dt, got = _timeit(engine.run, g, max_items=max_items, part=part,
                          reps=2)
        if not (got == want).all():
            raise AssertionError(
                f"{schedule} partitioned census != single-device")
        return dt, engine.stats

    t_async, st_a = run_once("async", part_skew)
    t_lock, st_l = run_once("lockstep", part_skew)
    t_ideal, st_i = run_once("async", part_bal)
    speedup = t_lock / t_async
    if speedup < 1.5:
        raise AssertionError(
            f"async only {speedup:.2f}x faster than lock-step on the "
            f"4x skew (need >= 1.5x)")
    if t_async > 1.25 * t_ideal:
        raise AssertionError(
            f"async on the skew is {t_async / t_ideal:.2f}x the "
            "balanced mean-shard ideal (need <= 1.25x)")
    rows.append(("async_smoke_skew", t_async * 1e6,
                 f"speedup_vs_lockstep={speedup:.2f}x;"
                 f"vs_mean_ideal={t_async / t_ideal:.2f}x;"
                 f"heaviest_over_mean={skew:.2f};"
                 f"windows={sum(st_a.shard_steps)};"
                 f"stalls={st_a.stall_steps};parity=ok"))
    rows.append(("async_smoke_lockstep", t_lock * 1e6,
                 f"collective_steps={max(st_l.shard_steps)};"
                 f"idle_steps={st_l.idle_steps};parity=ok"))
    rows.append(("async_smoke_ideal", t_ideal * 1e6,
                 f"windows={sum(st_i.shard_steps)};"
                 f"shard_max_over_mean="
                 f"{st_i.shard_max_over_mean:.3f};parity=ok"))


def dispatch_overhead(rows: list):
    """Microbench for the megastep's target regime: a small per-window
    item budget makes windows tiny and numerous, so per-dispatch Python
    overhead (trace-cache lookup, device_put, future bookkeeping)
    dominates device compute.  Rows compare async at one window per
    dispatch (PR 6), async with the 8-window megastep, and the
    lock-step oracle on the same 8-shard schedule."""
    import jax

    from repro.core import (CensusEngine, default_mesh,
                            scale_free_digraph)

    if len(jax.devices()) < 8:
        rows.append(("dispatch_overhead_skipped", 0.0,
                     f"needs 8 devices, have {len(jax.devices())}"))
        return
    g = scale_free_digraph(800, 6.0, 2.1, seed=3)
    max_items = 2_048          # tiny windows: dispatch-bound on purpose
    mesh = default_mesh(8)
    want = None
    for name, sched, cap in (("dispatch_async_k1", "async", 1),
                             ("dispatch_mega_k8", "async", 8),
                             ("dispatch_lockstep", "lockstep", 1)):
        engine = CensusEngine(mesh=mesh, backend="jnp", partition=True,
                              schedule=sched,
                              max_windows_per_dispatch=cap)
        got = engine.run(g, max_items=max_items)
        if want is None:
            want = got
        elif not (got == want).all():
            raise AssertionError(f"{name}: census mismatch")
        dt, _ = _timeit(engine.run, g, max_items=max_items)
        st = engine.stats
        rows.append((
            name, dt * 1e6,
            f"windows={sum(st.shard_steps)};"
            f"dispatches={st.dispatches_total};"
            f"win_per_disp={st.windows_per_dispatch_mean:.2f};"
            f"us_per_window={dt * 1e6 / max(sum(st.shard_steps), 1):.1f}"))


def mega_smoke(rows: list):
    """CI gate (benchmarks/check.sh --mega-smoke): in the tiny-window
    dispatch-bound regime on an 8-shard partition, the megastep must

    * stay bit-identical to the lock-step oracle AND the single-device
      census (per-window stacked partials + host int64 merge make the
      K-window scan indistinguishable from K single dispatches),
    * issue >= 2x fewer device dispatches than the one-window async
      schedule at an equal window budget, and
    * erase async's dispatch-overhead loss to lock-step: megastep
      walltime <= 1.15x lock-step on the same schedule (PR 6's
      one-window async pays ~windows× Python dispatch cost and loses
      this regime; amortizing K windows per dispatch is the fix).
    """
    import jax

    from repro.core import (CensusEngine, default_mesh,
                            scale_free_digraph)

    if len(jax.devices()) < 8:
        raise AssertionError(
            f"mega smoke needs 8 devices, have {len(jax.devices())} "
            "(run via benchmarks/run.py, which forces them)")
    g = scale_free_digraph(800, 6.0, 2.1, seed=3)
    max_items = 2_048
    want = CensusEngine(backend="jnp").run(g)
    mesh = default_mesh(8)

    def run_once(schedule, cap):
        engine = CensusEngine(mesh=mesh, backend="jnp",
                              partition=True, schedule=schedule,
                              max_windows_per_dispatch=cap)
        dt, got = _timeit(engine.run, g, max_items=max_items, reps=2)
        if not (got == want).all():
            raise AssertionError(
                f"{schedule}/cap={cap} census != single-device")
        return dt, engine.stats

    t_k1, st_k1 = run_once("async", 1)
    t_mega, st_mega = run_once("async", 8)
    t_lock, st_lock = run_once("lockstep", 1)
    if sum(st_mega.shard_steps) != sum(st_k1.shard_steps):
        raise AssertionError(
            "window budgets diverged: "
            f"{sum(st_mega.shard_steps)} != {sum(st_k1.shard_steps)}")
    if st_mega.dispatches_total * 2 > st_k1.dispatches_total:
        raise AssertionError(
            f"megastep dispatches {st_mega.dispatches_total} not >= 2x "
            f"fewer than one-window async {st_k1.dispatches_total}")
    if t_mega > 1.15 * t_lock:
        raise AssertionError(
            f"megastep is {t_mega / t_lock:.2f}x lock-step in the "
            "dispatch-bound regime (need <= 1.15x)")
    rows.append(("mega_smoke", t_mega * 1e6,
                 f"windows={sum(st_mega.shard_steps)};"
                 f"dispatches={st_mega.dispatches_total}v"
                 f"{st_k1.dispatches_total};"
                 f"win_per_disp={st_mega.windows_per_dispatch_mean:.2f}/"
                 f"{st_mega.windows_per_dispatch_max};"
                 f"vs_async_k1={t_mega / t_k1:.2f}x;"
                 f"vs_lockstep={t_mega / t_lock:.2f}x;parity=ok"))
    rows.append(("mega_smoke_async_k1", t_k1 * 1e6,
                 f"dispatches={st_k1.dispatches_total};parity=ok"))
    rows.append(("mega_smoke_lockstep", t_lock * 1e6,
                 f"collective_steps={st_lock.dispatches_total};"
                 f"idle_steps={st_lock.idle_steps};parity=ok"))


def fault_smoke(rows: list):
    """CI gate (benchmarks/check.sh --fault-smoke): the fault-tolerance
    layer on an 8-virtual-device mesh must

    * survive a seeded :class:`FaultPlan` carrying a producer plan-gen
      error, a transient dispatch error AND a device retirement —
      finishing bit-identical to the single-device census with >= 1
      recorded failover (the dead device's queue drained by survivors),
    * cost nothing when nothing fails: an armed engine (injection hooks
      threaded, watchdog set, empty fault plan) within 1.05x of the
      plain async walltime on the same workload, and
    * resume: a run killed mid-stream with ``checkpoint=`` journaling
      restores the landed windows and completes to the exact same
      census, with > 0 resumed (journal-skipped) windows.
    """
    import os
    import tempfile

    import jax

    from repro.core import (CensusEngine, FaultPlan, default_mesh,
                            scale_free_digraph)

    if len(jax.devices()) < 8:
        raise AssertionError(
            f"fault smoke needs 8 devices, have {len(jax.devices())} "
            "(run via benchmarks/run.py, which forces them)")
    g = scale_free_digraph(1500, 8.0, 2.1, seed=0)
    max_items = 16_384
    want = CensusEngine(backend="jnp").run(g)
    mesh = default_mesh(8)

    # plain async baseline (the PR 8 machinery, no fault layer armed)
    # vs armed-but-idle: injection hooks fire on every producer/upload/
    # dispatch event against an EMPTY plan, watchdog timers run — the
    # pure overhead of carrying the fault-tolerance layer.  Single runs
    # of this threaded pipeline jitter ~10% with host scheduling, so
    # the bound is checked on the MEDIAN of 8 back-to-back paired
    # ratios (pairing cancels load drift; the median sheds scheduler
    # outliers)
    plain = CensusEngine(mesh=mesh, backend="jnp", partition=True)
    armed = CensusEngine(mesh=mesh, backend="jnp", partition=True,
                         faults=FaultPlan(faults=[], seed=0),
                         watchdog_timeout=30.0)
    for eng, label in ((plain, "plain async"), (armed, "armed fault-free")):
        got = eng.run(g, max_items=max_items)        # warmup / compile
        if not (got == want).all():
            raise AssertionError(f"{label} census != single-device")
    ratios, ta = [], []
    for _ in range(8):
        t0 = time.perf_counter()
        plain.run(g, max_items=max_items)
        tp = time.perf_counter() - t0
        t0 = time.perf_counter()
        armed.run(g, max_items=max_items)
        ta.append(time.perf_counter() - t0)
        ratios.append(ta[-1] / tp)
    dt_armed = min(ta)
    overhead = float(np.median(ratios))
    if overhead > 1.05:
        raise AssertionError(
            f"fault-free overhead {overhead:.3f}x plain async "
            "(need <= 1.05x)")

    # adversarial: producer error + transient dispatch error + one
    # device retired mid-run — survivors drain its queue, merge order
    # doesn't matter, census must not move a bit
    adv = CensusEngine(mesh=mesh, backend="jnp", partition=True,
                       faults=FaultPlan.seeded(
                           7, 8, producer_errors=1, dispatch_errors=1,
                           retire_devices=1))
    dt_adv, got = _timeit(adv.run, g, max_items=max_items, reps=2)
    if not (got == want).all():
        raise AssertionError("faulted census != single-device")
    st = adv.stats
    if st.failovers < 1 or not st.retired_devices:
        raise AssertionError(
            f"seeded retirement did not fail over (failovers="
            f"{st.failovers}, retired={st.retired_devices})")
    if st.retries < 1:
        raise AssertionError("seeded transient faults were not retried")

    # checkpoint/resume: kill the run mid-stream, resume from the
    # journal, land the exact same census with > 0 skipped windows
    class _Killer:
        def __init__(self, after):
            self.after, self.calls = after, 0

        def __call__(self, done, total, num=None):
            self.calls += 1
            if self.calls == self.after:
                raise KeyboardInterrupt

    with tempfile.TemporaryDirectory() as td:
        ck = os.path.join(td, "census.ckpt")
        eng = CensusEngine(mesh=mesh, backend="jnp", partition=True)
        try:
            eng.run(g, max_items=max_items, checkpoint=ck,
                    progress=_Killer(8))
        except KeyboardInterrupt:
            pass
        t0 = time.perf_counter()
        got = eng.resume(g, ck, max_items=max_items)
        dt_resume = time.perf_counter() - t0
        if not (got == want).all():
            raise AssertionError("resumed census != uninterrupted")
        resumed = eng.stats.resumed_windows
        if resumed < 1:
            raise AssertionError(
                "resume did not skip any journaled windows")

    rows.append(("fault_smoke_adversarial", dt_adv * 1e6,
                 f"retries={st.retries};failovers={st.failovers};"
                 f"retired={sorted(st.retired_devices)};"
                 f"windows={sum(st.shard_steps)};parity=ok"))
    rows.append(("fault_smoke_overhead", dt_armed * 1e6,
                 f"vs_plain_async={overhead:.3f}x;parity=ok"))
    rows.append(("fault_smoke_resume", dt_resume * 1e6,
                 f"resumed_windows={resumed};parity=ok"))


def partition_smoke(rows: list):
    """CI gate (benchmarks/check.sh --partition-smoke): on an 8-virtual-
    host mesh, partitioned censuses must be bit-identical to the
    single-device path (jnp × both emits × both orients, monolithic +
    streamed, plus pallas-fused and an incremental partitioned session),
    with shard item imbalance ≤ 1.2 and ≥ 2x per-device graph-byte
    reduction on the power-law workload."""
    import jax

    from repro.core import CensusEngine, default_mesh, pair_space

    if len(jax.devices()) < 8:
        raise AssertionError(
            f"partition smoke needs 8 devices, have {len(jax.devices())} "
            "(run via benchmarks/run.py, which forces them)")
    g = paper_workload("patents", n=4_000, avg_degree=3.0, seed=0)
    want = CensusEngine(backend="jnp").run(g)
    w_pre = pair_space(g).num_items_preprune
    mesh = default_mesh(8)
    for backend, emits, orients in (
            ("jnp", ("device", "host"), ("none", "degree")),
            ("pallas-fused", ("device",), ("none",))):
        for emit in emits:
            for orient in orients:
                t0 = time.perf_counter()
                engine = CensusEngine(mesh=mesh, backend=backend,
                                      partition=True, emit=emit)
                for max_items in (None, max(w_pre // 4, 1)):
                    got = engine.run(g, max_items=max_items,
                                     orient=orient)
                    if not (got == want).all():
                        raise AssertionError(
                            f"{backend}/{emit}/{orient}: partitioned "
                            "census != single-device")
                st = engine.stats
                if st.shard_max_over_mean > 1.2:
                    raise AssertionError(
                        f"{backend}/{emit}/{orient}: shard imbalance "
                        f"{st.shard_max_over_mean:.3f} > 1.2")
                if st.graph_replicated_bytes < \
                        2 * st.graph_resident_bytes:
                    raise AssertionError(
                        f"{backend}/{emit}/{orient}: byte reduction "
                        f"{st.graph_replicated_bytes}/"
                        f"{st.graph_resident_bytes} < 2x")
                dt = time.perf_counter() - t0
                rows.append((
                    f"part_smoke_{backend}_{emit}_{orient}", dt * 1e6,
                    f"chunks={st.chunks};"
                    f"shard_max_over_mean={st.shard_max_over_mean:.3f};"
                    f"graph_bytes={st.graph_resident_bytes}v"
                    f"{st.graph_replicated_bytes};parity=ok"))
    # incremental partitioned session: delta updates must stay
    # bit-identical to the unpartitioned session's
    rng = np.random.default_rng(2)
    add = (rng.integers(0, 4_000, 80), rng.integers(0, 4_000, 80))
    rem = (rng.integers(0, 4_000, 80), rng.integers(0, 4_000, 80))
    t0 = time.perf_counter()
    ses = {p: CensusEngine(mesh=mesh, backend="jnp",
                           partition=p).session(g, max_items=w_pre)
           for p in (False, True)}
    if not (ses[False].census() == ses[True].census()).all():
        raise AssertionError("partitioned session census diverges")
    got_r = ses[False].update(*add, *rem)
    got_p = ses[True].update(*add, *rem)
    if not (got_r == got_p).all():
        raise AssertionError("partitioned incremental update diverges")
    st = ses[True].stats
    dt = time.perf_counter() - t0
    rows.append(("part_smoke_session", dt * 1e6,
                 f"affected_pairs={st.affected_pairs};items={st.items};"
                 f"dispatched_shards="
                 f"{sum(1 for x in st.shard_items if x)};parity=ok"))


def twod_smoke(rows: list):
    """CI gate (benchmarks/check.sh --2d-smoke): the 2D pair×vertex
    decomposition on an 8-virtual-host mesh.

    Bit-identity: 2D censuses at (4,2) and (2,4) must equal the 1D
    partitioned path and the single-device reference — both emits, both
    orients, monolithic + streamed, async + lockstep, plus an
    incremental 2D session.

    Halo gate: on the power-law workload, the max per-device resident
    adjacency entries (the halo — the replicated CSR words the vertex
    axis shards; pair descriptors scale with owned work, not graph
    size, and entries are structurally 2x the pair count, so total
    bytes are pair-bound) must shrink ≥ 1.5x further than 1D at 8
    devices on the (4,2) mesh and ≥ 2x on the (2,4) mesh, with total
    per-device resident bytes no worse than 1D."""
    import jax

    from repro.core import (CensusEngine, default_mesh, pair_space,
                            partition_graph, partition_graph_2d)

    if len(jax.devices()) < 8:
        raise AssertionError(
            f"2d smoke needs 8 devices, have {len(jax.devices())} "
            "(run via benchmarks/run.py, which forces them)")
    g = paper_workload("patents", n=4_000, avg_degree=3.0, seed=0)
    want = CensusEngine(backend="jnp").run(g)
    w_pre = pair_space(g).num_items_preprune
    mesh = default_mesh(8)
    c1 = CensusEngine(mesh=mesh, backend="jnp", partition=True).run(g)
    if not (c1 == want).all():
        raise AssertionError("1D partitioned census != single-device")
    for mesh_shape in ((4, 2), (2, 4)):
        for emit in ("device", "host"):
            for orient in ("none", "degree"):
                t0 = time.perf_counter()
                for schedule in ("async", "lockstep"):
                    engine = CensusEngine(mesh=mesh, backend="jnp",
                                          partition_2d=mesh_shape,
                                          emit=emit, schedule=schedule)
                    for max_items in (None, max(w_pre // 4, 1)):
                        got = engine.run(g, max_items=max_items,
                                         orient=orient)
                        if not (got == want).all():
                            raise AssertionError(
                                f"{mesh_shape}/{emit}/{orient}/"
                                f"{schedule}: 2D census != reference")
                st = engine.stats
                dt = time.perf_counter() - t0
                rows.append((
                    f"twod_smoke_{mesh_shape[0]}x{mesh_shape[1]}"
                    f"_{emit}_{orient}", dt * 1e6,
                    f"chunks={st.chunks};"
                    f"mesh={st.partition_shape};parity=ok"))
    # incremental 2D session: delta updates bit-identical to the
    # unpartitioned session's
    rng = np.random.default_rng(2)
    add = (rng.integers(0, 4_000, 80), rng.integers(0, 4_000, 80))
    rem = (rng.integers(0, 4_000, 80), rng.integers(0, 4_000, 80))
    t0 = time.perf_counter()
    ses_r = CensusEngine(mesh=mesh, backend="jnp").session(g)
    ses_2 = CensusEngine(mesh=mesh, backend="jnp",
                         partition_2d=(4, 2)).session(g)
    if not (ses_r.census() == ses_2.census()).all():
        raise AssertionError("2D session census diverges")
    if not (ses_r.update(*add, *rem) == ses_2.update(*add, *rem)).all():
        raise AssertionError("2D incremental update diverges")
    dt = time.perf_counter() - t0
    rows.append(("twod_smoke_session", dt * 1e6,
                 f"affected_pairs={ses_2.stats.affected_pairs};"
                 f"items={ses_2.stats.items};parity=ok"))
    # halo gate on the power-law workload (host-side partition stats —
    # no device work, so full scale is cheap)
    gh = paper_workload("patents", n=20_000, avg_degree=8.0, seed=0)
    t0 = time.perf_counter()
    p1 = partition_graph(gh, num_shards=8)
    halo_1d = max(p1.stats.shard_entries)
    bytes_1d = p1.stats.max_shard_bytes
    for mesh_shape, need in (((4, 2), 1.5), ((2, 4), 2.0)):
        p2 = partition_graph_2d(gh, mesh_shape=mesh_shape)
        halo = max(p2.stats.shard_entries)
        cut = halo_1d / max(halo, 1)
        if cut < need:
            raise AssertionError(
                f"{mesh_shape}: halo cut {cut:.2f}x < {need}x "
                f"({halo_1d} -> {halo} resident entries)")
        if mesh_shape == (4, 2) and \
                p2.stats.max_shard_bytes > bytes_1d:
            raise AssertionError(
                f"{mesh_shape}: total resident bytes regressed "
                f"{bytes_1d} -> {p2.stats.max_shard_bytes}")
        rows.append((
            f"twod_smoke_halo_{mesh_shape[0]}x{mesh_shape[1]}",
            (time.perf_counter() - t0) * 1e6,
            f"halo_entries={halo_1d}v{halo};cut={cut:.2f}x;"
            f"bytes={bytes_1d}v{p2.stats.max_shard_bytes};"
            f"entry_replication={p1.stats.entry_replication:.2f}v"
            f"{p2.stats.entry_replication:.2f}"))


def _run_monitor(src, dst, n, window, stride, incremental,
                 backend="jnp", max_items=4096, index=True):
    from repro.core import TriadMonitor
    mon = TriadMonitor(n, window=window, stride=stride, history=5,
                       backend=backend, incremental=incremental,
                       max_items=max_items, index=index)
    t0 = time.perf_counter()
    mon.observe(src, dst)
    dt = time.perf_counter() - t0
    return mon, dt


def temporal_windows(rows: list):
    """Tentpole rows: full per-window recompute vs incremental delta
    updates of sliding windows, at 5% / 20% / 50% stride-to-window
    overlap ratios.  Asserts bit-identical censuses in-row and reports
    the items processed plus the affected-pair fraction per window."""
    rng = np.random.default_rng(0)
    window = 4000
    src, dst, n = monitor_stream(rng, 80, 3000, 800, 11 * window)
    # warm the shared jitted chunk step (same static args / chunk shape
    # for every monitor below) so neither timed mode absorbs the compile
    warm = 2 * window
    _run_monitor(src[:warm], dst[:warm], n, window, window // 2,
                 incremental=True)
    for frac in (0.05, 0.20, 0.50):
        stride = max(1, int(window * frac))
        mon_full, dt_full = _run_monitor(src, dst, n, window, stride,
                                         incremental=False)
        mon_inc, dt_inc = _run_monitor(src, dst, n, window, stride,
                                       incremental=True)
        if not (mon_full.censuses == mon_inc.censuses).all():
            raise AssertionError(
                f"incremental != full at stride {frac:.0%}")
        slid = mon_inc.window_stats[1:]     # first window is always full
        items = sum(s.items for s in slid)
        full_items = sum(s.full_items for s in slid)
        aff = np.mean([s.affected_pairs for s in slid])
        tag = f"s{int(frac * 100):02d}"
        rows.append((f"temporal_full_{tag}", dt_full * 1e6,
                     f"windows={len(mon_full.window_stats)};"
                     f"items={sum(s.items for s in mon_full.window_stats)}"))
        rows.append((f"temporal_incr_{tag}", dt_inc * 1e6,
                     f"windows={len(mon_inc.window_stats)};items={items};"
                     f"item_reduction={full_items / max(items, 1):.2f}x;"
                     f"mean_affected_pairs={aff:.0f};"
                     f"speedup={dt_full / max(dt_inc, 1e-9):.2f}x"))


def temporal_smoke(rows: list):
    """CI gate (benchmarks/check.sh --temporal-smoke): sliding windows at
    a 10% stride, asserting (a) incremental censuses are bit-identical to
    full per-window recomputes and (b) the incremental path processes
    >= 2x fewer census items, on the jnp and pallas-fused backends."""
    rng = np.random.default_rng(0)
    window = 1500
    src, dst, n = monitor_stream(rng, 40, 1500, 300, 5 * window)
    stride = window // 10
    for backend in ("jnp", "pallas-fused"):
        # warm the chunk step so the timed runs compare algorithms, not
        # jit-cache states
        _run_monitor(src[:2 * window], dst[:2 * window], n, window,
                     stride, incremental=True, backend=backend,
                     max_items=2048)
        mon_full, dt_full = _run_monitor(
            src, dst, n, window, stride, incremental=False,
            backend=backend, max_items=2048)
        mon_inc, dt_inc = _run_monitor(
            src, dst, n, window, stride, incremental=True,
            backend=backend, max_items=2048)
        if not (mon_full.censuses == mon_inc.censuses).all():
            raise AssertionError(f"incremental != full on {backend}")
        slid_inc = mon_inc.window_stats[1:]
        items = sum(s.items for s in slid_inc)
        full_items = sum(s.full_items for s in slid_inc)
        if full_items < 2 * items:
            raise AssertionError(
                f"{backend}: incremental processed {items} items vs "
                f"{full_items} full — less than the required 2x reduction")
        compiles = sum(s.step_compiles for s in mon_inc.window_stats)
        if compiles > 1:
            raise AssertionError(
                f"{backend}: session step recompiled ({compiles}) "
                f"across {len(mon_inc.window_stats)} windows")
        rows.append((f"temporal_smoke_{backend}", dt_inc * 1e6,
                     f"windows={len(mon_inc.window_stats)};"
                     f"items={items};full_items={full_items};"
                     f"item_reduction={full_items / max(items, 1):.2f}x;"
                     f"step_compiles={compiles};parity=ok"))


def incr_host_smoke(rows: list):
    """CI gate (benchmarks/check.sh --incr-host-smoke): the
    delta-incremental host planner.  Warm sliding-window updates with the
    persistent pair-space index must be (a) bit-identical to the
    rebuild-from-scratch oracle (``index=False``), (b) >= 1.5x faster
    end-to-end in walltime at a 5% stride, and (c) >= 1.3x faster in the
    pair-space host phase alone.

    The workload is the backbone-dominated monitoring regime the index
    targets: a large stable service backbone (the pair space stays at
    P ~ 150k) with a small ephemeral churn fraction (1 slot in 50), under
    the degree-oriented planner — per slide the oracle rebuilds the O(P)
    pair space and repays the O(m + P log m) post-prune closed form,
    while the index edits both in O(delta log P + affected).
    """
    from repro.core import TriadMonitor
    rng = np.random.default_rng(0)
    window = 200_000
    n_slides = {0.05: 8, 0.20: 4}
    length = window + int(max(f * s for f, s in n_slides.items())
                          * window)
    src, dst, n = monitor_stream(rng, 20000, 50000, 150000, length,
                                  eph_every=50)
    for frac, gates in ((0.05, (1.5, 1.3)), (0.20, None)):
        stride = int(window * frac)
        end = window + n_slides[frac] * stride
        runs = {}
        for index in (True, False):
            mon = TriadMonitor(n, window=window, stride=stride,
                               history=5, backend="jnp", orient="degree",
                               incremental=True, max_items=16384,
                               index=index)
            # first window: full census — session open + jit warm for
            # both modes, so the timed region is pure warm updates
            mon.observe(src[:window], dst[:window])
            t0 = time.perf_counter()
            mon.observe(src[window:end], dst[window:end])
            runs[index] = (mon, time.perf_counter() - t0)
        mon_on, dt_on = runs[True]
        mon_off, dt_off = runs[False]
        if not (mon_on.censuses == mon_off.censuses).all():
            raise AssertionError(
                f"indexed censuses != rebuild oracle at stride "
                f"{frac:.0%}")
        slid = [s for s in mon_on.window_stats[1:] if s is not None]
        slid_off = [s for s in mon_off.window_stats[1:] if s is not None]
        if [s.full_items for s in slid] != \
                [s.full_items for s in slid_off]:
            raise AssertionError(
                "maintained post-prune item totals != oracle recompute")
        speedup = dt_off / max(dt_on, 1e-9)
        pair_on = sum(s.host_pair_seconds for s in slid)
        pair_off = sum(s.host_pair_seconds for s in slid_off)
        pair_speedup = pair_off / max(pair_on, 1e-9)
        if gates is not None:
            wall_gate, pair_gate = gates
            if speedup < wall_gate:
                raise AssertionError(
                    f"indexed warm updates only {speedup:.2f}x faster "
                    f"than the per-window rebuild at stride {frac:.0%} "
                    f"(gate {wall_gate}x)")
            if pair_speedup < pair_gate:
                raise AssertionError(
                    f"indexed pair-space phase only {pair_speedup:.2f}x "
                    f"faster than the rebuild at stride {frac:.0%} "
                    f"(gate {pair_gate}x)")
        host_on = sum(s.plan_host_seconds for s in slid)
        host_off = sum(s.plan_host_seconds for s in slid_off)
        tag = f"s{int(frac * 100):02d}"
        rows.append((
            f"incr_host_{tag}", dt_on / max(len(slid), 1) * 1e6,
            f"windows={len(slid)};walltime_speedup={speedup:.2f}x;"
            f"pair_speedup={pair_speedup:.2f}x;"
            f"host_s={host_on:.3f}/{host_off:.3f};"
            f"host_pair_s={pair_on:.3f};"
            f"host_merge_s={sum(s.host_merge_seconds for s in slid):.3f};"
            f"host_emit_s={sum(s.host_emit_seconds for s in slid):.3f};"
            f"parity=ok"))


def run(rows: list):
    fig6_degree_distributions(rows)
    fig9_balance(rows)
    scaling_fig(rows, "patents", "fig10")
    scaling_fig(rows, "orkut", "fig11")
    scaling_fig(rows, "webgraph", "fig13")
    table_census(rows)
    om_scaling(rows)
    kernel_throughput(rows)
    fused_vs_reference(rows)
    streaming_vs_monolithic(rows)
    device_emission(rows)
    partitioned_scaling(rows)
    dispatch_overhead(rows)
    temporal_windows(rows)
    incr_host_smoke(rows)


def run_smoke(rows: list):
    """Fast subset for CI (benchmarks/check.sh): kernel throughput plus
    the fused-vs-reference parity/latency columns on reduced workloads."""
    kernel_throughput(rows)
    fused_vs_reference(rows)
