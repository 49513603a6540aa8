"""Device-side work-item emission: descriptor windows, in-kernel
pair→item expansion, and the ``emit="device"`` engine/session paths.

The central property: the device-emission census — host ships O(pairs)
descriptors, the kernel expands each flat index back to its work item and
applies the pruning predicate in place — is bit-identical to host
emission for every backend, both orient modes, any chunk budget, and all
three execution paths (full runs, streamed chunks, incremental updates),
while shipping far fewer host→device plan bytes.
"""

import jax
import numpy as np
import pytest

from repro.core import census
from repro.core import (
    CensusEngine, PlanChunker, apply_delta, census_batagelj_mrvar,
    default_mesh, descriptor_window, from_edges, iter_descriptor_windows,
    monitor_stream, pair_space, paper_workload, scale_free_digraph,
    triad_census_graph)
from repro.core.planner import (
    DESC_ANCHOR_STRIDE, DESC_CUM_PAD, emit_items, num_desc_anchors,
    prune_items)


def hub_graph(n=24, hub_out=16, extra=40, seed=0):
    """Graph with a guaranteed hub pair costing > hub_out items."""
    rng = np.random.default_rng(seed)
    src = [0] * hub_out + list(rng.integers(0, n, extra))
    dst = list(range(1, hub_out + 1)) + list(rng.integers(0, n, extra))
    return from_edges(src, dst, n=max(n, hub_out + 1))


def expand_window_np(space, win):
    """Numpy reference of the device expansion (including the anchored
    search bound), returning the window's PRUNED (pair, slot, side)."""
    return prune_items(space, *preprune_window_np(space, win))


def preprune_window_np(space, win):
    """The window's pre-prune ``(pair, slot, side)`` lanes, in numpy,
    asserting the anchored search bound on the way."""
    nd = win.num_descs
    cum = win.desc_cum[:nd].astype(np.int64)
    idx = np.arange(win.num_preprune, dtype=np.int64)
    d = np.searchsorted(cum, idx, side="right") - 1
    # the anchored range must always contain the true descriptor
    a = idx // DESC_ANCHOR_STRIDE
    lo_d = win.anchors[a].astype(np.int64)
    assert (d >= lo_d).all()
    assert (d < lo_d + DESC_ANCHOR_STRIDE // 2 + 1).all()
    pair = win.desc_pair[d].astype(np.int64)
    within = win.desc_within0[d] + idx - cum[d]
    u = space.pair_u[pair]
    deg_u = space.deg[u]
    side = (within >= deg_u).astype(np.int8)
    slot = np.where(side == 0, space.indptr[u] + within,
                    space.indptr[space.pair_v[pair]] + within - deg_u)
    return pair, slot, side


# --------------------------------------------------------- descriptors


class TestDescriptorWindows:
    @pytest.mark.parametrize("orient", ["none", "degree"])
    @pytest.mark.parametrize("max_items", [3, 17, 101, 10**6])
    def test_expansion_partitions_the_item_space(self, orient, max_items):
        """Expanding every chunk's descriptor window reproduces exactly
        the host planner's emitted items, chunk by chunk."""
        g = hub_graph()
        ck = PlanChunker(g, max_items, orient=orient)
        for k in range(ck.num_chunks):
            win = ck.descriptors(k)
            got = expand_window_np(ck.space, win)
            want = emit_items(ck.space, win.start, win.stop)
            for a, b in zip(got, want):
                np.testing.assert_array_equal(a, b)

    def test_padding_and_shapes(self):
        g = hub_graph(seed=2)
        ck = PlanChunker(g, 37)
        for k in range(ck.num_chunks):
            win = ck.descriptors(k)
            assert win.desc_pair.shape == (ck.desc_shape,)
            assert win.anchors.shape == (ck.num_anchors,)
            assert (win.desc_cum[win.num_descs:] == DESC_CUM_PAD).all()
            assert (win.desc_pair[win.num_descs:] == 0).all()
            words = win.device_words()
            assert words.shape == (1 + 3 * ck.desc_shape
                                   + ck.num_anchors,)
            assert words[0] == win.num_preprune

    def test_hub_pair_spans_three_plus_chunks(self):
        """A hub pair split across >= 3 chunks surfaces as the SAME pair
        id in consecutive windows with advancing within-pair offsets —
        the intra-pair split expressed as offset windows."""
        g = hub_graph(hub_out=16)
        ck = PlanChunker(g, max_items=4)
        seen = {}             # pair id -> list of (chunk, within0)
        for k in range(ck.num_chunks):
            win = ck.descriptors(k)
            for j in range(win.num_descs):
                seen.setdefault(int(win.desc_pair[j]), []).append(
                    (k, int(win.desc_within0[j])))
        split = {p: v for p, v in seen.items() if len(v) >= 3}
        assert split, "no pair spanned >= 3 chunks"
        for spans in split.values():
            w0 = [w for _, w in spans]
            assert w0[0] == 0 and all(b > a for a, b in zip(w0, w0[1:]))

    def test_subset_windows_respect_both_caps(self):
        from repro.core import subset_descriptor_windows
        g = scale_free_digraph(n=80, avg_degree=5, exponent=2.2,
                               mutual_p=0.3, seed=11)
        space = pair_space(g)
        ids = np.arange(0, space.num_pairs, 2)
        total = int(space.counts[ids].sum())
        wins = list(subset_descriptor_windows(space, ids, 64, 8,
                                              num_desc_anchors(64)))
        assert sum(w.num_preprune for w in wins) == total
        assert all(w.num_preprune <= 64 for w in wins)
        assert all(w.num_descs <= 8 for w in wins)
        # windows tile the subset space exactly
        stops = [w.stop for w in wins]
        starts = [w.start for w in wins]
        assert starts[0] == 0 and stops[-1] == total
        assert starts[1:] == stops[:-1]

    def test_window_bounds_validated(self):
        space = pair_space(hub_graph())
        with pytest.raises(ValueError, match="outside"):
            descriptor_window(space.offsets, 0,
                              space.num_items_preprune + 1, 10**6,
                              num_desc_anchors(64))
        with pytest.raises(ValueError, match="desc_shape"):
            descriptor_window(space.offsets, 0,
                              space.num_items_preprune, 1,
                              num_desc_anchors(64))

    def test_empty_window(self):
        space = pair_space(hub_graph())
        win = descriptor_window(space.offsets, 5, 5, 4,
                                num_desc_anchors(16))
        assert win.num_descs == 0 and win.num_preprune == 0


# ------------------------------------------------------ stage contract


class TestStageContract:
    """``expand`` gathers each pair field and row bound a lane needs
    once; ``classify`` and ``keep`` read them from its output and agree,
    lane by lane, with the same items emitted on the host."""

    @pytest.mark.parametrize("orient", ["none", "degree"])
    def test_expand_feeds_classify_what_host_items_give(self, orient):
        g = hub_graph(n=40, hub_out=24, extra=160, seed=7)
        ck = PlanChunker(g, max_items=29, orient=orient)
        sp = ck.space
        lanes = ck.chunk_shape
        # the last window is partly padding
        assert 0 < sp.num_items_preprune % lanes
        tables = ck.device_arrays()
        indptr, packed, pair_u, pair_v, pair_code = tables
        idx = np.arange(lanes, dtype=np.int32)
        nd = ck.desc_shape

        @jax.jit
        def stages(tables, words, h_pair, h_slot, h_side, h_valid):
            ip, pk, pu, pv, pc = tables
            items = census.expand_work_items(
                ip, pu, pv, pc, words[1:1 + nd],
                words[1 + nd:1 + 2 * nd], words[1 + 2 * nd:1 + 3 * nd],
                words[1 + 3 * nd:], words[:1], idx, ck.desc_iters)
            host = census.gather_work_items(ip, pu, pv, pc, h_pair,
                                            h_slot, h_side, h_valid)
            dev = census.classify_items(pk, items, sp.search_iters)
            ref = census.classify_items(pk, host, sp.search_iters)
            keep = census.prune_keep_mask(dev[4], items, sp.orient,
                                          sp.prune_self)
            return items, dev, ref, keep

        windows_of = {}                      # pair -> windows holding it
        for k in range(ck.num_chunks):
            win = ck.descriptors(k)
            nv = win.num_preprune
            pair, slot, side = preprune_window_np(sp, win)
            for p in np.unique(pair):
                windows_of.setdefault(int(p), set()).add(k)
            valid = idx < nv
            host = [np.zeros(lanes, np.int32) for _ in range(3)]
            for h, a in zip(host, (pair, slot, side)):
                h[:nv] = a
            items, dev, ref, keep = jax.device_get(
                stages(tables, win.device_words(), *host, valid))

            # valid lanes: the tables' entries at the lane's pair
            u, v = pair_u[pair], pair_v[pair]
            np.testing.assert_array_equal(items.valid, valid)
            np.testing.assert_array_equal(items.u[:nv], u)
            np.testing.assert_array_equal(items.v[:nv], v)
            np.testing.assert_array_equal(items.pair_code[:nv],
                                          pair_code[pair])
            np.testing.assert_array_equal(items.u_lo[:nv], indptr[u])
            np.testing.assert_array_equal(items.u_hi[:nv], indptr[u + 1])
            np.testing.assert_array_equal(items.v_lo[:nv], indptr[v])
            np.testing.assert_array_equal(items.v_hi[:nv], indptr[v + 1])
            np.testing.assert_array_equal(items.slot[:nv], slot)
            np.testing.assert_array_equal(items.side[:nv], side)
            # padded lanes: slot and side 0, every field in range
            assert not items.slot[nv:].any() and not items.side[nv:].any()
            for a in (items.u[nv:], items.v[nv:]):
                assert ((0 <= a) & (a < sp.n)).all()
            for a in items[3:7]:
                assert ((0 <= a[nv:]) & (a[nv:] <= packed.shape[0])).all()

            # classify: the same (tricode, count, inter, is_mut) per lane
            for got, want in zip(dev[:4], ref[:4]):
                np.testing.assert_array_equal(got[:nv], want[:nv])
            np.testing.assert_array_equal(dev[4][:nv], sp.nbr[slot])
            for mask in (dev[1], dev[2], ref[1], ref[2], keep):
                assert not mask[nv:].any()
            # keep: the lanes it keeps are the host plan's items
            kept = keep[:nv]
            for got, want in zip((pair[kept], slot[kept], side[kept]),
                                 prune_items(sp, pair, slot, side)):
                np.testing.assert_array_equal(got, want)
        assert any(len(ks) >= 2 for ks in windows_of.values()), \
            "no pair split across windows"


# ------------------------------------------------------------- engines


class TestDeviceEmitParity:
    @pytest.mark.parametrize("orient", ["none", "degree"])
    @pytest.mark.parametrize("backend", ["jnp", "pallas", "pallas-fused"])
    def test_run_matches_oracle(self, orient, backend):
        g = scale_free_digraph(n=60, avg_degree=5, exponent=2.2,
                               mutual_p=0.3, seed=5)
        want = census_batagelj_mrvar(g)
        for max_items in (None, 64):
            engine = CensusEngine(backend=backend)   # emit="device"
            got = engine.run(g, max_items=max_items, orient=orient)
            np.testing.assert_array_equal(got, want)
            assert engine.stats.emit == "device"

    @pytest.mark.parametrize("orient", ["none", "degree"])
    def test_device_counts_match_host_schedule(self, orient):
        """Device-counted valid items per chunk equal the host plan's
        post-prune counts — same schedule, same numbers, no host items."""
        g = scale_free_digraph(n=100, avg_degree=6, exponent=2.2,
                               mutual_p=0.3, seed=6)
        dev = CensusEngine(backend="jnp", emit="device")
        host = CensusEngine(backend="jnp", emit="host")
        c_dev = dev.run(g, max_items=200, orient=orient)
        c_host = host.run(g, max_items=200, orient=orient)
        np.testing.assert_array_equal(c_dev, c_host)
        assert dev.stats.chunk_items == host.stats.chunk_items
        assert dev.stats.items == host.stats.items
        assert dev.stats.plan_upload_bytes < host.stats.plan_upload_bytes

    @pytest.mark.parametrize("backend,orient", [
        ("jnp", "none"), ("jnp", "degree"), ("pallas-fused", "none")])
    def test_upload_cut_fourfold(self, backend, orient):
        """Streamed runs and incremental session updates: device
        emission equals host emission, chunk for chunk, while shipping
        at least 4x fewer plan bytes per dispatch."""
        g = paper_workload("orkut", n=400, avg_degree=12.0, seed=0)
        max_items = max(pair_space(g).num_items_preprune // 6, 1)
        eng = {e: CensusEngine(backend=backend, emit=e)
               for e in ("host", "device")}
        got = {e: eng[e].run(g, max_items=max_items, orient=orient)
               for e in eng}
        np.testing.assert_array_equal(got["device"], got["host"])
        st_h, st_d = eng["host"].stats, eng["device"].stats
        assert st_h.chunks >= 4
        assert st_d.chunk_items == st_h.chunk_items
        assert st_h.plan_upload_bytes >= 4 * st_d.plan_upload_bytes
        rng = np.random.default_rng(1)
        add = (rng.integers(0, 400, 60), rng.integers(0, 400, 60))
        rem = (rng.integers(0, 400, 60), rng.integers(0, 400, 60))
        ses = {e: CensusEngine(backend=backend, emit=e).session(
            g, orient=orient, max_items=max_items)
            for e in ("host", "device")}
        np.testing.assert_array_equal(ses["device"].census(),
                                      ses["host"].census())
        np.testing.assert_array_equal(ses["device"].update(*add, *rem),
                                      ses["host"].update(*add, *rem))
        assert ses["host"].stats.plan_upload_bytes >= \
            4 * ses["device"].stats.plan_upload_bytes

    def test_mesh_device_emit(self):
        g = scale_free_digraph(n=50, avg_degree=5, exponent=2.2,
                               mutual_p=0.3, seed=8)
        want = census_batagelj_mrvar(g)
        got = triad_census_graph(g, mesh=default_mesh(), max_items=128)
        np.testing.assert_array_equal(got, want)

    def test_progress_hook_reports_device_counts(self):
        g = hub_graph(seed=3)
        seen = []
        engine = CensusEngine(backend="jnp")
        engine.run(g, max_items=50,
                   progress=lambda k, total, items: seen.append(
                       (k, total, items)))
        assert [k for k, _, _ in seen] == list(range(len(seen)))
        assert [i for _, _, i in seen] == engine.stats.chunk_items

    def test_zero_item_pairs(self):
        """A single mutual dyad: every pre-prune item is a self item, so
        the device dispatches a window whose keep count is zero and the
        census resolves from the closed forms — bit-identical to host."""
        g = from_edges([0, 1], [1, 0], n=5)
        want = census_batagelj_mrvar(g)
        for emit in ("device", "host"):
            engine = CensusEngine(backend="jnp", emit=emit)
            got = engine.run(g)
            np.testing.assert_array_equal(got, want)
            assert engine.stats.items == 0
        # device mode also agrees on the fused backend
        engine = CensusEngine(backend="pallas-fused")
        np.testing.assert_array_equal(engine.run(g), want)

    def test_empty_graph(self):
        g = from_edges(np.zeros(0, np.int64), np.zeros(0, np.int64), n=6)
        engine = CensusEngine(backend="jnp")
        got = engine.run(g)
        want = np.zeros(16, np.int64)
        want[0] = 6 * 5 * 4 // 6
        np.testing.assert_array_equal(got, want)
        assert engine.stats.chunks == 0

    def test_unknown_emit_rejected(self):
        with pytest.raises(ValueError, match="emit"):
            CensusEngine(emit="telepathy")
        with pytest.raises(ValueError, match="emit"):
            CensusEngine().run(hub_graph(), emit="telepathy")


# ------------------------------------------------------------ sessions


def random_arcs(rng, n, k):
    return rng.integers(0, n, k), rng.integers(0, n, k)


class TestDeviceEmitSession:
    @pytest.mark.parametrize("backend", ["jnp", "pallas-fused"])
    @pytest.mark.parametrize("orient", ["none", "degree"])
    def test_updates_match_oracle(self, backend, orient):
        rng = np.random.default_rng(13)
        g = scale_free_digraph(n=40, avg_degree=4, exponent=2.2,
                               mutual_p=0.3, seed=13)
        session = CensusEngine(backend=backend).session(
            g, orient=orient, max_items=128)
        assert session.emit == "device"
        np.testing.assert_array_equal(session.census(),
                                      census_batagelj_mrvar(g))
        for _ in range(3):
            add, rem = random_arcs(rng, g.n, 6), random_arcs(rng, g.n, 6)
            got = session.update(*add, *rem)
            g, _ = apply_delta(g, *add, *rem)
            np.testing.assert_array_equal(got, census_batagelj_mrvar(g))

    @pytest.mark.parametrize("emit", ["host", "device"])
    def test_reciprocal_updates_restore_census(self, emit):
        """Adding arcs and then deleting the same arcs returns the
        session to its exact starting census."""
        rng = np.random.default_rng(0)
        window = 4000
        src, dst, n = monitor_stream(rng, 80, 3000, 800, 2 * window)
        g = from_edges(src[:window], dst[:window], n=n)
        # arcs of the next window absent from g, so add then delete
        # restores g exactly (set semantics)
        base = src[:window] * n + dst[:window]
        cand_s, cand_d = src[window:], dst[window:]
        fresh = ~np.isin(cand_s * n + cand_d, base) & (cand_s != cand_d)
        d_src, d_dst = cand_s[fresh][:400], cand_d[fresh][:400]
        session = CensusEngine(backend="jnp", emit=emit).session(
            g, max_items=4096)
        want = session.census()
        np.testing.assert_array_equal(want, census_batagelj_mrvar(g))
        mid = session.update(d_src, d_dst)
        assert (mid != want).any()
        back = session.update(del_src=d_src, del_dst=d_dst)
        np.testing.assert_array_equal(back, want)
        assert session.stats.affected_pairs > 0

    def test_device_session_matches_host_session_stats(self):
        rng = np.random.default_rng(17)
        g = scale_free_digraph(n=60, avg_degree=5, exponent=2.2,
                               mutual_p=0.3, seed=17)
        add, rem = random_arcs(rng, g.n, 10), random_arcs(rng, g.n, 10)
        out = {}
        for emit in ("host", "device"):
            s = CensusEngine(backend="jnp", emit=emit).session(
                g, max_items=256)
            c0 = s.census()
            c1 = s.update(*add, *rem)
            out[emit] = (c0, c1, s.stats.items, s.stats.full_items)
        np.testing.assert_array_equal(out["host"][0], out["device"][0])
        np.testing.assert_array_equal(out["host"][1], out["device"][1])
        # device-counted subset items equal the host emission's count
        assert out["host"][2] == out["device"][2]
        assert out["host"][3] == out["device"][3]

    def test_empty_delta_short_circuits_without_dispatch(self, monkeypatch):
        """A no-op delta must return the running census with NO descriptor
        upload and NO device dispatch at all."""
        import repro.core.engine as engine_mod
        g = from_edges([0, 1, 2], [1, 2, 3], n=5)
        session = CensusEngine(backend="jnp").session(g)
        c0 = session.census()
        calls = []
        real_step = engine_mod._desc_step

        def spy(*a, **k):
            calls.append(1)
            return real_step(*a, **k)

        spy._cache_size = real_step._cache_size
        monkeypatch.setattr(engine_mod, "_desc_step", spy)
        got = session.update([0], [1])        # arc already present
        np.testing.assert_array_equal(got, c0)
        assert calls == []
        assert session.stats.chunks == 0 and session.stats.items == 0

    def test_compile_once_across_updates(self):
        rng = np.random.default_rng(19)
        g = scale_free_digraph(n=45, avg_degree=4, exponent=2.2,
                               mutual_p=0.3, seed=19)
        session = CensusEngine(backend="jnp").session(g, max_items=144)
        session.census()
        compiles = [session.stats.step_compiles]
        for _ in range(4):
            session.update(*random_arcs(rng, g.n, 5),
                           *random_arcs(rng, g.n, 5))
            compiles.append(session.stats.step_compiles)
            assert session.stats.capacity_recompiles == 0
        assert sum(compiles) <= 1, compiles

    def test_capacity_growth_recompiles_exactly_once(self):
        """Growing the resident buffers past capacity recompiles the step
        exactly once, attributed to ``capacity_recompiles`` (never
        ``step_compiles``); a same-capacity follow-up recompiles nothing.
        Unique n/max_items keep this test's jit entries out of every
        other test's cache."""
        g = scale_free_digraph(n=83, avg_degree=3, exponent=2.3,
                               mutual_p=0.2, seed=23)
        assert 128 < g.num_pairs < 256          # initial pair cap == 256
        session = CensusEngine(backend="jnp").session(g, max_items=277)
        session.census()
        first = (session.stats.step_compiles
                 + session.stats.capacity_recompiles)
        assert first == 1                       # fresh shapes compile once
        assert session.stats.capacity_recompiles == 0
        # push pairs past 256: the pair/entry caps double
        add_src = np.repeat(np.arange(40), 8)
        add_dst = (np.arange(320) * 7 + 1) % 83
        g2, _ = apply_delta(g, add_src, add_dst)
        assert g2.num_pairs > 256
        got = session.update(add_src, add_dst)
        np.testing.assert_array_equal(got, census_batagelj_mrvar(g2))
        assert session.stats.capacity_recompiles == 1
        assert session.stats.step_compiles == 0
        # steady state: same capacities, no compiles of either kind
        session.update([0], [2])
        assert session.stats.capacity_recompiles == 0
        assert session.stats.step_compiles == 0

    def test_monitor_device_emit_bit_identical(self):
        from repro.core import TriadMonitor
        rng = np.random.default_rng(29)
        src = rng.integers(0, 80, 3000)
        dst = rng.integers(0, 80, 3000)
        mons = {e: TriadMonitor(80, window=500, stride=100, history=2,
                                max_items=1024, emit=e)
                for e in ("host", "device")}
        for m in mons.values():
            m.observe(src, dst)
        np.testing.assert_array_equal(mons["host"].censuses,
                                      mons["device"].censuses)
        assert all(s.emit == "device"
                   for s in mons["device"].window_stats)
