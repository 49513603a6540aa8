"""Partitioned multi-device engine: shard the graph, not just the items.

Central properties:

* **Shard-count invariance** — censuses are bit-identical across
  1/2/4/8-device meshes, both orients, both emit modes, streamed and
  monolithic schedules, full runs and incremental sessions (the vertex
  relabeling is order-preserving and the pair partition is exact, so no
  per-item decision can differ).
* **Minimality** — each device holds only the CSR rows its pair shard's
  endpoints own (plus empty halo rows), so per-device resident graph
  bytes shrink vs the replicated baseline.
* **Routing** — an incremental update whose delta is confined to one
  shard dispatches NOTHING on the other devices.
"""

import numpy as np
import pytest

from repro.core import (
    CensusEngine, TriadMonitor, apply_delta, census_batagelj_mrvar,
    default_mesh, extract_shard, from_edges, lpt_assign, pair_space,
    partition_graph, partition_graph_2d, replicated_graph_bytes,
    scale_free_digraph, shard_report, to_dense, triad_census_graph)
from repro.core.digraph import entry_keys
from repro.core.partition import slice_pair_terms
from repro.core.planner import (
    INTER_SIDE_BIT, emit_items_for_pairs, postprune_pair_counts,
    range_postprune_pair_counts)


def pl_graph(n=100, deg=5, seed=7, mutual_p=0.3):
    return scale_free_digraph(n=n, avg_degree=deg, exponent=2.2,
                              mutual_p=mutual_p, seed=seed)


def hub_graph(n=40, hub_out=24, extra=60, seed=0):
    """Graph with one dominant hub vertex (vertex 0)."""
    rng = np.random.default_rng(seed)
    src = [0] * hub_out + list(rng.integers(0, n, extra))
    dst = list(range(1, hub_out + 1)) + list(rng.integers(0, n, extra))
    return from_edges(src, dst, n=max(n, hub_out + 1))


# ---------------------------------------------------------------- LPT


class TestLPT:
    def test_assignment_covers_all_pairs(self):
        space = pair_space(pl_graph())
        owner = lpt_assign(postprune_pair_counts(space), 4)
        assert owner.shape == (space.num_pairs,)
        assert owner.min() >= 0 and owner.max() < 4

    def test_balance_below_target_on_power_law(self):
        """The acceptance target: max/mean item imbalance ≤ 1.2 on a
        power-law graph at 8 shards."""
        part = partition_graph(pl_graph(n=400, deg=6, seed=3), 8)
        assert part.stats.max_over_mean <= 1.2
        assert sum(part.stats.shard_items) == part.stats.total_items

    def test_deterministic(self):
        costs = postprune_pair_counts(pair_space(pl_graph(seed=11)))
        a = lpt_assign(costs, 8)
        b = lpt_assign(costs, 8)
        np.testing.assert_array_equal(a, b)

    def test_single_shard_and_validation(self):
        costs = np.array([5, 3, 2], dtype=np.int64)
        np.testing.assert_array_equal(lpt_assign(costs, 1), [0, 0, 0])
        with pytest.raises(ValueError, match="num_shards"):
            lpt_assign(costs, 0)

    def test_small_inputs_match_heap_exactly(self):
        """≤ the exact-head cutoff the vectorized path delegates to the
        heap outright — bit-identical assignments, so every historical
        small-graph partition is preserved."""
        from repro.core import lpt_assign_heap
        rng = np.random.default_rng(5)
        for ns in (1, 2, 4, 8):
            costs = rng.integers(0, 100, size=700).astype(np.int64)
            np.testing.assert_array_equal(lpt_assign(costs, ns),
                                          lpt_assign_heap(costs, ns))

    def test_large_input_balance_matches_heap(self):
        """Above the cutoff the bucketed waterfill takes over; the
        assignment may differ from the heap but the achieved balance
        must match the heap oracle to within a hair."""
        from repro.core import lpt_assign_heap
        rng = np.random.default_rng(6)
        costs = np.minimum(rng.zipf(1.7, size=30_000), 50_000
                           ).astype(np.int64)
        for ns in (2, 4, 8):
            v = lpt_assign(costs, ns)
            assert v.shape == costs.shape
            assert v.min() >= 0 and v.max() < ns
            lv = np.bincount(v, weights=costs, minlength=ns)
            lh = np.bincount(lpt_assign_heap(costs, ns), weights=costs,
                             minlength=ns)
            assert lv.max() <= lh.max() * 1.01 + 1
            np.testing.assert_array_equal(lpt_assign(costs, ns), v)

    def test_large_input_is_vectorized_fast(self):
        """The point of the rewrite: millions of pairs assign in seconds
        where the python heap took minutes (loose bound — CI boxes)."""
        import time
        rng = np.random.default_rng(7)
        costs = np.minimum(rng.zipf(1.8, size=2_000_000), 10 ** 6
                           ).astype(np.int64)
        t0 = time.perf_counter()
        owner = lpt_assign(costs, 8)
        dt = time.perf_counter() - t0
        assert owner.shape == costs.shape
        loads = np.bincount(owner, weights=costs, minlength=8)
        assert loads.max() <= 1.05 * loads.sum() / 8
        assert dt < 10.0

    def test_zero_and_empty_costs(self):
        assert lpt_assign(np.zeros(0, np.int64), 4).shape == (0,)
        owner = lpt_assign(np.zeros(10_000, np.int64), 4)
        assert owner.min() >= 0 and owner.max() < 4

    def test_explicit_owner_override(self):
        """partition_graph(owner=...) takes ANY assignment — the skew
        hook — and validates shape + range."""
        g = pl_graph(n=50, seed=2)
        space = pair_space(g)
        owner = np.arange(space.num_pairs, dtype=np.int64) % 3
        part = partition_graph(g, num_shards=3, owner=owner)
        np.testing.assert_array_equal(part.owner, owner)
        with pytest.raises(ValueError, match="owner has"):
            partition_graph(g, num_shards=3, owner=owner[:-1])
        with pytest.raises(ValueError, match="outside"):
            partition_graph(g, num_shards=2, owner=owner)


# ----------------------------------------------------------- extraction


class TestExtractShard:
    def test_local_subgraph_invariants(self):
        g = pl_graph(seed=5)
        part = partition_graph(g, 4)
        all_ids = np.concatenate([sh.pair_ids for sh in part.shards])
        # shards tile the pair space exactly
        np.testing.assert_array_equal(np.sort(all_ids),
                                      np.arange(part.space.num_pairs))
        for sh in part.shards:
            # relabel table sorted (order-preserving) and consistent
            assert (np.diff(sh.verts) > 0).all()
            sh.graph.validate()
            # every local pair endpoint's row is the full global row,
            # relabeled
            for j in range(min(sh.num_pairs, 10)):
                gu = part.space.pair_u[sh.pair_ids[j]]
                lu = sh.space.pair_u[j]
                assert sh.verts[lu] == gu
                glob_row = part.space.nbr[
                    part.space.indptr[gu]:part.space.indptr[gu + 1]]
                loc_row = sh.graph.neighbors(lu)
                np.testing.assert_array_equal(sh.verts[loc_row], glob_row)
                np.testing.assert_array_equal(
                    sh.graph.codes(lu),
                    part.space.packed[part.space.indptr[gu]:
                                      part.space.indptr[gu + 1]] & 3)

    @pytest.mark.parametrize("orient", ["none", "degree"])
    def test_local_items_match_global_subset(self, orient):
        """The shard's local item emission is the global subset emission
        relabeled — same pair order, same slots' neighbor identities."""
        g = pl_graph(n=60, seed=9)
        space = pair_space(g, orient=orient)
        part = partition_graph(space=space, num_shards=3)
        for sh in part.shards:
            lp, ls, lside = emit_items_for_pairs(
                sh.space, np.arange(sh.num_pairs))
            gp, gs, gside = emit_items_for_pairs(space, sh.pair_ids)
            np.testing.assert_array_equal(lside, gside)
            # item pair ids map local -> global
            np.testing.assert_array_equal(sh.pair_ids[lp], gp)
            # gathered neighbor ids map through the relabel table
            np.testing.assert_array_equal(
                sh.verts[sh.space.nbr[ls]], space.nbr[gs])
            # post-prune per-shard items match the stats record
            assert lp.shape[0] == sh.items

    def test_resident_bytes_shrink(self):
        g = pl_graph(n=400, deg=6, seed=3)
        part = partition_graph(g, 8)
        rep = replicated_graph_bytes(part.space)
        assert part.stats.replicated_bytes == rep
        assert part.stats.max_shard_bytes * 2 <= rep
        assert part.stats.byte_reduction >= 2.0
        assert "reduction" in shard_report(part)

    def test_empty_and_tiny_shards(self):
        g = from_edges([0, 1], [1, 2], n=5)     # 2 pairs, 8 shards
        part = partition_graph(g, 8)
        empty = [sh for sh in part.shards if sh.num_pairs == 0]
        assert len(empty) == 6
        for sh in empty:
            assert sh.graph.n == 0 and sh.items == 0

    def test_bad_pair_ids_rejected(self):
        space = pair_space(pl_graph())
        with pytest.raises(ValueError, match="pair id"):
            extract_shard(space, [space.num_pairs])

    @pytest.mark.parametrize("case", [
        "power-law", "power-law-mutual", "hub", "empty-shard", "one-pair",
        "2d-tiles"])
    def test_relabel_matches_sorted_search_form(self, case):
        """Every array of a shard equals, value and dtype, what the
        relabel by ``np.union1d`` and ``np.searchsorted`` gave."""
        for space, sh, costs, term in shard_cases(case):
            want = reference_shard(space, sh.pair_ids, costs,
                                   sh.vertex_range, term)
            got = {"pair_ids": sh.pair_ids, "verts": sh.verts,
                   "keys": sh.keys, "indptr": sh.graph.indptr,
                   "packed": sh.graph.packed,
                   "pair_u": sh.space.pair_u, "pair_v": sh.space.pair_v,
                   "pair_code": sh.space.pair_code,
                   "pair_term": sh.space.pair_term}
            assert sh.items == want.pop("items")
            for name, arr in want.items():
                assert got[name].dtype == arr.dtype, name
                np.testing.assert_array_equal(got[name], arr, err_msg=name)


def shard_cases(case):
    """(global space, shard, the costs it was cut with, its pair terms or
    None) for each shard of one relabel case."""
    if case == "2d-tiles":
        space = pair_space(pl_graph(n=90, seed=4), orient="degree")
        part = partition_graph_2d(space=space, mesh_shape=(2, 3))
        terms = slice_pair_terms(space, part.vertex_bounds)
        for sh in part.shards:
            lo, hi = sh.vertex_range
            j = sh.index % part.num_vertex_slices
            yield (space, sh, range_postprune_pair_counts(space, lo, hi),
                   terms[j])
        return
    g = {"power-law": lambda: pl_graph(seed=12, mutual_p=0.0),
         "power-law-mutual": lambda: pl_graph(seed=12, mutual_p=0.3),
         "hub": hub_graph,
         "empty-shard": lambda: from_edges([0, 1], [1, 2], n=5),
         "one-pair": lambda: pl_graph(n=30, seed=2)}[case]()
    space = pair_space(g, orient="degree")
    costs = postprune_pair_counts(space)
    if case == "one-pair":
        shards = [extract_shard(space, [space.num_pairs // 2], costs=costs)]
    else:
        ns = 8 if case == "empty-shard" else 3
        shards = partition_graph(space=space, num_shards=ns).shards
    if case == "empty-shard":
        assert any(sh.num_pairs == 0 for sh in shards)
    for sh in shards:
        yield space, sh, costs, None


def reference_shard(space, pair_ids, costs, vertex_range=None,
                    pair_term=None):
    """A shard's arrays by the relabel's sorted-search form: the local
    vertices are ``np.union1d`` of the endpoints and their (in-range)
    neighbours, and every id is relabelled by ``np.searchsorted``."""
    lo, hi = vertex_range if vertex_range is not None else (0, space.n)

    def row(x):
        e = space.packed[space.indptr[x]:space.indptr[x + 1]].astype(
            np.int64)
        return e[((e >> 2) >= lo) & ((e >> 2) < hi)]

    ids = np.sort(np.asarray(pair_ids, dtype=np.int64))
    pu, pv = space.pair_u[ids], space.pair_v[ids]
    ends = np.unique(np.concatenate([pu, pv]))
    rows = [row(x) for x in ends]
    rows_packed = np.concatenate(rows + [np.zeros(0, np.int64)])
    nbrs = rows_packed >> 2
    verts = np.union1d(ends, nbrs)
    deg = np.zeros(verts.shape[0], dtype=np.int64)
    deg[np.searchsorted(verts, ends)] = [r.shape[0] for r in rows]
    indptr = np.zeros(verts.shape[0] + 1, dtype=np.int64)
    np.cumsum(deg, out=indptr[1:])
    term = space.pair_term if pair_term is None else pair_term
    return {"pair_ids": ids, "verts": verts, "keys": pu * space.n + pv,
            "indptr": indptr,
            "packed": ((np.searchsorted(verts, nbrs) << 2)
                       | (rows_packed & 3)).astype(np.int32),
            "pair_u": np.searchsorted(verts, pu),
            "pair_v": np.searchsorted(verts, pv),
            "pair_code": space.pair_code[ids], "pair_term": term[ids],
            "items": int(costs[ids].sum())}


# ------------------------------------------------------------ cost vector


def unsorted_search_counts(space, pair_ids=None):
    """:func:`postprune_pair_counts` by its closed form with both entry-key
    searches made in the pairs' own order."""
    ids = (np.arange(space.num_pairs) if pair_ids is None
           else np.asarray(pair_ids))
    pu, pv = space.pair_u[ids], space.pair_v[ids]
    counts = space.counts[ids]
    if space.orient != "degree":
        return counts - (2 if space.prune_self else 0)
    key = np.repeat(np.arange(space.n), space.deg) * space.n + space.nbr
    pos_v = np.searchsorted(key, pu * space.n + pv) - space.indptr[pu]
    pos_u = np.searchsorted(key, pv * space.n + pu) - space.indptr[pv]
    du, dv = space.deg[pu], space.deg[pv]
    inter = (space.pair_code[ids] >> INTER_SIDE_BIT) & 1
    return (np.where(inter == 0, du - 1, du - pos_v - 1)
            + np.where(inter == 1, dv - 1, dv - pos_u - 1))


class TestPostpruneCounts:
    @pytest.mark.parametrize("scope", ["full", "shuffled-subset",
                                       "shard-local"])
    @pytest.mark.parametrize("orient", ["none", "degree"])
    def test_matches_unsorted_search_and_emission(self, orient, scope):
        """The cost vector equals its unsorted-search form and the
        per-pair count of emitted items, in every kind of pair space."""
        g = pl_graph(n=150, deg=6, seed=19)
        space = pair_space(g, orient=orient)
        ids, kw = None, {}
        if scope == "shuffled-subset":
            rng = np.random.default_rng(19)
            ids = rng.permutation(space.num_pairs)[:space.num_pairs // 3]
            kw = {"entry_key": entry_keys(g)}
        elif scope == "shard-local":
            space = partition_graph(space=space, num_shards=3).shards[1].space
        got = postprune_pair_counts(space, ids, **kw)
        np.testing.assert_array_equal(got, unsorted_search_counts(space, ids))
        want_ids = np.arange(space.num_pairs) if ids is None else ids
        emitted = np.bincount(emit_items_for_pairs(space, want_ids)[0],
                              minlength=space.num_pairs)[want_ids]
        np.testing.assert_array_equal(got, emitted)
        assert space.num_items_postprune() == emit_items_for_pairs(
            space, np.arange(space.num_pairs))[0].shape[0]


# ------------------------------------------------- shard-count invariance


class TestShardCountInvariance:
    """Satellite: census bit-identical across 1/2/4/8 devices × both
    orients × emit host/device."""

    @pytest.mark.parametrize("num_devices", [1, 2, 4, 8])
    @pytest.mark.parametrize("orient", ["none", "degree"])
    @pytest.mark.parametrize("emit", ["device", "host"])
    def test_invariance(self, num_devices, orient, emit):
        g = pl_graph(n=70, seed=5)
        want = census_batagelj_mrvar(g)
        engine = CensusEngine(mesh=default_mesh(num_devices),
                              backend="jnp", partition=True, emit=emit)
        for max_items in (None, 120):
            got = engine.run(g, max_items=max_items, orient=orient)
            np.testing.assert_array_equal(got, want)
        st = engine.stats
        assert st.partitioned and len(st.shard_items) == num_devices
        assert st.emit == emit

    @pytest.mark.parametrize("backend", ["pallas", "pallas-fused"])
    def test_backends(self, backend):
        g = pl_graph(n=40, deg=4, seed=8)
        want = census_batagelj_mrvar(g)
        engine = CensusEngine(mesh=default_mesh(4), backend=backend,
                              partition=True)
        np.testing.assert_array_equal(engine.run(g), want)
        np.testing.assert_array_equal(engine.run(g, max_items=80), want)

    def test_hub_pairs_straddle_three_shards(self):
        """A hub vertex's pairs must straddle ≥ 3 shards (LPT scatters
        the heavy pairs) and the census must stay bit-identical."""
        g = hub_graph()
        part = partition_graph(g, 4)
        hub_owner = np.unique(part.owner[
            (part.space.pair_u == 0) | (part.space.pair_v == 0)])
        assert hub_owner.size >= 3
        want = census_batagelj_mrvar(g)
        got = triad_census_graph(g, mesh=default_mesh(4), partition=True)
        np.testing.assert_array_equal(got, want)

    def test_compile_once_across_steps(self):
        g = pl_graph(n=90, seed=21)
        engine = CensusEngine(mesh=default_mesh(4), backend="jnp",
                              partition=True)
        engine.run(g, max_items=64)        # many windows per shard
        assert engine.stats.chunks >= 4
        # one compile per device at most, never one per window
        assert engine.stats.step_compiles <= 4
        engine.run(g, max_items=64)
        assert engine.stats.step_compiles == 0

    def test_graph_bytes_reported(self):
        g = pl_graph(n=300, deg=6, seed=3)
        engine = CensusEngine(mesh=default_mesh(8), backend="jnp",
                              partition=True)
        engine.run(g)
        st = engine.stats
        assert st.graph_replicated_bytes >= 2 * st.graph_resident_bytes
        assert st.shard_max_over_mean <= 1.2
        assert "partitioned" in st.summary()

    def test_partition_requires_mesh(self):
        with pytest.raises(ValueError, match="mesh"):
            CensusEngine(partition=True)

    def test_run_plan_rejected(self):
        from repro.core import build_plan
        engine = CensusEngine(mesh=default_mesh(2), partition=True)
        with pytest.raises(ValueError, match="partitioned"):
            engine.run_plan(build_plan(pl_graph()))

    def test_empty_graph(self):
        g = from_edges(np.zeros(0, np.int64), np.zeros(0, np.int64), n=7)
        engine = CensusEngine(mesh=default_mesh(4), partition=True)
        got = engine.run(g)
        want = np.zeros(16, np.int64)
        want[0] = 7 * 6 * 5 // 6
        np.testing.assert_array_equal(got, want)


# ------------------------------------------------------------- sessions


def random_arcs(rng, n, k):
    return rng.integers(0, n, k), rng.integers(0, n, k)


class TestPartitionedSession:
    @pytest.mark.parametrize("emit", ["device", "host"])
    @pytest.mark.parametrize("orient", ["none", "degree"])
    def test_updates_match_oracle(self, emit, orient):
        rng = np.random.default_rng(13)
        g = pl_graph(n=40, deg=4, seed=13)
        session = CensusEngine(mesh=default_mesh(4), backend="jnp",
                               partition=True, emit=emit).session(
            g, orient=orient, max_items=256)
        np.testing.assert_array_equal(session.census(),
                                      census_batagelj_mrvar(g))
        for _ in range(3):
            add, rem = random_arcs(rng, g.n, 6), random_arcs(rng, g.n, 6)
            got = session.update(*add, *rem)
            g, _ = apply_delta(g, *add, *rem)
            np.testing.assert_array_equal(got, census_batagelj_mrvar(g))
        assert session.stats.partitioned

    def test_matches_unpartitioned_session(self):
        rng = np.random.default_rng(17)
        g = pl_graph(n=60, seed=17)
        add, rem = random_arcs(rng, g.n, 10), random_arcs(rng, g.n, 10)
        out = {}
        for partition in (False, True):
            s = CensusEngine(mesh=default_mesh(4), backend="jnp",
                             partition=partition).session(g, max_items=512)
            out[partition] = (s.census(), s.update(*add, *rem),
                              s.stats.items, s.stats.full_items)
        np.testing.assert_array_equal(out[False][0], out[True][0])
        np.testing.assert_array_equal(out[False][1], out[True][1])
        assert out[False][2] == out[True][2]     # same recount schedule
        assert out[False][3] == out[True][3]

    def test_one_shard_delta_other_shards_dispatch_nothing(
            self, monkeypatch):
        """A delta confined to one shard's pairs must upload and dispatch
        on that shard's device ONLY (monkeypatch counts every descriptor
        dispatch and records which device it ran on)."""
        import repro.core.engine as engine_mod
        # main component on 0..29; vertices 30..33 isolated
        base = pl_graph(n=30, deg=3, seed=3)
        a = to_dense(base)
        s, d = np.nonzero(a)
        g = from_edges(s, d, n=34)
        session = CensusEngine(mesh=default_mesh(4), backend="jnp",
                               partition=True).session(g)
        session.census()
        # update 1: a fresh 3-vertex component — all of its pairs are
        # assigned to ONE shard (locality-first assignment)
        got = session.update([30, 30, 31], [31, 32, 32])
        g, _ = apply_delta(g, [30, 30, 31], [31, 32, 32])
        np.testing.assert_array_equal(got, census_batagelj_mrvar(g))
        new_keys = [30 * 34 + 31, 30 * 34 + 32, 31 * 34 + 32]
        owners = {s for s in range(4)
                  if np.isin(new_keys, session._keys[s]).any()}
        assert len(owners) == 1
        (owner,) = owners
        owner_dev = session._devices[owner].id
        # update 2: flip one arc inside the component — every affected
        # pair lives on `owner`; no other device may see a dispatch
        calls = []
        real_step = engine_mod._desc_step

        def spy(*args, **kw):
            calls.append(list(args[0].devices())[0].id)
            return real_step(*args, **kw)

        spy._cache_size = real_step._cache_size
        monkeypatch.setattr(engine_mod, "_desc_step", spy)
        got = session.update([32], [30])
        monkeypatch.setattr(engine_mod, "_desc_step", real_step)
        g, _ = apply_delta(g, [32], [30])
        np.testing.assert_array_equal(got, census_batagelj_mrvar(g))
        assert calls, "expected the owning shard to dispatch"
        assert set(calls) == {owner_dev}
        nz = [i for i, x in enumerate(session.stats.shard_items) if x]
        assert nz == [owner] and session.stats.items > 0

    def test_empty_delta_no_dispatch(self, monkeypatch):
        import repro.core.engine as engine_mod
        g = from_edges([0, 1, 2], [1, 2, 3], n=5)
        session = CensusEngine(mesh=default_mesh(2), backend="jnp",
                               partition=True).session(g)
        c0 = session.census()
        calls = []

        def spy(*a, **k):
            calls.append(1)

        spy._cache_size = engine_mod._desc_step._cache_size
        monkeypatch.setattr(engine_mod, "_desc_step", spy)
        got = session.update([0], [1])        # arc already present
        np.testing.assert_array_equal(got, c0)
        assert calls == []
        assert session.stats.chunks == 0

    def test_set_graph_repartitions(self):
        g1 = pl_graph(n=50, seed=1)
        g2 = pl_graph(n=50, seed=2)
        session = CensusEngine(mesh=default_mesh(4), backend="jnp",
                               partition=True).session(g1)
        np.testing.assert_array_equal(session.census(),
                                      census_batagelj_mrvar(g1))
        session.set_graph(g2)
        assert session.counts is None
        np.testing.assert_array_equal(session.census(),
                                      census_batagelj_mrvar(g2))
        with pytest.raises(ValueError, match="pinned"):
            session.set_graph(pl_graph(n=51, seed=2))

    def test_churn_keeps_ownership_balanced(self):
        """Sustained arc churn must not concentrate the pair space onto
        one shard (locality-capped assignment + lightest-shard spill)."""
        rng = np.random.default_rng(23)
        g = pl_graph(n=60, deg=5, seed=23)
        session = CensusEngine(mesh=default_mesh(4), backend="jnp",
                               partition=True).session(g, max_items=2048)
        session.census()
        for _ in range(12):
            add = random_arcs(rng, g.n, 25)
            rem = random_arcs(rng, g.n, 25)
            session.update(*add, *rem)
            g, _ = apply_delta(g, *add, *rem)
        np.testing.assert_array_equal(session.counts,
                                      census_batagelj_mrvar(g))
        loads = [sh.items for sh in session.shards]
        assert max(loads) <= 1.6 * (sum(loads) / len(loads))

    def test_explicit_rebalance_restores_lpt_balance(self):
        """Satellite: rebalance() re-runs the LPT over the churned pair
        space, recovers ≤ 1.1 imbalance, and the census stays exact."""
        rng = np.random.default_rng(31)
        g = pl_graph(n=60, deg=5, seed=31)
        session = CensusEngine(mesh=default_mesh(4), backend="jnp",
                               partition=True).session(g, max_items=2048)
        session.census()
        for _ in range(10):
            add = random_arcs(rng, g.n, 30)
            rem = random_arcs(rng, g.n, 30)
            session.update(*add, *rem)
            g, _ = apply_delta(g, *add, *rem)
        session.rebalance()
        assert session.rebalances == 1
        assert session.load_max_over_mean <= 1.1
        # census after rebalance is still exact, and further updates work
        np.testing.assert_array_equal(session.census(),
                                      census_batagelj_mrvar(g))
        add = random_arcs(rng, g.n, 10)
        got = session.update(*add, [], [])
        g, _ = apply_delta(g, *add, [], [])
        np.testing.assert_array_equal(got, census_batagelj_mrvar(g))

    def test_auto_rebalance_threshold(self):
        """Churn past the threshold triggers rebalance inside update();
        the returned census is still the exact post-delta census."""
        rng = np.random.default_rng(37)
        g = pl_graph(n=60, deg=5, seed=37)
        session = CensusEngine(mesh=default_mesh(4), backend="jnp",
                               partition=True).session(
            g, max_items=2048, auto_rebalance_threshold=1.1)
        session.census()
        for _ in range(12):
            add = random_arcs(rng, g.n, 35)
            rem = random_arcs(rng, g.n, 35)
            got = session.update(*add, *rem)
            g, _ = apply_delta(g, *add, *rem)
            np.testing.assert_array_equal(got, census_batagelj_mrvar(g))
        assert session.rebalances >= 1
        assert session.load_max_over_mean <= 1.1

    def test_auto_rebalance_threshold_validation(self):
        eng = CensusEngine(mesh=default_mesh(2), backend="jnp",
                           partition=True)
        with pytest.raises(ValueError, match="threshold"):
            eng.session(pl_graph(n=20), auto_rebalance_threshold=0.5)
        with pytest.raises(ValueError, match="partition"):
            CensusEngine(backend="jnp").session(
                pl_graph(n=20), auto_rebalance_threshold=1.2)


# -------------------------------------------------------------- monitor


class TestPartitionedMonitor:
    def test_monitor_bit_identical(self):
        rng = np.random.default_rng(29)
        src = rng.integers(0, 60, 1500)
        dst = rng.integers(0, 60, 1500)
        mons = {
            False: TriadMonitor(60, window=300, stride=100, history=2,
                                max_items=1024),
            True: TriadMonitor(60, window=300, stride=100, history=2,
                               max_items=1024, mesh=default_mesh(4),
                               partition=True),
        }
        for m in mons.values():
            m.observe(src, dst)
        np.testing.assert_array_equal(mons[False].censuses,
                                      mons[True].censuses)
        assert all(s.partitioned for s in mons[True].window_stats)
        assert all(len(s.shard_items) == 4
                   for s in mons[True].window_stats)


# ---------------------------------------------------------------- stats


class TestPhysicalStats:
    def test_host_emit_upload_bytes_are_per_device(self):
        """Satellite fix: under a mesh the packed item arrays are SHARDED,
        so the physical per-device upload is chunk bytes / ndev."""
        from repro.core.engine import ITEM_BYTES
        g = pl_graph(n=80, seed=31)
        single = CensusEngine(backend="jnp", emit="host")
        meshy = CensusEngine(mesh=default_mesh(8), backend="jnp",
                             emit="host")
        single.run(g, max_items=400)
        meshy.run(g, max_items=400)
        assert single.stats.plan_upload_bytes == \
            ITEM_BYTES * single.stats.chunk_shape
        assert meshy.stats.plan_upload_bytes == \
            ITEM_BYTES * meshy.stats.chunk_shape // 8
        # graph bytes: replicated path reports the full footprint on
        # every device
        assert meshy.stats.graph_resident_bytes == \
            meshy.stats.graph_replicated_bytes == \
            replicated_graph_bytes(pair_space(g))

    def test_partitioned_upload_is_private_window(self):
        from repro.core.planner import num_desc_anchors
        g = pl_graph(n=80, seed=31)
        # the stats record the per-dispatch (single-device) window:
        # chunk_shape is per device, and so is the upload unit
        part = CensusEngine(mesh=default_mesh(4), backend="jnp",
                            partition=True, emit="device")
        part.run(g, max_items=400)
        st = part.stats
        assert st.partitioned
        assert st.plan_upload_bytes == 4 * (
            1 + 3 * st.desc_shape + num_desc_anchors(st.chunk_shape))
