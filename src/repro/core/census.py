"""Vectorized triad census — the device half of the algorithm.

Each flat work item (pair p=(u,v), neighbor slot) is processed
independently: decode w and its direction code from the packed entry,
binary-search w in the *other* endpoint's sorted row (the TPU-native
replacement for the paper's pointer merge), classify the triad in situ from
the 2-bit codes, and accumulate a 64-bin tricode histogram with
``segment``-style reductions — no atomics, which is the structural version
of the paper's privatized census vectors.

Backends:

* ``jnp``          — pure XLA; the oracle for everything below, and the
                     path that runs on a TPU at real graph sizes.
* ``pallas``       — classification in XLA, the 64-bin histogram hot loop
                     in the Pallas :mod:`repro.kernels.tricode_hist` kernel.
* ``pallas-fused`` — the whole per-item pipeline (gather, binary search,
                     classification, histogram) in one Pallas kernel; the
                     per-item tricode array never materializes in HBM
                     (:mod:`repro.kernels.census_fused`).  Interpret mode
                     only: the TPU compiler refuses it (:data:`TPU_REFUSED`).

Returned per device/shard: ``hist64`` (connected-triad tricode histogram)
and ``inter`` (2-bin count of N(u)∩N(v) elements split by pair mutuality),
from which the host assembles the exact 16-type census.

Dispatch lives in :class:`repro.core.engine.CensusEngine`, which runs these
partials either as one monolithic plan dispatch or as a stream of bounded
fixed-shape chunks accumulated on the host (the partials are integer sums,
so any chunking of the work items yields bit-identical censuses).
:func:`triad_census` below is the thin single-device wrapper.

Work items reach a dispatch in one of two forms: pre-packed item words
(:func:`census_partials` — host emission, via :func:`gather_work_items`)
or pair descriptors that the device expands back into items itself
(:func:`census_partials_desc`, via :func:`expand_work_items` — device
emission, no host-side item materialization).  Both hand the same
:class:`WorkItems` to :func:`classify_items`, and every
item the host-side planner would have pruned is provably a zero
contribution of the classification masks, which is why the two forms are
bit-identical on every backend and orient mode.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.planner import CensusPlan
from repro.core.tricode import FOLD_64_TO_16

BACKENDS = ("jnp", "pallas", "pallas-fused")

#: backends the TPU kernel compiler (Mosaic) refuses, with its reason
TPU_REFUSED = {
    "pallas-fused": (
        "Mosaic refuses the fused kernel's in-kernel 1-D vector gathers "
        "(NotImplementedError: Only 2D gather is supported), and the "
        "kernel pins the whole CSR and pair arrays in VMEM; use "
        "backend='jnp'"),
}


def check_backend(backend: str, platform: str) -> None:
    """Raise :class:`ValueError` for an unknown ``backend``, or for one
    whose kernels cannot be built on ``platform``."""
    if backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; one of {BACKENDS}")
    if platform == "tpu" and backend in TPU_REFUSED:
        raise ValueError(f"backend {backend!r} cannot run on a TPU: "
                         f"{TPU_REFUSED[backend]}")


def _stage(name: str):
    """Trace the decorated census stage under ``jax.named_scope(name)``:
    every step that calls it carries the name in its operations'
    metadata, so a profile attributes device time to ``expand``,
    ``classify``, ``keep`` and ``reduce`` by name.  Op metadata only —
    the compiled program and every count are unchanged."""
    def wrap(fn):
        @functools.wraps(fn)
        def staged(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return staged
    return wrap


def segment_searchsorted(keys, lo, hi, q, iters: int):
    """First index i in [lo, hi) with keys[i] >= q, per element (batched).

    ``iters`` must be >= ceil(log2(max segment length + 1)); it is a static
    plan property so the loop unrolls to a fixed depth.
    """
    size = keys.shape[0]
    def body(_, state):
        lo, hi = state
        mid = (lo + hi) >> 1
        km = keys[jnp.clip(mid, 0, size - 1)]
        go_right = km < q
        return jnp.where(go_right, mid + 1, lo), jnp.where(go_right, hi, mid)
    lo, hi = jax.lax.fori_loop(0, iters, body, (lo, hi), unroll=True)
    return lo


class WorkItems(NamedTuple):
    """Per-lane fields of a batch of work items, each gathered once.

    ``u``/``v`` are the pair's endpoints and ``pair_code`` its packed
    dyad code; ``u_lo``/``u_hi`` and ``v_lo``/``v_hi`` bound the rows of
    ``u`` and ``v`` in ``packed``, of which :func:`classify_items` reads
    only the other endpoint's (``v``'s on side 0, ``u``'s on side 1).
    ``slot`` indexes ``w``'s packed entry in the row of ``side``'s
    endpoint.  Invalid lanes carry ``slot`` and ``side`` 0 and in-range
    values elsewhere, so every gather stays in bounds; every mask drops
    them.
    """
    u: jax.Array
    v: jax.Array
    pair_code: jax.Array
    u_lo: jax.Array
    u_hi: jax.Array
    v_lo: jax.Array
    v_hi: jax.Array
    slot: jax.Array
    side: jax.Array
    valid: jax.Array


@_stage("classify")
def gather_work_items(indptr, pair_u, pair_v, pair_code,
                      item_pair, item_slot, item_side, item_valid):
    """:class:`WorkItems` of host-emitted items: the pair fields and the
    other endpoint's row bounds, gathered at ``item_pair``.  Only the
    other endpoint's bounds are read, so they fill both endpoints'."""
    u = pair_u[item_pair]
    v = pair_v[item_pair]
    other = jnp.where(item_side == 0, v, u)
    lo = indptr[other]
    hi = indptr[other + 1]
    return WorkItems(u, v, pair_code[item_pair], lo, hi, lo, hi,
                     item_slot, item_side, item_valid)


@_stage("classify")
def classify_items(packed, items: WorkItems, search_iters: int):
    """Per-item triad classification: (tricode, count_mask, inter_mask,
    is_mut, w).

    tricode is in [0, 64); count_mask marks items contributing a connected
    triad under the canonical-selection predicate; inter_mask marks items
    witnessing an element of N(u) ∩ N(v) on the pair's designated witness
    side (bit 2 of ``pair_code``; 0 unless the plan is degree-oriented);
    w is the third vertex.  The only gathers are ``w``'s packed entry,
    the binary search in the other endpoint's row, and its hit.
    """
    u, v, side = items.u, items.v, items.side
    nbr_ids = packed >> 2
    w_packed = packed[items.slot]
    w = w_packed >> 2
    c_side = w_packed & 3

    c_uv = items.pair_code & 3
    inter_side = (items.pair_code >> 2) & 1

    lo = jnp.where(side == 0, items.v_lo, items.u_lo)
    hi = jnp.where(side == 0, items.v_hi, items.u_hi)
    pos = segment_searchsorted(nbr_ids, lo, hi, w, search_iters)
    hit = packed[jnp.clip(pos, 0, packed.shape[0] - 1)]
    found = (pos < hi) & ((hit >> 2) == w)
    c_other = jnp.where(found, hit & 3, 0)

    c_uw = jnp.where(side == 0, c_side, c_other)
    c_vw = jnp.where(side == 0, c_other, c_side)

    not_self = (w != u) & (w != v)
    dedup = ~(found & (side == 1))      # union duplicates count once
    canonical = (v < w) | ((u < w) & (w < v) & (c_uw == 0))
    count_mask = items.valid & not_self & dedup & canonical
    inter_mask = items.valid & not_self & found & (side == inter_side)

    tricode = c_uv * 16 + c_uw * 4 + c_vw
    return tricode, count_mask, inter_mask, c_uv == 3, w


@_stage("expand")
def expand_work_items(indptr, pair_u, pair_v, pair_code, desc_pair,
                      desc_cum, desc_within0, anchors, num_valid, idx,
                      desc_iters: int) -> WorkItems:
    """Map flat item indices back to their :class:`WorkItems` from a
    per-pair descriptor window — the device-resident inverse of the host
    planner's ``emit_items``.  Each pair field and row bound a lane needs
    is gathered here, once, at the lane's pair.

    ``desc_cum`` is the window-local cumulative-offset table (padded with
    :data:`repro.core.planner.DESC_CUM_PAD`, which is larger than any
    real index, so the lower-bound search can never land on padding).
    ``anchors`` pre-resolves each :data:`DESC_ANCHOR_STRIDE`-item span to
    its first descriptor, so the per-lane search covers at most
    ``stride + 1`` candidates (every descriptor spans >= 1 pre-prune
    item — 2D vertex-sliced tiles keep pairs with a single in-slice
    item, so the old ``stride/2 + 1`` bound under the global >= 2
    items-per-pair invariant no longer holds) and ``desc_iters`` is the
    constant :data:`repro.core.planner.DESC_SEARCH_ITERS` — extra
    iterations are harmless (the converged lower bound is a fixed point
    of the search body, and the result is clamped into the anchored
    range).
    ``num_valid`` is a traced scalar: lanes past it are padding and keep
    the clamped descriptor's pair with slot and side 0.
    """
    from repro.core.planner import DESC_ANCHOR_STRIDE
    num_descs = desc_cum.shape[0]
    a = jnp.clip(idx // DESC_ANCHOR_STRIDE, 0, anchors.shape[0] - 1)
    lo_d = anchors[a]
    hi_d = jnp.minimum(lo_d + DESC_ANCHOR_STRIDE + 1, num_descs)
    d = segment_searchsorted(desc_cum, lo_d, hi_d, idx + 1,
                             desc_iters) - 1
    d = jnp.minimum(jnp.clip(d, 0, num_descs - 1), hi_d - 1)
    pair = desc_pair[d]
    within = desc_within0[d] + idx - desc_cum[d]
    u = pair_u[pair]
    v = pair_v[pair]
    u_lo = indptr[u]
    u_hi = indptr[u + 1]
    v_lo = indptr[v]
    v_hi = indptr[v + 1]
    deg_u = u_hi - u_lo
    side = (within >= deg_u).astype(jnp.int32)
    slot = jnp.where(side == 0, u_lo + within, v_lo + within - deg_u)
    valid = idx < num_valid
    return WorkItems(u, v, pair_code[pair], u_lo, u_hi, v_lo, v_hi,
                     jnp.where(valid, slot, 0), jnp.where(valid, side, 0),
                     valid)


@_stage("keep")
def prune_keep_mask(w, items: WorkItems, orient: str, prune_self: bool):
    """Device-side mirror of the planner's plan-time pruning predicate
    (:func:`repro.core.planner.prune_items`): which expanded items a host
    plan would have shipped, from the third vertex ``w`` that
    :func:`classify_items` returns and the lanes' pair fields — no
    gathers of its own.  Pruned items already contribute zero to every
    census counter (their count/inter masks are provably false), so this
    mask only feeds the valid-item statistics — dropping it can never
    change a census."""
    u, v, side = items.u, items.v, items.side
    not_self = (w != u) & (w != v)
    if orient == "degree":
        inter_side = (items.pair_code >> 2) & 1
        can_count = jnp.where(side == 0, w > v, w > u)
        return items.valid & not_self & ((side == inter_side) | can_count)
    if prune_self:
        return items.valid & not_self
    return items.valid


@_stage("reduce")
def _partials_reduce(tricode, count_mask, inter_mask, is_mut,
                     histogram_fn=None, keep_mask=None):
    """Shared reduction tail: fold per-item classifications into the
    ``hist64`` histogram and the intersection counters (plus a valid-item
    count when ``keep_mask`` is given — the device-emission stats lane)."""
    if histogram_fn is None:
        hist64 = jnp.zeros(64, jnp.int32).at[
            jnp.where(count_mask, tricode, 0)
        ].add(count_mask.astype(jnp.int32))
    else:
        hist64 = histogram_fn(tricode, count_mask)
    lanes = [
        jnp.sum((inter_mask & ~is_mut).astype(jnp.int32)),
        jnp.sum((inter_mask & is_mut).astype(jnp.int32)),
    ]
    if keep_mask is not None:
        lanes.append(jnp.sum(keep_mask.astype(jnp.int32)))
    return hist64, jnp.stack(lanes)


def census_partials(indptr, packed, pair_u, pair_v, pair_code,
                    item_sp, item_pv, search_iters: int, histogram_fn=None):
    """Shard-local partials from packed work items: (hist64, inter2) int32."""
    item_slot = item_sp >> 1
    item_side = item_sp & 1
    item_pair = item_pv >> 1
    item_valid = (item_pv & 1) == 1
    items = gather_work_items(indptr, pair_u, pair_v, pair_code,
                              item_pair, item_slot, item_side, item_valid)
    tricode, count_mask, inter_mask, is_mut, _ = classify_items(
        packed, items, search_iters)
    return _partials_reduce(tricode, count_mask, inter_mask, is_mut,
                            histogram_fn)


def census_partials_desc(indptr, packed, pair_u, pair_v, pair_code,
                         desc_pair, desc_cum, desc_within0, anchors,
                         num_valid, idx, search_iters: int,
                         desc_iters: int, orient: str, prune_self: bool,
                         histogram_fn=None):
    """Shard-local partials from *pair descriptors*: ``(hist64, inter3)``.

    The device expands each flat index in ``idx`` back to its work item
    (:func:`expand_work_items`) and classifies it in place — no host-side
    item materialization, no O(W) item upload.  ``inter3`` carries the two
    intersection counters plus the count of items the plan-time pruning
    predicate would have kept (:func:`prune_keep_mask`) so the engine's
    valid-item statistics stay comparable with host emission.
    """
    items = expand_work_items(indptr, pair_u, pair_v, pair_code, desc_pair,
                              desc_cum, desc_within0, anchors, num_valid,
                              idx, desc_iters)
    tricode, count_mask, inter_mask, is_mut, w = classify_items(
        packed, items, search_iters)
    keep = prune_keep_mask(w, items, orient, prune_self)
    return _partials_reduce(tricode, count_mask, inter_mask, is_mut,
                            histogram_fn, keep_mask=keep)


def census_partials_desc_batch(indptr, packed, pair_u, pair_v, pair_code,
                               words_batch, idx, search_iters: int,
                               desc_iters: int, orient: str,
                               prune_self: bool, backend: str = "jnp"):
    """Multi-window megastep partials: ``lax.scan`` over K stacked
    descriptor windows inside ONE compiled dispatch.

    ``words_batch`` is a fixed-shape ``(K, words)`` int32 buffer of
    stacked :meth:`repro.core.planner.DescriptorWindow.device_words`
    rows — the megabatch a
    :class:`repro.core.plan_stream.WindowBatcher` coalesces so Python
    dispatch cost is paid once per K windows instead of once per window.
    Rows past the batch's real window count are all-zero padding: their
    leading ``num_preprune`` word is 0, every lane of
    :func:`expand_work_items` comes out invalid, and the masked window
    contributes EXACT ZEROS — which is why any (real, padding) split of
    the batch is bit-identical to K separate single-window dispatches.
    A ``lax.cond`` on that word additionally skips the padded rows'
    compute, so a partially-filled batch costs only its real windows.

    Returns the per-window partials STACKED, ``(hist64s (K, 64),
    inter3s (K, 3))`` int32, rather than device-reduced: the engine
    merges them on the host in int64 exactly like the single-window
    async path (jax's default int32 lattice cannot hold a K-window sum
    without x64 mode, and the tiny (K, 67) transfer keeps the
    per-window ``chunk_items`` stats lane intact).
    """
    from repro.core.planner import num_desc_anchors
    num_anchors = num_desc_anchors(idx.shape[0])
    num_descs = (words_batch.shape[1] - 1 - num_anchors) // 3
    partials = desc_partials_fn(backend, search_iters, desc_iters,
                                orient, prune_self)

    def one(words):
        nv = words[:1]
        dp = words[1:1 + num_descs]
        dc = words[1 + num_descs:1 + 2 * num_descs]
        dw = words[1 + 2 * num_descs:1 + 3 * num_descs]
        an = words[1 + 3 * num_descs:]
        return partials(indptr, packed, pair_u, pair_v, pair_code,
                        dp, dc, dw, an, nv, idx)

    def zeros(_words):
        return jnp.zeros(64, jnp.int32), jnp.zeros(3, jnp.int32)

    def body(carry, words):
        return carry, jax.lax.cond(words[0] > 0, one, zeros, words)

    _, (hist64s, inter3s) = jax.lax.scan(body, None, words_batch)
    return hist64s, inter3s


def assemble_counts(n: int, base_asym: int, base_mut: int,
                    hist64: np.ndarray, inter: np.ndarray) -> np.ndarray:
    """Combine (accumulated) device partials with the closed-form bases
    into the 16 counts — the plan-free core of :func:`assemble_census`,
    used by the streaming engine where the bases arrive as per-chunk
    additive shares."""
    hist64 = np.asarray(hist64, dtype=np.int64)
    inter = np.asarray(inter, dtype=np.int64)
    census = FOLD_64_TO_16 @ hist64
    census[1] += base_asym + int(inter[0])   # 012
    census[2] += base_mut + int(inter[1])    # 102
    total = n * (n - 1) * (n - 2) // 6
    census[0] = total - census[1:].sum()
    return census


def assemble_census(plan: CensusPlan, hist64: np.ndarray,
                    inter: np.ndarray) -> np.ndarray:
    """Combine device partials with host closed forms into the 16 counts."""
    return assemble_counts(plan.n, plan.base_asym, plan.base_mut,
                           hist64, inter)


def partials_fn(backend: str, search_iters: int):
    """Per-shard partials callable for ``backend`` — the single dispatch
    point shared by the single-device and distributed drivers.  The
    returned function maps the 7 device arrays (graph + pairs + packed
    items) to ``(hist64, inter)``."""
    if backend == "pallas-fused":
        from repro.kernels import ops as kops
        return functools.partial(kops.fused_census_partials,
                                 search_iters=search_iters)
    histogram_fn = None
    if backend == "pallas":
        from repro.kernels import ops as kops
        histogram_fn = kops.tricode_histogram
    return functools.partial(census_partials, search_iters=search_iters,
                             histogram_fn=histogram_fn)


def desc_partials_fn(backend: str, search_iters: int, desc_iters: int,
                     orient: str, prune_self: bool):
    """Descriptor-expansion counterpart of :func:`partials_fn`: maps the
    9 device arrays (graph + pairs + descriptor window + valid count) and
    the resident flat-index array to ``(hist64, inter3)``."""
    if backend == "pallas-fused":
        from repro.kernels import ops as kops
        return functools.partial(kops.fused_census_desc_partials,
                                 search_iters=search_iters,
                                 desc_iters=desc_iters, orient=orient,
                                 prune_self=prune_self)
    histogram_fn = None
    if backend == "pallas":
        from repro.kernels import ops as kops
        histogram_fn = kops.tricode_histogram
    return functools.partial(census_partials_desc,
                             search_iters=search_iters,
                             desc_iters=desc_iters, orient=orient,
                             prune_self=prune_self,
                             histogram_fn=histogram_fn)


def triad_census(plan: CensusPlan, backend: str = "jnp") -> np.ndarray:
    """Single-device exact 16-type triad census from a plan.

    Thin wrapper over :class:`repro.core.engine.CensusEngine` (mesh-less,
    monolithic).  ``backend='pallas'`` routes the histogram hot loop
    through the Pallas kernel; ``backend='pallas-fused'`` runs the whole
    per-item pipeline in one Pallas kernel (interpret mode off TPU; refused
    on TPU).
    """
    from repro.core.engine import CensusEngine
    return CensusEngine(mesh=None, backend=backend).run_plan(plan)
