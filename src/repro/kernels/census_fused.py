"""Pallas TPU kernel: the whole census per-item pipeline, fused.

The histogram-only kernel (:mod:`repro.kernels.tricode_hist`) still lets
XLA materialize full per-item ``tricode``/mask arrays in HBM between the
classification stage and the reduction.  This kernel fuses the entire
per-item pipeline into one grid pass: each step loads a block of *packed*
work items (two int32 words per item, see
:func:`repro.core.planner.pack_items`) into VMEM, gathers ``w`` and its
direction code from the CSR row data, runs the unrolled binary search into
the other endpoint's row, classifies the triad from the 2-bit dyad codes,
and folds a one-hot 64-bin histogram plus the 2-bin intersection counters
into a VMEM-resident output block revisited across the grid.  The per-item
tricode never touches HBM — the VMEM analogue of the paper's privatized
census vectors, one level lower in the hierarchy.

Graph-shaped inputs (indptr, packed CSR, pair arrays) ride along as
whole-array blocks pinned across grid steps; the kernel therefore requires
them to fit in VMEM (fine for per-shard subproblems — shard the graph via
:mod:`repro.core.distributed` before they outgrow it).  Runs in interpret
mode only: the TPU compiler refuses the in-kernel 1-D vector gathers of
:func:`repro.core.census.classify_items` ("Only 2D gather is
supported"), so :class:`repro.core.engine.CensusEngine` refuses this
backend on a TPU (:data:`repro.core.census.TPU_REFUSED`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.planner import DESC_CUM_PAD

#: Work-item block geometry per grid step: (ROWS, 128) packed words.
ROWS = 64
LANES = 128
BLOCK_ITEMS = ROWS * LANES

#: Sentinel padding for the packed CSR array: larger than any real entry,
#: keeps padded tails sorted and un-matchable ((sentinel >> 2) != any id).
PACKED_PAD = 2**31 - 1


def _accumulate_block(out_ref, tricode, count_mask, inter_mask, is_mut,
                      keep_mask=None):
    """Fold one item block's classifications into the VMEM-resident
    (8, 128) output: row 0 = hist64, row 1 lanes 0/1 = intersection
    counters (+ lane 2 = pruning-predicate keep count when given) — all
    vector-shaped updates."""
    # one-hot fold: masked items get tricode 64, outside the one-hot range
    tricode = jnp.where(count_mask, tricode, 64)
    cls = jax.lax.broadcasted_iota(jnp.int32, (BLOCK_ITEMS, 64), 1)
    counts = jnp.sum((tricode[:, None] == cls).astype(jnp.int32), axis=0)
    inter_a = jnp.sum((inter_mask & ~is_mut).astype(jnp.int32))
    inter_m = jnp.sum((inter_mask & is_mut).astype(jnp.int32))

    row = jax.lax.broadcasted_iota(jnp.int32, (8, LANES), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (8, LANES), 1)
    counts128 = jnp.concatenate([counts, jnp.zeros(64, jnp.int32)])
    block = jnp.where(row == 0, counts128[None, :], 0)
    block = block + jnp.where((row == 1) & (lane == 0), inter_a, 0)
    block = block + jnp.where((row == 1) & (lane == 1), inter_m, 0)
    if keep_mask is not None:
        kept = jnp.sum(keep_mask.astype(jnp.int32))
        block = block + jnp.where((row == 1) & (lane == 2), kept, 0)
    out_ref[...] += block


def _kernel(ip_ref, pk_ref, pu_ref, pv_ref, pc_ref, sp_ref, pw_ref,
            out_ref, *, search_iters: int):
    # lazy import: repro.core.census lazily imports this package in turn
    from repro.core.census import classify_items, gather_work_items

    @pl.when(pl.program_id(0) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    # whole-graph blocks, flattened back to 1-D for gathers
    ip = ip_ref[...].reshape(-1)
    pk = pk_ref[...].reshape(-1)
    pu = pu_ref[...].reshape(-1)
    pvv = pv_ref[...].reshape(-1)
    pc = pc_ref[...].reshape(-1)

    # unpack the two-word item encoding
    sp = sp_ref[...].reshape(-1)          # slot << 1 | side
    pw = pw_ref[...].reshape(-1)          # pair << 1 | valid
    slot = sp >> 1
    side = sp & 1
    pair = pw >> 1
    valid = (pw & 1) == 1

    # gather + unrolled binary search + classification: the same pure-jnp
    # implementation as the oracle backend, traced on VMEM-resident values
    items = gather_work_items(ip, pu, pvv, pc, pair, slot, side, valid)
    tricode, count_mask, inter_mask, is_mut, _ = classify_items(
        pk, items, search_iters)
    _accumulate_block(out_ref, tricode, count_mask, inter_mask, is_mut)


def _desc_kernel(ip_ref, pk_ref, pu_ref, pv_ref, pc_ref, dp_ref, dc_ref,
                 dw_ref, an_ref, nv_ref, idx_ref, out_ref, *,
                 num_descs: int, num_anchors: int, search_iters: int,
                 desc_iters: int, orient: str, prune_self: bool):
    """Device-emission variant: the item block arrives as flat *indices*
    only; the kernel expands each index to its work item from the
    VMEM-resident descriptor window before classifying — work items never
    exist on the host or in HBM at all."""
    from repro.core.census import (
        classify_items, expand_work_items, prune_keep_mask)

    @pl.when(pl.program_id(0) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    ip = ip_ref[...].reshape(-1)
    pk = pk_ref[...].reshape(-1)
    pu = pu_ref[...].reshape(-1)
    pvv = pv_ref[...].reshape(-1)
    pc = pc_ref[...].reshape(-1)
    # descriptor/anchor arrays sliced back to their true (static) lengths
    # (the anchored search geometry is defined on them, not on the
    # lane-padded tiles)
    dp = dp_ref[...].reshape(-1)[:num_descs]
    dc = dc_ref[...].reshape(-1)[:num_descs]
    dw = dw_ref[...].reshape(-1)[:num_descs]
    an = an_ref[...].reshape(-1)[:num_anchors]
    nv = nv_ref[...].reshape(-1)[0]
    idx = idx_ref[...].reshape(-1)

    items = expand_work_items(ip, pu, pvv, pc, dp, dc, dw, an, nv, idx,
                              desc_iters)
    tricode, count_mask, inter_mask, is_mut, w = classify_items(
        pk, items, search_iters)
    keep = prune_keep_mask(w, items, orient, prune_self)
    _accumulate_block(out_ref, tricode, count_mask, inter_mask, is_mut,
                      keep_mask=keep)


def _pad_1d_to_lanes(a: jax.Array, fill) -> jax.Array:
    """Pad a 1-D int32 array to a (rows, LANES) tile with ``fill``."""
    size = max(int(a.shape[0]), 1)
    padded = -(-size // LANES) * LANES
    a = jnp.concatenate(
        [a.astype(jnp.int32),
         jnp.full((padded - a.shape[0],), fill, jnp.int32)])
    return a.reshape(-1, LANES)


@functools.partial(jax.jit, static_argnames=("search_iters", "interpret"))
def census_fused_kernel(indptr, packed, pair_u, pair_v, pair_code,
                        item_sp, item_pv, search_iters: int, *,
                        interpret: bool):
    """Fused census partials: ``(hist64 (64,), inter (2,))`` int32.

    ``item_sp``/``item_pv`` are the planner's packed work-item words,
    pre-padded by the caller so their length is a BLOCK_ITEMS multiple.
    """
    w = item_sp.shape[0]
    assert w % BLOCK_ITEMS == 0 and item_pv.shape[0] == w, (
        w, item_pv.shape)
    grid = w // BLOCK_ITEMS

    ip2 = _pad_1d_to_lanes(indptr, fill=indptr[-1])
    pk2 = _pad_1d_to_lanes(packed, fill=PACKED_PAD)
    pu2 = _pad_1d_to_lanes(pair_u, fill=0)
    pv2 = _pad_1d_to_lanes(pair_v, fill=0)
    pc2 = _pad_1d_to_lanes(pair_code, fill=0)
    sp2 = item_sp.reshape(grid * ROWS, LANES)
    pw2 = item_pv.reshape(grid * ROWS, LANES)

    whole = lambda a: pl.BlockSpec(a.shape, lambda i: (0, 0))
    item = pl.BlockSpec((ROWS, LANES), lambda i: (i, 0))
    out = pl.pallas_call(
        functools.partial(_kernel, search_iters=search_iters),
        grid=(grid,),
        in_specs=[whole(ip2), whole(pk2), whole(pu2), whole(pv2),
                  whole(pc2), item, item],
        out_specs=pl.BlockSpec((8, LANES), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((8, LANES), jnp.int32),
        interpret=interpret,
    )(ip2, pk2, pu2, pv2, pc2, sp2, pw2)
    return out[0, :64], out[1, :2]


@functools.partial(jax.jit, static_argnames=(
    "search_iters", "desc_iters", "orient", "prune_self", "interpret"))
def census_fused_desc_kernel(indptr, packed, pair_u, pair_v, pair_code,
                             desc_pair, desc_cum, desc_within0, anchors,
                             num_valid, idx, search_iters: int,
                             desc_iters: int, orient: str,
                             prune_self: bool, *, interpret: bool):
    """Fused census partials from pair descriptors:
    ``(hist64 (64,), inter (3,))`` int32.

    ``idx`` is the flat item-index array (its length, a BLOCK_ITEMS
    multiple, sets the grid); the descriptor window + anchor table ride
    along as whole-array VMEM blocks like the graph arrays, and each grid
    step expands + classifies one index block in place.  ``inter`` lane 2
    is the count of indices the plan-time pruning predicate would keep.
    """
    w = idx.shape[0]
    assert w % BLOCK_ITEMS == 0, w
    grid = w // BLOCK_ITEMS

    ip2 = _pad_1d_to_lanes(indptr, fill=indptr[-1])
    pk2 = _pad_1d_to_lanes(packed, fill=PACKED_PAD)
    pu2 = _pad_1d_to_lanes(pair_u, fill=0)
    pv2 = _pad_1d_to_lanes(pair_v, fill=0)
    pc2 = _pad_1d_to_lanes(pair_code, fill=0)
    dp2 = _pad_1d_to_lanes(desc_pair, fill=0)
    dc2 = _pad_1d_to_lanes(desc_cum, fill=DESC_CUM_PAD)
    dw2 = _pad_1d_to_lanes(desc_within0, fill=0)
    an2 = _pad_1d_to_lanes(anchors, fill=0)
    nv2 = _pad_1d_to_lanes(num_valid, fill=0)
    idx2 = idx.reshape(grid * ROWS, LANES)

    whole = lambda a: pl.BlockSpec(a.shape, lambda i: (0, 0))
    item = pl.BlockSpec((ROWS, LANES), lambda i: (i, 0))
    out = pl.pallas_call(
        functools.partial(_desc_kernel, num_descs=int(desc_pair.shape[0]),
                          num_anchors=int(anchors.shape[0]),
                          search_iters=search_iters,
                          desc_iters=desc_iters, orient=orient,
                          prune_self=prune_self),
        grid=(grid,),
        in_specs=[whole(ip2), whole(pk2), whole(pu2), whole(pv2),
                  whole(pc2), whole(dp2), whole(dc2), whole(dw2),
                  whole(an2), whole(nv2), item],
        out_specs=pl.BlockSpec((8, LANES), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((8, LANES), jnp.int32),
        interpret=interpret,
    )(ip2, pk2, pu2, pv2, pc2, dp2, dc2, dw2, an2, nv2, idx2)
    return out[0, :64], out[1, :3]
