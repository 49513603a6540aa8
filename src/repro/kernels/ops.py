"""Jit'd public wrappers around the Pallas kernels.

The raw kernels take ``interpret`` as a required argument; these wrappers
resolve ``interpret=None`` from the platform — compiled by Mosaic on a
TPU, the Pallas interpreter everywhere else — so no caller on a TPU
interprets by accident.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.census_fused import (
    census_fused_desc_kernel, census_fused_kernel)
from repro.kernels.census_fused import BLOCK_ITEMS as FUSED_BLOCK_ITEMS
from repro.kernels.tricode_hist import (
    BLOCK_ITEMS, tricode_histogram_kernel)
from repro.kernels.pair_codes import LANES, TILE_B, pair_codes_kernel

#: padding value for the flat-index array shipped to the desc kernel:
#: >= any possible valid-lane count (so padding lanes decode invalid) and
#: small enough that the in-kernel ``idx + 1`` can never overflow int32
IDX_PAD = 2**31 - 2


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def tricode_histogram(tricode: jax.Array, mask: jax.Array,
                      interpret: bool | None = None) -> jax.Array:
    """64-bin histogram of ``tricode`` where ``mask`` is set.

    Drop-in replacement for the scatter-add path in
    :func:`repro.core.census.census_partials`.
    """
    if interpret is None:
        interpret = _interpret_default()
    w = tricode.shape[0]
    masked = jnp.where(mask, tricode, 64).astype(jnp.int32)
    pad = (-w) % BLOCK_ITEMS
    if pad:
        masked = jnp.concatenate(
            [masked, jnp.full((pad,), 64, jnp.int32)])
    return tricode_histogram_kernel(masked, interpret=interpret)


def pair_codes(q: jax.Array, k: jax.Array, kc: jax.Array,
               interpret: bool | None = None) -> jax.Array:
    """Matched-key codes for (B, 128) tiles; pads B to the kernel tile."""
    if interpret is None:
        interpret = _interpret_default()
    b = q.shape[0]
    pad = (-b) % TILE_B
    if pad:
        zq = jnp.full((pad, LANES), -1, jnp.int32)
        zk = jnp.full((pad, LANES), -2, jnp.int32)
        zc = jnp.zeros((pad, LANES), jnp.int32)
        q = jnp.concatenate([q, zq])
        k = jnp.concatenate([k, zk])
        kc = jnp.concatenate([kc, zc])
    out = pair_codes_kernel(q, k, kc, interpret=interpret)
    return out[:b]


def fused_census_partials(indptr, packed, pair_u, pair_v, pair_code,
                          item_sp, item_pv, search_iters: int,
                          interpret: bool | None = None):
    """Fused single-pass census partials: ``(hist64 (64,), inter (2,))``.

    Drop-in replacement for :func:`repro.core.census.census_partials`
    (backend ``"pallas-fused"``): gather, binary search, classification
    and histogram all happen inside one Pallas kernel.  Pads the packed
    work-item words to the kernel block; zero words decode to
    ``valid == 0`` so padding contributes nothing.
    """
    if interpret is None:
        interpret = _interpret_default()
    w = item_sp.shape[0]
    pad = (-w) % FUSED_BLOCK_ITEMS
    item_sp = item_sp.astype(jnp.int32)
    item_pv = item_pv.astype(jnp.int32)
    if pad:
        zeros = jnp.zeros((pad,), jnp.int32)
        item_sp = jnp.concatenate([item_sp, zeros])
        item_pv = jnp.concatenate([item_pv, zeros])
    return census_fused_kernel(indptr, packed, pair_u, pair_v, pair_code,
                               item_sp, item_pv, search_iters,
                               interpret=interpret)


def fused_census_desc_partials(indptr, packed, pair_u, pair_v, pair_code,
                               desc_pair, desc_cum, desc_within0,
                               anchors, num_valid, idx,
                               search_iters: int, desc_iters: int,
                               orient: str, prune_self: bool,
                               interpret: bool | None = None):
    """Fused device-emission census partials: ``(hist64 (64,), inter (3,))``.

    Drop-in replacement for
    :func:`repro.core.census.census_partials_desc` (backend
    ``"pallas-fused"``): descriptor expansion, gather, binary search,
    classification and histogram all happen inside one Pallas kernel.
    Pads the flat-index array to the kernel block with ``IDX_PAD``, which
    always decodes to an invalid lane.
    """
    if interpret is None:
        interpret = _interpret_default()
    w = idx.shape[0]
    pad = (-w) % FUSED_BLOCK_ITEMS
    idx = idx.astype(jnp.int32)
    if pad:
        idx = jnp.concatenate(
            [idx, jnp.full((pad,), IDX_PAD, jnp.int32)])
    return census_fused_desc_kernel(
        indptr, packed, pair_u, pair_v, pair_code, desc_pair, desc_cum,
        desc_within0, anchors, num_valid, idx, search_iters, desc_iters,
        orient, prune_self, interpret=interpret)


# re-export oracles for test symmetry
tricode_histogram_ref = ref.tricode_histogram_ref
pair_codes_ref = ref.pair_codes_ref
