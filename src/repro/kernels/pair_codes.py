"""Pallas TPU kernel: blocked sorted-row membership with code extraction.

The in-situ triad classification of the paper's Fig 8 pointer merge,
re-shaped for the VPU: for tiles of query ids Q and sorted key ids K with
packed 2-bit direction codes, emit the code of the matching key (or 0).
A (tile, 128, 128) broadcast-compare replaces the serial two-pointer walk —
O(128) redundant compares per lane bought back by full vector width, the
classic latency->bandwidth trade on TPU (DESIGN.md §2).

Rows longer than one 128-lane tile are handled by the caller (multi-tile
sweep or the jnp binary-search path); power-law tails mean most rows fit.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

TILE_B = 8      #: rows per grid step
LANES = 128


def _kernel(q_ref, k_ref, kc_ref, out_ref):
    q = q_ref[...]          # (TILE_B, 128) query ids
    k = k_ref[...]          # (TILE_B, 128) key ids (sorted, padded with -1)
    kc = kc_ref[...]        # (TILE_B, 128) key codes
    eq = (q[:, :, None] == k[:, None, :])                # (TB, 128, 128)
    out_ref[...] = jnp.sum(
        jnp.where(eq, kc[:, None, :], 0), axis=2).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def pair_codes_kernel(q: jax.Array, k: jax.Array, kc: jax.Array, *,
                      interpret: bool) -> jax.Array:
    """Per-query matched code, 0 if absent. All inputs (B, 128) int32.

    Key ids must be unique within a row (CSR rows are strictly sorted), so
    the sum over matches has at most one non-zero term.
    """
    b = q.shape[0]
    assert q.shape == k.shape == kc.shape and q.shape[1] == LANES
    assert b % TILE_B == 0, b
    return pl.pallas_call(
        _kernel,
        grid=(b // TILE_B,),
        in_specs=[pl.BlockSpec((TILE_B, LANES), lambda i: (i, 0))] * 3,
        out_specs=pl.BlockSpec((TILE_B, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, LANES), jnp.int32),
        interpret=interpret,
    )(q, k, kc)
