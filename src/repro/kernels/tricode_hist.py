"""Pallas TPU kernel: fused tricode histogram (the census hot loop).

The paper's hot spot is the concurrent increment of the shared census
vector, which it fixes with 64 hash-privatized copies.  On TPU we eliminate
contention structurally: each grid step compares an 8K-item ``(ROWS,
LANES)`` VMEM tile of tricodes against each of the 64 classes and folds
the matches down the sublanes into per-lane counts — row ``c`` of a
``(64, LANES)`` VMEM-resident output block revisited across the grid
holds class ``c``'s per-lane partial sums, i.e. privatization at the
VMEM level, one final lane fold outside the kernel.  The tile keeps its
native 2-D layout throughout (no reshape to a column, which Mosaic
cannot lay out).

Masked (padding / non-canonical) items carry tricode 64 and match no
class, contributing nothing.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

#: Block geometry: (ROWS, 128) int32 items per grid step.
ROWS = 64
LANES = 128
BLOCK_ITEMS = ROWS * LANES


#: histogram classes (the 64 tricodes); one output row each
CLASSES = 64


def _kernel(tri_ref, out_ref):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    tri = tri_ref[...]                                   # (ROWS, LANES)
    for c in range(CLASSES):
        out_ref[c:c + 1, :] += jnp.sum(
            (tri == c).astype(jnp.int32), axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def tricode_histogram_kernel(tricode_masked: jax.Array, *,
                             interpret: bool) -> jax.Array:
    """64-bin histogram of tricodes in [0, 64); values >= 64 are ignored.

    ``tricode_masked``: (W,) int32, padded by the wrapper so that
    W % BLOCK_ITEMS == 0.  ``interpret`` runs the Pallas interpreter
    instead of compiling for the TPU (:mod:`repro.kernels.ops` picks it
    from the platform).
    """
    w = tricode_masked.shape[0]
    assert w % BLOCK_ITEMS == 0, w
    grid = w // BLOCK_ITEMS
    tri2d = tricode_masked.reshape(grid * ROWS, LANES)
    out = pl.pallas_call(
        _kernel,
        grid=(grid,),
        in_specs=[pl.BlockSpec((ROWS, LANES), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((CLASSES, LANES), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((CLASSES, LANES), jnp.int32),
        interpret=interpret,
    )(tri2d)
    return jnp.sum(out, axis=1)
