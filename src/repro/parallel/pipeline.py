"""GPipe-style pipeline parallelism over the ``pod`` mesh axis.

At 2+ pods, cross-pod ICI is the scarcest link; instead of DP over pods
(one full gradient all-reduce across pods per step) the pipeline sends
only microbatch activations over ``collective-permute`` — the multi-pod
placement alternative exposed by the launcher.

Implementation: ``shard_map`` over ``pod``; every pod holds one *stage*
(an equal slice of the layer stack, leading-axis sharded). The GPipe
schedule runs M + S - 1 ticks; at tick t stage s processes microbatch
t - s. Activations hop stages via ``ppermute`` (differentiable — its
transpose is the reverse permute, so ``jax.grad`` through a pipeline step
yields the GPipe backward schedule automatically).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_apply(stage_fn, mesh: Mesh, axis: str = "pod"):
    """Build a pipelined apply: (stage_params, microbatches) -> outputs.

    ``stage_params``: pytree with leading axis = num_stages (sharded over
    ``axis``). ``microbatches``: (M, ...) array stack, logically fed to
    stage 0 and collected from the last stage; replicated in/out specs
    keep the API simple (activations are small relative to weights).
    ``stage_fn(params_for_stage, x) -> y`` with y.shape == x.shape.
    """
    n_stages = dict(zip(mesh.axis_names, mesh.devices.shape))[axis]

    def inner(stage_params, mbs):
        stage_id = jax.lax.axis_index(axis)
        m = mbs.shape[0]
        ticks = m + n_stages - 1
        local_params = jax.tree.map(lambda a: a[0], stage_params)

        def tick(carry, t):
            buf, outs = carry
            mb_idx = jnp.clip(t - stage_id, 0, m - 1)
            active = (t >= stage_id) & (t - stage_id < m)
            x_in = jnp.where(stage_id == 0,
                             mbs[jnp.clip(t, 0, m - 1)], buf)
            y = stage_fn(local_params, x_in)
            y = jnp.where(active, y, buf)
            # pass to the next stage (last stage wraps; value unused)
            nxt = jax.lax.ppermute(
                y, axis, [(i, (i + 1) % n_stages)
                          for i in range(n_stages)])
            out_slot = t - (n_stages - 1)
            is_out = (stage_id == n_stages - 1) & (out_slot >= 0)
            outs = jax.lax.dynamic_update_index_in_dim(
                outs, jnp.where(is_out, y, outs[jnp.clip(out_slot, 0,
                                                         m - 1)]),
                jnp.clip(out_slot, 0, m - 1), 0)
            return (nxt, outs), None

        buf0 = jnp.zeros_like(mbs[0])
        outs0 = jnp.zeros_like(mbs)
        (buf, outs), _ = jax.lax.scan(
            tick, (buf0, outs0), jnp.arange(ticks))
        # every stage computed an ``outs``; only the last stage's is real.
        # broadcast it: sum over stages of masked outs
        outs = jnp.where(stage_id == n_stages - 1, outs, 0.0)
        return jax.lax.psum(outs, axis)

    spec_params = P(axis)
    other_axes = [a for a in mesh.axis_names if a != axis]
    return shard_map(
        inner, mesh=mesh,
        in_specs=(spec_params, P(*([None] * 1))),
        out_specs=P(),
        check_vma=False)


def split_stages(params_list: list, n_stages: int):
    """Stack per-layer param pytrees into (n_stages, layers/stage, ...)."""
    per = len(params_list) // n_stages
    assert per * n_stages == len(params_list)
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *params_list)
    return jax.tree.map(
        lambda a: a.reshape(n_stages, per, *a.shape[1:]), stacked)
