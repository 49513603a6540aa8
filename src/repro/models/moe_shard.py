"""Explicit shard_map MoE dispatch — the beyond-paper optimization for the
MoE cells (§Perf), and the purest expression of the paper's patterns:

* each device's tokens form exactly one dispatch group (the manhattan-
  collapsed routing loop, privatized per device — zero cross-device
  traffic for the route/position/capacity logic);
* expert exchange is ONE ``all_to_all`` over the ``model`` axis each way
  (vs. the GSPMD baseline's inferred all-gather/permute storm);
* router/load statistics are per-device partials merged with a single
  ``psum`` — the paper's 64 privatized census vectors, verbatim;
* FSDP weight gathers are explicit ``all_gather`` (transpose:
  reduce-scatter), so the collective schedule is exactly what you read.

Used by the hillclimb variants via ``build_train_step(..., moe_impl=
"shard_map")``; numerics match the grouped GSPMD path (same per-group
capacity semantics), asserted in tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from repro.models.ffn import GATED


def _gather_weight(w, spec: P, skip: tuple = ()):
    """Explicit FSDP: all-gather a weight along every sharded dim.

    Axes in ``skip`` stay sharded (for EP, the expert dim's ``model``
    sharding IS the expert assignment — each shard keeps its experts)."""
    for dim, part in enumerate(spec):
        if part is None:
            continue
        for ax in (part if isinstance(part, tuple) else (part,)):
            if ax in skip:
                continue
            w = jax.lax.all_gather(w, ax, axis=dim, tiled=True)
    return w


def _local_dispatch(xt, logits32, e: int, k: int, cap: int):
    """Per-device routing + scatter (no collectives at all)."""
    tl, d = xt.shape
    probs = jax.nn.softmax(logits32, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, k)           # (Tl, k)
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9)
    ge = expert_idx.reshape(tl * k)
    gg = gate_vals.reshape(tl * k)
    local_t = jax.lax.broadcasted_iota(jnp.int32, (tl * k,), 0) // k
    onehot = jax.nn.one_hot(ge, e, dtype=jnp.int32)
    pos = jnp.take_along_axis(
        jnp.cumsum(onehot, axis=0) - onehot, ge[:, None], 1)[:, 0]
    keep = pos < cap
    slot = jnp.where(keep, ge * cap + pos, e * cap)
    buf = jnp.zeros((e * cap + 1, d), xt.dtype).at[slot].add(xt[local_t])
    stats = (probs, onehot, keep)
    return buf[:-1].reshape(e, cap, d), (slot, gg, local_t), stats


def _expert_ffn_local(cfg, ws, xe):
    up = jnp.einsum("ecd,edf->ecf", xe, ws["w_up"].astype(xe.dtype))
    if cfg.ffn_activation in GATED:
        gate = jnp.einsum("ecd,edf->ecf", xe,
                          ws["w_gate"].astype(xe.dtype))
        h = (jax.nn.silu(gate) if cfg.ffn_activation == "swiglu"
             else jax.nn.gelu(gate)) * up
    elif cfg.ffn_activation == "sq_relu":
        r = jax.nn.relu(up)
        h = r * r
    else:
        h = jax.nn.gelu(up)
    return jnp.einsum("ecf,efd->ecd", h, ws["w_down"].astype(h.dtype))


def make_sharded_moe(cfg, mesh: Mesh, batch_axes_, expert_specs: dict,
                     capacity_factor: float = 1.25):
    """Build apply(p, x) -> (y, metrics) running the dispatch in
    shard_map. ``expert_specs`` are the actual param PartitionSpecs
    (from the sharding rules) so in_specs match storage exactly."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    nm = sizes.get("model", 1)
    e, k = cfg.num_experts, cfg.top_k
    ep = e % nm == 0 and nm > 1
    all_axes = tuple(mesh.axis_names)
    b_axes = (batch_axes_ if isinstance(batch_axes_, tuple)
              else ((batch_axes_,) if batch_axes_ else ()))

    def inner(p, x):
        b_l, s_l, d = x.shape
        tl = b_l * s_l
        xt = x.reshape(tl, d)
        wr = p["router"].astype(xt.dtype)                 # replicated
        logits32 = jnp.einsum("td,de->te", xt, wr).astype(jnp.float32)
        cap = max(int(np.ceil(tl * k / e * capacity_factor)), 4)
        xe, (slot, gg, local_t), (probs, onehot, keep) = _local_dispatch(
            xt, logits32, e, k, cap)

        # EP: weights stay model-sharded on the expert dim (that sharding
        # IS the expert->shard assignment); FSDP dims are gathered.
        skip = ("model",) if ep else ()
        ws = {key: _gather_weight(p[key], expert_specs[key], skip=skip)
              for key in ("w_up", "w_down", "w_gate") if key in p}
        if ep:
            # ONE all-to-all each way over `model`: (E, C, d) -> (E/nm,
            # nm*C, d) gathers each owner's expert buffers from its row
            xe = jax.lax.all_to_all(xe, "model", split_axis=0,
                                    concat_axis=1, tiled=True)
            ye = _expert_ffn_local(cfg, ws, xe)
            ye = jax.lax.all_to_all(ye, "model", split_axis=1,
                                    concat_axis=0, tiled=True)
        else:
            ye = _expert_ffn_local(cfg, ws, xe)
        ye = ye.reshape(e * cap, d)
        ye = jnp.concatenate([ye, jnp.zeros((1, d), ye.dtype)])
        items = ye[slot] * gg[:, None].astype(ye.dtype)
        out = jnp.zeros((tl, d), ye.dtype).at[local_t].add(items)

        if cfg.num_shared_experts:
            su = _gather_weight(p["shared_up"],
                                expert_specs["shared_up"])
            sd = _gather_weight(p["shared_down"],
                                expert_specs["shared_down"])
            h = jnp.einsum("td,df->tf", xt, su.astype(xt.dtype))
            if "shared_gate" in p:
                sg = _gather_weight(p["shared_gate"],
                                    expert_specs["shared_gate"])
                h = jax.nn.silu(jnp.einsum(
                    "td,df->tf", xt, sg.astype(xt.dtype))) * h
            else:
                h = jax.nn.gelu(h)
            out = out + jnp.einsum("tf,fd->td", h, sd.astype(h.dtype))

        # privatized stats -> ONE reduction (the paper's census pattern)
        me = jax.lax.pmean(probs.mean(axis=0), all_axes)
        load = jax.lax.psum(onehot.sum(axis=0), all_axes)
        tk = jax.lax.psum(jnp.asarray(tl * k, jnp.float32), all_axes)
        ce = load.astype(jnp.float32) / tk
        aux_loss = e * jnp.sum(me * ce)
        z_loss = jax.lax.pmean(
            jnp.mean(jax.nn.logsumexp(logits32, axis=-1) ** 2), all_axes)
        dropped = jax.lax.psum(jnp.sum(1 - keep.astype(jnp.int32)),
                               all_axes)
        metrics = {"moe_aux_loss": aux_loss, "moe_z_loss": z_loss,
                   "expert_load": load, "dropped_tokens": dropped}
        return out.reshape(b_l, s_l, d), metrics

    x_spec = P(b_axes if len(b_axes) > 1 else
               (b_axes[0] if b_axes else None), "model", None)
    p_specs = dict(expert_specs)
    p_specs["router"] = P(None, None)
    in_specs = ({k: p_specs[k] for k in p_specs}, x_spec)
    out_specs = (x_spec, P())

    fn = shard_map(inner, mesh=mesh, in_specs=in_specs,
                   out_specs=out_specs, check_vma=False)

    def apply(p, x):
        pp = {k: p[k] for k in p_specs if k in p}
        return fn(pp, x)

    return apply
