"""Mixture-of-Experts FFN with top-k token-choice routing.

Two execution paths with identical semantics:

  * ``local``    — one-hot dispatch einsum on this device's tokens; used for
                   CPU smoke tests and single-device runs.
  * ``sharded``  — ``shard_map`` expert parallelism: tokens are locally
                   dispatched into per-expert capacity buffers, exchanged with
                   ``all_to_all`` over the ``model`` mesh axis (experts live
                   there), FFN'd, and returned.  This is the production EP
                   path; the all-to-all pair is the collective the roofline
                   attributes to MoE layers.

Capacity: per-group capacity C = ceil(tokens * top_k * capacity_factor / E);
overflowing tokens are dropped (their residual stream passes through), the
standard GShard/Switch behaviour.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .layers import Compute, truncated_normal

__all__ = ["init_moe", "moe_mlp"]


def init_moe(key, d_model: int, d_ff: int, n_experts: int):
    ks = jax.random.split(key, 4)
    return {
        "router": truncated_normal(ks[0], (d_model, n_experts), d_model ** -0.5),
        "wi_gate": truncated_normal(ks[1], (n_experts, d_model, d_ff), d_model ** -0.5),
        "wi_up": truncated_normal(ks[2], (n_experts, d_model, d_ff), d_model ** -0.5),
        "wo": truncated_normal(ks[3], (n_experts, d_ff, d_model), d_ff ** -0.5),
    }


def _route(p, x_flat, top_k: int):
    """Router: probs -> top-k (gates renormalized, Mixtral-style)."""
    logits = jnp.einsum("nd,de->ne", x_flat, p["router"].astype(Compute))
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gates, ids = jax.lax.top_k(probs, top_k)              # [n, k]
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    # load-balancing auxiliary loss (Switch eq. 4): E * sum_e f_e * p_e
    e = probs.shape[-1]
    me = probs.mean(axis=0)
    ce = jnp.zeros((e,), jnp.float32).at[ids.reshape(-1)].add(
        jnp.ones_like(ids.reshape(-1), jnp.float32)) / ids.size
    aux = e * jnp.sum(me * ce)
    return gates.astype(Compute), ids, aux


def _dispatch_tensors(ids, gates, n_experts: int, capacity: int):
    """Position-in-expert assignment -> dispatch/combine one-hots.

    ids [n, k] int32, gates [n, k].  Returns
      dispatch [n, E, C] bool-ish Compute, combine [n, E, C] Compute.
    """
    n, k = ids.shape
    flat_ids = ids.reshape(-1)                            # [n*k], token-major
    onehot = jax.nn.one_hot(flat_ids, n_experts, dtype=jnp.int32)  # [n*k, E]
    pos_in_expert = jnp.cumsum(onehot, axis=0) - onehot   # rank within expert
    pos = (pos_in_expert * onehot).sum(-1)                # [n*k]
    keep = pos < capacity
    disp = (jax.nn.one_hot(flat_ids, n_experts, dtype=Compute)[:, :, None]
            * jax.nn.one_hot(pos, capacity, dtype=Compute)[:, None, :]
            * keep[:, None, None].astype(Compute))        # [n*k, E, C]
    disp = disp.reshape(n, k, n_experts, capacity)
    combine = disp * gates[..., None, None]
    return disp.sum(1), combine.sum(1)                    # [n, E, C]


def _expert_ffn(p, h):
    """h [E, C, d] -> [E, C, d] SwiGLU per expert (E-major grouped GEMM)."""
    gate = jnp.einsum("ecd,edf->ecf", h, p["wi_gate"].astype(Compute))
    up = jnp.einsum("ecd,edf->ecf", h, p["wi_up"].astype(Compute))
    return jnp.einsum("ecf,efd->ecd", jax.nn.silu(gate) * up, p["wo"].astype(Compute))


def moe_mlp(p, x, *, top_k: int, capacity_factor: float = 1.25,
            mesh: Optional[jax.sharding.Mesh] = None,
            expert_axis: str = "model",
            batch_axes: tuple[str, ...] = ("pod", "data")):
    """x [B, T, D] -> ([B, T, D], aux_loss)."""
    if mesh is None or expert_axis not in mesh.axis_names:
        return _moe_local(p, x, top_k, capacity_factor)
    return _moe_sharded(p, x, top_k, capacity_factor, mesh, expert_axis, batch_axes)


def _moe_local(p, x, top_k, capacity_factor):
    b, t, d = x.shape
    e = p["router"].shape[1]
    x_flat = x.reshape(-1, d)
    n = x_flat.shape[0]
    capacity = max(top_k, int(math.ceil(n * top_k * capacity_factor / e)))
    gates, ids, aux = _route(p, x_flat, top_k)
    disp, combine = _dispatch_tensors(ids, gates, e, capacity)
    buf = jnp.einsum("nd,nec->ecd", x_flat, disp)          # [E, C, d]
    h = _expert_ffn(p, buf)
    out = jnp.einsum("ecd,nec->nd", h, combine)
    return out.reshape(b, t, d), aux


def _moe_sharded(p, x, top_k, capacity_factor, mesh, expert_axis, batch_axes):
    """shard_map EP: local dispatch + all_to_all over the expert axis.

    Inside the region each device holds a [b_loc, t_loc, d] block (sequence
    additionally split over the expert/model axis so routing work is spread),
    builds [E, C_loc, d] send buffers, and exchanges them so each device runs
    its resident experts on tokens from every peer.
    """
    from jax import shard_map

    axes = tuple(a for a in batch_axes if a in mesh.axis_names)
    ep = mesh.shape[expert_axis]
    e = p["router"].shape[1]
    assert e % ep == 0, (e, ep)

    dp_size = math.prod(mesh.shape[a] for a in axes) if axes else 1
    batch_spec = axes if (axes and x.shape[0] % dp_size == 0 and dp_size > 1) \
        else None
    # split the sequence over the expert axis too when it divides (spreads
    # routing work); decode steps (t == 1) keep the sequence whole.
    seq_spec = expert_axis if x.shape[1] % ep == 0 else None

    in_specs = (
        {  # params: experts sharded over the expert axis, router replicated
            "router": P(),
            "wi_gate": P(expert_axis), "wi_up": P(expert_axis), "wo": P(expert_axis),
        },
        P(batch_spec, seq_spec, None),
    )
    out_specs = (P(batch_spec, seq_spec, None), P())

    def body(p_loc, x_loc):
        b_loc, t_loc, d = x_loc.shape
        x_flat = x_loc.reshape(-1, d)
        n = x_flat.shape[0]
        capacity = max(top_k, int(math.ceil(n * top_k * capacity_factor / e)))
        gates, ids, aux = _route(p_loc, x_flat, top_k)
        disp, combine = _dispatch_tensors(ids, gates, e, capacity)
        send = jnp.einsum("nd,nec->ecd", x_flat, disp)     # [E, C, d]
        # exchange: split expert dim, concat capacity dim across the axis
        recv = jax.lax.all_to_all(send, expert_axis, split_axis=0, concat_axis=1,
                                  tiled=True)              # [E/ep, ep*C, d]
        h = _expert_ffn(p_loc, recv)
        back = jax.lax.all_to_all(h, expert_axis, split_axis=1, concat_axis=0,
                                  tiled=True)               # [E, C, d]
        out = jnp.einsum("ecd,nec->nd", back, combine)
        aux = jax.lax.pmean(aux, expert_axis)
        for a in axes:
            aux = jax.lax.pmean(aux, a)
        return out.reshape(b_loc, t_loc, d), aux

    fn = shard_map(body, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                   check_vma=False)
    return fn(p, x)
