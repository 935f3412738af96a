"""GPipe-style pipeline parallelism over a mesh axis via collective_permute.

Optional parallelism mode (DESIGN.md §5): the layer stack is split into S
stages laid out on a ``stage`` mesh axis; microbatches stream through with
the classic (M + S - 1)-step schedule, activations hopping stages with
``ppermute``.  Bubble fraction = (S-1)/(M+S-1); compute/comm overlap comes
from XLA scheduling the permute of step t against stage compute of step t+1.

This module is self-contained so PP can be validated on small host meshes
(tests spawn an 8-device subprocess); wiring PP into the main trainer is a
config flag that reshapes (data, model) -> (data, stage, model).
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

__all__ = ["pipeline_apply"]


def pipeline_apply(stage_fn: Callable, stage_params, x_micro: jnp.ndarray,
                   mesh, *, axis: str = "stage") -> jnp.ndarray:
    """Run ``stage_fn(params_s, x)`` through S pipeline stages.

    stage_params : pytree with leading [S] dim (stage-major stack)
    x_micro      : [M, ...] microbatches
    Returns [M, ...] outputs of the final stage, in order.
    """
    s = mesh.shape[axis]
    m = x_micro.shape[0]
    steps = m + s - 1

    p_specs = jax.tree.map(lambda _: P(axis), stage_params)

    def body(params_loc, xs):
        params_loc = jax.tree.map(lambda a: a[0], params_loc)  # my stage
        idx = jax.lax.axis_index(axis)
        first = idx == 0
        last = idx == s - 1
        perm = [(i, i + 1) for i in range(s - 1)]

        buf = jnp.zeros_like(xs[0])              # activation held by my stage
        outs = jnp.zeros((m,) + xs.shape[1:], xs.dtype)

        def step(t, carry):
            buf, outs = carry
            # stage 0 ingests microbatch t (when available)
            feed = jax.lax.dynamic_index_in_dim(
                xs, jnp.clip(t, 0, m - 1), axis=0, keepdims=False)
            inp = jnp.where(first, feed, buf)
            out = stage_fn(params_loc, inp)
            # the last stage banks its finished microbatch (t - (s-1))
            done_idx = t - (s - 1)
            outs = jax.lax.cond(
                jnp.logical_and(last, done_idx >= 0),
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, out, jnp.maximum(done_idx, 0), axis=0),
                lambda o: o, outs)
            # hop activations one stage forward
            buf = jax.lax.ppermute(out, axis, perm)
            return buf, outs

        buf, outs = jax.lax.fori_loop(0, steps, step, (buf, outs))
        # only the last stage banked results; psum broadcasts them so the
        # replicated out_spec is honest (other stages hold zeros)
        return jax.lax.psum(outs, axis)

    fn = shard_map(body, mesh=mesh, in_specs=(p_specs, P()),
                   out_specs=P(), check_vma=False)
    return fn(stage_params, x_micro)
