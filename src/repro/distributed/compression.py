"""int8 gradient compression with error feedback for cross-pod all-reduce.

Cross-pod (DCI) bandwidth is the scarce resource at multi-pod scale; the pod
axis is pure DP so its gradient all-reduce moves full model-gradients every
step.  This module quantizes that exchange to int8 (4x less traffic) with
per-tensor scales and keeps the quantization residual in an error-feedback
buffer (Seide et al. / 1-bit SGD lineage), which restores convergence to the
uncompressed trajectory up to O(lr * residual) terms.

Used by training/train_loop.py when ``grad_compression="int8"``; the exchange
itself is an all-gather of int8 + local dequant-mean inside shard_map.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

__all__ = ["init_error_state", "quantize", "dequantize",
           "compressed_pod_mean"]


def init_error_state(params):
    return jax.tree.map(lambda p: jnp.zeros_like(p, jnp.float32), params)


def quantize(g: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    scale = jnp.maximum(jnp.max(jnp.abs(g)), 1e-12) / 127.0
    q = jnp.clip(jnp.round(g / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize(q: jnp.ndarray, scale: jnp.ndarray) -> jnp.ndarray:
    return q.astype(jnp.float32) * scale


def compressed_pod_mean(grads, error, mesh, *, axis: str = "pod"):
    """Error-feedback int8 mean over ``axis``.  Returns (mean_grads, new_error).

    g_corrected = g + e;  q = Q(g_corrected);  e' = g_corrected - deQ(q);
    exchange q (int8) + scale, dequant-mean locally.
    Falls back to plain pmean when the axis is absent (single-pod mesh).
    """
    if axis not in mesh.axis_names:
        return grads, error

    def body(g, e):
        def per_leaf(gl, el):
            corrected = gl.astype(jnp.float32) + el
            q, scale = quantize(corrected)
            new_e = corrected - dequantize(q, scale)
            qs = jax.lax.all_gather(q, axis)            # [pods, ...] int8 wire
            scales = jax.lax.all_gather(scale, axis)
            mean = jnp.mean(qs.astype(jnp.float32)
                            * scales.reshape((-1,) + (1,) * gl.ndim), axis=0)
            return mean.astype(gl.dtype), new_e

        flat_g, treedef = jax.tree.flatten(g)
        flat_e = treedef.flatten_up_to(e)
        outs = [per_leaf(gl, el) for gl, el in zip(flat_g, flat_e)]
        return (treedef.unflatten([o[0] for o in outs]),
                treedef.unflatten([o[1] for o in outs]))

    fn = shard_map(body, mesh=mesh, in_specs=(P(), P()), out_specs=(P(), P()),
                   check_vma=False)
    return fn(grads, error)
