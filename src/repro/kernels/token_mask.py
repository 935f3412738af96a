"""Pallas TPU kernel: fused constrained-decoding logit mask.

Serving integration of the DFA engine (DESIGN.md §3.2): each sequence in the
decode batch carries a grammar-DFA state; the per-state allowed-token table
``allowed[Q, V]`` gives the legal next tokens.  This kernel fuses the row
gather with the logit masking epilogue so the [B, V] mask tensor never
round-trips through HBM — at V = 128K and B = 128 that saves a 16 MB
materialization per decode step.

Grid: (B, V / v_blk); the allowed table streams one [Q, v_blk] tile per
column block (grammar DFAs are small: Q ~ 10^2..10^3).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["token_mask_kernel", "token_mask_pallas"]


def token_mask_kernel(states_ref, allowed_ref, logits_ref, out_ref, *, neg: float):
    """states [B] int32; allowed tile [Q, v_blk] uint8; logits tile [1, v_blk]."""
    b = pl.program_id(0)
    s = jax.lax.dynamic_slice_in_dim(states_ref[...], b, 1)[0]
    row = allowed_ref[pl.ds(s, 1), :]  # dynamic-slice row load [1, v_blk]
    logits = logits_ref[...]
    out_ref[...] = jnp.where(row > 0, logits, jnp.asarray(neg, logits.dtype))


@functools.partial(jax.jit, static_argnames=("v_blk", "neg", "interpret"))
def token_mask_pallas(states: jnp.ndarray, allowed: jnp.ndarray,
                      logits: jnp.ndarray, *, v_blk: int = 2048,
                      neg: float = -1e30, interpret: bool | None = None) -> jnp.ndarray:
    """Pallas-backed equivalent of ``ref.token_mask_ref``.

    states [B] int32; allowed [Q, V] uint8/bool; logits [B, V] float.
    V % v_blk == 0 (ops.py pads the vocab tail).
    """
    b, v = logits.shape
    q = allowed.shape[0]
    assert v % v_blk == 0, (v, v_blk)
    kernel = functools.partial(token_mask_kernel, neg=neg)
    from .ops import _interpret  # deferred: ops imports this module
    return pl.pallas_call(
        kernel,
        grid=(b, v // v_blk),
        in_specs=[
            pl.BlockSpec((b,), lambda i, j: (0,)),
            pl.BlockSpec((q, v_blk), lambda i, j: (0, j)),
            pl.BlockSpec((1, v_blk), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((1, v_blk), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((b, v), logits.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=_interpret(interpret),
    )(states.astype(jnp.int32), allowed.astype(jnp.uint8), logits)
