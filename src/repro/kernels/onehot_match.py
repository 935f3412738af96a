"""Pallas TPU kernel: MXU (one-hot matmul) DFA block maps — beyond-paper.

The paper's matching loop is a serial chain of gathers: L-deep dependency,
VPU-bound.  The TPU has a 128x128 systolic MXU sitting idle during that loop.
This kernel re-expresses a block of L symbols as a product of one-hot
transition matrices:

    M_block = P_{s_1} @ P_{s_2} @ ... @ P_{s_L},   P_c[q, q'] = [table[q,c] = q']

Each row of ``P_c`` (and of any product of such matrices) has exactly one 1,
so bf16 storage and fp32 accumulation are *exact* — the argmax recovers the
integer map.  Blocks are independent (grid "parallel"), so the serial chain
shrinks from L to L/blocks composed in log-depth outside — the Ladner–Fischer
prefix idea [26] made MXU-native, hybridized with the paper's speculation:
ops.py picks gather vs MXU by the roofline crossover (S lanes vs Q^2 flops).

VMEM: acc [Q, Q] bf16 + one P_c tile; Q <= 256 fits comfortably (256^2 * 2B *
2 = 256 KiB).  Larger Q falls back to the gather kernel.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["onehot_match_kernel", "onehot_block_maps_pallas", "build_pmats"]


def build_pmats(table: jnp.ndarray) -> jnp.ndarray:
    """Per-class one-hot transition matrices, flattened [n_cls * Q, Q] bf16."""
    q, n_cls = table.shape
    eye = jnp.eye(q, dtype=jnp.bfloat16)
    pmats = eye[table.T.astype(jnp.int32)]  # [n_cls, Q, Q]; row q = onehot(table[q,c])
    return pmats.reshape(n_cls * q, q)


def onehot_match_kernel(syms_ref, pmats_ref, out_ref, *, q: int):
    """One symbol-block: compose P matrices on the MXU, emit the int map.

    syms_ref  : [l_blk] int32 symbol classes of this block
    pmats_ref : [n_cls * Q, Q] bf16 one-hot transition matrices (whole, VMEM)
    out_ref   : [1, Q] int32 block map
    """
    syms = syms_ref[...]

    def body(l, acc):
        c = jax.lax.dynamic_slice_in_dim(syms, l, 1)[0]
        p_c = pmats_ref[pl.ds(c * q, q), :]  # dynamic-slice load [Q, Q]
        nxt = jnp.dot(acc, p_c, preferred_element_type=jnp.float32)
        return nxt.astype(jnp.bfloat16)

    acc = jax.lax.fori_loop(0, syms.shape[0], body, jnp.eye(q, dtype=jnp.bfloat16))
    out_ref[...] = jnp.argmax(acc, axis=1).astype(jnp.int32)[None, :]


@functools.partial(jax.jit, static_argnames=("l_blk", "interpret"))
def onehot_block_maps_pallas(table: jnp.ndarray, symbols: jnp.ndarray, *,
                             l_blk: int = 256,
                             interpret: bool | None = None) -> jnp.ndarray:
    """Pallas-backed equivalent of ``ref.onehot_block_maps_ref``.

    table [Q, n_cls] int32, symbols [L] int32 with L % l_blk == 0.
    Returns [L / l_blk, Q] int32 block maps (compose with lvec_compose).
    """
    q, n_cls = table.shape
    (l,) = symbols.shape
    assert l % l_blk == 0, (l, l_blk)
    pmats = build_pmats(table)
    kernel = functools.partial(onehot_match_kernel, q=q)
    from .ops import _interpret  # deferred: ops imports this module
    return pl.pallas_call(
        kernel,
        grid=(l // l_blk,),
        in_specs=[
            pl.BlockSpec((l_blk,), lambda b: (b,)),
            pl.BlockSpec((n_cls * q, q), lambda b: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, q), lambda b: (b, 0)),
        out_shape=jax.ShapeDtypeStruct((l // l_blk, q), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=_interpret(interpret),
    )(symbols.astype(jnp.int32), pmats)
