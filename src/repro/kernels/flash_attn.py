"""Pallas TPU kernel: fused flash attention forward (causal/windowed, GQA).

§Perf identified the XLA-lowered attention tiles as the dominant memory term
of every train/prefill cell: XLA materializes each [q_blk, kv_blk] logit/prob
tile in HBM between fusions.  This kernel is the deployment fix — the online-
softmax recurrence runs entirely in VMEM (m/l/acc scratch carried across the
kv grid dimension), so HBM traffic drops to Q/K/V reads + O output writes:
arithmetic intensity rises from O(1) to O(block) — the same HBM->VMEM
blocking the paper's AVX2 gather loop applies to the DFA table.

Layout: heads are flattened into the leading grid dim (GQA expansion happens
in ops.py by indexing, not copying); grid = (BH, nq, ns) with the kv dim
sequential ("arbitrary") and scratch carries per (head, q-block).  Causal /
window masks are applied in-tile from program ids; fully-dead tiles are
skipped with ``pl.when`` (the valid-pair pruning of §Perf iteration 1b,
expressed at kernel level).

Forward-only: the backward runs the XLA path (remat recomputes through this
kernel on TPU).  Validated against models.attention_core.direct_attention in
interpret mode.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attn_kernel", "flash_attn_pallas"]

NEG = -1e30


def flash_attn_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                      q_blk: int, kv_blk: int, ns: int, causal: bool,
                      window: int, scale: float):
    """One (head, q-block, kv-block) grid step.

    q_ref [1, q_blk, D]; k_ref/v_ref [1, kv_blk, D]; o_ref [1, q_blk, D];
    scratch: m/l [q_blk], acc [q_blk, D] — carried across the kv dimension.
    """
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_lo = i * q_blk
    k_lo = j * kv_blk
    # static-shape positions; block-level liveness decided per step
    live = True
    if causal:
        live = k_lo <= q_lo + q_blk - 1
    if window > 0:
        live = jnp.logical_and(live, k_lo + kv_blk - 1 > q_lo - window)

    @pl.when(live)
    def _tile():
        q = q_ref[0]                       # [q_blk, D]
        k = k_ref[0]                       # [kv_blk, D]
        v = v_ref[0]
        logit = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        q_pos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (q_blk, kv_blk), 0)
        k_pos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (q_blk, kv_blk), 1)
        ok = jnp.ones((q_blk, kv_blk), jnp.bool_)
        if causal:
            ok = jnp.logical_and(ok, k_pos <= q_pos)
        if window > 0:
            ok = jnp.logical_and(ok, k_pos > q_pos - window)
        logit = jnp.where(ok, logit, NEG)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, logit.max(axis=-1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(logit - m_new[:, None]).astype(q.dtype)   # bf16 tile, VMEM
        l_ref[...] = l_ref[...] * alpha + p.astype(jnp.float32).sum(axis=-1)
        pv = jnp.dot(p, v, preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + pv
        m_ref[...] = m_new

    @pl.when(j == ns - 1)
    def _done():
        denom = jnp.maximum(l_ref[...], 1e-30)[:, None]
        o_ref[...] = (acc_ref[...] / denom).astype(o_ref.dtype)[None]


@functools.partial(jax.jit, static_argnames=("q_blk", "kv_blk", "causal",
                                             "window", "interpret"))
def flash_attn_pallas(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                      q_blk: int = 256, kv_blk: int = 256,
                      causal: bool = True, window: int = 0,
                      interpret: bool | None = None) -> jnp.ndarray:
    """q [BH, T, D]; k, v [BH, S, D] -> out [BH, T, D].

    BH = batch x heads (GQA callers index k/v per group before the call).
    T % q_blk == 0 and S % kv_blk == 0 (ops-level padding as usual).
    """
    bh, t, d = q.shape
    s = k.shape[1]
    q_blk = min(q_blk, t)
    kv_blk = min(kv_blk, s)
    assert t % q_blk == 0 and s % kv_blk == 0, (t, s, q_blk, kv_blk)
    nq, ns = t // q_blk, s // kv_blk
    kernel = functools.partial(
        flash_attn_kernel, q_blk=q_blk, kv_blk=kv_blk, ns=ns, causal=causal,
        window=window, scale=d ** -0.5)
    from .ops import _interpret  # deferred: ops imports this module
    return pl.pallas_call(
        kernel,
        grid=(bh, nq, ns),
        in_specs=[
            pl.BlockSpec((1, q_blk, d), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, kv_blk, d), lambda h, i, j: (h, j, 0)),
            pl.BlockSpec((1, kv_blk, d), lambda h, i, j: (h, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, q_blk, d), lambda h, i, j: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, t, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((q_blk,), jnp.float32),
            pltpu.VMEM((q_blk,), jnp.float32),
            pltpu.VMEM((q_blk, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_interpret(interpret),
    )(q, k, v)
