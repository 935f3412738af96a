"""Pallas TPU kernel: vectorized speculative DFA chunk matching.

TPU adaptation of the paper's AVX2 gather loop (Listing 2).  Design:

  * The flattened transition table (the paper's ``SBase``, with next-state
    values *pre-scaled* by n_classes so the hot loop is add+gather, Listing 1)
    is pinned whole in **VMEM** — grammar/scan DFAs are small (Q·n_cls·4B;
    1288 states x 32 classes = 165 KiB, far under the ~16 MiB working-set
    budget in DESIGN.md §2.1).
  * Lanes = chunks x speculative candidate states.  AVX2 gave the paper 8
    lanes; one TPU core's VPU is 8x128 int32 lanes, so a (8, 128) block of
    (chunk, state-lane) pairs advances per step.
  * The symbol dimension is a sequential recurrence, so it rides the grid's
    trailing ("arbitrary") dimension with the state carried in VMEM scratch;
    chunk blocks ride the leading ("parallel") dimension.

Grid: ``(C / c_blk, L / l_blk)``; BlockSpecs stream symbol blocks HBM->VMEM
while the carry stays resident.  Correctness is validated against
``ref.spec_match_ref`` in interpret mode.  Mosaic (the TPU compiler) refuses
these kernels as written: the 1-D ``jnp.take`` from the flat table is not a
supported gather (2-D gathers must stay inside one 8x128 source tile), the
value-level ``dynamic_slice`` is unimplemented, and the ``(1, c)`` /
``(1, 1)`` blocks are not (8, 128)-tiled (tests/test_chip_compile.py keeps
each refusal as a strict xfail).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["spec_match_kernel", "spec_match_pallas",
           "spec_match_merge_kernel", "spec_match_merge_pallas",
           "spec_match_merge_lanes_kernel", "spec_match_merge_lanes_pallas"]


def spec_match_kernel(table_ref, chunks_ref, init_ref, out_ref, carry_ref, *,
                      n_classes: int, l_blocks: int):
    """One (chunk-block, symbol-block) grid step.

    table_ref : [Q * n_classes] int32, pre-scaled flat table (VMEM, whole)
    chunks_ref: [c_blk, l_blk] int32 symbol classes for this block
    init_ref  : [c_blk, S] int32 candidate initial states
    out_ref   : [c_blk, S] int32 final states (written on the last l-block)
    carry_ref : [c_blk, S] int32 VMEM scratch carrying pre-scaled states
    """
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        carry_ref[...] = init_ref[...] * n_classes

    table = table_ref[...]            # resident VMEM vector [Q * n_classes]
    syms = chunks_ref[...]            # [c_blk, l_blk]
    states = carry_ref[...]           # [c_blk, S] pre-scaled

    def body(l, states):
        # idx = state * n_classes + class  (the paper's 1-D SBase lookup);
        # values are already pre-scaled so no multiply in the loop.
        idx = states + jax.lax.dynamic_slice_in_dim(syms, l, 1, axis=1)
        return jnp.take(table, idx, axis=0)

    states = jax.lax.fori_loop(0, syms.shape[1], body, states)
    carry_ref[...] = states

    @pl.when(j == l_blocks - 1)
    def _done():
        out_ref[...] = carry_ref[...] // n_classes


@functools.partial(jax.jit, static_argnames=("c_blk", "l_blk", "interpret"))
def spec_match_pallas(table: jnp.ndarray, chunks: jnp.ndarray,
                      init_states: jnp.ndarray, *, c_blk: int = 8,
                      l_blk: int = 512,
                      interpret: bool | None = None) -> jnp.ndarray:
    """Pallas-backed equivalent of ``ref.spec_match_ref``.

    table [Q, n_cls] int32; chunks [C, L]; init_states [C, S].
    C must divide by c_blk and L by l_blk (ops.py pads/chooses blocks).
    """
    q, n_cls = table.shape
    c, l = chunks.shape
    s = init_states.shape[1]
    assert c % c_blk == 0 and l % l_blk == 0, (c, l, c_blk, l_blk)
    flat = (table.astype(jnp.int32) * n_cls).reshape(-1)  # pre-scaled SBase
    l_blocks = l // l_blk

    kernel = functools.partial(spec_match_kernel, n_classes=n_cls,
                               l_blocks=l_blocks)
    from .ops import _interpret  # deferred: ops imports this module
    return pl.pallas_call(
        kernel,
        grid=(c // c_blk, l_blocks),
        in_specs=[
            pl.BlockSpec((q * n_cls,), lambda i, j: (0,)),       # whole table
            pl.BlockSpec((c_blk, l_blk), lambda i, j: (i, j)),   # symbol block
            pl.BlockSpec((c_blk, s), lambda i, j: (i, 0)),       # init states
        ],
        out_specs=pl.BlockSpec((c_blk, s), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((c, s), jnp.int32),
        scratch_shapes=[pltpu.VMEM((c_blk, s), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=_interpret(interpret),
    )(flat, chunks.astype(jnp.int32), init_states.astype(jnp.int32))


# --------------------------------------------------------------------------
# Batched multi-pattern kernel: grid over documents, merge fused in-kernel
# --------------------------------------------------------------------------

def _scan_block_with_exit(table_ref, chunks_ref, init_ref, absorb_ref,
                          skip_ref, carry_ref, done_ref, *, n_cls_pad: int,
                          early_exit: bool):
    """Shared symbol-block scan of the fused merge kernels, with the
    in-flight all-absorbed early exit.

    The per-document done flag lives in SMEM scratch and is read *before*
    the block body, so the block that discovers the condition still runs and
    every later grid step along the "arbitrary" dimension is a no-op (the
    skipped-step counter accumulates into ``skip_ref``).  Freezing the carry
    is bit-exact: absorbing states self-loop on every class including the
    identity pad column, so the remaining symbol blocks could not have moved
    any lane.  The probe itself is one [C, K*S] gather + reduction per block
    — amortized over ``l_blk`` symbol steps.
    """
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        carry_ref[...] = init_ref[0] * n_cls_pad
        skip_ref[0, 0] = 0
        done_ref[0] = 0

    live = done_ref[0] == 0

    @pl.when(live)
    def _scan():
        table = table_ref[...]
        syms = chunks_ref[0]          # [C, l_blk]
        states = carry_ref[...]       # [C, K * S] pre-scaled

        def body(l, states):
            # idx = state * n_cls_pad + class (the paper's 1-D SBase lookup)
            idx = states + jax.lax.dynamic_slice_in_dim(syms, l, 1, axis=1)
            return jnp.take(table, idx, axis=0)

        states = jax.lax.fori_loop(0, syms.shape[1], body, states)
        carry_ref[...] = states
        if early_exit:
            absorbed = jnp.take(absorb_ref[...], states // n_cls_pad, axis=0)
            done_ref[0] = absorbed.all().astype(jnp.int32)

    @pl.when(jnp.logical_not(live))
    def _skip():
        skip_ref[0, 0] = skip_ref[0, 0] + 1


def spec_match_merge_kernel(table_ref, chunks_ref, init_ref, la_ref, cidx_ref,
                            sinks_ref, absorb_ref, out_ref, skip_ref,
                            carry_ref, done_ref, *, n_cls_pad: int,
                            l_blocks: int, n_patterns: int, pad_cls: int,
                            early_exit: bool):
    """One (document, symbol-block) grid step of the fused batch pipeline.

    table_ref : [Q_total * n_cls_pad] int32 pre-scaled flat packed table (VMEM)
    chunks_ref: [1, C, l_blk] int32 joint classes for this doc/symbol block
    init_ref  : [1, C, K * S] int32 candidate initial packed states.  Chunk
                0's lanes are *exact* entry states and its merge reads lane
                0 — the pattern starts for whole documents, or a streaming
                cursor's resumed states (the ``LanePlan`` entry-seed stage,
                ``engine.executors.LaneExecutor._seed_chunk0``; the kernel
                is agnostic to which, by construction).
    la_ref    : [1, C] int32 per-chunk boundary key (entry 0 unused)
    cidx_ref  : [n_keys_pad, Q_total] int32 candidate-lane index (VMEM, whole)
    sinks_ref : [K] int32 packed sink per pattern (-1 if none)
    absorb_ref: [Q_total] int32 absorbing-state flags (the early-exit probe)
    out_ref   : [1, K] int32 final packed state per pattern (last block only)
    skip_ref  : [1, 1] int32 symbol blocks skipped by the in-kernel exit
    carry_ref : [C, K * S] int32 VMEM scratch carrying pre-scaled states
    done_ref  : [1] int32 SMEM scratch — the per-document all-absorbed flag

    The Eq. 8 fold over chunks runs *inside* the kernel on the final symbol
    block, so one grid pass emits the per-document answer — no host-driven
    ``lax.scan`` over chunk L-vectors and no intermediate [B, C, S] output.
    With ``early_exit`` the symbol-block body is guarded on the SMEM done
    flag (``_scan_block_with_exit``): once every lane of the document sits
    in an absorbing state, the remaining grid steps along the "arbitrary"
    dimension only bump the skipped counter.  The merge still runs on the
    last block, reading the frozen (exact) carry.
    """
    _scan_block_with_exit(table_ref, chunks_ref, init_ref, absorb_ref,
                          skip_ref, carry_ref, done_ref, n_cls_pad=n_cls_pad,
                          early_exit=early_exit)

    @pl.when(pl.program_id(1) == l_blocks - 1)
    def _merge():
        states = carry_ref[...]
        c = states.shape[0]
        lv = (states // n_cls_pad).reshape(c, n_patterns, -1)
        la = la_ref[0]
        cidx = cidx_ref[...]
        sinks = sinks_ref[...]

        def fold(i, s):  # s [K] packed states
            la_i = jax.lax.dynamic_index_in_dim(la, i, 0, keepdims=False)
            lv_i = jax.lax.dynamic_index_in_dim(lv, i, 0, keepdims=False)
            lane = jnp.take(jnp.take(cidx, la_i, axis=0), s)
            hit = jnp.take_along_axis(
                lv_i, jnp.maximum(lane, 0)[:, None], axis=1)[:, 0]
            nxt = jnp.where(lane < 0, jnp.where(sinks >= 0, sinks, s), hit)
            nxt = jnp.where(la_i == pad_cls, s, nxt)
            return nxt.astype(jnp.int32)

        out_ref[0, :] = jax.lax.fori_loop(1, c, fold, lv[0, :, 0])


def spec_match_merge_lanes_kernel(table_ref, chunks_ref, init_ref, la_ref,
                                  cidx_ref, sinks_ref, absorb_ref, out_ref,
                                  skip_ref, carry_ref, done_ref, *,
                                  n_cls_pad: int, l_blocks: int,
                                  n_patterns: int, pad_cls: int,
                                  early_exit: bool):
    """Lane-carrying variant of ``spec_match_merge_kernel`` (streaming tick).

    Same operands and scan, but chunk 0's lanes are the Eq. 11 candidate
    entries of each document's boundary key — not an exact state — and the
    in-kernel Eq. 8 fold keeps the full ``[K, S]`` carry, composing later
    chunks lane-for-lane (``ref.spec_merge_lanes_ref`` semantics).
    ``out_ref [1, K * S]`` is the document's restricted transition map; the
    lowering composes it with the caller's cursor lanes in one tiny jnp op
    (``LaneExecutor._compose_cursor``).  This is what puts
    ``Matcher.advance_cursors`` — the streaming hot path — on the fused
    kernel instead of staged jnp.
    """
    _scan_block_with_exit(table_ref, chunks_ref, init_ref, absorb_ref,
                          skip_ref, carry_ref, done_ref, n_cls_pad=n_cls_pad,
                          early_exit=early_exit)

    @pl.when(pl.program_id(1) == l_blocks - 1)
    def _merge():
        states = carry_ref[...]
        c = states.shape[0]
        s = states.shape[1] // n_patterns
        lv = (states // n_cls_pad).reshape(c, n_patterns, s)
        la = la_ref[0]
        cidx = cidx_ref[...]
        sinks = sinks_ref[...]

        def fold(i, st):  # st [K, S] carried lane states
            la_i = jax.lax.dynamic_index_in_dim(la, i, 0, keepdims=False)
            lv_i = jax.lax.dynamic_index_in_dim(lv, i, 0, keepdims=False)
            lane = jnp.take(jnp.take(cidx, la_i, axis=0), st)      # [K, S]
            hit = jnp.take_along_axis(lv_i, jnp.maximum(lane, 0), axis=1)
            sk = sinks[:, None]
            nxt = jnp.where(lane < 0, jnp.where(sk >= 0, sk, st), hit)
            nxt = jnp.where(la_i == pad_cls, st, nxt)
            return nxt.astype(jnp.int32)

        out_ref[0, :] = jax.lax.fori_loop(1, c, fold, lv[0]).reshape(-1)


def _merge_pallas_call(kernel_fn, table, chunks, init_states, lookahead,
                       cand_index, sinks, absorbing, *, pad_cls, l_blk,
                       out_width, early_exit, interpret):
    """Shared ``pallas_call`` plumbing of the two fused merge kernels."""
    q, n_cls_pad = table.shape
    b, c, l = chunks.shape
    s_tot = init_states.shape[-1]
    k = sinks.shape[0]
    n_keys_pad = cand_index.shape[0]
    assert l % l_blk == 0, (l, l_blk)
    flat = (table.astype(jnp.int32) * n_cls_pad).reshape(-1)
    l_blocks = l // l_blk

    kernel = functools.partial(kernel_fn, n_cls_pad=n_cls_pad,
                               l_blocks=l_blocks, n_patterns=k,
                               pad_cls=pad_cls, early_exit=early_exit)
    from .ops import _interpret  # deferred: ops imports this module
    out, skipped = pl.pallas_call(
        kernel,
        grid=(b, l_blocks),
        in_specs=[
            pl.BlockSpec((q * n_cls_pad,), lambda i, j: (0,)),     # flat table
            pl.BlockSpec((1, c, l_blk), lambda i, j: (i, 0, j)),   # symbols
            pl.BlockSpec((1, c, s_tot), lambda i, j: (i, 0, 0)),   # init lanes
            pl.BlockSpec((1, c), lambda i, j: (i, 0)),             # lookahead
            pl.BlockSpec((n_keys_pad, q), lambda i, j: (0, 0)),    # cand index
            pl.BlockSpec((k,), lambda i, j: (0,)),                 # sinks
            pl.BlockSpec((q,), lambda i, j: (0,)),                 # absorbing
        ],
        out_specs=[pl.BlockSpec((1, out_width), lambda i, j: (i, 0)),
                   pl.BlockSpec((1, 1), lambda i, j: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((b, out_width), jnp.int32),
                   jax.ShapeDtypeStruct((b, 1), jnp.int32)],
        scratch_shapes=[pltpu.VMEM((c, s_tot), jnp.int32),
                        pltpu.SMEM((1,), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=_interpret(interpret),
    )(flat, chunks.astype(jnp.int32), init_states.astype(jnp.int32),
      lookahead.astype(jnp.int32), cand_index.astype(jnp.int32),
      sinks.astype(jnp.int32), absorbing.astype(jnp.int32))
    return out, skipped[:, 0]


@functools.partial(jax.jit, static_argnames=("pad_cls", "l_blk", "early_exit",
                                             "interpret"))
def spec_match_merge_pallas(table: jnp.ndarray, chunks: jnp.ndarray,
                            init_states: jnp.ndarray, lookahead: jnp.ndarray,
                            cand_index: jnp.ndarray, sinks: jnp.ndarray,
                            absorbing: jnp.ndarray, *, pad_cls: int,
                            l_blk: int = 512, early_exit: bool = True,
                            interpret: bool | None = None
                            ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Pallas-backed equivalent of ``ref.spec_match_merge_ref``.

    table [Q_total, n_cls_pad] (identity pad column included); chunks
    [B, C, L]; init_states [B, C, K*S]; lookahead [B, C] boundary keys;
    cand_index [n_keys_pad, Q_total]; sinks [K]; absorbing [Q_total].
    L must divide by l_blk (ops.py pads/picks the block).  Grid:
    (B, L / l_blk) — documents ride the parallel grid dimension, the symbol
    recurrence rides the arbitrary one.  Returns ``(finals [B, K],
    skipped [B])`` — symbol blocks skipped per document by the in-kernel
    all-absorbed early exit (0 when ``early_exit=False``).
    """
    return _merge_pallas_call(spec_match_merge_kernel, table, chunks,
                              init_states, lookahead, cand_index, sinks,
                              absorbing, pad_cls=pad_cls, l_blk=l_blk,
                              out_width=sinks.shape[0],
                              early_exit=early_exit, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("pad_cls", "l_blk", "early_exit",
                                             "interpret"))
def spec_match_merge_lanes_pallas(table: jnp.ndarray, chunks: jnp.ndarray,
                                  init_states: jnp.ndarray,
                                  lookahead: jnp.ndarray,
                                  cand_index: jnp.ndarray, sinks: jnp.ndarray,
                                  absorbing: jnp.ndarray, *, pad_cls: int,
                                  l_blk: int = 512, early_exit: bool = True,
                                  interpret: bool | None = None
                                  ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Pallas-backed equivalent of ``ref.spec_match_merge_lanes_ref``.

    Same operands as ``spec_match_merge_pallas`` but the output keeps the
    candidate lane axis: ``(lanes [B, K * S], skipped [B])`` — each
    document's restricted transition map under every Eq. 11 candidate entry
    of its boundary key.
    """
    return _merge_pallas_call(spec_match_merge_lanes_kernel, table, chunks,
                              init_states, lookahead, cand_index, sinks,
                              absorbing, pad_cls=pad_cls, l_blk=l_blk,
                              out_width=init_states.shape[-1],
                              early_exit=early_exit, interpret=interpret)
