"""Public jit'd wrappers around the Pallas kernels.

Each op pads/blocks its inputs to kernel-legal shapes, dispatches to the
Pallas kernel (interpret mode off-TPU, compiled on TPU: ``_interpret``), and
exposes the same semantics as its ``ref.py`` oracle.  ``spec_match``
additionally implements the gather-vs-MXU crossover (DESIGN.md §2,
beyond-paper): wide speculation (S approaching Q) on small-Q DFAs is
cheaper as one-hot matmuls with log-depth composition than as an L-deep
serial gather chain.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from . import ref
from .dfa_match import (spec_match_merge_lanes_pallas,
                        spec_match_merge_pallas, spec_match_pallas)
from .flash_attn import flash_attn_pallas
from .lvec_compose import (lvec_compose_pallas, spec_compose_lanes_pallas,
                           spec_compose_lanes_tree_pallas)
from .onehot_match import onehot_block_maps_pallas
from .token_mask import token_mask_pallas

__all__ = ["on_tpu", "spec_match", "spec_match_merge",
           "spec_match_merge_lanes", "spec_compose_lanes", "lvec_compose",
           "onehot_block_maps", "token_mask", "mxu_profitable", "flash_attn"]


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _interpret(interpret: bool | None = None) -> bool:
    """Resolve a kernel's ``interpret`` option: ``None`` interprets off the
    TPU and compiles on it.  Every raw ``*_pallas`` wrapper defaults to
    ``None`` and resolves here, so no caller runs the interpreter on a TPU
    without asking for it."""
    return not on_tpu() if interpret is None else bool(interpret)


def _pick_block(n: int, target: int) -> int:
    """Largest divisor of n that is <= target (>=1)."""
    best = 1
    for d in range(1, int(math.isqrt(n)) + 1):
        if n % d == 0:
            for cand in (d, n // d):
                if cand <= target and cand > best:
                    best = cand
    return best


def _pad_to_block(n: int, target: int) -> tuple[int, int]:
    """Block size and padded extent for a length-``n`` axis.

    Returns ``(block, n_padded)`` with ``block = min(n, target)`` and
    ``n_padded`` the next multiple of ``block``.  This replaces the old
    exact-divisor search (``_pick_block``), which degenerated to block size
    1 for prime/odd ``n`` — turning the kernels into symbol-at-a-time grids.
    Callers pad the axis with identity-class symbols (or identity maps), so
    the extra tail is a semantic no-op.
    """
    blk = max(1, min(n, target))
    return blk, n + (-n) % blk


def _identity_padded_table(table: jnp.ndarray) -> tuple[jnp.ndarray, int]:
    """Append an identity class column (state q maps to itself).

    Raw transition tables have no reserved padding class; this returns a
    widened table plus the new class index, giving padded symbols a sound
    no-op transition.  (Packed ``table_pad`` variants already carry an
    identity ``pad_cls`` column, so they never need this.)
    """
    q = table.shape[0]
    ident = jnp.arange(q, dtype=table.dtype)[:, None]
    return jnp.concatenate([table, ident], axis=1), table.shape[1]


def mxu_profitable(q: int, s: int, *, vpu_lanes: int = 1024,
                   mxu_dim: int = 128) -> bool:
    """Roofline crossover for gather vs one-hot-matmul matching.

    Gather path: per symbol, ceil(S / vpu_lanes) VPU gather steps.
    MXU path: per symbol, (Q/128)^2 MXU issue slots, but removes the L-deep
    serial chain (blocks compose in log depth).  Profitable when the DFA is
    small enough that a [Q, Q] matmul costs about one issue slot and the
    speculation is wide (S close to Q) — i.e. gamma ~ 1 DFAs, where the
    paper's lookahead optimization helps least.  Heuristic, tuned in §Perf.
    """
    return q <= mxu_dim * 2 and s >= q // 2 and s > vpu_lanes // mxu_dim


def spec_match(table: jnp.ndarray, chunks: jnp.ndarray,
               init_states: jnp.ndarray, *, use_mxu: bool | None = None,
               interpret: bool | None = None) -> jnp.ndarray:
    """Match [C] chunks x [S] lanes; semantics of ``ref.spec_match_ref``."""
    c, l = chunks.shape
    q = table.shape[0]
    s = init_states.shape[1]
    if use_mxu is None:
        use_mxu = mxu_profitable(q, s)
    if use_mxu:
        l_blk, l_pad = _pad_to_block(l, 256)
        if l_pad != l:
            table, id_cls = _identity_padded_table(table)
            chunks = jnp.pad(chunks, ((0, 0), (0, l_pad - l)),
                             constant_values=id_cls)
        def per_chunk(syms):
            maps = onehot_block_maps_pallas(table, syms, l_blk=l_blk,
                                            interpret=interpret)
            full = lvec_compose(maps, interpret=interpret)  # [Q]
            return full
        full_maps = jax.vmap(per_chunk)(chunks)             # [C, Q]
        return jnp.take_along_axis(full_maps, init_states.astype(jnp.int32), axis=1)
    c_blk, c_pad = _pad_to_block(c, 8)
    l_blk, l_pad = _pad_to_block(l, 512)
    if (c_pad, l_pad) != (c, l):
        table, id_cls = _identity_padded_table(table)
        chunks = jnp.pad(chunks, ((0, c_pad - c), (0, l_pad - l)),
                         constant_values=id_cls)
        init_states = jnp.pad(init_states, ((0, c_pad - c), (0, 0)))
        return spec_match_pallas(table, chunks, init_states, c_blk=c_blk,
                                 l_blk=l_blk, interpret=interpret)[:c]
    return spec_match_pallas(table, chunks, init_states, c_blk=c_blk,
                             l_blk=l_blk, interpret=interpret)


def _pad_merge_chunks(chunks: jnp.ndarray, pad_cls: int,
                      l_blk_target: int) -> tuple[jnp.ndarray, int]:
    """Pad the symbol axis of [B, C, L] chunks with the identity pad class."""
    l = chunks.shape[-1]
    l_blk, l_pad = _pad_to_block(l, l_blk_target)
    if l_pad != l:
        chunks = jnp.pad(chunks, ((0, 0), (0, 0), (0, l_pad - l)),
                         constant_values=pad_cls)
    return chunks, l_blk


def spec_match_merge(table: jnp.ndarray, chunks: jnp.ndarray,
                     init_states: jnp.ndarray, lookahead: jnp.ndarray,
                     cand_index: jnp.ndarray, sinks: jnp.ndarray,
                     absorbing: jnp.ndarray, *, pad_cls: int,
                     pad_key: int | None = None, early_exit: bool = True,
                     l_blk: int = 512, interpret: bool | None = None
                     ) -> tuple[jnp.ndarray, jnp.ndarray, int]:
    """Fused batch classify-stream match + merge; see ``ref.spec_match_merge_ref``.

    One kernel launch covers a whole document bucket: grid over documents,
    Eq. 8 merge fused into the last symbol block, output [B, K] finals only.
    ``table`` must be the padded packed table (identity ``pad_cls`` column);
    L is padded with ``pad_cls`` symbols up to the block multiple.
    ``pad_key`` is the merge fold's passthrough boundary key — it equals
    ``pad_cls`` for r=1 lookahead tables (the default) but is ``n_classes**2``
    under r=2 pair keys.  Returns ``(finals [B, K], skipped [B], l_blk)`` —
    per-document symbol blocks skipped by the in-kernel all-absorbed early
    exit, and the block size the lowering needs to convert that count into an
    exit position.
    """
    pad_key = pad_cls if pad_key is None else pad_key
    chunks, l_blk = _pad_merge_chunks(chunks, pad_cls, l_blk)
    out, skipped = spec_match_merge_pallas(
        table, chunks, init_states, lookahead, cand_index, sinks, absorbing,
        pad_cls=pad_key, l_blk=l_blk, early_exit=early_exit,
        interpret=interpret)
    return out, skipped, l_blk


def spec_match_merge_lanes(table: jnp.ndarray, chunks: jnp.ndarray,
                           init_states: jnp.ndarray, lookahead: jnp.ndarray,
                           cand_index: jnp.ndarray, sinks: jnp.ndarray,
                           absorbing: jnp.ndarray, *, pad_cls: int,
                           pad_key: int | None = None,
                           early_exit: bool = True, l_blk: int = 512,
                           interpret: bool | None = None
                           ) -> tuple[jnp.ndarray, jnp.ndarray, int]:
    """Fused lane-carrying match + merge; see ``ref.spec_match_merge_lanes_ref``.

    The streaming-tick variant: the full [K, S] candidate lane axis survives
    the in-kernel Eq. 8 fold, so the output is each document's restricted
    transition map rather than a single final per pattern.  Returns
    ``(lanes [B, K, S], skipped [B], l_blk)``.  ``pad_key`` as in
    ``spec_match_merge``.
    """
    pad_key = pad_cls if pad_key is None else pad_key
    chunks, l_blk = _pad_merge_chunks(chunks, pad_cls, l_blk)
    out, skipped = spec_match_merge_lanes_pallas(
        table, chunks, init_states, lookahead, cand_index, sinks, absorbing,
        pad_cls=pad_key, l_blk=l_blk, early_exit=early_exit,
        interpret=interpret)
    k = sinks.shape[0]
    return out.reshape(out.shape[0], k, -1), skipped, l_blk


def spec_compose_lanes(lane_maps: jnp.ndarray, entry_keys: jnp.ndarray,
                       cand_index: jnp.ndarray, sinks: jnp.ndarray, *,
                       pad_key: int, mode: str = "carry", n_blk: int = 8,
                       interpret: bool | None = None) -> jnp.ndarray:
    """Fold [B, N, K, S] keyed lane-map runs in one kernel launch.

    The OOO gap-close compose (``Matcher.compose_lane_maps``): per batch
    element, element 0's lanes seed the carry and elements 1..N-1 fold in
    keyed by ``entry_keys`` (``pad_key`` elements are identities, so ragged
    runs arrive right-padded).  ``mode="carry"`` rides the block-sequential
    grid-carry kernel (N padded to an ``n_blk`` multiple); ``mode="tree"``
    rides the in-kernel Blelloch reduce (N padded to a power of two).
    Returns the final composition [B, K, S]; semantics of
    ``ref.spec_compose_lanes_ref`` == ``spec_merge_lanes_scan_ref[:, -1]``.

    Contract caveat: the combine is associative on *real* candidate lanes
    (the only lanes ``cand_index`` can ever select for a consumer), where
    every lowering is bit-identical.  Pad lanes — filler states a key's
    candidate row repeats to reach width S — pass through the acc-fallback
    and so carry evaluation-order-dependent values: sequential ``"carry"``
    matches the oracle everywhere, ``"tree"`` may differ from it on pad
    lanes only.  No decision path reads a pad lane.
    """
    b, n, k, s = lane_maps.shape
    assert n >= 1, "empty runs are the caller's fast path"
    if mode == "tree":
        n_pad = 1 << max(0, n - 1).bit_length() if n > 1 else 1
        if n_pad != n:
            lane_maps = jnp.pad(lane_maps,
                                ((0, 0), (0, n_pad - n), (0, 0), (0, 0)))
            entry_keys = jnp.pad(entry_keys, ((0, 0), (0, n_pad - n)),
                                 constant_values=pad_key)
        return spec_compose_lanes_tree_pallas(
            lane_maps, entry_keys, cand_index, sinks, pad_key=pad_key,
            interpret=interpret)
    if mode != "carry":
        raise ValueError(f"unknown compose mode {mode!r}")
    n_blk, n_pad = _pad_to_block(n, n_blk)
    if n_pad != n:  # pad_key tail elements compose as identities
        lane_maps = jnp.pad(lane_maps,
                            ((0, 0), (0, n_pad - n), (0, 0), (0, 0)))
        entry_keys = jnp.pad(entry_keys, ((0, 0), (0, n_pad - n)),
                             constant_values=pad_key)
    return spec_compose_lanes_pallas(
        lane_maps, entry_keys, cand_index, sinks, pad_key=pad_key,
        n_blk=n_blk, interpret=interpret)


def lvec_compose(maps: jnp.ndarray, *, interpret: bool | None = None) -> jnp.ndarray:
    """Compose [C, Q] maps left-to-right -> [Q]; see ``ref.lvec_compose_ref``."""
    c, q = maps.shape
    c_blk, c_pad = _pad_to_block(c, 8)
    if c_pad != c:  # identity maps compose as no-ops
        ident = jnp.broadcast_to(jnp.arange(q, dtype=maps.dtype),
                                 (c_pad - c, q))
        maps = jnp.concatenate([maps, ident], axis=0)
    return lvec_compose_pallas(maps, c_blk=c_blk, interpret=interpret)


def onehot_block_maps(table: jnp.ndarray, symbols: jnp.ndarray, *,
                      block_l: int = 256,
                      interpret: bool | None = None) -> jnp.ndarray:
    """Block maps via the MXU formulation; see ``ref.onehot_block_maps_ref``.

    Non-multiple L is padded with an appended identity class, so any extra
    trailing block maps are identity permutations (no-ops under
    composition).
    """
    l = symbols.shape[0]
    l_blk, l_pad = _pad_to_block(l, block_l)
    if l_pad != l:
        table, id_cls = _identity_padded_table(table)
        symbols = jnp.pad(symbols, (0, l_pad - l), constant_values=id_cls)
    return onehot_block_maps_pallas(table, symbols, l_blk=l_blk,
                                    interpret=interpret)


def token_mask(states: jnp.ndarray, allowed: jnp.ndarray, logits: jnp.ndarray,
               *, neg: float = -1e30,
               interpret: bool | None = None) -> jnp.ndarray:
    """Fused grammar mask; see ``ref.token_mask_ref``.  Pads V to the tile."""
    b, v = logits.shape
    v_blk, v_pad = _pad_to_block(v, 2048)
    if v_pad != v:  # ragged vocab: pad to the tile boundary (masked -> neg)
        logits_p = jnp.pad(logits, ((0, 0), (0, v_pad - v)))
        allowed_p = jnp.pad(allowed.astype(jnp.uint8),
                            ((0, 0), (0, v_pad - v)))
        out = token_mask_pallas(states, allowed_p, logits_p, v_blk=v_blk,
                                neg=neg, interpret=interpret)
        return out[:, :v]
    return token_mask_pallas(states, allowed, logits, v_blk=v_blk, neg=neg,
                             interpret=interpret)


def flash_attn(q, k, v, *, causal: bool = True, window: int = 0,
               q_blk: int = 256, kv_blk: int = 256,
               interpret: bool | None = None):
    """Fused flash-attention forward; see ``ref.flash_attn_ref``.

    The TPU deployment path for the attention memory bottleneck identified in
    EXPERIMENTS.md §Perf (tiles stay in VMEM).  The XLA path
    (models.attention_core.flash_attention) remains the autodiff/dry-run path.
    """
    t, st = q.shape[1], k.shape[1]
    return flash_attn_pallas(q, k, v, q_blk=_pick_block(t, q_blk),
                             kv_blk=_pick_block(st, kv_blk), causal=causal,
                             window=window, interpret=interpret)
