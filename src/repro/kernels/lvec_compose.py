"""Pallas TPU kernels: L-vector composition (paper Eq. 9 reduction leaf).

Two families live here:

* ``lvec_compose_*`` — the original full-map leaf: composes a block of
  [C, Q] state maps left-to-right (``acc <- m_i[acc]``, one VMEM gather per
  map).  This is the leaf reduction of the hierarchical 2-tier merge
  (DESIGN.md §2).

* ``spec_compose_lanes_*`` — the OOO gap-close fold: composes ragged-padded
  [N, K*S] candidate-keyed lane-map runs (``Matcher.compose_lane_maps``,
  the Eq. 9 monoid restricted to speculative candidate lanes with Eq. 13
  boundary keys).  Per batch element the combine is exactly
  ``core.lvector.merge_scan_lanes_jnp``'s: gather the carry states through
  the next element's candidate index, fall back to the per-pattern sink on
  a candidate miss, and pass the carry through unchanged under the
  ``pad_key`` identity.  Two lowerings, measured against each other in
  ``benchmarks --only ooo_throughput``:

  - block-sequential grid carry (``spec_compose_lanes_pallas``): grid
    (B, N/n_blk), the [K, S] carry lives in VMEM scratch across the
    sequential N dimension — O(N) combines but each is one VPU gather.
  - in-kernel Blelloch tree (``spec_compose_lanes_tree_pallas``): grid (B,),
    the whole pow2-padded run reduces pairwise in log2(N) unrolled levels.

The map dimension is sequential (grid "arbitrary"); carries live in
VMEM scratch.  Q / K*S ride the lane dimension (pad to 128 on hardware).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["lvec_compose_kernel", "lvec_compose_pallas",
           "spec_compose_lanes_kernel", "spec_compose_lanes_pallas",
           "spec_compose_lanes_tree_kernel",
           "spec_compose_lanes_tree_pallas"]


def lvec_compose_kernel(maps_ref, out_ref, carry_ref, *, c_blocks: int):
    """maps_ref [c_blk, Q]; carry/out [Q] — fold maps into the carry."""
    j = pl.program_id(0)

    @pl.when(j == 0)
    def _init():
        carry_ref[...] = jax.lax.broadcasted_iota(
            jnp.int32, (carry_ref.shape[0],), 0)

    maps = maps_ref[...]
    acc = carry_ref[...]

    def body(i, acc):
        row = jax.lax.dynamic_slice_in_dim(maps, i, 1, axis=0)[0]
        return jnp.take(row, acc, axis=0)

    acc = jax.lax.fori_loop(0, maps.shape[0], body, acc)
    carry_ref[...] = acc

    @pl.when(j == c_blocks - 1)
    def _done():
        out_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("c_blk", "interpret"))
def lvec_compose_pallas(maps: jnp.ndarray, *, c_blk: int = 8,
                        interpret: bool | None = None) -> jnp.ndarray:
    """Pallas-backed equivalent of ``ref.lvec_compose_ref``.

    maps [C, Q] int32 with C % c_blk == 0; returns the composed map [Q].
    """
    c, q = maps.shape
    assert c % c_blk == 0, (c, c_blk)
    c_blocks = c // c_blk
    kernel = functools.partial(lvec_compose_kernel, c_blocks=c_blocks)
    from .ops import _interpret  # deferred: ops imports this module
    return pl.pallas_call(
        kernel,
        grid=(c_blocks,),
        in_specs=[pl.BlockSpec((c_blk, q), lambda j: (j, 0))],
        out_specs=pl.BlockSpec((q,), lambda j: (0,)),
        out_shape=jax.ShapeDtypeStruct((q,), jnp.int32),
        scratch_shapes=[pltpu.VMEM((q,), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=_interpret(interpret),
    )(maps.astype(jnp.int32))


def spec_compose_lanes_kernel(lanes_ref, keys_ref, cidx_ref, sinks_ref,
                              out_ref, carry_ref, *, n_blocks: int,
                              pad_key: int):
    """Grid-carry fold of one doc's keyed lane-map run.

    lanes_ref [1, n_blk, K, S]; keys_ref [1, n_blk]; cidx_ref [n_keys+1, Q];
    sinks_ref [K]; out/carry [K, S].  Element 0 seeds the carry (its key is
    never read — the scan's first prefix IS its lanes); every later element
    folds in with the ``merge_scan_lanes_jnp`` combine.
    """
    j = pl.program_id(1)
    lanes = lanes_ref[0]
    keys = keys_ref[0]
    cidx = cidx_ref[...]
    sk = sinks_ref[...][:, None]                        # [K, 1]

    @pl.when(j == 0)
    def _init():
        carry_ref[...] = lanes[0]

    acc = carry_ref[...]

    def body(i, acc):
        lv = jax.lax.dynamic_slice_in_dim(lanes, i, 1, axis=0)[0]   # [K, S]
        key = jax.lax.dynamic_slice_in_dim(keys, i, 1, axis=0)[0]
        lane = jnp.take(jnp.take(cidx, key, axis=0), acc)           # [K, S]
        hit = jnp.take_along_axis(lv, jnp.maximum(lane, 0), axis=-1)
        nxt = jnp.where(lane < 0, jnp.where(sk >= 0, sk, acc), hit)
        return jnp.where(key == pad_key, acc, nxt)

    start = jnp.where(j == 0, 1, 0)
    acc = jax.lax.fori_loop(start, lanes.shape[0], body, acc)
    carry_ref[...] = acc

    @pl.when(j == n_blocks - 1)
    def _done():
        out_ref[0] = acc


@functools.partial(jax.jit,
                   static_argnames=("n_blk", "pad_key", "interpret"))
def spec_compose_lanes_pallas(lanes: jnp.ndarray, keys: jnp.ndarray,
                              cand_index: jnp.ndarray, sinks: jnp.ndarray, *,
                              pad_key: int, n_blk: int = 8,
                              interpret: bool | None = None) -> jnp.ndarray:
    """Block-sequential grid-carry compose of [B, N, K, S] lane-map runs.

    N % n_blk == 0 (pad trailing elements with ``pad_key`` keys — identity).
    Returns the final composition [B, K, S]; semantics of
    ``ref.spec_compose_lanes_ref``.
    """
    b, n, k, s = lanes.shape
    assert n % n_blk == 0, (n, n_blk)
    n_blocks = n // n_blk
    kernel = functools.partial(spec_compose_lanes_kernel,
                               n_blocks=n_blocks, pad_key=pad_key)
    nk, q = cand_index.shape
    from .ops import _interpret  # deferred: ops imports this module
    return pl.pallas_call(
        kernel,
        grid=(b, n_blocks),
        in_specs=[
            pl.BlockSpec((1, n_blk, k, s), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, n_blk), lambda i, j: (i, j)),
            pl.BlockSpec((nk, q), lambda i, j: (0, 0)),
            pl.BlockSpec((k,), lambda i, j: (0,)),
        ],
        out_specs=pl.BlockSpec((1, k, s), lambda i, j: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, k, s), jnp.int32),
        scratch_shapes=[pltpu.VMEM((k, s), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=_interpret(interpret),
    )(lanes.astype(jnp.int32), keys.astype(jnp.int32),
      cand_index.astype(jnp.int32), sinks.astype(jnp.int32))


def spec_compose_lanes_tree_kernel(lanes_ref, keys_ref, cidx_ref, sinks_ref,
                                   out_ref, *, pad_key: int):
    """Blelloch-style in-kernel tree reduce of one doc's keyed run.

    lanes_ref [1, N, K, S] with N a power of two; each unrolled level
    combines adjacent pairs (the combine is associative — it backs
    ``lax.associative_scan`` in the jnp lowering), halving N until one
    composed [K, S] map remains.  A combined pair keeps the LEFT key, so
    ``pad_key`` tail padding stays a right identity at every level.
    """
    lanes = lanes_ref[0]                                # [N, K, S]
    keys = keys_ref[0]                                  # [N]
    cidx = cidx_ref[...]
    q = cidx.shape[1]
    sk = sinks_ref[...][:, None]                        # [K, 1]
    n = lanes.shape[0]
    while n > 1:
        half = n // 2
        pairs = lanes.reshape(half, 2, *lanes.shape[1:])
        a, bl = pairs[:, 0], pairs[:, 1]                # [half, K, S]
        kp = keys.reshape(half, 2)
        ak, bk = kp[:, 0], kp[:, 1]                     # [half]
        lane = jnp.take(cidx.reshape(-1), bk[:, None, None] * q + a)
        hit = jnp.take_along_axis(bl, jnp.maximum(lane, 0), axis=-1)
        out = jnp.where(lane < 0, jnp.where(sk >= 0, sk, a), hit)
        lanes = jnp.where((bk == pad_key)[:, None, None], a, out)
        keys = ak
        n = half
    out_ref[0] = lanes[0]


@functools.partial(jax.jit, static_argnames=("pad_key", "interpret"))
def spec_compose_lanes_tree_pallas(lanes: jnp.ndarray, keys: jnp.ndarray,
                                   cand_index: jnp.ndarray,
                                   sinks: jnp.ndarray, *, pad_key: int,
                                   interpret: bool | None = None
                                   ) -> jnp.ndarray:
    """Tree-reduce compose of [B, N, K, S] runs; N must be a power of two."""
    b, n, k, s = lanes.shape
    assert n >= 1 and (n & (n - 1)) == 0, n
    kernel = functools.partial(spec_compose_lanes_tree_kernel,
                               pad_key=pad_key)
    nk, q = cand_index.shape
    from .ops import _interpret  # deferred: ops imports this module
    return pl.pallas_call(
        kernel,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, n, k, s), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((1, n), lambda i: (i, 0)),
            pl.BlockSpec((nk, q), lambda i: (0, 0)),
            pl.BlockSpec((k,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((1, k, s), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, k, s), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=_interpret(interpret),
    )(lanes.astype(jnp.int32), keys.astype(jnp.int32),
      cand_index.astype(jnp.int32), sinks.astype(jnp.int32))
