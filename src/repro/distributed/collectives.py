"""Topology-aware collectives: the paper's 2-tier merge, on the mesh hierarchy.

The paper found (Sec. 5.2) that on EC2 a flat binary-tree reduction of
L-vectors loses to a hierarchy that exploits the intra-node/inter-node
latency gap (2.7us vs 362us).  TPU pods have the same two-level structure:
ICI within a pod vs DCI across pods.  ``hierarchical_merge_lvecs`` merges
chunk maps over "data" (pod-local, ICI) first, then over "pod" (DCI) — only
one composition step crosses the slow tier, mirroring Fig. 9's node-leader /
master scheme.

``hierarchical_mean`` applies the same structure to gradient reduction:
reduce-scatter + all-gather inside the pod, single all-reduce across pods.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

__all__ = ["hierarchical_merge_lvecs", "flat_merge_lvecs", "hierarchical_mean",
           "distributed_membership"]


def _fold_local(maps: jnp.ndarray) -> jnp.ndarray:
    """Compose [C_loc, Q] maps left-to-right (worker-local leaf reduction)."""

    def step(acc, m):
        return m[acc], None

    acc0 = jnp.arange(maps.shape[1], dtype=jnp.int32)
    out, _ = jax.lax.scan(step, acc0, maps)
    return out


def _fold_gathered(stacked: jnp.ndarray) -> jnp.ndarray:
    def step(acc, m):
        return m[acc], None

    acc0 = jnp.arange(stacked.shape[1], dtype=jnp.int32)
    out, _ = jax.lax.scan(step, acc0, stacked)
    return out


def hierarchical_merge_lvecs(maps: jnp.ndarray, mesh) -> jnp.ndarray:
    """maps [C_global, Q] (chunk-major, sharded over dp axes) -> global map [Q].

    Tier 0: each device folds its local chunk maps.
    Tier 1: all-gather + fold over "data"  (pod-local; paper's node leader).
    Tier 2: all-gather + fold over "pod"   (cross-pod; paper's master).
    """
    axes = [a for a in ("data", "pod") if a in mesh.axis_names]
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)

    def body(m_loc):
        acc = _fold_local(m_loc)
        for axis in axes:  # data (fast tier) first, pod (slow tier) last
            gathered = jax.lax.all_gather(acc, axis, axis=0, tiled=False)
            acc = _fold_gathered(gathered)
        return acc

    spec_in = P(dp, None) if dp else P(None, None)
    fn = shard_map(body, mesh=mesh, in_specs=(spec_in,), out_specs=P(None),
                   check_vma=False)
    return fn(maps)


def flat_merge_lvecs(maps: jnp.ndarray, mesh) -> jnp.ndarray:
    """Baseline: single flat all-gather over all dp axes, then fold.

    The comparison partner for the 2-tier scheme in benchmarks (Sec. 5.2).
    """
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)

    def body(m_loc):
        acc = _fold_local(m_loc)
        gathered = jax.lax.all_gather(acc, dp, axis=0, tiled=False)
        return _fold_gathered(gathered)

    spec_in = P(dp, None) if dp else P(None, None)
    fn = shard_map(body, mesh=mesh, in_specs=(spec_in,), out_specs=P(None),
                   check_vma=False)
    return fn(maps)


def hierarchical_mean(tree, mesh):
    """Two-tier gradient mean: psum over "data" (ICI) then "pod" (DCI)."""
    axes = [a for a in ("data", "pod") if a in mesh.axis_names]

    def body(t):
        for axis in axes:
            t = jax.tree.map(lambda g: jax.lax.pmean(g, axis), t)
        return t

    fn = shard_map(body, mesh=mesh, in_specs=(P(),), out_specs=P(),
                   check_vma=False)
    return fn(tree)


def distributed_membership(table: np.ndarray, classes: np.ndarray, start: int,
                           sink: int, accepting: np.ndarray, mesh,
                           num_chunks_per_device: int = 4) -> int:
    """End-to-end distributed DFA membership test (holub-style full maps).

    The corpus-scan integration point: the byte stream is chunked across all
    dp devices (uniform SPMD layout; host-level weighted partitioning happens
    in data/loader.py), each chunk's full state map is computed in parallel,
    and maps are merged with the 2-tier hierarchy.
    """
    import math

    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    dp_size = math.prod(mesh.shape[a] for a in dp) if dp else 1
    c = dp_size * num_chunks_per_device
    n = classes.shape[0]
    l = n // c
    body = jnp.asarray(classes[: l * c], jnp.int32).reshape(c, l)
    table_j = jnp.asarray(table)
    q = table.shape[0]

    dp_spec = P(dp, None) if dp else P(None, None)

    def chunk_maps(chunks_loc):
        init = jnp.broadcast_to(jnp.arange(q, dtype=jnp.int32),
                                (chunks_loc.shape[0], q))

        def step(states, cls_row):
            return table_j[states, cls_row[:, None]], None

        final, _ = jax.lax.scan(step, init, chunks_loc.T)
        return final

    maps = shard_map(chunk_maps, mesh=mesh, in_specs=(dp_spec,),
                     out_specs=dp_spec, check_vma=False)(body)
    total = hierarchical_merge_lvecs(maps, mesh)
    state = int(jax.device_get(total)[start])
    # sequential tail on host
    for cls in classes[l * c:]:
        state = int(table[state, int(cls)])
    return state
