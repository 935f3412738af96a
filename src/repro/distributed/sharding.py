"""Path-based PartitionSpec rules for params, optimizer state, batches, caches.

Divisibility-aware: every rule degrades to replication for any dimension the
mesh axis does not divide (e.g. recurrentgemma's 10 query heads on a 16-way
model axis fall back to head_dim sharding).  This keeps one rule set valid
across all 10 architectures and both meshes.

Conventions:
  * params: FSDP over "data" on the d_model-ish dim, TP over "model" on the
    heads/ff/vocab dim; MoE experts over "model" ONLY (must match the
    shard_map in_specs in models/moe.py); pods replicate params (pure DP).
  * stacked layer/group leading dims are never sharded.
  * activations/batches: batch over (pod, data); model-dim annotations are
    left to XLA propagation from the param shardings.
"""

from __future__ import annotations

from typing import Any

import jax
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

__all__ = ["param_specs", "batch_specs", "cache_specs", "state_specs",
           "named", "opt_state_specs",
           "matcher_chunk_specs", "matcher_lane_specs", "doc_batch_spec"]

STACK_KEYS = {"layers", "groups", "enc", "dec"}
MOE_EXPERT_KEYS = {"wi_gate", "wi_up", "wo"}


def _div(n: int, mesh, axis: str) -> bool:
    return axis in mesh.axis_names and n % mesh.shape[axis] == 0 and n >= mesh.shape[axis]


def _dp(mesh) -> tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def _fsdp_axis(mesh, n: int):
    return "data" if _div(n, mesh, "data") else None


def _tp_axis(mesh, n: int):
    return "model" if _div(n, mesh, "model") else None


def _leaf_spec(path_names: list[str], shape: tuple[int, ...], mesh,
               in_moe: bool) -> P:
    name = path_names[-1] if path_names else ""
    stacked = any(k in STACK_KEYS for k in path_names[:-1])
    core = _core_spec(name, shape[1:] if stacked else shape, mesh, in_moe)
    return P(None, *core) if stacked else P(*core)


def _core_spec(name: str, shape: tuple[int, ...], mesh, in_moe: bool) -> tuple:
    nd = len(shape)
    if in_moe and name in MOE_EXPERT_KEYS and nd == 3:
        # experts over model ONLY (shard_map contract in models/moe.py)
        return ("model" if _div(shape[0], mesh, "model") else None, None, None)
    if name == "router":
        return (None,) * nd
    if name == "table" and nd == 2:        # embedding [V, D]
        return (_tp_axis(mesh, shape[0]), _fsdp_axis(mesh, shape[1]))
    if name in ("wq", "wk", "wv") and nd == 3:   # [D, N|K, H]
        if _div(shape[1], mesh, "model"):
            return (_fsdp_axis(mesh, shape[0]), "model", None)
        if _div(shape[2], mesh, "model"):
            return (_fsdp_axis(mesh, shape[0]), None, "model")
        return (_fsdp_axis(mesh, shape[0]), None, None)
    if name == "wo" and nd == 3:                  # [N, H, D]
        if _div(shape[0], mesh, "model"):
            return ("model", None, _fsdp_axis(mesh, shape[2]))
        if _div(shape[1], mesh, "model"):
            return (None, "model", _fsdp_axis(mesh, shape[2]))
        return (None, None, _fsdp_axis(mesh, shape[2]))
    if nd == 2 and name in ("wi_gate", "wi_up", "wx", "wgate", "wz", "wi",
                            "wf", "wog", "wo_gate", "w"):
        # column-parallel [D_in, D_out]
        return (_fsdp_axis(mesh, shape[0]), _tp_axis(mesh, shape[1]))
    if nd == 2 and name in ("wo", "w_r", "w_i"):
        # row-parallel [D_inner, D_out]
        return (_tp_axis(mesh, shape[0]), _fsdp_axis(mesh, shape[1]))
    if nd == 3 and name in ("wq", "wk", "wv"):
        return (_fsdp_axis(mesh, shape[0]), None, _tp_axis(mesh, shape[2]))
    if nd == 2 and name == "conv_w":
        return (None, _tp_axis(mesh, shape[1]))
    if nd == 1:
        # vectors: shard large ones (rglru lam/bias) over model, keep norms whole
        if name in ("lam", "conv_b") and _div(shape[0], mesh, "model"):
            return ("model",)
        return (None,)
    return (None,) * nd


def param_specs(params: Any, mesh) -> Any:
    """PartitionSpec tree matching the params tree."""

    def spec_of(path, leaf):
        names = [getattr(k, "key", getattr(k, "name", str(k))) for k in path]
        in_moe = "moe" in names
        return _leaf_spec(names, leaf.shape, mesh, in_moe)

    return jax.tree_util.tree_map_with_path(spec_of, params)


def opt_state_specs(params: Any, mesh) -> Any:
    """Adam m/v mirror the param sharding (ZeRO-style fully sharded states)."""
    return param_specs(params, mesh)


def batch_specs(batch: Any, mesh, global_batch: int) -> Any:
    """Shard the leading batch dim over (pod, data) when divisible."""
    dp = _dp(mesh)
    import math
    dp_size = math.prod(mesh.shape[a] for a in dp) if dp else 1

    def spec_of(leaf):
        if not hasattr(leaf, "shape") or leaf.ndim == 0:
            return P()
        if leaf.shape[0] == global_batch and global_batch % max(dp_size, 1) == 0 \
                and dp_size > 1:
            return P(dp, *(None,) * (leaf.ndim - 1))
        return P(*(None,) * leaf.ndim)

    return jax.tree.map(spec_of, batch)


def cache_specs(cache: Any, mesh, batch: int) -> Any:
    """KV caches [L, B, S, K, H]: batch over dp, heads (or head_dim) over model."""
    dp = _dp(mesh)
    import math
    dp_size = math.prod(mesh.shape[a] for a in dp) if dp else 1

    def spec_of(leaf):
        if leaf.ndim != 5:
            return P(*(None,) * leaf.ndim)
        l, b, s, k, h = leaf.shape
        bs = dp if (b == batch and b % max(dp_size, 1) == 0 and dp_size > 1) else None
        if _div(k, mesh, "model"):
            return P(None, bs, None, "model", None)
        if _div(h, mesh, "model"):
            return P(None, bs, None, None, "model")
        return P(None, bs, None, None, None)

    return jax.tree.map(spec_of, cache)


def state_specs(state: Any, mesh, batch: int) -> Any:
    """Recurrent decode states: batch dim over dp, widest trailing dim over model."""
    dp = _dp(mesh)
    import math
    dp_size = math.prod(mesh.shape[a] for a in dp) if dp else 1

    def spec_of(leaf):
        nd = leaf.ndim
        spec = [None] * nd
        for i, d in enumerate(leaf.shape):
            if d == batch and d % max(dp_size, 1) == 0 and dp_size > 1:
                spec[i] = dp
                break
        # shard the last model-divisible dim not already taken
        for i in range(nd - 1, -1, -1):
            if spec[i] is None and _div(leaf.shape[i], mesh, "model"):
                spec[i] = "model"
                break
        return P(*spec)

    return jax.tree.map(spec_of, state)


def matcher_chunk_specs(mesh) -> tuple[tuple[P, P, P, P], P]:
    """in/out specs for the mesh-sharded matcher body (engine/sharded.py).

    The speculative path lives on a 2-D ("doc", "chunk") matcher mesh
    (``launch.mesh.make_matcher_mesh``); legacy 1-D "data" meshes degrade to
    pure chunk sharding (doc axis absent -> replicated rows).

    Inputs (chunk-major):
      chunks [C, B, Lmax]  P(chunk, doc, None)  class ids per chunk slice
      lookahead [C, B]     P(chunk, doc)        boundary class before a chunk
      exact [C, B]         P(chunk, doc)        chunk matched exactly from
                                                its row-block's entry states
      entry [B, K]         P(doc, None)         per-document entry states
                                                (pattern starts, or a stream
                                                cursor's states)
    Output [Dc, B, K] finals: P(chunk, doc, None) — each doc shard folds only
    its own row block after the "chunk"-axis all_gather; doc shards never
    communicate, so every chunk device of a mesh row holds the same [B/Dd, K]
    answer.  The copies are returned behind an explicit leading chunk-axis
    dim (callers read ``out[0]``) so the out spec mentions *every* mesh axis:
    under jit, shard_map with ``check_vma=False`` turns an out spec that
    omits an axis into a psum over it when the operands were assembled inside
    the jit — 4x-scaled garbage, not a copy (jax 0.4 GSPMD lowering).
    """
    c_ax, d_ax = _matcher_axes(mesh)
    return ((P(c_ax, d_ax, None), P(c_ax, d_ax), P(c_ax, d_ax),
             P(d_ax, None)), P(c_ax, d_ax, None))


def _matcher_axes(mesh) -> tuple:
    if "chunk" in mesh.axis_names:
        return "chunk", ("doc" if "doc" in mesh.axis_names else None)
    return ("data" if "data" in mesh.axis_names else None), None


def matcher_lane_specs(mesh) -> tuple[tuple[P, P, P, P, P], P]:
    """in/out specs for the lane-plan (``ENTRY_LANES``) merge-stage body —
    the streaming device merge on the ("doc", "chunk") mesh
    (engine/sharded.py ``body_lanes``).

    Inputs extend ``matcher_chunk_specs`` for candidate-keyed cursors:
      chunks [C, B, Lmax]    P(chunk, doc, None)  as for exact plans
      lookahead [C, B]       P(chunk, doc)
      exact [C, B]           P(chunk, doc)
      cursor lanes [B, K, S] P(doc, None, None)   each stream's Eq. 11 lane
                                                  map — rides its doc shard,
                                                  never crosses "chunk"
      boundary class [B]     P(doc)               keys both the segment's
                                                  chunk-0 candidates and the
                                                  on-device composition
    Output [Dc, B, K, S] composed lanes: P(chunk, doc, None, None) — the
    same every-axis-mentioned shape discipline as ``matcher_chunk_specs``
    (callers read ``out[0]``); the cursor merge runs after the "chunk"-axis
    all_gather, per doc shard, so doc shards still never communicate.
    """
    c_ax, d_ax = _matcher_axes(mesh)
    return ((P(c_ax, d_ax, None), P(c_ax, d_ax), P(c_ax, d_ax),
             P(d_ax, None, None), P(d_ax)), P(c_ax, d_ax, None, None))


_DOC_AXES = ("pod", "data", "doc", "chunk")


def doc_batch_spec(mesh, batch: int) -> P:
    """Document-batch spec [B, ...]: shard the doc axis over the mesh's
    data-parallel axes when they divide it, replicate otherwise.

    On production meshes the dp axes are (pod, data); on a matcher mesh the
    batched *sequential* path treats every device as a row worker, so the doc
    axis spreads over ("doc", "chunk") jointly — rows are independent and
    nothing is exchanged, unlike the speculative chunk split."""
    axes = tuple(a for a in _DOC_AXES if a in mesh.axis_names)
    import math
    dp_size = math.prod(mesh.shape[a] for a in axes) if axes else 1
    if axes and dp_size > 1 and batch % dp_size == 0:
        return P(axes)
    return P()


def named(tree_specs: Any, mesh) -> Any:
    return jax.tree.map(lambda s: NamedSharding(mesh, s), tree_specs,
                        is_leaf=lambda x: isinstance(x, P))
