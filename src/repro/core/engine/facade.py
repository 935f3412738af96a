"""``Matcher`` facade: one entry point over the plan/executor layers.

The facade wires a packed pattern table, a ``Planner`` (bucketing, chunk
partitioning, capacity weighting) and an executor backend together behind
the pre-refactor ``BatchMatcher`` API:

    Matcher(dfas, backend="local")                      # jitted jnp path
    Matcher(dfas, backend="pallas")                     # fused Pallas kernel
    Matcher(dfas, backend="sharded", capacities=[...])  # mesh-sharded,
                                                        # capacity-balanced
    Matcher(dfas, backend="sharded", mesh_shape=(2, 4)) # 2-D doc x chunk

``BatchMatcher`` remains as a compatibility shim (``use_kernel=True`` maps to
the ``pallas`` backend).  Decisions stay bit-identical to per-document
sequential matching on every backend, mesh shape and capacity profile.
See README.md for the backend/mesh support matrix and docs/architecture.md
for the layer map.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from ..automata import DFA, PackedDFA, pack_dfas, packed_signature
from ..partition import capacity_weights
from .executors import LocalExecutor, named_jit
from .plan import (ENTRY_LANES, ENTRY_STARTS, ENTRY_STATES, DeviceTables,
                   MeshLayout, Planner, layout_device_work, next_pow2)

__all__ = ["BatchResult", "SegmentBatchResult", "CursorBatchResult",
           "Matcher", "BatchMatcher"]

BACKENDS = ("local", "pallas", "sharded")


@dataclasses.dataclass
class BatchResult:
    """Per-batch outcome of ``Matcher.membership_batch``.

    ``accepted``/``final_states`` are [B, K] (K = packed pattern count);
    work arrays are per-document model quantities mirroring ``MatchResult``.
    ``early_exits`` counts documents retired by the absorbing-state early
    exit before their real end; ``device_work`` (sharded backend) is the [D]
    real symbols assigned per device by the plan's chunk layouts, in mesh
    row-major order (device (doc r, chunk c) at index ``r * Dc + c``).
    """

    accepted: np.ndarray        # [B, K] bool
    final_states: np.ndarray    # [B, K] int32 packed state ids
    work_parallel: np.ndarray   # [B] scalar-model work
    work_sequential: np.ndarray # [B] n * K
    time_steps: np.ndarray      # [B] lane-parallel matching steps
    bucket_calls: int           # device dispatches consumed by this batch
    early_exits: int = 0        # docs fully absorbed before their last symbol
    device_work: Optional[np.ndarray] = None  # [D] real symbols per device

    @property
    def model_speedup(self) -> float:
        return float(self.work_sequential.sum()) / max(float(self.work_parallel.sum()), 1.0)

    @property
    def lane_speedup(self) -> float:
        return float(self.work_sequential.sum()) / max(float(self.time_steps.sum()), 1.0)


@dataclasses.dataclass
class SegmentBatchResult:
    """Outcome of ``Matcher.advance_segments`` (the streaming tick call).

    ``final_states[i]`` is the exact [K] packed states after advancing
    segment ``i`` from its entry states — i.e. the next cursor states.
    ``absorbed`` marks patterns that landed in absorbing states (further
    bytes cannot move them; the scheduler's stream-level early exit).
    ``padded_rows`` counts the device rows actually dispatched (tile-padded)
    — the denominator of the scheduler's batch-occupancy metric.
    """

    final_states: np.ndarray  # [B, K] int32 packed states after the segment
    absorbed: np.ndarray      # [B, K] bool
    lengths: np.ndarray       # [B] int64 segment byte lengths
    bucket_calls: int         # fused device dispatches consumed
    padded_rows: int          # batch_tile rows dispatched across all tiles
    early_exits: int          # segments retired by the absorbing early exit


@dataclasses.dataclass
class CursorBatchResult:
    """Outcome of ``Matcher.advance_cursors`` (the candidate-keyed tick).

    ``lane_states[i]`` is stream ``i``'s [K, S] cursor lane map extended by
    its segment — the exit state per Eq. 11 candidate entry of the stream's
    *original* boundary class, composed on device with the segment's
    independent lane map (``kernels.ref.cursor_merge_ref`` is the host
    reference).  ``absorbed`` marks patterns whose every lane is absorbing.
    """

    lane_states: np.ndarray   # [B, K, S] int32 composed cursor lanes
    absorbed: np.ndarray      # [B, K] bool — all lanes absorbing
    lengths: np.ndarray       # [B] int64 segment byte lengths
    bucket_calls: int         # fused device dispatches consumed
    padded_rows: int          # batch_tile rows dispatched across all tiles
    early_exits: int          # segments retired by the absorbing early exit


class Matcher:
    """Batched, multi-pattern membership over padded shape buckets.

    Accepts a single ``DFA``, a pre-built ``PackedDFA``, or a sequence of
    DFAs (packed on the fly).  The planner owns the bucketing / padding /
    retracing policy (see ``engine.plan``); the executor owns the device
    dispatch (see ``engine.executors`` / ``engine.sharded``).

    **Bit-identity guarantee**: every public decision — ``membership_batch``,
    ``accepts_batch``, ``advance_segments``, ``advance_classes`` — is
    bit-identical to per-document sequential matching, on every backend,
    mesh shape and capacity profile.

    Parameters
    ----------
    source       : DFA | PackedDFA | sequence of DFA.
    num_chunks   : uniform chunk count C per document (rounded up to a
                   multiple of the mesh chunk extent on the sharded backend).
    max_buckets  : lifetime compiled-shape budget for the speculative path.
    batch_tile   : fixed row count of every device call (rounded up to a
                   power of two; must be a multiple of the mesh doc extent
                   on a 2-D sharded mesh).
    backend      : "local" | "pallas" | "sharded".
    mesh         : sharded backend only — a ("doc", "chunk") mesh from
                   ``launch.mesh.make_matcher_mesh`` (legacy 1-D "data"
                   meshes count as doc extent 1).
    mesh_shape   : sharded backend only, alternative to ``mesh`` — passed to
                   ``make_matcher_mesh(devices, shape=mesh_shape)``: ``None``
                   for the 1-D (1, D) chunk layout, ``"auto"`` for
                   near-square auto-factoring (8 devices -> 2x4), or an
                   explicit ``(doc, chunk)`` tuple.
    devices      : sharded backend only, with ``mesh_shape`` — how many local
                   devices the built mesh uses (default: all).
    capacities   : sharded backend only — measured per-device capacities
                   (symbols/us, e.g. from ``core.profiling.profile_capacity``
                   with ``devices=``), one per mesh device in row-major
                   (doc, chunk) order; normalized to Eq. 1 weights *per doc
                   row* for the planner's capacity-balanced chunk layouts.
                   ``None`` = uniform.
    spec_m       : weighted-layout work model: 1 = lane-parallel chunk sizes
                   proportional to capacity (default); ``i_max`` reproduces
                   the paper's scalar-worker Eqs. 2–7.
    calibrate    : sharded backend only — when True and no ``capacities``
                   were passed, measure per-device symbols/sec at
                   construction (``core.profiling.profile_capacity`` with
                   ``devices=``, the paper's Sec. 4.1 step 1 run at cluster
                   start) and feed the measurements into the
                   capacity-weighted chunk layout automatically.
    early_exit_segments : absorbing-state early-exit granularity per scan
                   (1 disables; pow2, local/seq paths only).
    lookahead_r  : boundary-key lookahead depth of the candidate tables:
                   1 (the paper's Eq. 11 last-byte class), 2 (Eq. 13 pair
                   keys — smaller feasible candidate sets shrink the lane
                   width S), or "auto" (default: r=2 exactly when it strictly
                   shrinks S and its tables fit the memory cap; static per
                   DFA).
    autotune     : opt-in shape autotuner (``core.profiling
                   .autotune_spec_shapes``): times candidate ``(num_chunks,
                   l_blk, mesh_shape)`` configurations on a synthetic probe
                   workload at construction and applies the winner —
                   replacing the near-square ``mesh_shape="auto"`` heuristic
                   with measured choices.  Results cache per (dfa, shape,
                   devices, backend) key, on disk when
                   ``$REPRO_AUTOTUNE_CACHE`` points at a JSON path.
    """

    def __init__(self, source, *, num_chunks: int = 8, max_buckets: int = 2,
                 batch_tile: int = 64, backend: str = "local", mesh=None,
                 mesh_shape=None, devices: Optional[int] = None,
                 capacities: Optional[Sequence[float]] = None,
                 spec_m: int = 1, calibrate: bool = False,
                 early_exit_segments: int = 4,
                 lookahead_r: int | str = "auto", autotune: bool = False):
        packed = self._pack_source(source)
        if num_chunks < 1:
            raise ValueError("num_chunks must be >= 1")
        if max_buckets < 1:
            raise ValueError("max_buckets must be >= 1")
        if batch_tile < 1:
            raise ValueError("batch_tile must be >= 1")
        if backend not in BACKENDS:
            raise ValueError(f"unknown backend {backend!r}; pick from {BACKENDS}")
        self.packed = packed
        self.backend = backend
        self.max_buckets = int(max_buckets)
        self.batch_tile = next_pow2(int(batch_tile))
        self._lookahead_r = lookahead_r  # swap_patterns rebuilds with it
        self.dev = DeviceTables.build(packed, lookahead_r=lookahead_r)
        self.pad_cls = self.dev.pad_cls
        self.autotune = bool(autotune)
        self._tuned = None
        if self.autotune:
            from ..profiling import autotune_spec_shapes
            self._tuned = autotune_spec_shapes(
                packed, backend=backend,
                num_chunks_candidates=sorted({4, 8, int(num_chunks)}),
                mesh_shape=mesh_shape, devices=devices,
                lookahead_r=lookahead_r)
            num_chunks = self._tuned.num_chunks
            if backend == "sharded" and mesh is None and mesh_shape == "auto":
                mesh_shape = self._tuned.mesh_shape

        if backend == "sharded":
            from ...launch.mesh import make_matcher_mesh, matcher_mesh_extents
            if mesh is None:
                mesh = make_matcher_mesh(devices, shape=mesh_shape)
            elif mesh_shape is not None or devices is not None:
                raise ValueError("pass either mesh= or mesh_shape=/devices=, "
                                 "not both")
            doc_shards, chunk_shards = matcher_mesh_extents(mesh)
            n_dev = doc_shards * chunk_shards
            if self.batch_tile % doc_shards:
                raise ValueError(
                    f"batch_tile={self.batch_tile} must be a multiple of the "
                    f"mesh doc extent {doc_shards}")
            self._doc_shards, self._chunk_shards = doc_shards, chunk_shards
            self._mesh_devices = list(np.asarray(mesh.devices).reshape(-1))[:n_dev]
            if calibrate and capacities is None:
                # cached per (device set, benchmark): repeated construction
                # over the same fleet measures once; Matcher.recalibrate owns
                # the explicit refresh
                from ..profiling import calibrated_capacities
                capacities = calibrated_capacities(self._mesh_devices,
                                                   n_symbols=20_000, repeats=3)
            if capacities is None:
                self.capacities = weights = row_weights = None
            else:
                caps = np.asarray(capacities, np.float64)
                if caps.size != n_dev:
                    raise ValueError(f"need {n_dev} capacities (one per mesh "
                                     f"device), got {caps.size}")
                self.capacities = caps
                weights = self._row_weights(caps)
                row_weights = self._doc_row_weights(caps)
            self.planner = Planner(num_chunks=num_chunks,
                                   max_buckets=max_buckets,
                                   devices=chunk_shards, weights=weights,
                                   spec_m=spec_m, doc_shards=doc_shards,
                                   row_weights=row_weights)
            from .sharded import ShardedExecutor
            self.executor = ShardedExecutor(
                self.dev, num_chunks=self.planner.num_chunks, mesh=mesh,
                early_exit_segments=early_exit_segments)
            self.n_devices = n_dev
        else:
            if capacities is not None:
                raise ValueError("capacities only apply to the sharded backend")
            if mesh is not None or mesh_shape is not None or devices is not None:
                raise ValueError("mesh/mesh_shape/devices only apply to the "
                                 "sharded backend")
            if spec_m != 1:
                raise ValueError("spec_m only applies to the sharded backend")
            if calibrate:
                raise ValueError("calibrate only applies to the sharded "
                                 "backend (single-device layouts are uniform)")
            self.capacities = None
            self.planner = Planner(num_chunks=num_chunks,
                                   max_buckets=max_buckets, devices=1)
            self.executor = LocalExecutor(
                self.dev, num_chunks=self.planner.num_chunks,
                use_kernel=(backend == "pallas"),
                early_exit_segments=early_exit_segments)
            self.n_devices = 1
        self.num_chunks = self.planner.num_chunks
        if self._tuned is not None and self._tuned.l_blk:
            self.executor.spec_l_blk[0] = int(self._tuned.l_blk)  # default key
        self._advance_fn = named_jit(self._advance_impl, "advance_classes")
        # scan-compose dispatch counter: one per compose_lane_maps device
        # call — lets the OOO tier assert "one associative_scan per
        # contiguous run", the same way merge_calls() guards the tick path
        self.compose_calls = 0
        # cumulative counts of _dispatch (perf_report()["dispatch"]): tile
        # rows dispatched, the real symbols they held, the row-steps a loop
        # stopping at each tile's longest row would run, and the row-steps
        # the scan loops ran; ``calls`` numbers the public calls' root spans
        self.dispatched = {"rows": 0, "real_symbols": 0, "bound_symbols": 0,
                           "run_symbols": 0}
        self.calls = 0
        # observed-traffic accounting: every dispatched tile feeds a bounded
        # (fill, length) reservoir; maybe_retune re-runs the autotuner on a
        # probe shaped like this traffic once it drifts from what the
        # current shapes were tuned on (the synthetic probe at cold start)
        from ..profiling import TrafficProfile, synthetic_traffic
        self.traffic = TrafficProfile()
        self._tuned_traffic = (synthetic_traffic()
                               if self._tuned is not None else None)
        self.retunes = 0

    @staticmethod
    def _pack_source(source) -> PackedDFA:
        """Normalize every accepted pattern source to one ``PackedDFA``.

        A multi-block ``PatternSet`` is refused here on purpose: one Matcher
        runs exactly one table, and silently flattening the blocks would
        defeat the set's whole point (``core.engine.BlockedMatcher`` is the
        multi-block front end).
        """
        from ..patterns import PatternSet
        if isinstance(source, PatternSet):
            if source.n_blocks != 1:
                raise ValueError(
                    f"PatternSet has {source.n_blocks} blocks; a Matcher "
                    "runs exactly one — use core.engine.BlockedMatcher for "
                    "multi-block sets (or raise k_blk to cover all patterns)")
            return source.blocks[0]
        if isinstance(source, PackedDFA):
            return source
        if isinstance(source, DFA):
            return pack_dfas([source])
        return pack_dfas(list(source))

    def swap_patterns(self, source) -> bool:
        """Hot-swap the pattern tables in place; True iff anything changed.

        An identical table content (``automata.packed_signature``) is a
        guaranteed no-op and returns False — in-flight streaming cursors
        carry over bit-identically.  On a real change the planner keeps its
        sticky buckets and compiled seq width (*shapes* survive the swap),
        but every compiled lowering baked the old device tables as trace
        constants, so the executor cache clears (``LaneExecutor.retable``)
        and programs re-lower lazily on next dispatch; ``Planner
        .table_epoch`` stamps every post-swap plan so a stale program can
        never be served.  Block-granular lowering *reuse* lives one level up
        — ``BlockedMatcher.swap_patterns`` leaves unchanged blocks' matchers
        untouched.  Streaming callers must swap at a tick boundary
        (``StreamMatcher.swap_patterns`` owns the cursor carry rules).
        """
        packed = self._pack_source(source)
        if packed_signature(packed) == packed_signature(self.packed):
            return False
        self.packed = packed
        self.dev = DeviceTables.build(packed, lookahead_r=self._lookahead_r)
        self.pad_cls = self.dev.pad_cls
        self.planner.table_epoch += 1
        self.executor.retable(self.dev)
        # the jitted cursor advance baked the old tables too — fresh wrapper,
        # fresh trace cache
        self._advance_fn = named_jit(self._advance_impl, "advance_classes")
        return True

    # -- properties ---------------------------------------------------------

    @property
    def n_patterns(self) -> int:
        return self.packed.n_patterns

    @property
    def tables(self):
        """Packed Eq. 11 lookahead tables (built lazily on first access)."""
        return self.dev.tables

    @property
    def trace_count(self) -> int:
        """Number of shapes compiled so far (increments once per retrace)."""
        return self.executor.traces

    @property
    def _spec_keys(self) -> list[int]:
        """Compiled speculative bucket keys (compat alias for the planner's)."""
        return self.planner.spec_keys

    # -- capacity rebalancing (sharded backend) ------------------------------

    def _row_weights(self, caps: np.ndarray) -> np.ndarray:
        # Eq. 1 weights per doc row-block: each mesh row balances its own
        # chunk axis; rows split documents, not symbols
        caps2 = caps.reshape(self._doc_shards, self._chunk_shards)
        return np.stack([capacity_weights(caps2[r])
                         for r in range(self._doc_shards)])

    def _doc_row_weights(self, caps: np.ndarray) -> Optional[np.ndarray]:
        # Eq. 1 on the doc axis: a mesh row's aggregate capacity (its chunk
        # devices matching in parallel) sets how many *documents* it should
        # host per tile — the ragged doc-tiling weights
        if self._doc_shards <= 1:
            return None
        caps2 = caps.reshape(self._doc_shards, self._chunk_shards)
        return capacity_weights(caps2.sum(axis=1))

    def rebalance(self, capacities: Sequence[float]) -> None:
        """Re-derive the capacity-weighted chunk layouts from new measured
        capacities (sharded backend only).

        The straggler-mitigation hook (paper Eq. 5): when observed per-device
        times drift — a degraded host, a corrupted capacity profile — the
        planner's weights update and its cached layouts drop; the executor's
        layout epoch bumps so sharded spec lowerings (which bake chunk
        boundaries as static slices) re-lower lazily while every
        layout-independent compiled program survives.  Decisions stay
        bit-identical across any rebalance — only *where* chunks are matched
        moves, never the answer.  Callers must never rebalance mid-dispatch
        (the scheduler applies it strictly between ticks).
        """
        if self.backend != "sharded":
            raise ValueError("rebalance applies to the sharded backend only "
                             "(single-device layouts are uniform)")
        caps = np.asarray(capacities, np.float64).reshape(-1)
        if caps.size != self.n_devices:
            raise ValueError(f"need {self.n_devices} capacities (one per "
                             f"mesh device), got {caps.size}")
        if not np.all(np.isfinite(caps)) or (caps <= 0).any():
            raise ValueError("capacities must be finite and > 0")
        self.capacities = caps
        self.planner.set_weights(self._row_weights(caps),
                                 row_weights=self._doc_row_weights(caps))
        self.executor.invalidate_layouts()

    def recalibrate(self, *, n_symbols: int = 20_000,
                    repeats: int = 3) -> np.ndarray:
        """Re-measure per-device capacities and rebalance onto them.

        Bypasses (and replaces) the process-wide calibration cache entry for
        this device set — the explicit refresh the rebalance path owns when
        the cached profile no longer reflects reality.  Returns the fresh
        [D] capacities.
        """
        if self.backend != "sharded":
            raise ValueError("recalibrate applies to the sharded backend "
                             "only (single-device layouts are uniform)")
        from ..profiling import calibrated_capacities
        caps = calibrated_capacities(self._mesh_devices, n_symbols=n_symbols,
                                     repeats=repeats, refresh=True)
        self.rebalance(caps)
        return caps

    # -- observed-traffic autotuning -----------------------------------------

    def traffic_profile(self):
        """Signature of the traffic dispatched so far (``ObservedTraffic``),
        or None before any dispatch."""
        return self.traffic.snapshot()

    def maybe_retune(self, *, drift_threshold: float = 1.0,
                     min_docs: int = 64, force: bool = False,
                     time_fn=None) -> bool:
        """Re-run the shape autotuner on the *observed* traffic when it has
        drifted from what the current shapes were tuned on.

        The construction-time tune measured a synthetic probe (8 x 2048-byte
        documents); once real dispatches have accumulated ``min_docs``
        documents and their ``ObservedTraffic`` signature has drifted
        ``drift_threshold`` doublings or more (median length or tile fill,
        ``ObservedTraffic.drift``) from the last-tuned traffic, the tuner
        re-times candidates on a probe corpus shaped like the real traffic
        and applies the winning ``l_blk`` — the one shape axis that can move
        post-construction (``num_chunks`` and the mesh are baked into the
        planner and executor; the tuned values still land in
        ``perf_report()["autotune"]`` for the next cold start, and the disk
        cache remembers them).  Returns True iff a retune ran.  ``force``
        skips the drift gate (not the traffic requirement); ``time_fn`` is
        the autotuner's deterministic measurement override for tests.
        Requires ``autotune=True`` at construction; callers must invoke it
        between batches, never mid-dispatch.
        """
        if not self.autotune:
            raise ValueError("maybe_retune requires Matcher(autotune=True)")
        obs = self.traffic.snapshot()
        if obs is None or self.traffic.n_docs < int(min_docs):
            return False
        if not force and self._tuned_traffic is not None \
                and self._tuned_traffic.drift(obs) < float(drift_threshold):
            return False
        from ..profiling import autotune_spec_shapes
        mesh_shape = (None if self.backend != "sharded"
                      else (self._doc_shards, self._chunk_shards))
        self._tuned = autotune_spec_shapes(
            self.packed, backend=self.backend,
            num_chunks_candidates=sorted({4, 8, int(self.num_chunks)}),
            mesh_shape=mesh_shape,
            devices=(self.n_devices if self.backend == "sharded" else None),
            lookahead_r=self._lookahead_r, observed=obs, time_fn=time_fn)
        self._tuned_traffic = obs
        self.retunes += 1
        if self._tuned.l_blk:
            self.executor.spec_l_blk[0] = int(self._tuned.l_blk)
            self.executor.invalidate_block_sizes()
        return True

    # -- public API ---------------------------------------------------------

    def classes(self, doc: bytes | np.ndarray) -> np.ndarray:
        return self.packed.classes_of(doc).astype(np.int32)

    # -- the one bucket-dispatch loop (every public path rides it) -----------

    @staticmethod
    def _as_arrays(docs) -> tuple[list[np.ndarray], np.ndarray]:
        arrs = [np.frombuffer(d, np.uint8)
                if isinstance(d, (bytes, bytearray))
                else np.asarray(d, np.uint8) for d in docs]
        return arrs, np.array([a.shape[0] for a in arrs], np.int64)

    def _root_span(self, entry: str, docs: int) -> TraceAnnotation:
        """The profiler span of one public call that rides ``_dispatch``."""
        self.calls += 1
        return TraceAnnotation(f"repro.{entry}", docs=docs, call=self.calls)

    def _dispatch(self, mplan, arrs, lengths, out, *, entry_mode: str,
                  entry: Optional[np.ndarray] = None,
                  entry_cls: Optional[np.ndarray] = None, tile_hook=None,
                  finish=None) -> tuple[int, int, int, object]:
        """Run every bucket tile of a ``MatchPlan`` through the lane program.

        One loop serves whole documents (``ENTRY_STARTS``), resumed segments
        (``ENTRY_STATES``) and candidate-keyed cursor ticks (``ENTRY_LANES``)
        — the planner emits the ``LanePlan``, the executor lowers it, and
        this loop only packs tiles and scatters results into ``out`` (shape
        [B, K] or [B, K, S] to match the plan's output).  Returns
        ``(bucket_calls, padded_rows, early_exits, finish(out))``.
        Tiles follow ``bucket.doc_idx``, which the planner emits longest
        document first, and ``sel`` carries every result back.

        Each tile runs in three profiler spans, with kwargs ``tile`` and
        ``width``: ``repro.pack`` (buffer, lengths, entry operands),
        ``repro.launch`` (the executor call up to its return: lowering
        lookup, dispatch, operand transfer) and ``repro.wait`` (one fetch of
        the tile's outputs).  ``repro.finish`` then scatters every tile into
        ``out`` and applies ``finish``; its kwargs carry the call's counts,
        which also accumulate in ``self.dispatched``.
        """
        k = self.packed.n_patterns
        calls = rows = real = bound = ran = 0
        fetched = []
        for bucket in mplan.buckets:
            spec = bucket.kind == "spec"
            layout = (self.planner.layout_for(bucket.chunk_len)
                      if spec else None)
            # the per-DFA r choice only matters to programs that gather from
            # the candidate tables; keying it conditionally keeps the lazy
            # lookahead analysis unforced for pure-seq exact traffic
            spec_r = (self.dev.spec_r if (spec or entry_mode == ENTRY_LANES)
                      else 1)
            lane = self.planner.lane_plan(bucket, entry=entry_mode,
                                          spec_r=spec_r)
            ragged = (spec and isinstance(layout, MeshLayout)
                      and layout.is_ragged)
            # the scan rows of one tile: documents, or document-chunks
            tile_rows = self.batch_tile * (bucket.width // bucket.chunk_len
                                           if spec else 1)
            for lo in range(0, bucket.doc_idx.size, self.batch_tile):
                tag = {"tile": calls, "width": bucket.width}
                with TraceAnnotation("repro.pack", **tag):
                    sel = bucket.doc_idx[lo:lo + self.batch_tile]
                    # ragged doc tiling: capacity-weighted layouts place
                    # real documents into mesh row-blocks proportionally
                    # (Eq. 7 on the doc axis) — slow rows get more
                    # zero-length pad rows.  rowpos[r] is doc sel[r]'s
                    # physical tile row; results come back through the same
                    # (invertible) placement, so answers are bit-identical
                    # to the dense front-fill by construction
                    rowpos = (layout.tile_rows(sel.size, self.batch_tile)
                              if ragged else np.arange(sel.size))
                    buf = np.zeros((self.batch_tile, bucket.width), np.uint8)
                    lens = np.zeros(self.batch_tile, np.int32)
                    for r, i in enumerate(sel):
                        buf[rowpos[r], :lengths[i]] = arrs[i]
                        lens[rowpos[r]] = lengths[i]
                    if tile_hook is not None:
                        tile_hook(bucket, layout, sel, lens)
                    self.traffic.record(sel.size, lengths[sel])
                    # operands stay host numpy: jit transfers them once at
                    # call time, where an eager jnp.asarray per operand
                    # costs an extra device round-trip each on the
                    # streaming hot path
                    ent = ecls = None
                    if entry_mode == ENTRY_STATES:
                        # pad rows scan from the pattern starts (ignored)
                        ent = np.tile(self.packed.starts,
                                      (self.batch_tile, 1)).astype(np.int32)
                        ent[rowpos] = entry[sel]
                    elif entry_mode == ENTRY_LANES:
                        # pad rows carry in-range lanes and the pad boundary
                        # key, which the device merge composes as the
                        # identity
                        s = self.tables.i_max
                        ent = np.broadcast_to(
                            self.packed.starts.astype(np.int32)[None, :,
                                                                None],
                            (self.batch_tile, k, s)).copy()
                        ent[rowpos] = entry[sel]
                        ecls = np.full(self.batch_tile, self.dev.pad_key,
                                       np.int32)
                        ecls[rowpos] = entry_cls[sel]
                with TraceAnnotation("repro.launch", **tag):
                    outs = self.executor.run(
                        lane, buf, lens, layout=layout,
                        entry=ent, entry_classes=ecls)
                with TraceAnnotation("repro.wait", **tag):
                    res, pos, steps = jax.device_get(outs)
                # a doc "exited early" if all its lanes hit absorbing states
                # before its real symbols ran out (spec positions are
                # chunk-local, so compare against the per-chunk fill)
                eff = (np.minimum(bucket.chunk_len, lengths[sel]) if spec
                       else lengths[sel])
                fetched.append((sel, rowpos, res, pos, eff))
                real += int(lengths[sel].sum())
                # every row run to the tile's longest effective row: what
                # one loop stopping exactly there runs (an absorbing exit,
                # or a mesh's per-shard loops, can run less)
                bound += tile_rows * int(eff.max())
                ran += tile_rows // steps.size * int(steps.sum())
                calls += 1
                rows += self.batch_tile
        with TraceAnnotation("repro.finish", tiles=calls, rows=rows,
                             real_symbols=real, bound_symbols=bound,
                             run_symbols=ran):
            early = 0
            for sel, rowpos, res, pos, eff in fetched:
                out[sel] = res[rowpos]
                early += int((pos[rowpos] < eff).sum())
            done = None if finish is None else finish(out)
        for key, n in (("rows", rows), ("real_symbols", real),
                       ("bound_symbols", bound), ("run_symbols", ran)):
            self.dispatched[key] += n
        return calls, rows, early, done

    def membership_batch(self, docs: Sequence[bytes | np.ndarray]) -> BatchResult:
        """Match every doc against every packed pattern; no per-doc syncs.

        ``docs`` is a ragged sequence of B byte strings / uint8 arrays.
        Returns a ``BatchResult`` whose [B, K] decisions are bit-identical to
        running each document through sequential matching per pattern — on
        every backend and mesh shape (the sharded backend's 2-D doc x chunk
        split changes only *where* chunks are matched, never the answer).
        """
        b = len(docs)
        k = self.packed.n_patterns
        if b == 0:
            z = np.zeros(0, np.int64)
            return BatchResult(np.zeros((0, k), bool), np.zeros((0, k), np.int32),
                               z, z, z, 0)
        with self._root_span("membership_batch", b):
            return self._membership_batch(docs)

    def _membership_batch(self, docs) -> BatchResult:
        b, k = len(docs), self.packed.n_patterns
        with TraceAnnotation("repro.plan"):
            arrs, lengths = self._as_arrays(docs)
            plan = self.planner.plan(lengths)
        finals = np.tile(self.packed.starts, (b, 1)).astype(np.int32)
        steps = np.where(plan.spec_mask, 0, lengths)
        device_work = (np.zeros(self.n_devices, np.int64)
                       if self.backend == "sharded" else None)
        seen_buckets: set[int] = set()

        def account(bucket, layout, sel, lens):
            # work-model bookkeeping per bucket (steps) and per tile (2-D
            # layouts assign work positionally: tile row-block -> mesh row;
            # pad rows carry 0 symbols)
            nonlocal device_work
            if bucket.kind != "spec":
                return
            if id(bucket) not in seen_buckets:
                seen_buckets.add(id(bucket))
                steps[bucket.doc_idx] = self.executor.steps_for(layout)
                if device_work is not None and not isinstance(layout,
                                                              MeshLayout):
                    device_work += layout_device_work(layout,
                                                      lengths[bucket.doc_idx])
            if device_work is not None and isinstance(layout, MeshLayout):
                device_work += layout.device_work(lens.astype(np.int64))

        calls, _, early, accepted = self._dispatch(
            plan, arrs, lengths, finals, entry_mode=ENTRY_STARTS,
            tile_hook=account,
            finish=lambda out: self.packed.accepting[out])
        # lanes forces the lazy lookahead tables — only on speculative work
        lanes = k * self.tables.i_max if plan.spec_mask.any() else k
        work_par = np.where(plan.spec_mask, steps * lanes, lengths * k)
        return BatchResult(accepted, finals, work_par, lengths * k, steps,
                           calls, early_exits=early, device_work=device_work)

    def accepts_batch(self, docs: Sequence[bytes | np.ndarray]) -> np.ndarray:
        """[B, K] bool accept matrix (convenience ``membership_batch`` wrapper,
        same bit-identity guarantee)."""
        return self.membership_batch(docs).accepted

    # -- streaming hook ------------------------------------------------------

    def advance_segments(self, segments: Sequence[bytes | np.ndarray],
                         entry_states: np.ndarray) -> SegmentBatchResult:
        """Advance B independent streams by one segment each, batched.

        ``segments[i]`` is the next byte segment of stream ``i`` and
        ``entry_states[i]`` its current [K] exact packed states (a
        ``streaming.MatchCursor``'s states; the pattern starts for a fresh
        stream), so ``entry_states`` is [B, K] int32.  Segments share the
        planner's sticky shape buckets with whole-document matching, and
        each bucket tile is one fused device call through the executor's
        segment-entry path — so segments from many unrelated streams
        coalesce exactly like documents of a batch.  On the sharded backend
        the same 2-D doc x chunk mesh split applies (entry states shard over
        "doc" with their rows).  Results are bit-identical to matching each
        stream's concatenated bytes in one shot (Eq. 8 composition is
        associative), on every backend and mesh shape.
        """
        b = len(segments)
        k = self.packed.n_patterns
        entry = np.ascontiguousarray(np.asarray(entry_states, np.int32))
        if entry.shape != (b, k):
            raise ValueError(f"entry_states must be [{b}, {k}], "
                             f"got {entry.shape}")
        if b == 0:
            return SegmentBatchResult(entry.copy(), np.zeros((0, k), bool),
                                      np.zeros(0, np.int64), 0, 0, 0)
        with self._root_span("advance_segments", b):
            with TraceAnnotation("repro.plan"):
                arrs, lengths = self._as_arrays(segments)
                plan = self.planner.plan(lengths)
            finals = entry.copy()  # zero-length segments pass through
            calls, rows, early, absorbed = self._dispatch(
                plan, arrs, lengths, finals, entry_mode=ENTRY_STATES,
                entry=entry, finish=lambda out: self.dev.absorbing[out])
        return SegmentBatchResult(final_states=finals, absorbed=absorbed,
                                  lengths=lengths, bucket_calls=calls,
                                  padded_rows=rows, early_exits=early)

    def advance_cursors(self, segments: Sequence[bytes | np.ndarray],
                        lane_states: np.ndarray,
                        last_classes: np.ndarray) -> CursorBatchResult:
        """Advance B candidate-keyed cursors by one segment each — the
        streaming device merge.

        Where ``advance_segments`` needs each stream's *exact* [K] states,
        this path needs only each stream's boundary class: ``lane_states[i]``
        is stream ``i``'s [K, S] cursor lane map (exit state per Eq. 11
        candidate entry of the stream's original boundary class — a
        ``streaming.MatchCursor``'s ``lane_states``, or an exact cursor
        broadcast across the lane axis) and ``last_classes[i]`` the joint
        class of the last byte the cursor absorbed.  Each bucket tile is one
        fused device call that (a) matches the segments *independently*,
        candidate-keyed on each row's boundary class, and (b) composes the
        cursor lanes with the resulting segment maps on device — the Eq. 8
        composition that ``streaming.cursor.merge`` performs per stream on
        the host, batched (``kernels.ref.cursor_merge_ref`` is the host
        reference; bit-identity is property-tested on every backend and
        mesh shape in tests/test_device_merge.py).

        Contract: every cursor must have enough absorbed history for a
        boundary key (``last_classes`` in ``[0, DeviceTables.n_keys)`` —
        under r=1 the joint class of the last byte, under r=2 the pair key
        ``DeviceTables.advance_key`` maintains) — a fresh stream's states
        are exactly the pattern starts, so it has no candidate keying and
        belongs in ``advance_segments``.  Zero-length segments compose as
        the identity.  Plans, buckets and tiles are shared with the exact
        paths, so mixed whole-document / segment / cursor traffic reuses the
        same compiled programs per shape.
        """
        b = len(segments)
        k = self.packed.n_patterns
        s = self.tables.i_max
        lanes = np.ascontiguousarray(np.asarray(lane_states, np.int32))
        if lanes.shape != (b, k, s):
            raise ValueError(f"lane_states must be [{b}, {k}, {s}], "
                             f"got {lanes.shape}")
        last = np.asarray(last_classes, np.int32).reshape(-1)
        if last.shape != (b,):
            raise ValueError(f"last_classes must be [{b}], got {last.shape}")
        if b and ((last < 0) | (last >= self.dev.n_keys)).any():
            raise ValueError(
                "last_classes must be boundary keys in [0, n_keys); fresh "
                "streams (no usable history) have exact states — advance "
                "them with advance_segments")
        if b == 0:
            return CursorBatchResult(lanes.copy(), np.zeros((0, k), bool),
                                     np.zeros(0, np.int64), 0, 0, 0)
        with self._root_span("advance_cursors", b):
            with TraceAnnotation("repro.plan"):
                arrs, lengths = self._as_arrays(segments)
                plan = self.planner.plan(lengths)
            out = lanes.copy()  # zero-length segments compose as identity
            calls, rows, early, absorbed = self._dispatch(
                plan, arrs, lengths, out, entry_mode=ENTRY_LANES,
                entry=lanes, entry_cls=last,
                finish=lambda o: self.dev.absorbing[o].all(axis=2))
        return CursorBatchResult(lane_states=out, absorbed=absorbed,
                                 lengths=lengths, bucket_calls=calls,
                                 padded_rows=rows, early_exits=early)

    def compose_lane_maps(self, lane_maps: np.ndarray,
                          entry_keys: np.ndarray) -> np.ndarray:
        """Fold B runs of candidate-keyed lane maps in ONE device scan.

        ``lane_maps [B, N, K, S]`` holds, per row, a run of transition maps
        (leftmost first — e.g. a stream's cursor broadcast to lane width
        followed by buffered segment maps); ``entry_keys [B, N]`` the
        boundary key selecting each map's Eq. 11 candidate entry row.
        Returns the ``[B, K, S]`` composition of every row via a single
        log-depth ``lax.associative_scan`` dispatch (``lvector
        .merge_scan_lanes_jnp``; ``kernels.ref.spec_merge_lanes_scan_ref``
        is the sequential oracle) — the out-of-order gap-close bulk path:
        one device call per batch of contiguous runs, not one compose per
        segment.

        Keys equal to ``DeviceTables.pad_key`` compose as the identity, so
        ragged runs are padded on the right; element 0's key is never read.
        N is padded to a power of two here to bound retraces (the compiled
        scan is cached per padded N).  ``compose_calls`` counts dispatches.

        All lowerings (jnp scan, Pallas carry/tree kernels, sharded) are
        bit-identical on real candidate lanes — the only lanes a consumer
        can address through ``cand_index``.  Pad lanes (filler states
        repeated to reach width S) hold evaluation-order-dependent
        passthrough values; see ``kernels.ops.spec_compose_lanes``.
        """
        k = self.packed.n_patterns
        s = self.tables.i_max
        lanes = np.ascontiguousarray(np.asarray(lane_maps, np.int32))
        if lanes.ndim != 4 or lanes.shape[2:] != (k, s):
            raise ValueError(f"lane_maps must be [B, N, {k}, {s}], "
                             f"got {lanes.shape}")
        b, n = lanes.shape[:2]
        keys = np.asarray(entry_keys, np.int32)
        if keys.shape != (b, n):
            raise ValueError(f"entry_keys must be [{b}, {n}], "
                             f"got {keys.shape}")
        pad_key = self.dev.pad_key
        if n and ((keys[:, 1:] < 0) | (keys[:, 1:] > pad_key)).any():
            raise ValueError("entry_keys[:, 1:] must be boundary keys in "
                             "[0, n_keys] (pad_key = identity)")
        if b == 0 or n == 0:
            return np.zeros((b, k, s), np.int32)
        if n == 1:
            return lanes[:, 0].copy()
        np2 = next_pow2(n)
        if np2 != n:
            lanes = np.concatenate(
                [lanes, np.zeros((b, np2 - n, k, s), np.int32)], axis=1)
            keys = np.concatenate(
                [keys, np.full((b, np2 - n), pad_key, np.int32)], axis=1)
        out = np.asarray(self.executor.compose_lane_maps(lanes, keys))
        self.compose_calls += 1
        return out.astype(np.int32)

    # -- serving hook -------------------------------------------------------

    def _advance_impl(self, states: jnp.ndarray, classes: jnp.ndarray) -> jnp.ndarray:
        def step(st, col):  # st [B], col [B]
            return self.dev.table_pad_j[st, col], None

        out, _ = jax.lax.scan(step, states.astype(jnp.int32), classes.T)
        return out

    def advance_classes(self, states: jnp.ndarray,
                        classes: jnp.ndarray) -> jnp.ndarray:
        """Advance [B] packed states through [B, T] class columns in one scan.

        ``pad_cls`` columns are identity moves (the padded table's extra
        column), which is how callers encode "this position advances no DFA"
        — e.g. special tokens in grammar-constrained serving.
        """
        classes = jnp.asarray(classes, jnp.int32)
        if classes.ndim != 2:
            raise ValueError("advance_classes expects [B, T] classes")
        if classes.shape[1] == 0:
            return jnp.asarray(states, jnp.int32)
        return self._advance_fn(states, classes)

    # -- introspection -------------------------------------------------------

    def perf_report(self) -> dict:
        """Raw-speed introspection for benchmark artifacts.

        Reports the lowering chosen per compiled plan (fused kernel vs jnp
        stages), the in-kernel early-exit skip counter (pallas backend), the
        resolved boundary-key depth and lane width after r=2 shrinking, and
        the autotuner's choice when one was applied — so a BENCH number
        explains *why* it moved.  Never forces the lazy lookahead analysis:
        fields stay ``None`` until the work that builds them has run.
        """
        rep: dict = {
            "backend": self.backend,
            "spec_r": None,
            "lane_width": None,
            "lowerings": {"|".join(map(str, key)): kind
                          for key, kind in
                          self.executor.lowering_kinds.items()},
            "kernel_skipped_steps": None,
            "table_epoch": self.planner.table_epoch,
            # single-table matchers have no block gate; the key exists so
            # perf consumers read one schema (BlockedMatcher fills it in)
            "prefilter_skipped_blocks": None,
            "autotune": dataclasses.asdict(self._tuned)
                        if self._tuned is not None else None,
            # which lowering compose_lane_maps (the OOO gap-close bulk path)
            # actually rode: "compose-kernel-{carry,tree}" on the pallas
            # backend, "compose-scan" (jnp associative_scan) elsewhere;
            # None until the first compose dispatch
            "compose_lowering": next(
                (kind for kind in self.executor.lowering_kinds.values()
                 if kind.startswith("compose")), None),
            "compose_calls": self.compose_calls,
            "retunes": self.retunes,
            "traffic": None,
            # cumulative since construction: tiles and documents (the
            # traffic profile's counts), tile rows dispatched, the real
            # symbols scanned, rows x each tile's longest effective row,
            # and rows x steps the scan loops ran (rows are
            # document-chunks on spec tiles); real / run is the scan's
            # fill, the product of packing (real / bound) and segment
            # rounding (bound / run)
            "dispatch": {"tiles": self.traffic.n_tiles,
                         "docs": self.traffic.n_docs, **self.dispatched},
        }
        obs = self.traffic.snapshot()
        if obs is not None:
            rep["traffic"] = {
                "n_tiles": self.traffic.n_tiles,
                "n_docs": self.traffic.n_docs,
                "batch": obs.batch,
                "median_len": int(np.median(obs.lengths)),
            }
        if "tables" in self.dev.__dict__:  # lookahead analysis already ran
            rep["spec_r"] = self.dev.spec_r
            rep["lane_width"] = self.dev.i_max
        if hasattr(self.executor, "kernel_skipped_steps"):
            rep["kernel_skipped_steps"] = self.executor.kernel_skipped_steps()
        return rep


class BatchMatcher(Matcher):
    """Compatibility shim: the pre-refactor batched engine constructor.

    ``use_kernel=True`` routes chunk matching + merge through the fused
    Pallas kernel (the ``pallas`` backend); everything else is the facade.
    Deprecated — new code should construct ``Matcher(..., backend=...)``
    directly (tests/test_compat_shims.py keeps this path covered).
    """

    def __init__(self, source, *, num_chunks: int = 8, max_buckets: int = 2,
                 batch_tile: int = 64, use_kernel: bool = False):
        import warnings
        warnings.warn("BatchMatcher is a compatibility shim; use "
                      "Matcher(..., backend='pallas'|'local') instead",
                      DeprecationWarning, stacklevel=2)
        super().__init__(source, num_chunks=num_chunks, max_buckets=max_buckets,
                         batch_tile=batch_tile,
                         backend="pallas" if use_kernel else "local")
        self.use_kernel = bool(use_kernel)
