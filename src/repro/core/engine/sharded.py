"""Mesh-sharded lowering: capacity-balanced matching on a (doc, chunk) mesh.

The paper's cloud result (288 EC2 cores) comes from two ingredients: split
the input across workers, and size each worker's slice by its *measured
matching capacity* (Eq. 1, ``core.profiling.profile_workers``).  This
executor is the device-mesh lowering of the one ``LanePlan`` (see
``engine.executors``), on a 2-D ``("doc", "chunk")`` mesh
(``launch.mesh.make_matcher_mesh``):

  * the **chunk axis is sharded over "chunk"** (``jax.shard_map``):
    each device matches its contiguous run of chunks x candidate lanes
    locally;
  * the **document axis is sharded over "doc"**: mesh row ``r`` owns tile
    row-block ``r`` outright, so batch sizes beyond one host's memory scale
    along "doc" with no extra traffic — speculative documents no longer
    replicate on every device.  Physical row-blocks keep the uniform
    ``batch_tile / Dd`` SPMD shape even under capacity-weighted *document*
    placement: ragged doc tiling (``plan.MeshLayout.tile_rows``) assigns
    capacity-proportional document *counts* per row by routing real
    documents to row-blocks host-side — a slow row simply receives more
    zero-length pad rows, and this lowering never sees the difference (the
    facade inverts the placement when scattering results);
  * chunk boundaries come from the planner's layout — uniform, or
    capacity-weighted via the paper's Eqs. 2–7 so a device with twice the
    measured capacity receives twice the real symbols.  On a 2-D mesh each
    doc row-block gets its *own* ``ChunkLayout`` weighted by that mesh row's
    devices (``plan.MeshLayout``); trailing identity-pad columns equalize the
    SPMD buffer shapes and advance no DFA;
  * devices exchange **only the per-chunk L-vector lane states**
    (``[C, B/Dd, K, S]`` int32, independent of chunk length) in one
    ``all_gather`` **over the "chunk" axis only** — doc shards never
    communicate, and the documents' bytes never cross devices;
  * each doc shard folds its gathered lane states per document (Eq. 8),
    exactly as the single-device lowering, so results are bit-identical to
    sequential matching for any mesh shape and any capacity profile
    (tests/test_sharded_executor.py sweeps 1x1, 2x4, 4x2, 8x1).

**Entry modes** are the plan's, not the backend's: exact entry states shard
over "doc" with their rows (``ENTRY_STATES``), and lane plans
(``ENTRY_LANES``) additionally shard the ``[B, K, S]`` cursor lanes and
boundary classes over "doc" and run the device cursor merge per doc shard
after the chunk fold (``distributed.sharding.matcher_lane_specs``).

The **sequential plan** needs no exchange at all: short documents are
independent rows, so the document axis shards over *both* mesh axes
jointly (``distributed.sharding.doc_batch_spec``) and every device scans
``B / (Dd * Dc)`` rows.

See docs/architecture.md for the data-flow diagram and the "adding an
executor backend" guide.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from .executors import NO_EXIT, LaneExecutor
from .plan import (ENTRY_LANES, ENTRY_STARTS, ChunkLayout, DeviceTables,
                   LanePlan, MeshLayout)

__all__ = ["ShardedExecutor"]


class ShardedExecutor(LaneExecutor):
    """shard_map-backed lowering over a ("doc", "chunk") matcher mesh.

    Parameters
    ----------
    tables      : shared ``DeviceTables`` bundle.
    num_chunks  : total chunk count C (a multiple of the mesh chunk extent;
                  the planner rounds up).
    mesh        : mesh from ``launch.mesh.make_matcher_mesh`` (legacy 1-D
                  "data" meshes count as doc extent 1); defaults to a 1-D
                  chunk mesh over all local devices.
    """

    def __init__(self, tables: DeviceTables, *, num_chunks: int,
                 mesh=None, early_exit_segments: int = 4):
        super().__init__(tables, num_chunks=num_chunks,
                         early_exit_segments=early_exit_segments)
        from ...launch.mesh import make_matcher_mesh, matcher_mesh_extents
        if mesh is None:
            mesh = make_matcher_mesh()
        # the lowering places every operand itself (shard_map specs; the
        # tables are program constants, replicated by the compiler), so it
        # runs on Auto axes whatever axis types the caller's mesh carries:
        # Explicit axes (jax.make_mesh's default) refuse both the table
        # closure and the out[0] slice
        self.mesh = jax.sharding.Mesh(
            mesh.devices, mesh.axis_names,
            axis_types=(jax.sharding.AxisType.Auto,) * len(mesh.axis_names))
        self.doc_shards, self.chunk_shards = matcher_mesh_extents(mesh)
        self.chunk_axis = "chunk" if "chunk" in mesh.axis_names else "data"
        self.devices = self.doc_shards * self.chunk_shards
        if self.num_chunks % self.chunk_shards != 0:
            raise ValueError(
                f"num_chunks={self.num_chunks} must be a multiple of the mesh "
                f"chunk extent {self.chunk_shards} (the planner rounds up "
                "for you)")

    # -- lowering dispatch ---------------------------------------------------

    def _plan_key(self, plan: LanePlan, batch: int) -> tuple:
        # seq programs shard the row axis, so their compiled form depends on
        # the tile row count (doc_batch_spec); spec programs do not — but
        # they *bake* the layout's chunk boundaries as static slices, so a
        # capacity rebalance (layout_epoch bump) keys them to a fresh
        # lowering while every seq entry survives the rebalance untouched
        if plan.kind == "seq":
            return plan.key + (batch,)
        return plan.key + (self.layout_epoch,)

    def _lower(self, plan: LanePlan, layout, batch: int):
        if plan.kind == "seq":
            if self.devices == 1 or batch % self.devices != 0:
                # indivisible tiles fall back to the single-device lowering
                self.lowering_kinds[self._plan_key(plan, batch)] = "seq-jnp"
                return self._lower_seq_local(plan)
            self.lowering_kinds[self._plan_key(plan, batch)] = "seq-sharded"
            return self._lower_seq_sharded(plan, batch)
        self.lowering_kinds[self._plan_key(plan, batch)] = "spec-sharded"
        return self._lower_spec_sharded(plan, layout)

    # -- sequential plan: document axis over both mesh axes ------------------

    def _lower_seq_sharded(self, plan: LanePlan, batch: int):
        """Short documents are independent rows, so the document axis shards
        cleanly over every mesh axis jointly (doc_batch_spec) — each device
        classifies and scans B/(Dd*Dc) rows, nothing is exchanged.  Entry
        states (and lane-plan cursor lanes + boundary classes) split
        row-wise with their documents."""
        from jax.sharding import PartitionSpec as P

        from ...distributed.sharding import doc_batch_spec
        from jax import shard_map

        row_ax = tuple(doc_batch_spec(self.mesh, batch))
        buf_spec, len_spec = P(*row_ax, None), P(*row_ax)
        # the specs follow the plan's entry arity; the body is the shared one
        # (each shard's scan loop reports its own steps: [1] per shard)
        if plan.entry == ENTRY_STARTS:
            in_specs = (buf_spec, len_spec)
            out_specs = (buf_spec, len_spec, len_spec)
        elif plan.entry == ENTRY_LANES:
            in_specs = (buf_spec, len_spec, P(*row_ax, None, None), len_spec)
            out_specs = (P(*row_ax, None, None), len_spec, len_spec)
        else:
            in_specs = (buf_spec, len_spec, P(*row_ax, None))
            out_specs = (buf_spec, len_spec, len_spec)
        body = shard_map(lambda *args: self._seq_body(plan, *args),
                         mesh=self.mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
        return self._jit_lowering(body, "seq_scan")

    # -- speculative plan ----------------------------------------------------

    def _layout_rows(self, layout: ChunkLayout | MeshLayout
                     ) -> tuple[ChunkLayout, ...]:
        """Per-doc-shard row layouts; a plain ChunkLayout broadcasts to every
        row (uniform boundaries on every row-block)."""
        if isinstance(layout, MeshLayout):
            if layout.doc_shards != self.doc_shards:
                raise ValueError(f"layout has {layout.doc_shards} doc shards, "
                                 f"mesh has {self.doc_shards}")
            return layout.rows
        return (layout,) * self.doc_shards

    def _lower_spec_sharded(self, plan: LanePlan,
                            layout: ChunkLayout | MeshLayout):
        """Jit one bucket width; every row-block's boundaries are baked in as
        static slices (deterministic per width, so the cache key is the
        plan)."""
        from ...distributed.sharding import (matcher_chunk_specs,
                                             matcher_lane_specs)
        from jax import shard_map

        t = self.t
        rows = self._layout_rows(layout)
        lmax = max(r.lmax for r in rows)
        n_chunks = rows[0].num_chunks
        row_bounds = [list(zip(r.starts.tolist(), r.ends.tolist()))
                      for r in rows]
        row_exact = [r.exact.copy() for r in rows]
        chunk_ax = self.chunk_axis
        lanes_mode = plan.entry == ENTRY_LANES
        if lanes_mode:
            in_specs, out_spec = matcher_lane_specs(self.mesh)
        else:
            in_specs, out_spec = matcher_chunk_specs(self.mesh)
        table_pad, cand_pad, cidx_pad = (t.table_pad_j, t.cand_pad_j,
                                         t.cidx_pad_j)

        @jax.named_scope("chunk_scan")
        def scan_chunks(chunk_loc, init):
            """Per-device chunk-scan stage over this shard's lanes."""
            c_loc, b_loc = chunk_loc.shape[0], chunk_loc.shape[1]
            k, s = t.n_patterns, t.i_max
            sym_t = chunk_loc.reshape(c_loc * b_loc, lmax).T

            def step(st, row):
                return table_pad[st, row[:, None]], None

            lvecs, _ = jax.lax.scan(
                step, init.reshape(c_loc * b_loc, k * s).astype(jnp.int32),
                sym_t)
            return lvecs.reshape(c_loc, b_loc, k, s)

        @jax.named_scope("merge")
        def gather_chunk_axis(lvecs, la_loc, exact_loc):
            # the only cross-device exchange, and only over "chunk": lane
            # states, not symbols; doc shards stay silent
            lv_all = jax.lax.all_gather(lvecs, chunk_ax, axis=0, tiled=True)
            la_all = jax.lax.all_gather(la_loc, chunk_ax, axis=0, tiled=True)
            ex_all = jax.lax.all_gather(exact_loc, chunk_ax, axis=0,
                                        tiled=True)
            return lv_all, la_all, ex_all

        def body(chunk_loc, la_loc, exact_loc, entry_loc):
            # chunk_loc [C_loc, B_loc, Lmax]; la_loc/exact_loc [C_loc,
            # B_loc]; entry_loc [B_loc, K] — this doc shard's segment entry
            # states; exact chunks (stream position 0) seed from them instead
            # of the Eq. 11 candidates.  All rows of this shard belong to one
            # doc row-block, so they share one set of chunk boundaries.
            c_loc, b_loc = chunk_loc.shape[0], chunk_loc.shape[1]
            k, s = t.n_patterns, t.i_max
            with jax.named_scope("seed"):
                cand = cand_pad[la_loc]                # [C_loc, B_loc, K, S]
                start = jnp.broadcast_to(
                    entry_loc.astype(jnp.int32)[None, :, :, None],
                    (c_loc, b_loc, k, s))
                init = jnp.where(exact_loc[:, :, None, None], start, cand)
            lv_all, la_all, ex_all = gather_chunk_axis(
                scan_chunks(chunk_loc, init), la_loc, exact_loc)
            # every chunk device of this mesh row now folds the same gathered
            # states; return the copy behind a leading chunk-axis dim so the
            # out spec mentions every mesh axis (see matcher_chunk_specs)
            return self._merge_gathered(lv_all, la_all, ex_all,
                                        cidx_pad)[None]

        def body_lanes(chunk_loc, la_loc, exact_loc, lanes_loc, ecls_loc):
            # Lane plan: exact chunks seed from the Eq. 11 candidate row of
            # each document's boundary class (``ecls_loc [B_loc]``) — the
            # segment is matched *independently* of the prefix — and after
            # the chunk fold the caller's cursor lanes compose on device
            # (the streaming device merge).
            with jax.named_scope("seed"):
                cand = cand_pad[la_loc]
                seed = jnp.broadcast_to(cand_pad[ecls_loc][None],
                                        cand.shape)
                init = jnp.where(exact_loc[:, :, None, None], seed, cand)
            lv_all, la_all, ex_all = gather_chunk_axis(
                scan_chunks(chunk_loc, init), la_loc, exact_loc)
            seg = self._merge_gathered(lv_all, la_all, ex_all, cidx_pad,
                                       lanes=True)
            return self._compose_cursor(lanes_loc.astype(jnp.int32), seg,
                                        ecls_loc)[None]

        sharded_body = shard_map(body_lanes if lanes_mode else body,
                                 mesh=self.mesh, in_specs=in_specs,
                                 out_specs=out_spec, check_vma=False)

        def run(bytes_buf, lengths, entry, entry_cls):
            b, w = bytes_buf.shape
            if b % self.doc_shards:
                raise ValueError(f"batch of {b} rows does not split over "
                                 f"{self.doc_shards} doc shards (raise "
                                 "batch_tile to a doc-shard multiple)")
            rps = b // self.doc_shards
            cls = self._classify(bytes_buf, lengths)     # [B, W]
            chunk_buf, la, ex = chunk_operands(cls, rps)
            if lanes_mode:
                out = sharded_body(chunk_buf, la, ex,
                                   entry.astype(jnp.int32), entry_cls)[0]
            else:
                out = sharded_body(chunk_buf, la, ex, entry)[0]
            # each device's scan runs all lmax steps over its C * B / devices
            # document-chunks: one loop per device
            return (out, jnp.full((b,), NO_EXIT, jnp.int32),
                    jnp.full((self.devices,), lmax, jnp.int32))

        @jax.named_scope("seed")
        def chunk_operands(cls, rps):
            """[C, B, Lmax] chunk symbols, [C, B] boundary keys and exact
            flags from the [B, W] classes."""
            b, w = cls.shape
            # one extra identity-pad column makes column index w the "no
            # symbol here" slot — chunk tails past a boundary and the absent
            # predecessor of exact chunks both point at it
            cls_pad = jnp.pad(cls, ((0, 0), (0, 1)),
                              constant_values=t.pad_cls)
            # static (trace-time) chunk boundaries per (chunk, row):
            # row-block r's documents read row r's chunks.  The [C, B, Lmax]
            # gather map is built on device from them — baked, it would be
            # a program constant four times the size of the byte buffer
            def per_row(rows):  # [Dd, C] -> [C, B]
                return np.repeat(np.asarray(rows).T, rps, axis=1)

            starts = per_row([[s0 for s0, _ in rb] for rb in row_bounds])
            lens = per_row([[e0 - s0 for s0, e0 in rb] for rb in row_bounds])
            ex_np = per_row(row_exact).astype(bool)
            if t.spec_r == 2 and (starts == 1).any():
                # ChunkLayout.MIN_CUT keeps interior cuts >= 2
                raise ValueError("spec_r=2 boundary keys need chunk cuts "
                                 ">= 2 symbols into the stream")
            la_idx = np.where(starts > 0, starts - 1, w).astype(np.int32)
            la2_idx = np.where(starts > 1, starts - 2, w).astype(np.int32)
            span = jnp.arange(lmax, dtype=jnp.int32)
            col_idx = jnp.where(span < jnp.asarray(lens, jnp.int32)[..., None],
                                jnp.asarray(starts, jnp.int32)[..., None]
                                + span, jnp.int32(w))
            rows_b = jnp.arange(b, dtype=jnp.int32)
            chunk_buf = cls_pad[rows_b[None, :, None], col_idx]  # [C, B, Lmax]
            la1 = cls_pad[rows_b[None, :], jnp.asarray(la_idx)]  # [C, B]
            if t.spec_r == 2:
                la2 = cls_pad[rows_b[None, :], jnp.asarray(la2_idx)]
                la = jnp.where(la1 == t.pad_cls, jnp.int32(t.pad_key),
                               la2 * jnp.int32(t.pad_cls) + la1)
            else:
                la = la1  # r=1: the key *is* the class (pad_cls == pad_key)
            return chunk_buf, la, jnp.asarray(ex_np)     # ex: [C, B] bool

        if lanes_mode:
            return self._jit_lowering(run, "spec_scan")
        if plan.entry == ENTRY_STARTS:
            def run0(bytes_buf, lengths):
                b = bytes_buf.shape[0]
                e = jnp.broadcast_to(t.starts_j[None, :], (b, t.n_patterns))
                return run(bytes_buf, lengths, e, None)

            return self._jit_lowering(run0, "spec_scan")
        return self._jit_lowering(
            lambda bytes_buf, lengths, entry: run(bytes_buf, lengths, entry,
                                                  None), "spec_scan")

    @jax.named_scope("merge")
    def _merge_gathered(self, lv_all: jnp.ndarray, la_all: jnp.ndarray,
                        exact_all: jnp.ndarray, cidx_pad: jnp.ndarray,
                        lanes: bool = False) -> jnp.ndarray:
        """Eq. 8 fold over gathered chunk lane states, with exact-chunk flags.

        lv_all [C, B_loc, K, S]; la_all/exact_all [C, B_loc] — a chunk
        starting at stream position 0 is matched exactly from its entry
        states (or candidate-keyed from the boundary class, for lane plans),
        so the merge reads its lanes instead of a candidate lookup.  Every
        local row belongs to the same doc row-block (shard_map places whole
        row-blocks), so the per-chunk exact flags are constant across the
        local rows and column 0 carries them.  Delegates to the one shared
        merge definition (``kernels.ref.spec_merge_ref`` /
        ``spec_merge_lanes_ref``, doc-major) so sharded and local stay
        bit-identical by construction.
        """
        from ...kernels.ref import spec_merge_lanes_ref, spec_merge_ref

        t = self.t
        fold = spec_merge_lanes_ref if lanes else spec_merge_ref
        return fold(jnp.swapaxes(lv_all, 0, 1), la_all.T,
                    cidx_pad, t.sinks_j, pad_cls=t.pad_key,
                    exact=exact_all[:, 0])
