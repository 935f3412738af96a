"""Executor layer: one lane program, lowered per backend.

The matching operation is a single inner loop — indexed transition-table
loads over chunk lanes — and the planner describes it once as a ``LanePlan``
(classify -> entry-seed -> chunk-scan -> merge; see ``engine.plan``).  An
executor backend is a *lowering* of that one plan, not a family of
hand-rolled variants: every backend exposes exactly

    run(plan, bytes_buf, lengths, *, layout=None, entry=None,
        entry_classes=None) -> (finals, absorbed_pos, steps)

and lowers a plan at most once (``lower``; compiled programs are cached by
``plan.key``).  All lowerings consume the same operands —

  * ``bytes_buf [B, W] uint8``  — raw document bytes, zero-padded (byte ->
    class classification happens **on device**, fused into the bucket call;
    ``kernels.ref.classify_pad_ref`` is the host oracle),
  * ``lengths [B] int32``       — real byte counts (positions beyond a
    document's length classify to the identity pad class),
  * ``layout``                  — the planner's ``ChunkLayout``/``MeshLayout``
    for spec plans,
  * ``entry``                   — per-row entry operand selected by
    ``plan.entry``: absent (``ENTRY_STARTS``), exact ``[B, K]`` states
    (``ENTRY_STATES``), or ``[B, K, S]`` cursor lanes plus ``entry_classes
    [B]`` boundary classes (``ENTRY_LANES`` — the streaming device merge),

and must be bit-identical to per-document sequential matching.  The return
is ``(finals [B, K], absorbed_pos [B], steps [n])`` — or ``([B, K, S], pos,
steps)`` for lane plans — where ``absorbed_pos`` is the scan position
(chunk-local for spec, stream for seq) at which every lane of a document
became absorbing, or the ``NO_EXIT`` sentinel, and ``steps`` holds the
symbol steps each of the program's ``n`` scan loops ran.  The loops split
the program's rows evenly (documents for seq plans, document-chunks for
spec plans), so ``rows / n * steps.sum()`` is the row-steps the gather chain
executed: one loop on a single device, one per shard on a mesh, one per
document for the Pallas kernels (derived from their skipped blocks).

Every compiled program is named for what it runs (``seq_scan``,
``spec_scan``, ``compose_scan``), and its stages sit in ``jax.named_scope``
blocks (``classify``, ``seed``, ``chunk_scan``, ``merge``,
``compose_cursor``), so a profiler trace can tell them apart.  The first
call of a newly lowered program, where jit traces and compiles, runs inside
a ``repro.compile`` profiler span.

Backends (the three lowerings):

  * ``LocalExecutor``                  — pure-jnp jitted lowering (the
    oracle), with an absorbing-state early exit: the symbol scan runs in
    segments inside a ``lax.while_loop`` and stops once every lane of every
    document is absorbing.
  * ``LocalExecutor(use_kernel=True)`` — the fused Pallas kernels
    (``kernels.ops.spec_match_merge`` for exact-entry plans,
    ``kernels.ops.spec_match_merge_lanes`` for ``ENTRY_LANES`` — the
    streaming tick rides the fused kernel too, no jnp-stage fallback).
    Both carry an **in-kernel early exit** (symbol blocks after a
    document's lanes all absorb are skipped on the grid; the per-document
    skipped-block counts drain via ``kernel_skipped_steps()``), wrapped in
    an **all-absorbed bucket early exit**: when every row of the bucket is
    already absorbed (or empty), the kernel dispatch is skipped entirely —
    absorbing states self-loop, so returning the entry states (or cursor
    lanes) verbatim is exact.
  * ``engine.sharded.ShardedExecutor`` — the ("doc", "chunk") mesh lowering
    (own module).

**Entry seeding** is one stage, not separate entry points: chunk 0 (and any
chunk at stream position 0) seeds from the pattern starts, the caller's
exact states, or the Eq. 11 candidate rows of each row's boundary class.
``ENTRY_STATES`` is what makes matching *resumable* (a ``streaming
.MatchCursor`` carries states across segment boundaries); ``ENTRY_LANES``
additionally keeps the candidate lane axis and fuses the Eq. 8 cursor
composition (``kernels.ref.cursor_merge_ref``) into the same device call —
the streaming tick's device merge.  ``traces`` counts jit retraces (the
side effect fires at trace time only).
"""

from __future__ import annotations

import contextlib
from typing import Optional, Protocol

import numpy as np

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from ..lvector import merge_scan_lanes_jnp
from .plan import (ENTRY_LANES, ENTRY_STARTS, ENTRY_STATES, DeviceTables,
                   LanePlan)

__all__ = ["Executor", "LaneExecutor", "LocalExecutor", "NO_EXIT"]

NO_EXIT = np.int32(2 ** 30)  # absorbed_pos sentinel: never fully absorbed


class Executor(Protocol):
    """The one-method backend protocol: lower and run a ``LanePlan``."""

    traces: int

    def run(self, plan: LanePlan, bytes_buf: jnp.ndarray,
            lengths: jnp.ndarray, *, layout=None,
            entry: Optional[jnp.ndarray] = None,
            entry_classes: Optional[jnp.ndarray] = None
            ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]: ...

    def steps_for(self, layout) -> int: ...


def _prev_pow2(n: int) -> int:
    return 1 if n <= 1 else 1 << (n.bit_length() - 1)


def named_jit(fn, name: str):
    """``jax.jit`` of ``fn`` under a stable program name (``jit_<name>`` in
    the compiled module and the profiler's ``XLA Modules`` line)."""
    def program(*args):
        return fn(*args)

    program.__name__ = program.__qualname__ = name
    return jax.jit(program)


class LaneExecutor:
    """Shared lane-program stages plus the lowering cache (all backends).

    Subclasses override ``_lower`` (and, when compiled programs depend on
    more than the plan — e.g. the sharded backend's per-batch row specs —
    ``_plan_key``).  The base class owns the stage implementations every
    lowering composes: on-device classification, the early-exit segmented
    scan, entry seeding, and the device cursor merge.
    """

    def __init__(self, tables: DeviceTables, *, num_chunks: int,
                 early_exit_segments: int = 4):
        self.t = tables
        self.num_chunks = int(num_chunks)
        # segments must divide the pow2 scan widths -> round down to a pow2
        self.early_exit_segments = _prev_pow2(max(int(early_exit_segments), 1))
        self.traces = 0
        self._lowered: dict[tuple, object] = {}
        # plan.key -> human-readable lowering name ("spec-kernel",
        # "spec-jnp", "seq-jnp", ...) for bench/introspection reporting
        self.lowering_kinds: dict[tuple, str] = {}
        # per-bucket block-size targets set by the shape autotuner
        # (core.profiling.autotune_spec_shapes); consulted at lowering time,
        # keyed by chunk_len (key 0 = tuned default) — 512 when untuned
        self.spec_l_blk: dict[int, int] = {}
        # bumped by invalidate_layouts() when chunk boundaries move (capacity
        # rebalance); only lowerings that *bake* boundaries fold it into
        # their cache key, so layout-independent programs keep their entries
        self.layout_epoch = 0

    # -- the one entry point ------------------------------------------------

    def run(self, plan: LanePlan, bytes_buf: jnp.ndarray,
            lengths: jnp.ndarray, *, layout=None,
            entry: Optional[jnp.ndarray] = None,
            entry_classes: Optional[jnp.ndarray] = None
            ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
        batch = int(bytes_buf.shape[0])
        fresh = self._plan_key(plan, batch) not in self._lowered
        fn = self.lower(plan, layout=layout, batch=batch)
        args = {ENTRY_STARTS: (), ENTRY_STATES: (entry,),
                ENTRY_LANES: (entry, entry_classes)}[plan.entry]
        with (TraceAnnotation("repro.compile") if fresh
              else contextlib.nullcontext()):
            return fn(bytes_buf, lengths, *args)

    def lower(self, plan: LanePlan, *, layout=None, batch: int = 0):
        """Compiled program for one plan (cached; lowering happens once)."""
        key = self._plan_key(plan, batch)
        fn = self._lowered.get(key)
        if fn is None:
            fn = self._lower(plan, layout, batch)
            self._lowered[key] = fn
        return fn

    def _plan_key(self, plan: LanePlan, batch: int) -> tuple:
        return plan.key

    def invalidate_layouts(self) -> None:
        """Signal that chunk layout boundaries changed (capacity rebalance).

        Bumps ``layout_epoch`` instead of clearing ``_lowered``: backends
        whose compiled programs bake layout boundaries (the sharded spec
        lowering) key on the epoch and re-lower lazily; every
        layout-independent program — seq scans, the local/pallas lowerings,
        which chunk uniformly — survives untouched, and returning to a
        previously-seen layout is never required to recompile what never
        depended on it.
        """
        self.layout_epoch += 1

    def invalidate_block_sizes(self) -> None:
        """Drop compiled programs that baked a ``spec_l_blk`` choice.

        The observed-traffic retune path (``Matcher.maybe_retune``) updates
        ``spec_l_blk`` after construction; only the Pallas spec lowerings
        consult it (at lowering time, as a static block shape), so only
        entries whose kind starts with ``spec-kernel`` drop — everything
        else (seq scans, jnp spec, compose lowerings) keeps its program and
        the new block size takes effect on the next dispatch of each shape.
        """
        stale = [key for key, kind in self.lowering_kinds.items()
                 if kind.startswith("spec-kernel")]
        for key in stale:
            self._lowered.pop(key, None)
            self.lowering_kinds.pop(key, None)

    def retable(self, tables: DeviceTables) -> None:
        """Swap the constant matcher tables underneath the executor (the
        hot pattern swap, ``Matcher.swap_patterns``).

        Every compiled lowering closed over the *old* ``DeviceTables``
        arrays at trace time, so — unlike ``invalidate_layouts`` — there is
        nothing table-independent to keep: the whole cache drops and
        programs re-lower lazily against the new tables.  The planner's
        bumped ``table_epoch`` is stamped into every subsequent
        ``LanePlan.key``, so even an entry that somehow escaped the clear
        could never be looked up again.  ``traces`` keeps counting
        monotonically; unchanged blocks of a ``BlockedMatcher`` swap never
        pass through here, which is what makes their lowering survival
        observable (and asserted) from outside.
        """
        self.t = tables
        self._lowered.clear()
        self.lowering_kinds.clear()

    def _jit_lowering(self, body, name: str):
        """jit a lowering body, named ``name``, under the retrace counter.

        ``body`` takes the plan's runtime operands positionally —
        ``(bytes_buf, lengths[, entry[, entry_classes]])`` per
        ``plan.entry`` — which is exactly how ``run`` calls the compiled
        program, so one wrapper serves every entry mode.  Nothing is
        donated: no output has the byte buffer's shape and dtype, so XLA
        could alias none of it.
        """
        def counted(*args):
            self.traces += 1  # side effect fires at trace time only
            return body(*args)

        return named_jit(counted, name)

    def _lower(self, plan: LanePlan, layout, batch: int):
        """Backend hook: build the compiled program of one plan."""
        if plan.kind == "seq":
            self.lowering_kinds[plan.key] = "seq-jnp"
            return self._lower_seq_local(plan)
        raise NotImplementedError("spec plans need a backend lowering")

    def steps_for(self, layout) -> int:
        return layout.lmax  # lane-parallel wall steps = longest chunk buffer

    # -- stage: classify (the retired host numpy path lives in
    # kernels/ref.classify_pad_ref as the oracle) ---------------------------

    @jax.named_scope("classify")
    def _classify(self, bytes_buf: jnp.ndarray, lengths: jnp.ndarray) -> jnp.ndarray:
        """bytes [B, W] + lengths -> [B, W] class ids, pad_cls past the end."""
        cls = self.t.byte_to_class_j[bytes_buf.astype(jnp.int32)]
        pos = jnp.arange(bytes_buf.shape[1], dtype=jnp.int32)[None, :]
        return jnp.where(pos < lengths[:, None].astype(jnp.int32), cls,
                         jnp.int32(self.t.pad_cls))

    # -- stage: entry seed --------------------------------------------------

    @jax.named_scope("seed")
    def _seed_rows(self, plan: LanePlan, b: int, entry, entry_cls) -> jnp.ndarray:
        """Entry-seed stage for sequential rows: [B, K] exact states, or
        [B, K, S] candidate lanes for lane plans."""
        if plan.entry == ENTRY_STARTS:
            return jnp.broadcast_to(self.t.starts_j[None, :],
                                    (b, self.t.n_patterns))
        if plan.entry == ENTRY_STATES:
            return entry.astype(jnp.int32)
        return self.t.cand_pad_j[entry_cls]            # [B, K, S]

    @jax.named_scope("seed")
    def _seed_chunk0(self, plan: LanePlan, b: int, entry, entry_cls) -> jnp.ndarray:
        """Entry-seed stage for spec chunk 0: [B, 1, K, S] lanes."""
        k, s = self.t.n_patterns, self.t.i_max
        if plan.entry == ENTRY_LANES:
            return self.t.cand_pad_j[entry_cls][:, None]        # [B, 1, K, S]
        e = self._seed_rows(plan, b, entry, entry_cls)          # [B, K]
        return jnp.broadcast_to(e[:, None, :, None], (b, 1, k, s))

    # -- stage: chunk scan with absorbing-state early exit -------------------

    @jax.named_scope("chunk_scan")
    def _segmented_match(self, sym_t: jnp.ndarray, states: jnp.ndarray,
                         eff_len: jnp.ndarray, scan_len: int,
                         early_exit: bool = True
                         ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
        """Scan ``states [R, S]`` through ``sym_t [L, R]`` symbol columns in
        segments, stopping once every document is *done*: all its lanes are
        absorbing, or the scan has passed its real symbols (``eff_len [B]``
        per-doc; pure-padding rows of a partial tile are done immediately,
        so they never pin the loop to the full scan).

        Rows are doc-major (R = B * rows_per_doc).  Returns (final states,
        absorbed_pos [B], steps [1]) with ``absorbed_pos`` the first segment
        boundary at which a document's lanes were all absorbing (sentinel
        ``NO_EXIT`` otherwise) and ``steps`` the symbol steps the loop ran
        over all R rows.  Exactness: absorbing states self-loop on every
        class and padding is the identity column, so skipping the remaining
        symbols of a done document is bit-identical.
        """
        table = self.t.table_pad_j
        absorbing = self.t.absorbing_j
        b = eff_len.shape[0]

        def seg_scan(st, cols):
            def step(s, row):
                return table[s, row[:, None]], None
            out, _ = jax.lax.scan(step, st, cols)
            return out

        segs = min(self.early_exit_segments if early_exit else 1, scan_len)
        pos0 = jnp.full((b,), NO_EXIT, jnp.int32)
        if segs <= 1 or scan_len == 0:
            return (seg_scan(states, sym_t), pos0,
                    jnp.full((1,), scan_len, jnp.int32))
        seg_len = scan_len // segs

        def cond(carry):
            _, g, _, all_done = carry
            return (g < segs) & ~all_done

        def body(carry):
            st, g, pos, _ = carry
            cols = jax.lax.dynamic_slice_in_dim(sym_t, g * seg_len, seg_len,
                                                axis=0)
            st = seg_scan(st, cols)
            doc_abs = absorbing[st].reshape(b, -1).all(axis=1)
            boundary = ((g + 1) * seg_len).astype(jnp.int32)
            pos = jnp.where(doc_abs & (pos == NO_EXIT), boundary, pos)
            done = doc_abs | (boundary >= eff_len.astype(jnp.int32))
            return st, g + 1, pos, done.all()

        states, g, pos, _ = jax.lax.while_loop(
            cond, body, (states, jnp.int32(0), pos0, jnp.bool_(False)))
        return states, pos, (g * seg_len).astype(jnp.int32)[None]

    # -- stage: device cursor merge (lane plans) -----------------------------

    @jax.named_scope("compose_cursor")
    def _compose_cursor(self, cursor_lanes: jnp.ndarray,
                        seg_lanes: jnp.ndarray,
                        entry_cls: jnp.ndarray) -> jnp.ndarray:
        """Eq. 8 composition of cursor lanes with a segment's lane map, on
        device — must stay bit-identical to ``kernels.ref.cursor_merge_ref``
        (tests/test_device_merge.py asserts so on every backend)."""
        t = self.t
        lane = t.cidx_pad_j[entry_cls[:, None, None], cursor_lanes]
        hit = jnp.take_along_axis(seg_lanes, jnp.maximum(lane, 0), axis=2)
        sk = t.sinks_j[None, :, None]
        out = jnp.where(lane < 0, jnp.where(sk >= 0, sk, cursor_lanes), hit)
        out = jnp.where((entry_cls == t.pad_key)[:, None, None],
                        cursor_lanes, out)
        return out.astype(jnp.int32)

    # -- stage: bulk scan-compose (the OOO gap-close path) -------------------

    def compose_lane_maps(self, lane_maps, entry_keys) -> jnp.ndarray:
        """Fold runs of candidate-keyed lane maps in one log-depth scan.

        ``lane_maps [B, N, K, S]`` + ``entry_keys [B, N]`` -> ``[B, K, S]``
        compositions (the last scan prefix), via ``lvector
        .merge_scan_lanes_jnp`` — one ``associative_scan`` dispatch for the
        whole batch of runs.  Keys equal to ``pad_key`` are right
        identities, so ragged runs arrive padded to a shared N; the compiled
        program is cached per N (plain jnp: the sharded backend runs it
        replicated, bit-identical by construction).
        """
        key = ("compose_scan", int(lane_maps.shape[1]))
        fn = self._lowered.get(key)
        if fn is None:
            t = self.t

            def body(lanes, keys):
                out = merge_scan_lanes_jnp(lanes, keys, t.cidx_pad_j,
                                           t.sinks_j, pad_key=t.pad_key,
                                           axis=1)
                return out[:, -1]

            fn = self._jit_lowering(body, "compose_scan")
            self._lowered[key] = fn
            self.lowering_kinds[key] = "compose-scan"
        return fn(jnp.asarray(lane_maps, jnp.int32),
                  jnp.asarray(entry_keys, jnp.int32))

    # -- seq lowering (shared: single-device rows; also the per-shard body
    # of the sharded backend's document-axis split) --------------------------

    def _seq_body(self, plan: LanePlan, bytes_buf: jnp.ndarray,
                  lengths: jnp.ndarray, entry=None, entry_cls=None):
        """Batched Algorithm 1 as a lane program: classify -> entry-seed ->
        scan (rows are independent; the merge stage is a no-op)."""
        b, w = bytes_buf.shape
        cls = self._classify(bytes_buf, lengths)
        init = self._seed_rows(plan, b, entry, entry_cls)
        rows = init.reshape(b, -1).astype(jnp.int32)
        finals, pos, steps = self._segmented_match(
            cls.T, rows, jnp.minimum(lengths, w), w,
            early_exit=plan.early_exit)
        if plan.entry == ENTRY_LANES:
            seg = finals.reshape(b, self.t.n_patterns, self.t.i_max)
            return self._compose_cursor(entry.astype(jnp.int32), seg,
                                        entry_cls), pos, steps
        return finals, pos, steps

    def _lower_seq_local(self, plan: LanePlan):
        return self._jit_lowering(
            lambda *args: self._seq_body(plan, *args), "seq_scan")

    # -- spec stage bodies (shared by the local jnp and kernel lowerings) ----

    def _spec_stages(self, plan: LanePlan, bytes_buf: jnp.ndarray,
                     lengths: jnp.ndarray, entry, entry_cls):
        """classify + chunking + entry-seed of the uniform speculative path:
        returns (body [B, C, Lc] classes, la [B, C] boundary keys, init
        [B, C, K*S] lanes).

        Boundary keys follow ``DeviceTables.spec_r``: the class of the last
        byte before each chunk (r=1, the paper's Eq. 11), or the pair key
        ``c_prev * n_classes + c_last`` of the two preceding bytes (r=2,
        Eq. 13).  Padding is always a document suffix, so a padded last byte
        means the whole following chunk is padding — its key degrades to the
        identity ``pad_key`` and the merge passes it through.
        """
        t = self.t
        b, w = bytes_buf.shape
        c = self.num_chunks
        lc = w // c
        k, s = t.n_patterns, t.i_max
        cls = self._classify(bytes_buf, lengths)
        body = cls.reshape(b, c, lc)
        if t.spec_r == 2 and lc < 2:
            raise ValueError(
                f"spec_r=2 boundary keys need chunk_len >= 2, got {lc}")
        start = self._seed_chunk0(plan, b, entry, entry_cls)   # [B, 1, K, S]
        with jax.named_scope("seed"):
            last1 = body[:, :-1, -1]                           # [B, C-1]
            if t.spec_r == 2:
                key = body[:, :-1, -2] * jnp.int32(t.pad_cls) + last1
                key = jnp.where(last1 == t.pad_cls, jnp.int32(t.pad_key),
                                key)
            else:
                key = last1  # r=1: the key *is* the class (pad == pad_key)
            la = jnp.concatenate([jnp.zeros((b, 1), jnp.int32), key], axis=1)
            cand = t.cand_pad_j[la[:, 1:]]                     # [B, C-1, K, S]
            init = jnp.concatenate([start, cand], axis=1).reshape(b, c,
                                                                  k * s)
        return body, la, init

    def _spec_body(self, plan: LanePlan, bytes_buf: jnp.ndarray,
                   lengths: jnp.ndarray, entry=None, entry_cls=None):
        """Fused classify/chunk/candidate-gather/match/merge, one bucket.

        Chunk 0's entry seed is exact for ``starts``/``states`` plans (all
        its lanes carry the entry state) and candidate-keyed for lane plans;
        later chunks stay speculative from the Eq. 11 candidate rows.  Lane
        plans keep the [K, S] carry through the merge fold and compose the
        caller's cursor lanes on device.
        """
        from ...kernels import ref as kref

        t = self.t
        b, w = bytes_buf.shape
        c = self.num_chunks
        lc = w // c
        k, s = t.n_patterns, t.i_max
        body, la, init = self._spec_stages(plan, bytes_buf, lengths, entry,
                                           entry_cls)
        sym_t = body.reshape(b * c, lc).T                      # [Lc, B*C]
        # per-chunk effective fill: a doc's deepest chunk-local real symbol
        lvecs, pos, steps = self._segmented_match(
            sym_t, init.reshape(b * c, k * s), jnp.minimum(lengths, lc), lc,
            early_exit=plan.early_exit)
        lv = lvecs.reshape(b, c, k, s)
        if plan.entry == ENTRY_LANES:
            with jax.named_scope("merge"):
                seg = kref.spec_merge_lanes_ref(lv, la, t.cidx_pad_j,
                                                t.sinks_j, pad_cls=t.pad_key)
            return self._compose_cursor(entry.astype(jnp.int32), seg,
                                        entry_cls), pos, steps
        with jax.named_scope("merge"):
            finals = kref.spec_merge_ref(lv, la, t.cidx_pad_j, t.sinks_j,
                                         pad_cls=t.pad_key)
        return finals, pos, steps


class LocalExecutor(LaneExecutor):
    """Single-device lowering: pure-jnp reference or fused Pallas kernel.

    The speculative lowering fuses classification residue, uniform chunking,
    candidate gather, chunk matching, and the Eq. 8 merge in one jitted call
    per bucket; only the [B, K] final-state array crosses back to the host.
    With ``use_kernel=True`` every spec plan — exact-entry *and*
    ``ENTRY_LANES`` — dispatches a fused Pallas kernel behind an
    all-absorbed bucket early exit, and the kernel itself skips symbol
    blocks past the point a document's lanes all absorb (the in-kernel early
    exit; per-document skipped-block counts drain via
    ``kernel_skipped_steps()``).
    """

    def __init__(self, tables: DeviceTables, *, num_chunks: int,
                 use_kernel: bool = False, early_exit_segments: int = 4,
                 compose_mode: str = "carry"):
        super().__init__(tables, num_chunks=num_chunks,
                         early_exit_segments=early_exit_segments)
        self.use_kernel = bool(use_kernel)
        # which spec_compose_lanes kernel the OOO gap-close fold rides:
        # "carry" (block-sequential grid carry) or "tree" (in-kernel
        # Blelloch reduce); benchmarks measure both
        self.compose_mode = compose_mode
        # device arrays of per-doc skipped symbol blocks, appended per kernel
        # dispatch and summed lazily (no sync on the hot path)
        self._skipped_log: list = []
        self._skipped_total = 0

    def kernel_skipped_steps(self) -> int:
        """Total symbol blocks skipped by the in-kernel early exit so far.

        Draining the log syncs the pending device arrays — call this from
        tests/benchmarks, not between hot-path ticks.
        """
        while self._skipped_log:
            self._skipped_total += int(np.asarray(self._skipped_log.pop()).sum())
        return self._skipped_total

    def compose_lane_maps(self, lane_maps, entry_keys) -> jnp.ndarray:
        """OOO gap-close fold, lowered to the ``spec_compose_lanes`` Pallas
        kernel when this executor runs the kernel backend.

        Same contract as the base jnp lowering (``("compose_scan", N)``):
        ragged runs arrive right-padded with ``pad_key`` identities and only
        the whole-run composition returns.  The kernel program is cached per
        ``("compose_kernel", N)`` and shows up as ``"compose-kernel"`` in
        ``lowering_kinds`` — ``Matcher.perf_report()`` surfaces which one
        the OOO tick actually rode (CI asserts no silent jnp fallback on
        the Pallas backend).
        """
        if not self.use_kernel:
            return super().compose_lane_maps(lane_maps, entry_keys)
        key = ("compose_kernel", int(lane_maps.shape[1]))
        fn = self._lowered.get(key)
        if fn is None:
            from ...kernels import ops as kops

            t = self.t
            mode = self.compose_mode

            def body(lanes, keys):
                return kops.spec_compose_lanes(
                    lanes, keys, t.cidx_pad_j, t.sinks_j,
                    pad_key=t.pad_key, mode=mode)

            fn = self._jit_lowering(body, "compose_scan")
            self._lowered[key] = fn
            self.lowering_kinds[key] = f"compose-kernel-{mode}"
        return fn(jnp.asarray(lane_maps, jnp.int32),
                  jnp.asarray(entry_keys, jnp.int32))

    def _lower(self, plan: LanePlan, layout, batch: int):
        if plan.kind == "seq":
            self.lowering_kinds[plan.key] = "seq-jnp"
            return self._lower_seq_local(plan)
        if self.use_kernel:
            self.lowering_kinds[plan.key] = (
                "spec-kernel-lanes" if plan.entry == ENTRY_LANES
                else "spec-kernel")
            return self._lower_spec_kernel(plan)
        self.lowering_kinds[plan.key] = "spec-jnp"
        return self._jit_lowering(
            lambda *args: self._spec_body(plan, *args), "spec_scan")

    def _lower_spec_kernel(self, plan: LanePlan):
        """Fused Pallas lowering: bucket-level + in-kernel early exit.

        A bucket whose every row is already absorbed — or empty — cannot
        move any lane: absorbing states self-loop on every class, so
        returning the entry states (or, for lane plans, the caller's cursor
        lanes — composition through a restricted map fixes absorbing states)
        verbatim is bit-identical and the whole kernel dispatch is skipped
        (``lax.cond``).  This is the streaming case where a tick's segments
        all belong to decided streams.  Inside the kernel, the symbol-block
        grid additionally skips blocks once a single document's lanes all
        absorb mid-scan; the per-document skipped counts convert to the
        standard ``absorbed_pos`` contract here (block granularity — the jnp
        lowering reports segment granularity, both are upper bounds of the
        true absorb position).
        """
        from ...kernels import ops as kops

        t = self.t
        lanes_mode = plan.entry == ENTRY_LANES
        lc = plan.chunk_len
        l_blk, l_pad = kops._pad_to_block(
            lc, self.spec_l_blk.get(lc, self.spec_l_blk.get(0, 512)))
        l_blocks = l_pad // l_blk

        def kernel_body(plan, bytes_buf, lengths, entry=None, entry_cls=None):
            b = bytes_buf.shape[0]
            if lanes_mode:
                e = entry.astype(jnp.int32)      # [B, K, S] cursor lanes
            else:
                e = self._seed_rows(plan, b, entry, None)       # [B, K]

            def run_kernel():
                # classify/chunk/candidate-gather prep lives *inside* the
                # taken branch so an all-absorbed bucket skips it too, not
                # just the kernel dispatch
                body, la, init = self._spec_stages(plan, bytes_buf, lengths,
                                                   entry, entry_cls)
                absorbing = t.absorbing_j.astype(jnp.int32)
                if lanes_mode:
                    lanes, skipped, _ = kops.spec_match_merge_lanes(
                        t.table_pad_j, body, init, la, t.cidx_pad_j,
                        t.sinks_j, absorbing, pad_cls=t.pad_cls,
                        pad_key=t.pad_key, early_exit=plan.early_exit,
                        l_blk=l_blk)
                    return self._compose_cursor(e, lanes, entry_cls), skipped
                finals, skipped, _ = kops.spec_match_merge(
                    t.table_pad_j, body, init, la, t.cidx_pad_j, t.sinks_j,
                    absorbing, pad_cls=t.pad_cls, pad_key=t.pad_key,
                    early_exit=plan.early_exit, l_blk=l_blk)
                return finals, skipped

            def ran(skipped):  # the symbols a document's blocks scanned
                return (jnp.int32(l_blocks) - skipped) * jnp.int32(l_blk)

            # steps: one scan per document over its C chunks, the blocks it
            # did not skip (none when the bucket's dispatch was skipped)
            if not plan.early_exit:  # same contract as the jnp lowerings
                out, skipped = run_kernel()
                return (out, jnp.full((b,), NO_EXIT, jnp.int32), skipped,
                        ran(skipped))
            doc_abs = t.absorbing_j[e].reshape(b, -1).all(axis=1)
            done = doc_abs | (lengths.astype(jnp.int32) <= 0)
            zero = jnp.zeros((b,), jnp.int32)
            out, skipped = jax.lax.cond(
                done.all(), lambda: (e.astype(jnp.int32), zero), run_kernel)
            pos = jnp.where(skipped > 0, ran(skipped), NO_EXIT)
            pos = jnp.where(done.all() & doc_abs, jnp.int32(0), pos)
            return out, pos, skipped, jnp.where(done.all(), 0, ran(skipped))

        jit_fn = self._jit_lowering(lambda *args: kernel_body(plan, *args),
                                    "spec_scan")

        def wrapper(*args):
            out, pos, skipped, steps = jit_fn(*args)
            self._skipped_log.append(skipped)
            return out, pos, steps

        return wrapper
