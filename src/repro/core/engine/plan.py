"""Planner layer: everything decided *before* a device call.

The planner owns the four host-side decisions of the batched matching
pipeline and freezes them into an explicit ``MatchPlan`` that every executor
backend consumes unchanged:

  * **spec-vs-seq split** — documents shorter than ``4 * num_chunks`` take the
    batched sequential scan (one fused call for all of them), the rest take
    the speculative chunk path;
  * **shape bucketing** — speculative documents are grouped by
    ``next_pow2(ceil(n / C))`` chunk length; bucket keys are *sticky* across
    calls (``Planner`` keeps the compiled-key set) and fresh keys merge upward
    until the lifetime ``max_buckets`` shape budget is respected;
  * **chunk partitioning / capacity weighting** — a ``ChunkLayout`` maps the
    padded symbol width of a bucket onto per-device chunk boundaries, either
    uniform or capacity-weighted via the paper's Eqs. 1–7
    (``core.partition.weighted_partition`` with per-worker weights from
    ``core.profiling.profile_workers``);
  * **lookahead-table selection** — the packed Eq. 11 candidate tables plus
    the identity-pad-column device arrays are bundled once in
    ``DeviceTables`` and shared by all executors.

Nothing in this module touches a device except ``DeviceTables.build`` (which
uploads the constant tables); planning is pure numpy and therefore cheap to
re-run per batch and trivial to test.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp

from ..automata import PackedDFA
from ..lookahead import PackedLookaheadTables, build_packed_lookahead_tables
from ..partition import Partition, uniform_partition, weighted_partition

__all__ = ["next_pow2", "DeviceTables", "ChunkLayout", "MeshLayout",
           "BucketPlan", "MatchPlan", "LanePlan", "Planner",
           "ENTRY_STARTS", "ENTRY_STATES", "ENTRY_LANES",
           "expand_device_weights", "layout_device_work"]

# Entry-seed stage modes of a LanePlan (how chunk 0 / the scan rows start):
ENTRY_STARTS = "starts"  # the packed pattern start states (whole documents)
ENTRY_STATES = "states"  # caller-supplied exact [B, K] states (resumed
                         # stream segments -- Matcher.advance_segments)
ENTRY_LANES = "lanes"    # Eq. 11 candidate rows of each row's boundary
                         # class [B]; output keeps the [B, K, S] lane axis
                         # and is composed with the caller's cursor lanes on
                         # device (Matcher.advance_cursors)


def next_pow2(n: int) -> int:
    return 1 if n <= 1 else 1 << (n - 1).bit_length()


# --------------------------------------------------------------------------
# Device-ready matcher tables (lookahead-table selection)
# --------------------------------------------------------------------------

_R2_TABLE_CAP = 1 << 22  # max int32 entries of the r=2 [n_keys+1, Q] index


class DeviceTables:
    """Constant device arrays shared by every executor backend.

    ``table_pad`` appends the identity transition column ``pad_cls`` (padding
    advances no DFA); ``cand_pad``/``cidx_pad`` append the matching pad rows
    (the pad candidates row is never merged through but must hold in-range
    states for the gather; the pad ``cand_index`` row stays -1).
    ``absorbing[q]`` marks states with only self-loops over *real* classes —
    the early-exit test (a document whose every lane is absorbing can stop
    matching).

    **Boundary keys.**  Speculative chunk entries are keyed by the *boundary
    key* of the r bytes before the chunk: for ``lookahead_r=1`` the paper's
    Eq. 11 class of the last byte (``n_keys == n_classes``), for
    ``lookahead_r=2`` the Eq. 13 pair key ``c_prev * n_classes + c_last``
    (``n_keys == n_classes ** 2``), whose feasible candidate sets are usually
    far smaller — shrinking the shared lane width S.  ``lookahead_r="auto"``
    (default) picks r=2 per DFA exactly when it strictly shrinks S and the
    r=2 index tables fit the memory cap; the choice is static per DFA and
    keyed into every ``LanePlan``.  ``pad_key == n_keys`` is the identity
    boundary key (whole-chunk padding / zero-byte segments).

    The lookahead candidate tables build lazily on first speculative use:
    consumers that only advance states through the padded table (e.g.
    grammar-constrained serving) never pay the O(n_keys * Q) analysis.
    """

    def __init__(self, packed: PackedDFA, *, lookahead_r: int | str = "auto"):
        if lookahead_r not in ("auto", 1, 2):
            raise ValueError(f"lookahead_r must be 'auto', 1 or 2, "
                             f"got {lookahead_r!r}")
        self.packed = packed
        self.lookahead_r = lookahead_r
        self.pad_cls = packed.n_classes
        q = packed.n_states
        ident = np.arange(q, dtype=np.int32).reshape(-1, 1)
        self.table_pad_j = jnp.asarray(          # [Q, n_cls + 1] int32
            np.concatenate([packed.table, ident], axis=1))
        self.starts_j = jnp.asarray(packed.starts)        # [K] int32
        self.sinks_j = jnp.asarray(packed.sinks)          # [K] int32
        self.byte_to_class_j = jnp.asarray(packed.byte_to_class)  # [256]
        # host copy kept for the streaming cursor layer (absorbed flags /
        # stream-level early exit) — the pad column is identity by
        # construction, so absorbing-over-real-classes is absorbing outright
        self.absorbing = (packed.table
                          == np.arange(q, dtype=np.int32)[:, None]).all(axis=1)
        self.absorbing_j = jnp.asarray(self.absorbing)    # [Q] bool

    @classmethod
    def build(cls, packed: PackedDFA, *,
              lookahead_r: int | str = "auto") -> "DeviceTables":
        return cls(packed, lookahead_r=lookahead_r)

    @property
    def n_patterns(self) -> int:
        return self.packed.n_patterns

    @property
    def i_max(self) -> int:
        return self.tables.i_max

    @property
    def spec_r(self) -> int:
        """Resolved reverse-lookahead depth of the boundary-key space."""
        return self.tables.r

    @property
    def n_keys(self) -> int:
        """Boundary-key count (``n_classes ** spec_r``)."""
        return self.tables.n_keys

    @property
    def pad_key(self) -> int:
        """The identity boundary key (pad row of ``cand_pad``/``cidx_pad``)."""
        return self.tables.n_keys

    @functools.cached_property
    def tables(self) -> PackedLookaheadTables:
        if self.lookahead_r != "auto":
            return build_packed_lookahead_tables(self.packed,
                                                 r=int(self.lookahead_r))
        t1 = build_packed_lookahead_tables(self.packed)
        n, q = self.packed.n_classes, self.packed.n_states
        k = self.packed.n_patterns
        # r=2 must strictly shrink S to be worth the bigger key space, and
        # its [n_keys + 1, Q] / [n_keys + 1, K, S] tables must fit the cap
        fits = (n * n + 1) * max(q, k * t1.i_max) <= _R2_TABLE_CAP
        if t1.i_max > 1 and n >= 2 and fits:
            t2 = build_packed_lookahead_tables(self.packed, r=2)
            if t2.i_max < t1.i_max:
                return t2
        return t1

    def advance_key(self, prev_key: int, data: bytes | np.ndarray) -> int:
        """Boundary key of a stream after it absorbs ``data`` (host-side).

        ``prev_key`` is the stream's key before the segment (``-1`` =
        no/insufficient history).  r=1 degrades to the class of the last
        byte — exactly the pre-r=2 ``last_class``.  r=2 shifts the 2-byte
        window: a segment of >= 2 bytes keys on its own suffix; a 1-byte
        segment reuses ``prev_key``'s last class as the new first class; a
        stream without 2 bytes of usable history returns ``-1``
        (``streaming.cursor.ENTRY_EXACT``) — sound, merely conservative (its
        next segment needs exact entry instead of candidate keying).
        """
        arr = (np.frombuffer(data, np.uint8)
               if isinstance(data, (bytes, bytearray))
               else np.asarray(data, np.uint8))
        if arr.size == 0:
            return int(prev_key)
        b2c = self.packed.byte_to_class
        if self.spec_r == 1:
            return int(b2c[arr[-1]])
        n = self.packed.n_classes
        if arr.size >= 2:
            return int(b2c[arr[-2]]) * n + int(b2c[arr[-1]])
        if 0 <= int(prev_key) < n * n:
            return (int(prev_key) % n) * n + int(b2c[arr[-1]])
        return -1

    @functools.cached_property
    def cand_pad_j(self) -> jnp.ndarray:  # [n_cls + 1, K, S] int32
        t = self.tables
        with jax.ensure_compile_time_eval():  # first touch may be mid-trace
            return jnp.asarray(
                np.concatenate([t.candidates, t.candidates[:1]], axis=0))

    @functools.cached_property
    def cidx_pad_j(self) -> jnp.ndarray:  # [n_cls + 1, Q] int32
        with jax.ensure_compile_time_eval():
            return jnp.asarray(np.concatenate(
                [self.tables.cand_index,
                 np.full((1, self.packed.n_states), -1, np.int32)], axis=0))


# --------------------------------------------------------------------------
# Chunk layouts (partitioning + capacity weighting)
# --------------------------------------------------------------------------

def expand_device_weights(weights: np.ndarray, chunks_per_device: int) -> np.ndarray:
    """Per-chunk weights from per-device weights (device d owns a contiguous
    run of ``chunks_per_device`` chunks)."""
    w = np.asarray(weights, dtype=np.float64)
    return np.repeat(w, chunks_per_device)


@dataclasses.dataclass
class ChunkLayout:
    """Static chunk boundaries of one bucket width, assigned to devices.

    ``starts``/``ends`` partition ``[0, width)`` into ``C`` contiguous chunks;
    chunk ``i`` lives on device ``device_of[i]``.  ``exact[i]`` marks chunks
    that start at stream position 0 and are therefore matched exactly from
    the start states (chunk 0, plus any chunk behind zero-length leading
    chunks).  ``lmax`` is the padded per-chunk buffer length every executor
    allocates — trailing identity-pad columns never move a lane, so padding a
    chunk's tail is free in state space.
    """

    width: int
    starts: np.ndarray     # [C] int64
    ends: np.ndarray       # [C] int64
    device_of: np.ndarray  # [C] int64
    exact: np.ndarray      # [C] bool
    lmax: int

    @property
    def num_chunks(self) -> int:
        return int(self.starts.shape[0])

    @property
    def num_devices(self) -> int:
        return int(self.device_of.max()) + 1 if self.starts.size else 1

    @property
    def sizes(self) -> np.ndarray:
        return self.ends - self.starts

    # interior chunk boundaries keep >= 2 preceding symbols so r=2 boundary
    # keys (the pair of the two bytes before the cut) always exist; moving a
    # cut from 1 to 2 only resizes neighbouring chunks (harmless for r=1)
    MIN_CUT = 2

    @classmethod
    def from_partition(cls, part: Partition, width: int, devices: int) -> "ChunkLayout":
        c = part.start.shape[0]
        if c % devices != 0:
            raise ValueError(f"{c} chunks do not divide over {devices} devices")
        starts, ends = part.start.copy(), part.end.copy()
        if (starts[1:] == ends[:-1]).all():  # contiguous: clamp cut points
            cuts = np.where((starts > 0) & (starts < cls.MIN_CUT),
                            np.int64(cls.MIN_CUT), starts)
            cuts = np.minimum(np.maximum.accumulate(cuts), width)
            starts = cuts
            ends = np.append(cuts[1:], ends[-1])
        sizes = ends - starts
        return cls(width=width, starts=starts, ends=ends,
                   device_of=np.repeat(np.arange(devices), c // devices),
                   exact=(starts == 0), lmax=int(max(sizes.max(), 1)))

    @classmethod
    def uniform(cls, width: int, num_chunks: int, devices: int = 1) -> "ChunkLayout":
        return cls.from_partition(uniform_partition(width, num_chunks, 1),
                                  width, devices)

    @classmethod
    def weighted(cls, width: int, num_chunks: int, devices: int,
                 weights: np.ndarray, m: int = 1) -> "ChunkLayout":
        """Capacity-weighted boundaries (paper Eqs. 2–7 over the bucket width).

        ``m = 1`` is the lane-parallel model (chunk sizes proportional to
        capacity; equal capacities degrade to ``uniform``); ``m = I_max``
        reproduces the paper's scalar-worker model where the exact chunk 0 is
        ``m``x longer.
        """
        w_chunks = expand_device_weights(weights, num_chunks // devices)
        return cls.from_partition(weighted_partition(width, w_chunks, m),
                                  width, devices)


@dataclasses.dataclass
class MeshLayout:
    """Per-doc-shard chunk layouts of one bucket width on a 2-D mesh.

    A ("doc", "chunk") mesh splits a bucket tile both ways: doc row-block
    ``r`` (tile rows ``[r * B/Dd, (r+1) * B/Dd)``) is owned by mesh row ``r``,
    and ``rows[r]`` is that row's own ``ChunkLayout`` — its chunk boundaries
    are capacity-weighted by *that row's* chunk-axis devices (the paper's
    Eqs. 1–7 applied per doc row-block), so a heterogeneous fleet stays
    balanced along both axes.  All rows share ``width``; ``lmax`` is the
    maximum padded chunk buffer over the rows, so the SPMD chunk buffer keeps
    a single shape (shorter chunks tail-pad with the identity class — free in
    state space).

    **Ragged doc rows.**  ``row_weights`` (Eq. 1 weights of each mesh row's
    *aggregate* capacity) makes the document axis capacity-weighted too:
    ``doc_counts`` applies Eq. 7 to the *document count* of a tile, and
    ``tile_rows`` packs real documents into the fixed physical row-blocks
    raggedly — a slow mesh row receives proportionally fewer real documents
    (its remaining slots carry zero-length pads, free in the work model and
    skipped by the early exit).  SPMD shard shapes stay uniform — only the
    doc -> tile-row *placement* moves, so results are bit-identical to the
    uniform layout by construction.  ``None`` = uniform placement.
    """

    width: int
    rows: tuple[ChunkLayout, ...]
    row_weights: Optional[tuple[float, ...]] = None

    @property
    def doc_shards(self) -> int:
        return len(self.rows)

    @property
    def is_ragged(self) -> bool:
        return self.row_weights is not None

    def doc_counts(self, n: int) -> np.ndarray:
        """Eq. 7 applied to the doc axis: documents per mesh row, summing
        to ``n`` (uniform rows split evenly)."""
        if self.row_weights is None:
            d = self.doc_shards
            return np.diff(np.linspace(0, n, d + 1).astype(np.int64))
        part = weighted_partition(n, np.asarray(self.row_weights), 1)
        return (part.end - part.start).astype(np.int64)

    def tile_rows(self, m: int, tile: int) -> np.ndarray:
        """Physical tile-row of each of ``m`` real documents ([m] int64).

        The tile keeps ``tile // doc_shards`` physical rows per mesh row
        (SPMD shard shapes are uniform); real documents pack into the
        row-blocks per ``doc_counts``, clipped to the block size with the
        overflow waterfilled into the fastest rows that still have spare
        slots.  Uniform layouts return ``arange(m)`` — the legacy positional
        packing, so the ragged path degrades to it exactly.
        """
        d = self.doc_shards
        if tile % d:
            raise ValueError(f"tile of {tile} rows does not split over "
                             f"{d} doc shards")
        if m > tile:
            raise ValueError(f"{m} documents exceed the {tile}-row tile")
        if self.row_weights is None:
            return np.arange(m, dtype=np.int64)
        rps = tile // d
        counts = np.minimum(self.doc_counts(m), rps)
        short = int(m - counts.sum())
        order = np.argsort(-np.asarray(self.row_weights, np.float64),
                           kind="stable")
        while short > 0:
            for r in order:
                if short and counts[r] < rps:
                    counts[r] += 1
                    short -= 1
        return np.concatenate(
            [r * rps + np.arange(counts[r], dtype=np.int64)
             for r in range(d)]) if m else np.zeros(0, np.int64)

    @property
    def num_chunks(self) -> int:
        return self.rows[0].num_chunks

    @property
    def lmax(self) -> int:
        return max(r.lmax for r in self.rows)

    @property
    def num_devices(self) -> int:
        return self.doc_shards * self.rows[0].num_devices

    def device_work(self, lengths: np.ndarray) -> np.ndarray:
        """Real symbols per device for one full tile of document lengths.

        ``lengths [B]`` must cover the whole tile (pad rows as zeros) since
        row-block membership is positional; returns ``[Dd * Dc]`` in mesh
        row-major order (device (r, c) at index ``r * Dc + c``)."""
        lengths = np.asarray(lengths, dtype=np.int64)
        if lengths.shape[0] % self.doc_shards:
            raise ValueError(f"tile of {lengths.shape[0]} rows does not "
                             f"split over {self.doc_shards} doc shards")
        rps = lengths.shape[0] // self.doc_shards
        return np.concatenate(
            [layout_device_work(row, lengths[r * rps:(r + 1) * rps])
             for r, row in enumerate(self.rows)])


def layout_device_work(layout: ChunkLayout, lengths: np.ndarray) -> np.ndarray:
    """Real symbols matched per device for documents of the given lengths.

    A chunk's real work on a document of length ``n`` is the overlap of its
    ``[start, end)`` span with ``[0, n)`` — trailing pad columns are free in
    the model (and on real heterogeneous fleets would not be shipped at all).
    Returns ``[D]`` summed over all documents.
    """
    lengths = np.asarray(lengths, dtype=np.int64)
    overlap = (np.minimum(layout.ends[None, :], lengths[:, None])
               - np.minimum(layout.starts[None, :], lengths[:, None]))
    per_chunk = overlap.sum(axis=0)
    d = layout.num_devices
    work = np.zeros(d, dtype=np.int64)
    np.add.at(work, layout.device_of, per_chunk)
    return work


# --------------------------------------------------------------------------
# The plan
# --------------------------------------------------------------------------

@dataclasses.dataclass
class BucketPlan:
    """One fused device dispatch group: documents sharing a compiled shape."""

    kind: str            # "seq" | "spec"
    width: int           # padded byte/symbol width of the device buffer
    chunk_len: int       # Lc for spec buckets (width == C * Lc); 0 for seq
    doc_idx: np.ndarray  # [n_docs] int64 batch indices, in tile order


@dataclasses.dataclass(frozen=True)
class LanePlan:
    """One lane-program: the single stage pipeline every backend lowers.

    The matching inner loop is one program — **classify** bytes to joint
    classes, **entry-seed** the scan lanes, **chunk-scan** them through the
    padded transition table, **merge** per-chunk lane states (Eq. 8) — and a
    ``LanePlan`` is its complete static description.  Executor backends do
    not implement variants; they *lower* this one plan (``Executor.run``),
    so a new backend writes one lowering instead of four run-methods:

      kind       "seq" (merge stage is a no-op: rows scan start-to-end) or
                 "spec" (chunked scan + Eq. 8 merge of the lane states);
      entry      entry-seed mode — ``ENTRY_STARTS`` (pattern starts),
                 ``ENTRY_STATES`` (caller [B, K] exact states), or
                 ``ENTRY_LANES`` (Eq. 11 candidate rows keyed by each row's
                 boundary key; the merge stage then also composes the
                 caller's [B, K, S] cursor lanes on device);
      early_exit absorbing-state early exit enabled for this program;
      spec_r     reverse-lookahead depth of the boundary-key space the
                 candidate tables were built for (``DeviceTables.spec_r``;
                 static per DFA — keyed so an r change re-lowers).

    ``width``/``chunk_len`` pin the compiled buffer shape; ``key`` is the
    lowering cache key (one compiled program per distinct plan).
    """

    kind: str        # "seq" | "spec"
    width: int       # padded byte/symbol width of the device buffer
    chunk_len: int   # Lc for spec plans (width == C * Lc); 0 for seq
    entry: str       # ENTRY_STARTS | ENTRY_STATES | ENTRY_LANES
    early_exit: bool = True
    spec_r: int = 1  # boundary-key lookahead depth (DeviceTables.spec_r)
    table_epoch: int = 0  # pattern-set generation (Planner.table_epoch):
    #   bumped by hot swaps the way layout_epoch tracks boundary moves, so a
    #   compiled program that baked pre-swap tables can never be looked up
    #   again even if an executor cache entry survived

    def __post_init__(self):
        if self.kind not in ("seq", "spec"):
            raise ValueError(f"unknown plan kind {self.kind!r}")
        if self.entry not in (ENTRY_STARTS, ENTRY_STATES, ENTRY_LANES):
            raise ValueError(f"unknown entry mode {self.entry!r}")
        if self.spec_r not in (1, 2):
            raise ValueError(f"spec_r must be 1 or 2, got {self.spec_r!r}")

    @property
    def key(self) -> tuple:
        return (self.kind, self.width, self.chunk_len, self.entry,
                self.early_exit, self.spec_r, self.table_epoch)


@dataclasses.dataclass
class MatchPlan:
    """Everything an executor needs to run one batch, decided up front."""

    buckets: list[BucketPlan]
    lengths: np.ndarray      # [B] int64 document byte lengths
    spec_mask: np.ndarray    # [B] bool — True: speculative chunk path
    chunk_len: np.ndarray    # [B] int64 assigned Lc (0 for seq docs)

    @property
    def n_docs(self) -> int:
        return int(self.lengths.shape[0])


def _tile_order(idx: np.ndarray, lengths: np.ndarray) -> np.ndarray:
    """``idx`` longest document first; a stable sort keeps ties in order."""
    return idx[np.argsort(-lengths[idx], kind="stable")]


class Planner:
    """Sticky-bucket batch planner (state lives here, not in the facade).

    Parameters mirror the old ``BatchMatcher`` policy: ``max_buckets`` is the
    lifetime compiled-shape budget for the speculative path (new chunk
    lengths snap up into compiled buckets; fresh keys merge upward), and the
    short-document sequential width is fixed at ``next_pow2(4C - 1)`` so the
    seq path compiles exactly once (it grows only in the ``num_chunks <= 1``
    everything-sequential configuration).

    ``devices`` is the *chunk-axis* extent; ``doc_shards`` the doc-axis
    extent of a 2-D ("doc", "chunk") matcher mesh (1 for every single-host
    backend).  ``weights`` holds per-device capacity weights — a flat
    ``[doc_shards * devices]`` array in mesh row-major order (or an already
    2-D ``[doc_shards, devices]``); with ``doc_shards > 1`` the planner
    emits a ``MeshLayout`` whose row ``r`` applies Eqs. 1–7 with mesh row
    ``r``'s weights only.
    """

    def __init__(self, *, num_chunks: int = 8, max_buckets: int = 2,
                 devices: int = 1, weights: Optional[np.ndarray] = None,
                 spec_m: int = 1, doc_shards: int = 1,
                 row_weights: Optional[np.ndarray] = None):
        if num_chunks < 1:
            raise ValueError("num_chunks must be >= 1")
        if max_buckets < 1:
            raise ValueError("max_buckets must be >= 1")
        if devices < 1:
            raise ValueError("devices must be >= 1")
        if doc_shards < 1:
            raise ValueError("doc_shards must be >= 1")
        # round the chunk count up to a device multiple so the chunk axis
        # shards evenly (a no-op for the single-device executors)
        self.num_chunks = -(-int(num_chunks) // int(devices)) * int(devices)
        self.max_buckets = int(max_buckets)
        self.devices = int(devices)
        self.doc_shards = int(doc_shards)
        self.spec_m = int(spec_m)
        # pattern-set generation: Matcher.swap_patterns bumps it so every
        # post-swap LanePlan keys differently from pre-swap programs
        self.table_epoch = 0
        self.weights: Optional[np.ndarray] = None
        self.row_weights: Optional[np.ndarray] = None
        self.spec_keys: list[int] = []
        self.seq_width = next_pow2(max(4 * self.num_chunks - 1, 1))
        self._layouts: dict[int, ChunkLayout | MeshLayout] = {}
        if weights is not None or row_weights is not None:
            self.set_weights(weights, row_weights=row_weights)

    def set_weights(self, weights: Optional[np.ndarray], *,
                    row_weights: Optional[np.ndarray] = None) -> None:
        """Replace the per-device capacity weights; drop cached layouts.

        The between-tick rebalance path (``Matcher.rebalance``) lands here:
        cached ``ChunkLayout``/``MeshLayout`` boundaries bake the *old*
        weights, so the layout cache clears — while the sticky bucket keys
        and the compiled seq width survive (only chunk boundaries move, not
        shapes; executors that bake boundaries into lowered programs key
        their cache on a layout epoch, see ``executors.LaneExecutor``).

        ``row_weights`` are the Eq. 1 weights of each mesh row's *aggregate*
        capacity ([doc_shards]) — they make the document axis of every
        emitted ``MeshLayout`` ragged (capacity-proportional per-row document
        counts via ``MeshLayout.doc_counts``/``tile_rows``).  ``None`` keeps
        the uniform doc split.
        """
        if row_weights is None:
            self.row_weights = None
        else:
            rw = np.asarray(row_weights, np.float64).reshape(-1)
            if rw.shape != (self.doc_shards,):
                raise ValueError(f"need one row weight per doc shard: "
                                 f"expected {self.doc_shards}, got {rw.size}")
            if not np.all(np.isfinite(rw)) or (rw <= 0).any():
                raise ValueError("row weights must be finite and > 0")
            self.row_weights = rw
        if weights is None:
            self.weights = None
        else:
            w = np.asarray(weights, np.float64)
            if w.ndim == 1:
                w = w.reshape(self.doc_shards, -1)
            if w.shape != (self.doc_shards, self.devices):
                raise ValueError("need one capacity weight per (doc, chunk) "
                                 f"device: expected {self.doc_shards}x"
                                 f"{self.devices}, got {w.shape}")
            if not np.all(np.isfinite(w)) or (w <= 0).any():
                raise ValueError("capacity weights must be finite and > 0")
            self.weights = w
        self._layouts.clear()

    # -- chunk layouts ------------------------------------------------------

    def layout_for(self, chunk_len: int) -> ChunkLayout | MeshLayout:
        """Chunk boundaries for one spec bucket width (cached, deterministic).

        Returns a ``ChunkLayout`` for single-row meshes (unchanged contract
        for the local/pallas backends and the 1-D sharded layout) and a
        ``MeshLayout`` of per-doc-row-block layouts when ``doc_shards > 1``.
        """
        if chunk_len not in self._layouts:
            width = self.num_chunks * chunk_len

            def row_layout(r: int) -> ChunkLayout:
                if self.weights is None:
                    return ChunkLayout.uniform(width, self.num_chunks,
                                               self.devices)
                return ChunkLayout.weighted(width, self.num_chunks,
                                            self.devices, self.weights[r],
                                            m=self.spec_m)

            if self.doc_shards == 1:
                self._layouts[chunk_len] = row_layout(0)
            else:
                rw = (tuple(float(w) for w in self.row_weights)
                      if self.row_weights is not None else None)
                self._layouts[chunk_len] = MeshLayout(
                    width=width,
                    rows=tuple(row_layout(r)
                               for r in range(self.doc_shards)),
                    row_weights=rw)
        return self._layouts[chunk_len]

    # -- lane programs ------------------------------------------------------

    def lane_plan(self, bucket: BucketPlan, *, entry: str = ENTRY_STARTS,
                  early_exit: bool = True, spec_r: int = 1) -> LanePlan:
        """The lane program of one bucket dispatch (see ``LanePlan``).

        ``spec_r`` is the boundary-key depth of the lookahead tables the
        program will gather from (``DeviceTables.spec_r``); the facade passes
        it for plans that touch candidate tables (spec buckets and every
        ``ENTRY_LANES`` program) so the lazily-resolved per-DFA r choice is
        part of the lowering cache key.
        """
        return LanePlan(kind=bucket.kind, width=bucket.width,
                        chunk_len=bucket.chunk_len, entry=entry,
                        early_exit=early_exit, spec_r=spec_r,
                        table_epoch=self.table_epoch)

    # -- batch planning -----------------------------------------------------

    def plan(self, lengths: np.ndarray) -> MatchPlan:
        """Assign every document to a bucket, updating the sticky key set.

        Each bucket's ``doc_idx`` is in tile order: longest document first,
        ties in arrival order.  The dispatch loop cuts it into consecutive
        tiles, and a tile's scan stops only once its longest row is done,
        so grouping similar lengths lets each tile stop near its own
        longest row; results return to their documents through ``doc_idx``.
        """
        lengths = np.asarray(lengths, dtype=np.int64)
        b = lengths.shape[0]
        c = self.num_chunks
        spec = (lengths >= 4 * c) & (c > 1)
        chunk_len = np.zeros(b, np.int64)
        buckets: list[BucketPlan] = []

        seq_idx = _tile_order(np.flatnonzero(~spec), lengths)
        if seq_idx.size and int(lengths[seq_idx].max()) > 0:
            lmax = int(lengths[seq_idx].max())
            if lmax > self.seq_width:  # only reachable when num_chunks <= 1
                self.seq_width = next_pow2(lmax)
            buckets.append(BucketPlan("seq", self.seq_width, 0, seq_idx))

        spec_idx = np.flatnonzero(spec)
        if spec_idx.size:
            lc = np.array([next_pow2(-(-int(n) // c)) for n in lengths[spec_idx]])
            # snap each doc up into an already-compiled bucket when one fits
            known = sorted(self.spec_keys)
            for j, v in enumerate(lc):
                fit = [key for key in known if key >= v]
                if fit:
                    lc[j] = fit[0]
            # fresh keys: merge smallest upward until within the lifetime
            # shape budget (always allowing at least one new key so oversized
            # documents can still be matched)
            fresh = sorted(set(lc.tolist()) - set(known))
            allowed = max(1, self.max_buckets - len(known))
            while len(fresh) > allowed:
                lc[lc == fresh[0]] = fresh[1]
                fresh.pop(0)
            self.spec_keys = sorted(set(known) | set(fresh))
            for key in sorted(set(lc.tolist())):
                sel = _tile_order(spec_idx[lc == key], lengths)
                chunk_len[sel] = key
                buckets.append(BucketPlan("spec", c * key, key, sel))

        return MatchPlan(buckets=buckets, lengths=lengths, spec_mask=spec,
                         chunk_len=chunk_len)
