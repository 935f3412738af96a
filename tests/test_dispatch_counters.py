"""The matcher's dispatch counters (``Matcher.perf_report()["dispatch"]``).

``run_symbols`` is rows x the symbol steps each scan loop ran, summed over
loops, ``real_symbols`` the real bytes scanned, and ``bound_symbols`` rows x
each tile's longest effective row (the longest document on seq tiles, its
share of one chunk on spec tiles).  Each case works out the loop's steps on
the host from the documents (where each stops being undecided) and the
early-exit segments, and checks the counters against them: on the local backend (seq and spec plans, with and without the early
exit, and the Pallas kernel's skipped blocks) and on the sharded backend
over 4 devices, where every shard runs its own loop.  A scan loop checks
for the absorbing exit once per segment and never runs past its tile's
longest row, so a tile with no absorbed row runs exactly its bound.
"""

import re

import numpy as np
import pytest

import jax

from repro.core import Matcher, compile_regex, make_search_dfa

NEVER = 1 << 30


def _matcher(**kw):
    # ".*ab" in search form: every state reading "ab" lands in the absorbing
    # accept state, so a document is decided 2 symbols after each "ab"
    return Matcher([make_search_dfa(compile_regex(".*ab"))], **kw)


def _absorb_at(doc: bytes) -> int:
    i = doc.find(b"ab")
    return NEVER if i < 0 else i + 2


def loop_steps(docs, width: int, segs: int, absorb_at=_absorb_at) -> int:
    """Steps of one early-exit scan loop over these rows: the first segment
    check at which every row is absorbed or past its symbols, clipped at the
    rows' longest length (the loop never runs past it)."""
    longest = max(map(len, docs))
    seg = width // segs
    for g in range(1, segs + 1):
        if all(min(len(d), absorb_at(d)) <= g * seg for d in docs):
            return min(g * seg, longest)
    return longest


def _delta(m, docs):
    before = dict(m.perf_report()["dispatch"])
    m.membership_batch(docs)
    after = m.perf_report()["dispatch"]
    return {k: after[k] - before[k] for k in after}


def _x(n: int) -> bytes:
    return b"x" * n


SEQ_TILES = [
    # the loop stops at the longest row (600), not at the boundary 768
    pytest.param([_x(100), _x(300), _x(500), _x(600)], 4, 600, id="no-exit"),
    pytest.param([b"ab" + _x(998), b"ab" + _x(500), _x(1), b"xab"], 4, 256,
                 id="early-exit"),
    # no absorbing check: the loop runs to the longest row (1,000 of 1,024)
    pytest.param([b"ab" + _x(998), _x(10), b"ab", _x(700)], 1, 1000,
                 id="exit-off"),
]


@pytest.mark.parametrize("docs,segs,steps", SEQ_TILES)
def test_local_seq_counts(docs, segs, steps):
    m = _matcher(num_chunks=1, batch_tile=4, early_exit_segments=segs)
    d = _delta(m, docs)
    width = 1024  # the sticky seq width of documents up to 1,000 bytes
    assert d["tiles"] == 1 and d["docs"] == 4 and d["rows"] == 4
    assert d["real_symbols"] == sum(map(len, docs))
    assert d["bound_symbols"] == 4 * max(map(len, docs))
    assert loop_steps(docs, width, segs) == steps
    assert d["run_symbols"] == 4 * steps


@pytest.mark.parametrize("docs,chunk_steps", [
    pytest.param([_x(1000)] * 3, 256, id="no-exit"),
    # every 256-symbol chunk opens with "ab": all lanes absorb in segment 0
    pytest.param([(b"ab" + _x(62)) * 16] * 3, 64, id="early-exit"),
])
def test_local_spec_counts(docs, chunk_steps):
    m = _matcher(num_chunks=4, batch_tile=4)
    d = _delta(m, docs)
    # rows are document-chunks: the tile's 4 rows x 4 chunks in one loop
    assert d["rows"] == 4
    assert d["real_symbols"] == sum(map(len, docs))
    # each document fills its 256-symbol chunks
    assert d["bound_symbols"] == 4 * 4 * 256
    assert d["run_symbols"] == 4 * 4 * chunk_steps


def test_pallas_counts_from_skipped_blocks():
    m = _matcher(num_chunks=4, batch_tile=4, backend="pallas")
    m.executor.spec_l_blk[0] = 64  # 4 blocks per 256-symbol chunk
    full = _delta(m, [_x(1000)] * 4)
    assert full["run_symbols"] == 4 * 4 * 256
    # block 0 absorbs every lane; the kernel skips the other three
    skipped = _delta(m, [(b"ab" + _x(62)) * 16] * 4)
    assert skipped["run_symbols"] == 4 * 4 * 64
    assert skipped["real_symbols"] == 4 * 1024


def _sharded(**kw):
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 host devices (conftest forces 8)")
    return _matcher(backend="sharded", mesh_shape=(1, 4), num_chunks=4, **kw)


def test_sharded_seq_counts_per_shard():
    m = _sharded(batch_tile=8)
    # seq rows split 2 per device in the planner's order, longest first,
    # each device's loop stopping on its own
    docs = [b"ab" + _x(10), b"ab" + _x(3), _x(13), b"ab",
            _x(3), _x(2), _x(9), b"ab" + _x(13)]
    order = m.planner.plan(np.array([len(d) for d in docs])).buckets[0].doc_idx
    np.testing.assert_array_equal(order, [7, 2, 0, 6, 1, 4, 3, 5])
    rows = [docs[i] for i in order]
    d = _delta(m, docs)
    assert "seq-sharded" in m.perf_report()["lowerings"].values()
    width, segs = 16, 4  # the seq width of a 4-chunk planner
    want = sum(2 * loop_steps(rows[i:i + 2], width, segs)
               for i in range(0, 8, 2))
    # shard 0: 15 and 13 symbols -> 15, its longest row; shard 1: 12
    # (absorbed by 4) and 9 -> 12; shard 2: 5 (absorbed) and 3 -> 4; shard
    # 3: 2 and 2 -> 2
    assert want == 2 * (15 + 12 + 4 + 2)
    assert d["run_symbols"] == want
    assert d["real_symbols"] == sum(map(len, docs))
    assert d["bound_symbols"] == 8 * 15


def test_sharded_spec_counts_every_device_loop():
    m = _sharded(batch_tile=4)
    docs = [_x(1000), _x(700), b"ab" + _x(900)]
    d = _delta(m, docs)
    assert "spec-sharded" in m.perf_report()["lowerings"].values()
    # no early exit on the mesh: each of the 4 devices scans its one
    # 256-symbol chunk of the tile's 4 rows to the end
    assert d["run_symbols"] == 4 * 4 * 256
    assert d["real_symbols"] == sum(map(len, docs))
    assert d["tiles"] == 1 and d["docs"] == 3


def test_counts_accumulate_over_calls_and_entries():
    m = _matcher(num_chunks=1, batch_tile=4)
    docs = [_x(100), b"ab" + _x(50)]
    m.membership_batch(docs)
    m.advance_segments(docs, np.tile(m.packed.starts, (2, 1)))
    rep = m.perf_report()["dispatch"]
    assert rep["tiles"] == 2 and rep["docs"] == 4 and rep["rows"] == 8
    assert rep["real_symbols"] == 2 * sum(map(len, docs))


# -- the loop stops at its tile's longest row --------------------------------

PAIR = ["ab", "ba"]  # a document is decided once it holds both
BOUND_WIDTH = 64     # the seq width of segments of 33 - 64 bytes
# two tiles of 4 in the planner's order: (61, 50, 45, 41), (38, 36, 35, 33),
# neither longest row a multiple of a 16-step segment
RAGGED = [61, 50, 45, 41, 38, 36, 35, 33]
HEADS = {"starts": b"", "states": b"yxa", "lanes": b"yx"}


def _clean(rng, n: int) -> bytes:
    # every a or b is followed by x: neither pattern can match
    parts = rng.choice([b"ax", b"bx", b"x", b"y", b"yy"], size=n)
    return b"".join(parts)[:n]


def _decided(entry: str, seg: bytes) -> int:
    """Where a segment's row becomes all-absorbing: exact rows carry their
    head's history, and a lane row's worst lane starts from the pattern
    starts (the head ends in x, whose candidates are the start and the
    absorbing accept)."""
    head = b"" if entry == "lanes" else HEADS[entry]
    rxs = [re.compile(p.encode()) for p in PAIR]
    for i in range(len(seg) + 1):
        if all(rx.search(head + seg[:i]) for rx in rxs):
            return i
    return NEVER


def _old_early(lens, decided, segs: int) -> int:
    """Early exits under the rule before the loop stopped at its longest row:
    a row is seen absorbed at the first 16-step segment boundary at or past
    where it is decided (never with one segment)."""
    if segs <= 1:
        return 0
    seg = BOUND_WIDTH // segs
    return sum(max(1, -(-t // seg)) * seg < n for n, t in zip(lens, decided))


def _bound_run(m, entry, segs_bytes):
    """Scan the segments from ``entry``; returns (delta of the scan call's
    counters, its early exits, exact finals of head + segment)."""
    heads = [HEADS[entry]] * len(segs_bytes)
    starts = np.tile(m.packed.starts, (len(segs_bytes), 1))
    if entry == "starts":
        before = dict(m.perf_report()["dispatch"])
        res = m.membership_batch(segs_bytes)
        fin = res.final_states
    else:
        r0 = m.advance_segments(heads, starts)
        before = dict(m.perf_report()["dispatch"])
        if entry == "states":
            res = m.advance_segments(segs_bytes, r0.final_states)
            fin = res.final_states
        else:
            keys = np.array([m.dev.advance_key(-1, h) for h in heads],
                            np.int32)
            lanes = m.dev.tables.candidates[keys].astype(np.int32)
            res = m.advance_cursors(segs_bytes, lanes, keys)
            lane = m.dev.tables.cand_index[keys[:, None], r0.final_states]
            hit = np.take_along_axis(res.lane_states,
                                     np.maximum(lane, 0)[..., None],
                                     axis=2)[..., 0]
            sinks = m.packed.sinks[None, :]
            fin = np.where(lane < 0,
                           np.where(sinks >= 0, sinks, r0.final_states), hit)
    after = m.perf_report()["dispatch"]
    return ({k: after[k] - before[k] for k in after}, res.early_exits, fin)


@pytest.mark.parametrize("segs", [1, 4])
@pytest.mark.parametrize("entry", ["starts", "states", "lanes"])
@pytest.mark.parametrize("batch", ["clean", "absorbing", "pow2"])
def test_scan_loop_stops_at_tile_longest_row(batch, entry, segs):
    rng = np.random.default_rng(16)
    dfas = [make_search_dfa(compile_regex(".*" + p)) for p in PAIR]
    m = Matcher(dfas, num_chunks=1, batch_tile=4, early_exit_segments=segs)
    lens = [BOUND_WIDTH] * 8 if batch == "pow2" else RAGGED
    docs = [_clean(rng, n) for n in lens]
    if batch == "absorbing":
        # the 61-symbol row and every row of the second tile hold both
        # patterns in their first symbols: the second tile is all absorbed
        for i in (0, 4, 5, 6, 7):
            docs[i] = b"aba" + docs[i][3:]
    d, early, fin = _bound_run(m, entry, docs)

    full = [HEADS[entry] + doc for doc in docs]
    want = np.array([[re.search(p.encode(), f) is not None for p in PAIR]
                     for f in full])
    np.testing.assert_array_equal(m.packed.accepting[fin], want)
    for j, dfa in enumerate(dfas):
        off = int(m.packed.offsets[j])
        assert [int(s) - off for s in fin[:, j]] == [dfa.run(f)
                                                     for f in full]

    order = m.planner.plan(np.array(lens)).buckets[0].doc_idx
    tiles = [[docs[i] for i in order[lo:lo + 4]] for lo in (0, 4)]
    decided = {doc: _decided(entry, doc) for doc in docs}
    steps = [loop_steps(t, BOUND_WIDTH, segs, absorb_at=decided.get)
             for t in tiles]
    assert d["tiles"] == 2 and d["rows"] == 8
    assert d["real_symbols"] == sum(lens)
    assert d["bound_symbols"] == 4 * sum(max(map(len, t)) for t in tiles)
    assert d["run_symbols"] == 4 * sum(steps)
    assert early == _old_early(lens, [decided[doc] for doc in docs], segs)
    if batch == "pow2":
        # equal rows at the width: every step of the width, as before
        assert d["run_symbols"] == 8 * BOUND_WIDTH
    elif batch == "clean" or segs == 1:
        # no row absorbed (or no check): the loops stop at their bounds
        assert d["run_symbols"] == d["bound_symbols"] == 4 * (61 + 38)
    else:
        # the first tile runs to 61; the second stops at its first check
        assert steps == [61, 16] and early == 5
    if batch != "absorbing":
        assert early == 0
