"""The matcher's dispatch counters (``Matcher.perf_report()["dispatch"]``).

``run_symbols`` is rows x the symbol steps each scan loop ran, summed over
loops, ``real_symbols`` the real bytes scanned, and ``bound_symbols`` rows x
each tile's longest effective row (the longest document on seq tiles, its
share of one chunk on spec tiles).  Each case works out the loop's steps on
the host from the documents (where each stops being undecided) and the
early-exit segments, and checks the counters against them: on the local backend (seq and spec plans, with and without the early
exit, and the Pallas kernel's skipped blocks) and on the sharded backend
over 4 devices, where every shard runs its own loop.
"""

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


def loop_steps(docs, width: int, segs: int) -> int:
    """Steps of one early-exit scan loop over these rows: the first segment
    boundary at which every row is absorbed or past its symbols."""
    seg = width // segs
    for g in range(1, segs + 1):
        if all(min(len(d), _absorb_at(d)) <= g * seg for d in docs):
            return g * seg
    return width


def _delta(m, docs):
    before = dict(m.perf_report()["dispatch"])
    m.membership_batch(docs)
    after = m.perf_report()["dispatch"]
    return {k: after[k] - before[k] for k in after}


def _x(n: int) -> bytes:
    return b"x" * n


SEQ_TILES = [
    pytest.param([_x(100), _x(300), _x(500), _x(600)], 4, id="no-exit"),
    pytest.param([b"ab" + _x(998), b"ab" + _x(500), _x(1), b"xab"], 4,
                 id="early-exit"),
    pytest.param([b"ab" + _x(998), _x(10), b"ab", _x(700)], 1,
                 id="exit-off"),
]


@pytest.mark.parametrize("docs,segs", SEQ_TILES)
def test_local_seq_counts(docs, segs):
    m = _matcher(num_chunks=1, batch_tile=4, early_exit_segments=segs)
    d = _delta(m, docs)
    width = 1024  # the sticky seq width of documents up to 1,000 bytes
    assert d["tiles"] == 1 and d["docs"] == 4 and d["rows"] == 4
    assert d["real_symbols"] == sum(map(len, docs))
    assert d["bound_symbols"] == 4 * max(map(len, docs))
    assert d["run_symbols"] == 4 * loop_steps(docs, width, segs)


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
    # shard 0: 15 and 13 symbols -> 16; shard 1: 12 (absorbed by 4) and 9
    # -> 12; shard 2: 5 (absorbed) and 3 -> 4; shard 3: 2 and 2 -> 4
    assert want == 2 * (16 + 12 + 4 + 4)
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
