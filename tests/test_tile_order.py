"""Tile order carries no meaning: answers follow each document, not its tile.

The planner emits every bucket longest document first, so a batch's tiles
no longer hold its documents in arrival order.  ``_dispatch`` packs the
entry operands (``entry[sel]``, ``entry_cls[sel]``) and places rows on a
mesh (``rowpos``) through the same ``sel`` that scatters the results back.
Here ragged batches several tiles long go through each entry mode —
``membership_batch`` (pattern starts), ``advance_segments`` (exact entry
states) and ``advance_cursors`` (candidate-keyed lanes) — on the local
backend and a (1, 4) sharded mesh: the verdicts equal ``re.search``, and
every permutation of the batch returns the same answers, bit for bit,
permuted with it.
"""

import re

import numpy as np
import pytest

import jax

from repro.core import Matcher, compile_regex, make_search_dfa
from repro.launch.mesh import make_matcher_mesh

PATTERNS = ["(ab|ba){2}", "[0-9]{3}", "x+y"]
ALPHABET = list(b"abxy0189")
BATCH_TILE = 4


def _matcher(backend):
    kw = {}
    if backend == "sharded":
        if len(jax.devices()) < 4:
            pytest.skip("needs 4 host devices (conftest forces 8)")
        kw["mesh"] = make_matcher_mesh(shape=(1, 4))
    dfas = [make_search_dfa(compile_regex(".*" + p)) for p in PATTERNS]
    return Matcher(dfas, backend=backend, num_chunks=4,
                   batch_tile=BATCH_TILE, **kw)


def _docs(rng, n=22):
    # seq (< 16 bytes) and spec rows, lengths far apart within a tile
    lens = rng.integers(2, 300, n)
    lens[::5] = rng.integers(2, 15, lens[::5].size)
    return [bytes(rng.choice(ALPHABET, size=int(k)).astype(np.uint8))
            for k in lens]


def _search(docs):
    rxs = [re.compile(p.encode(), re.DOTALL) for p in PATTERNS]
    return np.array([[rx.search(d) is not None for rx in rxs] for d in docs])


def _starts(m, docs):
    res = m.membership_batch(docs)
    return res.final_states, res.accepted


def _states(m, docs):
    # two segments per stream: the first from the pattern starts, the
    # second from the exact states the first left
    heads, tails = [d[:len(d) // 3] for d in docs], [d[len(d) // 3:]
                                                    for d in docs]
    r0 = m.advance_segments(heads, np.tile(m.packed.starts, (len(docs), 1)))
    r1 = m.advance_segments(tails, r0.final_states)
    return r1.final_states, m.packed.accepting[r1.final_states]


def _lanes(m, docs):
    # an exact 2-byte prefix supplies each stream's boundary key; the rest
    # is one candidate-keyed segment composed on the device, then
    # collapsed onto the prefix's exact states
    heads, tails = [d[:2] for d in docs], [d[2:] for d in docs]
    r0 = m.advance_segments(heads, np.tile(m.packed.starts, (len(docs), 1)))
    keys = np.array([m.dev.advance_key(-1, h) for h in heads], np.int32)
    lanes = m.dev.tables.candidates[keys].astype(np.int32)
    res = m.advance_cursors(tails, lanes, keys)
    lane = m.dev.tables.cand_index[keys[:, None], r0.final_states]
    hit = np.take_along_axis(res.lane_states, np.maximum(lane, 0)[..., None],
                             axis=2)[..., 0]
    sinks = m.packed.sinks[None, :]
    fin = np.where(lane < 0, np.where(sinks >= 0, sinks, r0.final_states),
                   hit)
    return fin, m.packed.accepting[fin]


ENTRIES = {"starts": _starts, "states": _states, "lanes": _lanes}


@pytest.mark.parametrize("entry", list(ENTRIES))
@pytest.mark.parametrize("backend", ["local", "sharded"])
def test_answers_follow_documents_under_any_order(backend, entry):
    rng = np.random.default_rng(14)
    m = _matcher(backend)
    run = ENTRIES[entry]
    docs = _docs(rng)
    assert len(docs) > 4 * BATCH_TILE
    finals, accepted = run(m, docs)
    want = _search(docs)
    np.testing.assert_array_equal(accepted, want)
    assert want.any() and not want.all()
    for perm in (np.arange(len(docs))[::-1], rng.permutation(len(docs)),
                 rng.permutation(len(docs))):
        got_finals, got = run(m, [docs[i] for i in perm])
        np.testing.assert_array_equal(got, accepted[perm])
        np.testing.assert_array_equal(got_finals, finals[perm])
