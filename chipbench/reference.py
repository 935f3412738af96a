"""The plain reference, and the control that breaks one of its guarantees.

The reference is Python's ``re`` over the same bytes: pattern k is in a
document (or in a flow's first b bytes) iff ``re.search`` finds it there.
It imports nothing of the program and reads none of its tables.

The control is the reference put in the program's place with the
guarantee of the paper's method taken away.  On one chip every chunk of a
document (or every segment of a flow) is searched on its own, from the
start, and the verdicts are or-ed: the step a speed-up is tempted by, since
it needs no speculation and no merge, and it misses every match that spans
a boundary.  Across chips the tempting step is to leave out the exchange:
each chip answers from its own share of the chunks, and the first chip's
answer is the one returned.
"""

from __future__ import annotations

import re

import numpy as np


def compile_patterns(patterns: list[str]) -> list[re.Pattern]:
    """The config's regexes as byte patterns (``.`` matches every byte)."""
    return [re.compile(p.encode("latin-1"), re.DOTALL) for p in patterns]


def doc_verdicts(rxs: list[re.Pattern], docs: list[bytes]) -> np.ndarray:
    """[B, K] bool: pattern k occurs in document b."""
    out = np.zeros((len(docs), len(rxs)), bool)
    for b, d in enumerate(docs):
        for k, rx in enumerate(rxs):
            out[b, k] = rx.search(d) is not None
    return out


def prefix_verdicts(rxs: list[re.Pattern], data: bytes,
                    bounds: list[int]) -> np.ndarray:
    """[len(bounds), K] bool: pattern k occurs in ``data[:bound]``, for
    ascending ``bounds``.  Occurrence in a prefix is monotone in its
    length, so each pattern needs one search of the whole stream and a
    binary search over the bounds before its first match's end."""
    out = np.zeros((len(bounds), len(rxs)), bool)
    b = np.asarray(bounds, np.int64)
    for k, rx in enumerate(rxs):
        m = rx.search(data, 0, int(b[-1]) if b.size else 0)
        if m is None:
            continue
        hi = int(np.searchsorted(b, m.end()))  # first bound holding it
        lo = 0
        while lo < hi:  # is it already in an earlier, shorter prefix?
            mid = (lo + hi) // 2
            if rx.search(data, 0, int(b[mid])) is not None:
                hi = mid
            else:
                lo = mid + 1
        out[hi:, k] = True
    return out


def chunked_verdicts(rxs: list[re.Pattern], docs: list[bytes],
                     num_chunks: int) -> np.ndarray:
    """The control's [B, K]: each of ``num_chunks`` equal chunks searched
    on its own, verdicts or-ed."""
    out = np.zeros((len(docs), len(rxs)), bool)
    for b, d in enumerate(docs):
        cuts = np.linspace(0, len(d), num_chunks + 1).astype(int).tolist()
        pieces = [d[x:y] for x, y in zip(cuts[:-1], cuts[1:])]
        for k, rx in enumerate(rxs):
            out[b, k] = any(rx.search(p) is not None for p in pieces)
    return out


def first_share_verdicts(rxs: list[re.Pattern], docs: list[bytes],
                         chips: int) -> np.ndarray:
    """The control across ``chips`` chips: [B, K] from the first
    ``1 / chips`` of each document alone."""
    return doc_verdicts(rxs, [d[:len(d) // chips] for d in docs])
