"""The text the traffic generators draw from: the same seed gives the same
bytes.  A mix under ``traffic/`` names its generator (``generators/``);
both generators build on these.

  log      synthetic log-like text; most lines are routine ``INFO`` lines
           and a share ``special_rate`` of them carry one of a document's
           own subset of special kinds (addresses, dates, URLs, tags, ...),
           so verdicts depend on what each document holds;
  protein  sequences over the 20 amino acids, drawn at the mix's residue
           frequencies;
  lengths  the fixed multiset of one call's lengths, from the mix's length
           distribution.
"""

from __future__ import annotations

import numpy as np

RESIDUES = b"ACDEFGHIKLMNPQRSTVWY"
_WORDS = [b"ok", b"job", b"run", b"the", b"to", b"of", b"and", b"new",
          b"set", b"get", b"put", b"log", b"id", b"key", b"end", b"req"]


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """Independent generator per (seed, stream id); any non-negative seed,
    however many bits it has."""
    return np.random.default_rng([int(seed), *stream])


# -- log text -----------------------------------------------------------------

def line_pools(rng: np.random.Generator, per_kind: int = 64
               ) -> list[list[bytes]]:
    """Pools of log lines, one per kind; kind 0 is the routine line.  Each
    other kind carries the shapes some of the PCRE patterns look for."""

    def num(lo, hi):
        return str(int(rng.integers(lo, hi))).encode()

    def pick(alphabet: bytes, n: int) -> bytes:
        return np.frombuffer(alphabet, np.uint8)[
            rng.integers(0, len(alphabet), size=n)].tobytes()

    def hexs(n):
        return pick(b"0123456789abcdef", n)

    def b64(n):
        return pick(b"ABCDEFGHIJKLMNOPQRSTUVWXYZabcdef0123456789+/", n)

    def plain():
        return b" ".join(rng.choice(_WORDS, size=int(rng.integers(3, 9))))

    kinds = [
        lambda: b"INFO " + plain(),
        lambda: b"at 2026-10-%02d %02d:%02d:%02d.%s load %s.%s" % (
            int(rng.integers(1, 29)), int(rng.integers(0, 24)),
            int(rng.integers(0, 60)), int(rng.integers(0, 60)),
            num(0, 999), num(0, 9), num(0, 99)),
        lambda: b"conn from %s.%s.%s.%s port %s" % (
            num(1, 255), num(0, 255), num(0, 255), num(0, 255),
            num(1, 65535)),
        lambda: b"mail to %s_%s@host%s.org" % (
            rng.choice(_WORDS), num(0, 99), num(0, 9)),
        lambda: b'GET https://ex.com/%s/%s "%s"' % (
            rng.choice(_WORDS), num(0, 999), rng.choice(_WORDS)),
        lambda: b"<td class=c%s>%s</td>" % (num(0, 9), rng.choice(_WORDS)),
        lambda: b"color #%s" % hexs(6),
        lambda: b"req %s-%s-%s" % (hexs(8), hexs(4), hexs(4)),
        lambda: b"tel +%s %s %s" % (num(1, 99), num(100, 999),
                                     num(1000, 99999)),
        lambda: b"while (x) return y; else break",
        lambda: b"token=%s==" % b64(int(rng.integers(12, 17))),
        lambda: b"seq abab" + b"ba" * int(rng.integers(0, 3)),
    ]
    return [[k() + b"\n" for _ in range(per_kind)] for k in kinds]


def log_doc(rng: np.random.Generator, pools: list[list[bytes]], n: int,
            special_rate: float) -> bytes:
    """One ``n``-byte log-like document.  It draws its own subset of the
    special kinds (each with probability 1/2); each line is one of them
    with probability ``special_rate``, else a routine line."""
    special = [k for k in range(1, len(pools)) if rng.random() < 0.5] or [1]
    routine = pools[0]
    avg = sum(map(len, routine)) / len(routine)
    out = b""
    while len(out) < n:
        n_lines = int((n - len(out)) / avg * 1.1) + 8
        is_special = rng.random(n_lines) < special_rate
        kind = np.asarray(special)[rng.integers(0, len(special),
                                                size=n_lines)]
        kind = np.where(is_special, kind, 0)
        idx = rng.integers(0, len(routine), size=n_lines)
        out += b"".join([pools[k][i] for k, i in
                         zip(kind.tolist(), idx.tolist())])
    return out[:n]


# -- lengths ------------------------------------------------------------------

def length_quantiles(mix: dict, n: int) -> np.ndarray:
    """The ``n`` mid-quantiles of the mix's length distribution, clipped to
    ``[min_bytes, max_bytes]``: the fixed multiset of one call's lengths."""
    q = (np.arange(n) + 0.5) / n
    lo, hi = int(mix["min_bytes"]), int(mix["max_bytes"])
    if mix["lengths"] == "log_uniform":
        x = np.exp(np.log(lo) + q * (np.log(hi + 1) - np.log(lo)))
    elif mix["lengths"] == "log_normal":
        from statistics import NormalDist
        z = np.array([NormalDist().inv_cdf(float(p)) for p in q])
        x = np.exp(np.log(float(mix["median_bytes"])) + float(mix["sigma"]) * z)
    else:
        raise ValueError(f"unknown length distribution {mix['lengths']!r}")
    return np.clip(x.astype(np.int64), lo, hi)


def protein_seqs(rng: np.random.Generator, lengths: np.ndarray,
                 freqs: dict) -> list[bytes]:
    """One sequence per length, residues drawn at ``freqs`` (percent)."""
    alphabet = np.frombuffer(b"".join(k.encode() for k in freqs), np.uint8)
    p = np.array(list(freqs.values()), np.float64)
    flat = alphabet[rng.choice(alphabet.size, size=int(lengths.sum()),
                               p=p / p.sum())].tobytes()
    ends = np.cumsum(lengths).tolist()
    return [flat[e - n:e] for e, n in zip(ends, lengths.tolist())]
