#!/usr/bin/env python3
"""Bring-up run of the matcher's main path on a TPU.

    python chip_smoke.py             # one chip: pcre, prosite, stream phases
    python chip_smoke.py --chips 4   # four chips: the sharded phase only

One chip drives the entry points users call, at the widths of the repo's
fixture rule sets (``tests/fixtures/pattern_corpus.json``, compiled as
``.*(p)`` search DFAs), on the default ``local`` backend:

  pcre     the 14 PCRE patterns packed into one ``Matcher``, scanning at
           least 64 MiB of seeded log-like text in 64 KiB - 1 MiB documents
           (speculative plan), then one batch of documents under 1 KiB
           (sequential plan);
  prosite  the 20 PROSITE motifs packed into one table (72,531 states,
           lane width 22,857), scanning 8,192 seeded protein sequences of
           100 - 2,000 residues (median about 350) row by row, and the 8
           longest of them through the chunked speculative plan;
  stream   ``StreamMatcher`` on the PCRE pack: 512 streams, each fed 16
           interleaved segments of 64 B - 1.5 KB, then closed.

``--chips 4`` runs ``Matcher(backend="sharded")`` on the (doc, chunk)
meshes (1, 4) and (2, 2) over a 16 MiB PCRE corpus and the stream phase,
and the same inputs through the one-device ``local`` matcher; all must
agree bit for bit.

Every (document or stream, pattern) verdict is compared with Python's
``re.search`` on the same bytes.  Each phase prints one JSON line of facts
(bytes, documents, compile and wall seconds, peak device bytes); the last
line is ``{"ok": true, "device": {...}}``.  Without a TPU, or when any phase
fails, the script exits non-zero and prints no result.
"""

from __future__ import annotations

import argparse
import functools
import json
import pathlib
import re
import sys
import time

import numpy as np

import jax

ROOT = pathlib.Path(__file__).resolve().parent
KiB, MiB = 1 << 10, 1 << 20
RESIDUES = b"ACDEFGHIKLMNPQRSTVWY"


def require_tpu(chips: int = 1):
    """The device check: the first thing the run does.  Exits (non-zero,
    no result line) unless JAX's default devices are at least ``chips``
    TPUs; there is no CPU fallback."""
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU; JAX found "
                 f"{devices[0].platform!r} devices")
    if len(devices) < chips:
        sys.exit(f"chip_smoke: needs {chips} TPU chips, found {len(devices)}")
    return devices


# -- seeded inputs, made in bulk ---------------------------------------------

def _log_line_pool(rng: np.random.Generator, per_kind: int = 64
                   ) -> list[list[bytes]]:
    """Pools of log-like lines, one pool per kind.  Each kind carries the
    shapes some of the PCRE patterns look for (addresses, dates, URLs,
    tags, ...), so a document's verdicts depend on which kinds it holds."""
    words = [b"ok", b"job", b"run", b"the", b"to", b"of", b"and", b"new",
             b"set", b"get", b"put", b"log", b"id", b"key", b"end", b"req"]

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
        return b" ".join(rng.choice(words, size=int(rng.integers(3, 9))))

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
            rng.choice(words), num(0, 99), num(0, 9)),
        lambda: b'GET https://ex.com/%s/%s "%s"' % (
            rng.choice(words), num(0, 999), rng.choice(words)),
        lambda: b"<td class=c%s>%s</td>" % (num(0, 9), rng.choice(words)),
        lambda: b"color #%s" % hexs(6),
        lambda: b"req %s-%s-%s" % (hexs(8), hexs(4), hexs(4)),
        lambda: b"tel +%s %s %s" % (num(1, 99), num(100, 999),
                                     num(1000, 99999)),
        lambda: b"while (x) return y; else break",
        lambda: b"token=%s==" % b64(int(rng.integers(12, 17))),
        lambda: b"seq abab" + b"ba" * int(rng.integers(0, 3)),
    ]
    return [[k() + b"\n" for _ in range(per_kind)] for k in kinds]


def _log_doc(rng: np.random.Generator, pools: list[list[bytes]],
             n: int) -> bytes:
    """One ``n``-byte log-like document.  It draws its own subset of line
    kinds, so per-pattern verdicts vary across documents."""
    kinds = [0] + [k for k in range(1, len(pools)) if rng.random() < 0.5]
    lines = [ln for k in kinds for ln in pools[k]]
    avg = sum(map(len, lines)) / len(lines)
    pick = rng.integers(0, len(lines), size=int(n / avg) + 8)
    return b"".join([lines[i] for i in pick])[:n]


def log_corpus(rng: np.random.Generator, *, total_bytes: int, min_len: int,
               max_len: int) -> list[bytes]:
    """Log-like documents with log-uniform lengths in [min_len, max_len],
    at least ``total_bytes`` in all."""
    pools = _log_line_pool(rng)
    docs: list[bytes] = []
    made = 0
    while made < total_bytes:
        n = int(np.exp(rng.uniform(np.log(min_len), np.log(max_len + 1))))
        n = min(max(n, min_len), max_len)
        docs.append(_log_doc(rng, pools, n))
        made += n
    return docs


def short_docs(rng: np.random.Generator, n: int) -> list[bytes]:
    """``n`` log-like documents under 1 KiB; half are under 32 bytes, the
    length below which the planner takes the sequential plan (4 x the
    default 8 chunks)."""
    pools = _log_line_pool(rng)
    lines = [ln for pool in pools for ln in pool]
    out = []
    for i in range(n):
        cap = int(rng.integers(0, 32)) if i % 2 else int(rng.integers(32, KiB))
        pick = rng.integers(0, len(lines), size=cap // 8 + 2)
        out.append(b"".join([lines[j] for j in pick])[:cap])
    return out


def protein_corpus(rng: np.random.Generator, n: int, *, median: int = 350,
                   min_len: int = 100, max_len: int = 2000) -> list[bytes]:
    """``n`` protein sequences over the 20-letter alphabet, log-normal
    lengths clipped to [min_len, max_len] with the given median."""
    lens = np.clip(np.exp(rng.normal(np.log(median), 0.6, size=n)),
                   min_len, max_len).astype(np.int64)
    flat = np.frombuffer(RESIDUES, np.uint8)[
        rng.integers(0, len(RESIDUES), size=int(lens.sum()))].tobytes()
    ends = np.cumsum(lens)
    return [flat[e - n_:e] for e, n_ in zip(ends.tolist(), lens.tolist())]


def stream_segments(rng: np.random.Generator, n_streams: int, n_segments: int,
                    *, min_len: int = 64, max_len: int = 1536
                    ) -> list[list[bytes]]:
    """[stream][segment]: each stream is one log-like document cut into
    ``n_segments`` consecutive segments of min_len - max_len bytes."""
    pools = _log_line_pool(rng)
    lens = rng.integers(min_len, max_len + 1, size=(n_streams, n_segments))
    out = []
    for row in lens.tolist():
        doc = _log_doc(rng, pools, sum(row))
        cuts = np.cumsum([0] + row).tolist()
        out.append([doc[a:b] for a, b in zip(cuts[:-1], cuts[1:])])
    return out


# -- oracle and measurement ---------------------------------------------------

def search_oracle(patterns: list[str], docs: list[bytes]) -> np.ndarray:
    """[B, K] bool: ``re.search`` of pattern k in document b (bytes)."""
    rxs = [re.compile(p.encode("latin-1"), re.DOTALL) for p in patterns]
    return np.array([[rx.search(d) is not None for rx in rxs] for d in docs],
                    dtype=bool).reshape(len(docs), len(rxs))


def check(name: str, got: np.ndarray, want: np.ndarray) -> None:
    if got.shape != want.shape or not np.array_equal(got, want):
        bad = (np.argwhere(got != want)[:5].tolist()
               if got.shape == want.shape else (got.shape, want.shape))
        raise AssertionError(f"{name}: verdicts differ from the reference "
                             f"(first mismatches {bad})")


_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")


@functools.cache
def _compile_clock() -> list[float]:
    """One-element accumulator of the seconds JAX spends tracing, lowering
    and compiling, from the first call on (JAX's listeners are
    process-wide, so there is one per process)."""
    total = [0.0]

    def on_event(event: str, duration: float, **_) -> None:
        if event in _COMPILE_EVENTS:
            total[0] += duration

    jax.monitoring.register_event_duration_secs_listener(on_event)
    return total


def measure(phase: str, fn, **facts) -> dict:
    """Run ``fn()`` (which returns host arrays, so its device work has
    finished) and print one JSON line of facts about it."""
    dev = jax.devices()[0]
    clock = _compile_clock()
    c0, t0 = clock[0], time.perf_counter()
    extra = fn() or {}
    wall = time.perf_counter() - t0
    stats = dev.memory_stats() or {}
    line = {"phase": phase, "device_kind": dev.device_kind,
            "platform": dev.platform, **facts, **extra,
            "compile_s": clock[0] - c0, "wall_s": wall,
            "peak_bytes_in_use": stats.get("peak_bytes_in_use")}
    print(json.dumps(line), flush=True)
    return line


# -- phases -------------------------------------------------------------------

def pattern_pack(fixtures: list[dict], kind: str):
    """The fixture patterns of one kind as a one-block search PatternSet,
    plus their ``re`` patterns."""
    from repro.core import PatternSet

    entries = [e for e in fixtures if e["kind"] == kind]
    ps = PatternSet({e["name"]: e["pattern"] for e in entries},
                    k_blk=1 << 30, search=True)
    return ps, [e["pattern"] for e in entries]


def bulk_verdicts(matcher, docs: list[bytes]) -> tuple[np.ndarray, np.ndarray]:
    res = matcher.membership_batch(docs)
    return res.accepted, res.final_states


def stream_verdicts(sm, streams: list[list[bytes]]
                    ) -> tuple[np.ndarray, np.ndarray]:
    """Feed every stream's segments round-robin (segment r of every stream,
    then r + 1), then close them all."""
    sessions = [sm.open() for _ in streams]
    for r in range(len(streams[0])):
        for s, segs in zip(sessions, streams):
            s.feed(segs[r])
    results = [s.close() for s in sessions]
    return (np.stack([r.accepted for r in results]),
            np.stack([r.final_states for r in results]))


def require_plans(name: str, matchers, want: set[str]) -> list[str]:
    """The lowerings the matchers ran; fails unless ``want`` are among
    them."""
    got = sorted({kind for m in matchers
                  for kind in m.perf_report()["lowerings"].values()})
    if not want <= set(got):
        raise AssertionError(f"{name}: expected lowerings {sorted(want)} to "
                             f"run, got {got}")
    return got


def phase_pcre(fixtures, rng, *, total_bytes=64 * MiB, min_len=64 * KiB,
               max_len=MiB, n_short=128) -> dict:
    """PCRE pack, default Matcher: long documents (spec plan), then one
    batch of short ones (seq plan)."""
    from repro.core import Matcher

    ps, patterns = pattern_pack(fixtures, "pcre")
    docs = log_corpus(rng, total_bytes=total_bytes, min_len=min_len,
                      max_len=max_len)
    short = short_docs(rng, n_short)
    m = Matcher(ps)

    def run():
        acc, _ = bulk_verdicts(m, docs)
        check("pcre bulk", acc, search_oracle(patterns, docs))
        acc_s, _ = bulk_verdicts(m, short)
        check("pcre short", acc_s, search_oracle(patterns, short))
        return {"hit_rate": float(np.concatenate([acc, acc_s]).mean()),
                "lane_width": int(m.dev.i_max),
                "lowerings": require_plans("pcre", [m],
                                           {"spec-jnp", "seq-jnp"})}

    return measure("pcre", run, patterns=len(patterns),
                   states=int(m.packed.n_states), docs=len(docs) + len(short),
                   bytes=sum(map(len, docs)) + sum(map(len, short)))


def phase_prosite(fixtures, rng, *, n_seqs=8192, n_spec=8,
                  spec_batch_tile=8) -> dict:
    """PROSITE pack over protein sequences.  Every sequence runs row by row
    (``num_chunks=1``, the sequential plan); the ``n_spec`` longest also run
    through the chunked speculative plan (default ``num_chunks``), whose
    [chunks, K, S] lane carry is 22,857 lanes wide per pattern: 234M lanes
    per symbol step in a 64-row tile, which fits the chip's memory but not
    the run's time, so that plan runs 8-row tiles."""
    from repro.core import Matcher

    ps, patterns = pattern_pack(fixtures, "prosite")
    seqs = protein_corpus(rng, n_seqs)
    longest_idx = np.argsort([len(s) for s in seqs], kind="stable")[-n_spec:]
    longest = [seqs[i] for i in longest_idx]
    seq_m = Matcher(ps, num_chunks=1)
    spec_m = Matcher(ps, batch_tile=spec_batch_tile)

    def run():
        want = search_oracle(patterns, seqs)
        acc, fin = bulk_verdicts(seq_m, seqs)
        check("prosite seq", acc, want)
        acc_l, fin_l = bulk_verdicts(spec_m, longest)
        check("prosite spec", acc_l, search_oracle(patterns, longest))
        check("prosite spec vs seq finals", fin_l, fin[longest_idx])
        return {"hit_rate": float(acc.mean()),
                "lane_width": int(spec_m.dev.i_max),
                "lowerings": require_plans("prosite", [seq_m, spec_m],
                                           {"spec-jnp", "seq-jnp"})}

    return measure("prosite", run, patterns=len(patterns),
                   states=int(seq_m.packed.n_states), docs=len(seqs),
                   bytes=sum(map(len, seqs)), spec_docs=len(longest),
                   spec_bytes=sum(map(len, longest)),
                   spec_batch_tile=spec_batch_tile,
                   median_len=int(np.median([len(s) for s in seqs])))


def phase_stream(fixtures, rng, *, n_streams=512, n_segments=16) -> dict:
    """StreamMatcher on the PCRE pack; one tick per round of segments."""
    from repro.streaming import StreamMatcher, TickPolicy

    ps, patterns = pattern_pack(fixtures, "pcre")
    streams = stream_segments(rng, n_streams, n_segments)
    sm = StreamMatcher(ps, policy=TickPolicy(max_batch=n_streams,
                                             max_delay=n_streams * 4))

    def run():
        acc, _ = stream_verdicts(sm, streams)
        check("stream", acc, search_oracle(patterns,
                                           [b"".join(s) for s in streams]))
        return {"ticks": int(sm.stats.ticks), "hit_rate": float(acc.mean()),
                "lowerings": require_plans("stream", [sm.matcher], set())}

    return measure("stream", run, patterns=len(patterns), streams=n_streams,
                   segments=n_streams * n_segments,
                   bytes=sum(len(x) for s in streams for x in s))


def phase_sharded(fixtures, rng, *, meshes=((1, 4), (2, 2)),
                  total_bytes=16 * MiB, min_len=64 * KiB, max_len=256 * KiB,
                  n_short=128, n_streams=512, n_segments=16) -> list[dict]:
    """The sharded backend on each (doc, chunk) mesh against the one-device
    local matcher: bulk verdicts and final states, and closed streams, bit
    for bit, and both against ``re``.  The bulk corpus is the pcre phase's
    cut to 16 MiB of 64 - 256 KiB documents: the local reference pays for
    every symbol step on four chips' time."""
    from repro.core import Matcher
    from repro.streaming import StreamMatcher, TickPolicy

    ps, patterns = pattern_pack(fixtures, "pcre")
    docs = log_corpus(rng, total_bytes=total_bytes, min_len=min_len,
                      max_len=max_len) + short_docs(rng, n_short)
    streams = stream_segments(rng, n_streams, n_segments)
    policy = TickPolicy(max_batch=n_streams, max_delay=n_streams * 4)
    want_docs = search_oracle(patterns, docs)
    want_streams = search_oracle(patterns, [b"".join(s) for s in streams])
    ref: dict = {}

    def run_one(label, **kw):
        m = Matcher(ps, **kw)
        sm = StreamMatcher(ps, policy=policy, **kw)

        def run():
            out = bulk_verdicts(m, docs) + stream_verdicts(sm, streams)
            check(f"{label} bulk", out[0], want_docs)
            check(f"{label} stream", out[2], want_streams)
            if ref:
                for name, a, b in zip(("accepted", "final_states",
                                       "stream accepted",
                                       "stream final_states"), out, ref["out"]):
                    check(f"{label} vs local {name}", a, b)
            else:
                ref["out"] = out
            want = {"spec-sharded"} if kw else {"spec-jnp"}
            return {"lowerings": require_plans(label, [m, sm.matcher], want)}

        return measure(f"sharded/{label}", run, patterns=len(patterns),
                       docs=len(docs), bytes=sum(map(len, docs)),
                       streams=n_streams)

    return [run_one("local")] + [
        run_one(f"{d}x{c}", backend="sharded", mesh_shape=(d, c))
        for d, c in meshes]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded phase, on four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = require_tpu(args.chips)
    sys.path.insert(0, str(ROOT / "src"))
    from repro.data import load_pattern_fixtures
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    fixtures = load_pattern_fixtures()
    rng = np.random.default_rng(args.seed)
    if args.chips == 4:
        phase_sharded(fixtures, rng)
    else:
        phase_pcre(fixtures, rng)
        phase_prosite(fixtures, rng)
        phase_stream(fixtures, rng)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
