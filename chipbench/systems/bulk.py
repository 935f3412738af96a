"""Closed loop of ``Matcher.membership_batch``: a caller that waits for
every reply, calling back to back on the batches of the mix's pool
(cycled).  Reports the bytes whose verdicts came back.

The config gives the ``Matcher`` options (``matcher``: backend, mesh,
chunks) and the rule set; its inputs are a list of batches
(``generators/docs.py``).  The control puts ``re`` in the program's place
with the method's guarantee broken (``reference.py``): on one chip each of
8 chunks (the program's default chunk count) searched from the start and
or-ed; on a mesh whose chunk axis spans D chips the exchange left out, so
the first chip's share answers.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time

import numpy as np

from chipbench import reference, tables

CONTROL_CHUNKS = 8


class BulkSystem:
    """``Matcher.membership_batch``: [B, K] verdicts per call."""

    def __init__(self, matcher):
        self.matcher = matcher

    def match(self, docs: list[bytes]) -> np.ndarray:
        return self.matcher.membership_batch(docs).accepted


@dataclasses.dataclass
class ClosedWindow:
    window_s: float
    n_bytes: int
    calls: list  # (batch index, [B, K] verdicts)


def closed_loop(system, batches: list[list[bytes]], seconds: float, *,
                span=contextlib.nullcontext, clock=time.perf_counter
                ) -> ClosedWindow:
    """Calls until ``seconds`` have passed; the window ends with the reply
    of the last call, so every byte counted was answered inside it."""
    sizes = [sum(map(len, b)) for b in batches]
    calls, n_bytes = [], 0
    t0 = clock()
    while True:
        b = len(calls) % len(batches)
        with span("chipbench.call"):
            acc = np.array(system.match(batches[b]), bool)
        calls.append((b, acc))
        n_bytes += sizes[b]
        if clock() - t0 >= seconds:
            break
    return ClosedWindow(clock() - t0, n_bytes, calls)


class Cell:
    def __init__(self, cfg: dict, mix: dict, batches: list[list[bytes]],
                 plant):
        from repro.core import Matcher
        if int(mix["max_bytes"]) > int(cfg["max_doc_bytes"]):
            raise ValueError("the mix's documents are longer than the "
                             "config's max_doc_bytes")
        self.cfg, self.mix = cfg, mix
        packed, self.tables_cached = tables.packed_tables(cfg)
        self.matcher = Matcher(packed, **cfg["matcher"])
        self.system = plant(BulkSystem(self.matcher))
        self.batches = batches

    def warm(self) -> None:
        self.system.match(self.batches[0])

    def window(self, seconds: float, span) -> None:
        self.win = closed_loop(self.system, self.batches, seconds, span=span)

    def end_to_end(self) -> dict:
        return {"scan_MB_per_s": self.win.n_bytes / self.win.window_s / 1e6}

    def counters(self) -> dict:
        rep = self.matcher.perf_report()
        return {"bytes": self.win.n_bytes, "calls": len(self.win.calls),
                "docs": sum(len(self.batches[b]) for b, _ in self.win.calls),
                "lowerings": sorted(set(rep["lowerings"].values())),
                "tables_cached": self.tables_cached}

    def check(self) -> dict:
        rxs = reference.compile_patterns(
            [p["regex"] for p in self.cfg["patterns"]])
        want = {}
        wrong = bad_docs = unanswered = docs = 0
        for b, got in self.win.calls:
            if b not in want:
                want[b] = reference.doc_verdicts(rxs, self.batches[b])
            docs += len(self.batches[b])
            if got.shape != want[b].shape:
                unanswered += len(self.batches[b])
                continue
            diff = got != want[b]
            wrong += int(diff.sum())
            bad_docs += int(diff.any(axis=1).sum())
        return {"attempted": docs, "failed": bad_docs + unanswered,
                "verdict_mismatches": wrong, "unanswered": unanswered}


class ControlBulk:
    def __init__(self, patterns: list[str], chunk_chips: int = 1):
        self.rxs = reference.compile_patterns(patterns)
        self.chunk_chips = chunk_chips

    def match(self, docs: list[bytes]) -> np.ndarray:
        if self.chunk_chips > 1:
            return reference.first_share_verdicts(self.rxs, docs,
                                                  self.chunk_chips)
        return reference.chunked_verdicts(self.rxs, docs, CONTROL_CHUNKS)


def control(cfg: dict):
    """The plant that puts the control in the program's place."""
    patterns = [p["regex"] for p in cfg["patterns"]]
    mesh = cfg["matcher"].get("mesh_shape") or [1, 1]
    return lambda system: ControlBulk(patterns, chunk_chips=int(mesh[1]))
