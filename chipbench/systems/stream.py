"""Open loop of ``StreamMatcher`` flows: each arrival is fed once it is
due, whether or not the matcher has kept up, and the oldest pending
segment is flushed once it has waited ``max_delay_s`` (the scheduler owns
no timer).  Latency runs from when a segment was due to when the tick that
consumed it returned.

The config gives the ``Matcher`` options (``matcher``), the
``tick_policy`` and the rule set; its inputs are an ``Arrivals`` schedule
(``generators/flows.py``).  The control answers every fed segment at once
from the start state with ``re`` (no cursor carried across ticks).
"""

from __future__ import annotations

import contextlib
import dataclasses
import time

import numpy as np

from chipbench import reference, tables


class StreamSystem:
    """``StreamMatcher`` open / feed / flush / close."""

    def __init__(self, sm):
        self.sm = sm

    def open(self):
        return self.sm.open()

    def feed(self, session, data: bytes) -> None:
        self.sm.feed(session, data)

    def flush(self) -> None:
        self.sm.flush()

    def ticks(self) -> int:
        return self.sm.stats.ticks

    def pending(self, session) -> bool:
        return session.pending_bytes > 0

    def snapshot(self, session):
        """What the flow has answered so far; cheap (cursors are frozen)."""
        return session.cursor

    def answer(self, snap) -> np.ndarray:
        return snap.accepted(self.sm.matcher.dev)

    def close(self, session) -> np.ndarray:
        return session.close().accepted


@dataclasses.dataclass
class OpenWindow:
    window_s: float
    due: np.ndarray      # [N] s after the window opened
    fed: np.ndarray      # [N] when the arrival was fed (lateness = fed - due)
    done: np.ndarray     # [N] when its tick returned (NaN: never)
    marks: list          # per flow: [(bytes answered for, snapshot), ...]
    fed_bytes: np.ndarray  # [F] bytes fed per flow


def open_loop(system, sessions: list, arr, max_delay_s: float, *,
              span=contextlib.nullcontext, clock=time.perf_counter,
              sleep=time.sleep) -> OpenWindow:
    """Feed every arrival when it is due; flush the oldest pending segment
    at ``max_delay_s``; run until every arrival has been consumed."""
    n = arr.n
    fed = np.full(n, np.nan)
    done = np.full(n, np.nan)
    fed_bytes = np.zeros(len(sessions), np.int64)
    marks: list = [[] for _ in sessions]
    inflight: list[int] = []

    def consumed(now: float) -> None:
        # a tick drains the whole queue: every in-flight segment is answered
        done[inflight] = now
        for f in dict.fromkeys(arr.flow[inflight].tolist()):
            marks[f].append((int(fed_bytes[f]),
                             system.snapshot(sessions[f])))
        inflight.clear()

    i = 0
    t0 = clock()
    while i < n or inflight:
        now = clock() - t0
        if i < n and arr.due[i] <= now:
            f = int(arr.flow[i])
            ticks = system.ticks()
            fed[i] = now
            with span("chipbench.feed"):
                system.feed(sessions[f], arr.segment(i))
            fed_bytes[f] = arr.end[i]
            inflight.append(i)
            if system.ticks() != ticks:
                consumed(clock() - t0)
            elif not system.pending(sessions[f]):
                # a decided flow is answered at admission, with no tick
                inflight.pop()
                done[i] = clock() - t0
                marks[f].append((int(fed_bytes[f]),
                                 system.snapshot(sessions[f])))
            i += 1
            continue
        wake = arr.due[i] if i < n else np.inf
        if inflight:
            deadline = fed[inflight[0]] + max_delay_s
            if now >= deadline:
                with span("chipbench.flush"):
                    system.flush()
                consumed(clock() - t0)
                continue
            wake = min(wake, deadline)
        with span("chipbench.wait"):
            sleep(max(0.0, wake - now))
    return OpenWindow(clock() - t0, arr.due, fed, done, marks, fed_bytes)


class Cell:
    def __init__(self, cfg: dict, mix: dict, arrivals, plant):
        from repro.streaming import StreamMatcher, TickPolicy
        self.cfg, self.mix = cfg, mix
        packed, self.tables_cached = tables.packed_tables(cfg)
        self.sm = StreamMatcher(packed,
                                policy=TickPolicy(**cfg["tick_policy"]),
                                **cfg["matcher"])
        self.system = plant(StreamSystem(self.sm))
        self.load(arrivals)

    def load(self, arrivals) -> None:
        """A schedule and fresh flows to run it on (set-up, not window)."""
        self.arr = arrivals
        self.sessions = [self.system.open()
                         for _ in range(int(self.mix["flows"]))]

    def warm(self) -> None:
        # the scheduler's sequential width is sticky and grows with the
        # longest coalesced segment: open it at the mix's warm width, so
        # no tick of the window meets a new shape
        s = self.system.open()
        self.system.feed(s, b"\n" * int(self.mix["warm_width"]))
        self.system.flush()
        self.system.close(s)

    def window(self, seconds: float, span) -> None:
        st = self.sm.stats
        before = (st.segments, st.rows_dispatched, st.ticks)
        self.win = open_loop(
            self.system, self.sessions, self.arr,
            float(self.cfg["tick_policy"]["max_delay_s"]), span=span)
        self.delta = [a - b for a, b in zip(
            (st.segments, st.rows_dispatched, st.ticks), before)]

    def latencies_ms(self) -> np.ndarray:
        return (self.win.done - self.win.due) * 1e3

    def end_to_end(self) -> dict:
        # a segment never consumed has no latency; the check counts it
        # (``unanswered``) and the run is not correct
        lat = self.latencies_ms()
        lat = lat[np.isfinite(lat)]
        return {"stream_p50_ms": float(np.percentile(lat, 50)),
                "stream_p95_ms": float(np.percentile(lat, 95))}

    def counters(self) -> dict:
        segs, rows, ticks = self.delta
        late = (self.win.fed - self.win.due) * 1e3
        return {"bytes": int(self.win.fed_bytes.sum()), "arrivals": self.arr.n,
                "segments": segs, "rows": rows, "ticks": ticks,
                "occupancy": segs / rows if rows else None,
                "lateness_ms_p50": float(np.median(late)) if late.size else None,
                "lateness_ms_p99": (float(np.percentile(late, 99))
                                    if late.size else None),
                "lowerings": sorted(set(self.sm.matcher.perf_report()
                                        ["lowerings"].values())),
                "tables_cached": self.tables_cached}

    def check(self) -> dict:
        rxs = reference.compile_patterns(
            [p["regex"] for p in self.cfg["patterns"]])
        unanswered = int(np.isnan(self.win.done).sum())
        wrong = bad = 0
        for f, sess in enumerate(self.sessions):
            marks = self.win.marks[f]
            bounds = [b for b, _ in marks] + [int(self.win.fed_bytes[f])]
            got = [np.asarray(self.system.answer(s), bool) for _, s in marks]
            got.append(np.asarray(self.system.close(sess), bool))
            want = reference.prefix_verdicts(rxs, self.arr.flows[f], bounds)
            diff = np.stack(got) != want
            wrong += int(diff.sum())
            bad += int(diff.any(axis=1).sum())
        return {"attempted": self.arr.n, "failed": bad + unanswered,
                "verdict_mismatches": wrong, "unanswered": unanswered}


class ControlStream:
    """Every fed segment is answered at once, from the start state."""

    def __init__(self, patterns: list[str]):
        self.rxs = reference.compile_patterns(patterns)
        self.n_fed = 0

    def open(self) -> list:
        return [np.zeros(len(self.rxs), bool)]

    def feed(self, session: list, data: bytes) -> None:
        session[0] = session[0] | reference.doc_verdicts(self.rxs, [data])[0]
        self.n_fed += 1

    def flush(self) -> None:
        pass

    def ticks(self) -> int:
        return self.n_fed

    def pending(self, session) -> bool:
        return False

    def snapshot(self, session) -> np.ndarray:
        return session[0].copy()

    def answer(self, snap) -> np.ndarray:
        return snap

    def close(self, session) -> np.ndarray:
        return session[0]


def control(cfg: dict):
    """The plant that puts the control in the program's place."""
    patterns = [p["regex"] for p in cfg["patterns"]]
    return lambda system: ControlStream(patterns)
