#!/usr/bin/env python3
"""The program's own spans in a profiler trace, on the device's clock.

    python3 chipbench/program_spans.py [trace dir or .xplane.pb] [--chips n]

prints the reduction below as JSON (default: the newest trace under
``chipbench/.trace``, which a ``--trace 1`` run leaves behind).

The matcher writes host spans named ``repro.*`` (``repro.core.engine``
``Matcher._dispatch``): one root span per public call
(``repro.membership_batch``, ``repro.advance_segments``,
``repro.advance_cursors``) holding ``repro.plan``, then per tile
``repro.pack``, ``repro.launch`` (with ``repro.compile`` inside on a new
program's first call) and ``repro.wait``, and last ``repro.finish``, whose
kwargs carry the call's ``real_symbols`` and ``run_symbols``.  Its device
programs are named (``jit_seq_scan``, ``jit_spec_scan``, ...) and its
stages sit in named scopes (``classify``, ``seed``, ``chunk_scan``,
``merge``, ``compose_cursor``).  Over the same window as
``tracing.summarize`` (the ``chipbench.window`` span), this module adds:

  * an idle gap is labelled by the innermost span, ``chipbench.*`` or
    ``repro.*``, that covers more than half of it; where none does, by the
    span that overlaps it most, and by the window where none overlaps;
  * host stall: the share of the window in which the device was idle
    while the host was inside a root span, mean over chips; the rest of
    the idle is the caller's;
  * scan fill: the real symbols over the row-steps the scan loops ran,
    summed over the ``repro.finish`` spans in the window;
  * a device op is named ``<program>/<stage>/<hlo name>`` where the trace
    says which program and stage it belongs to, else by its bare HLO name.
    The program is the ``XLA Modules`` event (``jit_seq_scan(<id>)``) the
    op runs in; the stage is read from the ``tf_op`` stat of the op's event
    metadata (``jit(seq_scan)/chunk_scan/while/...``), which ``ProfileData``
    does not expose, so ``op_scopes`` reads it from the file's bytes;
  * the clock check: each tile's device program starts after its
    ``repro.launch`` begins and ends before its ``repro.wait`` ends.

Where the program wrote no such span (a build without them), each reading
is None.  The metrics ``host_stall_pct.bulk`` and ``scan_fill_pct.bulk``
read this module; ``tracing.py`` is left as the accepted reduction.
"""

from __future__ import annotations

import bisect
import dataclasses
import functools
import json
import pathlib
import re
import sys

import numpy as np

if __package__ in (None, ""):
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from chipbench import tracing  # noqa: E402

ROOT_SPANS = ("repro.membership_batch", "repro.advance_segments",
              "repro.advance_cursors")
FINISH_SPAN = "repro.finish"
MODULES_LINE = "XLA Modules"
STAGES = ("classify", "seed", "chunk_scan", "merge", "compose_cursor")
# ``jit(<program>)/...`` in a scope stat, ``jit_<program>(<id>)`` as the
# name of an XLA Modules event
_JIT_SCOPE = re.compile(r"jit\((\w+)\)")
_MODULE = re.compile(r"^jit_(\w+?)(\(\d+\))?$")


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start: float   # ns
    end: float     # ns
    stats: dict

    @property
    def length(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class ProgramTrace:
    lo: float                          # window, ns
    hi: float
    spans: list[Span]                  # chipbench.* and repro.*, by start
    busy: list[np.ndarray]             # per chip: disjoint busy intervals
    op_s: list[dict[str, float]]       # per chip: scoped op name -> s
    modules: list[list[Span]]          # per chip: XLA Modules events

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) * 1e-9

    def program_spans(self, names=None) -> list[Span]:
        return [s for s in self.spans if s.name.startswith("repro.")
                and (names is None or s.name in names)]


def _varint(buf, i: int) -> tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf, lo: int, hi: int):
    """(field number, value) of the protobuf message in ``buf[lo:hi]``: an
    int for varints, a (start, end) slice for length-delimited fields."""
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif wire in (1, 5):
            value, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, value


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def op_scopes(data: bytes) -> dict[str, str]:
    """HLO event name -> its ``tf_op`` stat, over the device planes of a
    serialized XSpace (``XPlane.event_metadata`` and ``stat_metadata``;
    fields 4 and 5 of tsl's xplane.proto, ``XStat.str_value`` or
    ``ref_value`` 5 and 7).  Lines are skipped whole, so this costs little
    even on a large trace."""
    buf = memoryview(data)
    out: dict[str, str] = {}
    for f, plane in _fields(buf, 0, len(buf)):
        if f != 1:
            continue
        name, events, stat_names = "", [], {}
        for pf, v in _fields(buf, *plane):
            if pf == 2:
                name = _text(buf, v)
            elif pf == 4:
                events += [ev for kf, ev in _fields(buf, *v) if kf == 2]
            elif pf == 5:
                md = dict(kv for kv in _fields(buf, *v))
                if 2 in md:
                    sm = dict(_fields(buf, *md[2]))
                    stat_names[sm.get(1, 0)] = _text(buf, sm[2]) \
                        if 2 in sm else ""
        if not tracing.DEVICE_PLANE.match(name):
            continue
        tf_op = {k for k, n in stat_names.items() if n == "tf_op"}
        for ev in events:
            ev_name = scope = None
            for ef, v in _fields(buf, *ev):
                if ef == 2:
                    ev_name = _text(buf, v)
                elif ef == 5:
                    st = dict(_fields(buf, *v))
                    if st.get(1) in tf_op:
                        scope = (_text(buf, st[5]) if 5 in st
                                 else stat_names.get(st.get(7)))
            if ev_name and scope:
                out[ev_name] = scope
    return out


def scoped_name(hlo: str, scope: str | None, module: str | None) -> str:
    """``<program>/<stage>/<hlo>`` from what the trace says; the parts it
    does not say are left out, so the HLO name is always the suffix."""
    program = stage = None
    if scope:
        m = _JIT_SCOPE.search(scope)
        program = m.group(1) if m else None
        parts = scope.split("/")
        stage = next((p for p in parts if p in STAGES), None)
    if program is None and module:
        m = _MODULE.match(module)
        program = m.group(1) if m else None
    return "/".join(p for p in (program, stage, hlo) if p)


def _spans_of(line, keep=None) -> list[Span]:
    """The line's events (those whose name ``keep`` accepts, with their
    stats; all of them, without, when ``keep`` is None)."""
    return [Span(e.name, float(e.start_ns), float(e.start_ns + e.duration_ns),
                 {} if keep is None else dict(e.stats))
            for e in line.events if keep is None or keep(e.name)]


def _module_at(modules: list[Span], starts: list[float], t: float):
    j = bisect.bisect_right(starts, t) - 1
    if j >= 0 and modules[j].end >= t:
        return modules[j].name
    return None


def reduce_planes(planes, chips: int | None = None,
                  scopes: dict[str, str] | None = None) -> ProgramTrace:
    """Like ``tracing.summarize_planes``, over the same planes and window,
    keeping the program's spans and naming device ops by their program
    and by their ``scopes`` (``op_scopes``)."""
    scopes = scopes or {}
    spans: list[Span] = []
    devices = []
    for plane in planes:
        m = tracing.DEVICE_PLANE.match(plane.name)
        if m and (chips is None or int(m.group(1)) < chips):
            lines = {line.name: line for line in plane.lines}
            mods = (_spans_of(lines[MODULES_LINE])
                    if MODULES_LINE in lines else [])
            ops = (_spans_of(lines[tracing.OPS_LINE])
                   if tracing.OPS_LINE in lines else [])
            devices.append((int(m.group(1)), ops, mods))
        elif plane.name == tracing.HOST_PLANE:
            for line in plane.lines:
                spans += _spans_of(line, lambda n: n.startswith(
                    ("chipbench.", "repro.")))
    windows = [s for s in spans if s.name == tracing.WINDOW_SPAN]
    if not windows:
        raise ValueError(f"trace has no {tracing.WINDOW_SPAN!r} host span")
    if not devices:
        raise ValueError("trace has no /device:TPU:<n> plane")
    lo, hi = windows[0].start, windows[0].end
    busy, op_s, modules = [], [], []
    for _, ops, mods in sorted(devices, key=lambda d: d[0]):
        mods.sort(key=lambda s: s.start)
        starts = [s.start for s in mods]
        iv = np.array([[o.start, o.end] for o in ops],
                      np.float64).reshape(-1, 2)
        busy.append(tracing.union(tracing.clip(iv, lo, hi)))
        per_op: dict[str, float] = {}
        names: dict[tuple, str] = {}  # a few dozen ops repeat per step
        for o in ops:
            d = min(o.end, hi) - max(o.start, lo)
            if d > 0:
                key = (o.name, _module_at(mods, starts, o.start))
                if key not in names:
                    names[key] = scoped_name(tracing.op_name(o.name),
                                             scopes.get(o.name), key[1])
                per_op[names[key]] = per_op.get(names[key], 0.0) + d * 1e-9
        op_s.append(per_op)
        modules.append([s for s in mods if s.end > lo and s.start < hi])
    spans.sort(key=lambda s: s.start)
    return ProgramTrace(lo, hi, spans, busy, op_s, modules)


def _overlap(s: Span, lo: float, hi: float) -> float:
    return max(0.0, min(s.end, hi) - max(s.start, lo))


def gap_label(spans: list[Span], lo: float, hi: float) -> str:
    """The innermost span covering more than half of [lo, hi]; else the
    span overlapping it most; else the window."""
    inner = [s for s in spans if s.name != tracing.WINDOW_SPAN
             and s.start < hi and s.end > lo]
    most = [s for s in inner if 2 * _overlap(s, lo, hi) > hi - lo]
    if most:
        return min(most, key=lambda s: s.length).name
    if inner:
        return max(inner, key=lambda s: _overlap(s, lo, hi)).name
    return tracing.WINDOW_SPAN


def _idle_with_spans(t: ProgramTrace):
    """(gap start, gap end, the spans open in the gap) over every idle gap
    of every chip."""
    inner = [s for s in t.spans if s.name != tracing.WINDOW_SPAN]
    starts = np.array([s.start for s in inner])
    ends = np.array([s.end for s in inner])
    for busy in t.busy:
        for gs, ge in tracing.gaps(busy, t.lo, t.hi).tolist():
            idx = np.flatnonzero((starts < ge) & (ends > gs))
            yield gs, ge, [inner[i] for i in idx]


def idle_gaps(t: ProgramTrace) -> list[tuple[str, float]]:
    """Every idle gap of every chip, labelled, longest first (seconds)."""
    out = [(gap_label(open_, gs, ge), (ge - gs) * 1e-9)
           for gs, ge, open_ in _idle_with_spans(t)]
    out.sort(key=lambda g: -g[1])
    return out


def idle_by_span(t: ProgramTrace) -> dict[str, float]:
    """Idle seconds split by the innermost span open at each idle instant
    (the window where none of ours is), mean over chips."""
    out: dict[str, float] = {}
    for gs, ge, open_ in _idle_with_spans(t):
        cuts = sorted({gs, ge} | {min(max(x, gs), ge) for s in open_
                                  for x in (s.start, s.end)})
        for a, b in zip(cuts, cuts[1:]):
            mid = (a + b) / 2
            cover = [s for s in open_ if s.start <= mid < s.end]
            name = (min(cover, key=lambda s: s.length).name if cover
                    else tracing.WINDOW_SPAN)
            out[name] = out.get(name, 0.0) + (b - a) * 1e-9 / len(t.busy)
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def _intersect_s(a: np.ndarray, b: np.ndarray) -> float:
    """Total length of the intersection of two sets of disjoint sorted
    intervals, in seconds."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i, 0], b[j, 0]), min(a[i, 1], b[j, 1])
        if hi > lo:
            total += hi - lo
        if a[i, 1] < b[j, 1]:
            i += 1
        else:
            j += 1
    return total * 1e-9


def host_stall_pct(t: ProgramTrace) -> float | None:
    """Share of the window in which a chip idled while the host was inside
    a root span, mean over chips; None without root spans."""
    roots = t.program_spans(ROOT_SPANS)
    if not roots:
        return None
    iv = np.array([[s.start, s.end] for s in roots], np.float64)
    inside = tracing.union(tracing.clip(iv, t.lo, t.hi))
    return float(np.mean([
        100.0 * _intersect_s(tracing.gaps(b, t.lo, t.hi), inside)
        / t.window_s for b in t.busy]))


def dispatch_counts(t: ProgramTrace) -> dict | None:
    """The calls' counts summed over the ``repro.finish`` spans that start
    in the window; None where there are none."""
    done = [s for s in t.program_spans((FINISH_SPAN,))
            if t.lo <= s.start < t.hi]
    if not done:
        return None
    keys = ("tiles", "rows", "real_symbols", "run_symbols")
    return {k: int(sum(s.stats.get(k, 0) for s in done)) for k in keys}


def scan_fill_pct(t: ProgramTrace) -> float | None:
    n = dispatch_counts(t)
    if not n or not n["run_symbols"]:
        return None
    return 100.0 * n["real_symbols"] / n["run_symbols"]


def clock_check(t: ProgramTrace) -> dict | None:
    """Pairs the window's tiles (``repro.launch`` and the ``repro.wait``
    after it) with chip 0's program executions in order, and counts the
    tiles whose program starts after the launch begins and ends before
    the wait ends."""
    launches = [s for s in t.program_spans(("repro.launch",))
                if t.lo <= s.start < t.hi]
    waits = [s for s in t.program_spans(("repro.wait",))
             if t.lo <= s.start < t.hi]
    runs = t.modules[0] if t.modules else []
    if not launches or not runs:
        return None
    n = min(len(launches), len(waits), len(runs))
    early = np.array([runs[i].start - launches[i].start
                      for i in range(n)]) * 1e-3
    late = np.array([waits[i].end - runs[i].end for i in range(n)]) * 1e-3
    held = int(((early >= 0) & (late >= 0)).sum())
    return {"tiles": len(launches), "programs": len(runs), "paired": n,
            "held": held, "held_pct": 100.0 * held / n,
            # program start after launch start, wait end after program
            # end: quartiles in microseconds (negative: the clock check
            # fails by that much)
            "start_after_launch_us": np.percentile(early, [25, 50, 75])
            .round(1).tolist(),
            "wait_after_end_us": np.percentile(late, [25, 50, 75])
            .round(1).tolist()}


def breakdown(t: ProgramTrace, top: int = 10) -> dict:
    chips = len(t.busy)
    total: dict[str, float] = {}
    for ops in t.op_s:
        for n, s in ops.items():
            total[n] = total.get(n, 0.0) + s / chips
    return {
        "window_s": t.window_s,
        "idle_pct": float(np.mean([
            100.0 * (1.0 - (b[:, 1] - b[:, 0]).sum() * 1e-9 / t.window_s)
            for b in t.busy])),
        "host_stall_pct": host_stall_pct(t),
        "scan_fill_pct": scan_fill_pct(t),
        "dispatch": dispatch_counts(t),
        "clock_check": clock_check(t),
        "idle_s_by_span": idle_by_span(t),
        "device_ops": [[n, s] for n, s in
                       sorted(total.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[n, s] for n, s in idle_gaps(t)[:top]],
    }


def reduce_serialized(data: bytes, chips: int | None = None
                      ) -> ProgramTrace:
    """``reduce_planes`` over a serialized XSpace (an ``.xplane.pb``)."""
    from jax.profiler import ProfileData
    return reduce_planes(ProfileData.from_serialized_xspace(data).planes,
                         chips, op_scopes(data))


@functools.lru_cache(maxsize=4)
def _load(path: str, mtime: float, chips: int | None) -> ProgramTrace:
    return reduce_serialized(pathlib.Path(path).read_bytes(), chips)


def load(path: pathlib.Path, chips: int | None = None) -> ProgramTrace:
    path = pathlib.Path(path)
    if path.is_dir():
        path = tracing.latest_xplane(path)
    return _load(str(path), path.stat().st_mtime, chips)


def for_readings(ctx) -> ProgramTrace | None:
    """The traced window of a run, for a per-layer metric reader: the trace
    the harness wrote (``ctx.trace_path`` where the readings carry one)."""
    if ctx.trace is None:
        return None
    path = getattr(ctx, "trace_path", None)
    if path is None:
        from chipbench import harness
        path = harness.TRACE_DIR
    return load(path, ctx.chips)


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace", nargs="?",
                    default=str(pathlib.Path(__file__).resolve().parent
                                / ".trace"))
    ap.add_argument("--chips", type=int, default=None)
    ap.add_argument("--top", type=int, default=10)
    args = ap.parse_args(argv)
    print(json.dumps(breakdown(load(args.trace, args.chips), args.top)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
