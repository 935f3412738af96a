"""The reduction from a profiler trace to device numbers.

Reads the ``.xplane.pb`` the JAX profiler writes with nothing but
``jax.profiler.ProfileData``:

  * device planes are ``/device:TPU:<n>``; an operation is an event on
    their ``XLA Ops`` line, named by its HLO name (a loop's ``while`` and
    the ops inside it are both events);
  * the window is the host span ``chipbench.window`` the harness records
    around the measured loop; device events are clipped to it;
  * busy time is the union of operation intervals; idle share is
    1 - busy / window, averaged over chips;
  * an operation's time is the sum of its events' durations inside the
    window, averaged over chips; a share of busy time (such as the
    all-gather share) is taken per chip, then averaged;
  * an idle gap is labelled by the harness span (``chipbench.*``) the host
    was inside for most of it.
"""

from __future__ import annotations

import bisect
import dataclasses
import pathlib
import re

import numpy as np

WINDOW_SPAN = "chipbench.window"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"


def latest_xplane(directory: pathlib.Path) -> pathlib.Path:
    found = sorted(pathlib.Path(directory).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return found[-1]


def union(intervals: np.ndarray) -> np.ndarray:
    """Merge [N, 2] (start, end) intervals into sorted disjoint ones."""
    if intervals.size == 0:
        return intervals.reshape(0, 2)
    iv = intervals[np.argsort(intervals[:, 0], kind="stable")]
    out = [list(iv[0])]
    for s, e in iv[1:].tolist():
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.asarray(out, dtype=np.float64)


def clip(intervals: np.ndarray, lo: float, hi: float) -> np.ndarray:
    iv = np.clip(intervals, lo, hi)
    return iv[iv[:, 1] > iv[:, 0]]


def gaps(busy: np.ndarray, lo: float, hi: float) -> np.ndarray:
    """Complement of disjoint sorted ``busy`` within [lo, hi]."""
    edges = np.concatenate([[lo], busy.reshape(-1), [hi]]).reshape(-1, 2)
    return edges[edges[:, 1] > edges[:, 0]]


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: list[float]              # per chip
    op_s: list[dict[str, float]]     # per chip: op name -> seconds
    idle_gaps: list[tuple[str, float]]  # longest first, all chips

    @property
    def chips(self) -> int:
        return len(self.busy_s)

    @property
    def busy_mean_s(self) -> float:
        return float(np.mean(self.busy_s))

    @property
    def busy_max_s(self) -> float:
        return float(np.max(self.busy_s))

    @property
    def idle_pct(self) -> float:
        return float(np.mean([100.0 * (1.0 - b / self.window_s)
                              for b in self.busy_s]))

    def share_pct(self, pattern: str) -> float | None:
        """Mean over chips of the share of busy time in operations whose
        name matches ``pattern``; None when no such operation ran."""
        rx = re.compile(pattern)
        per_chip = [sum(s for n, s in ops.items() if rx.search(n))
                    for ops in self.op_s]
        if not any(per_chip):
            return None
        return float(np.mean([100.0 * s / b if b else 0.0
                              for s, b in zip(per_chip, self.busy_s)]))

    def breakdown(self, top: int = 10) -> dict:
        total: dict[str, float] = {}
        for ops in self.op_s:
            for n, s in ops.items():
                total[n] = total.get(n, 0.0) + s / self.chips
        ops = sorted(total.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": [[n, s] for n, s in self.idle_gaps[:top]]}


def op_name(text: str) -> str:
    """The HLO name of a device event (``%fusion.44 = s32[...] ...`` gives
    ``fusion.44``)."""
    return text.split(" = ", 1)[0].lstrip("%")


def _events(line, name=lambda text: text):
    return [(name(e.name), float(e.start_ns),
             float(e.start_ns + e.duration_ns)) for e in line.events]


def summarize_planes(planes, chips: int | None = None) -> TraceSummary:
    """The reduction over any objects shaped like ProfileData's planes
    (``name``, ``lines`` of ``name``/``events`` with ``name``,
    ``start_ns``, ``duration_ns``), over the first ``chips`` devices (all
    when None): the chips the run used."""
    host_spans: list[tuple[str, float, float]] = []
    devices: list[tuple[int, list]] = []
    for plane in planes:
        m = DEVICE_PLANE.match(plane.name)
        if m and (chips is None or int(m.group(1)) < chips):
            ops = [ev for line in plane.lines if line.name == OPS_LINE
                   for ev in _events(line, op_name)]
            devices.append((int(m.group(1)), ops))
        elif plane.name == HOST_PLANE:
            host_spans += [ev for line in plane.lines
                           for ev in _events(line)
                           if ev[0].startswith("chipbench.")]
    windows = [(s, e) for n, s, e in host_spans if n == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"trace has no {WINDOW_SPAN!r} host span")
    if not devices:
        raise ValueError("trace has no /device:TPU:<n> plane")
    lo, hi = windows[0]
    inner = sorted(((n, s, e) for n, s, e in host_spans
                    if n != WINDOW_SPAN), key=lambda sp: sp[1])
    starts = [s for _, s, _ in inner]
    busy_s, op_s, idle = [], [], []
    for _, ops in sorted(devices):
        iv = clip(np.array([[s, e] for _, s, e in ops],
                           np.float64).reshape(-1, 2), lo, hi)
        busy = union(iv)
        busy_s.append(float((busy[:, 1] - busy[:, 0]).sum()) * 1e-9)
        per_op: dict[str, float] = {}
        for n, s, e in ops:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                per_op[n] = per_op.get(n, 0.0) + d * 1e-9
        op_s.append(per_op)
        for gs, ge in gaps(busy, lo, hi).tolist():
            idle.append((_host_label(inner, starts, gs, ge),
                         (ge - gs) * 1e-9))
    idle.sort(key=lambda g: -g[1])
    return TraceSummary((hi - lo) * 1e-9, busy_s, op_s, idle)


def _host_label(spans, starts: list[float], lo: float, hi: float) -> str:
    """The harness span overlapping [lo, hi] the most (the window itself
    when none does).  ``spans`` are sorted by start and do not nest."""
    best, label = 0.0, WINDOW_SPAN
    j = bisect.bisect_right(starts, hi) - 1
    while j >= 0:
        n, s, e = spans[j]
        if e <= lo:
            break
        d = min(e, hi) - max(s, lo)
        if d > best:
            best, label = d, n
        j -= 1
    return label


def summarize(path: pathlib.Path, chips: int | None = None
              ) -> TraceSummary:
    from jax.profiler import ProfileData
    return summarize_planes(ProfileData.from_file(str(path)).planes, chips)
