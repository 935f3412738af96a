"""Open-loop arrivals: Poisson times at ``rate_per_s`` over the window on
``flows`` flows picked uniformly; segments of ``seg_min`` - ``seg_max``
bytes, uniform, cut in order from one log-like stream per flow.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from chipbench import gen


@dataclasses.dataclass
class Arrivals:
    """An open-loop schedule: arrival ``i`` is due at ``due[i]`` seconds
    after the window opens and carries ``flow[i]``'s next segment, bytes
    ``[start[i], end[i])`` of that flow's stream ``flows[flow[i]]``."""

    due: np.ndarray     # [N] float64 seconds, ascending
    flow: np.ndarray    # [N] int64
    start: np.ndarray   # [N] int64
    end: np.ndarray     # [N] int64
    flows: list[bytes]  # per-flow stream bytes

    def segment(self, i: int) -> bytes:
        return self.flows[self.flow[i]][self.start[i]:self.end[i]]

    @property
    def n(self) -> int:
        return int(self.due.size)


def make(mix: dict, seed: int, seconds: float) -> Arrivals:
    rng = gen.rng_for(seed, 2)
    n = int(rng.poisson(float(mix["rate_per_s"]) * seconds))
    due = np.sort(rng.uniform(0.0, seconds, size=n))
    n_flows = int(mix["flows"])
    flow = rng.integers(0, n_flows, size=n)
    lens = rng.integers(int(mix["seg_min"]), int(mix["seg_max"]) + 1, size=n)
    start = np.zeros(n, np.int64)
    totals = np.zeros(n_flows, np.int64)
    for i, (f, ln) in enumerate(zip(flow.tolist(), lens.tolist())):
        start[i] = totals[f]
        totals[f] += ln
    pools = gen.line_pools(gen.rng_for(seed, 0))
    rate_special = float(mix["special_rate"])
    flows = [gen.log_doc(gen.rng_for(seed, 3, f), pools, int(t), rate_special)
             for f, t in enumerate(totals.tolist())]
    return Arrivals(due=due, flow=flow, start=start, end=start + lens,
                    flows=flows)
