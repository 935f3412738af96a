#!/usr/bin/env python3
"""Sweep of a stream deployment's arrival rate, to find its knee before a
cell fixes its rate.

    python3 chipbench/knee.py --config chipbench/configs/<config>.json \
        --traffic <mix> --seeds 1,2,3 --seconds 51 --rates 700,800,900

The config must name the ``stream`` system.  One set-up, then one window
per seed and rate on fresh flows.  For each it prints the latency
quantiles, and for the window's first and last fifth the median latency
and the 99th percentile of how late the generator fed: where the backlog
grows, the last fifth waits longer or the generator falls behind.  The
knee is the highest rate at which neither grows on any seed; the cell's
rate (``rate_per_s`` in its traffic file) is 0.8 x the knee.  Like a run
of the benchmark it needs the chips the config asks for.  The benchmark's
runs never call this.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import pathlib
import sys

import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from chipbench import harness, registry  # noqa: E402


def fifths(run, seconds: float) -> dict:
    lat = run.latencies_ms()
    late = (run.win.fed - run.win.due) * 1e3
    due = run.win.due
    out = {}
    for part, sel in (("first", due < seconds / 5),
                      ("last", due >= seconds * 4 / 5)):
        out[f"{part}_fifth_p50_ms"] = float(np.median(lat[sel]))
        out[f"{part}_fifth_lateness_p99_ms"] = float(
            np.percentile(late[sel], 99))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    with open(args.config) as f:
        cfg = json.load(f)
    mix = registry.traffic(args.traffic)
    harness.require_program()
    harness.require_chips(int(cfg["chips"]))
    harness.use_compile_cache()
    make = registry.generator(mix["generator"])
    seeds = [int(s) for s in args.seeds.split(",")]
    run = registry.system(cfg["system"]).Cell(
        cfg, mix, make(mix, seeds[0], args.seconds), lambda s: s)
    run.warm()
    for seed in seeds:
        for rate in [float(r) for r in args.rates.split(",")]:
            run.load(make({**mix, "rate_per_s": rate}, seed, args.seconds))
            run.window(args.seconds, contextlib.nullcontext)
            lat = run.latencies_ms()
            c = run.counters()
            print(json.dumps({
                "seed": seed, "rate_per_s": rate, "arrivals": run.arr.n,
                "p50_ms": float(np.percentile(lat, 50)),
                "p95_ms": float(np.percentile(lat, 95)),
                "p99_ms": float(np.percentile(lat, 99)),
                **fifths(run, args.seconds),
                "ticks": c["ticks"], "occupancy": c["occupancy"],
                "window_s": run.win.window_s}), flush=True)
            for s in run.sessions:
                run.system.close(s)
    return 0


if __name__ == "__main__":
    sys.exit(main())
