#!/usr/bin/env python3
"""On-chip benchmark of the matcher, one cell per run.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Cells, configurations and metrics are named in ``BENCHMARK.json`` at the
root of the checkout; ``chipbench/registry.py`` says where each piece
lives.  The run needs the program (``src/repro``) and a TPU with as many
chips as the cell asks for: without either it exits non-zero and prints no
result.  Earlier stdout lines are diagnostics (``{"diag": ...}``); the
last is the result, and the last stderr lines are each compared number
beside its limit.
"""

import time

T_START = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from chipbench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        ap.error("--seed must be >= 0 and --seconds > 0")
    try:
        line = harness.run_cell(args.workload, args.seed, args.seconds,
                                bool(args.trace), t_start=T_START)
    except (harness.Refused, LookupError) as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    for name, c in line["checks"].items():
        print(f"check {name} = {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
