#!/usr/bin/env python3
"""The control: the reference in the program's place, with the guarantee
of the paper's method broken, driven through a whole run of a cell.

    python3 chipbench/control.py --workload <cell> --seeds 1,2,3 \
        --seconds <s>

Each system names its control (``control`` in ``systems/<system>.py``);
for the bulk system each chunk of a document is searched on its own from
the start and the verdicts are or-ed, or on a mesh the exchange between
chips is left out.  Every run must come out ``correct: false``; its
compared numbers are the upper readings the limits are set below.  Like a
run of the benchmark it needs the chips the cell asks for.  The
benchmark's own runs never run this.  Exits 0 when every seed's run came
out not correct.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from chipbench import harness, registry  # noqa: E402


def plant_control(cell_name: str, bench: dict | None = None):
    bench = registry.load_benchmark() if bench is None else bench
    cfg = registry.config(bench, registry.workload(bench, cell_name))
    return registry.system(cfg["system"]).control(cfg)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    ok = True
    for seed in [int(s) for s in args.seeds.split(",")]:
        line = harness.run_cell(args.workload, seed, args.seconds, False,
                                plant=plant_control(args.workload),
                                diag=lambda *a, **k: None)
        print(json.dumps({"control": args.workload, "seed": seed,
                          "correct": line["correct"],
                          "attempted": line["attempted"],
                          "checks": line["checks"]}), flush=True)
        ok &= not line["correct"]
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
