"""Benchmark harness: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (the harness contract).
``derived`` is the figure's model quantity (speedup, gamma, reduction rate,
CV, ...); wall-clock is single-host CPU and serves as a relative measure.

Run:  PYTHONPATH=src python -m benchmarks.run [--quick]
"""

from __future__ import annotations

import argparse
import inspect
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="skip the 10M-symbol scaling points")
    ap.add_argument("--only", default=None,
                    help="comma-separated benchmark names")
    ap.add_argument("--smoke", action="store_true",
                    help="CI sizes: tiny inputs, regression guards still "
                         "enforced (benchmarks that accept smoke=)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write the emitted rows as a BENCH_*.json "
                         "artifact (schema: benchmarks/common.write_json)")
    args = ap.parse_args()

    from repro.launch.compile_cache import use_compile_cache

    from . import paper_figs as pf

    use_compile_cache()

    benches = [
        ("speedup_vs_states", pf.bench_speedup_vs_states),   # Fig 10 + 15
        ("holub_stekr", pf.bench_holub_stekr),               # Fig 11
        ("scanprosite", pf.bench_scanprosite),               # Fig 12
        ("vectorization", pf.bench_vectorization),           # Fig 13
        ("imax_reduction", pf.bench_imax_reduction),         # Fig 16 / Table 4
        ("lookahead_overhead", pf.bench_lookahead_overhead), # Fig 17
        ("input_scaling", pf.bench_input_scaling),           # Fig 18/19
        ("load_balance", pf.bench_load_balance),             # Table 3
        ("merge_strategies", pf.bench_merge_strategies),     # Sec 5.2
        ("batch_throughput", pf.bench_batch_throughput),     # batched pipeline
        ("capacity_balance", pf.bench_capacity_balance),     # sharded runtime
        ("stream_throughput", pf.bench_stream_throughput),   # streaming runtime
        ("ooo_throughput", pf.bench_ooo_throughput),         # out-of-order tier
        ("pattern_scale", pf.bench_pattern_scale),           # pattern-set scale tier
    ]
    if args.only:
        names = set(args.only.split(","))
        benches = [(n, f) for n, f in benches if n in names]

    print("name,us_per_call,derived")
    t0 = time.time()
    for name, fn in benches:
        sys.stderr.write(f"[bench] {name}\n")
        if args.quick and name == "input_scaling":
            continue
        kwargs = {}
        if args.smoke and "smoke" in inspect.signature(fn).parameters:
            kwargs["smoke"] = True
        fn(**kwargs)
    total = time.time() - t0
    sys.stderr.write(f"[bench] total {total:.1f}s\n")
    if args.json:
        from . import common
        common.write_json(args.json, meta={
            "argv": sys.argv[1:], "total_s": round(total, 2),
            "benchmarks": [n for n, _ in benches]})
        sys.stderr.write(f"[bench] wrote {args.json}\n")


if __name__ == "__main__":
    main()
