"""One run of one cell: set-up, the measured window, the check, the line.

The order is fixed by what each step may see:

  1. the program must be in the checkout and the chips the cell asks for
     must be TPUs (``Refused`` otherwise: no result line);
  2. set-up: the seed's inputs (the mix's generator), the program and its
     tables (the config's system), and one pass over every shape the
     window uses (compiles land here);
  3. the window: ``--seconds`` of the system's loop, or with ``--trace 1`` at
     most the mix's ``trace_seconds``, under the profiler;
  4. peak device memory, then the check of every verdict the window
     returned against ``reference.py``, then the metrics.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import json
import os
import pathlib
import shutil
import sys
import time

from . import registry, tracing

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
JAX_CACHE = HERE / ".jax_cache"   # fixed: the path is part of the cache key
TRACE_DIR = HERE / ".trace"


class Refused(Exception):
    """The run cannot measure here; it exits non-zero with no result."""


# -- environment ---------------------------------------------------------------

def require_program() -> None:
    if not (ROOT / "src" / "repro").is_dir():
        raise Refused(f"the program is not in this checkout ({ROOT / 'src'} "
                      "has no repro package)")
    if str(ROOT / "src") not in sys.path:
        sys.path.insert(0, str(ROOT / "src"))


def require_chips(n: int) -> list:
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise Refused(f"needs a TPU; JAX found {devices[0].platform!r} "
                      "devices")
    if len(devices) < n:
        raise Refused(f"needs {n} TPU chips, found {len(devices)}")
    return devices


def use_compile_cache() -> None:
    """JAX's persistent cache, where ``$JAX_COMPILATION_CACHE_DIR`` says or
    else at a fixed path in the checkout; every program is cached."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(JAX_CACHE))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/backend_compile_duration")


@functools.cache
def compile_counter() -> dict:
    """Process-wide count and seconds of traces and backend compiles."""
    import jax
    count = {"n": 0, "s": 0.0}

    def on_event(event: str, duration: float, **_) -> None:
        if event in _COMPILE_EVENTS:
            count["n"] += 1
            count["s"] += duration

    jax.monitoring.register_event_duration_secs_listener(on_event)
    return count


def peak_bytes(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


@contextlib.contextmanager
def profiled(active: bool):
    """The profiler around the window, writing into ``.trace``; yields a
    span factory (host spans land in the same trace)."""
    if not active:
        yield contextlib.nullcontext, None
        return
    import jax
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    jax.profiler.start_trace(str(TRACE_DIR))
    holder = {}
    try:
        with jax.profiler.TraceAnnotation(tracing.WINDOW_SPAN):
            yield jax.profiler.TraceAnnotation, holder
    finally:
        jax.profiler.stop_trace()
    holder["path"] = tracing.latest_xplane(TRACE_DIR)


LIMITS = {"verdict_mismatches": 0, "unanswered": 0}


@dataclasses.dataclass
class Readings:
    """What a per-layer metric reader may read."""

    cell: dict
    config: dict
    counters: dict
    trace: tracing.TraceSummary | None
    device_kind: str
    chips: int


# -- one run ------------------------------------------------------------------

def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             require_chip: bool = True, overrides: dict | None = None,
             plant=lambda system: system, t_start: float | None = None,
             bench: dict | None = None, diag=print) -> dict:
    """Run one cell and return its result line (a dict)."""
    t_start = time.perf_counter() if t_start is None else t_start
    bench = registry.load_benchmark() if bench is None else bench
    cell = registry.workload(bench, name)
    cfg = registry.config(bench, cell)
    mix = {**registry.traffic(cell["traffic"]), **(overrides or {})}
    require_program()
    import jax
    devices = (require_chips(int(cell["chips"])) if require_chip
               else jax.devices())
    use_compile_cache()
    compiles = compile_counter()

    seconds = min(seconds, float(mix["trace_seconds"])) if trace else seconds
    system = registry.system(cfg["system"])
    make = registry.generator(mix["generator"])
    t = time.perf_counter()
    inputs = make(mix, seed, seconds)
    inputs_s = time.perf_counter() - t
    t = time.perf_counter()
    run = system.Cell(cfg, mix, inputs, plant)
    build_s = time.perf_counter() - t
    t = time.perf_counter()
    run.warm()
    warm_s = time.perf_counter() - t

    c0 = dict(compiles)
    setup_s = time.perf_counter() - t_start
    with profiled(trace) as (span, traced):
        run.window(seconds, span)
    in_window = {"compiles_in_window": compiles["n"] - c0["n"],
                 "compile_s_in_window": compiles["s"] - c0["s"]}
    mem = peak_bytes(devices)
    counters = run.counters()
    diag(json.dumps({"diag": {
        "cell": name, "seed": seed, "window_s": run.win.window_s,
        "setup_s": setup_s, "inputs_s": inputs_s, "build_s": build_s,
        "warm_s": warm_s, **in_window, **counters,
        "memory_peak_bytes": mem}}), flush=True)

    t = time.perf_counter()
    checked = run.check()
    diag(json.dumps({"diag": {"check_s": time.perf_counter() - t}}),
         flush=True)

    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": mem}
    metrics: dict = {}
    breakdown = None
    if trace:
        t = time.perf_counter()
        summary = tracing.summarize(traced["path"], int(cell["chips"]))
        diag(json.dumps({"diag": {"trace_parse_s": time.perf_counter() - t,
                                  "trace_bytes": traced["path"].stat()
                                  .st_size}}), flush=True)
        device["busy_s"] = summary.busy_mean_s
        device["window_s"] = summary.window_s
        breakdown = summary.breakdown()
        readings = Readings(cell, cfg, counters, summary, dev.device_kind,
                            int(cell["chips"]))
        for m in registry.per_layer(bench, name):
            value = registry.metric_reader(m["name"])(readings)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = {"setup_s": setup_s, **run.end_to_end()}
        for m in registry.end_to_end(bench, name):
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}

    checks = {k: {"value": checked[k], "limit": lim}
              for k, lim in LIMITS.items()}
    line = {"correct": all(checked[k] <= lim for k, lim in LIMITS.items()),
            "attempted": checked["attempted"], "failed": checked["failed"],
            "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = checks
    return line
