"""Finds every piece of a cell by the name ``BENCHMARK.json`` gives it.

    configs/<config>.json     the deployment: rule set, the ``system`` that
                              runs it and that system's options, chips
    systems/<system>.py       a ``Cell`` that builds the program from the
                              config and drives the window over the inputs,
                              and the ``control`` put in its place
    traffic/<mix>.json        parameters, and the ``generator`` that reads
                              them
    generators/<gen>.py       ``make(mix, seed, seconds)``: the inputs
    metrics/<metric>.py       a reader ``read(ctx) -> float | None``

A later cell, mix, system, generator or per-layer metric is new files and
entries, never an edit here.  An unknown name is a ``LookupError``.
"""

from __future__ import annotations

import functools
import importlib.util
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
HERE = pathlib.Path(__file__).resolve().parent
TRAFFIC_DIRS = [HERE / "traffic"]


def load_benchmark(path: pathlib.Path = ROOT / "BENCHMARK.json") -> dict:
    with open(path) as f:
        return json.load(f)


def _named(entries: list[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise LookupError(f"no {what} named {name!r} in BENCHMARK.json "
                      f"(known: {sorted(e['name'] for e in entries)})")


def workload(bench: dict, name: str) -> dict:
    return _named(bench["workloads"], name, "workload")


def config(bench: dict, cell: dict) -> dict:
    """The cell's configuration file, as it is run."""
    entry = _named(bench["configs"], cell["config"], "config")
    with open(ROOT / entry["file"]) as f:
        return json.load(f)


def traffic(name: str) -> dict:
    for d in TRAFFIC_DIRS:
        path = d / f"{name}.json"
        if path.is_file():
            with open(path) as f:
                return json.load(f)
    raise LookupError(f"no traffic mix {name!r} (no {name}.json in "
                      f"{[str(d) for d in TRAFFIC_DIRS]})")


def applies(metric: dict, cell_name: str) -> bool:
    """A metric without ``workloads`` applies to every cell."""
    return cell_name in metric.get("workloads", [cell_name])


def end_to_end(bench: dict, cell_name: str) -> list[dict]:
    return [m for m in bench["end_to_end"] if applies(m, cell_name)]


def per_layer(bench: dict, cell_name: str) -> list[dict]:
    return [m for m in bench["per_layer"] if applies(m, cell_name)]


@functools.cache
def _module(kind: str, name: str):
    path = HERE / kind / f"{name}.py"
    if not path.is_file():
        raise LookupError(f"no {kind} entry {name!r} ({path} is missing)")
    mod_name = (f"chipbench_{kind}_"
                + name.replace(".", "_").replace("-", "_"))
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod  # dataclasses look their module up there
    spec.loader.exec_module(mod)
    return mod


def system(name: str):
    """``systems/<name>.py``: its ``Cell`` and ``control``."""
    return _module("systems", name)


def generator(name: str):
    """``make(mix, seed, seconds)`` of ``generators/<name>.py``."""
    return _module("generators", name).make


def metric_reader(name: str):
    """``read(ctx)`` of ``metrics/<name>.py``."""
    return _module("metrics", name).read
