"""A whole run off the chip, at a size a test run can hold, of the
benchmark's cell and of test cells that drive the stream and sharded paths
on the repo's fixture patterns (``testdata/bench.json``): sound, it comes
out correct; with the timed path broken underneath, or
with the control in the program's place, it does not.

Faults planted (each cell gets those it can have): a step that returns
its state unchanged, half of the batch left out (its answers taken from
the rest), an answer altered where it is produced, and on the four-chip
cell the exchange between chips left out.
"""

import json
import os
import pathlib
import subprocess
import sys

import numpy as np
import pytest

from chipbench import control, harness, registry, tables

ROOT = pathlib.Path(__file__).resolve().parents[1]
TESTDATA = ROOT / "chipbench" / "testdata"
# the benchmark's cells and test cells on the repo's fixture patterns
BENCH = registry.load_benchmark(TESTDATA / "bench.json")
TINY = {
    "pcre14-bulk-logs": {"docs_per_call": 8, "max_bytes": 2048,
                         "pool_calls": 2},
    "prosite20-scan": {"docs_per_call": 8, "max_bytes": 400,
                       "pool_calls": 2},
    "pcre14-stream-ids": {"flows": 12, "rate_per_s": 150,
                          "warm_width": 4096},
}


@pytest.fixture(autouse=True)
def _off_chip(monkeypatch, tmp_path):
    # no persistent caches from tests: tables into tmp, compile cache off
    monkeypatch.setattr(tables, "CACHE", tmp_path / "tables")
    monkeypatch.setattr(harness, "use_compile_cache", lambda: None)
    monkeypatch.setattr(registry, "TRAFFIC_DIRS",
                        [*registry.TRAFFIC_DIRS, TESTDATA / "traffic"])


def run(cell, plant=lambda s: s, seconds=0.5, overrides=None):
    return harness.run_cell(cell, 2**31 + 99, seconds, False,
                            require_chip=False,
                            overrides=TINY[cell] if overrides is None
                            else overrides,
                            plant=plant, bench=BENCH,
                            diag=lambda *a, **k: None)


# -- bulk faults, planted in Matcher.membership_batch's answer ----------------

def _bulk(fault):
    def plant(system):
        m = system.matcher
        inner = m.membership_batch

        def membership_batch(docs):
            res = inner(docs)
            acc = res.accepted.copy()
            if fault == "state_unchanged":
                acc[:] = m.packed.accepting[m.packed.starts]
            elif fault == "half_batch":
                h = (len(docs) + 1) // 2
                acc = inner(docs[:h]).accepted
                acc = np.concatenate([acc, acc[:len(docs) - h]])
            elif fault == "answer_altered":
                acc[0, 0] = ~acc[0, 0]
            res.accepted = acc
            return res

        m.membership_batch = membership_batch
        return system
    return plant


# -- stream faults, planted in Matcher.advance_segments (the tick) -----------

def _stream(fault):
    def plant(system):
        m = system.sm.matcher
        inner = m.advance_segments

        def advance_segments(segments, entry_states):
            res = inner(segments, entry_states)
            entry = np.asarray(entry_states, np.int32)
            if fault == "state_unchanged":
                res.final_states = entry.copy()
            elif fault == "half_batch":
                h = (len(segments) + 1) // 2
                res.final_states[h:] = entry[h:]
            res.absorbed = m.dev.absorbing[res.final_states]
            return res

        m.advance_segments = advance_segments
        if fault == "answer_altered":
            answer = system.answer
            system.answer = lambda snap: ~answer(snap)
        return system
    return plant


@pytest.mark.parametrize("cell", sorted(TINY))
def test_sound_run_is_correct(cell):
    line = run(cell)
    assert line["correct"], line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert list(line)[-1] == "checks"
    assert set(line["metrics"]) >= {"setup_s"}


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "answer_altered"])
@pytest.mark.parametrize("cell", ["pcre14-bulk-logs", "prosite20-scan"])
def test_bulk_fault_is_caught(cell, fault):
    line = run(cell, _bulk(fault))
    assert not line["correct"]
    assert line["checks"]["verdict_mismatches"]["value"] > 0


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "answer_altered"])
def test_stream_fault_is_caught(fault):
    line = run("pcre14-stream-ids", _stream(fault), seconds=1.0)
    assert not line["correct"]
    assert line["checks"]["verdict_mismatches"]["value"] > 0


@pytest.mark.parametrize("cell", sorted(TINY))
def test_control_is_not_correct(cell):
    # the control is host code alone, so it runs at the cell's own size
    line = run(cell, control.plant_control(cell, BENCH), seconds=1.0,
               overrides={})
    assert not line["correct"]
    assert line["checks"]["verdict_mismatches"]["value"] > 0


_SHARDED = r"""
import json, sys
import jax, jax.numpy as jnp
sys.path.insert(0, sys.argv[1])
from chipbench import control, harness, registry
harness.use_compile_cache = lambda: None
testdata = registry.HERE / "testdata"
registry.TRAFFIC_DIRS.append(testdata / "traffic")
bench = registry.load_benchmark(testdata / "bench.json")
tiny = {"docs_per_call": 8, "min_bytes": 1024, "max_bytes": 4096,
        "pool_calls": 2}

def run(plant=lambda s: s):
    return harness.run_cell("pcre14-sharded-bulk", 7, 0.5, False,
                            require_chip=False, overrides=tiny, plant=plant,
                            bench=bench, diag=lambda *a, **k: None)

out = {"sound": run()["correct"]}
gather = jax.lax.all_gather

def no_exchange(x, axis_name, *, axis=0, tiled=False, **kw):
    # each chip keeps its own chunks and never sees the others'
    n = jax.lax.psum(1, axis_name)
    return jnp.concatenate([x] * n, axis=axis) if tiled else jnp.stack(
        [x] * n, axis=axis)

jax.lax.all_gather = no_exchange
out["no_exchange"] = run()["correct"]
jax.lax.all_gather = gather
out["control"] = run(control.plant_control("pcre14-sharded-bulk",
                                           bench))["correct"]
print(json.dumps(out))
"""


def test_sharded_cell_catches_a_missing_exchange(tmp_path):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    script = tmp_path / "sharded.py"
    script.write_text(_SHARDED)
    proc = subprocess.run([sys.executable, str(script), str(ROOT)],
                          env=env, capture_output=True, text=True,
                          timeout=600, cwd=tmp_path)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out == {"sound": True, "no_exchange": False, "control": False}
