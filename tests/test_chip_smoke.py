"""chip_smoke.py on the CPU: its device check, and every phase at tiny sizes.

The script's sizes are for one TPU v5e; here each phase runs on the CPU
backend (8 host devices from conftest.py, so the sharded phase gets its
(1, 4) and (2, 2) meshes) and still checks every verdict against ``re``.
"""

import importlib.util
import os
import pathlib
import subprocess
import sys

import numpy as np
import pytest

from repro.data import load_pattern_fixtures

ROOT = pathlib.Path(__file__).resolve().parents[1]
_spec = importlib.util.spec_from_file_location("chip_smoke",
                                               ROOT / "chip_smoke.py")
cs = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(cs)

FIXTURES = load_pattern_fixtures()


def test_device_check_refuses_cpu():
    with pytest.raises(SystemExit) as exc:
        cs.require_tpu()
    assert "needs a TPU" in str(exc.value.code)


def test_script_fails_without_tpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                          capture_output=True, text=True, env=env,
                          cwd=ROOT, timeout=300)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


def test_check_reports_mismatch():
    got = np.zeros((2, 3), bool)
    cs.check("same", got, got.copy())
    want = got.copy()
    want[1, 2] = True
    with pytest.raises(AssertionError, match="first mismatches"):
        cs.check("differs", got, want)


def test_phase_pcre_tiny():
    line = cs.phase_pcre(FIXTURES, np.random.default_rng(0),
                         total_bytes=64 << 10, min_len=2 << 10,
                         max_len=16 << 10, n_short=32)
    assert line["lowerings"] == ["seq-jnp", "spec-jnp"]
    assert line["bytes"] >= 64 << 10 and line["lane_width"] == 15
    assert 0.0 < line["hit_rate"] < 1.0


def test_phase_prosite_tiny():
    line = cs.phase_prosite(FIXTURES, np.random.default_rng(0), n_seqs=24,
                            n_spec=2, spec_batch_tile=2)
    assert line["states"] == 72531 and line["lane_width"] == 22857
    assert line["lowerings"] == ["seq-jnp", "spec-jnp"]


def test_phase_stream_tiny():
    line = cs.phase_stream(FIXTURES, np.random.default_rng(0), n_streams=16,
                           n_segments=4)
    assert line["ticks"] == 4 and line["segments"] == 64


def test_phase_sharded_tiny():
    lines = cs.phase_sharded(FIXTURES, np.random.default_rng(0),
                             total_bytes=32 << 10, min_len=2 << 10,
                             max_len=8 << 10, n_short=16, n_streams=8,
                             n_segments=4)
    assert [ln["phase"] for ln in lines] == [
        "sharded/local", "sharded/1x4", "sharded/2x2"]
    assert all("spec-sharded" in ln["lowerings"] for ln in lines[1:])
