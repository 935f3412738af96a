"""Off a TPU, or without the program beside it, the command exits non-zero
and prints no result line."""

import os
import pathlib
import shutil
import subprocess
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
ARGS = ["--workload", "prosite20-scan", "--seed", "1", "--seconds", "1",
        "--trace", "0"]


def _run(cwd):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    return subprocess.run([sys.executable, "chipbench/run.py", *ARGS],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=300)


def test_fails_without_a_tpu():
    proc = _run(ROOT)
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout
    assert "needs a TPU" in proc.stderr


def test_fails_with_only_the_benchmark_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns(".*", "__pycache__"))
    proc = _run(tmp_path)
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout
    assert "program is not in this checkout" in proc.stderr
