"""Tests for the dry-run/roofline infrastructure (census math, mesh, specs)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.launch.hlo_census import hlo_census
from repro.launch.roofline import wire_bytes, tokens_of


def test_census_counts_while_trip_multipliers():
    """A jitted double-scan program must census flops = trips * body flops."""
    n_outer, n_inner, d = 3, 4, 32

    def prog(w, x):
        def outer(x, _):
            def inner(x, _):
                return jnp.tanh(x @ w), None
            x, _ = jax.lax.scan(inner, x, None, length=n_inner)
            return x, None
        x, _ = jax.lax.scan(outer, x, None, length=n_outer)
        return x

    w = jnp.eye(d, dtype=jnp.float32)
    x = jnp.ones((8, d), jnp.float32)
    compiled = jax.jit(prog).lower(w, x).compile()
    census = hlo_census(compiled.as_text(), 1)
    expect = 2 * 8 * d * d * n_outer * n_inner
    assert census["dot_flops"] == pytest.approx(expect, rel=0.01), census
    assert census["max_multiplier"] == n_outer * n_inner


def test_census_collectives_on_forced_devices():
    """Collective census sees the psum inserted by a sharded reduction."""
    import subprocess, sys, os, textwrap
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=os.path.join(repo, "src"))
    body = textwrap.dedent("""
        import jax, jax.numpy as jnp
        from jax import set_mesh
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.launch.hlo_census import hlo_census
        mesh = jax.make_mesh((8,), ("data",))
        def f(x):
            return x.sum()
        sh = NamedSharding(mesh, P("data"))
        x = jax.ShapeDtypeStruct((64, 4), jnp.float32)
        with set_mesh(mesh):
            compiled = jax.jit(f, in_shardings=sh).lower(x).compile()
        c = hlo_census(compiled.as_text(), 8)
        total = sum(v["count"] for v in c["collectives"].values())
        assert total >= 1, c["collectives"]
        print("census collectives OK")
    """)
    proc = subprocess.run([sys.executable, "-c", body], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr


def test_wire_bytes_ring_factors():
    coll = {
        "all-gather": {"bytes": 100.0, "group_sizes": [4]},
        "all-reduce": {"bytes": 100.0, "group_sizes": [4]},
        "reduce-scatter": {"bytes": 100.0, "group_sizes": [4]},
        "all-to-all": {"bytes": 0.0, "group_sizes": []},
        "collective-permute": {"bytes": 100.0, "group_sizes": [2]},
    }
    got = wire_bytes(coll)
    expect = 100 * 3 / 4 + 2 * 100 * 3 / 4 + 100 * 3 + 100
    assert got == pytest.approx(expect)


def test_tokens_of_shapes():
    assert tokens_of("train_4k") == (4096 * 256, 6.0)
    assert tokens_of("prefill_32k") == (32768 * 32, 2.0)
    assert tokens_of("decode_32k") == (128, 2.0)


def test_make_local_mesh_and_dp_axes():
    from repro.launch.mesh import dp_axes, make_local_mesh, mesh_info
    mesh = make_local_mesh(1, 1)
    assert dp_axes(mesh) == ("data",)
    info = mesh_info(mesh)
    assert info["n_devices"] == 1


def test_param_specs_divisibility_fallback():
    """Sharding rules must degrade to replication for non-dividing dims."""
    from jax.sharding import PartitionSpec as P
    from repro.distributed import sharding as shr
    mesh = jax.make_mesh((1, 1), ("data", "model"))

    params = {"layers": {"attn": {
        "wq": jnp.zeros((64, 10, 16)),   # 10 heads never divide
        "wk": jnp.zeros((64, 2, 16)),
        "wo": jnp.zeros((10, 16, 64)),
    }}}
    specs = shr.param_specs(params, mesh)
    # mesh axes of size 1 -> everything replicated (still valid specs)
    for leaf in jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P)):
        assert isinstance(leaf, P)


def test_roofline_count_params_moe_active():
    from repro.launch.roofline import count_params
    total, active = count_params("phi3.5-moe-42b-a6.6b")
    # 42B-class total, ~6.6B-class active + embeddings
    assert 38e9 < total < 46e9, total
    assert active < total / 3, (total, active)


def test_roofline_count_params_dense():
    from repro.launch.roofline import count_params
    total, active = count_params("llama3-8b")
    assert 7e9 < total < 9.5e9, total
    assert total == active


def _run_cache_probe(env_dir, compile_once: bool) -> str:
    """In a fresh process: call use_compile_cache() and, optionally, compile
    one program with the cache's size/time thresholds at zero."""
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    code = ("import jax, jax.numpy as jnp\n"
            "from repro.launch.compile_cache import use_compile_cache\n"
            "print(use_compile_cache())\n"
            "print(jax.config.jax_compilation_cache_dir)\n")
    if compile_once:
        code += ("jax.config.update("
                 "'jax_persistent_cache_min_compile_time_secs', 0)\n"
                 "jax.config.update("
                 "'jax_persistent_cache_min_entry_size_bytes', 0)\n"
                 "jax.jit(lambda x: x * 3 + 1)(jnp.arange(8))"
                 ".block_until_ready()\n")
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=os.path.join(repo, "src"))
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=repo,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout


def test_compile_cache_lands_in_env_dir(tmp_path):
    lines = _run_cache_probe(tmp_path, compile_once=True).split()
    assert lines == [str(tmp_path), str(tmp_path)]
    assert any(tmp_path.iterdir())  # the compiled program was cached there


def test_compile_cache_defaults_to_repo_dir():
    import pathlib

    repo = pathlib.Path(__file__).resolve().parents[1]
    lines = _run_cache_probe(None, compile_once=False).split()
    assert lines == [str(repo / ".jax_cache")] * 2
