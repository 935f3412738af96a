"""The main path compiled for a described TPU v5e, with no chip attached.

The TPU compiler installed with jax compiles for a chip that is described
(``jax.experimental.topologies``) and not attached.  These compiles catch
what interpret mode cannot: blocks not tiled to (8, 128), gathers Mosaic
cannot lower, and programs that do not fit the chip's 16 GB.  Nothing runs,
so they say nothing about results or times.

* The jitted spec and seq programs of the default ``local`` lowering, at the
  widths of the fixture rule sets packed as search DFAs: PCRE (194 states,
  lane width 15, 1 MiB documents) and PROSITE (72,531 states, lane width
  22,857, 2,048-residue sequences), at the default 64-row tile.
* The sharded spec program on a described 2x2 ("doc", "chunk") mesh.
* The fused Pallas kernels of the ``pallas`` backend, which Mosaic refuses
  today: each is a strict xfail carrying the compiler's message, so the
  change that makes one legal has to flip its test.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and under several pytest workers the
one given this file does.  Keep these tests in this one file.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core import Matcher, PatternSet
from repro.core.engine.plan import ENTRY_STARTS, ENTRY_STATES, BucketPlan
from repro.core.engine.sharded import ShardedExecutor
from repro.data import load_pattern_fixtures

V5E_HBM_BYTES = 16 * 10**9   # one v5e chip (Google Cloud, "TPU v5e")
TILE = 64                    # Matcher's default batch_tile
NUM_CHUNKS = 8               # Matcher's default num_chunks


@pytest.fixture(scope="module")
def topo():
    """A described v5e:2x2 slice, with the persistent compilation cache off
    (a described-device compile is written to it but cannot be read back)
    and the TPU compiler's logs off."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    mp = pytest.MonkeyPatch()
    mp.setenv("TPU_LOG_DIR", "disabled")
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe skips
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)
        mp.undo()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def packs():
    """The fixture rule sets as one-block search pattern sets."""
    fixtures = load_pattern_fixtures()
    return {kind: PatternSet({e["name"]: e["pattern"] for e in fixtures
                              if e["kind"] == kind},
                             k_blk=1 << 30, search=True)
            for kind in ("pcre", "prosite")}


def _device_bytes(compiled) -> int:
    """Bytes the compiled program holds on one device: operands, results,
    temporaries and its code (the tables are program constants)."""
    ma = compiled.memory_analysis()
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes + ma.generated_code_size_in_bytes
            - ma.alias_size_in_bytes)


# kind, plan, entry, bucket width (bytes per row) — the widest bucket each
# phase of chip_smoke.py dispatches
LOCAL_PROGRAMS = [
    pytest.param("pcre", "spec", ENTRY_STARTS, NUM_CHUNKS * (128 << 10),
                 id="pcre-spec-1MiB"),
    pytest.param("pcre", "seq", ENTRY_STARTS, 32, id="pcre-seq"),
    pytest.param("pcre", "seq", ENTRY_STATES, 2048, id="pcre-stream-seq"),
    pytest.param("prosite", "spec", ENTRY_STARTS, NUM_CHUNKS * 256,
                 id="prosite-spec-2048"),
    pytest.param("prosite", "seq", ENTRY_STARTS, 2048, id="prosite-seq-2048"),
]


@pytest.mark.parametrize("kind,plan_kind,entry,width", LOCAL_PROGRAMS)
def test_local_program_compiles_and_fits(packs, one_chip, kind, plan_kind,
                                         entry, width):
    m = Matcher(packs[kind], num_chunks=NUM_CHUNKS if plan_kind == "spec"
                else 1)
    ex, dev = m.executor, m.dev
    chunk_len = width // NUM_CHUNKS if plan_kind == "spec" else 0
    plan = m.planner.lane_plan(
        BucketPlan(plan_kind, width, chunk_len, np.arange(1)), entry=entry,
        spec_r=dev.spec_r if plan_kind == "spec" else 1)
    body = ex._spec_body if plan_kind == "spec" else ex._seq_body
    args = [jax.ShapeDtypeStruct((TILE, width), jnp.uint8, sharding=one_chip),
            jax.ShapeDtypeStruct((TILE,), jnp.int32, sharding=one_chip)]
    if entry == ENTRY_STATES:
        args.append(jax.ShapeDtypeStruct((TILE, m.n_patterns), jnp.int32,
                                         sharding=one_chip))
    compiled = jax.jit(lambda *a: body(plan, *a)).lower(*args).compile()
    assert "gather" in compiled.as_text()
    assert _device_bytes(compiled) < V5E_HBM_BYTES


def test_sharded_spec_compiles_on_2x2_mesh(packs, topo):
    from repro.core.engine.plan import DeviceTables, Planner

    mesh = Mesh(np.asarray(topo.devices).reshape(2, 2), ("doc", "chunk"))
    dev = DeviceTables.build(packs["pcre"].blocks[0])
    planner = Planner(num_chunks=NUM_CHUNKS, devices=2, doc_shards=2)
    ex = ShardedExecutor(dev, num_chunks=planner.num_chunks, mesh=mesh)
    chunk_len = 4096
    plan = planner.lane_plan(
        BucketPlan("spec", NUM_CHUNKS * chunk_len, chunk_len, np.arange(1)),
        entry=ENTRY_STARTS, spec_r=dev.spec_r)
    program = ex.lower(plan, layout=planner.layout_for(chunk_len), batch=TILE)
    rep = NamedSharding(mesh, P())
    compiled = program.lower(
        jax.ShapeDtypeStruct((TILE, NUM_CHUNKS * chunk_len), jnp.uint8,
                             sharding=rep),
        jax.ShapeDtypeStruct((TILE,), jnp.int32, sharding=rep)).compile()
    assert "all-gather" in compiled.as_text()  # the one chunk-axis exchange
    assert _device_bytes(compiled) < V5E_HBM_BYTES


# The pallas backend's fused kernels, at the PCRE pack's widths.  Each
# refusal below is the first one Mosaic gives; behind it wait the 1-D
# jnp.take from the flat table ("Only 2D gather is supported"; 2-D gathers
# stay inside one 8x128 source tile) and the (1, 1) scalar stores to VMEM.
UNTILED = ("The Pallas TPU lowering currently requires that the last two "
           "dimensions of your block shape are divisible by 8 and 128 "
           "respectively, or be equal to the respective dimensions of the "
           "overall array")
Q, N_CLS, N_KEYS, K, S, C, L = 194, 38, 1370, 14, 15, NUM_CHUNKS, 4096


def _merge_args(one_chip):
    def spec(shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    return (spec((Q, N_CLS)), spec((TILE, C, L)), spec((TILE, C, K * S)),
            spec((TILE, C)), spec((N_KEYS, Q)), spec((K,)), spec((Q,)))


def _compose_args(one_chip, n=8):
    def spec(shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    return (spec((TILE, n, K, S)), spec((TILE, n)), spec((N_KEYS, Q)),
            spec((K,)))


def _kernel_program(name):
    from repro.kernels.dfa_match import (spec_match_merge_lanes_pallas,
                                         spec_match_merge_pallas)
    from repro.kernels.lvec_compose import (spec_compose_lanes_pallas,
                                            spec_compose_lanes_tree_pallas)

    merge = dict(pad_cls=N_CLS - 1, l_blk=512, interpret=False)
    programs = {
        "spec_match_merge_pallas": (
            lambda *a: spec_match_merge_pallas(*a, **merge), _merge_args),
        "spec_match_merge_lanes_pallas": (
            lambda *a: spec_match_merge_lanes_pallas(*a, **merge),
            _merge_args),
        "spec_compose_lanes_pallas": (
            lambda *a: spec_compose_lanes_pallas(
                *a, pad_key=N_KEYS, n_blk=8, interpret=False), _compose_args),
        "spec_compose_lanes_tree_pallas": (
            lambda *a: spec_compose_lanes_tree_pallas(
                *a, pad_key=N_KEYS, interpret=False), _compose_args),
    }
    return programs[name]


REFUSED_KERNELS = [
    pytest.param(name, marks=pytest.mark.xfail(
        strict=True, raises=ValueError,
        reason=f"{UNTILED}: {operand} block (1, 8) of a (64, 8) array"),
        id=name)
    for name, operand in [
        ("spec_match_merge_pallas", "lookahead"),
        ("spec_match_merge_lanes_pallas", "lookahead"),
        ("spec_compose_lanes_pallas", "keys"),
        ("spec_compose_lanes_tree_pallas", "keys"),
    ]]


@pytest.mark.parametrize("name", REFUSED_KERNELS)
def test_pallas_kernel_compiles(one_chip, name):
    program, make_args = _kernel_program(name)
    try:
        jax.jit(program).lower(*make_args(one_chip)).compile()
    except ValueError as e:
        # a different refusal fails here: bring the reason up to date
        assert UNTILED in str(e), str(e)
        raise
