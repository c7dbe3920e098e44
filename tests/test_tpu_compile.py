"""Compile every Pallas kernel of the serving path for a described TPU v5e.

Nothing runs: each test lowers and compiles one kernel with interpret=False
at the widths a SIFT1B-shaped deployment uses (d = 128, R = 64, m = 32,
batches up to 1024, worklists up to 256) against a `v5e:2x2` topology
description, so a kernel the chip's compiler refuses fails here, at no chip
time. The reference mode's XLA ADC is compiled the same way, to check the
form the chip gets, and so is the in-memory executor's whole pipeline at
the DEEP1B cell's shapes, against index shapes alone. The topology is
described inside a fixture (never at import): only one process may load
the TPU compiler library, and every test worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

B, R, T, M, D = 1024, 64, 256, 32, 128


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler library here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_cache():
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these tests.
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def compile_tpu(one_chip, no_cache):
    def compile_(fn, *shapes):
        args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
        text = jax.jit(fn).lower(*args).compile().as_text()
        assert "tpu_custom_call" in text
        return text

    return compile_


def _step_shapes(n_lines):
    f32, i32 = jnp.float32, jnp.int32
    return [
        ((B, M, 256), f32), ((n_lines, 128), i32), ((B, R), i32),
        ((B, R), jnp.bool_), ((B, T), f32), ((B, T), i32), ((B, T), jnp.bool_),
        ((B,), jnp.bool_),
    ]


@pytest.mark.parametrize("resident,n", [(True, 1 << 19), (False, 10_000_000)])
@pytest.mark.parametrize("eager", [True, False])
def test_fused_step_compiles(compile_tpu, resident, n, eager):
    from repro.kernels.search_step.search_step import fused_step_pallas, lines_bytes

    fn = lambda *a: fused_step_pallas(
        *a, eager=eager, resident=resident, interpret=False
    )
    compile_tpu(fn, *_step_shapes(lines_bytes(n, M) // 512))


@pytest.mark.parametrize("eager", [True, False])
def test_fused_traverse_compiles(compile_tpu, eager):
    from repro.kernels.search_step.search_step import fused_traverse_pallas

    f32, i32 = jnp.float32, jnp.int32
    fn = lambda *a: fused_traverse_pallas(*a, eager=eager, interpret=False)
    compile_tpu(fn, ((B, R), f32), ((B, R), i32), ((B, T), f32), ((B, T), i32),
                ((B, T), jnp.bool_), ((B,), jnp.bool_))


@pytest.mark.parametrize("resident,n_loc", [(True, 1 << 16), (False, 2_500_000)])
def test_local_adc_compiles(compile_tpu, resident, n_loc):
    from repro.kernels.search_step.search_step import lines_bytes, local_adc_pallas

    fn = lambda *a: local_adc_pallas(*a, resident=resident, interpret=False)
    compile_tpu(fn, ((B, M, 256), jnp.float32),
                ((lines_bytes(n_loc, M) // 512, 128), jnp.int32),
                ((B, R), jnp.int32), ((B, R), jnp.bool_))


def test_sort_kv_compiles(compile_tpu):
    from repro.kernels.bitonic.bitonic import sort_kv_pallas

    fn = lambda d, i: sort_kv_pallas(d, i, interpret=False)
    compile_tpu(fn, ((B, R), jnp.float32), ((B, R), jnp.int32))


def test_merge_compiles(compile_tpu):
    from repro.kernels.bitonic.bitonic import merge_pallas

    fn = lambda *a: merge_pallas(*a, t=T, interpret=False)
    compile_tpu(fn, ((B, T), jnp.float32), ((B, T), jnp.int32),
                ((B, T), jnp.bool_), ((B, R), jnp.float32), ((B, R), jnp.int32))


def test_adc_compiles(compile_tpu):
    from repro.kernels.pq_adc.pq_adc import adc_pallas

    fn = lambda *a: adc_pallas(*a, interpret=False)
    compile_tpu(fn, ((B, M, 256), jnp.float32), ((B, R, M), jnp.int32),
                ((B, R), jnp.bool_))


def test_dist_table_compiles(compile_tpu):
    from repro.kernels.pq_table.pq_table import dist_table_pallas

    fn = lambda *a: dist_table_pallas(*a, interpret=False)
    compile_tpu(fn, ((B, M, D // M), jnp.float32), ((M, 256, D // M), jnp.float32))


def test_exact_sq_dists_compiles(compile_tpu):
    from repro.kernels.rerank_l2.rerank_l2 import exact_sq_dists_pallas

    fn = lambda *a: exact_sq_dists_pallas(*a, interpret=False)
    compile_tpu(fn, ((B, D), jnp.float32), ((B, 392, D), jnp.float32))


def test_reference_adc_compiles_without_gather(one_chip, no_cache):
    """The chip gets the one-hot ADC: no element gather, no (B, R, m, 256)
    temporary."""
    from repro.core import pq

    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in [((B, M, 256), jnp.float32), ((B, R, M), jnp.uint8)]]
    compiled = pq.adc_distance.lower(*args).compile()
    assert "gather(" not in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


N_CELL, D_CELL, T_CELL, K_CELL = 10_000_000, 96, 32, 10
INDEX_SHAPES = ("[10000000,64]", "[10000000,96]", "[10000000,32]",
                "[5000000,128]", "[2500000,384]")


@pytest.mark.filterwarnings("ignore:Some donated buffers were not usable")
@pytest.mark.parametrize("variant", ["inmem", "exact"])
def test_in_memory_executable_holds_its_index_once(one_chip, no_cache, variant):
    """The executor's own pipeline at the DEEP1B cell's shapes (N 10M, d 96,
    R 64, m 32, B 1024, t 32, k 10), compiled against shapes alone: no call
    copies the graph, the vectors or the codes, and the temporaries stay
    under 1 GiB (a column-major (N, 64) graph and (N, 96) vectors were
    copied whole on every call: 6.38 GB)."""
    import numpy as np

    from repro.core import pq, rows
    from repro.core.search import SearchConfig
    from repro.core.vamana import VamanaGraph
    from repro.runtime.executor import SearchExecutor

    small = 64
    graph = VamanaGraph(
        adjacency=np.random.default_rng(0).integers(
            0, small, (small, R)).astype(np.int32), medoid=0)
    ex = SearchExecutor(
        pq.PQCodec(jnp.zeros((M, 256, D_CELL // M))),
        jnp.zeros((small, M), jnp.uint8), graph, variant=variant,
        data_dev=jnp.zeros((small, D_CELL)))
    f32 = jnp.float32
    state = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        (jax.ShapeDtypeStruct((M, 256, D_CELL // M), f32),
         jax.ShapeDtypeStruct((N_CELL, M), jnp.uint8),
         jax.eval_shape(rows.pack, jax.ShapeDtypeStruct((N_CELL, R), jnp.int32)),
         jax.eval_shape(rows.pack, jax.ShapeDtypeStruct((N_CELL, D_CELL), f32))))
    compiled = ex._compile("cell", B, D_CELL, K_CELL, True,
                           SearchConfig(t=T_CELL), state=state)
    copies = [line for line in compiled.as_text().splitlines()
              if " copy(" in line and any(s in line for s in INDEX_SHAPES)]
    assert not copies
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30
