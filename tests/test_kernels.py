"""Per-kernel shape/dtype sweeps: Pallas (interpret) vs ref.py oracles, plus
the fused search_step megakernel (unit, property, and executor-level parity
across kernel_mode x batch bucket x serving variant)."""
import os
import sys

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.worklist import INVALID_ID, Worklist

try:
    from hypothesis import given, settings, strategies as st
except ImportError:
    from _hypothesis_compat import given, settings, strategies as st

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

KERNEL_MODES = ("reference", "staged", "fused")


@pytest.mark.parametrize("B,R,m", [(1, 4, 4), (3, 17, 9), (8, 64, 74), (5, 31, 16)])
def test_pq_adc(B, R, m, rng):
    from repro.kernels.pq_adc import ops

    table = jnp.asarray(rng.standard_normal((B, m, 256)).astype(np.float32) ** 2)
    codes = jnp.asarray(rng.integers(0, 256, (B, R, m)).astype(np.int32))
    valid = jnp.asarray(rng.random((B, R)) > 0.25)
    out = ops.adc(table, codes, valid)
    ref = ops.adc_ref(table, codes, valid)
    fin = np.isfinite(np.asarray(ref))
    np.testing.assert_allclose(np.asarray(out)[fin], np.asarray(ref)[fin], rtol=1e-5)
    assert np.array_equal(np.isinf(np.asarray(out)), ~fin)


@pytest.mark.parametrize("B,m,dsub", [(1, 1, 4), (7, 6, 11), (13, 8, 16), (4, 74, 2)])
def test_pq_table(B, m, dsub, rng):
    from repro.core.pq import PQCodec
    from repro.kernels.pq_table import ops

    cb = jnp.asarray(rng.standard_normal((m, 256, dsub)).astype(np.float32))
    q = jnp.asarray(rng.standard_normal((B, m * dsub)).astype(np.float32))
    out = ops.build_dist_table(PQCodec(cb), q)
    ref = ops.dist_table_ref(q.reshape(B, m, dsub), cb)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("B,n", [(1, 2), (5, 16), (9, 23), (3, 64), (2, 100)])
def test_bitonic_sort(B, n, rng):
    from repro.kernels.bitonic import ops

    d = jnp.asarray(rng.standard_normal((B, n)).astype(np.float32))
    # duplicate keys exercise the (dist, id) tie-break
    d = jnp.concatenate([d[:, : n // 2], d[:, : n - n // 2]], axis=-1)
    i = jnp.asarray(rng.integers(0, 10_000, (B, n)).astype(np.int32))
    sd, si = ops.sort_kv(d, i)
    rd, ri = ops.sort_kv_ref(d, i)
    np.testing.assert_allclose(np.asarray(sd), np.asarray(rd))
    np.testing.assert_array_equal(np.asarray(si), np.asarray(ri))


@pytest.mark.parametrize("B,t,R", [(1, 4, 4), (6, 16, 12), (3, 64, 64), (2, 33, 7)])
def test_bitonic_merge(B, t, R, rng):
    from repro.kernels.bitonic import ops

    wl_d = jnp.sort(jnp.asarray(rng.standard_normal((B, t)).astype(np.float32)), axis=-1)
    wl_i = jnp.asarray(rng.integers(0, 1000, (B, t)).astype(np.int32))
    wl_v = jnp.asarray(rng.random((B, t)) > 0.5)
    cd = jnp.sort(jnp.asarray(rng.standard_normal((B, R)).astype(np.float32)), axis=-1)
    ci = jnp.asarray(rng.integers(1000, 2000, (B, R)).astype(np.int32))
    out = ops.merge_worklist(Worklist(wl_d, wl_i, wl_v), cd, ci)
    rd, ri, rv = ops.merge_ref(wl_d, wl_i, wl_v, cd, ci, t)
    np.testing.assert_allclose(np.asarray(out.dists), np.asarray(rd))
    np.testing.assert_array_equal(np.asarray(out.ids), np.asarray(ri))
    np.testing.assert_array_equal(np.asarray(out.visited), np.asarray(rv))


@pytest.mark.parametrize("B,C,d", [(1, 1, 8), (5, 19, 37), (4, 200, 128), (2, 7, 129)])
def test_rerank_l2(B, C, d, rng):
    from repro.kernels.rerank_l2 import ops

    q = jnp.asarray(rng.standard_normal((B, d)).astype(np.float32))
    v = jnp.asarray(rng.standard_normal((B, C, d)).astype(np.float32))
    out = ops.exact_sq_dists(q, v)
    ref = ops.exact_sq_dists_ref(q, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)


def test_kernel_search_path_matches_reference_path(small_ann_index, rng):
    """End-to-end: use_kernels=True returns bit-identical neighbour ids."""
    from repro.core import SearchConfig

    data, idx = small_ann_index
    queries = rng.standard_normal((8, data.shape[1])).astype(np.float32)
    ids_k, _ = idx.search(queries, 10, cfg=SearchConfig(t=32, bloom_z=4096, use_kernels=True))
    ids_r, _ = idx.search(queries, 10, cfg=SearchConfig(t=32, bloom_z=4096, use_kernels=False))
    np.testing.assert_array_equal(np.asarray(ids_k), np.asarray(ids_r))


# ------------------------------------------------- fused search_step kernel
def _random_step_inputs(rng, B, R, t, m, n):
    """Random iteration state; integer-valued tables keep every ADC sum
    exactly representable in f32, so summation order cannot perturb parity
    and the oracle comparison is bitwise."""
    table = jnp.asarray(rng.integers(0, 1000, (B, m, 256)).astype(np.float32))
    codes = jnp.asarray(rng.integers(0, 256, (n, m)).astype(np.uint8))
    nbrs = jnp.asarray(rng.integers(0, n, (B, R)).astype(np.int32))
    fresh = jnp.asarray(rng.random((B, R)) > 0.3)
    # sorted random worklist with ids disjoint from the candidate range
    wd = np.sort(rng.integers(0, 5000, (B, t)).astype(np.float32), axis=-1)
    wi = rng.permutation(np.arange(n, n + t * B)).reshape(B, t).astype(np.int32)
    order = np.lexsort((wi, wd), axis=-1)
    wl = Worklist(
        jnp.asarray(np.take_along_axis(wd, order, -1)),
        jnp.asarray(np.take_along_axis(wi, order, -1)),
        jnp.asarray(rng.random((B, t)) > 0.5),
    )
    active = jnp.asarray(rng.random((B,)) > 0.2)
    return table, codes, nbrs, fresh, wl, active


def _assert_step_matches_oracle(table, codes, nbrs, fresh, wl, active, eager,
                                tile_rows=0):
    from repro.kernels.search_step import ops

    wl2, u, a = ops.fused_step(table, ops.code_lines(codes), codes.shape[0],
                               wl, nbrs, fresh, active,
                               eager=eager, tile_rows=tile_rows)
    rd, ri, rv, ru, ra = ops.step_ref(
        table, codes, nbrs, fresh, wl.dists, wl.ids, wl.visited, active,
        eager=eager,
    )
    np.testing.assert_array_equal(np.asarray(wl2.ids), np.asarray(ri))
    np.testing.assert_array_equal(np.asarray(wl2.dists), np.asarray(rd))
    np.testing.assert_array_equal(np.asarray(wl2.visited), np.asarray(rv))
    np.testing.assert_array_equal(np.asarray(u), np.asarray(ru))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(ra))


@pytest.mark.parametrize("B,R,t,m,n", [
    (1, 1, 4, 1, 16),          # degenerate single-candidate step
    (3, 17, 24, 9, 120),       # non-pow2 R and t, odd m
    (8, 32, 32, 8, 256),       # pow2 everywhere (the serving shape)
    (2, 24, 33, 6, 90),        # t just past a pow2 boundary
])
@pytest.mark.parametrize("eager", [True, False])
def test_search_step_matches_oracle(B, R, t, m, n, eager, rng):
    _assert_step_matches_oracle(*_random_step_inputs(rng, B, R, t, m, n), eager)


@pytest.mark.parametrize("B,R,t", [(1, 4, 8), (5, 31, 16), (9, 16, 64)])
@pytest.mark.parametrize("eager", [True, False])
def test_fused_traverse_matches_oracle(B, R, t, eager, rng):
    from repro.kernels.search_step import ops

    fresh = jnp.asarray(rng.random((B, R)) > 0.3)
    cd = jnp.where(fresh, jnp.asarray(
        rng.integers(0, 5000, (B, R)).astype(np.float32)), jnp.inf)
    ci = jnp.where(fresh, jnp.asarray(
        rng.integers(0, 10_000, (B, R)).astype(np.int32)), INVALID_ID)
    _, _, _, _, wl, active = _random_step_inputs(rng, B, R, t, 1, 16)
    wl2, u, a = ops.fused_traverse(wl, cd, ci, active, eager=eager)
    rd, ri, rv, ru, ra = ops.traverse_ref(
        cd, ci, wl.dists, wl.ids, wl.visited, active, eager=eager
    )
    np.testing.assert_array_equal(np.asarray(wl2.ids), np.asarray(ri))
    np.testing.assert_array_equal(np.asarray(wl2.dists), np.asarray(rd))
    np.testing.assert_array_equal(np.asarray(wl2.visited), np.asarray(rv))
    np.testing.assert_array_equal(np.asarray(u), np.asarray(ru))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(ra))


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_fused_step_property_random_worklists(seed):
    """Property: the megakernel equals the ref.py oracle on arbitrary
    worklist/candidate/activity states, both selection modes."""
    prng = np.random.default_rng(seed)
    B = int(prng.integers(1, 5))
    R = int(prng.integers(1, 25))
    t = int(prng.integers(4, 33))
    m = int(prng.integers(1, 13))
    n = int(prng.integers(16, 200))
    eager = bool(prng.integers(0, 2))
    _assert_step_matches_oracle(
        *_random_step_inputs(prng, B, R, t, m, n), eager
    )


@pytest.mark.parametrize("variant", ["inmem", "base", "sharded", "sharded-base"])
@pytest.mark.parametrize("batch", [5, 12])   # -> buckets 8 and 16
def test_executor_kernel_mode_parity(small_ann_index, variant, batch, rng):
    """Executor-level matrix: every kernel_mode returns bit-identical ids
    and (re-ranked, exact) dists on every serving variant and bucket."""
    from repro.core import SearchConfig

    data, idx = small_ann_index
    queries = rng.standard_normal((batch, data.shape[1])).astype(np.float32)
    cfg = SearchConfig(t=16, bloom_z=4096)
    out = {}
    for mode in KERNEL_MODES:
        ids, dists = idx.search(
            queries, 5, cfg=cfg, variant=variant, kernel_mode=mode
        )
        out[mode] = (np.asarray(ids), np.asarray(dists))
    ref_ids, ref_dists = out["reference"]
    assert ref_ids.shape == (batch, 5)
    for mode in ("staged", "fused"):
        np.testing.assert_array_equal(out[mode][0], ref_ids)
        # kernel modes re-rank through the rerank_l2 Pallas kernel, whose
        # exact-L2 accumulation order differs from the XLA reference by at
        # most an ulp; ids above are bit-identical.
        np.testing.assert_allclose(
            out[mode][1], ref_dists, rtol=1e-6, atol=1e-5
        )
    # fused and staged share the one-hot ADC op sequence and both re-rank
    # through the kernel: bit-identical to each other.
    np.testing.assert_array_equal(out["fused"][1], out["staged"][1])
    # cross-variant: the PQ cells agree bitwise with single-device inmem
    in_ids, in_dists = idx.search(queries, 5, cfg=cfg, variant="inmem")
    np.testing.assert_array_equal(ref_ids, np.asarray(in_ids))
    np.testing.assert_array_equal(ref_dists, np.asarray(in_dists))


def test_kernel_mode_compile_cache_isolation(small_ann_index, rng):
    """Each kernel_mode compiles its own bucketed executable exactly once."""
    from repro.core import SearchConfig
    from repro.runtime import SearchExecutor

    data, idx = small_ann_index
    ex = SearchExecutor.from_index(idx, variant="inmem")
    queries = rng.standard_normal((4, data.shape[1])).astype(np.float32)
    cfg = SearchConfig(t=16, bloom_z=4096)
    for mode in KERNEL_MODES:
        for _ in range(2):
            ex.search(queries, 5, cfg=cfg, kernel_mode=mode)
    assert ex.cache_size == len(KERNEL_MODES)
    assert ex.n_traces == len(KERNEL_MODES)
    with pytest.raises(ValueError, match="kernel_mode"):
        ex.search(queries, 5, cfg=cfg, kernel_mode="warp")


def test_hbm_accounting_fused_strictly_fewer():
    """Acceptance: the fused step issues strictly fewer HBM-visible
    intermediates -- one candidate-tile round-trip per hop, zero bytes of
    inter-stage temporaries."""
    from repro.kernels.search_step import ops

    assert ops.hbm_candidate_roundtrips_per_hop("fused") == 1
    assert (
        ops.hbm_candidate_roundtrips_per_hop("fused")
        < ops.hbm_candidate_roundtrips_per_hop("staged")
    )
    B, R, m, t = 64, 32, 16, 64
    fused = ops.hbm_intermediate_bytes_per_hop("fused", B, R, m, t)
    staged = ops.hbm_intermediate_bytes_per_hop("staged", B, R, m, t)
    assert fused == 0 and fused < staged
    # the staged bill is dominated by the (B, R, m) gathered-codes temporary
    assert staged >= B * R * m * 4


def test_bench_kernel_row_json_schema():
    """bench_kernels' executor-lane rows: schema + fused < staged traffic."""
    import json

    if REPO not in sys.path:
        sys.path.insert(0, REPO)   # benchmarks/ lives next to src/, not in it
    from benchmarks.bench_kernels import KERNEL_ROW_SCHEMA, kernel_row

    rows = {
        mode: kernel_row(
            f"exec_inmem_{mode}_b16", mode, "inmem", 12, 16,
            qps=100.0, us_per_query=10.0, per_hop_us=1.0, n_iters=32,
            R=16, m=8, compile_s=1.0, t=16,
        )
        for mode in KERNEL_MODES
    }
    for row in rows.values():
        assert set(row) == set(KERNEL_ROW_SCHEMA)
        assert row == json.loads(json.dumps(row))
    assert (
        rows["fused"]["hbm_candidate_roundtrips_per_hop"]
        < rows["staged"]["hbm_candidate_roundtrips_per_hop"]
    )
    assert (
        rows["fused"]["hbm_intermediate_bytes_per_hop"]
        < rows["staged"]["hbm_intermediate_bytes_per_hop"]
    )


# ------------------------------------------------ beyond-VMEM DMA pipeline
def test_resolve_codes_tiling_policy(monkeypatch):
    from repro.kernels.search_step import ops

    # Resident while the packed lines fit the default budget.
    assert ops.codes_resident(1200, 8)
    # Explicit tile_rows: the autotuner's knob forces the HBM placement,
    # unless it covers the whole block.
    assert not ops.codes_resident(1200, 8, 64)
    assert not ops.codes_resident(1200, 8, 3)
    assert ops.codes_resident(1200, 8, 1200)
    assert ops.codes_resident(1200, 8, 5000)
    with pytest.raises(ValueError, match="tile_rows"):
        ops.codes_resident(1200, 8, -1)
    # Auto beyond the (env-forced) budget: HBM. 1200 rows of m = 8 pack
    # into 19 lines of 512 bytes.
    monkeypatch.setenv("REPRO_VMEM_BUDGET", "2048")
    assert ops.vmem_budget_bytes() == 2048
    assert ops.lines_bytes(1200, 8) == 19 * 512
    assert not ops.codes_resident(1200, 8)
    assert ops.codes_resident(30, 8)


@pytest.mark.parametrize("n", [8, 16, 64, 100, 119])
@pytest.mark.parametrize("eager", [True, False])
def test_fused_step_dma_matches_resident(n, eager, rng):
    """The HBM-placed megakernel (one row DMA per candidate) is bit-identical
    to the VMEM-resident one (and hence the ref.py oracle) for blocks that
    fill, overfill and underfill their packed code lines."""
    from repro.kernels.common import interpret_mode
    from repro.kernels.search_step.search_step import (
        code_lines, fused_step_pallas,
    )

    table, codes, nbrs, fresh, wl, active = _random_step_inputs(
        rng, 4, 17, 24, 9, n
    )
    res, dma = (
        fused_step_pallas(
            table, code_lines(codes), nbrs, fresh, wl.dists, wl.ids,
            wl.visited, active, eager=eager, resident=resident,
            interpret=interpret_mode(),
        )
        for resident in (True, False)
    )
    for a, b in zip(res, dma):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("m", [4, 9, 32])
def test_local_adc_dma_matches_resident(m, rng):
    """Sharded owner-shard fused fetch+ADC: HBM placement bit-identical to
    the resident one and to the oracle's owned rows."""
    from repro.kernels.common import interpret_mode
    from repro.kernels.pq_adc.ref import adc_ref
    from repro.kernels.search_step.search_step import (
        code_lines, local_adc_pallas,
    )

    B, R, n_loc = 5, 13, 120
    table = jnp.asarray(rng.integers(0, 1000, (B, m, 256)).astype(np.float32))
    codes = jnp.asarray(rng.integers(0, 256, (n_loc, m)).astype(np.uint8))
    rel = jnp.asarray(rng.integers(0, n_loc, (B, R)).astype(np.int32))
    own = jnp.asarray(rng.random((B, R)) > 0.4)
    res, dma = (
        local_adc_pallas(table, code_lines(codes), rel, own,
                         resident=resident, interpret=interpret_mode())
        for resident in (True, False)
    )
    np.testing.assert_array_equal(np.asarray(res), np.asarray(dma))
    ref = jnp.where(own, adc_ref(table, codes[rel], own), 0.0)
    np.testing.assert_array_equal(np.asarray(res), np.asarray(ref))


@pytest.mark.parametrize("tile_rows", [0, 16, 90])
@pytest.mark.parametrize("eager", [True, False])
def test_fused_step_tile_rows_dispatch_bit_exact(tile_rows, eager, rng,
                                                 monkeypatch):
    """ops.fused_step under a tiny VMEM budget (auto DMA) or an explicit
    tile matches the oracle bitwise -- the public dispatch layer."""
    monkeypatch.setenv("REPRO_VMEM_BUDGET", "256")   # 120*9 codes > 256
    table, codes, nbrs, fresh, wl, active = _random_step_inputs(
        rng, 3, 11, 16, 9, 120
    )
    from repro.kernels.search_step import ops

    assert not ops.codes_resident(120, 9, tile_rows)
    _assert_step_matches_oracle(table, codes, nbrs, fresh, wl, active, eager,
                                tile_rows=tile_rows)


@pytest.mark.parametrize("variant", ["inmem", "base", "sharded",
                                     "sharded-base", "exact"])
def test_beyond_vmem_executor_parity(small_ann_index, variant, rng,
                                     monkeypatch):
    """Acceptance: with the codes block forced past the VMEM budget, fused
    engages the DMA pipeline (never a staged fallback) on every serving
    variant and returns bit-identical ids vs staged and reference; fused
    dists are bitwise equal to staged (identical op sequence). Fresh
    executors per mode so the forced budget governs every compile."""
    from repro.core import SearchConfig
    from repro.kernels.search_step import ops as step_ops
    from repro.runtime import SearchExecutor, ShardedSearchExecutor

    monkeypatch.setenv("REPRO_VMEM_BUDGET", "2048")
    data, idx = small_ann_index
    n, m = idx.codes.shape
    assert not step_ops.codes_resident(n, m)
    queries = rng.standard_normal((6, data.shape[1])).astype(np.float32)
    cfg = SearchConfig(t=16, bloom_z=4096)
    out = {}
    for mode in KERNEL_MODES:
        if variant.startswith("sharded"):
            from repro.compat import make_mesh

            mesh = make_mesh((1, len(jax.devices())), ("data", "model"))
            ex = ShardedSearchExecutor.from_index(idx, mesh, variant=variant)
        else:
            ex = SearchExecutor.from_index(idx, variant=variant)
        ids, dists = ex.search(queries, 5, cfg=cfg, kernel_mode=mode)
        out[mode] = (np.asarray(ids), np.asarray(dists))
    for mode in ("staged", "fused"):
        np.testing.assert_array_equal(out[mode][0], out["reference"][0])
    if variant != "exact":
        # exact's fused/staged differ only in traversal schedule; the PQ
        # variants' fused ADC shares staged's op sequence bit-for-bit.
        np.testing.assert_array_equal(out["fused"][1], out["staged"][1])


def test_hbm_codes_stream_accounting():
    """The fused lane's analytic codes traffic: the resident block is staged
    once per hop, the HBM placement reads one 512-byte line per candidate;
    other modes report 0 (their codes traffic is inside the
    candidate-roundtrip/intermediate terms)."""
    from repro.kernels.search_step import ops

    B, n, m, R = 16, 8000, 16, 32
    assert ops.hbm_codes_stream_bytes_per_hop("staged", B, n, m, R, 64) == 0
    assert ops.hbm_codes_stream_bytes_per_hop("reference", B, n, m, R, 64) == 0
    # Resident lines: 32 rows of 16 codes per 512-byte line, staged once.
    assert ops.hbm_codes_stream_bytes_per_hop("fused", B, n, m, R, 0) == n * m
    # HBM placement: independent of n, one line per candidate lane.
    assert ops.hbm_codes_stream_bytes_per_hop("fused", B, n, m, R, 64) == (
        B * R * 512
    )


def test_bench_beyond_vmem_row_json_schema():
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    from benchmarks.bench_kernels import BEYOND_VMEM_ROW_SCHEMA

    assert {"per_hop_us", "codes_tile_rows", "num_tiles",
            "vmem_budget_bytes", "hbm_codes_stream_bytes_per_hop",
            } <= set(BEYOND_VMEM_ROW_SCHEMA)
