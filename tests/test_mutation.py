"""Streaming-mutability properties: insert/delete/consolidate under serving.

The contract under test (repro.runtime.mutation):

  * search-after-insert finds the new point (the exact delta scan fuses
    into the main results via merge_worklist);
  * search-after-delete NEVER returns the tombstoned id -- including via
    the ServePipeline result LRU and the hostio hot-adjacency cache;
  * drain() results are bit-exact invariant to max_batch and
    result_cache_size across a mutation epoch;
  * the recall floor holds mid-consolidation (tombstones + delta keep
    results correct while the background fold runs);
  * ids are stable across consolidations, the medoid is undeletable, and
    the variant x placement x kernel-mode matrix stays bit-exact.
"""
import threading

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # clean environment: seeded-random fallback shim
    from _hypothesis_compat import given, settings, strategies as st

import jax

from repro.core import BangIndex, SearchConfig, brute_force_knn, recall_at_k
from repro.runtime import MutableBangIndex, ServePipeline
from repro.runtime.hostio import HostIOConfig

K = 5
T = 32
CFG = SearchConfig(t=T, bloom_z=4096)


@pytest.fixture(scope="module")
def mut_base():
    """(data, BangIndex) shared across tests.

    MutableBangIndex never mutates the wrapped index (consolidation builds
    a *new* BangIndex), so each test wraps a fresh mutable layer around the
    same build.
    """
    from repro.data import gaussian_mixture

    data = gaussian_mixture(240, 8, n_clusters=8, seed=7)
    idx = BangIndex.build(data, m=4, R=8, L_build=16, kmeans_iters=4)
    return data, idx


def _live_gt(mut, queries, k):
    """Brute-force ground truth over the live corpus (global ids)."""
    with mut._lock:
        base = mut.index.data_np
        tomb = mut._tombstones.copy()
        delta_ids, delta_vecs = mut._alive_delta()
    live_base = np.nonzero(~tomb)[0]
    vecs = np.concatenate([base[live_base], delta_vecs], 0)
    gids = np.concatenate([live_base.astype(np.int64), delta_ids]).astype(
        np.int64
    )
    pos = brute_force_knn(vecs, queries, k)
    return gids[pos]


# --------------------------------------------------------------- tentpole
@settings(max_examples=5, deadline=None)
@given(st.integers(0, 10_000))
def test_search_after_insert_finds_new_point(mut_base, seed):
    data, idx = mut_base
    mut = MutableBangIndex(idx)
    rng = np.random.default_rng(seed)
    vec = data[int(rng.integers(len(data)))] + rng.normal(0, 0.05, data.shape[1]).astype(np.float32)
    gid = mut.insert(vec)
    ids, dists = mut.search(vec[None], k=K, t=T, cfg=CFG)
    assert ids[0, 0] == gid[0]
    np.testing.assert_allclose(dists[0, 0], 0.0, atol=1e-5)


@settings(max_examples=5, deadline=None)
@given(st.integers(0, 10_000))
def test_search_after_delete_never_returns_id(mut_base, seed):
    data, idx = mut_base
    mut = MutableBangIndex(idx)
    rng = np.random.default_rng(seed)
    q = data[rng.integers(len(data), size=6)] + 0.01
    ids0, _ = mut.search(q, k=K, t=T, cfg=CFG)
    medoid = int(idx.graph.medoid)
    victims = [int(i) for i in np.unique(ids0[:, 0]) if int(i) != medoid][:3]
    assert victims
    mut.delete(victims)
    ids1, _ = mut.search(q, k=K, t=T, cfg=CFG)
    assert not set(victims) & set(np.asarray(ids1).ravel().tolist())


def test_delete_invalidates_result_lru(mut_base):
    """A cached drain() result must never serve a tombstoned id."""
    data, idx = mut_base
    mut = MutableBangIndex(idx)
    q = data[:8] + 0.01
    pipe = ServePipeline(
        mut.executor("inmem"), k=K, cfg=CFG, max_batch=4,
        result_cache_size=64,
    )
    pipe.submit(q)
    ids0, _, _ = pipe.drain()
    # Second drain of the same rows: all LRU hits, bit-identical.
    pipe.submit(q)
    ids1, _, stats = pipe.drain()
    assert stats.result_cache_hits == len(q)
    np.testing.assert_array_equal(ids0, ids1)
    victim = int(ids0[0, 0])
    if victim == int(idx.graph.medoid):
        victim = int(ids0[0, 1])
    mut.delete([victim])
    # Epoch moved -> the LRU is dropped; no cached row can resurface it.
    pipe.submit(q)
    ids2, _, stats = pipe.drain()
    assert stats.result_cache_hits == 0
    assert victim not in np.asarray(ids2).ravel().tolist()
    assert stats.mutation is not None and stats.mutation["tombstones"] == 1


def test_delta_point_delete_and_reinsert(mut_base):
    data, idx = mut_base
    mut = MutableBangIndex(idx)
    vec = data[3] + 0.2
    g1 = int(mut.insert(vec)[0])
    ids, _ = mut.search(vec[None], k=K, t=T, cfg=CFG)
    assert ids[0, 0] == g1
    mut.delete([g1])
    ids, _ = mut.search(vec[None], k=K, t=T, cfg=CFG)
    assert g1 not in np.asarray(ids).ravel().tolist()
    # Re-insert the identical vector: new id, old one stays dead.
    g2 = int(mut.insert(vec)[0])
    assert g2 != g1
    ids, _ = mut.search(vec[None], k=K, t=T, cfg=CFG)
    assert ids[0, 0] == g2


def test_drain_bit_exact_across_batching_and_cache(mut_base):
    """drain() results are invariant to max_batch/result_cache_size across
    a mutation epoch (tentpole acceptance criterion)."""
    data, idx = mut_base
    q = data[10:34] + 0.01
    outs = []
    for max_batch, cache in [(4, 0), (16, 0), (7, 32), (24, 8)]:
        mut = MutableBangIndex(idx)
        pipe = ServePipeline(
            mut.executor("inmem"), k=K, cfg=CFG, max_batch=max_batch,
            result_cache_size=cache,
        )
        pipe.submit(q[:12])
        ids_a, dists_a, _ = pipe.drain()
        mut.insert(data[5] + 0.3)
        victim = int(ids_a[0, 0])
        if victim == int(idx.graph.medoid):
            victim = int(ids_a[0, 1])
        mut.delete([victim])
        pipe.submit(q)
        ids_b, dists_b, _ = pipe.drain()
        outs.append((ids_a, dists_a, ids_b, dists_b))
    ref = outs[0]
    for got in outs[1:]:
        for r, g in zip(ref, got):
            np.testing.assert_array_equal(r, g)


def test_consolidation_folds_delta_and_retires_deleted(mut_base):
    data, idx = mut_base
    mut = MutableBangIndex(idx)
    vec = data[17] + 0.15
    gid = int(mut.insert(vec)[0])
    ids0, _ = mut.search(data[:4] + 0.01, k=K, t=T, cfg=CFG)
    victim = int(ids0[0, 0])
    if victim == int(idx.graph.medoid):
        victim = int(ids0[0, 1])
    mut.delete([victim])
    stats = mut.consolidate()
    assert stats["generation"] == 1 and stats["delta_points"] == 0
    adj = mut.index.graph.adjacency
    # Deleted slot retired: all out-edges dark, no in-edges anywhere.
    assert (adj[victim] == -1).all()
    assert victim not in adj[adj >= 0]
    # The folded delta point is a first-class graph node now.
    assert (adj[gid] >= 0).any()
    ids1, d1 = mut.search(vec[None], k=K, t=T, cfg=CFG)
    assert ids1[0, 0] == gid and victim not in np.asarray(ids1).ravel()
    # Ids remain stable: the next insert continues the id space.
    g2 = int(mut.insert(data[2])[0])
    assert g2 == mut.index.n


def test_recall_floor_holds_mid_consolidation(mut_base):
    data, idx = mut_base
    mut = MutableBangIndex(idx)
    rng = np.random.default_rng(11)
    mut.insert(data[rng.integers(len(data), size=6)] + 0.1)
    ids0, _ = mut.search(data[:8] + 0.01, k=K, t=T, cfg=CFG)
    medoid = int(idx.graph.medoid)
    victims = [int(i) for i in np.unique(ids0[:, -1]) if int(i) != medoid][:4]
    mut.delete(victims)
    q = data[40:56] + 0.01
    gt = _live_gt(mut, q, K)

    th = mut.consolidate_async()
    floors = []
    while True:
        alive = th.is_alive()
        ids, _ = mut.search(q, k=K, t=T, cfg=CFG)
        floors.append(recall_at_k(ids, gt))
        if not alive:
            break
    th.join()
    assert mut.consolidate_error is None
    assert mut.generation == 1
    # At least one search raced the background fold; recall never dipped.
    assert len(floors) >= 2
    assert min(floors) >= 0.9
    # Post-consolidation ground truth is unchanged (same live corpus).
    ids, _ = mut.search(q, k=K, t=T, cfg=CFG)
    assert recall_at_k(ids, gt) >= 0.9


def test_medoid_delete_rejected(mut_base):
    _, idx = mut_base
    mut = MutableBangIndex(idx)
    with pytest.raises(ValueError, match="medoid"):
        mut.delete([int(idx.graph.medoid)])
    with pytest.raises(ValueError, match="unknown id"):
        mut.delete([10**6])


def test_rerank_false_rejected_with_live_delta(mut_base):
    data, idx = mut_base
    mut = MutableBangIndex(idx)
    # No delta yet: rerank=False is fine (tombstones alone don't need fusion).
    mut.search(data[:2], k=K, t=T, cfg=CFG, rerank=False)
    mut.insert(data[0] + 0.5)
    with pytest.raises(ValueError, match="rerank=False"):
        mut.search(data[:2], k=K, t=T, cfg=CFG, rerank=False)
    # The exact variant's worklist is already exact-space: always allowed.
    mut.search(data[:2], k=K, t=T, cfg=CFG, variant="exact", rerank=False)


# ---------------------------------------------- placement / kernel matrix
def test_mutation_parity_across_variants_and_modes(mut_base):
    """Insert/delete correctness across the variant x placement x
    kernel-mode matrix: ids bit-exact, dists to kernel float tolerance
    (matching the frozen-index parity contract in test_kernels)."""
    data, idx = mut_base
    mut = MutableBangIndex(idx)
    q = data[60:66] + 0.01
    gid = int(mut.insert(q[0].copy())[0])
    ids0, _ = mut.search(q, k=K, t=T, cfg=CFG)
    victim = int(ids0[1, 0])
    if victim == int(idx.graph.medoid) or victim == gid:
        victim = int(ids0[1, 1])
    mut.delete([victim])
    ref_ids, ref_dists = mut.search(q, k=K, t=T, cfg=CFG)
    assert ref_ids[0, 0] == gid
    assert victim not in np.asarray(ref_ids).ravel()

    cells = [("inmem", None), ("base", None), ("sharded", "mesh"),
             ("sharded-base", "mesh")]
    from repro.compat import make_mesh

    mesh = make_mesh((1, len(jax.devices())), ("data", "model"))
    for variant, m in cells:
        for kernel_mode in ("reference", "staged", "fused"):
            ids, dists = mut.search(
                q, k=K, t=T, cfg=CFG, variant=variant,
                mesh=mesh if m else None, kernel_mode=kernel_mode,
            )
            np.testing.assert_array_equal(
                np.asarray(ids), np.asarray(ref_ids),
                err_msg=f"{variant}/{kernel_mode}",
            )
            np.testing.assert_allclose(
                np.asarray(dists), np.asarray(ref_dists),
                rtol=1e-6, atol=1e-5,
                err_msg=f"{variant}/{kernel_mode}",
            )


def test_tombstones_flow_through_hot_adjacency_cache(mut_base):
    """Deletes hold through the hostio path, and consolidation refreshes
    the pinned hot-cache rows (delete-only fold keeps the shape)."""
    data, idx = mut_base
    mut = MutableBangIndex(idx)
    hio = HostIOConfig(workers=1, hot_cache_rows=64)
    ex = mut.executor("base", hostio=hio)
    pipe = ServePipeline(ex, k=K, cfg=CFG, max_batch=8)
    try:
        q = data[:8] + 0.01
        pipe.submit(q)
        ids0, _, _ = pipe.drain()
        victim = int(ids0[0, 0])
        if victim == int(idx.graph.medoid):
            victim = int(ids0[0, 1])
        mut.delete([victim])
        pipe.submit(q)
        ids1, _, _ = pipe.drain()
        assert victim not in np.asarray(ids1).ravel()
        cache = ex.hostio_runtime.cache
        rows_before = np.asarray(cache._rows).copy()
        mut.consolidate()
        # Same cache object, refreshed rows: pinned block now mirrors the
        # consolidated adjacency for the same hot ids.
        np.testing.assert_array_equal(
            np.asarray(cache._rows),
            mut.index.graph.adjacency[cache.hot_ids],
        )
        if victim in cache.hot_ids:
            assert not np.array_equal(np.asarray(cache._rows), rows_before)
        pipe.submit(q)
        ids2, _, _ = pipe.drain()
        assert victim not in np.asarray(ids2).ravel()
    finally:
        pipe.close()
        mut.close()


def test_tombstone_updates_never_retrace(mut_base):
    """The bitmap is an executable operand: deletes must not recompile."""
    data, idx = mut_base
    mut = MutableBangIndex(idx)
    ex = mut.executor("inmem")
    q = data[:4] + 0.01
    mut.search(q, k=K, t=T, cfg=CFG)
    traces = dict(ex.trace_counts)
    for i in (3, 9, 27):
        if i != int(idx.graph.medoid):
            mut.delete([i])
        mut.search(q, k=K, t=T, cfg=CFG)
    assert dict(ex.trace_counts) == traces


# ------------------------------------------------------------- accounting
def test_mutation_counters_in_exchange_and_stats(mut_base):
    data, idx = mut_base
    mut = MutableBangIndex(idx)
    mut.insert(data[:3] + 0.1)
    mut.delete([int(i) for i in range(4) if i != int(idx.graph.medoid)][:2])
    ex = mut.executor("inmem")
    x = ex.exchange_bytes_per_hop(8)
    assert x["delta_points"] == 3
    assert x["tombstone_fraction"] == pytest.approx(2 / idx.n)
    s = mut.mutation_stats()
    assert s["epoch"] == 2 and s["generation"] == 0
    assert s["tombstones"] == 2 and s["delta_total"] == 3
