"""End-to-end BANG search behaviour (the paper's claims as tests)."""
import numpy as np
import pytest

from repro.core import BangIndex, SearchConfig, brute_force_knn, recall_at_k
from repro.core.search import SearchConfig as SC, search_exact
from repro.core.vamana import build_fully_connected
from repro.data import gaussian_mixture, uniform_queries


@pytest.fixture(scope="module")
def queries(small_ann_index):
    data, _ = small_ann_index
    return uniform_queries(data, 24, seed=7)


def test_recall_at_headline_point(small_ann_index, queries):
    """Paper headline: high recall (>=0.9) at reasonable worklist size."""
    data, idx = small_ann_index
    gt = brute_force_knn(data, queries, 10)
    ids, _ = idx.search(queries, 10, cfg=SearchConfig(t=64, bloom_z=8192))
    assert recall_at_k(np.asarray(ids), gt) >= 0.9


def test_rerank_improves_recall(small_ann_index, queries):
    """Paper §4.9: re-ranking lifts recall materially."""
    data, idx = small_ann_index
    gt = brute_force_knn(data, queries, 10)
    cfg = SearchConfig(t=48, bloom_z=8192)
    with_rr, _ = idx.search(queries, 10, cfg=cfg, rerank=True)
    without_rr, _ = idx.search(queries, 10, cfg=cfg, rerank=False)
    r_with = recall_at_k(np.asarray(with_rr), gt)
    r_without = recall_at_k(np.asarray(without_rr), gt)
    assert r_with > r_without + 0.03


def test_base_variant_identical_to_inmem(small_ann_index, queries):
    """Host-graph (PCIe analogue) and device-graph searches must agree."""
    data, idx = small_ann_index
    cfg = SearchConfig(t=32, bloom_z=8192)
    a, _ = idx.search(queries, 10, variant="base", cfg=cfg)
    b, _ = idx.search(queries, 10, variant="inmem", cfg=cfg)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_exact_variant_beats_pq_no_rerank(small_ann_index, queries):
    data, idx = small_ann_index
    gt = brute_force_knn(data, queries, 10)
    cfg = SearchConfig(t=48, bloom_z=8192)
    ex, _ = idx.search(queries, 10, variant="exact", cfg=cfg)
    pq_ids, _ = idx.search(queries, 10, variant="inmem", cfg=cfg, rerank=False)
    assert recall_at_k(np.asarray(ex), gt) >= recall_at_k(np.asarray(pq_ids), gt)


def test_exact_search_on_complete_graph_is_exhaustive(rng):
    """Exact-distance greedy search on a complete graph with t>=n == brute force."""
    import jax.numpy as jnp

    n, d = 64, 8
    data = rng.standard_normal((n, d)).astype(np.float32)
    q = rng.standard_normal((5, d)).astype(np.float32)
    g = build_fully_connected(n)
    res = search_exact(
        jnp.asarray(q), jnp.asarray(data), jnp.asarray(g.adjacency), g.medoid,
        SC(t=n, bloom_z=4096, max_iters=2 * n),
    )
    gt = brute_force_knn(data, q, 10)
    found = np.asarray(res.worklist.ids[:, :10])
    assert recall_at_k(found, gt) == 1.0


def test_eager_vs_lazy_selection(small_ann_index, queries):
    """§4.6 eager candidate selection must not hurt recall materially."""
    data, idx = small_ann_index
    gt = brute_force_knn(data, queries, 10)
    r = {}
    for eager in (True, False):
        ids, _ = idx.search(queries, 10, cfg=SearchConfig(t=48, bloom_z=8192, eager=eager))
        r[eager] = recall_at_k(np.asarray(ids), gt)
    assert r[True] >= r[False] - 0.02


def test_iteration_count_near_worklist_size(small_ann_index, queries):
    """Paper Fig 10: queries converge in ~1.1x t iterations."""
    data, idx = small_ann_index
    t = 48
    _, _, stats = idx.search(
        queries, 10, cfg=SearchConfig(t=t, bloom_z=8192), return_stats=True
    )
    assert stats.p95_hops <= 1.6 * t       # generous bound for tiny datasets
    assert stats.mean_hops >= 0.4 * t      # and it genuinely explores


def test_larger_t_does_not_reduce_recall(small_ann_index, queries):
    data, idx = small_ann_index
    gt = brute_force_knn(data, queries, 10)
    r = []
    for t in (16, 48, 96):
        ids, _ = idx.search(queries, 10, cfg=SearchConfig(t=t, bloom_z=8192))
        r.append(recall_at_k(np.asarray(ids), gt))
    assert r[-1] >= r[0] - 1e-9
    assert r[-1] >= 0.9


@pytest.mark.parametrize("chunk_rows", [7, 64])
def test_host_gather_one_callback_matches_chunks(chunk_rows):
    """The TPU's form of the Base re-rank gather, the whole (B, C) block in
    one callback, returns the same vectors and the same re-ranked (ids,
    dists) as the chunked form XLA:CPU takes, bit for bit."""
    import jax
    import jax.numpy as jnp

    from repro.core import rerank as rr
    from repro.core.worklist import INVALID_ID

    rng = np.random.default_rng(3)
    b, c, d, k = 8, 24, 32, 5                 # 24 KiB: safe in one callback
    data = rng.standard_normal((300, d)).astype(np.float32)
    q = jnp.asarray(rng.standard_normal((b, d)).astype(np.float32))
    ids_np = rng.permuted(np.tile(np.arange(300, dtype=np.int32), (b, 1)),
                          axis=1)[:, :c]
    ids_np[:, -3:] = int(INVALID_ID)
    ids = jnp.asarray(ids_np)

    def run(rows):
        def fn(q, ids):
            vecs = rr.gather_host_vectors(data, ids, chunk_rows=rows)
            return (vecs, *rr.exact_topk(q, vecs, ids, k))
        text = jax.jit(fn).lower(q, ids).as_text()
        return text.count("xla_ffi_python_cpu_callback"), jax.jit(fn)(q, ids)

    n_one, one = run(b * c)
    n_chunked, chunked = run(chunk_rows)
    assert (n_one, n_chunked) == (1, -(-b * c // chunk_rows))
    for x, y in zip(one, chunked):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    safe = np.where(ids_np == int(INVALID_ID), 0, ids_np)
    np.testing.assert_array_equal(np.asarray(one[0]), data[safe])
