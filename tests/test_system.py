"""End-to-end behaviour tests for the whole BANG system."""
import numpy as np
import pytest

from repro.core import BangIndex, SearchConfig, brute_force_knn, recall_at_k
from repro.data import gaussian_mixture, uniform_queries


def test_full_pipeline_three_stages(small_ann_index):
    """Build -> (table, search, rerank) -> correct top-k, with stats."""
    data, idx = small_ann_index
    queries = uniform_queries(data, 16, seed=11)
    gt = brute_force_knn(data, queries, 10)
    ids, dists, stats = idx.search(
        queries, 10, cfg=SearchConfig(t=64, bloom_z=8192), return_stats=True
    )
    ids = np.asarray(ids)
    assert ids.shape == (16, 10)
    assert recall_at_k(ids, gt) >= 0.9
    # distances ascending and consistent with ids
    d = np.asarray(dists)
    assert (np.diff(d, axis=1) >= -1e-5).all()
    assert stats.qps > 0 and stats.n_iters > 0


def test_compression_ratio_recall_tradeoff():
    """Paper Fig 9: recall stable until aggressive compression, then drops."""
    data = gaussian_mixture(1200, 32, n_clusters=16, seed=21)
    queries = uniform_queries(data, 16, seed=22)
    gt = brute_force_knn(data, queries, 10)
    from repro.core.vamana import build_vamana

    graph = build_vamana(data, R=20, L=32, alpha=1.2, seed=0)
    recalls = {}
    for m in (16, 2):
        idx = BangIndex.build(data, m=m, graph=graph)
        ids, _ = idx.search(queries, 10, cfg=SearchConfig(t=48, bloom_z=8192))
        recalls[m] = recall_at_k(np.asarray(ids), gt)
    assert recalls[16] >= 0.85
    assert recalls[16] >= recalls[2]  # over-compression can only hurt


def test_batch_independence(small_ann_index):
    """Queries are embarrassingly parallel: results don't depend on batch."""
    data, idx = small_ann_index
    queries = uniform_queries(data, 8, seed=13)
    cfg = SearchConfig(t=32, bloom_z=8192)
    full, _ = idx.search(queries, 5, cfg=cfg)
    solo, _ = idx.search(queries[3:4], 5, cfg=cfg)
    np.testing.assert_array_equal(np.asarray(full)[3], np.asarray(solo)[0])
