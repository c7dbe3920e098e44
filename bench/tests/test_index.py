"""The index maker at small N: a graph every point can be reached in, and
recall@10 >= 0.9 through the program's own search."""
import numpy as np
import pytest

from bench.harness import index, reference

N, P = 16384, 2048
SPEC = index.CorpusSpec(n=N, d=96, queries=256, latent_dim=16, components=64,
                        center_scale=1.0, spread_min=0.5, spread_max=1.0,
                        noise=0.05)
GRAPH = index.GraphSpec(R=64, alpha=1.2, block=P, per_point=2, knn=32,
                        reverse_from=8, reverse_cap=32, kmeans_sample=8192,
                        kmeans_iters=10, chunk=4096, prune_chunk=512)


@pytest.fixture(scope="module")
def built():
    data, queries = index.make_corpus(index.seed_key(2**33 + 1, 0), SPEC)
    original = np.asarray(data)
    kept = {}
    adj = index.build_graph(data, index.seed_key(2**33 + 1, 1), GRAPH,
                            lambda d: kept.setdefault("data", np.asarray(d)))
    return (kept["data"], np.asarray(queries),
            np.asarray(adj).reshape(N, GRAPH.R), original)


def test_corpus_is_deterministic_in_the_seed():
    a, qa = index.make_corpus(index.seed_key(5, 0), SPEC)
    b, qb = index.make_corpus(index.seed_key(5, 0), SPEC)
    c, _ = index.make_corpus(index.seed_key(6, 0), SPEC)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(qa), np.asarray(qb))
    assert not np.array_equal(np.asarray(a), np.asarray(c))


def test_relabel_swaps_the_medoid_and_point_0_only(built):
    data, _, _, original = built
    moved = np.nonzero(np.any(data != original, 1))[0]
    assert len(moved) == 2 and moved[0] == 0
    np.testing.assert_array_equal(data[0], original[moved[1]])
    np.testing.assert_array_equal(data[moved[1]], original[0])


def test_graph_rows_are_well_formed(built):
    _, _, adj, _ = built
    rows = np.arange(N)[:, None]
    assert adj.min() >= -1 and adj.max() < N
    assert not np.any(adj == rows)
    for row in adj[:2000]:
        ids = row[row >= 0]
        assert len(set(ids.tolist())) == len(ids)
        # ids first, -1 padding last
        assert np.all(row[len(ids):] == -1)
    assert (adj >= 0).sum(1).min() >= 1


def test_every_point_is_reachable_from_the_medoid(built):
    _, _, adj, _ = built
    seen = np.zeros(N, bool)
    seen[0] = True
    front = np.array([0])
    while front.size:
        nb = adj[front].ravel()
        nb = np.unique(nb[nb >= 0])
        nb = nb[~seen[nb]]
        seen[nb] = True
        front = nb
    assert seen.all()


def test_entry_point_0_is_the_medoid(built):
    data, _, _, _ = built
    d = ((data - data.mean(0)) ** 2).sum(1)
    assert d[0] == pytest.approx(d.min(), rel=1e-5)


def test_recall_through_the_program(built):
    from repro.core import BangIndex
    from repro.core.vamana import VamanaGraph

    data, queries, adj, _ = built
    idx = BangIndex.build(data, m=32, R=64,
                          graph=VamanaGraph(adjacency=adj, medoid=0))
    ids, _ = idx.executor("inmem").search(queries, 10, t=32)
    truth, _ = reference.exact_knn(data, queries, 10)
    assert reference.recall_at_k(np.asarray(ids), truth).mean() >= 0.9
