"""The plain reference against numpy at small N."""
import numpy as np
import pytest

from bench.harness import reference


@pytest.fixture(scope="module")
def corpus():
    r = np.random.default_rng(0)
    return (r.normal(size=(3000, 32)).astype(np.float32),
            r.normal(size=(70, 32)).astype(np.float32))


def test_exact_knn_matches_numpy(corpus):
    data, q = corpus
    ids, d2 = reference.exact_knn(data, q, 10, block=32, chunk=512)
    full = ((q[:, None, :].astype(np.float64) - data[None]) ** 2).sum(-1)
    want = np.argsort(full, 1)[:, :10]
    np.testing.assert_array_equal(ids, want)
    np.testing.assert_allclose(d2, np.take_along_axis(full, want, 1),
                               rtol=1e-4)


def test_exact_sq_dists_and_padding(corpus):
    data, q = corpus
    ids = np.array([[0, 5, -1], [7, -1, 2]])
    got = reference.exact_sq_dists(data, q[:2], ids)
    assert np.isinf(got[0, 2]) and np.isinf(got[1, 1])
    assert got[1, 2] == pytest.approx(((data[2] - q[1].astype(np.float64)) ** 2).sum())


def test_recall_at_k():
    found = np.array([[1, 2, 3], [4, 5, 6]])
    truth = np.array([[3, 2, 9], [7, 8, 9]])
    np.testing.assert_allclose(reference.recall_at_k(found, truth), [2 / 3, 0])


def test_intrinsic_dim_of_a_known_cloud():
    r = np.random.default_rng(1)
    lift = r.normal(size=(4, 32))
    data = r.normal(size=(20000, 4)) @ lift
    q = r.normal(size=(50, 4)) @ lift
    _, d2 = reference.exact_knn(data.astype(np.float32), q.astype(np.float32),
                                100, block=50, chunk=4096)
    assert 3.0 < reference.local_intrinsic_dim(d2) < 5.5
