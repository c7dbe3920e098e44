"""Synthetic vector datasets: shapes, dtype and finiteness."""
import numpy as np

from repro.data import gaussian_mixture, uniform_queries


def test_vector_datasets():
    data = gaussian_mixture(500, 16, n_clusters=8, seed=0)
    assert data.shape == (500, 16) and data.dtype == np.float32
    q = uniform_queries(data, 10, seed=1)
    assert q.shape == (10, 16)
    # clustered: mean pairwise distance within much smaller than global std
    assert np.isfinite(data).all()
