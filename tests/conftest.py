"""Test fixtures. NOTE: never set xla_force_host_platform_device_count here --
smoke tests must see exactly 1 device; multi-device tests spawn subprocesses.
"""
import numpy as np
import pytest


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


@pytest.fixture(scope="session")
def small_ann_index():
    """A shared small BangIndex (build is the slow part).

    Sized for suite speed: 1200 points / R=16 / L_build=24 / 6 kmeans iters
    still clears every recall floor in test_search/test_recall_regression
    (verified with margin) at roughly half the build cost of the old fixture.
    """
    from repro.core import BangIndex
    from repro.data import gaussian_mixture

    data = gaussian_mixture(1200, 32, n_clusters=24, seed=3)
    idx = BangIndex.build(data, m=8, R=16, L_build=24, kmeans_iters=6)
    return data, idx
