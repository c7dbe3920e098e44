"""The load generator: determinism, and the same work for every seed."""
import numpy as np
import pytest

from bench.harness import load


def test_same_seed_same_order():
    a = load.pool_order({"order": "cycle"}, 2**33 + 5, 1000, 2500)
    b = load.pool_order({"order": "cycle"}, 2**33 + 5, 1000, 2500)
    np.testing.assert_array_equal(a, b)


def test_seeds_reorder_the_same_work():
    a = load.pool_order({"order": "cycle"}, 1, 1000, 3000)
    b = load.pool_order({"order": "cycle"}, 2, 1000, 3000)
    assert not np.array_equal(a, b)
    np.testing.assert_array_equal(np.sort(a), np.sort(b))


def test_cycle_order_is_a_repeated_permutation():
    idx = load.pool_order({"order": "cycle"}, 9, 100, 250)
    assert sorted(idx[:100]) == list(range(100))
    np.testing.assert_array_equal(idx[:100], idx[100:200])


def test_an_unknown_order_is_refused():
    with pytest.raises(ValueError):
        load.pool_order({"order": "zipf"}, 9, 100, 250)
