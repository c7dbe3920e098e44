"""The work count against a hand count, and the peaks table."""
import pytest

from bench.harness import work


def test_search_work_hand_count():
    # 2 queries, 5 expansions, one batch, R 4, m 2, d 8.
    got = work.search_work(5, 2, 1, R=4, m=2, d=8)
    per_exp_bytes = 4 * 4 + 4 * 2 + 8 * 4          # ids, codes, vector
    per_query_bytes = 2 * 256 * 4 + 8 * 4           # table, query
    assert got["bytes"] == 5 * per_exp_bytes + 2 * per_query_bytes + 256 * 8 * 4
    assert got["ops"] == 5 * (4 * 2 + 3 * 8) + 2 * 3 * 256 * 8


def test_least_time_names_its_bound():
    t = work.least_time({"bytes": 819e9, "ops": 1.0}, "TPU v5 lite")
    assert t["bound"] == "bytes"
    assert t["seconds"] == pytest.approx(1.0)
    t = work.least_time({"bytes": 1.0, "ops": 393e12}, "TPU v5 lite")
    assert t["bound"] == "ops"
    assert t["seconds"] == pytest.approx(1.0)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no peaks"):
        work.peaks("TPU v99")
