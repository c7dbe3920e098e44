"""SearchExecutor: compile-cache, shape bucketing, async dispatch, timing."""
import numpy as np
import pytest

from repro.core import SearchConfig
from repro.data import uniform_queries
from repro.runtime import SearchExecutor, ServePipeline, bucket_size, pad_batch


@pytest.fixture(scope="module")
def executor(small_ann_index):
    _, idx = small_ann_index
    return idx.executor("inmem")


def test_bucket_size_powers_of_two():
    assert bucket_size(1) == 8          # min bucket
    assert bucket_size(8) == 8
    assert bucket_size(9) == 16
    assert bucket_size(65, min_bucket=8) == 128
    assert bucket_size(3, min_bucket=1) == 4
    with pytest.raises(ValueError):
        bucket_size(0)


@pytest.mark.parametrize("bad", [0, -8, 3, 12, 1000])
def test_min_bucket_must_be_positive_pow2(bad, small_ann_index):
    """Regression: a non-power-of-two min_bucket would silently corrupt the
    bucket lattice (compile-cache keys and pad_batch disagree); both the
    free function and the executor constructors reject it up front."""
    _, idx = small_ann_index
    with pytest.raises(ValueError, match="power of two"):
        bucket_size(4, min_bucket=bad)
    with pytest.raises(ValueError, match="power of two"):
        SearchExecutor.from_index(idx, variant="inmem", min_bucket=bad)


def test_min_bucket_pow2_accepted(small_ann_index):
    _, idx = small_ann_index
    ex = SearchExecutor.from_index(idx, variant="inmem", min_bucket=16)
    assert ex._bucket_for(3) == 16


def test_pad_batch_replicates_last_row(rng):
    q = rng.standard_normal((5, 8)).astype(np.float32)
    p = pad_batch(q, 8)
    assert p.shape == (8, 8)
    np.testing.assert_array_equal(p[:5], q)
    np.testing.assert_array_equal(p[5:], np.repeat(q[-1:], 3, 0))
    assert pad_batch(q, 5) is q


def test_same_bucket_searches_trace_exactly_once(small_ann_index):
    """Two searches in the same (bucket, t, k, variant) -> one trace."""
    data, idx = small_ann_index
    ex = SearchExecutor.from_index(idx, variant="inmem")
    cfg = SearchConfig(t=32, bloom_z=8192)
    q1 = uniform_queries(data, 12, seed=41)   # bucket 16
    q2 = uniform_queries(data, 15, seed=42)   # bucket 16, different batch size
    assert ex.n_traces == 0
    _, _, s1 = ex.search(q1, 5, cfg=cfg, return_stats=True)
    assert ex.n_traces == 1 and s1.compile_s > 0.0
    _, _, s2 = ex.search(q2, 5, cfg=cfg, return_stats=True)
    assert ex.n_traces == 1, "same-bucket search retraced"
    assert s2.compile_s == 0.0
    assert ex.cache_size == 1
    # a different bucket or different t compiles a new executable
    ex.search(uniform_queries(data, 20, seed=43), 5, cfg=cfg)  # bucket 32
    assert ex.n_traces == 2
    ex.search(q1, 5, cfg=SearchConfig(t=48, bloom_z=8192))
    assert ex.n_traces == 3


def test_padded_batch_matches_unpadded(small_ann_index):
    """Bucket padding must not change any real lane's ids/dists."""
    data, idx = small_ann_index
    cfg = SearchConfig(t=32, bloom_z=8192)
    ex = idx.executor("inmem")
    queries = uniform_queries(data, 16, seed=44)     # exactly bucket 16
    full_ids, full_dists = ex.search(queries, 5, cfg=cfg)
    pad_ids, pad_dists = ex.search(queries[:11], 5, cfg=cfg)  # padded 11 -> 16
    np.testing.assert_array_equal(np.asarray(pad_ids), np.asarray(full_ids)[:11])
    np.testing.assert_array_equal(np.asarray(pad_dists), np.asarray(full_dists)[:11])


def test_executor_matches_index_search(small_ann_index):
    """The index's public search() is exactly the executor's answer."""
    data, idx = small_ann_index
    cfg = SearchConfig(t=32, bloom_z=8192)
    q = uniform_queries(data, 9, seed=45)
    a, _ = idx.search(q, 5, cfg=cfg)
    b, _ = idx.executor("inmem").search(q, 5, cfg=cfg)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_dispatch_finish_roundtrip(small_ann_index):
    """Async dispatch returns immediately; finish blocks both outputs."""
    data, idx = small_ann_index
    ex = idx.executor("inmem")
    cfg = SearchConfig(t=32, bloom_z=8192)
    q = uniform_queries(data, 8, seed=46)
    h = ex.dispatch(q, 5, cfg=cfg)
    assert h.batch == 8 and h.bucket == 8
    ids, dists, stats = ex.finish(h, return_stats=True)
    assert np.asarray(ids).shape == (8, 5)
    assert np.asarray(dists).shape == (8, 5)
    assert stats.wall_s > 0 and stats.qps > 0
    sync_ids, _ = ex.search(q, 5, cfg=cfg)
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(sync_ids))


def test_stats_separate_compile_from_steady_state(small_ann_index):
    data, idx = small_ann_index
    ex = SearchExecutor.from_index(idx, variant="inmem")
    cfg = SearchConfig(t=24, bloom_z=8192)
    q = uniform_queries(data, 8, seed=47)
    _, _, cold = ex.search(q, 5, cfg=cfg, return_stats=True)
    _, _, warm = ex.search(q, 5, cfg=cfg, return_stats=True)
    assert cold.compile_s > 0.0 and warm.compile_s == 0.0
    # wall_s is dispatch->ready only: the cold call's wall must not include
    # its multi-second trace+compile.
    assert cold.wall_s < cold.compile_s + 1.0
    assert warm.batch == 8 and warm.bucket == 8


def test_exact_variant_requires_device_data(small_ann_index):
    data, idx = small_ann_index
    with pytest.raises(ValueError):
        SearchExecutor(idx.codec, idx.codes, idx.graph, variant="exact")
    with pytest.raises(ValueError):
        SearchExecutor.from_index(idx, variant="nope")


def test_serve_pipeline_matches_direct_search(small_ann_index):
    """Micro-batched, double-buffered serving == one-shot batched search."""
    data, idx = small_ann_index
    cfg = SearchConfig(t=32, bloom_z=8192)
    queries = uniform_queries(data, 40, seed=48)
    direct_ids, direct_dists = idx.search(queries, 5, cfg=cfg)
    pipe = ServePipeline(idx.executor("inmem"), k=5, cfg=cfg, max_batch=16)
    pipe.submit(queries[:25])
    pipe.submit(queries[25:])
    assert pipe.pending() == 40
    ids, dists, stats = pipe.drain()
    assert pipe.pending() == 0
    np.testing.assert_array_equal(ids, np.asarray(direct_ids))
    np.testing.assert_array_equal(dists, np.asarray(direct_dists))
    assert stats.batches == 3 and stats.queries == 40       # 16+16+8
    assert stats.qps > 0 and stats.p95_ms >= stats.p50_ms > 0


def test_serve_pipeline_reports_recall(small_ann_index):
    from repro.core import brute_force_knn

    data, idx = small_ann_index
    queries = uniform_queries(data, 16, seed=49)
    gt = brute_force_knn(data, queries, 5)
    pipe = ServePipeline(
        idx.executor("inmem"), k=5, cfg=SearchConfig(t=48, bloom_z=8192),
        max_batch=8,
    )
    pipe.submit(queries, gt_ids=gt)
    reports = []
    _, _, stats = pipe.drain(on_batch=reports.append)
    assert stats.mean_recall is not None and stats.mean_recall >= 0.8
    assert [r.index for r in reports] == [0, 1]
    assert all(r.recall is not None for r in reports)


def test_serve_pipeline_recall_with_mixed_and_wide_gt(small_ann_index):
    """Micro-batches mixing gt/non-gt rows still score the gt rows, and
    ground truth wider than k must not deflate the reported recall."""
    from repro.core import brute_force_knn

    data, idx = small_ann_index
    queries = uniform_queries(data, 12, seed=50)
    wide_gt = brute_force_knn(data, queries, 20)       # wider than k=5
    pipe = ServePipeline(
        idx.executor("inmem"), k=5, cfg=SearchConfig(t=48, bloom_z=8192),
        max_batch=16,                                   # one mixed micro-batch
    )
    pipe.submit(queries[:8], gt_ids=wide_gt[:8])
    pipe.submit(queries[8:])                            # no ground truth
    _, _, stats = pipe.drain()
    assert stats.batches == 1
    assert stats.mean_recall is not None and stats.mean_recall >= 0.8
    # Ragged gt widths in ONE micro-batch (separate submits) must not crash:
    # rows are truncated to the narrowest width before scoring.
    pipe.submit(queries[:6], gt_ids=wide_gt[:6])        # width 20
    pipe.submit(queries[6:], gt_ids=wide_gt[6:, :8])    # width 8
    _, _, stats = pipe.drain()
    assert stats.batches == 1
    assert stats.mean_recall is not None and stats.mean_recall >= 0.8


def test_mean_recall_is_row_weighted(small_ann_index):
    """ServeStats.mean_recall must equal the flat per-row recall: a 1-row
    tail micro-batch may not weigh the same as a full batch (regression)."""
    from repro.core import brute_force_knn, recall_at_k

    data, idx = small_ann_index
    queries = uniform_queries(data, 9, seed=51)
    gt = brute_force_knn(data, queries, 5)
    pipe = ServePipeline(
        idx.executor("inmem"), k=5, cfg=SearchConfig(t=48, bloom_z=8192),
        max_batch=8,                                    # batches of 8 and 1
    )
    pipe.submit(queries, gt_ids=gt)
    ids, _, stats = pipe.drain()
    assert stats.batches == 2
    flat = recall_at_k(ids, np.asarray(gt))
    assert stats.mean_recall == pytest.approx(flat)


class _FlakyExecutor:
    """Wraps a real executor; dispatch raises after `ok_dispatches` calls."""

    def __init__(self, ex, ok_dispatches: int):
        self._ex = ex
        self._ok = ok_dispatches
        self.calls = 0

    def dispatch(self, *a, **kw):
        self.calls += 1
        if self.calls > self._ok:
            raise RuntimeError("injected dispatch failure")
        return self._ex.dispatch(*a, **kw)

    def finish(self, *a, **kw):
        return self._ex.finish(*a, **kw)


def test_drain_requeues_queries_on_dispatch_error(small_ann_index):
    """drain() must not lose queries when a dispatch fails mid-loop: the
    un-dispatched misses AND the rows of discarded in-flight batches are
    re-enqueued, and a retry serves everything (regression)."""
    data, idx = small_ann_index
    cfg = SearchConfig(t=32, bloom_z=8192)
    queries = uniform_queries(data, 40, seed=52)
    direct_ids, direct_dists = idx.search(queries, 5, cfg=cfg)

    flaky = _FlakyExecutor(idx.executor("inmem"), ok_dispatches=1)
    pipe = ServePipeline(flaky, k=5, cfg=cfg, max_batch=16)
    pipe.submit(queries)
    with pytest.raises(RuntimeError, match="injected"):
        pipe.drain()
    # Batch 1 (16 rows) was dispatched but its results were never recorded,
    # batch 2's dispatch raised before launch, batch 3 was never popped:
    # every row must be back in the queue.
    assert pipe.pending() == 40
    flaky._ok = 10**9                          # heal the executor
    ids, dists, stats = pipe.drain()
    assert pipe.pending() == 0
    np.testing.assert_array_equal(ids, np.asarray(direct_ids))
    np.testing.assert_array_equal(dists, np.asarray(direct_dists))
    assert stats.queries == 40


@pytest.mark.parametrize("variant", ["inmem", "exact"])
def test_packed_index_state_answers_as_plain_rows_and_base(small_ann_index,
                                                           variant):
    """The in-memory executors hold adjacency and vectors as `PackedRows`
    and answer bit for bit as the plain (n, w) rows they held before, and
    (inmem) as base; a second executor on the index shares that state and
    answers the same."""
    import jax.numpy as jnp

    from repro.core.rows import PackedRows

    data, idx = small_ann_index
    q = uniform_queries(data, 20, seed=81)
    cfg = SearchConfig(t=32, bloom_z=8192)

    def serve(ex):
        with ServePipeline(ex, k=10, cfg=cfg, max_batch=8) as pipe:
            pipe.submit(q)
            ids, dists, _ = pipe.drain()
        return np.asarray(ids), np.asarray(dists)

    ex = SearchExecutor.from_index(idx, variant=variant)
    assert isinstance(ex.adjacency_dev, PackedRows)
    assert ex._data_dev is idx.data_dev            # packed once, by the index
    got = serve(ex)

    plain = SearchExecutor.from_index(idx, variant=variant)
    plain._adjacency = jnp.asarray(idx.graph.adjacency)
    plain._data_dev = jnp.asarray(idx.data_np)
    for a, b in zip(got, serve(plain)):
        np.testing.assert_array_equal(a, b)
    if variant == "inmem":
        for a, b in zip(got, serve(idx.executor("base"))):
            np.testing.assert_array_equal(a, b)

    other = SearchExecutor.from_index(idx, variant=variant,
                                      adjacency_dev=ex.adjacency_dev)
    assert other.adjacency_dev is ex.adjacency_dev
    assert other._data_dev is ex._data_dev
    for a, b in zip(got, serve(other)):
        np.testing.assert_array_equal(a, b)
    for a, b in zip(got, serve(ex)):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("n,width", [(10, 64), (7, 96), (9, 3), (33, 17),
                                     (16, 128), (5, 200)])
def test_packed_rows_give_the_rows(n, width):
    """Both gather forms of `PackedRows` return the rows, from a host or a
    device array; lines are whole multiples of 128 lanes."""
    import jax.numpy as jnp

    from repro.core import rows

    x = np.random.default_rng(n).standard_normal((n, width)).astype(np.float32)
    ids = jnp.asarray(np.random.default_rng(1).integers(0, n, (4, 6)),
                      jnp.int32)
    for src in (x, jnp.asarray(x)):
        packed = rows.pack(src)
        assert packed.shape == (n, width)
        assert packed.lines.shape[1] % rows.LANES == 0
        assert rows.rows_per_line(width) in (1, 2, 4, 8)
        for take in (rows.take_lines, rows.take_slots):
            np.testing.assert_array_equal(
                np.asarray(take(packed.lines, ids, width)),
                x[np.asarray(ids)])
        np.testing.assert_array_equal(np.asarray(packed[ids]),
                                      x[np.asarray(ids)])
    assert rows.pack(packed) is packed
