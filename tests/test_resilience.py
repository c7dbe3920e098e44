"""Resilience subsystem (repro.runtime.resilience + its hostio/serving hooks).

Three layers under test:

  * **Service-level fault matrix** against a plain numpy `NeighborService`
    (no jax): deterministic injection, retry/backoff bit-exactness, degraded
    medoid/mask substitution, health transitions (auto-unhealthy, explicit
    failover, recovery), worker crashes that lose zero requests, stalled
    pools hedged inline, queue overflow falling back to inline gathers, and
    the stop()-poisons-pending-tickets contract.
  * **Admission control** in `ServePipeline` against a stub executor:
    submit-time validation, bounded-queue shedding (at most once, counted
    exactly), and per-request deadlines dropped at dispatch.
  * **End-to-end acceptance** on the shared fixture index: under a scripted
    fault schedule (the only host partition down + every worker stalled) the
    pipeline keeps answering with degraded-mode recall >= 0.8, never blows
    its request deadline, and after failover + recovery returns bit-exact
    ids AND dists vs the fault-free run.

Determinism: every injector here is seeded and window-scripted, so counter
assertions are exact, not thresholds.
"""
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # pragma: no cover - fallback shim keeps suite collectable
    from _hypothesis_compat import given, settings, strategies as st

from repro.core import SearchConfig, brute_force_knn
from repro.data import uniform_queries
from repro.runtime import ServePipeline
from repro.runtime.hostio import HostIOConfig, NeighborService
from repro.runtime.resilience import (
    FOREVER,
    FaultInjector,
    FaultSpec,
    InjectedWorkerCrash,
    PartitionDownError,
    ResilienceConfig,
    TransientGatherError,
    backoff_delay,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

N_LOC, R = 64, 6


def _parts(n_parts=2, seed=5):
    rng = np.random.default_rng(seed)
    return [
        rng.integers(0, 2 * N_LOC, (N_LOC, R)).astype(np.int32)
        for _ in range(n_parts)
    ]


def _request(svc, shard=0, B=48, seed=11):
    """One pooled request of B lanes, ~3/4 owned; returns (got, expected)."""
    rng = np.random.default_rng(seed)
    rel = rng.integers(0, N_LOC, B).astype(np.int32)
    own = rng.random(B) < 0.75
    got = svc.request(shard, rel, own, np.zeros(B, bool))
    exp = np.zeros((B, R), np.int32)
    exp[own] = svc._parts[shard][rel[own]] + 1
    return got, exp


# ------------------------------------------------------------- spec/config
def test_fault_spec_and_config_validation():
    with pytest.raises(ValueError):
        FaultSpec("meteor_strike")
    with pytest.raises(ValueError):
        FaultSpec("worker_stall", count=-1)
    with pytest.raises(ValueError):
        FaultSpec("worker_stall", probability=1.5)
    with pytest.raises(ValueError):
        FaultSpec("worker_stall", stall_s=-0.1)
    with pytest.raises(ValueError):
        ResilienceConfig(deadline_s=-1.0)
    with pytest.raises(ValueError):
        ResilienceConfig(max_retries=-1)
    with pytest.raises(ValueError):
        ResilienceConfig(unhealthy_after=0)
    with pytest.raises(ValueError):
        ResilienceConfig(degraded_mode="panic")
    with pytest.raises(TypeError):
        HostIOConfig(resilience="yes please")
    # Backoff doubles, caps at backoff_max_s, and never exceeds the deadline.
    cfg = ResilienceConfig(backoff_base_s=0.01, backoff_max_s=0.03)
    assert backoff_delay(cfg, 0, -1.0) == pytest.approx(0.01)
    assert backoff_delay(cfg, 1, -1.0) == pytest.approx(0.02)
    assert backoff_delay(cfg, 5, -1.0) == pytest.approx(0.03)
    assert backoff_delay(cfg, 5, 0.004) == pytest.approx(0.004)
    assert backoff_delay(cfg, 0, 0.0) == 0.0


def test_injector_window_and_determinism():
    # Window [2, 5): exactly ordinals 2, 3, 4 of shard 0's gather counter.
    def drive():
        inj = FaultInjector(
            [FaultSpec("transient_error", shard=0, start=2, count=3),
             FaultSpec("transient_error", shard=1, probability=0.5,
                       count=FOREVER)],
            seed=9,
        )
        pattern = []
        for shard in (0, 1):
            for _ in range(20):
                try:
                    inj.on_gather(shard)
                    pattern.append(0)
                except TransientGatherError:
                    pattern.append(1)
        return pattern, inj.injected()

    p1, c1 = drive()
    p2, c2 = drive()
    assert p1 == p2 and c1 == c2            # seeded => replayable exactly
    assert p1[:20] == [0, 0, 1, 1, 1] + [0] * 15
    # The probabilistic spec fired some but not all of shard 1's window.
    assert 0 < sum(p1[20:]) < 20
    assert c1["transient_error"] == sum(p1)


# --------------------------------------------------- retry / degrade paths
def test_transient_errors_retry_to_bit_exact():
    svc = NeighborService(
        _parts(), workers=1,
        resilience=ResilienceConfig(max_retries=3, backoff_base_s=1e-4),
        injector=FaultInjector(
            [FaultSpec("transient_error", shard=0, count=2)]
        ),
    )
    try:
        got, exp = _request(svc, shard=0)
        np.testing.assert_array_equal(got, exp)
        s = svc.stats()
        assert s["retries"] >= 1 and s["gather_failures"] == 2
        assert s["degraded_lanes"] == 0
    finally:
        svc.stop()


def test_exhausted_retries_degrade_to_medoid_row():
    parts = _parts()
    medoid = N_LOC + 7          # global id living in partition 1
    svc = NeighborService(
        parts, workers=1, medoid=medoid,
        resilience=ResilienceConfig(
            max_retries=1, backoff_base_s=1e-4,
            unhealthy_after=10_000, degraded_mode="medoid",
        ),
        injector=FaultInjector(
            [FaultSpec("transient_error", shard=0, count=FOREVER)]
        ),
    )
    try:
        got, exp = _request(svc, shard=0)
        lanes = np.nonzero((exp != 0).any(axis=1))[0]
        np.testing.assert_array_equal(
            got[lanes], np.broadcast_to(parts[1][7] + 1, (lanes.size, R))
        )
        assert svc.stats()["degraded_lanes"] == lanes.size
    finally:
        svc.stop()


def test_partition_down_mask_mode_yields_zero_contributions():
    svc = NeighborService(
        _parts(), workers=1,
        resilience=ResilienceConfig(
            max_retries=0, degraded_mode="mask", unhealthy_after=10_000
        ),
    )
    try:
        svc.mark_partition_down(0)
        assert svc.partition_state(0) == "down"
        got, exp = _request(svc, shard=0)
        # Mask mode: degraded lanes contribute 0 -- after the caller's -1
        # shift they surface as all -1 rows, the tombstone-padding encoding.
        assert (got == 0).all()
        s = svc.stats()
        assert s["degraded_lanes"] == (exp != 0).any(axis=1).sum()
        assert s["partitions_down"] == 1
        # The healthy partition is untouched by partition 0's outage.
        got1, exp1 = _request(svc, shard=1, seed=12)
        np.testing.assert_array_equal(got1, exp1)
    finally:
        svc.stop()


def test_failure_streak_marks_unhealthy_and_auto_fails_over():
    svc = NeighborService(
        _parts(), workers=1,
        resilience=ResilienceConfig(
            max_retries=4, backoff_base_s=1e-4,
            unhealthy_after=2, auto_failover=True,
        ),
        injector=FaultInjector(
            [FaultSpec("transient_error", shard=0, count=FOREVER)]
        ),
    )
    try:
        # Attempts 1+2 fail -> streak hits unhealthy_after -> the partition
        # flips to failover mid-retry-loop and attempt 3 reads the replica.
        got, exp = _request(svc, shard=0)
        np.testing.assert_array_equal(got, exp)
        assert svc.partition_state(0) == "failover"
        s = svc.stats()
        assert s["failovers"] == 1 and s["failover_gathers"] >= 1
        assert s["degraded_lanes"] == 0
    finally:
        svc.stop()


def test_explicit_failover_then_recovery_bit_exact():
    svc = NeighborService(_parts(), workers=2)
    try:
        baseline, exp = _request(svc, shard=1, seed=13)
        np.testing.assert_array_equal(baseline, exp)
        svc.fail_over(1)
        assert svc.partition_state(1) == "failover"
        got, _ = _request(svc, shard=1, seed=13)
        np.testing.assert_array_equal(got, baseline)   # replica == primary
        assert svc.stats()["failover_gathers"] >= 1
        svc.recover(1)
        assert svc.partition_state(1) == "up"
        got, _ = _request(svc, shard=1, seed=13)
        np.testing.assert_array_equal(got, baseline)
        assert svc.stats()["recoveries"] == 1
    finally:
        svc.stop()


# --------------------------------------------------- pool fault tolerance
def test_worker_crash_loses_no_request():
    svc = NeighborService(
        _parts(), workers=2,
        injector=FaultInjector([FaultSpec("worker_crash", shard=0, count=1)]),
    )
    try:
        got, exp = _request(svc, shard=0, B=64)
        np.testing.assert_array_equal(got, exp)        # pool mate finished it
        assert svc.stats()["worker_deaths"] == 1
        # Traffic keeps flowing through the surviving worker.
        got, exp = _request(svc, shard=0, B=64, seed=21)
        np.testing.assert_array_equal(got, exp)
    finally:
        svc.stop()


def test_stalled_pool_hedges_inline():
    svc = NeighborService(
        _parts(), workers=2,
        resilience=ResilienceConfig(hedge_s=0.03),
        injector=FaultInjector(
            [FaultSpec("worker_stall", stall_s=0.4, count=FOREVER)]
        ),
    )
    try:
        t0 = time.perf_counter()
        got, exp = _request(svc, shard=0, B=64)
        wall = time.perf_counter() - t0
        np.testing.assert_array_equal(got, exp)        # hedge is bit-exact
        assert wall < 0.4, f"hedge did not cut the stall: {wall:.3f}s"
        assert svc.stats()["hedged_gathers"] >= 1
    finally:
        svc.stop()


def test_queue_overflow_falls_back_inline():
    svc = NeighborService(
        _parts(), workers=2,
        injector=FaultInjector(
            [FaultSpec("queue_overflow", count=FOREVER)]
        ),
    )
    try:
        got, exp = _request(svc, shard=0, B=64)
        np.testing.assert_array_equal(got, exp)        # shed queueing, not work
        assert svc.stats()["enqueue_rejections"] >= 1
    finally:
        svc.stop()


# ------------------------------------------------ stop() drains & poisons
def test_stop_poisons_pending_tickets_and_is_idempotent():
    parts = _parts(1)
    svc = NeighborService(parts, workers=1)
    svc.start()
    release = threading.Event()
    assert svc._enqueue(0, release.wait)     # wedge the only worker
    rel = np.arange(8, dtype=np.int32)
    own = np.ones(8, bool)
    seq = svc.issue(0, rel, own)             # queued behind the wedge
    stopper = threading.Thread(target=svc.stop)
    stopper.start()
    try:
        # stop() poisons the ticket before joining the (wedged) pool, so
        # collect must return promptly via the inline-miss path, bit-exact.
        t0 = time.perf_counter()
        got = svc.collect(0, rel, own, np.zeros(8, bool), seq)
        assert time.perf_counter() - t0 < 2.0
        np.testing.assert_array_equal(got, parts[0][rel] + 1)
        assert svc.stats()["prefetch_misses"] == 1
    finally:
        release.set()
        stopper.join(timeout=10.0)
    assert not stopper.is_alive() and not svc.started
    svc.stop()                               # second stop: no-op, no raise
    assert not svc.started
    # start() after stop() revives the pools for fresh traffic.
    got, exp = _request(svc.start(), shard=0, B=16)
    np.testing.assert_array_equal(got, exp)
    svc.stop()


# ---------------------------------------------------- admission control
class _StubExecutor:
    """Minimal dispatch/finish contract: echoes row index as the top id."""

    class _H:
        def __init__(self, ids, dists):
            self.ids, self.dists = ids, dists
            self.compile_s = 0.0

    def __init__(self, d=8, k=4):
        self._d = d

    @property
    def query_dim(self):
        return self._d

    def dispatch(self, queries, k, cfg=None, rerank=True):
        q = np.asarray(queries)
        ids = np.tile(np.arange(k, dtype=np.int32), (q.shape[0], 1))
        return self._H(ids, np.zeros((q.shape[0], k), np.float32))

    def finish(self, h):
        return h.ids, h.dists


def test_submit_validates_shape_dtype_and_content():
    pipe = ServePipeline(_StubExecutor(d=8), k=3, max_batch=4)
    ok = np.zeros((2, 8), np.float32)
    with pytest.raises(ValueError):
        pipe.submit(np.zeros((2, 2, 2), np.float32))        # ndim
    with pytest.raises(TypeError):
        pipe.submit(np.array([["a"] * 8], dtype=object))    # dtype
    with pytest.raises(TypeError):
        pipe.submit(np.zeros((1, 8), np.complex64))
    with pytest.raises(ValueError):
        pipe.submit(np.full((1, 8), np.nan, np.float32))    # content
    with pytest.raises(ValueError):
        pipe.submit(np.zeros((2, 7), np.float32))           # executor width
    with pytest.raises(ValueError):
        pipe.submit(ok, gt_ids=np.zeros((3, 5), np.int32))  # gt row count
    with pytest.raises(ValueError):
        pipe.submit(ok, gt_ids=np.zeros((2, 5, 1), np.int32))
    with pytest.raises(TypeError):
        pipe.submit(ok, gt_ids=np.zeros((2, 5), np.float32))
    with pytest.raises(ValueError):
        pipe.submit(ok, deadline_s=-0.5)
    assert pipe.pending() == 0          # every rejection left nothing behind
    # Accepted spellings: 1-D row, 1-D gt for a single query, integer dtype,
    # non-contiguous strides -- all normalised to contiguous float32.
    assert pipe.submit(np.zeros(8, np.float32),
                       gt_ids=np.arange(3)) == 1
    assert pipe.submit(np.zeros((2, 8), np.int64)) == 2
    strided = np.zeros((2, 16), np.float64)[:, ::2]
    assert not strided.flags.c_contiguous
    assert pipe.submit(strided) == 2
    ids, dists, stats = pipe.drain()
    assert ids.shape == (5, 3) and (ids >= 0).all()
    assert stats.queries == 5 and stats.shed_queries == 0


def test_bounded_queue_sheds_at_submit_and_counts_once():
    pipe = ServePipeline(_StubExecutor(d=4), k=2, max_batch=8, max_queue=4)
    q = np.zeros((3, 4), np.float32)
    assert pipe.submit(q) == 3
    assert pipe.submit(q) == 1                  # only 1 seat left
    assert pipe.submit(q) == 0                  # full: everything sheds
    assert pipe.pending() == 4
    ids, _, stats = pipe.drain()
    assert stats.queries == 4 and stats.shed_queries == 5
    assert (ids >= 0).all()                     # every accepted row served
    # The shed counter reports once: the next window starts from zero.
    pipe.submit(q)
    _, _, stats = pipe.drain()
    assert stats.shed_queries == 0 and stats.queries == 3


def test_deadlines_drop_expired_rows_at_dispatch():
    pipe = ServePipeline(_StubExecutor(d=4), k=2, max_batch=8)
    live = np.ones((3, 4), np.float32)
    doomed = np.full((2, 4), 2.0, np.float32)
    assert pipe.submit(live, deadline_s=30.0) == 3
    assert pipe.submit(doomed, deadline_s=1e-4) == 2
    time.sleep(0.01)                            # let the tight deadline pass
    ids, dists, stats = pipe.drain()
    assert stats.expired_queries == 2 and stats.queries == 5
    assert (ids[:3] >= 0).all()                 # live rows answered
    assert (ids[3:] == -1).all() and np.isinf(dists[3:]).all()
    # Expired rows are excluded from the served-QPS numerator.
    assert stats.qps * max(stats.wall_s - stats.compile_s, 1e-9) == (
        pytest.approx(3.0, abs=1e-6)
    )


@settings(max_examples=20, deadline=None)
@given(
    batches=st.lists(st.integers(0, 7), min_size=1, max_size=10),
    max_queue=st.integers(1, 9),
)
def test_shedding_is_at_most_once_property(batches, max_queue):
    """Offered = served + shed, exactly: nothing lost, nothing double-shed."""
    pipe = ServePipeline(
        _StubExecutor(d=4), k=2, max_batch=3, max_queue=max_queue
    )
    offered = sum(batches)
    accepted = sum(
        pipe.submit(np.full((b, 4), i, np.float32))
        for i, b in enumerate(batches)
    )
    assert pipe.pending() == accepted <= max_queue
    ids, _, stats = pipe.drain()
    assert stats.queries == accepted
    assert stats.shed_queries == offered - accepted
    assert ids.shape[0] == accepted and (ids >= 0).all()
    assert stats.expired_queries == 0


# ----------------------------------------------- end-to-end fault matrix
RES_CFG = HostIOConfig(
    workers=2, hot_cache_rows=64, prefetch=True,
    resilience=ResilienceConfig(
        deadline_s=0.5, hedge_s=0.1, max_retries=3, backoff_base_s=1e-4,
        unhealthy_after=1_000_000, auto_failover=False,
    ),
)


@pytest.fixture(scope="module")
def resilient_setup(small_ann_index):
    data, idx = small_ann_index
    return data, idx, idx.executor("base", hostio=RES_CFG)


def test_fault_matrix_mid_stream_bit_exact(resilient_setup):
    """Injected faults mid-drain lose zero queries and stay bit-exact."""
    data, idx, ex = resilient_setup
    svc = ex.hostio_service
    cfg = SearchConfig(t=32, bloom_z=8192)
    q = uniform_queries(data, 16, seed=91)
    ids_0, d_0 = ex.search(q, 5, cfg=cfg)
    ids_0, d_0 = np.asarray(ids_0), np.asarray(d_0)
    matrix = [
        # count=2 (not FOREVER): both injected failures must be absorbed by
        # retries, or the degraded substitution would break exactness.
        ("transient_error",
         FaultSpec("transient_error", shard=0, count=2), "retries"),
        ("worker_crash",
         FaultSpec("worker_crash", shard=0, count=1), "worker_deaths"),
        ("worker_stall",
         FaultSpec("worker_stall", stall_s=0.05, count=3), None),
        ("queue_overflow",
         FaultSpec("queue_overflow", count=FOREVER), "enqueue_rejections"),
    ]
    for kind, spec, counter in matrix:
        inj = FaultInjector([spec], seed=4)
        svc.set_injector(inj)
        svc.reset_stats()
        try:
            ids, d = ex.search(q, 5, cfg=cfg)
        finally:
            svc.set_injector(None)
        np.testing.assert_array_equal(np.asarray(ids), ids_0, err_msg=kind)
        np.testing.assert_array_equal(np.asarray(d), d_0, err_msg=kind)
        assert inj.injected()[kind] > 0, kind
        if counter is not None:
            assert svc.stats()[counter] > 0, (kind, svc.stats())


ACCEPT_CFG = HostIOConfig(
    workers=2, hot_cache_rows=1024, prefetch=True,
    resilience=ResilienceConfig(
        deadline_s=0.25, hedge_s=0.05, max_retries=3, backoff_base_s=1e-4,
        unhealthy_after=1_000_000, auto_failover=False,
        degraded_mode="medoid",
    ),
)


def test_scripted_fault_schedule_degraded_recall_and_recovery(
        small_ann_index):
    """THE acceptance scenario (ISSUE.md): partition down + stalled worker.

    Phases: (A) healthy baseline -> (B) the only host partition down with
    every pool worker stalled: serving continues from the hot cache +
    medoid restarts with recall >= 0.8 and no request outlives its
    deadline -> (C) failover replica pinned: bit-exact vs A -> (D)
    partition recovered: bit-exact vs A.
    """
    data, idx = small_ann_index
    ex = idx.executor("base", hostio=ACCEPT_CFG)
    svc = ex.hostio_service
    k = 10
    cfg = SearchConfig(t=48, bloom_z=8192)
    q = uniform_queries(data, 32, seed=7)
    gt = np.asarray(brute_force_knn(data, q, k))
    pipe = ServePipeline(ex, k=k, cfg=cfg, max_batch=32, deadline_s=60.0)
    try:
        # -- A: healthy baseline ------------------------------------------
        pipe.submit(q, gt_ids=gt)
        ids_a, d_a, st_a = pipe.drain()
        assert st_a.mean_recall is not None and st_a.mean_recall > 0.8

        # -- B: partition 0 down (no replica) + stalled workers -----------
        svc.mark_partition_down(0)
        svc.set_injector(FaultInjector(
            [FaultSpec("worker_stall", stall_s=0.2, count=FOREVER)], seed=3
        ))
        svc.reset_stats()
        pipe.submit(q, gt_ids=gt)
        ids_b, d_b, st_b = pipe.drain()
        svc.set_injector(None)
        h = st_b.hostio
        assert h["partitions_down"] == 1
        assert h["degraded_lanes"] > 0          # unfetchable rows substituted
        assert st_b.expired_queries == 0        # no request blew its deadline
        assert (np.asarray(ids_b)[:, 0] >= 0).all()   # every query answered
        assert st_b.mean_recall is not None and st_b.mean_recall >= 0.8, (
            f"degraded-mode recall {st_b.mean_recall:.3f} < 0.8 "
            f"(degraded_lanes={h['degraded_lanes']}, "
            f"cache_hit_rate={h['cache_hit_rate']:.3f})"
        )

        # -- C: failover replica -> bit-exact vs the fault-free run -------
        svc.fail_over(0)
        svc.reset_stats()
        pipe.submit(q, gt_ids=gt)
        ids_c, d_c, st_c = pipe.drain()
        np.testing.assert_array_equal(np.asarray(ids_c), np.asarray(ids_a))
        np.testing.assert_array_equal(np.asarray(d_c), np.asarray(d_a))
        assert st_c.hostio["failover_gathers"] > 0

        # -- D: recovery -> primary reads, still bit-exact ----------------
        svc.recover(0)
        pipe.submit(q, gt_ids=gt)
        ids_d, d_d, st_d = pipe.drain()
        np.testing.assert_array_equal(np.asarray(ids_d), np.asarray(ids_a))
        np.testing.assert_array_equal(np.asarray(d_d), np.asarray(d_a))
        assert svc.partition_state(0) == "up"
        assert svc.stats()["recoveries"] == 1
    finally:
        svc.set_injector(None)
        pipe.close()


# ------------------------------------------- forced-device subprocesses
def _run(code: str, devices: int) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True, text=True,
        timeout=900,
    )
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    return out.stdout


FAILOVER_CODE = """
import numpy as np, jax
from repro.compat import make_mesh
from repro.core import BangIndex, SearchConfig
from repro.runtime import ShardedSearchExecutor
from repro.runtime.hostio import HostIOConfig
from repro.runtime.resilience import ResilienceConfig

devices = {devices}
assert len(jax.devices()) == devices, jax.devices()
rng = np.random.default_rng(2)
n, d, B, k = 600, 24, 20, 5
data = rng.standard_normal((n, d)).astype(np.float32)
queries = rng.standard_normal((B, d)).astype(np.float32)
idx = BangIndex.build(data, m=6, R=16, L_build=24)
cfg = SearchConfig(t=32, bloom_z=4096)
mesh = make_mesh({mesh_shape}, ("data", "model"))
hio = HostIOConfig(workers=2, hot_cache_rows=64, prefetch=True,
                   resilience=ResilienceConfig(deadline_s=0.5, hedge_s=0.1))
ex = ShardedSearchExecutor.from_index(
    idx, mesh, variant="sharded-base", hostio=hio)
svc = ex.hostio_service
ids_0, d_0 = ex.search(queries, k, cfg=cfg)
ids_0, d_0 = np.asarray(ids_0), np.asarray(d_0)
# One model shard's host partition dies; its replica serves on survivors.
svc.fail_over(1)
svc.reset_stats()
ids_f, d_f = ex.search(queries, k, cfg=cfg)
assert np.array_equal(np.asarray(ids_f), ids_0), "failover ids diverge"
assert np.array_equal(np.asarray(d_f), d_0), "failover dists diverge"
s = svc.stats()
assert s["failover_gathers"] > 0 and s["partitions_down"] == 1, s
svc.recover(1)
ids_r, d_r = ex.search(queries, k, cfg=cfg)
assert np.array_equal(np.asarray(ids_r), ids_0), "recovery ids diverge"
assert np.array_equal(np.asarray(d_r), d_0), "recovery dists diverge"
assert svc.partition_state(1) == "up"
print("SHARDED-FAILOVER-OK")
"""


def test_sharded_base_failover_parity_two_devices():
    out = _run(FAILOVER_CODE.format(devices=2, mesh_shape=(1, 2)), 2)
    assert "SHARDED-FAILOVER-OK" in out
