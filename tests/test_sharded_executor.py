"""ShardedSearchExecutor: bit-exact parity with the single-device executor,
compile-cache/bucketing behaviour on the sharded path, and the ownership
invariant the owner-shard collectives rest on.

The in-process tests adapt to however many devices the process has (1 in the
default tier-1 run; >1 under the CI multidevice job's
XLA_FLAGS=--xla_force_host_platform_device_count). The subprocess
tests force 1/2/4 host devices explicitly, proving parity holds on real
multi-device meshes regardless of the parent's device count.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # pragma: no cover - fallback shim keeps suite collectable
    from _hypothesis_compat import given, settings, strategies as st

import jax
import jax.numpy as jnp

from repro.compat import make_mesh
from repro.core import SearchConfig
from repro.core.distributed import _owned_at
from repro.core.worklist import INVALID_ID
from repro.data import uniform_queries
from repro.runtime import ServePipeline, ShardedSearchExecutor

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _local_mesh():
    """Largest ("data", "model") mesh this process's devices allow."""
    n = len(jax.devices())
    if n >= 4:
        return make_mesh((2, 2), ("data", "model"))
    if n >= 2:
        return make_mesh((1, 2), ("data", "model"))
    return make_mesh((1, 1), ("data", "model"))


@pytest.fixture(scope="module")
def sharded_setup(small_ann_index):
    data, idx = small_ann_index
    mesh = _local_mesh()
    return data, idx, mesh, idx.executor("sharded", mesh=mesh)


# ---------------------------------------------------------------- parity
def test_sharded_matches_single_device_bit_exact(sharded_setup):
    """Identical top-k ids AND distances: sharding must be invisible."""
    data, idx, mesh, ex = sharded_setup
    cfg = SearchConfig(t=32, bloom_z=8192)
    q = uniform_queries(data, 20, seed=61)
    ids1, d1 = idx.search(q, 5, cfg=cfg)
    ids2, d2 = ex.search(q, 5, cfg=cfg)
    np.testing.assert_array_equal(np.asarray(ids1), np.asarray(ids2))
    np.testing.assert_array_equal(np.asarray(d1), np.asarray(d2))


def test_sharded_through_index_search(sharded_setup):
    """variant="sharded" + mesh= threads to the same cached executor."""
    data, idx, mesh, ex = sharded_setup
    cfg = SearchConfig(t=32, bloom_z=8192)
    q = uniform_queries(data, 9, seed=62)
    a, _ = idx.search(q, 5, cfg=cfg, variant="sharded", mesh=mesh)
    b, _ = ex.search(q, 5, cfg=cfg)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert idx.executor("sharded", mesh=mesh) is ex
    with pytest.raises(ValueError):
        idx.executor("inmem", mesh=mesh)   # mesh only applies to sharded


def test_sharded_no_rerank_path(sharded_setup):
    """rerank=False serves the PQ-ordered worklist, like the base pipeline.

    Ids are identical; the approximate PQ distances are only allclose — the
    two programs reduce the m-axis ADC sum in different orders, so the last
    float bit may differ (the exact re-rank distances, by contrast, are
    bit-equal: see test_sharded_matches_single_device_bit_exact).
    """
    data, idx, mesh, ex = sharded_setup
    cfg = SearchConfig(t=32, bloom_z=8192)
    q = uniform_queries(data, 8, seed=63)
    ids1, d1 = idx.search(q, 5, cfg=cfg, rerank=False)
    ids2, d2 = ex.search(q, 5, cfg=cfg, rerank=False)
    np.testing.assert_array_equal(np.asarray(ids1), np.asarray(ids2))
    np.testing.assert_allclose(np.asarray(d1), np.asarray(d2), rtol=1e-5, atol=1e-5)


def test_sharded_padded_batch_matches_unpadded(sharded_setup):
    data, idx, mesh, ex = sharded_setup
    cfg = SearchConfig(t=32, bloom_z=8192)
    queries = uniform_queries(data, 16, seed=64)
    full_ids, full_dists = ex.search(queries, 5, cfg=cfg)
    pad_ids, pad_dists = ex.search(queries[:11], 5, cfg=cfg)
    np.testing.assert_array_equal(np.asarray(pad_ids), np.asarray(full_ids)[:11])
    np.testing.assert_array_equal(np.asarray(pad_dists), np.asarray(full_dists)[:11])


def test_serve_pipeline_fans_out_over_sharded_executor(sharded_setup):
    """Micro-batched mesh serving == one-shot single-device search."""
    data, idx, mesh, ex = sharded_setup
    cfg = SearchConfig(t=32, bloom_z=8192)
    queries = uniform_queries(data, 40, seed=65)
    direct_ids, direct_dists = idx.search(queries, 5, cfg=cfg)
    pipe = ServePipeline(ex, k=5, cfg=cfg, max_batch=16)
    pipe.submit(queries)
    ids, dists, stats = pipe.drain()
    np.testing.assert_array_equal(ids, np.asarray(direct_ids))
    np.testing.assert_array_equal(dists, np.asarray(direct_dists))
    assert stats.batches == 3 and stats.queries == 40


# ------------------------------------------------- compile cache / buckets
def test_sharded_compile_cache_and_bucketing(small_ann_index):
    data, idx = small_ann_index
    ex = ShardedSearchExecutor.from_index(idx, _local_mesh())
    cfg = SearchConfig(t=32, bloom_z=8192)
    q1 = uniform_queries(data, 12, seed=66)   # bucket 16
    q2 = uniform_queries(data, 15, seed=67)   # same bucket, other batch size
    assert ex.n_traces == 0
    _, _, s1 = ex.search(q1, 5, cfg=cfg, return_stats=True)
    assert ex.n_traces == 1 and s1.compile_s > 0.0
    _, _, s2 = ex.search(q2, 5, cfg=cfg, return_stats=True)
    assert ex.n_traces == 1, "same-bucket sharded search retraced"
    assert s2.compile_s == 0.0 and ex.cache_size == 1
    ex.search(uniform_queries(data, 20, seed=68), 5, cfg=cfg)   # bucket 32
    assert ex.n_traces == 2
    ex.search(q1, 5, cfg=SearchConfig(t=48, bloom_z=8192))      # new cfg
    assert ex.n_traces == 3


def test_sharded_bucket_divisible_by_data_shards(sharded_setup):
    data, idx, mesh, ex = sharded_setup
    D = ex.n_data_shards
    for b in (1, 3, 8, 11, 17, 64):
        bucket = ex._bucket_for(b)
        assert bucket >= b and bucket % D == 0


def test_exchange_accounting(sharded_setup):
    _, _, mesh, ex = sharded_setup
    x = ex.exchange_bytes_per_hop(16)
    b_loc = ex._bucket_for(16) // ex.n_data_shards
    assert x["payload_bytes"] == b_loc * ex.R * 8
    assert x["model_shards"] == mesh.shape["model"]
    if x["model_shards"] == 1:
        assert x["ring_bytes_per_device"] == 0
    else:
        assert 0 < x["ring_bytes_per_device"] <= 2 * x["payload_bytes"]


def test_mesh_axis_validation(small_ann_index):
    _, idx = small_ann_index
    bad = make_mesh((1,), ("rows",))
    with pytest.raises(ValueError):
        ShardedSearchExecutor.from_index(idx, bad)


# ------------------------------------------------------ ownership invariant
@settings(max_examples=30, deadline=None)
@given(data=st.data())
def test_owned_partitions_ids_exactly_once(data):
    """Over shards 0..S-1, `_owned_at` owns every in-range id exactly once
    and INVALID/negative/out-of-range ids never -- the invariant that makes
    the masked psums of the sharded pipeline a faithful row exchange."""
    S = data.draw(st.integers(1, 8))
    local_n = data.draw(st.integers(1, 64))
    n_total = S * local_n
    invalid = int(INVALID_ID)   # plain int: keep the host-side checks in numpy
    raw = data.draw(st.lists(
        st.integers(-n_total - 7, 2 * n_total + 7), min_size=1, max_size=40,
    ))
    inv = [data.draw(st.integers(0, 4)) == 0 for _ in raw]
    ids = np.array(
        [invalid if m else v for v, m in zip(raw, inv)], np.int32
    )
    owners = np.zeros(len(ids), np.int64)
    for s in range(S):
        rel, own = _owned_at(s, local_n, jnp.asarray(ids))
        rel, own = np.asarray(rel), np.asarray(own)
        assert rel.min() >= 0 and rel.max() < local_n, "rel ids must be safe gathers"
        # owned relative ids reconstruct the global id of this shard's block
        np.testing.assert_array_equal(rel[own] + s * local_n, ids[own])
        owners += own
    in_range = (ids >= 0) & (ids < n_total) & (ids != invalid)
    np.testing.assert_array_equal(owners, in_range.astype(np.int64))


# ------------------------------------------------- forced-device subprocesses
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


PARITY_CODE = """
import numpy as np, jax
from repro.compat import make_mesh
from repro.core import BangIndex, SearchConfig
from repro.runtime import ServePipeline, ShardedSearchExecutor

devices = {devices}
assert len(jax.devices()) == devices, jax.devices()
rng = np.random.default_rng(2)
n, d, B, k = 600, 24, 20, 5
data = rng.standard_normal((n, d)).astype(np.float32)
queries = rng.standard_normal((B, d)).astype(np.float32)
idx = BangIndex.build(data, m=6, R=16, L_build=24)
cfg = SearchConfig(t=32, bloom_z=4096)
mesh = make_mesh({mesh_shape}, ("data", "model"))
ex = ShardedSearchExecutor.from_index(idx, mesh)
ids1, d1 = idx.search(queries, k, cfg=cfg)
ids2, d2 = ex.search(queries, k, cfg=cfg)
assert np.array_equal(np.asarray(ids1), np.asarray(ids2)), "ids diverge"
assert np.array_equal(np.asarray(d1), np.asarray(d2)), "dists diverge"
assert ex._bucket_for(B) % ex.n_data_shards == 0
ex.search(queries[:13], k, cfg=cfg)
assert ex.n_traces == 2 and ex.cache_size == 2   # buckets 32 and 16
pipe = ServePipeline(ex, k=k, cfg=cfg, max_batch=8)
pipe.submit(queries)
pids, pdists, stats = pipe.drain()
assert np.array_equal(pids, np.asarray(ids1))
assert stats.batches == 3
print("OK", devices)
"""


@pytest.mark.parametrize(
    "devices,mesh_shape", [(1, (1, 1)), (2, (1, 2)), (4, (2, 2))]
)
def test_sharded_executor_parity_forced_devices(devices, mesh_shape):
    out = _run(PARITY_CODE.format(devices=devices, mesh_shape=mesh_shape), devices)
    assert f"OK {devices}" in out


def test_sharded_model_only_mesh_four_devices():
    """All four devices on `model` -- the graph-bigger-than-one-device shape."""
    out = _run(PARITY_CODE.format(devices=4, mesh_shape=(1, 4)), 4)
    assert "OK 4" in out
