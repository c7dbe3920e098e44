"""variant="sharded-base": host-resident graph shards behind per-shard
callbacks -- parity, cache-isolation, accounting, and the callback ownership
property.

The parity matrix mirrors tests/test_sharded_executor.py: sharded-base must
return bit-exact ids AND distances vs both single-device variants ("base",
"inmem") and vs the device-sharded "sharded" variant -- moving the graph to
host RAM may change where bytes flow, never what comes back. The in-process
tests adapt to however many devices the process has (1 in the default tier-1
run; >1 under the CI multidevice job's XLA_FLAGS); the subprocess
tests force 1/2/4 host devices and a model-only mesh explicitly.
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
from repro.core.distributed import _owned_at, host_shard_neighbor_fn, host_shard_service
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
def sharded_base_setup(small_ann_index):
    data, idx = small_ann_index
    mesh = _local_mesh()
    return data, idx, mesh, idx.executor("sharded-base", mesh=mesh)


# ---------------------------------------------------------------- parity
def test_sharded_base_matches_base_bit_exact(sharded_base_setup):
    """Sharding the host graph service must be invisible vs variant="base"."""
    data, idx, mesh, ex = sharded_base_setup
    cfg = SearchConfig(t=32, bloom_z=8192)
    q = uniform_queries(data, 20, seed=71)
    ids1, d1 = idx.search(q, 5, cfg=cfg, variant="base")
    ids2, d2 = ex.search(q, 5, cfg=cfg)
    np.testing.assert_array_equal(np.asarray(ids1), np.asarray(ids2))
    np.testing.assert_array_equal(np.asarray(d1), np.asarray(d2))


def test_sharded_base_matches_inmem_and_sharded_bit_exact(sharded_base_setup):
    """The full placement matrix agrees: host/device x single/sharded."""
    data, idx, mesh, ex = sharded_base_setup
    cfg = SearchConfig(t=32, bloom_z=8192)
    q = uniform_queries(data, 16, seed=72)
    ids_sb, d_sb = ex.search(q, 5, cfg=cfg)
    ids_im, d_im = idx.search(q, 5, cfg=cfg, variant="inmem")
    ids_sh, d_sh = idx.search(q, 5, cfg=cfg, variant="sharded", mesh=mesh)
    np.testing.assert_array_equal(np.asarray(ids_sb), np.asarray(ids_im))
    np.testing.assert_array_equal(np.asarray(d_sb), np.asarray(d_im))
    np.testing.assert_array_equal(np.asarray(ids_sb), np.asarray(ids_sh))
    np.testing.assert_array_equal(np.asarray(d_sb), np.asarray(d_sh))


def test_sharded_base_through_index_search(sharded_base_setup):
    """variant="sharded-base" + mesh= threads to the same cached executor."""
    data, idx, mesh, ex = sharded_base_setup
    cfg = SearchConfig(t=32, bloom_z=8192)
    q = uniform_queries(data, 9, seed=73)
    a, _ = idx.search(q, 5, cfg=cfg, variant="sharded-base", mesh=mesh)
    b, _ = ex.search(q, 5, cfg=cfg)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert idx.executor("sharded-base", mesh=mesh) is ex


def test_sharded_base_no_rerank_path(sharded_base_setup):
    """rerank=False serves the PQ-ordered worklist (ids exact, dists close)."""
    data, idx, mesh, ex = sharded_base_setup
    cfg = SearchConfig(t=32, bloom_z=8192)
    q = uniform_queries(data, 8, seed=74)
    ids1, d1 = idx.search(q, 5, cfg=cfg, variant="base", rerank=False)
    ids2, d2 = ex.search(q, 5, cfg=cfg, rerank=False)
    np.testing.assert_array_equal(np.asarray(ids1), np.asarray(ids2))
    np.testing.assert_allclose(np.asarray(d1), np.asarray(d2), rtol=1e-5, atol=1e-5)


def test_sharded_base_padded_batch_matches_unpadded(sharded_base_setup):
    data, idx, mesh, ex = sharded_base_setup
    cfg = SearchConfig(t=32, bloom_z=8192)
    queries = uniform_queries(data, 16, seed=75)
    full_ids, full_dists = ex.search(queries, 5, cfg=cfg)
    pad_ids, pad_dists = ex.search(queries[:11], 5, cfg=cfg)
    np.testing.assert_array_equal(np.asarray(pad_ids), np.asarray(full_ids)[:11])
    np.testing.assert_array_equal(np.asarray(pad_dists), np.asarray(full_dists)[:11])


def test_serve_pipeline_fans_out_over_sharded_base(sharded_base_setup):
    """Micro-batched host-graph mesh serving == one-shot base search."""
    data, idx, mesh, ex = sharded_base_setup
    cfg = SearchConfig(t=32, bloom_z=8192)
    queries = uniform_queries(data, 40, seed=76)
    direct_ids, direct_dists = idx.search(queries, 5, cfg=cfg, variant="base")
    pipe = ServePipeline(ex, k=5, cfg=cfg, max_batch=16)
    pipe.submit(queries)
    ids, dists, stats = pipe.drain()
    np.testing.assert_array_equal(ids, np.asarray(direct_ids))
    np.testing.assert_array_equal(dists, np.asarray(direct_dists))
    assert stats.batches == 3 and stats.queries == 40


# ----------------------------------------------------- cache isolation
def test_variant_mesh_cache_never_aliases_sharded_and_base(sharded_base_setup):
    """(variant, mesh) caching keeps the two sharded placements fully apart,
    and the base mode never uploads adjacency to the device."""
    _, idx, mesh, ex_base = sharded_base_setup
    ex_dev = idx.executor("sharded", mesh=mesh)
    assert ex_dev is not ex_base
    assert idx.executor("sharded", mesh=mesh) is ex_dev
    assert idx.executor("sharded-base", mesh=mesh) is ex_base
    assert ex_dev.variant == "sharded" and ex_base.variant == "sharded-base"
    # Base mode: graph pinned in host RAM, one partition per model shard,
    # nothing on device. In-memory mode: the exact opposite.
    assert ex_base._adjacency is None
    assert ex_base._host_partitions is not None
    assert len(ex_base._host_partitions) == mesh.shape["model"]
    assert all(isinstance(p, np.ndarray) for p in ex_base._host_partitions)
    assert sum(p.shape[0] for p in ex_base._host_partitions) >= idx.n
    assert ex_dev._adjacency is not None and ex_dev._host_partitions is None
    # Compiled-executable caches are per-executor, so they cannot alias.
    assert ex_dev._cache is not ex_base._cache


def test_sharded_base_compile_cache_and_bucketing(small_ann_index):
    data, idx = small_ann_index
    ex = ShardedSearchExecutor.from_index(idx, _local_mesh(), variant="sharded-base")
    cfg = SearchConfig(t=32, bloom_z=8192)
    q1 = uniform_queries(data, 12, seed=77)   # bucket 16
    q2 = uniform_queries(data, 15, seed=78)   # same bucket, other batch size
    assert ex.n_traces == 0
    _, _, s1 = ex.search(q1, 5, cfg=cfg, return_stats=True)
    assert ex.n_traces == 1 and s1.compile_s > 0.0
    _, _, s2 = ex.search(q2, 5, cfg=cfg, return_stats=True)
    assert ex.n_traces == 1, "same-bucket sharded-base search retraced"
    assert s2.compile_s == 0.0 and ex.cache_size == 1


def test_unknown_sharded_variant_rejected(small_ann_index):
    _, idx = small_ann_index
    with pytest.raises(ValueError):
        ShardedSearchExecutor.from_index(idx, _local_mesh(), variant="sharded-exact")


# ------------------------------------------------------------ accounting
def test_exchange_accounting_splits_host_link_from_collectives(sharded_base_setup):
    _, idx, mesh, ex = sharded_base_setup
    x = ex.exchange_bytes_per_hop(16)
    b_loc = ex._bucket_for(16) // ex.n_data_shards
    # Host link (paper's PCIe traffic): frontier ids out, adjacency rows back.
    assert x["host_ids_out_bytes"] == b_loc * 4
    assert x["host_rows_in_bytes"] == b_loc * ex.R * 4
    assert x["host_link_bytes"] == x["host_ids_out_bytes"] + x["host_rows_in_bytes"]
    # Inter-device collectives are unchanged by the graph placement.
    assert x["collective_bytes"] == x["payload_bytes"] == b_loc * ex.R * 8
    dev = idx.executor("sharded", mesh=mesh).exchange_bytes_per_hop(16)
    assert dev["host_link_bytes"] == 0
    assert dev["collective_bytes"] == x["collective_bytes"]


def test_single_device_executor_accounting(small_ann_index):
    """The single-device executors share the schema: base pays the host link,
    device-resident variants move nothing."""
    _, idx = small_ann_index
    ex_base = idx.executor("base")
    x = ex_base.exchange_bytes_per_hop(16)
    bucket = ex_base._bucket_for(16)
    R = idx.graph.adjacency.shape[1]
    assert x["host_link_bytes"] == bucket * 4 + bucket * R * 4
    assert x["collective_bytes"] == 0 and x["ring_bytes_per_device"] == 0
    assert idx.executor("inmem").exchange_bytes_per_hop(16)["host_link_bytes"] == 0


# ------------------------------------------------------ ownership property
class _RecordingPartition(np.ndarray):
    """ndarray view logging every row-index array used to gather from it --
    i.e. exactly which ids reach this shard's host memory."""

    def __getitem__(self, item):
        self.served.append(np.array(item, copy=True))
        return np.asarray(super().__getitem__(item))


@settings(max_examples=30, deadline=None)
@given(data=st.data())
def test_host_callback_serves_each_valid_id_exactly_once(data):
    """Extends the `_owned_at` exactly-once property to the callback path:
    over shards 0..S-1, every valid frontier id is gathered from exactly one
    shard's host partition, sentinel/padded/out-of-range ids never index host
    memory at all, and the summed contributions reconstruct the unsharded
    adjacency gather bit-for-bit."""
    S = data.draw(st.integers(1, 8))
    local_n = data.draw(st.integers(1, 64))
    R = data.draw(st.integers(1, 8))
    n_total = S * local_n
    adjacency = (
        np.arange(n_total * R, dtype=np.int64) % (n_total + 1) - 1
    ).astype(np.int32).reshape(n_total, R)   # values span [-1, n_total)
    invalid = int(INVALID_ID)   # plain int: keep the host-side checks in numpy
    raw = data.draw(st.lists(
        st.integers(-n_total - 7, 2 * n_total + 7), min_size=1, max_size=40,
    ))
    inv = [data.draw(st.integers(0, 4)) == 0 for _ in raw]
    ids = np.array(
        [invalid if m else v for v, m in zip(raw, inv)], np.int32
    )

    total = np.zeros((len(ids), R), np.int64)
    serve_counts = np.zeros(len(ids), np.int64)
    for s in range(S):
        part = adjacency[s * local_n : (s + 1) * local_n].view(_RecordingPartition)
        part.served = []
        rel, own = _owned_at(s, local_n, jnp.asarray(ids))
        rel, own = np.asarray(rel), np.asarray(own)
        contrib = host_shard_service(part, rel, own)
        served = (
            np.concatenate([np.atleast_1d(x).ravel() for x in part.served])
            if part.served else np.array([], np.int64)
        )
        served_global = served + s * local_n
        # Host memory sees exactly the owned lanes of this shard -- never a
        # sentinel, never another shard's rows (duplicates per lane kept).
        np.testing.assert_array_equal(np.sort(served_global), np.sort(ids[own]))
        assert contrib[~own].sum() == 0, "non-owned lanes must contribute 0"
        serve_counts += own
        total += contrib.astype(np.int64)
    in_range = (ids >= 0) & (ids < n_total) & (ids != invalid)
    np.testing.assert_array_equal(serve_counts, in_range.astype(np.int64))
    expect = np.where(
        in_range[:, None], adjacency[np.clip(ids, 0, n_total - 1)], -1
    )
    np.testing.assert_array_equal(total - 1, expect)


def test_host_shard_neighbor_fn_rejects_ragged_partitions():
    parts = [np.zeros((4, 3), np.int32), np.zeros((5, 3), np.int32)]
    with pytest.raises(ValueError):
        host_shard_neighbor_fn(parts)


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
ex = ShardedSearchExecutor.from_index(idx, mesh, variant="sharded-base")
assert ex._adjacency is None, "base mode must not upload adjacency"
assert len(ex._host_partitions) == mesh.shape["model"]
ids_b, d_b = idx.search(queries, k, cfg=cfg, variant="base")
ids_i, d_i = idx.search(queries, k, cfg=cfg, variant="inmem")
ids_s, d_s = ex.search(queries, k, cfg=cfg)
assert np.array_equal(np.asarray(ids_s), np.asarray(ids_b)), "ids diverge vs base"
assert np.array_equal(np.asarray(d_s), np.asarray(d_b)), "dists diverge vs base"
assert np.array_equal(np.asarray(ids_s), np.asarray(ids_i)), "ids diverge vs inmem"
assert np.array_equal(np.asarray(d_s), np.asarray(d_i)), "dists diverge vs inmem"
x = ex.exchange_bytes_per_hop(B)
assert x["host_link_bytes"] == x["host_ids_out_bytes"] + x["host_rows_in_bytes"] > 0
pipe = ServePipeline(ex, k=k, cfg=cfg, max_batch=8)
pipe.submit(queries)
pids, pdists, stats = pipe.drain()
assert np.array_equal(pids, np.asarray(ids_s))
assert stats.batches == 3
print("OK", devices)
"""


@pytest.mark.parametrize(
    "devices,mesh_shape", [(1, (1, 1)), (2, (1, 2)), (4, (2, 2))]
)
def test_sharded_base_parity_forced_devices(devices, mesh_shape):
    out = _run(PARITY_CODE.format(devices=devices, mesh_shape=mesh_shape), devices)
    assert f"OK {devices}" in out


def test_sharded_base_model_only_mesh_four_devices():
    """All four devices on `model`: four host graph partitions, one callback
    each -- the graph-bigger-than-one-device shape with zero device adjacency."""
    out = _run(PARITY_CODE.format(devices=4, mesh_shape=(1, 4)), 4)
    assert "OK 4" in out
