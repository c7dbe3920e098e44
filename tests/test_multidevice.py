"""Multi-device tests: run in subprocesses with fake CPU devices.

These prove the shard_map sharded search works on a real (fake-)device
mesh and returns the single-device answers.
"""
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True, text=True,
        timeout=900,
    )
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    return out.stdout


def test_sharded_search_matches_single_device():
    _run(
        """
import numpy as np, jax
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.compat import make_mesh
from repro.core import BangIndex, SearchConfig, brute_force_knn, recall_at_k
from repro.core.distributed import make_sharded_search, pad_to_multiple

rng = np.random.default_rng(1)
n, d, B, k = 600, 24, 16, 5
data = rng.standard_normal((n, d)).astype(np.float32)
queries = rng.standard_normal((B, d)).astype(np.float32)
idx = BangIndex.build(data, m=6, R=16, L_build=24)
mesh = make_mesh((4, 2), ("data", "model"))
cfg = SearchConfig(t=32, bloom_z=4096)
adj = pad_to_multiple(idx.graph.adjacency, 2, -1)
codes = pad_to_multiple(np.asarray(idx.codes), 2, 0)
dat = pad_to_multiple(data, 2, 1e9)
fn = make_sharded_search(mesh, idx.graph.medoid, k, cfg)
with jax.set_mesh(mesh):
    args = [
        jax.device_put(queries, NamedSharding(mesh, P("data", None))),
        jax.device_put(np.asarray(idx.codec.codebooks), NamedSharding(mesh, P())),
        jax.device_put(codes, NamedSharding(mesh, P("model", None))),
        jax.device_put(adj, NamedSharding(mesh, P("model", None))),
        jax.device_put(dat, NamedSharding(mesh, P("model", None))),
    ]
    ids, dists = fn(*args)
ids1, _ = idx.search(queries, k, variant="inmem", cfg=cfg)
assert np.array_equal(np.sort(np.asarray(ids), 1), np.sort(np.asarray(ids1), 1)), "sharded != single-device"
print("OK")
""",
    )
