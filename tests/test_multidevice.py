"""Multi-device tests: run in subprocesses with fake CPU devices.

These prove the shard_map sharded search and the pjit specs work on real
(fake-)device meshes, independent of the 512-device dry-run.
"""
import os
import subprocess
import sys

import pytest

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


@pytest.mark.slow
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


@pytest.mark.slow
def test_reduced_arch_train_step_on_mesh():
    """pjit train step with the production sharding rules on a 4x2 mesh."""
    _run(
        """
import numpy as np, jax, jax.numpy as jnp
import dataclasses
import repro.configs as configs
from repro.configs.base import ShapeSpec
from repro.launch.specs import step_and_specs
from repro.launch.mesh import make_test_mesh

from jax.sharding import NamedSharding, PartitionSpec as P
import numpy as _np

cfg = configs.get("glm4-9b").reduced(d_model=128, n_heads=8, n_kv_heads=2, head_dim=16, d_ff=256, vocab_size=512)
shape = ShapeSpec("t", "train", 64, 8)
mesh = make_test_mesh((4, 2), ("data", "model"))
step, specs, shardings = step_and_specs(cfg, shape, mesh)
with jax.set_mesh(mesh):
    jitted = jax.jit(step, in_shardings=shardings)
    # materialize real inputs placed with the expected shardings
    def mk(s, spec):
        host = (_np.zeros(s.shape, "int32") if s.dtype == jnp.int32
                else (_np.ones(s.shape, "float32") * 0.01).astype(s.dtype))
        return jax.device_put(host, NamedSharding(mesh, spec))
    args = jax.tree.map(mk, specs, shardings,
                        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
    params, opt, loss = jitted(*args)
assert np.isfinite(float(loss)), loss
print("OK", float(loss))
""",
    )


@pytest.mark.slow
def test_elastic_checkpoint_across_meshes(tmp_path):
    """Save on a 4-device mesh, restore onto a 2-device mesh."""
    code_save = f"""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.checkpoint import save_checkpoint
from repro.compat import make_mesh
mesh = make_mesh((4,), ("data",))
x = jax.device_put(np.arange(64, dtype=np.float32).reshape(8, 8), NamedSharding(mesh, P("data", None)))
save_checkpoint({str(tmp_path)!r}, 5, {{"x": x}})
print("saved")
"""
    code_load = f"""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.checkpoint import load_checkpoint
from repro.compat import make_mesh
mesh = make_mesh((2,), ("data",))
template = {{"x": jax.ShapeDtypeStruct((8, 8), jnp.float32)}}
def shard(key, arr):
    return NamedSharding(mesh, P("data", None))
tree, step = load_checkpoint({str(tmp_path)!r}, template, sharding_fn=shard)
assert step == 5
assert tree["x"].sharding.num_devices == 2
np.testing.assert_array_equal(np.asarray(tree["x"]), np.arange(64, dtype=np.float32).reshape(8, 8))
print("OK")
"""
    _run(code_save, devices=4)
    _run(code_load, devices=2)
