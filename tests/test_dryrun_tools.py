"""Dry-run tooling: HLO collective parser + spec builders (no big compiles)."""
import jax
import jax.numpy as jnp
import pytest

import repro.configs as configs
from repro.configs.base import LM_SHAPES, ShapeSpec
from repro.launch.dryrun import _shape_bytes, parse_collectives
from repro.launch.specs import batch_specs, cache_specs, param_specs, uses_bangkv


HLO_SNIPPET = """
  %all-reduce.1 = bf16[128,256]{1,0} all-reduce(%x), replica_groups={}
  %ag = (f32[16,8]{1,0}, f32[16,8]{1,0}) all-gather(%a, %b), dimensions={0}
  %cp-start = bf16[64]{0} collective-permute-start(%y), source_target_pairs={{0,1}}
  %noise = f32[2,2]{1,0} add(%p, %q)
  %a2a = s8[1024]{0} all-to-all(%z), dimensions={0}
"""


def test_shape_bytes():
    assert _shape_bytes("bf16[128,256]{1,0}") == 128 * 256 * 2
    assert _shape_bytes("(f32[16,8], u8[4])") == 16 * 8 * 4 + 4
    assert _shape_bytes("f32[]") == 4  # scalar


def test_parse_collectives():
    out = parse_collectives(HLO_SNIPPET)
    assert out["all-reduce"] == {"count": 1, "bytes": 128 * 256 * 2}
    assert out["all-gather"]["count"] == 1
    assert out["all-gather"]["bytes"] == 2 * 16 * 8 * 4
    assert out["collective-permute"]["count"] == 1
    assert out["all-to-all"]["bytes"] == 1024
    assert out["total_bytes"] > 0


@pytest.mark.parametrize("name", sorted(configs.ARCHS))
def test_input_specs_all_cells(name):
    """Every (arch x shape) cell has well-formed ShapeDtypeStruct inputs."""
    cfg = configs.get(name)
    for shape in LM_SHAPES.values():
        b = batch_specs(cfg, shape)
        assert b["tokens"].dtype == jnp.int32
        if shape.kind == "train":
            assert b["labels"].shape == b["tokens"].shape
        if cfg.frontend != "none":
            assert "frontend" in b
        if shape.kind == "decode":
            c = cache_specs(cfg, shape)
            leaves = jax.tree.leaves(c)
            assert leaves, "decode caches empty"
            total = sum(l.size * l.dtype.itemsize for l in leaves)
            assert total > 0


def test_bangkv_policy():
    """long_500k uses BANG-KV on attention archs, native on SSM."""
    long = LM_SHAPES["long_500k"]
    dec = LM_SHAPES["decode_32k"]
    assert uses_bangkv(configs.get("glm4-9b"), long)
    assert uses_bangkv(configs.get("gemma3-27b"), long)
    assert not uses_bangkv(configs.get("mamba2-2.7b"), long)
    assert uses_bangkv(configs.get("zamba2-2.7b"), long)  # shared attn block
    assert not uses_bangkv(configs.get("glm4-9b"), dec)   # 32k decode exact


def test_param_specs_structure():
    cfg = configs.get("granite-3-2b")
    p = param_specs(cfg)
    assert "embed" in p and p["embed"].shape == (49155, 2048)
    assert p["layers"]["attn"]["wq"].shape == (40, 2048, 2048)


def test_partitioning_rules_divisibility():
    """Odd dims must fall back to replication, divisible ones shard."""
    from repro.distributed import param_pspecs
    from repro.launch.mesh import make_production_mesh
    import os
    # production mesh needs 256 devices; use an abstract mesh instead
    from jax.sharding import PartitionSpec as P
    from jax.sharding import AbstractMesh
    mesh = AbstractMesh((16, 16), ("data", "model"))
    cfg = configs.get("granite-3-2b")
    specs = param_pspecs(param_specs(cfg), mesh)
    assert specs["embed"] == P(None, "data")      # vocab 49155 odd -> replicated
    assert specs["layers"]["attn"]["wq"] == P(None, "data", "model")
