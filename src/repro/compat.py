"""Thin wrappers over the JAX mesh / callback APIs the repo uses everywhere.

Every mesh-touching module imports these instead of spelling the arguments
out itself, so one place fixes the choices: meshes with Auto axis types,
`shard_map` with the replication checker on by default, and host callbacks
called once per batch member (never claimed to vectorize).

Keep this module dependency-free (jax only): it is imported by `core`,
`runtime`, `chip_smoke.py`, tests and subprocess snippets alike.
"""
from __future__ import annotations

from typing import Sequence

import jax

__all__ = ["make_mesh", "shard_map", "pure_callback"]


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str], *,
              devices=None):
    """`jax.make_mesh` with explicit Auto axis types (over `devices` if given)."""
    names = tuple(axis_names)
    return jax.make_mesh(
        tuple(axis_shapes), names,
        axis_types=(jax.sharding.AxisType.Auto,) * len(names),
        devices=devices,
    )


def shard_map(f, *, mesh, in_specs, out_specs, check_rep: bool = True):
    """`jax.shard_map`; `check_rep` is its `check_vma` replication checker.

    The sharded-search call sites opt out explicitly because their
    psum-reconstructed outputs defeat the checker.
    """
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=check_rep,
    )


def pure_callback(fn, result_shape_dtypes, *args):
    """`jax.pure_callback` called once per batch member, shard_map-safe.

    "sequential" never claims the host function vectorizes -- the only rule
    that is safe under `shard_map`, where the callback runs once per device
    with that device's local block. Host-service call sites (the BANG base
    and sharded-base graph callbacks, the host re-rank gather) go through
    here.
    """
    return jax.pure_callback(
        fn, result_shape_dtypes, *args, vmap_method="sequential"
    )
