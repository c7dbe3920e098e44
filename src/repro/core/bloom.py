"""Bloom filter for visited-vertex tracking (paper §4.4).

The paper uses one bloom filter per query -- "an array of z bools" -- with two
FNV-1a hash functions, to approximate the visited set on device with a small,
GPU/TPU-friendly memory footprint (a per-query bitmap over the full billion-node
graph would need 125 GB). False positives are tolerable (a node is skipped that
needn't be); false negatives never happen, which is the property our hypothesis
tests pin down.

We implement FNV-1a over the 4 little-endian bytes of the node id in uint32
arithmetic, exactly as the reference C implementation would, and derive the two
probe positions Kirsch-Mitzenmacher style from two independently-seeded FNV-1a
passes.

The batch's filters live in one flat (batch * z,) array, filter b in
[b * z, (b + 1) * z). That is the shape a TPU scatters into in place: a
(batch, z) array was laid out column-major, so every insertion relaid all
batch * z bytes out to flat and back (409 MB twice a hop at batch 1024).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

Array = jax.Array

FNV_OFFSET_BASIS = jnp.uint32(2166136261)
FNV_PRIME = jnp.uint32(16777619)
# Second hash: FNV-1a with a different offset basis (standard trick for
# independent hash families from the same mixer).
FNV_OFFSET_BASIS_2 = jnp.uint32(0x9747B28C)


def _fnv1a_u32(x: Array, basis: Array) -> Array:
    """FNV-1a over the 4 LE bytes of each element of an int32/uint32 array."""
    x = x.astype(jnp.uint32)
    h = jnp.full_like(x, basis)
    for shift in (0, 8, 16, 24):
        byte = (x >> jnp.uint32(shift)) & jnp.uint32(0xFF)
        h = (h ^ byte) * FNV_PRIME
    return h


def bloom_hashes(ids: Array, z: int) -> tuple[Array, Array]:
    """Two probe positions in [0, z) for each id."""
    h1 = _fnv1a_u32(ids, FNV_OFFSET_BASIS)
    h2 = _fnv1a_u32(ids, FNV_OFFSET_BASIS_2)
    zz = jnp.uint32(z)
    return (h1 % zz).astype(jnp.int32), (h2 % zz).astype(jnp.int32)


def bloom_init(batch: int, z: int) -> Array:
    """`batch` filters of z uint8 each, all clear: the paper's 'array of z
    bools' per query, flat (see the module docstring)."""
    if batch * z >= 2**31:
        raise ValueError(f"batch {batch} x z {z} bloom bytes exceed int32 "
                         "positions")
    return jnp.zeros((batch * z,), jnp.uint8)


def _positions(filt: Array, ids: Array) -> tuple[Array, Array]:
    """Flat positions of the two probes of ids (B, R) in B filters."""
    B = ids.shape[0]
    z = filt.shape[0] // B
    p1, p2 = bloom_hashes(ids, z)
    row = (jnp.arange(B, dtype=jnp.int32) * z)[:, None]
    return row + p1, row + p2


def bloom_set(filt: Array, ids: Array, valid: Array | None = None) -> Array:
    """Insert ids (B, R) into the B filters. valid masks padding."""
    p1, p2 = _positions(filt, ids)
    # Invalid lanes write 0 under max: a no-op on their probe positions.
    if valid is not None:
        v = valid.astype(jnp.uint8)
    else:
        v = jnp.ones_like(ids, jnp.uint8)
    filt = filt.at[p1].max(v)
    filt = filt.at[p2].max(v)
    return filt


def bloom_query(filt: Array, ids: Array) -> Array:
    """Membership test of ids (B, R) -> (B, R) bool (True = maybe-seen)."""
    p1, p2 = _positions(filt, ids)
    return (filt[p1] > 0) & (filt[p2] > 0)


def bloom_query_and_set(filt: Array, ids: Array, valid: Array | None = None) -> tuple[Array, Array]:
    """Fused filter step of Algorithm 2 lines 7-10: test-then-insert.

    Returns (fresh_mask, new_filter): fresh_mask is True for ids not seen
    before (and valid); those ids are inserted.
    """
    seen = bloom_query(filt, ids)
    fresh = ~seen
    if valid is not None:
        fresh = fresh & valid
    return fresh, bloom_set(filt, ids, fresh)
