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

The z slots are packed 32 to an int32 word: the batch's filters are one flat
(batch * zw,) array with zw = ceil(z / 32), and slot p of filter b is bit
p & 31 of word b * zw + (p >> 5) (51 MB at batch 1024 and z 399,887, against
409 MB as bytes). A test-and-set gathers the (B, 2R) probe words once, ORs
the fresh bits of the lanes whose probes share a word into the first such
lane of the query, and stores those words with one scatter whose indices
are unique: no combiner, no sub-word write, no order between updates. A
query's filter owns its words, so two queries never write the same word.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array

FNV_OFFSET_BASIS = jnp.uint32(2166136261)
FNV_PRIME = jnp.uint32(16777619)
# Second hash: FNV-1a with a different offset basis (standard trick for
# independent hash families from the same mixer).
FNV_OFFSET_BASIS_2 = jnp.uint32(0x9747B28C)


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=["words"], meta_fields=["z"])
@dataclasses.dataclass(frozen=True)
class BloomFilters:
    """A batch of z-slot filters, packed (see the module docstring)."""
    words: Array            # (batch * words_per_filter(z),) int32
    z: int


def words_per_filter(z: int) -> int:
    return -(-z // 32)


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


def bloom_init(batch: int, z: int) -> BloomFilters:
    """`batch` filters of z slots each, all clear: the paper's 'array of z
    bools' per query, packed (see the module docstring)."""
    if batch * z >= 2**31:
        raise ValueError(f"batch {batch} x z {z} bloom slots exceed int32 "
                         "positions")
    return BloomFilters(
        jnp.zeros((batch * words_per_filter(z),), jnp.int32), z)


def _probes(filt: BloomFilters, ids: Array) -> tuple[Array, Array]:
    """Word index and bit of both probes of ids (B, R): (B, 2R) each, the
    first probes in lanes [0, R), the second in [R, 2R)."""
    B = ids.shape[0]
    p = jnp.concatenate(bloom_hashes(ids, filt.z), axis=1)
    row = (jnp.arange(B, dtype=jnp.int32) * words_per_filter(filt.z))[:, None]
    return row + (p >> 5), jnp.left_shift(jnp.int32(1), p & 31)


def _store(filt: BloomFilters, word: Array, bit: Array, old: Array,
           put: Array) -> BloomFilters:
    """Set `bit` in `word` (B, L) for the lanes where `put`, given the
    words' `old` values: one scatter, each word written by one lane."""
    B, L = word.shape
    same = word[:, :, None] == word[:, None, :]              # (B, L, L)
    merged = jax.lax.reduce(
        jnp.where(same & put[:, None, :], bit[:, None, :], 0),
        np.int32(0), jax.lax.bitwise_or, (2,))
    lane = jnp.arange(L)
    first = ~jnp.any(same & (lane[None, :] < lane[:, None]), axis=2)
    new = old | merged
    # Lanes that write nothing aim at their own index past the end, so the
    # scatter's indices stay unique; mode="drop" discards those updates.
    spare = filt.words.shape[0] + jnp.arange(B * L, dtype=jnp.int32)
    idx = jnp.where(first & (new != old), word, spare.reshape(B, L))
    words = filt.words.at[idx.reshape(-1)].set(
        new.reshape(-1), mode="drop", unique_indices=True)
    return BloomFilters(words, filt.z)


def bloom_set(filt: BloomFilters, ids: Array, valid: Array | None = None) -> BloomFilters:
    """Insert ids (B, R) into the B filters. valid masks padding."""
    word, bit = _probes(filt, ids)
    put = jnp.ones(ids.shape, jnp.bool_) if valid is None else valid
    return _store(filt, word, bit, filt.words[word],
                  jnp.concatenate([put, put], axis=1))


def bloom_query(filt: BloomFilters, ids: Array) -> Array:
    """Membership test of ids (B, R) -> (B, R) bool (True = maybe-seen)."""
    word, bit = _probes(filt, ids)
    hit = (filt.words[word] & bit) != 0
    R = ids.shape[1]
    return hit[:, :R] & hit[:, R:]


def bloom_query_and_set(filt: BloomFilters, ids: Array, valid: Array | None = None) -> tuple[Array, BloomFilters]:
    """Fused filter step of Algorithm 2 lines 7-10: test-then-insert.

    Returns (fresh_mask, new_filter): fresh_mask is True for ids not seen
    before (and valid); those ids are inserted. One gather of the probe
    words serves both the test and the store.
    """
    R = ids.shape[1]
    word, bit = _probes(filt, ids)
    old = filt.words[word]
    hit = (old & bit) != 0
    fresh = ~(hit[:, :R] & hit[:, R:])
    if valid is not None:
        fresh = fresh & valid
    return fresh, _store(filt, word, bit, old,
                         jnp.concatenate([fresh, fresh], axis=1))
