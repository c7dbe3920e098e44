"""Bloom filter properties (paper §4.4): no false negatives, bounded FPR."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # clean environment: seeded-random fallback shim
    from _hypothesis_compat import given, settings, strategies as st

from repro.core import bloom


@settings(max_examples=30, deadline=None)
@given(
    ids=st.lists(st.integers(0, 2**31 - 2), min_size=1, max_size=64),
    z=st.sampled_from([512, 4096, 399_887]),
)
def test_no_false_negatives(ids, z):
    ids_a = jnp.asarray(np.array(ids, np.int32)[None, :])
    filt = bloom.bloom_set(bloom.bloom_init(1, z), ids_a)
    assert bool(jnp.all(bloom.bloom_query(filt, ids_a)))


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 1000))
def test_query_and_set_fresh_semantics(seed):
    rng = np.random.default_rng(seed)
    a = rng.choice(10_000, size=24, replace=False).astype(np.int32)
    first, second = a[:12][None], a[:12][None]
    filt = bloom.bloom_init(1, 8192)
    fresh1, filt = bloom.bloom_query_and_set(filt, jnp.asarray(first))
    fresh2, filt = bloom.bloom_query_and_set(filt, jnp.asarray(second))
    assert bool(jnp.all(fresh1))          # never-seen ids are fresh
    assert not bool(jnp.any(fresh2))      # re-inserted ids are filtered


def test_false_positive_rate_reasonable():
    rng = np.random.default_rng(1)
    inserted = rng.choice(2**30, size=400, replace=False).astype(np.int32)
    others = (inserted[None] + 2**30).astype(np.int32)  # disjoint
    z = 8192
    filt = bloom.bloom_set(bloom.bloom_init(1, z), jnp.asarray(inserted[None]))
    fp = float(jnp.mean(bloom.bloom_query(filt, jnp.asarray(others)).astype(jnp.float32)))
    # ~ (1 - e^{-kn/z})^k with k=2, n=400, z=8192 -> ~0.9%; allow slack
    assert fp < 0.05


def test_valid_mask_blocks_insertion():
    ids = jnp.asarray([[5, 6]], dtype=jnp.int32)
    valid = jnp.asarray([[True, False]])
    filt = bloom.bloom_set(bloom.bloom_init(1, 1024), ids, valid)
    q = bloom.bloom_query(filt, ids)
    assert bool(q[0, 0]) and not bool(q[0, 1])


def test_fnv1a_reference_value():
    """FNV-1a over LE bytes of 0x00000000 must match the canonical constant."""
    h = bloom._fnv1a_u32(jnp.asarray([0], jnp.int32), bloom.FNV_OFFSET_BASIS)
    # hand-computed: 4 zero bytes folded into offset basis (mod 2^32)
    expect = 2166136261
    for _ in range(4):
        expect = ((expect ^ 0) * 16777619) % (1 << 32)
    assert int(np.uint32(h[0])) == expect


def _np_fnv1a(ids: np.ndarray, basis: int) -> np.ndarray:
    h = np.full(ids.shape, basis, np.uint64)
    x = ids.astype(np.uint32).astype(np.uint64)
    for shift in (0, 8, 16, 24):
        h = ((h ^ ((x >> np.uint64(shift)) & np.uint64(0xFF)))
             * np.uint64(16777619)) % np.uint64(1 << 32)
    return h


def _np_slots(ids: np.ndarray, z: int) -> tuple[np.ndarray, np.ndarray]:
    return (_np_fnv1a(ids, 2166136261) % z).astype(np.int64), \
        (_np_fnv1a(ids, 0x9747B28C) % z).astype(np.int64)


def _unpack(filt, batch: int, z: int) -> np.ndarray:
    words = np.asarray(filt.words).view(np.uint32).reshape(batch, -1)
    bits = (words[:, :, None] >> np.arange(32, dtype=np.uint32)) & 1
    bits = bits.reshape(batch, -1).astype(bool)
    assert not bits[:, z:].any()          # the last word's spare bits
    return bits[:, :z]


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_query_and_set_matches_set_of_slots_model(seed):
    """Hop by hop, the packed test-and-set gives the fresh masks and set
    slots of a plain set-of-slots model, where probes of one query share
    words and slots in every hop (z not a multiple of 32)."""
    B, R, z, hops = 3, 32, 333, 60
    rng = np.random.default_rng(seed)
    pool = np.arange(200_000, dtype=np.int32)
    s1, s2 = _np_slots(pool, z)
    at_end = pool[(s1 == z - 1) | (s2 == z - 1)][:4]     # slot z - 1
    at_bit31 = pool[(s1 % 32 == 31) & (s2 % 32 == 31)][:4]
    assert len(at_end) == 4 and len(at_bit31) == 4
    model = [set() for _ in range(B)]
    step = jax.jit(bloom.bloom_query_and_set)

    filt = bloom.bloom_set(bloom.bloom_init(B, z),
                           jnp.zeros((B, 1), jnp.int32))  # the medoid, 0
    a, b = _np_slots(np.zeros(1, np.int32), z)
    for q in model:
        q.update((int(a[0]), int(b[0])))
    np.testing.assert_array_equal(
        _unpack(filt, B, z),
        [[p in q for p in range(z)] for q in model])

    for _ in range(hops):
        ids = rng.integers(0, 5_000, (B, R)).astype(np.int32)
        ids[:, :4] = rng.permutation(at_end)
        ids[:, 4:8] = rng.permutation(at_bit31)
        ids[:, 8:12] = ids[:, 12:16]                   # repeated in the row
        valid = rng.random((B, R)) < 0.8
        p1, p2 = _np_slots(ids, z)
        want = np.array([[bool(valid[i, j]) and not (
            p1[i, j] in model[i] and p2[i, j] in model[i])
            for j in range(R)] for i in range(B)])
        for i in range(B):
            for j in np.flatnonzero(want[i]):
                model[i].update((int(p1[i, j]), int(p2[i, j])))

        fresh, filt = step(filt, jnp.asarray(ids), jnp.asarray(valid))
        np.testing.assert_array_equal(np.asarray(fresh), want)
        np.testing.assert_array_equal(
            _unpack(filt, B, z),
            [[p in q for p in range(z)] for q in model])
