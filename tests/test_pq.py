"""PQ codec invariants (paper §2.3, §4.2, §4.5)."""
import numpy as np
import jax.numpy as jnp
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # clean environment: seeded-random fallback shim
    from _hypothesis_compat import given, settings, strategies as st

from repro.core import pq


def _codec(rng, m=4, dsub=8):
    cb = rng.standard_normal((m, 256, dsub)).astype(np.float32)
    return pq.PQCodec(jnp.asarray(cb))


def test_adc_equals_decompressed_distance(rng):
    """ADC(q, code) == ||q - decode(code)||^2 exactly (the §4.5 identity)."""
    codec = _codec(rng)
    d = codec.d
    q = jnp.asarray(rng.standard_normal((5, d)).astype(np.float32))
    codes = jnp.asarray(rng.integers(0, 256, (7, codec.m)).astype(np.uint8))
    table = pq.build_dist_table(codec, q)
    dec = pq.pq_decode(codec, codes)                       # (7, d)
    for b in range(5):
        adc = pq.adc_distance(table[b : b + 1], codes[None])[0]
        exact = jnp.sum((dec - q[b]) ** 2, axis=-1)
        np.testing.assert_allclose(np.asarray(adc), np.asarray(exact), rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("b,r,m", [(1, 4, 4), (3, 17, 9), (7, 33, 32), (5, 64, 74)])
def test_adc_onehot_bit_identical_to_gather(b, r, m):
    """The TPU's one-hot ADC returns the gather's distances bit for bit."""
    rng = np.random.default_rng(b * 1000 + r * 10 + m)
    table = rng.standard_normal((b, m, 256)).astype(np.float32)
    table[rng.random(table.shape) < 0.05] = 0.0            # zero and negative entries
    codes = rng.integers(0, 256, (b, r, m)).astype(np.uint8)
    codes[:, ::3, ::2] = 0                                 # both ends of the 256 lanes
    codes[:, 1::3, 1::2] = 255
    table, codes = jnp.asarray(table), jnp.asarray(codes)
    got = np.asarray(pq.adc_onehot(table, codes))
    want = np.asarray(pq.adc_gather(table, codes))
    assert got.shape == (b, r)
    np.testing.assert_array_equal(got.view(np.uint32), want.view(np.uint32))


def test_encode_is_argmin(rng):
    """Encoding picks the nearest centroid per subspace."""
    codec = _codec(rng, m=3, dsub=4)
    x = rng.standard_normal((20, codec.d)).astype(np.float32)
    codes = np.asarray(pq.pq_encode(codec, jnp.asarray(x)))
    xs = x.reshape(20, 3, 4)
    cb = np.asarray(codec.codebooks)
    for i in range(20):
        for j in range(3):
            d2 = ((cb[j] - xs[i, j]) ** 2).sum(-1)
            assert codes[i, j] == np.argmin(d2)


@given(st.integers(0, 2**31 - 1))
@settings(max_examples=25, deadline=None)
def test_first_argmin_matches_argmin(seed):
    """first_argmin == argmin, first index on ties, batched over leading axes."""
    from repro.core.kmeans import first_argmin

    r = np.random.default_rng(seed)
    x = r.integers(0, 6, (3, 17, 256)).astype(np.float32)  # many ties
    np.testing.assert_array_equal(
        np.asarray(first_argmin(jnp.asarray(x))), np.argmin(x, axis=-1)
    )


def test_training_reduces_quantization_error(rng):
    from repro.data import gaussian_mixture

    data = gaussian_mixture(2000, 32, n_clusters=16, seed=5)
    trained = pq.train_pq(jnp.asarray(data), m=8, iters=10)
    random_codec = _codec(np.random.default_rng(9), m=8, dsub=4)
    err_t = pq.quantization_error(trained, jnp.asarray(data))
    err_r = pq.quantization_error(random_codec, jnp.asarray(data))
    assert err_t < 0.5 * err_r


def test_split_subspaces_pads_distance_neutral(rng):
    """d not divisible by m: zero padding must not change L2 distances."""
    x = rng.standard_normal((4, 10)).astype(np.float32)
    sub = pq.split_subspaces(jnp.asarray(x), m=3)          # dsub = 4, padded
    assert sub.shape == (3, 4, 4)
    restored = np.asarray(sub).transpose(1, 0, 2).reshape(4, 12)
    np.testing.assert_allclose(restored[:, :10], x)
    np.testing.assert_allclose(restored[:, 10:], 0)


@settings(max_examples=10, deadline=None)
@given(m=st.sampled_from([1, 2, 4, 8]), seed=st.integers(0, 99))
def test_table_matches_bruteforce(m, seed):
    rng = np.random.default_rng(seed)
    codec = _codec(rng, m=m, dsub=4)
    q = jnp.asarray(rng.standard_normal((3, codec.d)).astype(np.float32))
    table = np.asarray(pq.build_dist_table(codec, q))      # (3, m, 256)
    qs = np.asarray(q).reshape(3, m, 4)
    cb = np.asarray(codec.codebooks)
    expect = ((qs[:, :, None, :] - cb[None]) ** 2).sum(-1)
    np.testing.assert_allclose(table, expect, rtol=3e-4, atol=3e-4)
