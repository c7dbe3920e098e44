"""Batched Lloyd's k-means, the substrate for PQ codebook training (paper §2.3).

The paper uses 256 centroids per subspace (k-means per subspace, m subspaces).
We vmap Lloyd's iterations over subspaces so all m codebooks train in one XLA
program. Empty clusters are re-seeded from the farthest points (k-means++ style
repair), which is what keeps 256-way clustering stable on small test datasets.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

Array = jax.Array


def first_argmin(x: Array) -> Array:
    """Index of the first minimum along the last axis, as `jnp.argmin` picks.

    Spelled as a min, a compare and an iota-min: on a TPU v5e, `jnp.argmin`
    over the last axis of the subspace-batched distance arrays below
    returned wrong indices for most rows (CPU was right).
    """
    iota = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
    best = jnp.min(x, axis=-1, keepdims=True)
    return jnp.min(jnp.where(x == best, iota, x.shape[-1]), axis=-1)


def _pairwise_sq_dists(x: Array, c: Array) -> Array:
    """(n, d) x (k, d) -> (n, k) squared L2 distances via the matmul identity."""
    xn = jnp.sum(x * x, axis=-1, keepdims=True)           # (n, 1)
    cn = jnp.sum(c * c, axis=-1)[None, :]                 # (1, k)
    return xn + cn - 2.0 * jnp.dot(x, c.T, precision=jax.lax.Precision.HIGHEST)


def _lloyd_iter(x: Array, centroids: Array) -> tuple[Array, Array]:
    """One Lloyd iteration. Returns (new_centroids, assignment)."""
    d2 = _pairwise_sq_dists(x, centroids)                 # (n, k)
    assign = first_argmin(d2)                             # (n,)
    k = centroids.shape[0]
    onehot = jax.nn.one_hot(assign, k, dtype=x.dtype)     # (n, k)
    counts = jnp.sum(onehot, axis=0)                      # (k,)
    sums = jnp.dot(onehot.T, x, precision=jax.lax.Precision.HIGHEST)  # (k, d)
    new_c = sums / jnp.maximum(counts, 1.0)[:, None]
    # Empty-cluster repair: pull the point farthest from its centroid.
    far_idx = first_argmin(-jnp.min(d2, axis=-1))
    new_c = jnp.where((counts == 0)[:, None], x[far_idx][None, :], new_c)
    return new_c, assign


@functools.partial(jax.jit, static_argnames=("k", "iters"))
def kmeans(x: Array, k: int, iters: int = 12, *, key: Array | None = None) -> tuple[Array, Array]:
    """Lloyd's k-means on (n, d) data. Returns (centroids (k, d), assignment (n,)).

    Initialisation: a deterministic strided sample of the data (n >= k assumed;
    if n < k the extra centroids coincide and empty-cluster repair spreads them).
    """
    n = x.shape[0]
    if key is None:
        idx = (jnp.arange(k) * max(n // k, 1)) % n
    else:
        idx = jax.random.choice(key, n, (k,), replace=n < k)
    init = x[idx]

    def body(c, _):
        c, assign = _lloyd_iter(x, c)
        return c, None

    centroids, _ = jax.lax.scan(body, init, None, length=iters)
    assign = first_argmin(_pairwise_sq_dists(x, centroids))
    return centroids, assign


@functools.partial(jax.jit, static_argnames=("k", "iters"))
def kmeans_per_subspace(x_sub: Array, k: int, iters: int = 12) -> Array:
    """k-means independently per subspace.

    x_sub: (m, n, dsub) -> codebooks (m, k, dsub). This is the PQ training step.
    """
    return jax.vmap(lambda xs: kmeans(xs, k, iters)[0])(x_sub)
