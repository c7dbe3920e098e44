"""Product Quantization codec (paper §2.3, §4.2).

PQ splits a d-dim vector into m subspaces of dsub = d/m dims, k-means-quantises
each subspace to 256 centroids, and represents each point by m uint8 cluster
ids. Distances to a query are then computed *asymmetrically* (ADC): a
per-query PQDistTable of shape (m, 256) holds the squared L2 distance from the
query's subvector to every centroid of every subspace; the distance to a
compressed point is the sum of m table lookups (paper Eq. in §2.3, §4.5).

The fast paths (distance-table construction and ADC accumulation) have Pallas
kernels under repro.kernels; this module is the reference/host implementation
and the codec (train / encode / decode) substrate.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from .kmeans import first_argmin, kmeans_per_subspace

Array = jax.Array

N_CLUSTERS = 256  # per subspace, as in the paper ("number of centroids is as
                  # used in prior works [26, 28]")


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class PQCodec:
    """Trained PQ codebooks. codebooks: (m, 256, dsub) float32."""

    codebooks: Array

    @property
    def m(self) -> int:
        return self.codebooks.shape[0]

    @property
    def dsub(self) -> int:
        return self.codebooks.shape[2]

    @property
    def d(self) -> int:
        return self.m * self.dsub

    def tree_flatten(self):
        return (self.codebooks,), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


def split_subspaces(x: Array, m: int) -> Array:
    """(n, d) -> (m, n, dsub). Pads d up to a multiple of m with zeros.

    Zero padding is distance-neutral for L2 as long as queries are padded the
    same way (both sides contribute 0 to the squared difference).
    """
    n, d = x.shape
    dsub = -(-d // m)
    if dsub * m != d:
        x = jnp.pad(x, ((0, 0), (0, dsub * m - d)))
    return x.reshape(n, m, dsub).transpose(1, 0, 2)


def train_pq(data: Array, m: int, *, iters: int = 12, sample: int | None = 65536) -> PQCodec:
    """Train PQ codebooks on (n, d) data (paper: k-means per subspace)."""
    n = data.shape[0]
    if sample is not None and n > sample:
        # Deterministic strided subsample for codebook training (cheap + stable).
        data = data[:: max(n // sample, 1)][:sample]
    x_sub = split_subspaces(jnp.asarray(data, jnp.float32), m)
    codebooks = kmeans_per_subspace(x_sub, N_CLUSTERS, iters)
    return PQCodec(codebooks)


# Rows encoded per step: the (rows, m, 256) f32 distance block of one step
# stays near 512 MiB at m = 32, whatever the corpus size.
_ENCODE_ROWS = 16384


@jax.jit
def pq_encode(codec: PQCodec, data: Array) -> Array:
    """(n, d) -> (n, m) uint8 cluster ids (argmin centroid per subspace).

    Encodes `_ENCODE_ROWS` rows per step, so a corpus of any size never
    materialises its full (n, m, 256) distance array. The last step starts
    early enough to end at row n (it re-encodes a few rows identically),
    so the corpus is never padded or copied. The nearest centroid is
    `first_argmin` of one (rows, m, 256) distance block: `jnp.argmin` over
    a subspace-vmapped block returned wrong codes on a TPU v5e.
    """
    x = jnp.asarray(data, jnp.float32)
    n, d = x.shape
    m, dsub = codec.m, codec.dsub
    rows = min(n, _ENCODE_ROWS)
    cb = codec.codebooks                                          # (m, 256, dsub)
    cn = jnp.sum(cb * cb, -1)[None]                               # (1, m, 256)

    def encode(block):                                            # -> (rows, m)
        xb = jnp.pad(block, ((0, 0), (0, m * dsub - d))).reshape(rows, m, dsub)
        d2 = jnp.sum(xb * xb, -1)[..., None] + cn - 2.0 * jnp.einsum(
            "rjs,jcs->rjc", xb, cb, precision=jax.lax.Precision.HIGHEST
        )                                                         # (rows, m, 256)
        return first_argmin(d2)

    def step(s, codes):
        start = jnp.minimum(s * rows, n - rows)
        block = jax.lax.dynamic_slice_in_dim(x, start, rows)
        return jax.lax.dynamic_update_slice_in_dim(codes, encode(block), start, 0)

    codes = jax.lax.fori_loop(
        0, -(-n // rows), step, jnp.zeros((n, m), jnp.int32)
    )
    return codes.astype(jnp.uint8)


@jax.jit
def pq_decode(codec: PQCodec, codes: Array) -> Array:
    """(n, m) uint8 -> (n, m*dsub) reconstruction (centroid concat)."""
    # codebooks: (m, 256, dsub); codes.T: (m, n)
    gathered = jax.vmap(lambda cb, c: cb[c])(codec.codebooks, codes.T.astype(jnp.int32))
    return gathered.transpose(1, 0, 2).reshape(codes.shape[0], -1)


@jax.jit
def build_dist_table(codec: PQCodec, queries: Array) -> Array:
    """PQDistTable construction (paper §4.2).

    queries: (B, d) -> table (B, m, 256) of squared L2 distances from each
    query subvector to each centroid. Kept resident for the whole search.
    """
    q_sub = split_subspaces(jnp.asarray(queries, jnp.float32), codec.m)  # (m, B, dsub)

    def per_subspace(qs, cb):
        return (
            jnp.sum(qs * qs, -1, keepdims=True)
            + jnp.sum(cb * cb, -1)[None, :]
            - 2.0 * jnp.dot(qs, cb.T, precision=jax.lax.Precision.HIGHEST)
        )  # (B, 256)

    table = jax.vmap(per_subspace)(q_sub, codec.codebooks)  # (m, B, 256)
    return table.transpose(1, 0, 2)


def adc_gather(table: Array, codes: Array) -> Array:
    """ADC as one element gather of the table entries (`adc_distance` off
    the TPU)."""
    idx = codes.astype(jnp.int32)                                   # (B, R, m)
    # take_along_axis over the 256 axis: table (B, m, 256) -> (B, R, m)
    gathered = jnp.take_along_axis(
        table[:, None, :, :],                                       # (B, 1, m, 256)
        idx[:, :, :, None],                                         # (B, R, m, 1)
        axis=3,
    )[..., 0]
    # Added in subspace order, as the Pallas ADC kernels add them, so every
    # kernel mode scores a candidate bit-identically.
    acc = gathered[..., 0]
    for j in range(1, gathered.shape[-1]):
        acc = acc + gathered[..., j]
    return acc


def adc_onehot(table: Array, codes: Array) -> Array:
    """ADC as a one-hot select of each subspace's table row and a sum over
    its 256 lanes (`adc_distance` on the TPU).

    The lane sum has one non-zero term, so each lookup is exact, and the
    subspaces are added in order: bit-identical to `adc_gather` (and to the
    Pallas kernels' `adc_column`) for any table without -0.0 entries, which
    a table of squared distances never holds. One subspace at a time, so no
    (B, R, m, 256) array is ever formed.
    """
    idx = codes.astype(jnp.int32)                                   # (B, R, m)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, 1, N_CLUSTERS), 2)

    def lookup(j):                                                  # -> (B, R)
        hit = idx[:, :, j, None] == lane                            # (B, R, 256)
        return jnp.sum(jnp.where(hit, table[:, None, j, :], 0.0), axis=-1)

    acc = lookup(0)
    for j in range(1, idx.shape[-1]):
        acc = acc + lookup(j)
    return acc


@jax.jit
def adc_distance(table: Array, codes: Array) -> Array:
    """Asymmetric distance computation (paper §4.5).

    table: (B, m, 256) per-query PQ distance table.
    codes: (B, R, m) uint8 codes of each query's R candidate points.
    returns (B, R) approximate squared L2 distances.

    The formulation follows the platform the call is lowered for: the TPU
    lowers an element gather to a slow per-element path, so it gets
    `adc_onehot`; every other backend keeps the `adc_gather` it runs fast.
    Both give bit-identical distances.
    """
    return jax.lax.platform_dependent(
        table, codes, tpu=adc_onehot, default=adc_gather
    )


def quantization_error(codec: PQCodec, data: Array) -> float:
    """Mean squared reconstruction error (codec quality diagnostic)."""
    rec = pq_decode(codec, pq_encode(codec, data))
    d = data.shape[1]
    return float(jnp.mean(jnp.sum((rec[:, :d] - data) ** 2, axis=-1)))
