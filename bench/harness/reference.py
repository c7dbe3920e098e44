"""The plain reference: exact k nearest neighbours under squared L2.

Written for the benchmark alone: it imports nothing of the program and takes
nothing the program made. Queries go in blocks, the corpus in chunks, with a
running top-k; matmuls ask for `Precision.HIGHEST`, so float32 inputs are
not rounded to one bfloat16 pass on a TPU. Each chunk's selection is the
TPU's fast approximate one, and a second pass proves every query's result
exact (see `_knn_block_verified`); a query it cannot prove is redone with a
full top-k. `exact_sq_dists` recomputes the
distance of given ids in float64 on the host, for the distance check.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def _chunk_d2(data, queries, qn, s, chunk: int, precision):
    """Squared distances to corpus rows [start, start + chunk) and their
    ids; the last chunk starts early, and rows an earlier chunk scored are
    +inf."""
    n = data.shape[0]
    start = jnp.minimum(s * chunk, n - chunk)
    x = jax.lax.dynamic_slice_in_dim(data, start, chunk)
    ids = start + jnp.arange(chunk, dtype=jnp.int32)
    d2 = qn + jnp.sum(x * x, -1)[None, :] - 2.0 * jnp.dot(
        queries, x.T, precision=precision)
    return jnp.where(ids[None, :] >= s * chunk, d2, jnp.inf), ids


def _merge(best, d, i, k: int):
    neg, pos = jax.lax.top_k(-jnp.concatenate([best[0], d], 1), k)
    return -neg, jnp.take_along_axis(jnp.concatenate([best[1], i], 1), pos, 1)


@functools.partial(jax.jit, static_argnames=("k", "chunk", "precision"))
def _knn_block(data, queries, k: int, chunk: int,
               precision=jax.lax.Precision.HIGHEST):
    """Exact top-k by a full sort of every chunk (the slow, plain form)."""
    steps = -(-data.shape[0] // chunk)
    qn = jnp.sum(queries * queries, -1)[:, None]

    def step(best, s):
        d2, ids = _chunk_d2(data, queries, qn, s, chunk, precision)
        neg, pos = jax.lax.top_k(-d2, k)
        return _merge(best, -neg, ids[pos], k), None

    B = queries.shape[0]
    init = (jnp.full((B, k), jnp.inf), jnp.full((B, k), -1, jnp.int32))
    (d, i), _ = jax.lax.scan(step, init, jnp.arange(steps))
    return i, d


@functools.partial(jax.jit, static_argnames=("k", "chunk", "precision"))
def _knn_block_verified(data, queries, k: int, chunk: int,
                        precision=jax.lax.Precision.HIGHEST):
    """Top-k from approximate per-chunk selection, and a proof per query.

    Pass 1 keeps each chunk's approximate k smallest and merges them
    exactly. Pass 2 recomputes every distance and counts those at or below
    the k-th found (with a relative margin of 1e-5). A count of exactly k
    proves the found set is the exact top-k; any other count sends the
    query to `_knn_block`.
    """
    steps = -(-data.shape[0] // chunk)
    qn = jnp.sum(queries * queries, -1)[:, None]

    def find(best, s):
        d2, ids = _chunk_d2(data, queries, qn, s, chunk, precision)
        d, pos = jax.lax.approx_min_k(d2, k, recall_target=0.999)
        return _merge(best, d, ids[pos], k), None

    B = queries.shape[0]
    init = (jnp.full((B, k), jnp.inf), jnp.full((B, k), -1, jnp.int32))
    (d, i), _ = jax.lax.scan(find, init, jnp.arange(steps))
    tau = d[:, -1:] * (1.0 + 1e-5)

    def count(c, s):
        d2, _ = _chunk_d2(data, queries, qn, s, chunk, precision)
        return c + jnp.sum(d2 <= tau, 1, dtype=jnp.int32), None

    c, _ = jax.lax.scan(count, jnp.zeros((B,), jnp.int32), jnp.arange(steps))
    return i, d, c


def exact_knn(data, queries: np.ndarray, k: int, *, block: int = 1024,
              chunk: int = 65536, precision=jax.lax.Precision.HIGHEST,
              info: dict | None = None) -> tuple[np.ndarray, np.ndarray]:
    """(q, k) ids and squared distances of each query's k nearest points.

    `data` may be a device array; queries are scored `block` at a time (the
    last block is padded by repeating a row, and the padding is dropped).
    Queries the proof pass cannot prove are redone 128 at a time with the
    full sort; `info["unproven"]` counts them.
    """
    data = jnp.asarray(data, jnp.float32)
    q = np.asarray(queries, np.float32)
    chunk = min(chunk, data.shape[0])
    ids = np.empty((len(q), k), np.int32)
    dists = np.empty((len(q), k), np.float32)
    unproven = []
    for s in range(0, len(q), block):
        part = q[s:s + block]
        rows = len(part)
        if rows < block:
            part = np.concatenate([part, np.repeat(part[-1:], block - rows, 0)])
        i, d, c = _knn_block_verified(data, jnp.asarray(part), k, chunk,
                                      precision)
        ids[s:s + rows] = np.asarray(i)[:rows]
        dists[s:s + rows] = np.asarray(d)[:rows]
        unproven.extend(s + np.nonzero(np.asarray(c)[:rows] != k)[0])
    if info is not None:
        info["unproven"] = info.get("unproven", 0) + len(unproven)
    redo = 128
    for s in range(0, len(unproven), redo):
        rows = np.asarray(unproven[s:s + redo])
        part = q[rows]
        if len(rows) < redo:
            part = np.concatenate([part, np.repeat(part[-1:],
                                                   redo - len(rows), 0)])
        i, d = _knn_block(data, jnp.asarray(part), k, chunk, precision)
        ids[rows] = np.asarray(i)[: len(rows)]
        dists[rows] = np.asarray(d)[: len(rows)]
    return ids, dists


def exact_sq_dists(data_np: np.ndarray, queries: np.ndarray,
                   ids: np.ndarray) -> np.ndarray:
    """float64 squared L2 from each query to each of its ids (-1 -> inf)."""
    q = np.asarray(queries, np.float64)
    out = np.full(ids.shape, np.inf)
    ok = ids >= 0
    for j in range(ids.shape[1]):
        rows = np.nonzero(ok[:, j])[0]
        diff = data_np[ids[rows, j]].astype(np.float64) - q[rows]
        out[rows, j] = np.einsum("ij,ij->i", diff, diff)
    return out


def recall_at_k(found: np.ndarray, truth: np.ndarray) -> np.ndarray:
    """Per-query |found ∩ truth| / k (k = truth's width)."""
    k = truth.shape[1]
    return np.array([len(set(f.tolist()) & set(t.tolist())) / k
                     for f, t in zip(found, truth)])


def local_intrinsic_dim(sq_dists: np.ndarray) -> float:
    """MLE (Levina-Bickel) local intrinsic dimension, averaged over queries.

    `sq_dists` is (q, k) squared distances to each query's k nearest points,
    ascending; the estimate uses the plain distances.
    """
    r = np.sqrt(np.maximum(np.asarray(sq_dists, np.float64), 1e-30))
    logs = np.log(r[:, :-1] / r[:, -1:])
    return float(np.mean(-1.0 / np.mean(logs, 1)))
