"""Re-ranking stage (paper §4.9).

PQ distances steer the traversal; the final answer quality comes from
re-computing *exact* L2 distances between each query and every candidate it
expanded during the search, then taking the true top-k. The paper reports a
10-15% recall gain from this stage, which our integration tests reproduce.

In BANG Base the full vectors live on the host and only the candidates' rows
cross the link ("only full vectors of selected nodes are sent to GPU") -- here
that is a pure_callback gather. In-memory variants gather from device HBM.
The exact-L2 + top-k math has a Pallas fast path (repro/kernels/rerank_l2).
The whole stage runs under the `bang.rerank` named scope (see
`core.search.STAGES`).
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.compat import pure_callback

from .worklist import INVALID_ID

Array = jax.Array


# Per-callback result budget for the host gather on XLA:CPU, in bytes. XLA:CPU
# farms any op touching >=128 KiB out to its intra-op threadpool; on a low-core
# host the pool's only thread can be the one parked inside the host callback,
# so a callback result that large, consumed by a parallelised kernel,
# deadlocks the runtime. Half the threshold keeps every chunk (and its
# consumer) inline. On the TPU the result's consumer is a device op, so the
# cap does not apply there, and every callback would cost a round trip of its
# own: the whole (B, C) block goes out in one.
_GATHER_CHUNK_BYTES = 64 * 1024


def gather_chunk_rows(d: int) -> int:
    """Rows of width `d` per XLA:CPU host-gather callback (_GATHER_CHUNK_BYTES)."""
    return max(1, _GATHER_CHUNK_BYTES // (d * 4))


def gather_host_vectors(
    data_np: np.ndarray, ids: Array, *, chunk_rows: int | None = None,
    tracer: Callable | None = None,
) -> Array:
    """Host-side candidate-vector service (BANG Base link traffic).

    The gather's pure_callbacks follow the platform the call is lowered for
    (`jax.lax.platform_dependent`): on the TPU one callback returns the
    whole block; elsewhere a sequence of bounded-size ones, each result
    under XLA:CPU's parallel-consumer threshold (see _GATHER_CHUNK_BYTES).
    An explicit `chunk_rows` chunks on every platform. Every form runs the
    same host gather, so the vectors are the same bits.

    `tracer` returns the telemetry `Tracer` to record into, or None. Each
    callback body calls it, so attaching a tracer later changes no traced
    program; while one is attached, every callback records a
    `rerank_gather` span (args: rows).
    """
    d = data_np.shape[1]

    def gather(idx: np.ndarray) -> np.ndarray:
        safe = np.where(idx == np.int32(2**31 - 1), 0, idx)
        return np.ascontiguousarray(data_np[safe], dtype=np.float32)

    host_gather = gather
    if tracer is not None:
        def host_gather(idx: np.ndarray) -> np.ndarray:
            tr = tracer()
            if tr is None:
                return gather(idx)
            with tr.span("rerank_gather", track="rerank", rows=int(idx.size)):
                return gather(idx)

    def gather_chunks(ids: Array, rows: int) -> Array:
        flat = ids.reshape(-1)
        total = flat.shape[0]
        if total <= rows:
            shape = jax.ShapeDtypeStruct((*ids.shape, d), jnp.float32)
            return pure_callback(host_gather, shape, ids)
        pieces = [
            pure_callback(
                host_gather,
                jax.ShapeDtypeStruct((min(rows, total - s), d), jnp.float32),
                flat[s : s + rows],
            )
            for s in range(0, total, rows)
        ]
        return jnp.concatenate(pieces, 0).reshape(*ids.shape, d)

    if chunk_rows is not None:
        return gather_chunks(ids, chunk_rows)
    return jax.lax.platform_dependent(
        ids, tpu=lambda i: gather_chunks(i, i.size),
        default=lambda i: gather_chunks(i, gather_chunk_rows(d)))


def exact_topk(
    queries: Array,
    cand_vecs: Array,
    cand_ids: Array,
    k: int,
    *,
    use_kernels: bool = False,
) -> tuple[Array, Array]:
    """Exact squared-L2 re-rank: top-k of candidates per query.

    queries (B, d), cand_vecs (B, C, d), cand_ids (B, C) with INVALID padding.
    Returns (ids (B, k), dists (B, k)) ascending.
    """
    if use_kernels:
        from repro.kernels.rerank_l2 import ops as rr_ops

        d2 = rr_ops.exact_sq_dists(queries, cand_vecs)
    else:
        diff = cand_vecs.astype(jnp.float32) - queries.astype(jnp.float32)[:, None]
        d2 = jnp.sum(diff * diff, -1)
    d2 = jnp.where(cand_ids == INVALID_ID, jnp.inf, d2)
    # Dedup: the same node can appear at most once in history by construction
    # (bloom filter), so no mask needed beyond padding.
    neg_top, pos = jax.lax.top_k(-d2, k)
    ids = jnp.take_along_axis(cand_ids, pos, axis=-1)
    return ids, -neg_top


def rerank(
    queries: Array,
    history_ids: Array,
    k: int,
    *,
    data: Array | None = None,
    data_np: np.ndarray | None = None,
    use_kernels: bool = False,
    tracer: Callable | None = None,
) -> tuple[Array, Array]:
    """Full re-rank stage: gather candidate vectors, exact top-k.

    Exactly one of data (device) / data_np (host) must be provided. Host
    gathers take one callback on the TPU and 64 KiB chunks elsewhere (see
    gather_host_vectors, which also says what `tracer` does).
    """
    assert (data is None) != (data_np is None)
    with jax.named_scope("bang.rerank"):
        if data is not None:
            safe = jnp.where(history_ids == INVALID_ID, 0, history_ids)
            vecs = data[safe].astype(jnp.float32)
        else:
            vecs = gather_host_vectors(data_np, history_ids, tracer=tracer)
        return exact_topk(queries, vecs, history_ids, k,
                          use_kernels=use_kernels)
