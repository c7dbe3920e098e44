"""Pod-scale BANG: the sharded-graph search (DESIGN.md §2, §6).

The paper keeps the graph + full vectors in host RAM (far memory) and the PQ
codes in GPU HBM (near memory), moving only O(frontier) bytes per hop over
PCIe. At pod scale the same split maps onto the TPU memory hierarchy: the
graph, codes, and full vectors are *sharded over the `model` mesh axis* (a
260 GB graph is ~0.5 GB/chip on 512 chips), queries are sharded over
(`pod`, `data`), and each hop exchanges only the frontier:

    neighbour fetch   : owner-shard gather + psum(model)    -- (B_loc, R) int32
    ADC distances     : owner-shard ADC     + psum(model)   -- (B_loc, R) f32
    worklist / bloom  : replicated per model shard (tiny, zero comms)
    re-rank           : owner-shard partial exact-L2 + psum

The neighbour fetch has two placements: `sharded_neighbor_fn` gathers from
device-sharded adjacency (the in-memory configuration), while
`host_shard_neighbor_fn` keeps each shard's graph block in *host RAM* behind
a per-shard `pure_callback` (the paper's CPU neighbour service at mesh
scale: only frontier ids cross the host link out, only adjacency rows come
back) -- same ownership math, bit-identical results.

Each valid node id is owned by exactly one shard (contiguous row sharding),
so a masked psum reconstructs the full row exchange -- the ragged all-to-all
of the paper's CPU service, expressed as a dense collective XLA can schedule
and overlap. The distance psum sends R floats per query per hop instead of
R·m code bytes: computing ADC *at the owner* is the pod-scale analogue of
"send only the bare minimum over the link" (§4.3).

These functions are designed to run INSIDE shard_map (via `repro.compat`);
`bang_search` is reused unchanged with sharded neighbour/distance callbacks.
`repro.runtime.sharded.ShardedSearchExecutor` wraps this block in the
serving contract (shape buckets, compiled cache, dispatch/finish).
"""
from __future__ import annotations

import functools
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.compat import pure_callback, shard_map

from . import pq as pqlib
from .search import SearchConfig, SearchResult, bang_search, make_step_fn
from .worklist import INVALID_ID

Array = jax.Array


def _owned_at(shard, local_n: int, ids: Array) -> tuple[Array, Array]:
    """(relative ids, ownership mask) for shard `shard` of contiguous rows.

    Pure in `shard` (an int or traced scalar) so ownership is unit-testable
    without a mesh: over shards 0..S-1, every id in [0, S*local_n) is owned
    exactly once, and INVALID/negative/out-of-range ids are owned by nobody.
    """
    lo = jnp.asarray(shard, jnp.int32) * local_n
    rel = ids - lo
    own = (rel >= 0) & (rel < local_n) & (ids != INVALID_ID) & (ids >= 0)
    return jnp.clip(rel, 0, local_n - 1), own


def _owned(local_n: int, ids: Array, axis: str) -> tuple[Array, Array]:
    """(relative ids, ownership mask) for globally-sharded contiguous rows."""
    return _owned_at(jax.lax.axis_index(axis), local_n, ids)


def sharded_neighbor_fn(adjacency_local: Array, axis: str = "model"):
    """Frontier adjacency fetch: owner gather + psum (Algorithm 2 line 5/6)."""
    n_loc, R = adjacency_local.shape

    def fn(u: Array) -> Array:
        rel, own = _owned(n_loc, u, axis)
        rows = adjacency_local[rel]                       # (B, R)
        # Shift by +1 so "0" is the neutral element of the psum (pad = -1).
        contrib = jnp.where(own[:, None], rows + 1, 0)
        summed = jax.lax.psum(contrib, axis)
        return summed - 1

    return fn


def host_shard_service(
    partition: np.ndarray, rel: np.ndarray, own: np.ndarray
) -> np.ndarray:
    """One shard's host-RAM adjacency contribution (numpy, runs per callback).

    `rel`/`own` come from `_owned_at`: only owned lanes index `partition`
    (sentinel/padded/out-of-shard ids never touch host memory -- the property
    tests/test_sharded_base.py pins), and the +1 shift makes 0 the neutral
    element of the cross-shard psum (pad neighbours are -1).
    """
    rel = np.asarray(rel)
    own = np.asarray(own, bool)
    out = np.zeros((rel.shape[0], partition.shape[1]), np.int32)
    out[own] = partition[rel[own]] + 1
    return out


def host_shard_neighbor_fn(
    partitions: Sequence[np.ndarray], axis: str = "model"
) -> Callable:
    """Sharded BANG Base: each model shard's graph block stays in host RAM.

    The JAX-native analogue of the paper's per-GPU CPU neighbour service
    (§4.1) at mesh scale: each shard ships its (B_loc,) frontier ids to *its
    own* host partition through `pure_callback`, the host gathers only the
    rows that shard owns (`_owned_at` contiguous ownership), and a masked
    psum over `axis` reconstructs the full (B_loc, R) row exchange -- so the
    device never holds the adjacency, and per hop the host link carries only
    frontier ids out and adjacency rows back.

    `partitions[s]` must be the contiguous rows [s*n_loc, (s+1)*n_loc) of the
    (padded) adjacency; results are bit-identical to `sharded_neighbor_fn`
    over the concatenated array.

    This inline single-shot callback is the synchronous oracle path; the
    serving executors can replace it with the async host-I/O subsystem
    (`repro.runtime.hostio`: multi-worker service + device-resident hot
    cache + prefetched exchange, same ownership math, bit-exact results).
    """
    parts = [np.ascontiguousarray(np.asarray(p, np.int32)) for p in partitions]
    n_loc, R = parts[0].shape
    if any(p.shape != (n_loc, R) for p in parts):
        raise ValueError("host partitions must share one (n_loc, R) shape")

    def host_gather(shard: np.ndarray, rel: np.ndarray, own: np.ndarray):
        return host_shard_service(parts[int(shard)], rel, own)

    def fn(u: Array) -> Array:
        shard = jax.lax.axis_index(axis)
        rel, own = _owned_at(shard, n_loc, u)
        res = jax.ShapeDtypeStruct((u.shape[0], R), jnp.int32)
        contrib = pure_callback(host_gather, res, shard, rel, own)
        return jax.lax.psum(contrib, axis) - 1

    return fn


def sharded_adc_distance_fn(
    table: Array,
    codes_local: Array,
    axis: str = "model",
    use_kernels: bool = False,
    *,
    kernel_mode: str | None = None,
    codes_tile_rows: int = 0,
):
    """Owner-computed ADC distances + psum (§4.5 at pod scale).

    table: (B, m, 256) replicated over `axis`; codes_local: (n_loc, m).
    kernel_mode (falls back to the legacy use_kernels flag):

      "reference"  XLA gather + `pq.adc_distance` ADC (one-hot select on
                   the TPU, take_along_axis elsewhere)
      "staged"     XLA gather into a (B, R, m) HBM temporary + pq_adc kernel
      "fused"      search_step.local_adc -- the fetch happens *inside* the
                   kernel on the shard's packed codes (VMEM-resident while
                   they fit the budget, fetched from HBM by row DMA beyond
                   it -- `codes_tile_rows` follows codes_resident),
                   masked to the rows this shard owns; no HBM temporary.

    All three contribute bit-identical owner rows (0 elsewhere), so the psum
    reconstruction -- and therefore the traversal -- is mode-independent.
    """
    n_loc = codes_local.shape[0]
    mode = kernel_mode or ("staged" if use_kernels else "reference")
    if mode == "fused":
        from repro.kernels.search_step import ops as step_ops

        lines_local = step_ops.code_lines(codes_local)   # once, outside the loop

    def fn(ids: Array, valid: Array) -> Array:
        rel, own = _owned(n_loc, ids, axis)
        if mode == "fused":
            d = step_ops.local_adc(
                table, lines_local, n_loc, rel, own, tile_rows=codes_tile_rows
            )
        elif mode == "staged":
            from repro.kernels.pq_adc import ops as adc_ops

            gathered = codes_local[rel]                   # (B, R, m)
            d = adc_ops.adc(table, gathered, own)
        else:
            gathered = codes_local[rel]                   # (B, R, m)
            d = pqlib.adc_distance(table, gathered)
        d = jnp.where(own & valid, d, 0.0)
        d = jax.lax.psum(d, axis)
        return jnp.where(valid, d, jnp.inf)

    return fn


def sharded_exact_dists(
    queries: Array, data_local: Array, ids: Array, axis: str = "model"
) -> Array:
    """Owner-computed exact squared L2 + psum (re-rank stage, §4.9)."""
    n_loc = data_local.shape[0]
    rel, own = _owned(n_loc, ids, axis)
    diff = data_local[rel].astype(jnp.float32) - queries.astype(jnp.float32)[:, None]
    d2 = jnp.where(own, jnp.sum(diff * diff, -1), 0.0)    # same math as exact_topk
    d2 = jax.lax.psum(d2, axis)
    return jnp.where(ids == INVALID_ID, jnp.inf, d2)


def sharded_bang_search_block(
    queries: Array,          # (B_loc, d)      sharded over data axes
    table: Array,            # (B_loc, m, 256) sharded over data axes
    codes_local: Array,      # (n_loc, m)      sharded over model axis
    adjacency_local: Array | None,  # (n_loc, R) sharded over model axis,
                             # or None when `neighbor_fn` serves the graph
    data_local: Array,       # (n_loc, d)      sharded over model axis
    medoid: int,
    k: int,
    cfg: SearchConfig,
    axis: str = "model",
    rerank: bool = True,
    neighbor_fn: Callable | None = None,
    prefetch_fn: Callable | None = None,
    tombstone_fn: Callable | None = None,
) -> tuple[Array, Array, Array, Array]:
    """The per-shard body: full BANG pipeline on sharded state.

    The graph source is pluggable: by default adjacency rows come from the
    device-sharded `adjacency_local` (`sharded_neighbor_fn`); the sharded
    base variant instead passes `neighbor_fn=host_shard_neighbor_fn(...)`
    (adjacency stays in host RAM, `adjacency_local=None`), or -- when the
    hostio subsystem serves the graph -- the multi-worker
    `repro.runtime.hostio.make_shard_exchange` pair, whose `prefetch_fn`
    double-buffers each shard's host gather behind the device merge. PQ
    codes and re-rank vectors are device-sharded either way.

    `tombstone_fn` (streaming mutability) masks deleted ids out of each
    hop's validity mask before the StepFn -- the bitmap it closes over is
    *replicated* per shard (n bytes, R·4x smaller than the graph it guards),
    so every model shard of a data group applies the identical mask and the
    replicated-worklist invariant is preserved.

    Returns (ids (B_loc, k), dists (B_loc, k), n_hops (B_loc,),
    n_iters (B_loc,)) -- all replicated over `axis` (the worklist/bloom state
    is replicated per model shard, so every shard of a model group computes
    identical results). `n_iters` is the scalar iteration count broadcast to
    the local batch so it can share the data-sharded output spec.
    """
    if neighbor_fn is None:
        neighbor_fn = sharded_neighbor_fn(adjacency_local, axis)
    # The same StepFn boundary as the single-device loop: the fused mode runs
    # owner-shard gather+ADC inside search_step.local_adc, the psum crosses
    # the mesh, and sort+select+merge run in the fused traverse kernel on the
    # reconstructed rows.
    distance_fn = sharded_adc_distance_fn(
        table, codes_local, axis, kernel_mode=cfg.resolved_kernel_mode(),
        codes_tile_rows=cfg.codes_tile_rows,
    )
    res: SearchResult = bang_search(
        queries,
        neighbor_fn=neighbor_fn,
        step_fn=make_step_fn(cfg, distance_fn),
        medoid=medoid,
        n_points=codes_local.shape[0],  # local; only used for sizing hints
        cfg=cfg,
        prefetch_fn=prefetch_fn,
        tombstone_fn=tombstone_fn,
    )
    if rerank:
        # Re-rank (§4.9) stays sharded: each shard scores only the expanded
        # candidates it owns, a masked psum rebuilds the exact distances.
        d2 = sharded_exact_dists(queries, data_local, res.history_ids, axis)
        neg_top, pos = jax.lax.top_k(-d2, k)
        ids = jnp.take_along_axis(res.history_ids, pos, axis=-1)
        dists = -neg_top
    else:
        ids = res.worklist.ids[:, :k]
        dists = res.worklist.dists[:, :k]
    n_iters = jnp.broadcast_to(res.n_iters, res.n_hops.shape)
    return ids, dists, res.n_hops, n_iters


def make_sharded_search(
    mesh: Mesh,
    medoid: int,
    k: int,
    cfg: SearchConfig,
    *,
    data_axes: Sequence[str] = ("data",),
    model_axis: str = "model",
):
    """Build the jitted pod-scale search fn over `mesh`.

    Input shardings:  queries (B, d)   P(data_axes, None)
                      codes   (n, m)   P(model_axis, None)
                      adjacency (n, R) P(model_axis, None)
                      data    (n, d)   P(model_axis, None)
                      codebooks        replicated
    Output:           ids/dists (B, k) P(data_axes, None)
    """
    dspec = tuple(data_axes) if len(data_axes) > 1 else data_axes[0]

    def fn(queries, codebooks, codes, adjacency, data):
        table = pqlib.build_dist_table(pqlib.PQCodec(codebooks), queries)
        ids, dists, _, _ = sharded_bang_search_block(
            queries, table, codes, adjacency, data, medoid, k, cfg, model_axis
        )
        return ids, dists

    sharded = shard_map(
        fn,
        mesh=mesh,
        in_specs=(
            P(dspec, None),          # queries
            P(),                     # codebooks (replicated)
            P(model_axis, None),     # codes
            P(model_axis, None),     # adjacency
            P(model_axis, None),     # data
        ),
        out_specs=(P(dspec, None), P(dspec, None)),
        check_rep=False,
    )
    return jax.jit(sharded)


def pad_to_multiple(x, multiple: int, fill):
    """Pad axis-0 so row-sharding divides evenly; fill must be search-neutral."""
    import numpy as np

    n = x.shape[0]
    pad = (-n) % multiple
    if pad == 0:
        return x
    return np.concatenate([x, np.full((pad, *x.shape[1:]), fill, x.dtype)], 0)
