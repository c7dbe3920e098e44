"""BangIndex: the paper's three-stage pipeline behind one public API.

    Stage 1  Distance-table construction   (§4.2, Pallas pq_table kernel)
    Stage 2  ANN search                    (§4.1-4.8, repro.core.search)
    Stage 3  Re-ranking                    (§4.9, repro.core.rerank)

Variant x placement matrix (`search(variant=...)`): distances down, graph
placement across. Every cell returns bit-exact ids+dists vs its row-mates
(the PQ cells re-rank with exact L2, so their outputs agree bitwise); each
cell also takes a `kernel_mode` -- kernels change the schedule, not the
variant semantics, and all three modes return bit-identical neighbour ids.

    distances \\ placement   single device        mesh-sharded (mesh=...)
    ----------------------  -------------------  ------------------------
    PQ, graph on device     "inmem"              "sharded"
    PQ, graph in host RAM   "base"               "sharded-base"
    exact, no re-rank       "exact"              --

    kernel_mode \\ variant   inmem / base / exact   sharded / sharded-base
    ----------------------  ---------------------  -------------------------
    "reference" (default)   pure-XLA body          XLA gather ADC + psum
    "staged"                per-stage Pallas       pq_adc kernel + psum,
                            kernels (ADC, sort,    bitonic sort/merge
                            merge; HBM between)
    "fused"                 search_step mega-      owner-shard fused
                            kernel: whole hop in   gather+ADC kernel + psum,
                            one pallas_call,       fused traverse kernel
                            in-kernel code gather  ("exact" keeps L2 outside
                                                   the kernel either way)

"base"/"sharded-base" are BANG proper (paper §5): the graph stays in host
RAM behind pure_callback neighbour services (one per model shard in the
sharded case) and only frontier ids / adjacency rows cross the host link.
"inmem"/"sharded" are BANG In-memory; "exact" is BANG Exact-distance.
Legacy `SearchConfig(use_kernels=True)` is an alias for
`kernel_mode="staged"`.

Beyond-VMEM regime (fallback rules): "fused" NEVER silently falls back to
"staged". When the PQ-codes block exceeds the VMEM budget
(`REPRO_VMEM_BUDGET` env, 16 MiB default -- the billion-scale shard regime)
the megakernel keeps the packed code lines in HBM and fetches each
candidate's 512-byte line by DMA, so a hop reads B x R lines, not the
block; results stay bit-exact vs the resident kernel and every other mode.
`SearchConfig.codes_tile_rows` > 0 forces that HBM placement (0 = decided
from the budget); `repro.kernels.autotune` sweeps the placement with the
eager/lazy §4.6 selection flavour per batch bucket and persists winners
as JSON keyed by (device kind, bucket, R, m), which executors built with
`autotune=` apply inside the compile-cache key. A missing/corrupt winners
file degrades to default configs with a warning.

The host-graph cells additionally take `hostio=HostIOConfig(...)` (the async
host-I/O subsystem, `repro.runtime.hostio`) -- the paper's CPU half as a
first-class service instead of an inline callback. Orthogonal to both axes
above and bit-exact in every cell x kernel mode:

    hostio knob \\ effect     base / sharded-base
    -----------------------  -------------------------------------------
    workers=N                multi-worker host gather service: N threads
                             per graph partition drain a request queue
                             (queue-depth/latency counters)
    hot_cache_rows=H         top-in-degree adjacency rows pinned in device
                             memory; hits skip the host link entirely
                             (measured hit rate + bytes saved in
                             exchange_bytes_per_hop)
    prefetch=True            double-buffered frontier exchange: hop k+1's
                             §4.6 eager candidate gather is issued while
                             the device merges hop k (measured
                             overlap_fraction)

Mutability semantics (`repro.runtime.mutation.MutableBangIndex`): a
`BangIndex` itself is immutable -- every executor closes over a frozen
snapshot. Streaming inserts/deletes layer on top of it:

  * deletes tombstone ids in a bitmap that rides every dispatch as an
    executable *operand*; a tombstoned id scores +inf in the §4.6 selection
    and can never enter 𝓛, the re-rank history, or the top-k, in any
    variant or kernel_mode;
  * inserts accumulate in a small delta set, searched exactly and fused
    into the main results with `worklist.merge_worklist` (PQ variants must
    `rerank=True` while delta points are live -- fusion needs exact-space
    distances);
  * `consolidate()` folds both back into a *new* BangIndex (robust_prune
    re-linking around deleted nodes, build-rule insertion of delta points)
    and swaps it in as a new generation.

Cache-invalidation contract: every mutation bumps the executor-visible
`mutation_epoch`, which scopes the `ServePipeline` query-result LRU (stale
hits are impossible -- the next drain drops the cache); consolidation bumps
`generation`, which keys the compiled-executable cache (old executables are
dropped, never served) and `refresh()`es retiring hostio hot-adjacency
caches so pinned rows always mirror the host partitions.

Failure-mode x handling matrix (`repro.runtime.resilience`, enabled via
`HostIOConfig(resilience=ResilienceConfig(...))` on the host-graph cells
plus `ServePipeline(max_queue=, deadline_s=)` for admission control).
Every fault below is reproducible through the seeded `FaultInjector`, the
handling is host-side only (the traced program never changes with health,
so recovery is structurally bit-exact), and each row names the counters
that surface in `ServeStats`:

    fault \\ contract         handling                     counters
    -----------------------  ---------------------------  ----------------
    transient gather error   retry w/ exponential         retries,
                             backoff (deadline-capped);   gather_failures
                             result bit-exact
    stalled worker / pool    hedged re-issue: bounded     hedged_gathers,
    (slow gather)            wait, then inline re-gather  deadline_hits
                             on the caller; bit-exact,
                             never blocks past budget
    worker crash             item requeued before the     worker_deaths
                             thread dies; pool mate or
                             hedge completes it -- zero
                             queries lost
    host partition down,     reads served from pinned     failovers,
    failover replica         replica by surviving         failover_gathers
                             workers; bit-exact
    host partition down,     degraded serving: hot-cache  degraded_lanes,
    no replica               hits unaffected, other       partitions_down
                             lanes get the medoid row
                             ("medoid": restart toward
                             centre) or -1 rows ("mask":
                             dropped like tombstones);
                             recall degrades, measured
                             via ServeStats.mean_recall
    queue overflow (host     enqueue rejected -> caller   enqueue_
    pool)                    gathers inline; no loss      rejections
    serve-queue overload     submit() sheds past          shed_queries
                             max_queue, exactly once,
                             at admission
    request deadline passed  dropped at dispatch, result  expired_queries
                             slots stay (-1, inf)
    partition recovery       primary reads resume;        recoveries
                             results bit-exact vs the
                             fault-free run

Observability (`repro.runtime.telemetry`): one `Telemetry` bundle attaches
to the whole serving stack (`ServePipeline(telemetry=...)` forwards to the
executor, the host-I/O service and -- via `MutableBangIndex.set_telemetry`
-- the mutation layer) and never perturbs it: telemetry is executor
*state*, outside every compile-cache key, so the traced programs and their
results are byte-identical attached or detached. Four components:

  * metrics registry (always on): cumulative counters/gauges/histograms,
    exported by `to_json()` (schema-versioned) and `to_prom()` (Prometheus
    text exposition). Families: `bang_serve_*` (queries/shed/expired/
    batches/result_cache_hits `_total` counters, `compile_seconds_total`,
    `latency_seconds` histogram, `qps`/`recall` last-window gauges),
    `bang_hostio_<counter>_total` for every NeighborService counter plus
    `max_queue_depth` (high-watermark gauge), `gather_seconds_total`/
    `gather_hidden_seconds_total`/`request_latency_seconds_total`, and the
    hot-cache gauges (`hot_cache_rows`/`device_bytes`/`refreshes`), and
    `bang_mutation_*` (inserts/deletes/consolidations counters, epoch/
    generation gauges). Per-drain windows surface as `ServeStats.
    telemetry` (a `registry.delta()` view over the cumulative registry).
  * tracer (opt-in): Chrome trace-event JSON timeline; span vocabulary in
    `repro.runtime.telemetry.tracing` -- `request`/`request_shed`/
    `request_expired` (exactly one per submitted row), `admission`/
    `dispatch`/`device`/`compile` batch spans, per-partition `gather`/
    `prefetch_gather` hostio spans, `consolidate` mutation spans, and
    `failover`/`partition_down`/`recover`/`degraded`/`deadline_hit`
    resilience instants.
  * hop profiler (opt-in): per-hop host-gather wall time, frontier
    occupancy, cache-hit lanes, and the modeled codes-stream bytes/hop at
    the host-callback seams the traversal already crosses.
  * flight recorder (opt-in): bounded event ring; every resilience
    transition (failover/partition-down/degrade/deadline) triggers a
    structured postmortem dump (`schema_version`, `reason`, `context`,
    ring `events`, registry `metrics` snapshot).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from . import pq as pqlib
from .search import SearchConfig
from .vamana import VamanaGraph, build_vamana

Array = jax.Array


@dataclasses.dataclass
class SearchStats:
    n_iters: int
    mean_hops: float
    p95_hops: float
    wall_s: float        # steady-state wall time: dispatch -> results ready
    qps: float           # batch / wall_s (excludes compile)
    compile_s: float = 0.0  # trace+compile paid by this call (0 on cache hit)
    batch: int = 0       # true batch size
    bucket: int = 0      # padded shape bucket the executable was built for


@dataclasses.dataclass
class BangIndex:
    """An immutable ANNS index over a dataset (codec + codes + graph)."""

    codec: pqlib.PQCodec
    codes: Array                 # (n, m) uint8, device-resident (the 74 GB star)
    graph: VamanaGraph           # host adjacency (base) / copied to device (inmem)
    data_np: np.ndarray          # host full vectors (base re-rank source)
    data_dev: Array | None = None  # device full vectors (inmem/exact variants)
    _executors: dict[str, Any] = dataclasses.field(
        default_factory=dict, repr=False, compare=False,
    )

    # ------------------------------------------------------------------ build
    @classmethod
    def build(
        cls,
        data: np.ndarray,
        *,
        m: int = 16,
        R: int = 32,
        L_build: int = 64,
        alpha: float = 1.2,
        kmeans_iters: int = 12,
        seed: int = 0,
        keep_device_data: bool = True,
        graph: VamanaGraph | None = None,
    ) -> "BangIndex":
        data = np.asarray(data, np.float32)
        data_dev = jnp.asarray(data)          # one upload serves all three uses
        codec = pqlib.train_pq(data_dev, m, iters=kmeans_iters)
        codes = pqlib.pq_encode(codec, data_dev)
        if graph is None:
            graph = build_vamana(data, R=R, L=L_build, alpha=alpha, seed=seed)
        return cls(
            codec=codec,
            codes=codes,
            graph=graph,
            data_np=data,
            data_dev=data_dev if keep_device_data else None,
        )

    @property
    def n(self) -> int:
        return self.codes.shape[0]

    # ----------------------------------------------------------------- search
    def executor(
        self, variant: str = "inmem", *, mesh=None, hostio=None,
        autotune=None,
    ):
        """The jit-cached executor serving this index for `variant`.

        Executors are created lazily and cached per variant; device state
        (codes, codebooks, adjacency, vectors) is uploaded once and shared —
        the inmem and exact executors reuse the same device adjacency.

        `variant="sharded"` returns a `ShardedSearchExecutor` over `mesh`
        (index state sharded over the mesh's `model` axis, queries over
        `data`); `variant="sharded-base"` is the same executor with the
        graph kept in host RAM, row-partitioned per model shard behind
        per-shard callbacks (no device adjacency upload). With `mesh=None`
        either builds a default 1 x n_devices ("data", "model") mesh — the
        whole graph spread over every local device. Sharded executors are
        cached per (variant, mesh), so the two sharded variants never share
        (or alias) executor state even on the same mesh.

        `hostio=HostIOConfig(...)` (host-graph variants only) serves the
        graph through the async host-I/O subsystem — multi-worker neighbour
        service, device-resident hot-adjacency cache, prefetched frontier
        exchange — instead of the inline synchronous callbacks; executors
        are cached per (variant, mesh, hostio), so differently-configured
        services never share worker pools or compiled executables.

        `autotune=AutotuneCache(...)` (`repro.kernels.autotune`) applies
        persisted megakernel tuning winners -- keyed by
        (device kind, bucket, R, m) -- to every compile of this executor;
        the tuned fields ride the compile-cache key. Executors are cached
        per (variant, mesh, hostio, autotune) by cache-object identity.
        """
        if variant in ("sharded", "sharded-base"):
            if mesh is None:
                from repro.compat import make_mesh

                mesh = make_mesh((1, len(jax.devices())), ("data", "model"))
        elif mesh is not None:
            raise ValueError(
                f"mesh= only applies to the sharded variants, got {variant!r}"
            )
        if hostio is not None and variant not in ("base", "sharded-base"):
            raise ValueError(
                "hostio= only applies to the host-resident-graph variants "
                f"('base', 'sharded-base'), got {variant!r}"
            )
        key: Any = (variant, mesh, hostio, autotune)
        ex = self._executors.get(key)
        if ex is None:
            if variant in ("sharded", "sharded-base"):
                from repro.runtime.sharded import ShardedSearchExecutor

                ex = ShardedSearchExecutor.from_index(
                    self, mesh, variant=variant, hostio=hostio,
                    autotune=autotune,
                )
            else:
                from repro.runtime.executor import SearchExecutor

                shared_adj = None
                if variant != "base":
                    for other in self._executors.values():
                        # Only single-device device-resident adjacency is
                        # shareable: the sharded executors' adjacency (when
                        # they have one at all) carries a mesh sharding.
                        if not str(getattr(other, "variant", "")).startswith("sharded") \
                                and other.adjacency_dev is not None:
                            shared_adj = other.adjacency_dev
                            break
                ex = SearchExecutor.from_index(
                    self, variant=variant, adjacency_dev=shared_adj,
                    hostio=hostio, autotune=autotune,
                )
            self._executors[key] = ex
        return ex

    def search(
        self,
        queries: np.ndarray | Array,
        k: int = 10,
        *,
        t: int = 64,
        variant: str = "inmem",
        rerank: bool = True,
        cfg: SearchConfig | None = None,
        return_stats: bool = False,
        mesh=None,
        kernel_mode: str | None = None,
        hostio=None,
    ) -> tuple[Array, Array] | tuple[Array, Array, SearchStats]:
        """Batched k-NN search. Returns (ids (B, k), dists (B, k)).

        Delegates to the per-variant executor: the three-stage pipeline
        (PQ table -> traversal -> re-rank) runs as one compiled executable,
        cached per query-batch shape bucket, with index state resident on
        device. Repeated searches with the same (bucket, t, k, variant,
        kernel_mode) never retrace. With `return_stats=True` the stats
        separate steady-state wall time from compile time.
        `variant="sharded"` / `"sharded-base"` (with an optional `mesh=`)
        serve from index state sharded across devices — the latter with the
        graph in host RAM behind per-shard callbacks; results are bit-exact
        equal to the single-device variants. `kernel_mode` picks the
        traversal-step implementation ("reference" | "staged" | "fused", see
        the module docstring matrix); all modes return bit-identical ids.
        `hostio=HostIOConfig(...)` serves the host-graph variants through
        the async host-I/O subsystem (see the hostio matrix above),
        bit-exact vs the inline-callback path in every configuration.
        """
        return self.executor(variant, mesh=mesh, hostio=hostio).search(
            queries, k, t=t, cfg=cfg, rerank=rerank,
            return_stats=return_stats, kernel_mode=kernel_mode,
        )


def brute_force_knn(data: np.ndarray, queries: np.ndarray, k: int) -> np.ndarray:
    """Ground truth for recall measurements (O(nd) per query)."""
    return np.asarray(_knn(jnp.asarray(data, jnp.float32),
                           jnp.asarray(queries, jnp.float32), k))


# Corpus rows scored per step: bounds the (B, rows) distance block, so the
# ground truth of a corpus of any size fits next to the corpus itself.
_KNN_ROWS = 65536


@functools.partial(jax.jit, static_argnames=("k",))
def _knn(data: Array, queries: Array, k: int) -> Array:
    n = data.shape[0]
    rows = min(n, _KNN_ROWS)
    steps = -(-n // rows)
    qn = jnp.sum(queries * queries, -1)[:, None]

    def step(best, s):
        # The last step starts early enough to end at row n; rows an
        # earlier step scored are masked, so the corpus is never padded.
        start = jnp.minimum(s * rows, n - rows)
        x = jax.lax.dynamic_slice_in_dim(data, start, rows)
        ids = start + jnp.arange(rows, dtype=jnp.int32)
        d2 = qn + jnp.sum(x * x, -1)[None, :] - 2.0 * jnp.dot(
            queries, x.T, precision=jax.lax.Precision.HIGHEST
        )
        d2 = jnp.where(ids >= s * rows, d2, jnp.inf)
        cand_d = jnp.concatenate([best[0], d2], axis=1)
        cand_i = jnp.concatenate(
            [best[1], jnp.broadcast_to(ids, d2.shape)], axis=1
        )
        neg, pos = jax.lax.top_k(-cand_d, k)
        return (-neg, jnp.take_along_axis(cand_i, pos, axis=1)), None

    B = queries.shape[0]
    init = (jnp.full((B, k), jnp.inf), jnp.full((B, k), -1, jnp.int32))
    (_, idx), _ = jax.lax.scan(step, init, jnp.arange(steps))
    return idx


def recall_at_k(found_ids: np.ndarray, true_ids: np.ndarray) -> float:
    """k-recall@k (paper §6.3): |found ∩ true| / k averaged over queries."""
    k = true_ids.shape[1]
    hits = 0
    for f, t in zip(np.asarray(found_ids), true_ids):
        hits += len(set(f.tolist()) & set(t.tolist()))
    return hits / (true_ids.shape[0] * k)
