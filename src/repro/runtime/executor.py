"""Compiled search executors: the three-stage pipeline as a resident service.

Two executors share one serving contract (dispatch/finish/search, shape
buckets, compiled-executable cache, `SearchStats`):

  * `SearchExecutor` (this module) -- **single device**. Index state lives on
    one accelerator; the three variants ("inmem"/"base"/"exact") reproduce
    the paper's single-GPU configurations.
  * `ShardedSearchExecutor` (`repro.runtime.sharded`) -- **mesh parallel**.
    PQ codes and full vectors are sharded over the mesh's `model` axis and
    queries over `data`, so the served graph can exceed one device's memory;
    each hop exchanges only O(frontier) bytes via masked psums
    (`repro.core.distributed`). The graph itself is either device-sharded
    (`variant="sharded"`) or host-resident behind per-shard callbacks
    (`variant="sharded-base"`). Drop-in subclass: `ServePipeline` and
    `BangIndex.search(variant="sharded"|"sharded-base", mesh=...)` drive
    either executor through the identical interface.

`BangIndex.search` used to re-trace the whole `lax.while_loop` pipeline and
re-upload the adjacency on every call, so measured QPS was dominated by
tracing, not search. `SearchExecutor` is the serving-grade fix (paper §4/§6:
the pipeline stays resident on the GPU across query batches):

  * **Device-resident state.** Codes, codebooks, adjacency and (for the
    in-memory variants) full vectors are uploaded once and passed to every
    call of the compiled executable as operands. They are never captured as
    closure constants: jit embeds a captured array in the program itself,
    which at deployment size (GBs of adjacency) exhausts host memory at
    compile time.
  * **One `jax.jit` over stages 1+2+3.** PQ distance-table construction,
    graph traversal and re-ranking fuse into a single executable with the
    query buffer donated, so XLA schedules the whole pipeline end to end.
  * **Shape-bucketed executable cache.** Batches are padded up to
    power-of-two buckets (`bucket_size`), and compiled executables are cached
    per `(bucket, k, rerank, SearchConfig)`; arbitrary batch sizes hit the
    cache instead of recompiling. `trace_counts` exposes the per-key trace
    count so tests can assert "compiled exactly once". `SearchConfig`
    carries the `kernel_mode` ("reference" | "staged" | "fused" -- the fused
    search_step megakernel compiled *inside* the bucketed, donated jit), so
    each mode gets its own bucket-padded executable; `dispatch`/`search`
    accept `kernel_mode=` as sugar for replacing it on the cfg.
  * **Async dispatch.** `dispatch()` returns a `SearchHandle` without
    blocking; `finish()` blocks on *both* ids and dists and reports
    steady-state wall time separated from compile time (`SearchStats`).
  * **Stage map.** `stage_map()` names the stage (`core.search.STAGES`) of
    every op of the compiled executables, so a device trace's op times can
    be summed per stage under names that survive a change to the program.

Typical use::

    ex = index.executor("inmem")            # cached per-variant on the index
    ids, dists, stats = ex.search(queries, k=10, t=64, return_stats=True)
    # stats.compile_s > 0 only on the first call for this shape bucket.
"""
from __future__ import annotations

import dataclasses
import time
import warnings
import weakref
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import pq as pqlib
from repro.core import rerank as rr
from repro.core import search as searchlib
from repro.core.bang import SearchStats
from repro.core.search import SearchConfig
from repro.core.vamana import VamanaGraph

from .hostio import HostIOConfig, HostIORuntime
from .telemetry.stages import op_stages
from .telemetry.tracing import NO_SPAN

Array = jax.Array

VARIANTS = ("inmem", "base", "exact")


def _validate_min_bucket(min_bucket: int) -> int:
    """min_bucket must be a positive power of two: the bucket lattice is
    pow2, so a non-pow2 floor would emit misaligned buckets (e.g. 12, then
    16 for batch 13) whose executables duplicate cache entries without ever
    being shape-compatible."""
    if min_bucket < 1 or (min_bucket & (min_bucket - 1)):
        raise ValueError(
            f"min_bucket must be a positive power of two, got {min_bucket}"
        )
    return min_bucket


def bucket_size(batch: int, *, min_bucket: int = 8) -> int:
    """Next power-of-two shape bucket holding `batch` queries."""
    _validate_min_bucket(min_bucket)
    if batch <= 0:
        raise ValueError(f"batch must be positive, got {batch}")
    return max(min_bucket, 1 << (batch - 1).bit_length())


def pad_batch(queries: np.ndarray, bucket: int) -> np.ndarray:
    """Pad (B, d) queries up to (bucket, d) by replicating the last row.

    Query lanes are independent (the batch advances in lock-step but never
    exchanges data), so padding lanes cannot perturb real lanes; replicating
    a real query keeps the padded lanes numerically tame. Callers slice the
    first B rows of every output.
    """
    B = queries.shape[0]
    if B > bucket:
        raise ValueError(f"batch {B} exceeds bucket {bucket}")
    if B == bucket:
        return queries
    return np.concatenate([queries, np.repeat(queries[-1:], bucket - B, 0)], 0)


@dataclasses.dataclass
class SearchHandle:
    """An in-flight (asynchronously dispatched) search batch."""

    ids: Array          # (bucket, k), possibly still being computed
    dists: Array        # (bucket, k)
    n_hops: Array       # (bucket,)
    n_iters: Array      # ()
    batch: int          # true batch size (<= bucket)
    bucket: int
    dispatch_t: float   # perf_counter at dispatch (after compile + upload)
    compile_s: float    # compile time this dispatch paid (0 on cache hit)


class SearchExecutor:
    """Device-resident, jit-cached three-stage BANG search pipeline."""

    def __init__(
        self,
        codec: pqlib.PQCodec,
        codes: Array,
        graph: VamanaGraph,
        *,
        variant: str = "inmem",
        data_dev: Array | None = None,
        data_np: np.ndarray | None = None,
        adjacency_dev: Array | None = None,
        min_bucket: int = 8,
        hostio: HostIOConfig | None = None,
        with_tombstones: bool = False,
        autotune=None,
    ) -> None:
        if variant not in VARIANTS:
            raise ValueError(f"unknown variant {variant!r}, expected one of {VARIANTS}")
        if variant == "exact" and data_dev is None:
            raise ValueError("exact variant needs device-resident data")
        if hostio is not None and variant != "base":
            raise ValueError(
                "hostio= only applies to the host-resident-graph variant "
                f"'base', got {variant!r}"
            )
        self.variant = variant
        self._codebooks = jnp.asarray(codec.codebooks)
        self._codes = jnp.asarray(codes)
        self._graph = graph
        self._data_dev = data_dev
        self._data_np = data_np
        self._hostio = hostio
        # Streaming mutability: tombstone-capable executables take a second
        # (n,) bool operand (the live-delete bitmap) so deletes never force a
        # recompile; the flag rides the compile-cache key like hostio does.
        self._with_tombstones = with_tombstones
        self._tombstone_len = int(np.asarray(graph.adjacency).shape[0])
        self.hostio_runtime = None
        self._exchange = (None, None)
        if variant == "base":
            # BANG Base: the graph stays in host RAM behind a pure_callback --
            # inline and synchronous by default, or served by the hostio
            # subsystem (multi-worker service + hot cache + prefetch) when a
            # HostIOConfig is given. Bit-exact either way.
            self._adjacency = None
            self._adjacency_np = np.asarray(graph.adjacency)
            if hostio is not None:
                self.hostio_runtime = HostIORuntime(
                    hostio, [np.asarray(self._adjacency_np, np.int32)],
                    self._adjacency_np, medoid=graph.medoid, name="hostio-base",
                )
                self._exchange = self.hostio_runtime.base_exchange()
        else:
            self._adjacency = (
                adjacency_dev if adjacency_dev is not None
                else jnp.asarray(graph.adjacency)
            )
            self._adjacency_np = None
        self._init_serving_state(min_bucket, autotune)

    def _init_serving_state(self, min_bucket: int, autotune=None) -> None:
        """Shared dispatch/finish bookkeeping; both executor classes call it.

        Host-I/O state (`_hostio`/`hostio_runtime`/`_exchange`) is NOT set
        here: each constructor assigns it explicitly before (and, for the
        host-graph variants, after) this call, so a future constructor that
        forgets it fails fast instead of silently serving without a service.

        `autotune` is a `repro.kernels.autotune.AutotuneCache` (or None):
        its winner for this executor's (device kind, bucket, R, m) is
        applied onto the SearchConfig in `_compiled`, *before* the
        compile-cache key is built.
        """
        self._min_bucket = _validate_min_bucket(min_bucket)
        self._autotune = autotune
        self._cache: dict[Any, Any] = {}
        self.trace_counts: dict[Any, int] = {}
        self.compile_s_total = 0.0
        # Observability bundle (repro.runtime.telemetry.Telemetry), attached
        # via set_telemetry. Executor *state*, deliberately NOT part of the
        # compile-cache key: attaching/detaching telemetry must never retrace
        # or recompile anything (test-asserted in tests/test_telemetry.py).
        self.telemetry = None

    @classmethod
    def from_index(cls, index, variant: str = "inmem", **kw) -> "SearchExecutor":
        return cls(
            index.codec, index.codes, index.graph, variant=variant,
            data_dev=index.data_dev, data_np=index.data_np, **kw,
        )

    # ------------------------------------------------------------- inspection
    @property
    def n_traces(self) -> int:
        return sum(self.trace_counts.values())

    @property
    def cache_size(self) -> int:
        return len(self._cache)

    @property
    def adjacency_dev(self) -> Array | None:
        """Device adjacency, for sharing across same-index executors."""
        return self._adjacency

    @property
    def hostio_service(self):
        """The live NeighborService (None unless hostio is configured)."""
        rt = self.hostio_runtime
        return None if rt is None else rt.service

    def stage_map(self) -> dict[str, str]:
        """HLO instruction name -> stage, over the executables compiled so far.

        Read from the compiled text (`telemetry.stages.op_stages`). A
        device trace names an op by its instruction name alone, so a name
        that the executables give different stages, or a stage in one and
        none in another, is left out.
        """
        seen: dict[str, set] = {}
        for compiled in self._cache.values():
            text = compiled.as_text()
            for name, stage in op_stages(text, searchlib.STAGES).items():
                seen.setdefault(name, set()).add(stage)
        return {name: next(iter(st)) for name, st in seen.items()
                if len(st) == 1 and None not in st}

    def _tracer_fn(self):
        """A function that returns the attached telemetry tracer or None.

        Host callbacks call it at call time, so attaching one never changes
        a traced program. It holds the executor weakly: a compiled program
        that held it strongly would keep it alive for good.
        """
        ref = weakref.ref(self)

        def tracer():
            ex = ref()
            tel = None if ex is None else ex.telemetry
            return None if tel is None else tel.tracer

        return tracer

    def set_telemetry(self, telemetry) -> "SearchExecutor":
        """Attach (or detach, with None) a telemetry bundle.

        Forwards to the host-I/O runtime when present so hostio counters,
        gather spans and fault postmortems report through the same bundle.
        Pure host-side state: the compile cache, its keys and every traced
        program are byte-identical with or without telemetry.
        """
        self.telemetry = telemetry
        rt = self.hostio_runtime
        if rt is not None:
            rt.set_telemetry(telemetry)
        return self

    @property
    def query_dim(self) -> int | None:
        """Expected query width d, or None if no vector store is attached.

        ServePipeline.submit() validates incoming queries against this up
        front, so a malformed batch fails with a clear error instead of
        deep inside dispatch padding. Row sharding never changes the width,
        so the sharded subclass inherits this off its device store.
        """
        src = self._data_np if self._data_dev is None else self._data_dev
        return None if src is None else int(src.shape[1])

    def autotune_shape(self) -> tuple[int, int, int]:
        """(R, m, codes_block_rows): the shape axes autotune winners key on.

        `codes_block_rows` is the row count of the codes block one fused
        kernel instance sees -- the full index here; the sharded subclass
        reports the per-model-shard block.
        """
        adj = (
            self._adjacency_np if self._adjacency is None else self._adjacency
        )
        return (
            int(adj.shape[1]),
            int(self._codes.shape[1]),
            int(self._codes.shape[0]),
        )

    # ------------------------------------------------------------- compiling
    def _compiled(self, bucket: int, d: int, k: int, rerank: bool,
                  cfg: SearchConfig):
        """Cache lookup + compile accounting; `_compile` builds the program.

        The hostio config rides the key: an executor's host-I/O wiring
        (worker pool, hot cache, prefetch) is fixed at construction, but
        keying it keeps executables from ever being confused across
        executors whose caches are merged or persisted externally.

        With an `autotune=` cache, the winner for this executor's
        `(device kind, bucket, R, m)` replaces the tuned SearchConfig
        fields (eager, codes_tile_rows) *here*, before the key is built:
        the tuned config IS the cache key, so reloading a persisted winners
        file reproduces identical executable keys, and an untuned shape
        falls through with `cfg` untouched.
        """
        if self._autotune is not None:
            from repro.kernels import autotune as autotune_lib

            R, m, _ = self.autotune_shape()
            cfg = self._autotune.apply(
                cfg, autotune_lib.device_kind(), bucket, R, m
            )
        key = (bucket, d, k, rerank, cfg, self._hostio, self._with_tombstones)
        entry = self._cache.get(key)
        if entry is not None:
            return entry, 0.0
        tel = self.telemetry
        tr = None if tel is None else tel.tracer
        t0 = time.perf_counter()
        with NO_SPAN if tr is None else tr.span(
                "compile", track="serve", bucket=bucket, k=k,
                kernel_mode=cfg.kernel_mode), warnings.catch_warnings():
            # Donation is best-effort: when no output aliases the (bucket, d)
            # query buffer (small k), XLA reports it unusable. Expected.
            warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable"
            )
            compiled = self._compile(key, bucket, d, k, rerank, cfg)
        compile_s = time.perf_counter() - t0
        self.compile_s_total += compile_s
        self._cache[key] = compiled
        if tel is not None:
            tel.registry.counter(
                "bang_serve_compile_seconds_total",
                "wall seconds spent compiling search executables",
            ).inc(compile_s)
        return compiled, compile_s

    def _compile(self, key, bucket: int, d: int, k: int, rerank: bool,
                 cfg: SearchConfig):
        """Trace + lower + compile one executable for `key` (subclass hook)."""
        variant = self.variant

        def pipeline(queries: Array, state, tombstones: Array | None = None):
            # Trace-time side effect: runs once per compiled executable.
            self.trace_counts[key] = self.trace_counts.get(key, 0) + 1
            codebooks, codes, adjacency, data_dev = state
            tombstone_fn = (
                None if tombstones is None
                else searchlib.tombstone_mask_fn(tombstones)
            )
            if variant == "exact":
                res = searchlib.search_exact(
                    queries, data_dev, adjacency,
                    self._graph.medoid, cfg, tombstone_fn=tombstone_fn,
                )
                # Exact-distance variant skips the re-rank (§5.2): the
                # worklist already holds exact distances.
                ids = res.worklist.ids[:, :k]
                dists = res.worklist.dists[:, :k]
            else:
                with jax.named_scope("bang.table"):
                    table = pqlib.build_dist_table(
                        pqlib.PQCodec(codebooks), queries)
                if variant == "inmem":
                    res = searchlib.search_inmem(
                        queries, table, codes, adjacency,
                        self._graph.medoid, cfg, tombstone_fn=tombstone_fn,
                    )
                else:
                    neighbor_fn, prefetch_fn = self._exchange
                    res = searchlib.search_base(
                        queries, table, codes, self._adjacency_np,
                        self._graph.medoid, cfg,
                        neighbor_fn=neighbor_fn, prefetch_fn=prefetch_fn,
                        tombstone_fn=tombstone_fn,
                    )
                if rerank:
                    if variant == "base" or data_dev is None:
                        ids, dists = rr.rerank(
                            queries, res.history_ids, k,
                            data_np=self._data_np,
                            use_kernels=cfg.uses_kernels(),
                            tracer=self._tracer_fn(),
                        )
                    else:
                        ids, dists = rr.rerank(
                            queries, res.history_ids, k,
                            data=data_dev,
                            use_kernels=cfg.uses_kernels(),
                        )
                else:
                    ids = res.worklist.ids[:, :k]
                    dists = res.worklist.dists[:, :k]
            return ids, dists, res.n_hops, res.n_iters

        spec = jax.ShapeDtypeStruct((bucket, d), jnp.float32)
        if not self._with_tombstones:
            return (
                jax.jit(pipeline, donate_argnums=0)
                .lower(spec, self._state())
                .compile()
            )
        # Tombstone-capable executable: the bitmap is a true operand (never a
        # captured constant), so deletes update it without retracing; only
        # the query buffer stays donated.
        tomb_spec = jax.ShapeDtypeStruct((self._tombstone_len,), jnp.bool_)
        return (
            jax.jit(pipeline, donate_argnums=0)
            .lower(spec, self._state(), tomb_spec)
            .compile()
        )

    def _state(self) -> tuple:
        """The device index state every executable call takes as operands."""
        return (self._codebooks, self._codes, self._adjacency, self._data_dev)

    # ----------------------------------------------------- subclass hooks
    # ShardedSearchExecutor overrides these three to place queries on the
    # mesh and feed the sharded index state to the executable; the serving
    # logic in dispatch/finish is shared verbatim.
    def _bucket_for(self, batch: int) -> int:
        return bucket_size(batch, min_bucket=self._min_bucket)

    def _device_queries(self, q_padded: np.ndarray) -> Array:
        # Fresh device buffer every call: the executable donates its input,
        # so dispatch() must never hand it a caller-owned device array (the
        # host round-trip in dispatch() is what guarantees that).
        return jax.device_put(q_padded)

    def _device_tombstones(self, tombstones: np.ndarray | None) -> Array:
        """Upload the (n,) bool delete bitmap (zeros when none was given)."""
        if tombstones is None:
            tombstones = np.zeros(self._tombstone_len, np.bool_)
        tombstones = np.asarray(tombstones, np.bool_)
        if tombstones.shape != (self._tombstone_len,):
            raise ValueError(
                f"tombstones must be ({self._tombstone_len},), got "
                f"{tombstones.shape}"
            )
        return jax.device_put(tombstones)

    def _run(self, compiled, q_dev: Array, tomb_dev: Array | None = None):
        if tomb_dev is None:
            return compiled(q_dev, self._state())
        return compiled(q_dev, self._state(), tomb_dev)

    # ------------------------------------------------------------ accounting
    def _hot_cache_fields(self, host_rows_in: int) -> dict:
        """Hot-adjacency-cache accounting shared by both executor classes.

        `hot_cache_hit_rate` is the *measured* service-side hit rate (0.0
        before any traffic); `host_bytes_saved_per_hop` scales the analytic
        rows-back leg by it -- the host-link bytes the device-resident cache
        absorbed. `host_link_bytes` in the caller is reduced by the saving,
        so with no cache (or no traffic yet) the legacy identity
        host_link == ids_out + rows_in still holds exactly.
        """
        rt = self.hostio_runtime
        if rt is None or rt.cache is None:
            return {
                "hot_cache_rows": 0,
                "hot_cache_hit_rate": 0.0,
                "host_bytes_saved_per_hop": 0,
            }
        rate = rt.service.cache_hit_rate()
        return {
            "hot_cache_rows": rt.cache.n_rows,
            "hot_cache_hit_rate": rate,
            "host_bytes_saved_per_hop": int(host_rows_in * rate),
        }

    def exchange_bytes_per_hop(self, batch: int) -> dict:
        """Logical link bytes one hop moves, same schema as the sharded peer.

        A single device pays no inter-device collectives; the "base" variant
        pays the paper's host link each hop -- (bucket,) int32 frontier ids
        out and (bucket, R) int32 adjacency rows back over the pure_callback
        (§4.1/§4.3). Device-resident-graph variants move nothing. With the
        hostio hot cache, `host_bytes_saved_per_hop` (measured hit rate x
        the rows-back leg) is subtracted from `host_link_bytes`: hit rows
        never cross the link.
        """
        bucket = self._bucket_for(batch)
        adj = self._adjacency_np if self._adjacency is None else self._adjacency
        R = adj.shape[1]
        host_ids_out = bucket * 4 if self.variant == "base" else 0
        host_rows_in = bucket * R * 4 if self.variant == "base" else 0
        hot = self._hot_cache_fields(host_rows_in)
        return {
            "payload_bytes": 0,
            "collective_bytes": 0,
            "ring_bytes_per_device": 0,
            "host_ids_out_bytes": host_ids_out,
            "host_rows_in_bytes": host_rows_in,
            "host_link_bytes": (
                host_ids_out + host_rows_in - hot["host_bytes_saved_per_hop"]
            ),
            "model_shards": 1,
            "data_shards": 1,
            # Streaming mutability (repro.runtime.mutation): fraction of
            # graph nodes tombstoned and live delta-graph points. Static
            # executors report the frozen-index identity (0.0, 0);
            # MutableSearchExecutor overrides them per epoch.
            "tombstone_fraction": 0.0,
            "delta_points": 0,
            **hot,
        }

    # -------------------------------------------------------------- serving
    def dispatch(
        self,
        queries: np.ndarray | Array,
        k: int = 10,
        *,
        t: int = 64,
        cfg: SearchConfig | None = None,
        rerank: bool = True,
        kernel_mode: str | None = None,
        tombstones: np.ndarray | None = None,
    ) -> SearchHandle:
        """Pad, compile-or-hit-cache, and asynchronously launch one batch.

        Returns immediately after dispatch (JAX async dispatch): the arrays in
        the handle may still be in flight. Pair with `finish()`.

        `kernel_mode` ("reference" | "staged" | "fused") overrides
        `cfg.kernel_mode`; it is part of the compile-cache key, so each mode
        compiles (once) to its own bucket-padded executable.

        `tombstones` (executors built with `with_tombstones=True` only) is
        the (n,) bool live-delete bitmap: it is a true operand of the
        compiled executable, so updating it between dispatches never
        retraces. None means "nothing deleted".
        """
        q = np.asarray(queries, np.float32)
        if q.ndim != 2:
            raise ValueError(f"queries must be (B, d), got shape {q.shape}")
        if tombstones is not None and not self._with_tombstones:
            raise ValueError(
                "tombstones= requires an executor built with "
                "with_tombstones=True"
            )
        B, d = q.shape
        cfg = cfg or SearchConfig(t=max(t, k))
        if kernel_mode is not None:
            if kernel_mode not in searchlib.KERNEL_MODES:
                raise ValueError(
                    f"unknown kernel_mode {kernel_mode!r}, expected one of "
                    f"{searchlib.KERNEL_MODES}"
                )
            cfg = dataclasses.replace(cfg, kernel_mode=kernel_mode)
        bucket = self._bucket_for(B)
        compiled, compile_s = self._compiled(bucket, d, k, rerank, cfg)
        q_dev = self._device_queries(pad_batch(q, bucket))
        tomb_dev = (
            self._device_tombstones(tombstones)
            if self._with_tombstones else None
        )
        t0 = time.perf_counter()
        tel = self.telemetry
        if tel is not None and tel.profiler is not None:
            # Stamp kernel metadata for codes-stream accounting. Host-side
            # only: the compiled program is the same object either way.
            R, m, n_block = self.autotune_shape()
            tel.profiler.set_kernel_info(
                kernel_mode=cfg.kernel_mode, batch=bucket, n=n_block, m=m,
                R=R, tile_rows=cfg.codes_tile_rows,
            )
        ids, dists, n_hops, n_iters = self._run(compiled, q_dev, tomb_dev)
        return SearchHandle(
            ids=ids, dists=dists, n_hops=n_hops, n_iters=n_iters,
            batch=B, bucket=bucket, dispatch_t=t0, compile_s=compile_s,
        )

    def finish(
        self, handle: SearchHandle, *, return_stats: bool = False
    ) -> tuple[Array, Array] | tuple[Array, Array, SearchStats]:
        """Block until the batch is done; slice padding off; report stats."""
        ids = jax.block_until_ready(handle.ids)[: handle.batch]
        dists = jax.block_until_ready(handle.dists)[: handle.batch]
        wall = time.perf_counter() - handle.dispatch_t
        if not return_stats:
            return ids, dists
        hops = np.asarray(handle.n_hops)[: handle.batch]
        stats = SearchStats(
            # Scalar on the single-device path; the sharded path reports one
            # count per lane (data shards converge independently) -> max.
            n_iters=int(np.max(np.asarray(handle.n_iters))),
            mean_hops=float(hops.mean()),
            p95_hops=float(np.percentile(hops, 95)),
            wall_s=wall,
            qps=handle.batch / wall,
            compile_s=handle.compile_s,
            batch=handle.batch,
            bucket=handle.bucket,
        )
        return ids, dists, stats

    def search(
        self,
        queries: np.ndarray | Array,
        k: int = 10,
        *,
        t: int = 64,
        cfg: SearchConfig | None = None,
        rerank: bool = True,
        return_stats: bool = False,
        kernel_mode: str | None = None,
        tombstones: np.ndarray | None = None,
    ) -> tuple[Array, Array] | tuple[Array, Array, SearchStats]:
        """Synchronous batched k-NN search: dispatch + finish."""
        handle = self.dispatch(
            queries, k, t=t, cfg=cfg, rerank=rerank, kernel_mode=kernel_mode,
            tombstones=tombstones,
        )
        return self.finish(handle, return_stats=return_stats)
