"""End-to-end driver: streaming ANN serving (the paper's workload).

Simulates a query front-end on top of the runtime subsystem: batches of
queries arrive in a queue, `ServePipeline` drains them through a compiled
`SearchExecutor` in double-buffered micro-batches (batch i+1's host-side
padding/bucketing overlaps batch i's device compute), and the server reports
rolling QPS / recall / latency percentiles with compile time separated from
steady-state search time.

`--variant base` keeps the graph behind a host callback -- the paper's
CPU-side graph service; `--variant inmem`/`exact` are the §5 variants.
`--variant sharded --devices N` serves the index sharded over an N-device
("model"-axis) mesh -- the graph-bigger-than-one-device regime -- and
`--variant sharded-base` is the same mesh with the graph staying in host
RAM, row-partitioned behind one callback per model shard (the server prints
the per-hop host-link vs collective byte split). `--kernel-mode fused` swaps
the traversal step for the search_step Pallas megakernel (one pallas_call per
hop, candidates never leave VMEM); `staged` is the per-stage kernel path.

The host-graph variants additionally take the async host-I/O subsystem
knobs: `--host-workers N` serves adjacency through a multi-worker neighbour
service (N gather threads per graph partition), `--hot-cache-rows H` pins
the H highest-in-degree adjacency rows in device memory (hits skip the host
link; the server prints the measured hit rate and bytes saved), and
`--prefetch` double-buffers the frontier exchange (hop k+1's expected gather
issued while the device merges hop k; the server prints the measured overlap
fraction). `--result-cache N` enables the ServePipeline cross-batch
query-result LRU (any variant). `--autotune` sweeps the fused megakernel's
scheduling knobs (eager/lazy §4.6 selection, VMEM-resident vs HBM codes) on
real searches before serving and persists the winners to `--autotune-cache`
(JSON keyed by device kind, bucket, R, m); a pre-existing cache file is
applied even without the sweep. `--mutate` interleaves live inserts/deletes
with the serving batches through a `MutableBangIndex` (plus a background
consolidation halfway through), scoring recall against the live corpus.
For a CPU rehearsal, `--devices N` forces N fake host devices (set before
any other use of jax in the process); on a TPU backend the sharded variants
use the real devices and `--devices` is refused. Compiles are cached under
$JAX_COMPILATION_CACHE_DIR, else `.jax_cache/` at the repo root. See
`--help` for the variant x placement, kernel-mode and host-I/O matrices.

    PYTHONPATH=src python examples/serve_ann.py --batches 5 --batch-size 128
    PYTHONPATH=src python examples/serve_ann.py --variant sharded --devices 4
    PYTHONPATH=src python examples/serve_ann.py --variant sharded-base --devices 4
    PYTHONPATH=src python examples/serve_ann.py --variant base \
        --host-workers 4 --hot-cache-rows 512 --prefetch

Sample output (all batches are enqueued before the drain starts, so per-row
latency includes queue wait and -- for the first batch -- the one-off compile;
steady-state QPS is the number to compare against the paper)::

    [serve] batch 0: 128 queries in 2501ms (51 QPS, compile 2.3s), recall@10=0.991
    [serve] batch 1: 128 queries in 180ms (711 QPS), recall@10=0.993
    ...
    [serve] TOTAL 640 queries | steady-state 702 QPS (compile 2.3s excluded)
    [serve] latency p50=2881ms p95=3320ms | mean recall@10=0.992 (variant=inmem)
"""
import argparse
import os

VARIANT_MATRIX = """\
variant matrix (distances down, graph placement across; every PQ cell is
bit-exact vs its row-mates, and every cell runs under each --kernel-mode
with bit-identical neighbour ids):

    distances \\ placement   single device        mesh-sharded (--devices N)
    ----------------------  -------------------  --------------------------
    PQ, graph on device     inmem                sharded
    PQ, graph in host RAM   base                 sharded-base
    exact, no re-rank       exact                --

kernel-mode matrix (traversal-step implementation, --kernel-mode):

    mode \\ variant     inmem / base / exact      sharded / sharded-base
    -----------------  ------------------------  --------------------------
    reference          pure XLA (default)        XLA gather ADC + psum
    staged             per-stage Pallas kernels  pq_adc kernel + psum,
                       (HBM between stages)      bitonic sort/merge
    fused              search_step megakernel:   owner-shard fused gather+
                       whole hop in one          ADC kernel + psum, fused
                       pallas_call, in-kernel    traverse kernel (exact L2
                       code gather               stays outside either way)

kernel-mode fallback rules: 'fused' NEVER silently falls back to 'staged'.
When the PQ-codes block exceeds the VMEM budget (REPRO_VMEM_BUDGET env, 16
MiB default) the fused kernel streams it through a double-buffered DMA
pipeline -- tile i+1's async copy overlaps tile i's ADC -- and stays
bit-exact vs every other mode. The DMA tile size is SearchConfig.
codes_tile_rows (0 = auto from the budget); --autotune sweeps it together
with the eager/lazy selection flavour and persists per-(device kind,
bucket, R, m) winners to --autotune-cache, which executors apply inside
the compile-cache key (a reloaded file reproduces identical keys). A
missing or corrupt cache file falls back to default configs with a
warning -- tuning can never take serving down.

host-I/O matrix (async host subsystem, base / sharded-base only; every
combination is bit-exact vs the inline-callback path in every kernel mode):

    knob               effect
    -----------------  ------------------------------------------------
    --host-workers N   multi-worker neighbour service: N gather threads
                       per host graph partition, queued batched gathers
    --hot-cache-rows H top-in-degree adjacency rows pinned on device;
                       hits never cross the host link (hit rate + bytes
                       saved reported)
    --prefetch         double-buffered frontier exchange: hop k+1's §4.6
                       eager-candidate gather overlaps hop k's merge
                       (measured overlap fraction reported)
    --result-cache N   ServePipeline cross-batch query-result LRU (any
                       variant): repeat queries served bit-identically
                       without touching the executor

streaming mutability (--mutate, repro.runtime.mutation): the server wraps
the index in a MutableBangIndex and interleaves inserts/deletes with the
serving batches, then consolidates in the background while traffic flows.
Cache-invalidation contract (what --mutate demonstrates):

    cache                    scope     invalidated by
    -----------------------  --------  --------------------------------
    ServePipeline result     epoch     every insert()/delete()/
    LRU (--result-cache)               consolidate() bumps the epoch;
                                       the next drain drops the LRU, so
                                       a hit can never return a deleted
                                       id or miss a fresh insert
    compiled executables     gen       consolidation bumps the
    (per-bucket jit cache)             generation; executors rebuild
                                       from the new snapshot, old
                                       executables are dropped
    hostio hot-adjacency     gen       retiring caches are refresh()ed
    cache (--hot-cache-rows)           with the consolidated rows

Consolidation guarantees: deleted ids never come back (slots are retired,
ids never reused); inserted ids are stable across the fold (delta ids are
base_n + ordinal); searches racing the background fold stay correct -- the
tombstone bitmap and the exact delta scan cover the gap until the atomic
generation swap.

failure-mode / degraded-serving matrix (repro.runtime.resilience; host
fault handling needs --host-workers >= 1 plus --host-deadline-ms, admission
control is --max-queue / --deadline-ms on any variant). Handling is
host-side only: the compiled program never changes with host health, so
recovery after failover is bit-exact by construction.

    fault                    contract
    -----------------------  ------------------------------------------
    transient gather error   retried with exponential backoff (capped
                             by the host deadline); result bit-exact
    stalled worker / pool    hedged re-issue: after the hedge budget the
                             gather re-runs inline on the caller; never
                             blocks past the deadline, result bit-exact
    worker crash             the item is requeued before the thread
                             dies; a pool mate or the hedge completes
                             it -- zero queries lost
    partition down +         reads come from the pinned replica via the
    failover replica         surviving workers; bit-exact
    partition down, no       degraded serving: hot-cache rows unaffect-
    replica                  ed; other lanes serve the medoid row
                             (restart toward the graph centre) or drop
                             like tombstones ("mask" mode). Recall
                             degrades and is measured in mean_recall;
                             degraded_lanes counts the substitutions
    host queue overflow      enqueue rejected -> inline gather, no loss
    serve queue overload     submit() sheds past --max-queue, exactly
                             once, at admission (shed_queries)
    request deadline hit     dropped at dispatch; result rows stay
                             (-1, inf) (expired_queries)
    partition recovery       primary reads resume, bit-exact vs the
                             fault-free run

observability (repro.runtime.telemetry; --metrics-json / --trace-out /
--profile-hops). One Telemetry bundle attaches to the pipeline, executor,
host-I/O service and (with --mutate) the mutation layer. It is executor
*state*, never part of a compile-cache key: attached or detached, the
traced programs, their cache keys and their results are byte-identical.

  metrics (--metrics-json PATH; '-' prints Prometheus text to stdout,
  *.prom writes Prometheus text, anything else writes the schema-versioned
  to_json() document). Exported names:

    serving    bang_serve_queries_total, bang_serve_shed_total,
               bang_serve_expired_total, bang_serve_batches_total,
               bang_serve_result_cache_hits_total,
               bang_serve_compile_seconds_total (counters);
               bang_serve_latency_seconds (histogram);
               bang_serve_qps, bang_serve_recall (last-window gauges)
    host I/O   bang_hostio_<counter>_total for every NeighborService
               counter (requests, rows_gathered, host_miss_lanes,
               cache_hit_lanes, prefetch_issued, prefetch_hits,
               prefetch_misses, prefetch_lane_mismatches, worker_errors,
               worker_deaths, retries, gather_failures, degraded_lanes,
               hedged_gathers, deadline_hits, failover_gathers,
               failovers, recoveries, enqueue_rejections);
               bang_hostio_gather_seconds_total,
               bang_hostio_gather_hidden_seconds_total,
               bang_hostio_request_latency_seconds_total (time counters);
               bang_hostio_max_queue_depth (high-watermark gauge);
               bang_hostio_hot_cache_rows / _device_bytes / _refreshes
               (gauges)
    mutation   bang_mutation_inserts_total, bang_mutation_deletes_total,
               bang_mutation_consolidations_total (counters);
               bang_mutation_epoch, bang_mutation_generation (gauges)

  tracing (--trace-out PATH): Chrome trace_event JSON -- load it in
  chrome://tracing or Perfetto. Tracks: 'serve' (pipeline), one
  'hostio-p<shard>' per graph partition, 'mutation', 'events'
  (resilience instants). Span vocabulary: every submitted query row gets
  exactly ONE terminal event -- a 'request' complete span (args: rid,
  outcome=served|cache_hit), a 'request_shed' instant or a
  'request_expired' instant; batch phases appear as 'admission',
  'dispatch', 'device' and 'compile' complete spans, each drain as a
  'drain' span with a 'gc' span per Python collection inside it; host
  gathers as 'gather' / 'prefetch_gather' spans (args: hop, rows, mode)
  and the Base re-rank's vector gathers as 'rerank_gather' spans (track
  'rerank', args: rows); mutation as 'consolidate' spans +
  'generation_swap' instants; resilience transitions as 'failover',
  'partition_down', 'recover', 'degraded' and 'deadline_hit' instants.
  While a jax.profiler trace is being captured, the spans that wrap work
  (gather, dispatch, compile, drain, rerank_gather, gc) also appear on
  its host plane as 'bang.<span>' events, on the device ops' clock, and
  every device op of the search carries its stage scope (bang.table,
  bang.fetch, bang.bloom, bang.step, bang.history, bang.rerank).

  hop profiler (--profile-hops): per-hop host-gather wall time, frontier
  occupancy, cache-hit lanes and the modeled PQ-codes-stream bytes/hop,
  printed as a summary table after the drain.

  flight recorder (used by the benches/tests; see
  repro.runtime.telemetry.flightrecorder): bounded in-memory ring of
  typed events; each failover / partition-down / degrade / deadline
  event triggers a postmortem dump -- a JSON document
  {schema_version: 1, seq, reason, t_wall, context, events: [ring,
  oldest first, ending in the 'trigger:<reason>' entry], metrics:
  <full registry snapshot>} -- retrievable via postmortems() or
  save_postmortems().
"""


def main() -> None:
    ap = argparse.ArgumentParser(
        epilog=VARIANT_MATRIX,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    ap.add_argument("--n", type=int, default=6000)
    ap.add_argument("--dim", type=int, default=64)
    ap.add_argument("--batches", type=int, default=5)
    ap.add_argument("--batch-size", type=int, default=128)
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--t", type=int, default=64)
    ap.add_argument("--max-batch", type=int, default=128,
                    help="micro-batch size the pipeline drains into")
    ap.add_argument("--variant", default="inmem",
                    choices=["base", "inmem", "exact", "sharded",
                             "sharded-base"])
    ap.add_argument("--kernel-mode", default="reference",
                    choices=["reference", "staged", "fused"],
                    help="traversal-step implementation (see the matrix "
                         "below); 'fused' runs the whole hop in one Pallas "
                         "megakernel (compiled on TPU, interpret elsewhere)")
    ap.add_argument("--devices", type=int, default=0,
                    help="force N host devices for the sharded variants "
                         "(0 = use whatever devices exist)")
    ap.add_argument("--host-workers", type=int, default=0,
                    help="serve the host graph through the async host-I/O "
                         "subsystem with N gather threads per partition "
                         "(base/sharded-base only; 0 = inline callbacks)")
    ap.add_argument("--hot-cache-rows", type=int, default=0,
                    help="pin the H highest-in-degree adjacency rows in "
                         "device memory (requires --host-workers >= 1)")
    ap.add_argument("--prefetch", action="store_true",
                    help="double-buffer the frontier exchange (requires "
                         "--host-workers >= 1)")
    ap.add_argument("--result-cache", type=int, default=0,
                    help="ServePipeline cross-batch query-result LRU size "
                         "(0 = off)")
    ap.add_argument("--max-queue", type=int, default=0,
                    help="admission control: shed submissions past this "
                         "backlog bound (0 = unbounded; see the failure-"
                         "mode matrix below)")
    ap.add_argument("--deadline-ms", type=float, default=0.0,
                    help="per-request serve deadline; expired rows are "
                         "dropped at dispatch (0 = none)")
    ap.add_argument("--host-deadline-ms", type=float, default=0.0,
                    help="host gather deadline: enables retry/backoff, "
                         "hedged re-issue and degraded-mode serving on "
                         "the host-I/O path (requires --host-workers "
                         ">= 1; 0 = legacy blocking behaviour)")
    ap.add_argument("--autotune", action="store_true",
                    help="sweep the fused megakernel's (eager, DMA tile) "
                         "configs on real searches before serving and "
                         "persist the winners to --autotune-cache; an "
                         "existing cache file is applied either way (see "
                         "the fallback rules below)")
    ap.add_argument("--autotune-cache", default="bang_autotune.json",
                    help="JSON winners file keyed by (device kind, bucket, "
                         "R, m) (default: %(default)s)")
    ap.add_argument("--metrics-json", default="",
                    help="dump the telemetry metrics registry after the "
                         "run: '-' prints Prometheus text to stdout, a "
                         "*.prom path writes Prometheus text, any other "
                         "path writes the schema-versioned JSON document "
                         "(see the observability section below)")
    ap.add_argument("--trace-out", default="",
                    help="write a Chrome trace_event JSON timeline of the "
                         "run to this path (load in chrome://tracing or "
                         "Perfetto; span vocabulary below)")
    ap.add_argument("--profile-hops", action="store_true",
                    help="profile the traversal's host-callback seams "
                         "per hop (gather wall time, frontier occupancy, "
                         "codes-stream bytes) and print a summary table")
    ap.add_argument("--mutate", action="store_true",
                    help="wrap the index in a MutableBangIndex and "
                         "interleave inserts/deletes with the serving "
                         "batches, consolidating in the background "
                         "(recall is scored against the live corpus; see "
                         "the mutability section below)")
    args = ap.parse_args()

    if args.devices > 0:
        # Fake CPU devices for a rehearsal. Must land before jax initializes
        # its backend; imports below are deferred past argparse for exactly
        # this reason.
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.devices}"
        ).strip()

    import jax

    if args.devices > 0 and jax.default_backend() == "tpu":
        raise SystemExit(
            "--devices forces fake CPU devices; on a TPU backend the sharded "
            "variants use the real devices: drop --devices"
        )

    from repro.compile_cache import setup_compile_cache
    from repro.kernels.autotune import AutotuneCache

    setup_compile_cache()

    from repro.core import BangIndex, SearchConfig, brute_force_knn
    from repro.data import gaussian_mixture, uniform_queries
    from repro.runtime import ServePipeline

    telemetry = None
    if args.metrics_json or args.trace_out or args.profile_hops:
        from repro.runtime import Telemetry

        telemetry = Telemetry.create(trace=bool(args.trace_out),
                                     profile=args.profile_hops)

    print(f"[serve] building index over {args.n} x {args.dim} corpus ...")
    data = gaussian_mixture(args.n, args.dim, n_clusters=48, seed=0)
    index = BangIndex.build(data, m=16, R=24, L_build=48)
    cfg = SearchConfig(t=args.t, bloom_z=16384)

    hostio = None
    if args.host_workers > 0:
        from repro.runtime.hostio import HostIOConfig

        if not args.variant.endswith("base"):
            raise SystemExit(
                "--host-workers applies to the host-graph variants only "
                "(base, sharded-base)"
            )
        resilience = None
        if args.host_deadline_ms > 0:
            from repro.runtime.resilience import ResilienceConfig

            resilience = ResilienceConfig(
                deadline_s=args.host_deadline_ms / 1e3
            )
        hostio = HostIOConfig(
            workers=args.host_workers,
            hot_cache_rows=args.hot_cache_rows,
            prefetch=args.prefetch,
            resilience=resilience,
        )
    elif args.hot_cache_rows or args.prefetch:
        raise SystemExit("--hot-cache-rows/--prefetch need --host-workers >= 1")
    elif args.host_deadline_ms:
        raise SystemExit("--host-deadline-ms needs --host-workers >= 1")

    autotune = None
    if args.autotune or os.path.exists(args.autotune_cache):
        if args.mutate and args.autotune:
            raise SystemExit("--autotune does not combine with --mutate "
                             "(tune first, then serve mutably)")
        # A pre-existing winners file is applied even without the sweep;
        # missing/corrupt files degrade to defaults with a warning.
        autotune = AutotuneCache.load(args.autotune_cache) \
            if os.path.exists(args.autotune_cache) else AutotuneCache()

    # sharded -> default all-device mesh
    mut = None
    if args.mutate:
        from repro.runtime import MutableBangIndex

        mut = MutableBangIndex(index)
        if telemetry is not None:
            mut.set_telemetry(telemetry)
        executor = mut.executor(args.variant, hostio=hostio)
    else:
        executor = index.executor(args.variant, hostio=hostio,
                                  autotune=autotune)

    if args.autotune:
        from repro.kernels.autotune import autotune_executor, device_kind

        tune_q = uniform_queries(data, min(args.batch_size, args.max_batch),
                                 seed=99)
        print(f"[serve] autotuning fused megakernel on {device_kind()} "
              f"(bucket for batch {len(tune_q)}) ...")
        autotune_executor(executor, tune_q, k=args.k, t=args.t,
                          cache=autotune)
        autotune.save(args.autotune_cache)
        for key, w in autotune.winners.items():
            print(f"[serve]   winner {key}: eager={w['eager']} "
                  f"codes_tile_rows={w['codes_tile_rows']} "
                  f"({w['per_hop_us']:.0f} us/hop)")
        print(f"[serve] winners persisted to {args.autotune_cache}")
    x = executor.exchange_bytes_per_hop(args.max_batch)
    if args.variant.startswith("sharded"):
        print(
            f"[serve] {args.variant} over {len(jax.devices())} devices "
            f"(model shards={x['model_shards']}): collective exchange "
            f"{x['collective_bytes']} B/hop (ring ~{x['ring_bytes_per_device']} "
            f"B/device)"
        )
    if x["host_link_bytes"]:
        print(
            f"[serve] host link per hop: {x['host_ids_out_bytes']} B frontier "
            f"ids out + {x['host_rows_in_bytes']} B adjacency rows back = "
            f"{x['host_link_bytes']} B (graph stays in host RAM)"
        )
    if args.kernel_mode != "reference":
        from repro.kernels.search_step import ops as step_ops

        trips = step_ops.hbm_candidate_roundtrips_per_hop(args.kernel_mode)
        if args.kernel_mode == "fused" and args.variant.startswith("sharded"):
            # The mesh path splits the fused step: owner-shard local_adc
            # kernel -> psum over `model` -> fused traverse kernel, so the
            # distances cross HBM once more for the collective.
            print(
                "[serve] kernel-mode fused (sharded): owner-shard fused "
                "gather+ADC kernel + psum + fused traverse kernel (candidate "
                "tile crosses HBM once each side of the collective)"
            )
        else:
            print(
                f"[serve] kernel-mode {args.kernel_mode}: candidate tile "
                f"crosses HBM {trips}x per hop"
            )
    if hostio is not None:
        print(
            f"[serve] host-I/O subsystem: {hostio.workers} worker(s)/partition"
            f", hot cache {hostio.hot_cache_rows} rows, "
            f"prefetch={'on' if hostio.prefetch else 'off'}"
        )
    pipe = ServePipeline(
        executor, k=args.k, cfg=cfg, max_batch=args.max_batch,
        kernel_mode=args.kernel_mode, result_cache_size=args.result_cache,
        max_queue=args.max_queue, deadline_s=args.deadline_ms / 1e3,
        telemetry=telemetry,
    )

    def on_batch(rep) -> None:
        compile_note = f", compile {rep.compile_s:.1f}s" if rep.compile_s else ""
        recall = "" if rep.recall is None else f", recall@{args.k}={rep.recall:.3f}"
        print(
            f"[serve] batch {rep.index}: {rep.size} queries in "
            f"{rep.wall_s*1e3:.0f}ms ({rep.size/rep.wall_s:.0f} QPS"
            f"{compile_note}){recall}"
        )

    if mut is None:
        for b in range(args.batches):
            queries = uniform_queries(data, args.batch_size, seed=100 + b)
            gt = brute_force_knn(data, queries, args.k)
            pipe.submit(queries, gt_ids=gt)
        _, _, stats = pipe.drain(on_batch=on_batch)
        total_queries = stats.queries
    else:
        # Mutate-under-load demo: each serving batch is preceded by a few
        # deletes + inserts (recall scored against the live corpus), with a
        # background consolidation kicked off halfway through.
        import numpy as np

        rng = np.random.default_rng(0)
        medoid = int(index.graph.medoid)
        consolidation = None
        total_queries = 0
        for b in range(args.batches):
            live_ids, _ = mut.live_points()
            mut.delete([int(v) for v in rng.choice(live_ids, 4, replace=False)
                        if int(v) != medoid])
            fresh = data[rng.integers(len(data), size=4)]
            fresh = fresh + rng.normal(0, 0.02, fresh.shape).astype(np.float32)
            mut.insert(fresh)
            if b == args.batches // 2:
                consolidation = mut.consolidate_async()
                print("[serve] background consolidation started")
            queries = uniform_queries(data, args.batch_size, seed=100 + b)
            live_ids, live_vecs = mut.live_points()
            gt = live_ids[np.asarray(brute_force_knn(live_vecs, queries,
                                                     args.k))]
            pipe.submit(queries, gt_ids=gt)
            _, _, stats = pipe.drain(on_batch=on_batch)
            total_queries += stats.queries
        if consolidation is not None:
            consolidation.join()
            if mut.consolidate_error is not None:
                raise mut.consolidate_error
    recall = ("n/a" if stats.mean_recall is None
              else f"{stats.mean_recall:.3f}")
    print(
        f"[serve] TOTAL {total_queries} queries | steady-state "
        f"{stats.qps:.0f} QPS (compile {stats.compile_s:.1f}s excluded)"
    )
    print(
        f"[serve] latency p50={stats.p50_ms:.0f}ms p95={stats.p95_ms:.0f}ms | "
        f"mean recall@{args.k}={recall} (variant={args.variant}, "
        f"kernel-mode={args.kernel_mode})"
    )
    if args.result_cache:
        print(
            f"[serve] result cache: {stats.result_cache_hits} hits "
            f"({stats.result_cache_hit_rate:.1%} of queries)"
        )
    if args.max_queue or args.deadline_ms:
        print(
            f"[serve] admission control: {stats.shed_queries} shed "
            f"(queue bound {args.max_queue or 'off'}), "
            f"{stats.expired_queries} expired "
            f"(deadline {args.deadline_ms or 'off'} ms)"
        )
    if stats.hostio is not None and args.host_deadline_ms:
        h = stats.hostio
        print(
            f"[serve] host resilience: {h['retries']} retries, "
            f"{h['hedged_gathers']} hedged, {h['degraded_lanes']} degraded "
            f"lanes, {h['worker_deaths']} worker deaths, "
            f"{h['partitions_down']} partition(s) down"
        )
    if stats.hostio is not None:
        h = stats.hostio
        xb = executor.exchange_bytes_per_hop(args.max_batch)
        print(
            f"[serve] host-I/O: {h['requests']} requests, "
            f"max queue depth {h['max_queue_depth']}, "
            f"mean gather {h['mean_latency_ms']:.2f}ms | "
            f"hot-cache hit rate {h['cache_hit_rate']:.1%} "
            f"(~{xb['host_bytes_saved_per_hop']} B/hop saved) | "
            f"prefetch overlap {h['overlap_fraction']:.1%} "
            f"({h['prefetch_hits']} hits, {h['prefetch_misses']} misses)"
        )
    if mut is not None and stats.mutation is not None:
        ms = stats.mutation
        print(
            f"[serve] mutation: epoch {ms['epoch']}, generation "
            f"{ms['generation']} ({ms['consolidations']} consolidation(s)), "
            f"{ms['tombstones']} tombstones "
            f"({ms['tombstone_fraction']:.2%}), {ms['delta_points']} live "
            f"delta points, base_n={ms['base_n']}"
        )
    if telemetry is not None and telemetry.profiler is not None:
        p = telemetry.profiler.summary()
        stream = ("n/a" if p["codes_stream_bytes_per_hop"] is None
                  else f"{p['codes_stream_bytes_per_hop']} B/hop modeled")
        print(
            f"[serve] hop profile: {p['hops']} host-seam hops, gather wall "
            f"p50={p['hop_wall_s_p50']*1e3:.2f}ms "
            f"p95={p['hop_wall_s_p95']*1e3:.2f}ms "
            f"(total {p['hop_wall_s_total']*1e3:.0f}ms) | frontier occupancy "
            f"{p['frontier_occupancy']:.1%} "
            f"({p['cache_hit_lanes_total']} cache-hit lanes) | "
            f"codes stream {stream}"
        )
    if telemetry is not None and args.trace_out:
        telemetry.tracer.save(args.trace_out)
        n_ev = len(telemetry.tracer.events())
        dropped = telemetry.tracer.dropped_events
        print(f"[serve] Chrome trace written to {args.trace_out} "
              f"({n_ev} events, {dropped} dropped)")
    if telemetry is not None and args.metrics_json:
        if args.metrics_json == "-":
            print(telemetry.registry.to_prom(), end="")
        elif args.metrics_json.endswith(".prom"):
            with open(args.metrics_json, "w") as f:
                f.write(telemetry.registry.to_prom())
            print(f"[serve] Prometheus metrics written to {args.metrics_json}")
        else:
            import json

            with open(args.metrics_json, "w") as f:
                json.dump(telemetry.registry.to_json(), f, indent=2)
            print(f"[serve] metrics JSON written to {args.metrics_json}")
    pipe.close()
    if mut is not None:
        mut.close()


if __name__ == "__main__":
    main()
