"""BANG batched greedy search -- Algorithm 2 of the paper.

One query per "CUDA thread block" becomes one query per batch lane: the whole
batch advances in lock-step iterations of a `lax.while_loop`, with a
convergence mask standing in for per-block exit (justified by the paper's
Fig 10: 95% of queries finish within 1.1·L iterations, so lock-step wastes
little work). Each iteration performs exactly the paper's stages:

    fetch neighbours of u*        (CPU in BANG Base; device gather in-memory)
    bloom-filter visited           (§4.4)
    PQ asymmetric distances        (§4.5)
    sort neighbours                (§4.7)
    merge into worklist 𝓛          (§4.8; merge-path)
    select next candidate u*       (§4.6 eager selection overlaps the fetch
                                    with sort+merge -- realised here as
                                    software pipelining: the loop state carries
                                    the *pre-selected* candidate, so XLA can
                                    schedule its gather before/alongside the
                                    merge of the previous iteration)

The distance/sort/select/merge stages live behind a single pluggable
**StepFn** boundary (`SearchConfig.kernel_mode`):

    "reference"  pure XLA: `pq.adc_distance` ADC (a one-hot select on the
                 TPU, which lowers an element gather slowly; take_along_axis
                 elsewhere) + lax.sort (the oracle path)
    "staged"     separate Pallas kernels per stage (pq_adc / bitonic sort /
                 bitonic merge) -- the (B, R) candidate tile round-trips HBM
                 between every stage
    "fused"      the search_step megakernel: one pallas_call per iteration
                 executes the whole body in VMEM (in-kernel code gather, so
                 no (B, R, m) HBM temporary either); candidates touch HBM
                 once per hop

All three produce bit-identical neighbour ids (tests pin this); the legacy
`use_kernels=True` flag is an alias for kernel_mode="staged".

Variants (paper §5):
    base          graph + full vectors on the host (pure_callback adjacency
                  service == the paper's CPU-side neighbour fetch over PCIe)
    inmem         graph on device, PQ distances (BANG In-memory)
    exact         graph + data on device, exact L2 distances, no re-ranking
                  (BANG Exact-distance)

`repro.core.distributed` lifts the same loop to a device mesh ("sharded":
graph rows device-sharded; "sharded-base": graph rows in host RAM behind
per-shard callbacks) by passing its own StepFn built on sharded
neighbour/distance collectives.

Stage scopes: each stage runs under a `jax.named_scope` from `STAGES`, the
same in every kernel mode, so every op of the compiled program carries its
stage in its `op_name` metadata (`SearchExecutor.stage_map` reads it back
for the device trace). Scopes are metadata only: the computation is the
same with or without them. `ReferenceStep` adds `adc` and `merge`
sub-scopes under `bang.step`; the fused megakernel cannot have them.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.compat import pure_callback

from . import bloom as bloomlib
from . import pq as pqlib
from .worklist import (
    INVALID_ID,
    Worklist,
    first_unvisited,
    mark_visited,
    merge_worklist,
    sort_candidates,
    worklist_init,
)

Array = jax.Array

KERNEL_MODES = ("reference", "staged", "fused")

# Algorithm 2's stages as named scopes: the PQ distance table (stage 1), the
# neighbour fetch (host exchange or device gather), the bloom filter with the
# validity mask it consumes, the StepFn (distances, sort, selection, merge),
# the history update, and the re-rank (stage 3).
STAGES = ("bang.table", "bang.fetch", "bang.bloom", "bang.step",
          "bang.history", "bang.rerank")


@dataclasses.dataclass(frozen=True)
class SearchConfig:
    t: int = 64                  # worklist size (paper's search parameter t/L)
    max_iters: int = 0           # 0 -> ceil(1.5*t)+8 (Fig 10 headroom)
    bloom_z: int = 399887        # paper §6.3 default
    eager: bool = True           # §4.6 eager candidate selection
    use_kernels: bool = False    # legacy alias for kernel_mode="staged"
    kernel_mode: str | None = None  # "reference" | "staged" | "fused"
    # Fused-kernel codes placement (kernels.search_step.ops.codes_resident):
    # 0 auto-places the packed PQ codes (VMEM-resident while they fit the
    # budget, else in HBM with one row DMA per candidate); > 0 forces the
    # HBM placement -- the autotuner's knob. All placements are
    # bit-identical; non-fused modes ignore it (but it still keys compiled
    # executables).
    codes_tile_rows: int = 0

    def __post_init__(self) -> None:
        if self.codes_tile_rows < 0:
            raise ValueError(
                f"codes_tile_rows must be >= 0, got {self.codes_tile_rows}"
            )

    def iters(self) -> int:
        return self.max_iters if self.max_iters > 0 else int(1.5 * self.t) + 8

    def resolved_kernel_mode(self) -> str:
        """Explicit kernel_mode wins; else the legacy use_kernels flag."""
        if self.kernel_mode is not None:
            if self.kernel_mode not in KERNEL_MODES:
                raise ValueError(
                    f"unknown kernel_mode {self.kernel_mode!r}, expected one "
                    f"of {KERNEL_MODES}"
                )
            return self.kernel_mode
        return "staged" if self.use_kernels else "reference"

    def uses_kernels(self) -> bool:
        """Whether any Pallas fast path (incl. re-rank) should be used."""
        return self.resolved_kernel_mode() != "reference"


class SearchResult(NamedTuple):
    worklist: Worklist      # final 𝓛 (B, t), sorted
    history_ids: Array      # (B, C) every expanded candidate, INVALID padded
    history_len: Array      # (B,) number of expanded candidates
    n_iters: Array          # () total lock-step iterations executed
    n_hops: Array           # (B,) per-query expansions (== history_len)


class _State(NamedTuple):
    wl: Worklist
    filt: Array             # bloom filters (B * z,), flat
    hist_ids: Array         # (B, C)
    hist_len: Array         # (B,)
    u: Array                # (B,) pending candidate (eagerly selected)
    active: Array           # (B,) not yet converged
    it: Array               # ()
    tok: Array              # (1,) prefetch ticket ((0,) when prefetch is off)


NeighborFn = Callable[[Array], Array]     # (B,) ids -> (B, R) neighbour ids
DistanceFn = Callable[[Array, Array], Array]  # ids (B,R), valid -> dists (B,R)
# (B,) expected next frontier -> (1,) int32 ticket ordering issue vs collect.
# Built by repro.runtime.hostio.prefetch; when given, neighbor_fn takes
# (u, token) and redeems the previous hop's ticket.
PrefetchFn = Callable[[Array], Array]
# (B, R) candidate ids -> (B, R) bool "deleted" mask (streaming mutability).
TombstoneFn = Callable[[Array], Array]


def tombstone_mask_fn(tombstones: Array) -> TombstoneFn:
    """TombstoneFn over a device-resident (n,) bool bitmap.

    The streaming-mutability tombstone seam (`repro.runtime.mutation`):
    deleted ids are folded into the per-hop *validity* mask before the StepFn
    boundary, so they are treated exactly like adjacency padding across all
    three kernel modes -- never scored (dist stays +inf), never entered into
    𝓛 or the bloom filter, never eligible for §4.6 selection, and therefore
    never expanded, recorded in the re-rank history, or returned. Sentinel /
    negative / out-of-range ids are never reported deleted (padding already
    masks them).

    Degraded-mode serving rides the *same* validity seam from the other
    side (`repro.runtime.resilience`): when a host partition is down and a
    neighbour row cannot be fetched, the host service substitutes either a
    zero contribution -- which the exchange's `-1` shift turns into an
    all -1 row, dropped by the `(nbrs >= 0)` check below exactly like
    tombstone padding -- or the medoid's adjacency row (a medoid restart
    for that lane). Either way the substitution happens host-side inside
    the callback, so the traced program here never changes with host
    health and post-recovery results are structurally bit-exact.
    """
    n = tombstones.shape[0]

    def fn(ids: Array) -> Array:
        safe = jnp.clip(ids, 0, n - 1)
        in_range = (ids >= 0) & (ids < n)
        return tombstones[safe].astype(jnp.bool_) & in_range

    return fn


# ---------------------------------------------------------------------------
# StepFn: the per-iteration body (§4.5 distances + §4.7 sort + §4.6 select +
# §4.8 merge) behind one pluggable boundary.
# ---------------------------------------------------------------------------

class StepFn:
    """One Algorithm-2 iteration body.

    `init_dists(ids, valid)` seeds the worklist (medoid distance);
    `step(wl, nbrs, fresh, active)` consumes the bloom-filtered neighbour
    tile and returns `(worklist', u_next, active')` with the §4.6 selection
    applied and the selected slot already marked visited.

    `step_with_prefetch` is the **async-fetch seam** for the host-I/O
    subsystem (`repro.runtime.hostio`): it additionally calls `prefetch_fn`
    with the expected next frontier and returns the resulting (1,) ticket,
    which the search loop threads into the next hop's neighbour fetch. The
    default issues after the full step; implementations whose eager
    selection is visible pre-merge (ReferenceStep/StagedStep) override it to
    issue *between selection and merge*, so the host gather overlaps the
    merge -- exactly the concurrency §4.6 exists for.
    """

    eager: bool = True

    def init_dists(self, ids: Array, valid: Array) -> Array:
        raise NotImplementedError

    def step(
        self, wl: Worklist, nbrs: Array, fresh: Array, active: Array
    ) -> tuple[Worklist, Array, Array]:
        raise NotImplementedError

    def step_with_prefetch(
        self, wl: Worklist, nbrs: Array, fresh: Array, active: Array,
        prefetch_fn: "PrefetchFn",
    ) -> tuple[Worklist, Array, Array, Array]:
        wl, u_next, active = self.step(wl, nbrs, fresh, active)
        return wl, u_next, active, prefetch_fn(u_next)


class ReferenceStep(StepFn):
    """Pure-XLA body: gather ADC (via distance_fn) + lax.sort sort/merge."""

    def __init__(self, distance_fn: DistanceFn, eager: bool = True) -> None:
        self.distance_fn = distance_fn
        self.eager = eager

    def init_dists(self, ids: Array, valid: Array) -> Array:
        return self.distance_fn(ids, valid)

    def _sort(self, d: Array, i: Array) -> tuple[Array, Array]:
        return sort_candidates(d, i)

    def _merge(self, wl: Worklist, sd: Array, si: Array) -> Worklist:
        return merge_worklist(wl, sd, si)

    def _body(
        self, wl: Worklist, nbrs: Array, fresh: Array, active: Array,
        prefetch_fn: "PrefetchFn | None" = None,
    ) -> tuple[Worklist, Array, Array, Array | None]:
        # 3. PQ (or exact) distances for fresh neighbours.
        with jax.named_scope("adc"):
            d = self.distance_fn(nbrs, fresh)
        cand_ids = jnp.where(fresh, nbrs, INVALID_ID)

        # 4. Sort the candidate list (parallel merge sort / bitonic kernel).
        sd, si = self._sort(d, cand_ids)

        # 5. Candidate selection. Eager (§4.6): best of {first unvisited in
        #    the *pre-merge* worklist, nearest fresh neighbour} -- computable
        #    before the merge. Lazy: first unvisited of the merged worklist.
        tok = None
        if self.eager:
            wl_u, wl_found = first_unvisited(wl)
            wl_d = jnp.where(
                wl_found,
                jnp.min(jnp.where(wl.visited, jnp.inf, wl.dists), axis=-1),
                jnp.inf,
            )
            cand_best_d, cand_best_i = sd[:, 0], si[:, 0]
            take_cand = cand_best_d < wl_d
            u_next = jnp.where(take_cand, cand_best_i, wl_u)
            found = wl_found | (cand_best_i != INVALID_ID)
            if prefetch_fn is not None:
                # §4.6 realised: the expected frontier is known *before* the
                # merge, so the host gather for hop k+1 is issued here and
                # runs while the device merges hop k. Prediction only -- the
                # convergence masking below may still retire a lane, and
                # collect() inline-gathers any mismatched lane.
                tok = prefetch_fn(u_next)
            with jax.named_scope("merge"):
                wl = self._merge(wl, sd, si)
        else:
            with jax.named_scope("merge"):
                wl = self._merge(wl, sd, si)
            u_next, found = first_unvisited(wl)

        active = active & found
        u_next = jnp.where(active, u_next, INVALID_ID)
        wl = mark_visited(wl, u_next)
        if prefetch_fn is not None and tok is None:
            tok = prefetch_fn(u_next)        # lazy selection: post-merge issue
        return wl, u_next, active, tok

    def step(
        self, wl: Worklist, nbrs: Array, fresh: Array, active: Array
    ) -> tuple[Worklist, Array, Array]:
        wl, u_next, active, _ = self._body(wl, nbrs, fresh, active)
        return wl, u_next, active

    def step_with_prefetch(
        self, wl: Worklist, nbrs: Array, fresh: Array, active: Array,
        prefetch_fn: "PrefetchFn",
    ) -> tuple[Worklist, Array, Array, Array]:
        return self._body(wl, nbrs, fresh, active, prefetch_fn)


class StagedStep(ReferenceStep):
    """Per-stage Pallas kernels (pq_adc / bitonic): the legacy use_kernels
    path -- each stage is its own pallas_call with the (B, R) candidate tile
    round-tripping HBM between them."""

    def _sort(self, d: Array, i: Array) -> tuple[Array, Array]:
        from repro.kernels.bitonic import ops as bitonic_ops

        return bitonic_ops.sort_kv(d, i)

    def _merge(self, wl: Worklist, sd: Array, si: Array) -> Worklist:
        from repro.kernels.bitonic import ops as bitonic_ops

        return bitonic_ops.merge_worklist(wl, sd, si)


class FusedTraverseStep(StepFn):
    """Distances from `distance_fn`, sort+select+merge in one fused kernel.

    Used when the distance stage cannot live inside the kernel: the exact
    variant (full-vector L2) and the sharded executors (owner-shard ADC +
    psum over `model` must cross the mesh between ADC and sort).
    """

    def __init__(self, distance_fn: DistanceFn, eager: bool = True) -> None:
        self.distance_fn = distance_fn
        self.eager = eager

    def init_dists(self, ids: Array, valid: Array) -> Array:
        return self.distance_fn(ids, valid)

    def step(
        self, wl: Worklist, nbrs: Array, fresh: Array, active: Array
    ) -> tuple[Worklist, Array, Array]:
        from repro.kernels.search_step import ops as step_ops

        d = self.distance_fn(nbrs, fresh)
        cand_ids = jnp.where(fresh, nbrs, INVALID_ID)
        return step_ops.fused_traverse(wl, d, cand_ids, active, eager=self.eager)


class FusedStep(StepFn):
    """The whole iteration body in one search_step megakernel.

    The code fetch happens *inside* the kernel (satisfying the VMEM-only
    candidate path): no (B, R, m) gathered-codes HBM temporary, no (B, R)
    intermediate tiles between stages. `tile_rows` picks the codes
    placement (0 = auto: VMEM-resident while it fits the budget, else in HBM
    with one row DMA per candidate) -- beyond-VMEM blocks are read from HBM
    instead of falling back to the staged path, bit-identically.
    """

    def __init__(
        self, table: Array, codes: Array, eager: bool = True,
        tile_rows: int = 0,
    ) -> None:
        from repro.kernels.search_step import ops as step_ops

        self.table = table
        self.codes = codes
        # Packed once per search, before the loop: the kernel's row format.
        self.lines = step_ops.code_lines(codes)
        self.eager = eager
        self.tile_rows = tile_rows

    def init_dists(self, ids: Array, valid: Array) -> Array:
        # One-off medoid seeding: same one-hot ADC kernel as the staged path
        # (one candidate per query; keeping the op sequence identical keeps
        # the fused and staged traversals bit-identical from iteration 0).
        from repro.kernels.pq_adc import ops as adc_ops

        safe = jnp.where(valid, ids, 0)
        d = adc_ops.adc(self.table, self.codes[safe].astype(jnp.int32), valid)
        return jnp.where(valid, d, jnp.inf)

    def step(
        self, wl: Worklist, nbrs: Array, fresh: Array, active: Array
    ) -> tuple[Worklist, Array, Array]:
        from repro.kernels.search_step import ops as step_ops

        return step_ops.fused_step(
            self.table, self.lines, self.codes.shape[0], wl, nbrs, fresh,
            active, eager=self.eager, tile_rows=self.tile_rows,
        )


def make_step_fn(cfg: SearchConfig, distance_fn: DistanceFn) -> StepFn:
    """StepFn for a pluggable distance source (sharded / exact paths)."""
    mode = cfg.resolved_kernel_mode()
    if mode == "fused":
        return FusedTraverseStep(distance_fn, cfg.eager)
    if mode == "staged":
        return StagedStep(distance_fn, cfg.eager)
    return ReferenceStep(distance_fn, cfg.eager)


def _adc_step_fn(table: Array, codes: Array, cfg: SearchConfig) -> StepFn:
    """StepFn for the PQ variants: fused gets the full megakernel (in-kernel
    code gather); staged/reference keep the XLA gather in the DistanceFn."""
    mode = cfg.resolved_kernel_mode()
    if mode == "fused":
        return FusedStep(table, codes, cfg.eager, cfg.codes_tile_rows)
    return make_step_fn(cfg, _adc_distance_fn(table, codes, mode == "staged"))


def _adc_distance_fn(table: Array, codes: Array, use_kernels: bool) -> DistanceFn:
    """PQ asymmetric distances for candidate ids (paper §4.5).

    The XLA `codes[safe]` gather materialises a (B, R, m) temporary in HBM
    before the distance math -- exactly what the fused StepFn avoids by
    gathering inside the megakernel.
    """

    def fn(ids: Array, valid: Array) -> Array:
        safe = jnp.where(valid, ids, 0)
        gathered = codes[safe]                        # (B, R, m) uint8
        if use_kernels:
            from repro.kernels.pq_adc import ops as adc_ops

            d = adc_ops.adc(table, gathered, valid)
        else:
            d = pqlib.adc_distance(table, gathered)
        return jnp.where(valid, d, jnp.inf)

    return fn


def _exact_distance_fn(data: Array, queries: Array) -> DistanceFn:
    """Exact squared-L2 distances (BANG Exact-distance variant, §5.2)."""
    qn = jnp.sum(queries * queries, axis=-1)          # (B,)

    def fn(ids: Array, valid: Array) -> Array:
        safe = jnp.where(valid, ids, 0)
        vecs = data[safe].astype(jnp.float32)         # (B, R, d)
        vn = jnp.sum(vecs * vecs, axis=-1)            # (B, R)
        dot = jnp.einsum(
            "brd,bd->br", vecs, queries.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST,
        )
        d = qn[:, None] + vn - 2.0 * dot
        return jnp.where(valid, d, jnp.inf)

    return fn


def device_neighbor_fn(adjacency: Array) -> NeighborFn:
    """In-memory variant: adjacency rows gathered from device HBM."""

    def fn(u: Array) -> Array:
        safe = jnp.where(u == INVALID_ID, 0, u)
        nbrs = adjacency[safe]
        return jnp.where((u == INVALID_ID)[:, None], -1, nbrs)

    return fn


def host_neighbor_fn(adjacency_np: np.ndarray) -> NeighborFn:
    """BANG Base: the graph lives in host RAM; each hop crosses the link.

    jax.pure_callback is the JAX-native analogue of the paper's CPU-side
    neighbour service: the device ships the (B,) frontier ids out, the host
    gathers adjacency rows, and ships (B, R) ids back -- exactly the Algorithm
    2 line 5/6 traffic, and nothing else.
    """
    R = adjacency_np.shape[1]

    def host_gather(u: np.ndarray) -> np.ndarray:
        safe = np.where(u == np.int32(2**31 - 1), 0, u)
        out = adjacency_np[safe]
        out[u == np.int32(2**31 - 1)] = -1
        return out.astype(np.int32)

    def fn(u: Array) -> Array:
        shape = jax.ShapeDtypeStruct((u.shape[0], R), jnp.int32)
        return pure_callback(host_gather, shape, u)

    return fn


def _scoped(name: str, fn: Callable) -> Callable:
    """`fn` traced under the named scope `name`."""

    def wrapped(*args):
        with jax.named_scope(name):
            return fn(*args)

    return wrapped


def bang_search(
    queries: Array,
    *,
    neighbor_fn: NeighborFn,
    distance_fn: DistanceFn | None = None,
    step_fn: StepFn | None = None,
    medoid: int,
    n_points: int,
    cfg: SearchConfig,
    prefetch_fn: PrefetchFn | None = None,
    tombstone_fn: TombstoneFn | None = None,
) -> SearchResult:
    """Run Algorithm 2 for a batch of queries. Pure function of its inputs.

    The iteration body is `step_fn` (built from `cfg.kernel_mode` +
    `distance_fn` when not given explicitly); the neighbour source stays a
    separate callback because it is what the variants change (device gather,
    host callback, sharded collective).

    With `prefetch_fn` (the hostio double-buffered exchange) the loop state
    carries a (1,) prefetch ticket: each hop's `step_with_prefetch` issues
    the next hop's expected-frontier gather and `neighbor_fn(u, token)`
    redeems the previous ticket, so the host gather overlaps device compute.
    Results are bit-exact vs the synchronous path.

    With `tombstone_fn` (streaming mutability, `tombstone_mask_fn`) deleted
    neighbour ids are masked out of the per-hop validity mask *before* the
    StepFn boundary -- one seam that covers every kernel mode, because every
    step implementation already treats invalid lanes as +inf/INVALID padding.
    Deleted ids therefore never enter 𝓛, the bloom filter, the selection, or
    the re-rank history. The search entry point (medoid) must not be
    tombstoned -- `repro.runtime.mutation` enforces that at delete() time.
    """
    if step_fn is None:
        if distance_fn is None:
            raise ValueError("bang_search needs distance_fn or step_fn")
        step_fn = make_step_fn(cfg, distance_fn)
    if prefetch_fn is not None:
        # The prefetch is exchange work wherever it is issued, including from
        # inside the step (the §4.6 seam): innermost scope names the stage.
        prefetch_fn = _scoped("bang.fetch", prefetch_fn)
    B = queries.shape[0]
    t, C = cfg.t, cfg.iters()

    # --- Initialisation: 𝓛 = {medoid}, bloom = {medoid} (Algorithm 2 line 2).
    med = jnp.full((B,), medoid, jnp.int32)
    med_valid = jnp.ones((B, 1), jnp.bool_)
    with jax.named_scope("bang.step"):
        med_d = step_fn.init_dists(med[:, None], med_valid)[:, 0]   # (B,)
        wl0 = worklist_init(B, t)
        wl0 = Worklist(
            dists=wl0.dists.at[:, 0].set(med_d),
            ids=wl0.ids.at[:, 0].set(med),
            visited=wl0.visited.at[:, 0].set(True),  # medoid: first expansion
        )
    with jax.named_scope("bang.bloom"):
        filt0 = bloomlib.bloom_set(
            bloomlib.bloom_init(B, cfg.bloom_z), med[:, None])
    with jax.named_scope("bang.history"):
        hist0 = jnp.full((B, C), INVALID_ID, jnp.int32).at[:, 0].set(med)
    # Warm-start ticket: the medoid fetch of iteration 0 redeems a prefetch
    # issued before the loop, so even the first hop's gather can overlap the
    # worklist/bloom initialisation above.
    tok0 = (
        jnp.zeros((0,), jnp.int32) if prefetch_fn is None else prefetch_fn(med)
    )
    state = _State(
        wl=wl0,
        filt=filt0,
        hist_ids=hist0,
        hist_len=jnp.ones((B,), jnp.int32),
        u=med,
        active=jnp.ones((B,), jnp.bool_),
        it=jnp.zeros((), jnp.int32),
        tok=tok0,
    )

    def cond(s: _State) -> Array:
        return jnp.any(s.active) & (s.it < C - 1)

    def body(s: _State) -> _State:
        # 1. Fetch neighbours of the pending candidate (host or device). This
        #    is the op the eager selection (§4.6) exists to overlap: u was
        #    chosen in the previous iteration *before* that iteration's merge,
        #    so this gather has no data dependency on the previous merge.
        #    With the hostio prefetched exchange the overlap is real: the
        #    ticket in the loop state redeems the gather issued last hop.
        with jax.named_scope("bang.fetch"):
            if prefetch_fn is None:
                nbrs = neighbor_fn(s.u)                           # (B, R)
            else:
                nbrs = neighbor_fn(s.u, s.tok)                    # (B, R)
        with jax.named_scope("bang.bloom"):
            # The (nbrs >= 0) validity check is also the degraded-serving
            # seam: unfetchable lanes (host partition down, "mask" mode)
            # arrive as all -1 rows from the exchange and are dropped here
            # exactly like adjacency padding -- no extra operand, no retrace.
            valid = (nbrs >= 0) & s.active[:, None]
            if tombstone_fn is not None:
                # Streaming mutability (§4.6 selection / worklist-merge
                # masks): tombstoned neighbours become padding lanes right
                # here, before the bloom filter and the StepFn, so every
                # kernel mode scores them +inf and they never enter 𝓛 or
                # the final top-k.
                valid = valid & ~tombstone_fn(nbrs)

            # 2. Bloom filter: drop already-seen neighbours, insert fresh.
            fresh, filt = bloomlib.bloom_query_and_set(s.filt, nbrs, valid)

        # 3-5. Distances + sort + select + merge: the StepFn boundary
        #    ("reference" XLA / "staged" per-stage kernels / "fused"
        #    megakernel -- one pallas_call, candidates never leave VMEM).
        #    The prefetched path additionally issues hop k+1's expected
        #    gather inside the step (§4.6 seam) and returns its ticket.
        with jax.named_scope("bang.step"):
            if prefetch_fn is None:
                wl, u_next, active = step_fn.step(s.wl, nbrs, fresh, s.active)
                tok = s.tok
            else:
                wl, u_next, active, tok = step_fn.step_with_prefetch(
                    s.wl, nbrs, fresh, s.active, prefetch_fn
                )

        # 6. Record the expansion for re-ranking (paper: every candidate sent
        #    to the CPU is retained for the final re-rank).
        with jax.named_scope("bang.history"):
            b_idx = jnp.arange(B, dtype=jnp.int32)
            pos = jnp.minimum(s.hist_len, C - 1)
            hist = s.hist_ids.at[b_idx, pos].set(
                jnp.where(active, u_next, s.hist_ids[b_idx, pos])
            )
            hist_len = s.hist_len + active.astype(jnp.int32)

        return _State(wl, filt, hist, hist_len, u_next, active, s.it + 1, tok)

    final = jax.lax.while_loop(cond, body, state)
    return SearchResult(
        worklist=final.wl,
        history_ids=final.hist_ids,
        history_len=final.hist_len,
        n_iters=final.it,
        n_hops=final.hist_len,
    )


# ---------------------------------------------------------------------------
# Convenience wrappers binding the three variants.
# ---------------------------------------------------------------------------

def search_inmem(
    queries: Array,
    table: Array,
    codes: Array,
    adjacency: Array,
    medoid: int,
    cfg: SearchConfig,
    *,
    tombstone_fn: TombstoneFn | None = None,
) -> SearchResult:
    return bang_search(
        queries,
        neighbor_fn=device_neighbor_fn(adjacency),
        step_fn=_adc_step_fn(table, codes, cfg),
        medoid=medoid,
        n_points=codes.shape[0],
        cfg=cfg,
        tombstone_fn=tombstone_fn,
    )


def search_base(
    queries: Array,
    table: Array,
    codes: Array,
    adjacency_np: np.ndarray,
    medoid: int,
    cfg: SearchConfig,
    *,
    neighbor_fn: NeighborFn | None = None,
    prefetch_fn: PrefetchFn | None = None,
    tombstone_fn: TombstoneFn | None = None,
) -> SearchResult:
    """BANG Base. The default neighbour source is the inline synchronous
    host callback; the hostio subsystem passes its own (neighbor_fn,
    prefetch_fn) exchange (multi-worker service + hot cache + double
    buffering) -- bit-exact either way."""
    return bang_search(
        queries,
        neighbor_fn=neighbor_fn or host_neighbor_fn(adjacency_np),
        step_fn=_adc_step_fn(table, codes, cfg),
        medoid=medoid,
        n_points=codes.shape[0],
        cfg=cfg,
        prefetch_fn=prefetch_fn,
        tombstone_fn=tombstone_fn,
    )


def search_exact(
    queries: Array,
    data: Array,
    adjacency: Array,
    medoid: int,
    cfg: SearchConfig,
    *,
    tombstone_fn: TombstoneFn | None = None,
) -> SearchResult:
    # Exact distances come from full vectors, so even "fused" keeps the
    # distance stage outside the kernel (FusedTraverseStep).
    dist = _exact_distance_fn(data, queries.astype(jnp.float32))
    return bang_search(
        queries,
        neighbor_fn=device_neighbor_fn(adjacency),
        step_fn=make_step_fn(cfg, dist),
        medoid=medoid,
        n_points=data.shape[0],
        cfg=cfg,
        tombstone_fn=tombstone_fn,
    )
