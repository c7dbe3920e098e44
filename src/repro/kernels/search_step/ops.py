"""Jitted public wrappers for the fused search_step megakernel.

`fused_step` runs one whole Algorithm-2 iteration (in-kernel code fetch +
ADC + sort + §4.6 selection + merge + mark-visited) per grid program;
`fused_traverse` is the distances-precomputed variant the sharded executors
use after their owner-ADC psum; `local_adc` is that owner-shard fused
fetch+ADC. All dispatch to compiled Pallas on TPU and interpret elsewhere,
like every kernel package here. The fused kernels read the codes as packed
lines (`code_lines`), which callers build once per search, outside the loop.

`hbm_candidate_roundtrips_per_hop` / `hbm_intermediate_bytes_per_hop` are the
analytic HBM-traffic model the in-executor benchmark lane and the tests pin:
the staged path bounces the (B, R) candidate tile through HBM at every
kernel boundary (gathered codes in, ADC distances out/in, sorted tile
out/in), the fused path reads it exactly once and materialises no
intermediates.

Beyond VMEM: `codes_resident` decides, per codes block, whether the
fused kernels keep the packed lines VMEM-resident or leave them in HBM and
fetch each candidate's line by DMA. The decision point is the VMEM
budget (`vmem_budget_bytes`, overridable via the REPRO_VMEM_BUDGET env var so
tests can force the DMA path on small blocks), or an explicit
`SearchConfig.codes_tile_rows` > 0, which forces the HBM placement. The DMA
path fetches rows, not tiles, so the value only selects the placement.
Either way `kernel_mode="fused"` never falls back to the staged path.
"""
from __future__ import annotations

import os

import jax

from repro.core.worklist import Worklist
from repro.kernels.common import interpret_mode

from .ref import step_ref, traverse_ref
from .search_step import (
    LINE_BYTES,
    code_lines,
    fused_step_pallas,
    fused_traverse_pallas,
    lines_bytes,
    local_adc_pallas,
)

# VMEM the resident fused kernels may give the packed codes lines. An
# assumption, not a reading: v5e cores have 128 MiB of VMEM, and the kernel
# asks the compiler for twice this (the pipeline double-buffers the block)
# plus the default 16 MiB scoped limit.
DEFAULT_VMEM_BUDGET = 16 * 1024 * 1024


def vmem_budget_bytes() -> int:
    """VMEM budget for the resident codes lines (REPRO_VMEM_BUDGET wins)."""
    env = os.environ.get("REPRO_VMEM_BUDGET")
    return int(env) if env else DEFAULT_VMEM_BUDGET


def codes_resident(n: int, m: int, tile_rows: int = 0) -> bool:
    """Whether the fused kernels keep an (n, m) block's code lines in VMEM.

    `tile_rows` == 0 is the auto policy: resident while the packed lines
    fit `vmem_budget_bytes`, else in HBM with one row DMA per candidate.
    `tile_rows` > 0 forces the HBM placement -- the autotuner's knob --
    unless it covers the whole block.
    """
    if tile_rows < 0:
        raise ValueError(f"tile_rows must be >= 0, got {tile_rows}")
    if tile_rows:
        return tile_rows >= n
    return lines_bytes(n, m) <= vmem_budget_bytes()


def fused_step(
    table: jax.Array,
    lines: jax.Array,
    n: int,
    wl: Worklist,
    nbrs: jax.Array,
    fresh: jax.Array,
    active: jax.Array,
    *,
    eager: bool = True,
    tile_rows: int = 0,
) -> tuple[Worklist, jax.Array, jax.Array]:
    """One fused iteration: returns (worklist', u_next (B,), active' (B,)).

    `lines` = code_lines(codes) of the (n, m) codes block. `tile_rows`
    follows `codes_resident`; both placements are bit-identical.
    """
    d, i, v, u, a = fused_step_pallas(
        table, lines, nbrs, fresh, wl.dists, wl.ids, wl.visited, active,
        eager=eager, resident=codes_resident(n, table.shape[1], tile_rows),
        interpret=interpret_mode(),
    )
    return Worklist(d, i, v), u, a


def fused_traverse(
    wl: Worklist,
    cand_dists: jax.Array,
    cand_ids: jax.Array,
    active: jax.Array,
    *,
    eager: bool = True,
) -> tuple[Worklist, jax.Array, jax.Array]:
    """Fused sort+select+merge on precomputed candidate distances."""
    d, i, v, u, a = fused_traverse_pallas(
        cand_dists, cand_ids, wl.dists, wl.ids, wl.visited, active,
        eager=eager, interpret=interpret_mode(),
    )
    return Worklist(d, i, v), u, a


def local_adc(
    table: jax.Array,
    lines_local: jax.Array,
    n_loc: int,
    rel: jax.Array,
    own: jax.Array,
    *,
    tile_rows: int = 0,
) -> jax.Array:
    """Owner-shard fused fetch+ADC: (B, R) contributions, 0 where not owned.

    `lines_local` = code_lines of the shard's (n_loc, m) codes; `tile_rows`
    places them exactly like `fused_step`.
    """
    return local_adc_pallas(
        table, lines_local, rel, own,
        resident=codes_resident(n_loc, table.shape[1], tile_rows),
        interpret=interpret_mode(),
    )


# ---------------------------------------------------------------- accounting
def hbm_candidate_roundtrips_per_hop(mode: str) -> int:
    """How many times one hop's (B, R) candidate tile crosses HBM.

    staged: ADC writes it, sort reads+writes it, merge reads it -- four
    crossings at the pallas_call boundaries (the reference XLA path has the
    same four logical stage boundaries; XLA may fuse some). fused: the tile
    enters the megakernel once and every intermediate stays in VMEM.
    """
    return {"fused": 1, "staged": 4, "reference": 4}[mode]


def hbm_intermediate_bytes_per_hop(
    mode: str, batch: int, R: int, m: int, t: int
) -> int:
    """HBM bytes of *intermediates* one hop materialises between stages.

    Counts only arrays that exist in HBM between kernel stages (not the
    stage inputs the loop state already owns: neighbour ids, bloom filter,
    worklist). staged: the (B, R, m) i32 gathered-codes temporary feeding the
    ADC kernel, the (B, R) f32 ADC output, the sorted (B, R) f32+i32 tile out
    of the sort kernel. fused: none -- the gather, distances and sorted tile
    live only in VMEM.
    """
    if mode == "fused":
        return 0
    gathered_codes = batch * R * m * 4        # i32 temp before the ADC kernel
    adc_out = batch * R * 4                   # f32 distances
    sorted_tile = batch * R * (4 + 4)         # f32 dists + i32 ids
    return gathered_codes + adc_out + sorted_tile


def hbm_codes_stream_bytes_per_hop(
    mode: str, batch: int, n: int, m: int, R: int, tile_rows: int = 0
) -> int:
    """HBM bytes of *code lines* one fused hop reads.

    Resident placement: the packed lines block is staged into VMEM once per
    kernel call, i.e. once per hop. HBM placement: one 512-byte line per
    candidate lane. staged/reference gather only the (B, R, m) candidate
    rows -- already counted by `hbm_intermediate_bytes_per_hop` -- so this
    lane reports 0 for them: the two estimates never double-count.
    """
    if mode != "fused":
        return 0
    if codes_resident(n, m, tile_rows):
        return lines_bytes(n, m)
    return batch * R * LINE_BYTES


__all__ = [
    "code_lines",
    "codes_resident",
    "lines_bytes",
    "fused_step",
    "fused_traverse",
    "local_adc",
    "step_ref",
    "traverse_ref",
    "hbm_candidate_roundtrips_per_hop",
    "hbm_intermediate_bytes_per_hop",
    "hbm_codes_stream_bytes_per_hop",
    "vmem_budget_bytes",
    "DEFAULT_VMEM_BUDGET",
]
