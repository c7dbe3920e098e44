"""Pallas TPU megakernel: one fused Algorithm-2 traversal iteration (§4.5-§4.8).

The paper wins its throughput by fusing the per-iteration stages so candidate
lists never leave fast memory; CAGRA (arXiv:2308.15136) keeps the whole
traversal step in shared memory for the same reason. Our staged kernel path
is the opposite: four separate `pallas_call`s (ADC, sort, merge, re-rank glue)
with full HBM round-trips of the (B, R) candidate tile between them. This
kernel executes the *whole iteration body* per grid program, entirely in VMEM:

    code fetch        each candidate's PQ code row is fetched by id inside
                      the kernel (no (B, R, m) HBM temporary)
    ADC distance      one-hot select of the query's table rows, added in
                      subspace order (`pq_adc.adc_column`)
    sort              full bitonic network over the candidate block
    selection         §4.6 eager (pre-merge best-of-two) or lazy (post-merge
                      first-unvisited) candidate selection
    merge             bitonic merge phase into the (t,) worklist, visited
                      marking included

so per hop the candidate tile touches HBM exactly once (the kernel input).
Grid: 8 queries per program (one sublane tile). The compute helpers are
shared with the standalone kernels (`pq_adc.adc_column`,
`bitonic.bitonic_stages`): the megakernel changes the schedule, not the math,
and fused results stay bit-identical to staged.

Lane layout: every worklist/candidate row is P = max(128, pow2(t + Rp))
lanes wide (Rp = pow2(R)). The worklist fills lanes [0, t); the candidates
fill the last Rp-lane block, which is an odd block, so the sort network
leaves them descending and the worklist ++ candidates row is already the
bitonic sequence the merge phase needs.

Code rows: the (n, m) uint8 codes are packed by `code_lines` into (L, 128)
int32 lines of 512 bytes, each holding 512 / pow2(m) consecutive rows; one
candidate's codes are one aligned word range of one line. The kernel fetches
the line of every candidate by its id (ids arrive in SMEM): with the lines
resident in VMEM (`resident=True`, while they fit the VMEM budget) by a
dynamic row load, beyond that from HBM by one row DMA per candidate, so a
hop reads B * R lines, never the whole block.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.bitonic.bitonic import bitonic_stages, lane_iota
from repro.kernels.common import LANES, next_pow2
from repro.kernels.pq_adc.pq_adc import adc_column, column_to_row

INVALID = 2**31 - 1  # plain int: jnp scalars would be captured consts in kernels
QROWS = 8            # queries per program (one sublane tile)
LINE_BYTES = 4 * LANES


def row_words(m: int) -> int:
    """int32 words one packed code row occupies (m rounded up to pow2 >= 4)."""
    mp = max(4, next_pow2(m))
    if mp > LINE_BYTES:
        raise ValueError(f"m={m} exceeds a {LINE_BYTES}-byte code line")
    return mp // 4


def code_lines(codes: jax.Array) -> jax.Array:
    """(n, m) uint8 codes -> (L, 128) int32 lines, little-endian bytes.

    Row i's code j is byte j % 4 of word (i % rows_per_line) * W + j // 4 of
    line i // rows_per_line, W = row_words(m). Zero padding is never read.
    """
    n, m = codes.shape
    W = row_words(m)
    rpl = LANES // W
    L = -(-n // rpl)
    c = jnp.pad(codes, ((0, L * rpl - n), (0, 4 * W - m))).reshape(L, LINE_BYTES)
    # Strided lane slices, not a (..., 4) axis: a minor dim of 4 would be
    # padded to 128 lanes in HBM.
    word = jnp.zeros((L, LANES), jnp.int32)
    for b in range(4):
        word = word | (c[:, b::4].astype(jnp.int32) << (8 * b))
    return word


def lines_bytes(n: int, m: int) -> int:
    """Bytes of the packed (L, 128) int32 code lines of an (n, m) block."""
    rpl = LANES // row_words(m)
    return -(-n // rpl) * LINE_BYTES


def _layout(t: int, R: int) -> tuple[int, int]:
    """(Rp, P): candidate block width and row width (see module docstring)."""
    Rp = next_pow2(R)
    return Rp, max(LANES, next_pow2(t + Rp))


def _first_unvisited(ids, vis, lane):
    """(Q, P) -> first unvisited id per row (INVALID if none), found flag."""
    P = ids.shape[-1]
    pos = jnp.min(jnp.where(vis == 0, lane, P), axis=-1, keepdims=True)
    found = pos < P
    u = jnp.sum(jnp.where(lane == pos, ids, 0), axis=-1, keepdims=True)
    return jnp.where(found, u, INVALID), found


def _traverse_math(wld, wli, wlv, cd, ci, act, *, eager: bool, t: int, Rp: int):
    """Sort + select + merge on (Q, P) rows (any Pallas kernel body).

    wld/wli/wlv: worklist in lanes [0, t), padded (+inf, INVALID, visited);
    cd/ci: candidates in the last Rp-lane block, (+inf, INVALID) elsewhere;
    act: (Q, 1) > 0 for still-active queries.
    Returns (wld', wli', wlv' (Q, P), u_next (Q, 1), active' (Q, 1) int32).
    """
    P = wld.shape[-1]
    lane = lane_iota(wld.shape)
    # §4.7 sort: the candidate block is odd, so it ends up descending.
    sd, si, _ = bitonic_stages(cd, ci, None, Rp, full_sort=True)

    def merge():
        # §4.8 merge: worklist ascending ++ candidates descending is bitonic,
        # so only the final merge phase runs.
        first = lane < P - Rp
        d = jnp.where(first, wld, sd)
        i = jnp.where(first, wli, si)
        v = jnp.where(first, wlv, 0)
        d, i, v = bitonic_stages(d, i, v, P, full_sort=False)
        # INVALID slots are never expandable: force them visited so bitonic
        # tie-shuffling of (inf, INVALID) pads can't leak an unvisited pad
        # into the kept prefix (the stable lax.sort reference never does).
        # Lanes past t are dropped, so they never count as unvisited either.
        return d, i, jnp.where((i == INVALID) | (lane >= t), 1, v)

    if eager:
        # §4.6 eager selection: best of {first unvisited of the *pre-merge*
        # worklist, nearest fresh candidate} -- computable before the merge.
        wl_u, wl_found = _first_unvisited(wli, wlv, lane)
        wl_d = jnp.where(
            wl_found,
            jnp.min(jnp.where(wlv > 0, jnp.inf, wld), axis=-1, keepdims=True),
            jnp.inf,
        )
        cand_d, cand_i = sd[:, P - 1:P], si[:, P - 1:P]
        u_next = jnp.where(cand_d < wl_d, cand_i, wl_u)
        found = wl_found | (cand_i != INVALID)
        d, i, v = merge()
    else:
        d, i, v = merge()
        u_next, found = _first_unvisited(i, v, lane)

    active = (act > 0) & found
    u_next = jnp.where(active, u_next, INVALID)
    v = jnp.where(i == u_next, 1, v)                # mark_visited, fused
    return d, i, v, u_next, active.astype(jnp.int32)


def _gather_adc(ids_ref, table_ref, lines_ref, idcol_ref, rows_ref, sem,
                dist_ref, *, resident: bool, off: int):
    """Fetch every candidate's code line, then ADC -> dist_ref (Q, width).

    ids_ref (Q, Ra) SMEM row ids; idcol_ref (Q * Ra, 1) the same ids as a
    column; candidate r of query q lands at lane off + r of dist_ref row q.
    """
    Q, Ra = ids_ref.shape
    m = table_ref.shape[1]
    W = row_words(m)
    shift = (LANES // W).bit_length() - 1           # log2(rows per line)

    for q in range(Q):
        def fetch(r, carry, q=q):
            line = ids_ref[q, r] >> shift
            dst = pl.ds(q * Ra + r, 1)
            if resident:
                rows_ref[dst, :] = lines_ref[pl.ds(line, 1), :]
            else:
                pltpu.make_async_copy(
                    lines_ref.at[pl.ds(line, 1), :], rows_ref.at[dst, :],
                    sem.at[0],
                ).start()
            return carry

        jax.lax.fori_loop(0, Ra, fetch, 0)
    if not resident:
        def drain(k, carry):
            pltpu.make_async_copy(
                lines_ref.at[pl.ds(0, 1), :], rows_ref.at[pl.ds(0, 1), :],
                sem.at[0],
            ).wait()
            return carry

        jax.lax.fori_loop(0, Q * Ra, drain, 0)

    lane = jax.lax.broadcasted_iota(jnp.int32, (Ra, LANES), 1)
    for q in range(Q):
        rows = rows_ref[pl.ds(q * Ra, Ra), :]                       # (Ra, 128)
        base = (idcol_ref[pl.ds(q * Ra, Ra), :] & (LANES // W - 1)) * W

        def code_col(j, rows=rows, base=base):
            word = jnp.sum(
                jnp.where(lane == base + j // 4, rows, 0), axis=1, keepdims=True
            )
            return jax.lax.shift_right_logical(word, (j % 4) * 8) & 255

        col = adc_column(
            lambda j, q=q: table_ref[q, pl.ds(j, 1), :], code_col, m, Ra
        )
        dist_ref[pl.ds(q, 1), :] = column_to_row(col, dist_ref.shape[1], off)


def _fused_step_kernel(
    ids_ref, table_ref, lines_ref, idcol_ref, nbr_ref, fresh_ref,
    wld_ref, wli_ref, wlv_ref, act_ref,
    owd_ref, owi_ref, owv_ref, un_ref, oact_ref,
    rows_ref, sem, dist_ref,
    *, eager: bool, t: int, Rp: int, resident: bool,
):
    P = wld_ref.shape[1]
    _gather_adc(ids_ref, table_ref, lines_ref, idcol_ref, rows_ref, sem,
                dist_ref, resident=resident, off=P - Rp)
    fresh = fresh_ref[...] > 0
    cd = jnp.where(fresh, dist_ref[...], jnp.inf)
    ci = jnp.where(fresh, nbr_ref[...], INVALID)
    d, i, v, u, a = _traverse_math(
        wld_ref[...], wli_ref[...], wlv_ref[...], cd, ci, act_ref[...],
        eager=eager, t=t, Rp=Rp,
    )
    owd_ref[...] = d
    owi_ref[...] = i
    owv_ref[...] = v
    un_ref[...] = u
    oact_ref[...] = a


def _traverse_kernel(
    cd_ref, ci_ref, wld_ref, wli_ref, wlv_ref, act_ref,
    owd_ref, owi_ref, owv_ref, un_ref, oact_ref,
    *, eager: bool, t: int, Rp: int,
):
    # Traverse-only variant: distances arrive precomputed (e.g. the sharded
    # owner-ADC + psum path).
    d, i, v, u, a = _traverse_math(
        wld_ref[...], wli_ref[...], wlv_ref[...], cd_ref[...], ci_ref[...],
        act_ref[...], eager=eager, t=t, Rp=Rp,
    )
    owd_ref[...] = d
    owi_ref[...] = i
    owv_ref[...] = v
    un_ref[...] = u
    oact_ref[...] = a


def _local_adc_kernel(ids_ref, table_ref, lines_ref, idcol_ref, own_ref,
                      out_ref, rows_ref, sem, *, resident: bool):
    # Owner-shard fused gather+ADC: shard-relative ids, ownership mask. Output
    # 0 where not owned -- the psum over `model` reconstructs the full row.
    _gather_adc(ids_ref, table_ref, lines_ref, idcol_ref, rows_ref, sem,
                out_ref, resident=resident, off=0)
    out_ref[...] = jnp.where(own_ref[...] > 0, out_ref[...], 0.0)


def _rows(x, rows: int, lo: int, hi: int, value):
    """Pad x (B, w) with `rows` extra rows and lanes [lo | x | hi]."""
    return jnp.pad(x, ((0, rows), (lo, hi)), constant_values=value)


def _id_operands(ids, pad_b: int):
    """Row ids (B, R) -> SMEM block operand (Bp, Ra) and column (Bp*Ra, 1)."""
    R = ids.shape[1]
    Ra = R + (-R) % 8
    ids = _rows(ids.astype(jnp.int32), pad_b, 0, Ra - R, 0)
    return ids, ids.reshape(-1, 1), Ra


def _gather_specs(m: int, Ra: int, lines_shape, resident: bool):
    """in_specs for (ids SMEM, table, lines, id column) of a gather kernel."""
    return [
        pl.BlockSpec((QROWS, Ra), lambda b: (b, 0), memory_space=pltpu.SMEM),
        pl.BlockSpec((QROWS, m, 256), lambda b: (b, 0, 0)),
        (pl.BlockSpec(lines_shape, lambda b: (0, 0)) if resident
         else pl.BlockSpec(memory_space=pl.ANY)),
        pl.BlockSpec((QROWS * Ra, 1), lambda b: (b, 0)),
    ]


def _compiler_params(lines_shape, resident: bool):
    # The resident lines block is double-buffered by the pipeline; leave
    # room for it on top of the default scoped VMEM.
    extra = 2 * lines_shape[0] * LINE_BYTES if resident else 0
    return pltpu.CompilerParams(vmem_limit_bytes=16 * 2**20 + extra)


def _traverse_operands(wld, wli, wlv, active, pad_b: int, P: int):
    t = wld.shape[1]
    return (
        _rows(wld.astype(jnp.float32), pad_b, 0, P - t, jnp.inf),
        _rows(wli.astype(jnp.int32), pad_b, 0, P - t, INVALID),
        _rows(wlv.astype(jnp.int32), pad_b, 0, P - t, 1),
        _rows(active.astype(jnp.int32)[:, None], pad_b, 0, 0, 0),
    )


def _traverse_outputs(Bp: int, P: int):
    spec_p = pl.BlockSpec((QROWS, P), lambda b: (b, 0))
    spec_1 = pl.BlockSpec((QROWS, 1), lambda b: (b, 0))
    specs = [spec_p, spec_p, spec_p, spec_1, spec_1]
    shapes = [
        jax.ShapeDtypeStruct((Bp, P), jnp.float32),
        jax.ShapeDtypeStruct((Bp, P), jnp.int32),
        jax.ShapeDtypeStruct((Bp, P), jnp.int32),
        jax.ShapeDtypeStruct((Bp, 1), jnp.int32),
        jax.ShapeDtypeStruct((Bp, 1), jnp.int32),
    ]
    return specs, shapes


def _unpad(out, B: int, t: int):
    d, i, v, u, a = out
    return (
        d[:B, :t], i[:B, :t], v[:B, :t].astype(jnp.bool_),
        u[:B, 0], a[:B, 0].astype(jnp.bool_),
    )


@functools.partial(jax.jit, static_argnames=("eager", "resident", "interpret"))
def fused_step_pallas(
    table: jax.Array,    # (B, m, 256) f32
    lines: jax.Array,    # (L, 128) i32 -- code_lines(codes)
    nbrs: jax.Array,     # (B, R) i32 candidate ids (post bloom)
    fresh: jax.Array,    # (B, R) bool
    wld: jax.Array,      # (B, t) f32
    wli: jax.Array,      # (B, t) i32
    wlv: jax.Array,      # (B, t) bool
    active: jax.Array,   # (B,) bool
    *,
    eager: bool = True,
    resident: bool = True,
    interpret: bool = True,
):
    B, t = wld.shape
    R = nbrs.shape[1]
    m = table.shape[1]
    Rp, P = _layout(t, R)
    pad_b = (-B) % QROWS
    Bp = B + pad_b
    safe = jnp.where(fresh, nbrs, 0)
    ids, idcol, Ra = _id_operands(safe, pad_b)
    place = lambda x, v: _rows(x, pad_b, P - Rp, Rp - R, v)
    spec_p = pl.BlockSpec((QROWS, P), lambda b: (b, 0))
    spec_1 = pl.BlockSpec((QROWS, 1), lambda b: (b, 0))
    out_specs, out_shape = _traverse_outputs(Bp, P)
    out = pl.pallas_call(
        functools.partial(
            _fused_step_kernel, eager=eager, t=t, Rp=Rp, resident=resident
        ),
        grid=(Bp // QROWS,),
        in_specs=_gather_specs(m, Ra, lines.shape, resident)
        + [spec_p] * 5 + [spec_1],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((QROWS * Ra, LANES), jnp.int32),
            pltpu.SemaphoreType.DMA((1,)),
            pltpu.VMEM((QROWS, P), jnp.float32),
        ],
        compiler_params=_compiler_params(lines.shape, resident),
        interpret=interpret,
        name="fused_step",
    )(
        ids,
        jnp.pad(table.astype(jnp.float32), ((0, pad_b), (0, 0), (0, 0))),
        lines,
        idcol,
        place(nbrs.astype(jnp.int32), INVALID),
        place(fresh.astype(jnp.int32), 0),
        *_traverse_operands(wld, wli, wlv, active, pad_b, P),
    )
    return _unpad(out, B, t)


@functools.partial(jax.jit, static_argnames=("eager", "interpret"))
def fused_traverse_pallas(
    cand_dists: jax.Array,   # (B, R) f32, +inf on masked lanes
    cand_ids: jax.Array,     # (B, R) i32, INVALID on masked lanes
    wld: jax.Array,
    wli: jax.Array,
    wlv: jax.Array,
    active: jax.Array,
    *,
    eager: bool = True,
    interpret: bool = True,
):
    B, t = wld.shape
    R = cand_dists.shape[1]
    Rp, P = _layout(t, R)
    pad_b = (-B) % QROWS
    Bp = B + pad_b
    place = lambda x, v: _rows(x, pad_b, P - Rp, Rp - R, v)
    spec_p = pl.BlockSpec((QROWS, P), lambda b: (b, 0))
    spec_1 = pl.BlockSpec((QROWS, 1), lambda b: (b, 0))
    out_specs, out_shape = _traverse_outputs(Bp, P)
    out = pl.pallas_call(
        functools.partial(_traverse_kernel, eager=eager, t=t, Rp=Rp),
        grid=(Bp // QROWS,),
        in_specs=[spec_p] * 5 + [spec_1],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
        name="fused_traverse",
    )(
        place(cand_dists.astype(jnp.float32), jnp.inf),
        place(cand_ids.astype(jnp.int32), INVALID),
        *_traverse_operands(wld, wli, wlv, active, pad_b, P),
    )
    return _unpad(out, B, t)


@functools.partial(jax.jit, static_argnames=("resident", "interpret"))
def local_adc_pallas(
    table: jax.Array,        # (B, m, 256) f32
    lines: jax.Array,        # (L, 128) i32 -- code_lines(codes_local)
    rel: jax.Array,          # (B, R) i32 shard-relative ids
    own: jax.Array,          # (B, R) bool ownership mask
    *,
    resident: bool = True,
    interpret: bool = True,
):
    B, R = rel.shape
    m = table.shape[1]
    pad_b = (-B) % QROWS
    Bp = B + pad_b
    Wo = R + (-R) % LANES
    ids, idcol, Ra = _id_operands(jnp.where(own, rel, 0), pad_b)
    spec_o = pl.BlockSpec((QROWS, Wo), lambda b: (b, 0))
    out = pl.pallas_call(
        functools.partial(_local_adc_kernel, resident=resident),
        grid=(Bp // QROWS,),
        in_specs=_gather_specs(m, Ra, lines.shape, resident) + [spec_o],
        out_specs=spec_o,
        out_shape=jax.ShapeDtypeStruct((Bp, Wo), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((QROWS * Ra, LANES), jnp.int32),
            pltpu.SemaphoreType.DMA((1,)),
        ],
        compiler_params=_compiler_params(lines.shape, resident),
        interpret=interpret,
        name="local_adc",
    )(
        ids,
        jnp.pad(table.astype(jnp.float32), ((0, pad_b), (0, 0), (0, 0))),
        lines,
        idcol,
        _rows(own.astype(jnp.int32), pad_b, 0, Wo - R, 0),
    )
    return out[:B, :R]
