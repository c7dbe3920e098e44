"""Pallas TPU kernel: PQ asymmetric distance computation (paper §4.5).

The paper's hottest kernel (~38% of billion-scale runtime): for each query and
each of its R candidate neighbours, sum m per-subspace centroid distances out
of the query's PQDistTable. The CUDA version tunes segmented warp reductions
(atomics vs CUB WarpReduce); neither exists on TPU, so we ADAPT: candidates
sit on the sublanes, and each subspace's lookup is a one-hot select of the
(1, 256) table row followed by a lane sum. The sum has exactly one non-zero
term, so every lookup is exact, and the m lookups are added in subspace
order -- the order `repro.core.pq.adc_distance` uses -- so the reference,
staged and fused search paths produce bit-identical distances.

Grid: 8 queries per program (one sublane tile). The (R, 1) column of each
query's distances is turned into a lane row by a diagonal select, the lane
layout the sort and merge kernels work in.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import LANES

QROWS = 8  # queries per program


def adc_column(table_row, code_col, m: int, rows: int) -> jax.Array:
    """sum_j table_row(j)[code_col(j)], added in order j = 0..m-1 -> (rows, 1).

    table_row(j) -> (1, 256) f32 row of subspace j; code_col(j) -> (rows, 1)
    int32 codes of subspace j. The shared ADC inner loop of this kernel and
    of the fused search_step megakernel (repro.kernels.search_step).
    """
    iota = jax.lax.broadcasted_iota(jnp.int32, (rows, 256), 1)

    def body(j, acc):
        hit = code_col(j) == iota
        term = jnp.where(hit, table_row(j), 0.0)
        return acc + jnp.sum(term, axis=1, keepdims=True)

    return jax.lax.fori_loop(0, m, body, jnp.zeros((rows, 1), jnp.float32))


def column_to_row(col: jax.Array, width: int, off: int = 0) -> jax.Array:
    """(rows, 1) -> (1, width): col[r] at lane off + r, 0 on every other lane."""
    rows = col.shape[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, width), 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (rows, width), 0)
    return jnp.sum(jnp.where(lane == row + off, col, 0.0), axis=0, keepdims=True)


def _adc_kernel(table_ref, codes_ref, valid_ref, out_ref):
    # table (Q, m, 256) f32 | codes (Q, Ra, m) i32 | valid/out (Q, W)
    Q, Ra, m = codes_ref.shape
    W = out_ref.shape[1]
    lane_m = jax.lax.broadcasted_iota(jnp.int32, (Ra, m), 1)
    for q in range(Q):
        cod = codes_ref[q]
        col = adc_column(
            lambda j: table_ref[q, pl.ds(j, 1), :],
            lambda j: jnp.sum(jnp.where(lane_m == j, cod, 0), axis=1, keepdims=True),
            m, Ra,
        )
        out_ref[pl.ds(q, 1), :] = column_to_row(col, W)
    out_ref[...] = jnp.where(valid_ref[...] > 0, out_ref[...], jnp.inf)


@functools.partial(jax.jit, static_argnames=("interpret",))
def adc_pallas(
    table: jax.Array,    # (B, m, 256) f32
    codes: jax.Array,    # (B, R, m) int32
    valid: jax.Array,    # (B, R) bool
    *,
    interpret: bool = True,
) -> jax.Array:
    B, m, _ = table.shape
    R = codes.shape[1]
    pad_b = (-B) % QROWS
    Ra = R + (-R) % 8
    W = R + (-R) % LANES
    table = jnp.pad(table, ((0, pad_b), (0, 0), (0, 0)))
    codes = jnp.pad(codes.astype(jnp.int32), ((0, pad_b), (0, Ra - R), (0, 0)))
    valid = jnp.pad(valid.astype(jnp.int32), ((0, pad_b), (0, W - R)))
    Bp = B + pad_b
    out = pl.pallas_call(
        _adc_kernel,
        grid=(Bp // QROWS,),
        in_specs=[
            pl.BlockSpec((QROWS, m, 256), lambda b: (b, 0, 0)),
            pl.BlockSpec((QROWS, Ra, m), lambda b: (b, 0, 0)),
            pl.BlockSpec((QROWS, W), lambda b: (b, 0)),
        ],
        out_specs=pl.BlockSpec((QROWS, W), lambda b: (b, 0)),
        out_shape=jax.ShapeDtypeStruct((Bp, W), jnp.float32),
        interpret=interpret,
        name="pq_adc",
    )(table, codes, valid)
    return out[:B, :R]
