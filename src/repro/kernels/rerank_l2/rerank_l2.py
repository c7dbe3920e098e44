"""Pallas TPU kernel: exact squared-L2 distances for re-ranking (paper §4.9).

After the search converges, every expanded candidate's *full* vector is
scored against the query exactly. The paper computes each candidate distance
with a parallel reduction per thread block; here each candidate's
sum((v - q)^2) is a lane reduction on the VPU, in f32 throughout (no MXU
pass that could round the operands).

Grid: (B/8, C/CT). Candidate tiles (8, CT, d) stream through VMEM while the
8 query rows stay resident; d is zero-padded to a lane multiple in the
wrapper (distance-neutral). Each query's (CT, 1) column of distances becomes
a lane row of the (8, CT) output tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import LANES
from repro.kernels.pq_adc.pq_adc import column_to_row

CT = 128  # candidates per program
QROWS = 8  # queries per program


def _rerank_kernel(q_ref, v_ref, out_ref):
    # q (8, d) f32 | v (8, CT, d) f32 -> out (8, CT) f32
    for q in range(QROWS):
        diff = v_ref[q] - q_ref[pl.ds(q, 1), :]                # (CT, d)
        col = jnp.sum(diff * diff, axis=1, keepdims=True)      # (CT, 1)
        out_ref[pl.ds(q, 1), :] = column_to_row(col, CT)


@functools.partial(jax.jit, static_argnames=("interpret",))
def exact_sq_dists_pallas(
    queries: jax.Array,    # (B, d)
    cand_vecs: jax.Array,  # (B, C, d)
    *,
    interpret: bool = True,
) -> jax.Array:
    B, C, d = cand_vecs.shape
    pad_d = (-d) % LANES
    pad_c = (-C) % CT
    pad_b = (-B) % QROWS
    queries = jnp.pad(queries.astype(jnp.float32), ((0, pad_b), (0, pad_d)))
    cand_vecs = jnp.pad(
        cand_vecs.astype(jnp.float32), ((0, pad_b), (0, pad_c), (0, pad_d))
    )
    dp = d + pad_d
    out = pl.pallas_call(
        _rerank_kernel,
        grid=((B + pad_b) // QROWS, (C + pad_c) // CT),
        in_specs=[
            pl.BlockSpec((QROWS, dp), lambda b, c: (b, 0)),
            pl.BlockSpec((QROWS, CT, dp), lambda b, c: (b, c, 0)),
        ],
        out_specs=pl.BlockSpec((QROWS, CT), lambda b, c: (b, c)),
        out_shape=jax.ShapeDtypeStruct((B + pad_b, C + pad_c), jnp.float32),
        interpret=interpret,
        name="rerank_l2",
    )(queries, cand_vecs)
    return out[:B, :C]
