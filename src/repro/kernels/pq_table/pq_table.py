"""Pallas TPU kernel: PQDistTable construction (paper §4.2).

For every query subvector q_j (dsub dims) compute its squared L2 distance to
all 256 centroids of subspace j. The CUDA version assigns one thread block per
query and loops subspaces sequentially per thread; on TPU we turn the whole
thing into MXU matmuls via the identity

    ||q - c||^2 = ||q||^2 - 2 q.c + ||c||^2

Grid: (m, B/BQ). Each program multiplies a (BQ, dsub) query tile against one
subspace's (256, dsub) centroid block -- dsub is zero-padded to a multiple of
128 in the wrapper (lane alignment; padding is distance-neutral since both
operands pad with zeros). The centroid norms arrive precomputed as a lane row.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import LANES

BQ = 8  # queries per program (sublane dim of the MXU operand)


def _table_kernel(q_ref, cb_ref, cn_ref, out_ref):
    # q (1, BQ, dsub) | cb (1, 256, dsub) | cn (1, 1, 256) -> out (1, BQ, 256)
    q = q_ref[0]
    qn = jnp.sum(q * q, axis=-1, keepdims=True)               # (BQ, 1)
    qc = jax.lax.dot_general(
        q, cb_ref[0], (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )                                                         # (BQ, 256)
    out_ref[0] = qn + cn_ref[0] - 2.0 * qc


@functools.partial(jax.jit, static_argnames=("interpret",))
def dist_table_pallas(
    q_sub: jax.Array,      # (B, m, dsub) f32
    codebooks: jax.Array,  # (m, 256, dsub) f32
    *,
    interpret: bool = True,
) -> jax.Array:
    B, m, dsub = q_sub.shape
    pad_d = (-dsub) % LANES
    pad_b = (-B) % BQ
    q = jnp.pad(q_sub.transpose(1, 0, 2), ((0, 0), (0, pad_b), (0, pad_d)))
    cb = jnp.pad(codebooks, ((0, 0), (0, 0), (0, pad_d)))
    cn = jnp.sum(codebooks * codebooks, axis=-1)[:, None, :]  # (m, 1, 256)
    dp = dsub + pad_d
    out = pl.pallas_call(
        _table_kernel,
        grid=(m, (B + pad_b) // BQ),
        in_specs=[
            pl.BlockSpec((1, BQ, dp), lambda j, b: (j, b, 0)),
            pl.BlockSpec((1, 256, dp), lambda j, b: (j, 0, 0)),
            pl.BlockSpec((1, 1, 256), lambda j, b: (j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, BQ, 256), lambda j, b: (j, b, 0)),
        out_shape=jax.ShapeDtypeStruct((m, B + pad_b, 256), jnp.float32),
        interpret=interpret,
        name="pq_table",
    )(q, cb, cn)
    return out[:, :B].transpose(1, 0, 2)
