"""Jitted public wrapper for the ADC kernel with platform dispatch."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.common import interpret_mode

from .pq_adc import adc_pallas
from .ref import adc_ref


def adc(table: jax.Array, codes: jax.Array, valid: jax.Array) -> jax.Array:
    """PQ asymmetric distances. table (B,m,256), codes (B,R,m), valid (B,R).

    Dispatches to the Pallas kernel (compiled on TPU, interpret elsewhere).
    """
    return adc_pallas(
        table.astype(jnp.float32),
        codes.astype(jnp.int32),
        valid,
        interpret=interpret_mode(),
    )


__all__ = ["adc", "adc_ref"]
