"""Shared Pallas kernel plumbing.

TPU is the target (pl.pallas_call + BlockSpec VMEM tiling). On any other
backend the same kernels execute under interpret=True, which is how every
kernel here is validated against its ref.py oracle on CPU. On a TPU backend
the kernels are always compiled: nothing can route a chip run through the
interpreter.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

LANES = 128  # lane width of a TPU vreg: the minor block dim of every kernel


def interpret_mode() -> bool:
    return jax.default_backend() != "tpu"


def pad_axis(x: jax.Array, axis: int, multiple: int, value) -> jax.Array:
    """Pad `axis` of x up to a multiple; returns x unchanged if aligned."""
    size = x.shape[axis]
    pad = (-size) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p
