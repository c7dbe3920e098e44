"""Pallas TPU kernels: bitonic sort + worklist merge (paper §4.7-§4.8).

The paper sorts <=64-entry neighbour lists with a parallel bottom-up merge
sort and merges them into the worklist with the merge-path algorithm (one
thread per element + binary search), both in GPU shared memory. TPUs have no
per-lane scatter/binary-search, so we ADAPT: a bitonic compare-exchange
network over (8, W) VMEM tiles -- 8 queries on the sublanes, W >= 128 lanes.
Every stage fetches each lane's XOR partner with two lane rotations and a
select, so the network needs no reshape, reversal or gather (none of which
Mosaic lowers on lane-sized tiles).

  * sort:  full bitonic network over aligned blocks of n lanes. The last
    stage sorts even blocks ascending and odd blocks descending, which the
    fused search step uses to get a reversed candidate list for free.
  * merge: list 1 ascending ++ list 2 descending is a bitonic sequence, so
    only the final merge phase (log W stages) runs -- the work-complexity
    analogue of the paper's merge-path step (O(l log l) work, O(log l) span).

Keys are (dist, id) lexicographic; payloads (visited) ride along through
the same selects. Padding uses (+inf, INT32_MAX, visited=1), which sorts
last and never blocks convergence.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import LANES, next_pow2

# numpy (not jnp) scalar: this module is imported lazily from *inside*
# traced step functions, and a module-level jnp constant created while a
# trace is active would capture that trace's tracer and poison every later
# use (UnexpectedTracerError). numpy scalars are trace-inert and behave
# identically in jnp expressions.
INT_MAX = np.int32(2**31 - 1)

BROWS = 8  # queries per program: one sublane tile


def lane_iota(shape) -> jax.Array:
    return jax.lax.broadcasted_iota(jnp.int32, shape, len(shape) - 1)


def xor_partner(x: jax.Array, j: int, lane: jax.Array) -> jax.Array:
    """x[..., lane ^ j] for a power of two j < width, by lane rotation.

    Both rotations are taken and the one whose rotated lane index equals the
    partner is kept, so the result does not depend on the rotation's sign
    convention.
    """
    n = x.shape[-1]
    axis = x.ndim - 1
    fwd = pltpu.roll(lane, j, axis) == (lane ^ j)
    return jnp.where(fwd, pltpu.roll(x, j, axis), pltpu.roll(x, n - j, axis))


def _compare_exchange(d, i, v, j: int, k: int, lane):
    """One bitonic stage: partner = lane ^ j, ascending iff (lane & k) == 0.

    The pair (a, b) = (lower, upper) lane swaps iff a > b on an ascending
    block (a < b on a descending one); both lanes of a pair compute the same
    decision, so payloads of equal keys move exactly as in the classic
    in-place network.
    """
    pd, pi = xor_partner(d, j, lane), xor_partner(i, j, lane)
    upper = (lane & j) != 0
    self_gt = (d > pd) | ((d == pd) & (i > pi))
    partner_gt = (pd > d) | ((pd == d) & (pi > i))
    # Boolean algebra, not selects: Mosaic has no select over i1 vectors.
    a_gt_b = (upper & partner_gt) | (~upper & self_gt)
    asc = (lane & k) == 0
    swap = (a_gt_b & asc) | (~a_gt_b & ~asc)
    d = jnp.where(swap, pd, d)
    i = jnp.where(swap, pi, i)
    if v is not None:
        v = jnp.where(swap, xor_partner(v, j, lane), v)
    return d, i, v


def bitonic_stages(d, i, v, n: int, full_sort: bool):
    """Bitonic network on (Q, W) values, W a power of two >= n.

    full_sort: the complete network over aligned n-lane blocks (even blocks
    end ascending, odd blocks descending; n == W sorts the whole row
    ascending). Otherwise only the final merge phase over all W lanes, which
    sorts a bitonic row ascending. `v` may be None (no payload).

    Pure function of jnp values -- usable from any Pallas kernel body,
    including the fused search_step megakernel (repro.kernels.search_step),
    which reuses it so the fused and staged sort/merge stay bit-identical.
    """
    W = d.shape[-1]
    lane = lane_iota(d.shape)
    ks = []
    if full_sort:
        k = 2
        while k <= n:
            ks.append(k)
            k *= 2
    else:
        ks = [W]
    for k in ks:
        j = k // 2
        while j >= 1:
            d, i, v = _compare_exchange(d, i, v, j, k, lane)
            j //= 2
    return d, i, v


def reverse_blocks(x, n: int):
    """Reverse every aligned n-lane block of x (n a power of two)."""
    lane = lane_iota(x.shape)
    j = 1
    while j < n:
        x = xor_partner(x, j, lane)
        j *= 2
    return x


def _sort_kernel(d_ref, i_ref, out_d_ref, out_i_ref):
    d, i, _ = bitonic_stages(
        d_ref[...], i_ref[...], None, d_ref.shape[-1], full_sort=True
    )
    out_d_ref[...] = d
    out_i_ref[...] = i


def _merge_kernel(
    d1_ref, i1_ref, v1_ref, d2_ref, i2_ref, out_d_ref, out_i_ref, out_v_ref,
    *, off: int, n2: int,
):
    # list 1 sits in lanes [0, off), list 2 (ascending) in the n2-lane block
    # at `off`; reversing that block makes the row bitonic.
    lane = lane_iota(d1_ref.shape)
    first = lane < off
    d = jnp.where(first, d1_ref[...], reverse_blocks(d2_ref[...], n2))
    i = jnp.where(first, i1_ref[...], reverse_blocks(i2_ref[...], n2))
    v = jnp.where(first, v1_ref[...], 0)
    d, i, v = bitonic_stages(d, i, v, d.shape[-1], full_sort=False)
    out_d_ref[...] = d
    out_i_ref[...] = i
    out_v_ref[...] = v


def _pad2(x, rows: int, lanes_lo: int, lanes_hi: int, value):
    return jnp.pad(x, ((0, rows), (lanes_lo, lanes_hi)), constant_values=value)


def _rows_call(kernel, width, rows, n_in, out_dtypes, *, interpret, name):
    """pallas_call over (rows, width) arrays, BROWS rows per program."""
    spec = pl.BlockSpec((BROWS, width), lambda b: (b, 0))
    return pl.pallas_call(
        kernel,
        grid=(rows // BROWS,),
        in_specs=[spec] * n_in,
        out_specs=[spec] * len(out_dtypes),
        out_shape=[jax.ShapeDtypeStruct((rows, width), dt) for dt in out_dtypes],
        interpret=interpret,
        name=name,
    )


@functools.partial(jax.jit, static_argnames=("interpret",))
def sort_kv_pallas(dists, ids, *, interpret: bool = True):
    """(B, n) sort ascending by (dist, id) via the bitonic network kernel."""
    B, n = dists.shape
    W = max(LANES, next_pow2(n))
    pad_b = (-B) % BROWS
    d = _pad2(dists.astype(jnp.float32), pad_b, 0, W - n, jnp.inf)
    i = _pad2(ids.astype(jnp.int32), pad_b, 0, W - n, INT_MAX)
    out_d, out_i = _rows_call(
        _sort_kernel, W, B + pad_b, 2, (jnp.float32, jnp.int32),
        interpret=interpret, name="bitonic_sort",
    )(d, i)
    return out_d[:B, :n], out_i[:B, :n]


@functools.partial(jax.jit, static_argnames=("t", "interpret"))
def merge_pallas(d1, i1, v1, d2, i2, *, t: int, interpret: bool = True):
    """Merge sorted (d1,i1,v1) (len t1) with sorted (d2,i2) (len R); keep t."""
    B, t1 = d1.shape
    R = d2.shape[1]
    n2 = next_pow2(R)
    W = max(LANES, next_pow2(t1 + n2))
    off = W - n2                      # list 2's block: the last n2 lanes
    pad_b = (-B) % BROWS
    d1 = _pad2(d1.astype(jnp.float32), pad_b, 0, W - t1, jnp.inf)
    i1 = _pad2(i1.astype(jnp.int32), pad_b, 0, W - t1, INT_MAX)
    v1 = _pad2(v1.astype(jnp.int32), pad_b, 0, W - t1, 1)
    d2 = _pad2(d2.astype(jnp.float32), pad_b, off, n2 - R, jnp.inf)
    i2 = _pad2(i2.astype(jnp.int32), pad_b, off, n2 - R, INT_MAX)
    out_d, out_i, out_v = _rows_call(
        functools.partial(_merge_kernel, off=off, n2=n2), W, B + pad_b, 5,
        (jnp.float32, jnp.int32, jnp.int32),
        interpret=interpret, name="bitonic_merge",
    )(d1, i1, v1, d2, i2)
    return out_d[:B, :t], out_i[:B, :t], out_v[:B, :t].astype(jnp.bool_)
