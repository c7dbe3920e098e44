"""Device row stores laid out for the search's row gathers.

The search gathers whole rows by id: adjacency rows each hop, full vectors
each hop (Exact-distance variant) and in the re-rank. A TPU tiles a 2-D
array in (8, 128) tiles and, for each shape, picks the axis order that pads
least. An (n, w) array with w < 128 comes out column-major, since row-major
would pad every row to 128 lanes. A row gather cannot read that layout in
place, so the compiled search copied the whole array into row-major on
every call: at 10M x 64 int32 adjacency and 10M x 96 float32 vectors, 6.4
GB of temporaries per batch.

`PackedRows` holds the same rows several to a line: an (ceil(n / p), p * s)
array with p * s a multiple of 128, whose default layout is row-major with
no padding. Each row sits in a slot of s lanes, its width rounded up to 16,
so p is 1, 2, 4 or 8. Indexing it by ids returns the same values as
indexing the (n, w) array, bit for bit, in a form picked by the platform it
is lowered for (`jax.lax.platform_dependent`): on the TPU a row gather of
whole lines and a select of each row's slot (`take_lines`); elsewhere one
gather of each row's slot (`take_slots`), which XLA:CPU fuses into the
consumer as it fused the plain row gather, so the distances summed after it
are the same bits. The layout is fixed when the store is made, once per
index.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array

LANES = 128
_SLOT_ALIGN = 16


def slot_width(width: int) -> int:
    """Lanes a row of `width` takes in a line."""
    return -(-width // _SLOT_ALIGN) * _SLOT_ALIGN


def rows_per_line(width: int) -> int:
    """Rows of `width` that share one line (a multiple of 128 lanes)."""
    return LANES // math.gcd(slot_width(width), LANES)


def take_lines(lines: Array, ids: Array, width: int) -> Array:
    """Rows `ids` of `lines`: gather whole lines, select each row's slot."""
    per, slot = rows_per_line(width), slot_width(width)
    line = lines[ids // per]
    out = line[..., :width]
    lane = ids % per
    for j in range(1, per):
        out = jnp.where((lane == j)[..., None],
                        line[..., j * slot:j * slot + width], out)
    return out


def take_slots(lines: Array, ids: Array, width: int) -> Array:
    """Rows `ids` of `lines`: one gather of each row's slot."""
    per, slot = rows_per_line(width), slot_width(width)
    starts = jnp.stack([ids // per, (ids % per) * slot], axis=-1)
    dnums = jax.lax.GatherDimensionNumbers(
        offset_dims=(ids.ndim,), collapsed_slice_dims=(0,),
        start_index_map=(0, 1))
    return jax.lax.gather(lines, starts, dnums, slice_sizes=(1, width),
                          mode=jax.lax.GatherScatterMode.PROMISE_IN_BOUNDS)


@dataclasses.dataclass(frozen=True)
class PackedRows:
    """(n, width) rows packed `rows_per_line(width)` to a line (see module)."""

    lines: Array   # (ceil(n / p), p * slot_width(width))
    n: int
    width: int

    @property
    def shape(self) -> tuple[int, int]:
        return (self.n, self.width)

    def __getitem__(self, ids: Array) -> Array:
        """Rows `ids` (any shape of in-range ids): ids.shape + (width,)."""
        w = self.width
        return jax.lax.platform_dependent(
            self.lines, ids,
            tpu=lambda lines, i: take_lines(lines, i, w),
            default=lambda lines, i: take_slots(lines, i, w))


jax.tree_util.register_dataclass(
    PackedRows, data_fields=["lines"], meta_fields=["n", "width"])


def pack(x: np.ndarray | Array | PackedRows) -> PackedRows:
    """The (n, width) rows of `x` as a device `PackedRows`.

    A host array is laid out on the host and uploaded once; a device array
    is repacked on the device. A `PackedRows` is returned as it is.
    """
    if isinstance(x, PackedRows):
        return x
    n, width = x.shape
    per, slot = rows_per_line(width), slot_width(width)
    pad_rows = -n % per
    if isinstance(x, np.ndarray):
        if pad_rows or slot != width:
            padded = np.zeros((n + pad_rows, slot), x.dtype)
            padded[:n, :width] = x
            x = padded
        lines = jnp.asarray(np.ascontiguousarray(x).reshape(-1, per * slot))
    else:
        lines = jnp.pad(x, ((0, pad_rows), (0, slot - width))).reshape(
            -1, per * slot)
    return PackedRows(lines, n, width)
