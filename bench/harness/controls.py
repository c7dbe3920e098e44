"""What the check must refuse: the control, and faults of the timed path.

* `ReferenceExecutor` is the control: the plain reference put in the
  program's place, computed in the precision below the configuration's
  (bfloat16 for float32 vectors). It has the executor's dispatch/finish
  contract, so it serves the cell's traffic through `ServePipeline`.
* `Fault` wraps the program's executor and breaks what it returns:
  "stale" answers every query with the first query's answer (a search
  whose state never moves), "half" answers the second half of each batch
  with the first half's answers (half of the batch left out), "altered"
  changes one id of one answer per batch where it is produced.

`make(kind)` gives a `wrap(executor, session)` for `runner.run`.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from . import reference

KINDS = ("control", "stale", "half", "altered")


@dataclasses.dataclass
class _Handle:
    ids: jax.Array
    dists: jax.Array
    n_hops: jax.Array
    n_iters: jax.Array
    batch: int
    bucket: int
    compile_s: float = 0.0


class ReferenceExecutor:
    """Exact kNN in bfloat16, with the executor's serving contract."""

    hostio_runtime = None

    def __init__(self, data_np: np.ndarray, dtype=jnp.bfloat16) -> None:
        self._data = jnp.asarray(data_np.astype(dtype))
        self._dtype = dtype
        self.query_dim = data_np.shape[1]

    def dispatch(self, queries, k=10, **_):
        q = np.asarray(queries, np.float32)
        bucket = max(8, 1 << (len(q) - 1).bit_length())
        pad = np.concatenate([q, np.repeat(q[-1:], bucket - len(q), 0)])
        ids, d, _ = reference._knn_block_verified(
            self._data, jnp.asarray(pad).astype(self._dtype), k,
            min(65536, self._data.shape[0]), jax.lax.Precision.DEFAULT)
        return _Handle(ids=ids, dists=d.astype(jnp.float32),
                       n_hops=jnp.zeros((bucket,), jnp.int32),
                       n_iters=jnp.zeros((), jnp.int32), batch=len(q),
                       bucket=bucket)

    def finish(self, handle, **_):
        ids = np.asarray(jax.block_until_ready(handle.ids))[: handle.batch]
        return ids, np.asarray(handle.dists)[: handle.batch]


class Fault:
    """The program's executor with its answers broken after `finish`."""

    def __init__(self, inner, kind: str, n: int) -> None:
        if kind not in ("stale", "half", "altered"):
            raise ValueError(f"unknown fault {kind!r}")
        self._inner, self.kind, self._n = inner, kind, n

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def dispatch(self, queries, k=10, **kw):
        return self._inner.dispatch(queries, k, **kw)

    def finish(self, handle, **kw):
        ids, dists = self._inner.finish(handle, **kw)
        ids = np.array(ids)
        dists = np.array(dists)
        if self.kind == "stale":
            ids[:], dists[:] = ids[0], dists[0]
        elif self.kind == "half":
            h = len(ids) // 2
            ids[h:2 * h], dists[h:2 * h] = ids[:h], dists[:h]
        else:
            ids[0, 0] = (ids[0, 0] + 1) % self._n
        return ids, dists


def make(kind: str):
    """`wrap(executor, session)` that puts `kind` in the program's place."""
    if kind == "control":
        return lambda ex, s: ReferenceExecutor(s.data_np)
    return lambda ex, s: Fault(ex, kind, s.data_np.shape[0])
