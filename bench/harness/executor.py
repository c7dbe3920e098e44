"""The executor wrapper: the harness's own spans around the executor layer.

`TimedExecutor` forwards `dispatch`/`finish` to the program's executor and
records, per batch, when it was dispatched, how long `dispatch()` took on
the host, when `finish()` returned (results ready: it ends in
`block_until_ready`), and the handle's counters (`n_hops`, `n_iters`).
`ServePipeline` accepts any object with that contract; every other
attribute is the wrapped executor's.
"""
from __future__ import annotations

import collections
import contextlib
import time

import jax
import numpy as np


class TimedExecutor:
    def __init__(self, inner, *, annotate: bool = False) -> None:
        self._inner = inner
        self._annotate = annotate
        self._open: collections.deque = collections.deque()
        self.batches: list[dict] = []

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def _span(self, name: str):
        if self._annotate:
            return jax.profiler.TraceAnnotation(name)
        return contextlib.nullcontext()

    def dispatch(self, queries, k=10, **kw):
        t0 = time.perf_counter()
        with self._span("bench.dispatch"):
            handle = self._inner.dispatch(queries, k, **kw)
        self._open.append({
            "size": handle.batch, "bucket": handle.bucket, "t_dispatch": t0,
            "dispatch_s": time.perf_counter() - t0,
            "compile_s": handle.compile_s,
        })
        return handle

    def finish(self, handle, **kw):
        with self._span("bench.finish"):
            out = self._inner.finish(handle, **kw)
        rec = self._open.popleft()
        rec["t_ready"] = time.perf_counter()
        rec["hops"] = np.asarray(handle.n_hops)[: handle.batch]
        rec["iters"] = int(np.max(np.asarray(handle.n_iters)))
        self.batches.append(rec)
        return out

    def take(self) -> list[dict]:
        """The batches finished since the last call."""
        out, self.batches = self.batches, []
        return out
