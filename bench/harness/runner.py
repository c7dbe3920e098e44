"""One run of one cell: set up, serve the window, check the answers, report.

The timed path is the user's: `BangIndex.build(data, graph=...)` ->
`index.executor(variant, hostio=...)` -> `ServePipeline.submit/drain`, with
the executor wrapped in `TimedExecutor`. A configuration fixes only what a
deployment sets (corpus, placement, R, m, t, k, max_batch); kernel mode,
tiling and host-I/O fields stay the program's defaults.

Set-up (everything before the window opens, from process start) is timed
stage by stage and printed on standard error. After the window, the
program's state is freed and the plain reference runs: exact kNN of every
query answered, and float64 distances of every id returned.
"""
from __future__ import annotations

import dataclasses
import gc
import glob
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Callable

import numpy as np

from . import index as indexlib
from . import load as loadlib
from . import reference
from . import spec as speclib
from . import trace as tracelib
from .executor import TimedExecutor

SRC = speclib.ROOT / "src"
if str(SRC) not in sys.path:
    sys.path.insert(0, str(SRC))


class NoDevice(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def log(**fields) -> None:
    print(json.dumps(fields, default=float), file=sys.stderr, flush=True)


class Ledger(dict):
    """Set-up seconds per stage, printed as they are taken."""

    def add(self, name: str, seconds: float) -> None:
        self[name] = seconds
        log(setup=name, seconds=seconds)


def setup_jax(root: Path) -> None:
    """JAX's persistent compile cache at a fixed path inside the checkout."""
    cache = str(root / ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    import jax

    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def devices(chips: int, require_tpu: bool = True) -> list:
    import jax

    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoDevice(f"need {chips} TPU chip(s), JAX found "
                       f"{len(devs)} {devs[0].platform} device(s)")
    return devs[:chips]


# ------------------------------------------------------------------- set-up
def make_index(config: dict, seed: int, ledger: Ledger, before_build=None,
               graph: bool = True):
    """The index from the seed, through `BangIndex.build`; host data too.

    `before_build(data_dev, queries)` runs while the corpus is on the device
    and before the program sees it (the tools' reference pass). With
    `graph=False` only the corpus is made, and the index is None (the
    control, which searches the corpus itself).
    """
    import jax

    from repro.core import BangIndex
    from repro.core.vamana import VamanaGraph

    cspec = indexlib.CorpusSpec.from_config(config)
    gspec = indexlib.GraphSpec.from_config(config)
    t = time.perf_counter()
    data, queries = indexlib.make_corpus(indexlib.seed_key(seed, 0), cspec)
    jax.block_until_ready(data)
    ledger.add("corpus_s", time.perf_counter() - t)
    queries_np = np.asarray(queries)
    host = {}

    def release(data_dev) -> None:
        t = time.perf_counter()
        if before_build is not None:
            before_build(data_dev, queries_np)
        host["data"] = np.asarray(data_dev)
        ledger.add("to_host_s", time.perf_counter() - t)

    del queries
    if not graph:
        release(data)
        return None, host.pop("data"), queries_np
    adj = indexlib.build_graph(data, indexlib.seed_key(seed, 1), gspec,
                               release, log=ledger.add)
    del data
    t = time.perf_counter()
    adj_np = np.asarray(adj).reshape(cspec.n, gspec.R)
    del adj
    data_np = host.pop("data")
    ledger.add("graph_to_host_s", time.perf_counter() - t)
    t = time.perf_counter()
    index = BangIndex.build(
        data_np, m=config["m"], R=config["R"],
        graph=VamanaGraph(adjacency=adj_np, medoid=0),
        keep_device_data=config["variant"] != "base")
    jax.block_until_ready(index.codes)
    ledger.add("pq_s", time.perf_counter() - t)
    return index, data_np, queries_np


def program_executor(index, config: dict):
    """The program's executor for the configuration's placement."""
    from repro.runtime import HostIOConfig

    hostio = None
    if config["variant"] == "base":
        hostio = HostIOConfig(**config.get("hostio", {}))
    return index.executor(config["variant"], hostio=hostio)


def warm_up(pipe, tex: TimedExecutor, queries: np.ndarray, batch: int,
            ledger: Ledger) -> None:
    """Compile and run the one shape a backlog sends, full batches, twice,
    so the steady pipeline has run before the window."""
    for _ in range(2):
        t = time.perf_counter()
        pipe.submit(queries[:batch])
        pipe.drain()
        rec = tex.take()[-1]
        ledger.add(f"warm_b{batch}_s", time.perf_counter() - t)
        if rec["compile_s"]:
            log(compile_bucket=rec["bucket"], seconds=rec["compile_s"])


def bytes_in_use(devs) -> int:
    """Device memory held now, on the fullest chip."""
    return max((d.memory_stats() or {}).get("bytes_in_use", 0) for d in devs)


# ------------------------------------------------------------------ windows
@dataclasses.dataclass
class Window:
    seconds: float            # length of the window as run
    pool: np.ndarray          # (q,) pool index of each query sent
    ids: np.ndarray           # (q, k) answers, -1 where none came
    dists: np.ndarray         # (q, k)
    batches: list[dict]       # TimedExecutor records of the window
    drain_s: list[float]      # wall seconds of each drain


def _span(name: str, on: bool):
    import contextlib

    import jax

    return jax.profiler.TraceAnnotation(name) if on else \
        contextlib.nullcontext()


def serve_backlog(pipe, tex, queries, traffic, seed, seconds, max_batch,
                  annotate=False) -> Window:
    """A closed backlog: full batches, `drain_batches` per drain, until
    `seconds` have passed; the rate is over all of it."""
    per = int(traffic["drain_batches"]) * max_batch
    pool, ids, dists, recs, drains = [], [], [], [], []
    sent = 0
    t0 = t = time.perf_counter()
    while True:
        idx = loadlib.pool_order(traffic, seed, len(queries), sent + per)[sent:]
        with _span("bench.submit", annotate):
            pipe.submit(queries[idx])
        with _span("bench.drain", annotate):
            got, d, _ = pipe.drain()
        sent += per
        pool.append(idx)
        ids.append(got)
        dists.append(d)
        recs.extend(tex.take())
        now = time.perf_counter()
        drains.append(now - t)
        t = now
        if now - t0 >= seconds:
            break
    return Window(seconds=time.perf_counter() - t0,
                  pool=np.concatenate(pool), ids=np.concatenate(ids),
                  dists=np.concatenate(dists), batches=recs, drain_s=drains)


# ------------------------------------------------------------- the checks
def reference_pass(data_dev, queries: np.ndarray, pool: np.ndarray, k: int,
                   lid_queries: int) -> np.ndarray:
    """The plain reference over the pool queries in `pool`: (len(queries),
    k) exact ids (rows outside `pool` are -1). Prints the corpus check."""
    import jax

    t0 = time.perf_counter()
    jax.block_until_ready(data_dev)
    t1 = time.perf_counter()
    uniq = np.unique(pool)
    truth = np.full((len(queries), k), -1, np.int32)
    info: dict = {}
    truth[uniq] = reference.exact_knn(data_dev, queries[uniq], k,
                                      info=info)[0]
    t2 = time.perf_counter()
    lid_q = uniq[:lid_queries]
    _, lid_d = reference.exact_knn(data_dev, queries[lid_q], 100, block=256)
    log(corpus_check={
        "local_intrinsic_dim": reference.local_intrinsic_dim(lid_d),
        "d10_over_d1": float(np.mean(np.sqrt(lid_d[:, 9] / lid_d[:, 0]))),
        "queries": int(len(lid_q))},
        reference={"upload_s": t1 - t0, "knn_s": t2 - t1,
                   "lid_s": time.perf_counter() - t2,
                   "queries": int(len(uniq)), **info})
    return truth


def judge(win: Window, data_np: np.ndarray, queries: np.ndarray,
          truth: np.ndarray, check: dict) -> tuple[dict, np.ndarray]:
    """Compare every answer with the plain reference. Returns the checks
    and each query's recall@k."""
    recall = reference.recall_at_k(win.ids, truth[win.pool])
    n = data_np.shape[0]
    answered = np.all(win.ids >= 0, 1)
    in_range = np.where(win.ids >= 0, win.ids < n, True)
    srt = np.sort(win.ids, 1)
    dup = np.any((srt[:, 1:] == srt[:, :-1]) & (srt[:, 1:] >= 0), 1)
    bad = int(np.sum(~np.all(in_range, 1) | dup))
    ok_rows = answered & np.all(in_range, 1)
    exact = reference.exact_sq_dists(
        data_np, queries[win.pool[ok_rows]], win.ids[ok_rows])
    floor = check["dist_floor_frac"] * max(float(np.median(exact)), 1e-30)
    gap = np.abs(win.dists[ok_rows].astype(np.float64) - exact) / np.maximum(
        exact, floor)
    checks = {
        "recall_at_10": {"value": float(recall.mean()),
                         "min": check["recall_at_10_min"]},
        "dist_gap": {"value": float(gap.max()) if gap.size else 0.0,
                     "max": check["dist_gap_max"]},
        "bad_ids": {"value": bad, "max": 0},
        "unanswered": {"value": int(np.sum(~answered)), "max": 0},
    }
    return checks, recall


def passed(checks: dict) -> bool:
    ok = True
    for c in checks.values():
        if "min" in c:
            ok &= c["value"] >= c["min"]
        if "max" in c:
            ok &= c["value"] <= c["max"]
    return bool(ok)


def profile_options():
    """Device and host tracing without the Python tracer, which would add
    an event per Python call to the window."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


# -------------------------------------------------------------------- run
@dataclasses.dataclass
class RunRecord:
    """What a metric reader reads (`bench/metrics/<name>.py`)."""

    config: dict
    traffic: dict
    window: Window
    setup_s: float
    recall: np.ndarray
    device_kind: str
    spans: list | None = None       # telemetry tracer events (traced run)
    window_t0_us: float = 0.0       # the window's opening on that clock
    trace: dict | None = None       # reduced device trace (traced run)


class Session:
    """One cell's set-up from one seed, and windows served over it.

    A run serves one window with the program's executor. The tools that
    read controls and faults serve several windows over one set-up, with
    `truth=True` computing the reference before the index is built, and
    `graph=False` making the corpus alone where only the control serves.
    """

    def __init__(self, workload: str, seed: int, *, t_process: float,
                 root: Path = speclib.ROOT, require_tpu: bool = True,
                 truth: bool = False, graph: bool = True) -> None:
        cell = speclib.resolve(workload, root)
        self.cell, self.root, self.seed = cell, root, seed
        self.config, self.traffic = cell["config"], cell["traffic"]
        if self.traffic["arrivals"] != "backlog":
            raise speclib.SpecError(
                f"unknown arrivals {self.traffic['arrivals']!r}")
        self.t_process = t_process
        setup_jax(root)
        self.devs = devices(cell["cell"]["chips"], require_tpu)
        self.ledger = Ledger()
        self.ledger.add("start_s", time.perf_counter() - t_process)
        self.truth = None
        hook = self._reference_first if truth else None
        self.index, self.data_np, self.queries = make_index(
            self.config, seed, self.ledger, before_build=hook, graph=graph)

    def _reference_first(self, data_dev, queries: np.ndarray) -> None:
        self.truth = reference_pass(
            data_dev, queries, np.arange(len(queries)), self.config["k"],
            self.config["check"]["lid_queries"])

    def serve(self, ex, seconds: float, trace: bool = False) -> dict:
        """Warm up the batch shape, then serve one window through `ex`."""
        import jax

        from repro.core import SearchConfig
        from repro.runtime import ServePipeline

        config, traffic = self.config, self.traffic
        tel = None
        if trace:
            from repro.runtime.telemetry import Telemetry

            tel = Telemetry.create(trace=True, trace_max_events=2_000_000)
        tex = TimedExecutor(ex, annotate=trace)
        max_batch = config["max_batch"]
        pipe = ServePipeline(tex, k=config["k"],
                             cfg=SearchConfig(t=config["t"]),
                             max_batch=max_batch, telemetry=tel)
        try:
            warm_up(pipe, tex, self.queries, max_batch, self.ledger)
            held_open = bytes_in_use(self.devs)
            trace_dir = None
            if trace:
                trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
                jax.profiler.start_trace(trace_dir,
                                         profiler_options=profile_options())
            t_open = time.perf_counter()
            with _span("bench.window", trace):
                win = serve_backlog(pipe, tex, self.queries, traffic,
                                    self.seed, seconds, max_batch, trace)
            if trace:
                jax.profiler.stop_trace()
            held_close = bytes_in_use(self.devs)
        finally:
            pipe.close()
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in self.devs)
        log(window_s=win.seconds, queries=len(win.pool),
            batches=len(win.batches), drain_s=win.drain_s)
        # The process peak is the index maker's; the window holds far less.
        log(memory={"bytes_in_use_window_open": held_open,
                    "bytes_in_use_window_close": held_close,
                    "peak_bytes_in_use_process": peak})
        return {
            "window": win, "setup_s": t_open - self.t_process, "peak": peak,
            "held": max(held_open, held_close),
            "spans": tel.tracer.events() if tel is not None else None,
            "window_t0_us": tel.tracer.at_us(t_open) if tel else 0.0,
            "trace_dir": trace_dir,
        }

    def judge(self, win: Window) -> tuple[dict, np.ndarray]:
        """The checks of one window; the reference runs here if it has not
        run yet, on the host data uploaded anew."""
        import jax.numpy as jnp

        truth = self.truth
        if truth is None:
            truth = reference_pass(jnp.asarray(self.data_np), self.queries,
                                   win.pool, self.config["k"],
                                   self.config["check"]["lid_queries"])
        return judge(win, self.data_np, self.queries, truth,
                     self.config["check"])


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        t_process: float, root: Path = speclib.ROOT,
        require_tpu: bool = True,
        wrap: Callable | None = None) -> dict:
    """One run; returns the result line. `wrap(executor, session)` replaces
    the executor under test (the control and faults, `controls.py`)."""
    s = Session(workload, seed, t_process=t_process, root=root,
                require_tpu=require_tpu)
    t = time.perf_counter()
    ex = program_executor(s.index, s.config)
    if wrap is not None:
        ex = wrap(ex, s)
    s.ledger.add("executor_s", time.perf_counter() - t)
    served = s.serve(ex, seconds, trace)
    win = served["window"]
    # The program's state goes before the reference runs.
    del ex
    s.index = None
    gc.collect()
    reduced = None
    if served["trace_dir"] is not None:
        t = time.perf_counter()
        pb = glob.glob(os.path.join(served["trace_dir"], "**",
                                    "*.xplane.pb"), recursive=True)
        reduced = tracelib.reduce(tracelib.load_events(pb[0])) if pb else None
        shutil.rmtree(served["trace_dir"], ignore_errors=True)
        log(trace_reduce_s=time.perf_counter() - t)

    checks, recall = s.judge(win)
    devs = s.devs
    rec = RunRecord(config=s.config, traffic=s.traffic, window=win,
                    setup_s=served["setup_s"], recall=recall,
                    device_kind=devs[0].device_kind, spans=served["spans"],
                    window_t0_us=served["window_t0_us"], trace=reduced)
    metrics = {}
    for m in s.cell["per_layer" if trace else "end_to_end"]:
        value = speclib.metric_reader(m["name"], root)(rec)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": int(served["peak"]),
              "window_bytes_in_use": int(served["held"])}
    out = {
        "correct": passed(checks),
        "attempted": int(len(win.pool)),
        "failed": int(checks["unanswered"]["value"]
                      + checks["bad_ids"]["value"]),
        "metrics": metrics,
        "device": device,
    }
    if reduced:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        out["breakdown"] = reduced["breakdown"]
    out["checks"] = checks
    return out
