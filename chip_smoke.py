"""Chip smoke test: BANG's serving path, once, on a TPU, with its answers checked.

    python chip_smoke.py              # one chip: phase 1 and phase 2
    python chip_smoke.py --chips 4    # four chips: the sharded variants only

Everything goes through the entry points a user calls: `BangIndex.build` ->
`index.executor(variant)` -> `ServePipeline.submit/drain`. Widths follow
BANG's SIFT1B deployment: d = 128, L2, graph degree R = 64, PQ m = 32; every
corpus is generated from `--seed`.

* Phase 1 (answers): a real Vamana index at the largest N the host build
  finishes in about two minutes, served in batches of 128 queries in every
  variant x kernel-mode cell. Each cell's recall@10 against brute force must
  reach 0.9, and every kernel mode must return the ids of `reference`.
* Phase 2 (state at deployment size): N = 10M (320 MB of codes and 2.6 GB of
  adjacency on the device, 5.1 GB of vectors on the host) over a seeded
  random R-regular graph -- the graph the Vamana build starts from, since the
  host build cannot reach this N. Recall means nothing there, so the check
  is parity: `base` (host graph, host-I/O service) and `inmem` return the
  same ids under `reference` and `staged`. `fused` runs one batch at an N
  whose codes exceed the VMEM budget, so its HBM path runs.
* `--chips 4`: the phase-1 corpus on a (1, 4) ("data", "model") mesh of the
  four chips, `sharded` and `sharded-base`, against the one-chip `base` ids.

Each report line is a JSON object. The last stdout line is
`{"ok": true, "device": {"platform", "kind", "count"}}`; any failed check
raises, so the script exits non-zero and prints no result line. Without a
TPU backend it exits 2 at once. Compiles are cached under
$JAX_COMPILATION_CACHE_DIR, else `.jax_cache/` next to this file.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import numpy as np  # noqa: E402


@dataclasses.dataclass(frozen=True)
class Shape:
    """Corpus and serving widths (SIFT1B's by default)."""

    d: int = 128
    R: int = 64
    m: int = 32
    L_build: int = 64
    t: int = 64
    k: int = 10
    batch: int = 128
    batches: int = 3


SIFT1B = Shape()
PHASE1_N = 5000          # host Vamana build: about two minutes at R = 64
PHASE2_N = 10_000_000
DMA_N = 1_000_000        # 32 MB of packed codes: past the 16 MiB VMEM budget
RECALL_FLOOR = 0.9
CUTS = ["base vectors are float32, not SIFT's uint8 (uint8 corpora are "
        "not supported yet: ROADMAP B2)"]


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def report(**fields) -> None:
    print(json.dumps(fields, default=str), flush=True)


def peak_bytes() -> int | None:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


# ------------------------------------------------------------------ corpora
def make_corpus(n: int, d: int, seed: int, *, clusters: int = 64,
                spread: float = 0.15, chunk: int = 1 << 20) -> np.ndarray:
    """(n, d) float32 Gaussian mixture, generated on the device in chunks."""
    import jax

    kc, kx = jax.random.split(jax.random.key(seed))
    centers = jax.random.normal(kc, (clusters, d))

    @jax.jit
    def gen(key, ids):
        ka, kn = jax.random.split(key)
        assign = jax.random.randint(ka, ids.shape, 0, clusters)
        return centers[assign] + spread * jax.random.normal(kn, ids.shape + (d,))

    out = np.empty((n, d), np.float32)
    for s in range(0, n, chunk):
        rows = min(chunk, n - s)
        out[s:s + rows] = np.asarray(gen(jax.random.fold_in(kx, s),
                                         np.zeros(rows, np.int32)))
    return out


def make_queries(data: np.ndarray, n: int, seed: int) -> np.ndarray:
    from repro.data import uniform_queries

    return uniform_queries(data, n, seed=seed)


def random_graph(n: int, R: int, seed: int, *, chunk: int = 1 << 20):
    """Seeded random R-regular graph without self-loops (Vamana's start)."""
    import jax
    import jax.numpy as jnp

    from repro.core.vamana import VamanaGraph

    key = jax.random.key(seed)

    @jax.jit
    def gen(k, ids):
        nb = jax.random.randint(k, (ids.shape[0], R), 0, n - 1, jnp.int32)
        return nb + (nb >= ids[:, None])

    adj = np.empty((n, R), np.int32)
    for s in range(0, n, chunk):
        ids = np.arange(s, min(s + chunk, n), dtype=np.int32)
        adj[s:s + ids.size] = np.asarray(gen(jax.random.fold_in(key, s), ids))
    return VamanaGraph(adjacency=adj, medoid=0)


# ------------------------------------------------------------------ serving
def serve(index, variant: str, mode: str, queries, shape: Shape, *, gt=None,
          hostio=None, mesh=None, batches: int | None = None):
    """Drain `batches` batches of queries through one executor cell."""
    from repro.core import SearchConfig
    from repro.runtime import ServePipeline

    nb = shape.batches if batches is None else batches
    q = queries[: nb * shape.batch]
    walls: list[float] = []
    ex = index.executor(variant, hostio=hostio, mesh=mesh)
    cfg = SearchConfig(t=shape.t, kernel_mode=mode)
    with ServePipeline(ex, k=shape.k, cfg=cfg, max_batch=shape.batch) as pipe:
        pipe.submit(q, gt_ids=None if gt is None else gt[: len(q)])
        ids, _, stats = pipe.drain(on_batch=lambda b: walls.append(b.wall_s))
    check(bool(np.all(ids >= 0)), f"{variant}/{mode}: unanswered queries")
    if hostio is not None:
        h = stats.hostio
        check(h["worker_errors"] == 0 and h["degraded_lanes"] == 0,
              f"{variant}/{mode}: host gathers failed or degraded: {h}")
    return ids, stats, walls


def fused_placement(n: int, m: int) -> str:
    from repro.kernels.search_step.ops import codes_resident

    return "resident" if codes_resident(n, m) else "dma"


def phase_answers(n: int, seed: int, shape: Shape = SIFT1B, *,
                  host_workers: int = 2) -> None:
    """Phase 1: a real Vamana index; recall and kernel-mode parity."""
    from repro.core import BangIndex, brute_force_knn
    from repro.runtime import HostIOConfig

    data = make_corpus(n, shape.d, seed)
    queries = make_queries(data, shape.batch * shape.batches, seed + 1)
    t0 = time.perf_counter()
    index = BangIndex.build(data, m=shape.m, R=shape.R,
                            L_build=shape.L_build, seed=seed)
    build_s = time.perf_counter() - t0
    gt = brute_force_knn(data, queries, shape.k)
    report(phase="answers", n=n, d=shape.d, R=shape.R, m=shape.m,
           build_s=build_s, queries=len(queries), batch=shape.batch,
           cuts=CUTS + [f"N = {n}: the host Vamana build (sequential "
                        "Python, ROADMAP B1) must finish in about two "
                        "minutes"],
           fused_codes=fused_placement(n, shape.m))
    hostio = HostIOConfig(workers=host_workers, prefetch=True)
    cells = [("inmem", ("reference", "staged", "fused"), None),
             ("exact", ("reference", "fused"), None),
             ("base", ("reference", "fused"), hostio)]
    out = {}
    for variant, modes, hio in cells:
        for mode in modes:
            ids, stats, walls = serve(index, variant, mode, queries, shape,
                                      gt=gt, hostio=hio)
            out[(variant, mode)] = ids
            same = bool(np.array_equal(ids, out[(variant, "reference")]))
            report(phase="answers", variant=variant, kernel_mode=mode,
                   recall_at_10=stats.mean_recall, ids_equal_reference=same,
                   compile_s=stats.compile_s, batch_wall_s=walls,
                   hostio=None if hio is None else {
                       k: stats.hostio[k] for k in
                       ("worker_errors", "degraded_lanes", "overlap_fraction")
                       if k in stats.hostio},
                   peak_bytes_in_use=peak_bytes())
            check(stats.mean_recall >= RECALL_FLOOR,
                  f"{variant}/{mode}: recall@10 {stats.mean_recall} < "
                  f"{RECALL_FLOOR}")
            check(same, f"{variant}/{mode}: ids differ from reference")


def phase_deployment(n: int, n_dma: int, seed: int, shape: Shape = SIFT1B, *,
                     host_workers: int = 2) -> None:
    """Phase 2: deployment-size state, parity instead of recall."""
    from repro.core import BangIndex
    from repro.kernels.search_step.ops import (
        hbm_codes_stream_bytes_per_hop, vmem_budget_bytes,
    )
    from repro.runtime import HostIOConfig

    t0 = time.perf_counter()
    data = make_corpus(n, shape.d, seed)
    graph = random_graph(n, shape.R, seed + 2)
    gen_s = time.perf_counter() - t0
    queries = make_queries(data, shape.batch * shape.batches, seed + 1)
    t0 = time.perf_counter()
    index = BangIndex.build(data, m=shape.m, R=shape.R, graph=graph, seed=seed,
                            keep_device_data=False)
    np.asarray(index.codes[:1])
    build_s = time.perf_counter() - t0
    report(phase="deployment", n=n, d=shape.d, R=shape.R, m=shape.m,
           generate_s=gen_s, build_s=build_s,
           device_codes_bytes=int(index.codes.nbytes),
           device_adjacency_bytes=int(graph.adjacency.nbytes),
           host_vector_bytes=int(data.nbytes),
           graph="seeded random R-regular (Vamana's initial graph)")
    hostio = HostIOConfig(workers=host_workers, prefetch=True)
    ids = {}
    for variant, hio in (("inmem", None), ("base", hostio)):
        for mode in ("reference", "staged"):
            got, stats, walls = serve(index, variant, mode, queries, shape,
                                      hostio=hio)
            ids[(variant, mode)] = got
            report(phase="deployment", variant=variant, kernel_mode=mode,
                   compile_s=stats.compile_s, batch_wall_s=walls,
                   peak_bytes_in_use=peak_bytes())
    ref = ids[("inmem", "reference")]
    parity = {f"{v}/{m}": bool(np.array_equal(x, ref))
              for (v, m), x in ids.items()}
    report(phase="deployment", parity_with_inmem_reference=parity)
    check(all(parity.values()), f"deployment parity failed: {parity}")
    del index, ids
    gc.collect()

    # The fused kernel's HBM path: one batch at an N past the VMEM budget.
    check(fused_placement(n_dma, shape.m) == "dma",
          f"N={n_dma} codes fit the VMEM budget: the DMA path would not run")
    sub = BangIndex.build(data[:n_dma], m=shape.m, R=shape.R,
                          graph=random_graph(n_dma, shape.R, seed + 3),
                          seed=seed, keep_device_data=False)
    got = {}
    for mode in ("reference", "fused"):
        got[mode], stats, walls = serve(sub, "inmem", mode, queries, shape,
                                        batches=1)
        report(phase="deployment_dma", n=n_dma, kernel_mode=mode,
               compile_s=stats.compile_s, batch_wall_s=walls,
               peak_bytes_in_use=peak_bytes())
    same = bool(np.array_equal(got["fused"], got["reference"]))
    report(phase="deployment_dma", n=n_dma, fused_codes="dma",
           vmem_budget_bytes=vmem_budget_bytes(),
           codes_stream_bytes_per_hop=hbm_codes_stream_bytes_per_hop(
               "fused", shape.batch, n_dma, shape.m, shape.R),
           ids_equal_reference=same)
    check(same, "fused (HBM codes) ids differ from reference")


def phase_sharded(n: int, chips: int, seed: int, shape: Shape = SIFT1B) -> None:
    """The phase-1 corpus on a (1, chips) mesh vs the one-chip base ids."""
    import jax

    from repro.compat import make_mesh
    from repro.core import BangIndex

    devices = jax.devices()
    check(len(devices) >= chips, f"need {chips} devices, have {devices}")
    report(phase="sharded", devices=[str(d) for d in devices])
    data = make_corpus(n, shape.d, seed)
    queries = make_queries(data, shape.batch * shape.batches, seed + 1)
    index = BangIndex.build(data, m=shape.m, R=shape.R,
                            L_build=shape.L_build, seed=seed)
    base, _, _ = serve(index, "base", "reference", queries, shape)
    mesh = make_mesh((1, chips), ("data", "model"), devices=devices[:chips])
    for variant in ("sharded", "sharded-base"):
        ids, stats, walls = serve(index, variant, "reference", queries, shape,
                                  mesh=mesh)
        codes = index.executor(variant, mesh=mesh)._codes
        shard_devices = sorted({s.device.id for s in codes.addressable_shards})
        same = bool(np.array_equal(ids, base))
        report(phase="sharded", variant=variant, mesh=dict(mesh.shape),
               codes_sharding=str(codes.sharding),
               codes_shard_devices=shard_devices, ids_equal_base=same,
               compile_s=stats.compile_s, batch_wall_s=walls)
        check(len(shard_devices) == chips,
              f"{variant}: codes sit on {shard_devices}, not {chips} devices")
        check(same, f"{variant}: ids differ from one-chip base")


def phase2_size(n: int, d: int, R: int) -> tuple[int, str | None]:
    """Cut phase 2's N only as far as host RAM forces it."""
    ram = os.sysconf("SC_PAGE_SIZE") * os.sysconf("SC_PHYS_PAGES")
    per_row = 3 * (4 * d + 4 * R)   # vectors + graph, plus transient copies
    if n * per_row <= ram:
        return n, None
    cut = ram // per_row
    return cut, f"host RAM {ram} B holds about {cut} rows of vectors + graph"


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--n1", type=int, default=PHASE1_N,
                    help="phase-1 corpus size (host Vamana build)")
    ap.add_argument("--n2", type=int, default=PHASE2_N,
                    help="phase-2 corpus size (random graph)")
    args = ap.parse_args(argv)

    import jax

    if jax.default_backend() != "tpu":
        print(f"chip_smoke: no TPU (JAX backend is {jax.default_backend()!r})",
              file=sys.stderr)
        return 2

    from repro.compile_cache import setup_compile_cache
    from repro.kernels.common import interpret_mode
    from repro.kernels.search_step.ops import DEFAULT_VMEM_BUDGET

    cache = setup_compile_cache()
    check(not interpret_mode(), "Pallas kernels would run in interpret mode")
    dev = jax.devices()[0]
    report(phase="setup", platform=dev.platform, device_kind=dev.device_kind,
           devices=len(jax.devices()), vmem_budget_bytes=DEFAULT_VMEM_BUDGET,
           vmem_budget="assumed, not read from the device",
           compile_cache=cache, jax=jax.__version__)
    t0 = time.perf_counter()
    if args.chips > 1:
        phase_sharded(args.n1, args.chips, args.seed)
    else:
        phase_answers(args.n1, args.seed)
        gc.collect()
        n2, why = phase2_size(args.n2, SIFT1B.d, SIFT1B.R)
        report(phase="deployment", n=n2, cut=why)
        phase_deployment(n2, min(DMA_N, n2), args.seed)
    report(phase="done", wall_s=time.perf_counter() - t0,
           peak_bytes_in_use=peak_bytes())
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
