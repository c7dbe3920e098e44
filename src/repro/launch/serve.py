"""ANNS serving entrypoint (the paper's production workload).

Single-host mode answers batched queries with the three-stage pipeline.
`--dryrun-sharded` additionally proves the pod-scale sharded-graph search
compiles on the production mesh (512 fake devices, codes/graph/vectors
sharded over `model`, queries over (`pod`,`data`)).

    PYTHONPATH=src python -m repro.launch.serve --n 4000 --batch-size 128
    PYTHONPATH=src python -m repro.launch.serve --dryrun-sharded
"""
from __future__ import annotations

import argparse
import sys


def _dryrun_sharded() -> int:
    # device-count env must be set before jax init; re-exec pattern not
    # needed because serve is invoked fresh per run.
    import os

    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    os.environ["JAX_PLATFORMS"] = "cpu"  # the 512 fake devices are CPU devices
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import SearchConfig
    from repro.core.distributed import make_sharded_search
    from repro.launch.mesh import make_production_mesh

    mesh = make_production_mesh(multi_pod=True)
    # paper batch is 10,000 queries; padded to the next multiple of the 32
    # data-parallel shards (queries are embarrassingly parallel, §3.2)
    n, d, m, R, B, k = 2_000_000, 96, 32, 64, 10_240, 10
    cfg = SearchConfig(t=152, bloom_z=399_887, max_iters=200)
    fn = make_sharded_search(mesh, medoid=0, k=k, cfg=cfg,
                             data_axes=("pod", "data"))
    specs = (
        jax.ShapeDtypeStruct((B, d), jnp.float32),            # queries
        jax.ShapeDtypeStruct((m, 256, d // m), jnp.float32),  # codebooks
        jax.ShapeDtypeStruct((n, m), jnp.uint8),              # codes
        jax.ShapeDtypeStruct((n, R), jnp.int32),              # adjacency
        jax.ShapeDtypeStruct((n, d), jnp.float32),            # full vectors
    )
    with jax.set_mesh(mesh):
        lowered = fn.lower(*specs)
        compiled = lowered.compile()
    print("sharded ANNS serve step compiled on", mesh.shape)
    try:
        ma = compiled.memory_analysis()
        print("  temp bytes:", getattr(ma, "temp_size_in_bytes", "?"))
    except Exception:  # noqa: BLE001
        pass
    return 0


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--dryrun-sharded", action="store_true")
    ap.add_argument("--n", type=int, default=4000)
    ap.add_argument("--dim", type=int, default=64)
    ap.add_argument("--batch-size", type=int, default=128)
    ap.add_argument("--batches", type=int, default=3)
    ap.add_argument("--t", type=int, default=64)
    args = ap.parse_args()

    if args.dryrun_sharded:
        sys.exit(_dryrun_sharded())

    import numpy as np

    from repro.core import BangIndex, SearchConfig, brute_force_knn, recall_at_k
    from repro.data import gaussian_mixture, uniform_queries

    data = gaussian_mixture(args.n, args.dim, n_clusters=48, seed=0)
    index = BangIndex.build(data, m=16, R=24, L_build=48)
    cfg = SearchConfig(t=args.t, bloom_z=16384)
    import time

    for b in range(args.batches):
        q = uniform_queries(data, args.batch_size, seed=b)
        t0 = time.perf_counter()
        ids, _ = index.search(q, 10, cfg=cfg)
        dt = time.perf_counter() - t0
        gt = brute_force_knn(data, q, 10)
        print(
            f"batch {b}: {args.batch_size/dt:.0f} QPS "
            f"recall@10={recall_at_k(np.asarray(ids), gt):.3f}"
        )


if __name__ == "__main__":
    main()
