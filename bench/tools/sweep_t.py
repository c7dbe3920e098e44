"""Find a configuration's worklist size t, and look at its graph.

    python3 bench/tools/sweep_t.py --config deeplike-10m-base --seed 11

One process: makes the configuration's index from the seed (as a run does)
and the plain reference for `--queries` held-out queries. Then it walks the
paper's sweep of t from the configuration's t: down while recall@10 stays
at or above the configuration's `recall_at_10_min`, else up until it
reaches it, serving full batches through the configuration's executor, and
prints each t's recall with its hop counts. The chosen t is the smallest
one that reaches it.

It also prints the graph's mean and least out-degree, the share of points
reachable from the entry point, and, at the chosen t with the loop's cap
lifted (4t iterations, no re-rank, one full batch), the distribution of
hops each query takes before its search ends. `--write` stores t and the
readings in the configuration file. Runs on the chip; exits 2 without a
TPU.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

import numpy as np  # noqa: E402

from bench.harness import reference, runner, spec  # noqa: E402

SWEEP = (16, 32, 48, 64, 96, 128, 152)
UNCAPPED = 4      # the lifted cap, in multiples of t


def emit(**fields) -> None:
    print(json.dumps(fields, default=float), flush=True)


def batches(ex, queries, k, cfg, batch, rerank=True):
    """(ids, n_hops, n_iters per batch, wall seconds) over full batches."""
    ids, hops, iters, walls = [], [], [], []
    for s in range(0, len(queries), batch):
        t0 = time.perf_counter()
        h = ex.dispatch(queries[s:s + batch], k, cfg=cfg, rerank=rerank)
        got, _ = ex.finish(h)
        walls.append(time.perf_counter() - t0)
        ids.append(np.asarray(got))
        hops.append(np.asarray(h.n_hops)[: h.batch])
        iters.append(int(np.max(np.asarray(h.n_iters))))
    return np.concatenate(ids), np.concatenate(hops), iters, walls


def reachable(adj: np.ndarray, entry: int = 0) -> tuple[float, int]:
    """Share of points reachable from `entry`, and the number of levels."""
    n = adj.shape[0]
    seen = np.zeros(n, bool)
    seen[entry] = True
    front, levels = np.array([entry]), 0
    while front.size:
        nb = adj[front].ravel()
        nb = nb[nb >= 0]
        nb = nb[~seen[nb]]
        seen[nb] = True
        new = np.zeros(n, bool)
        new[nb] = True
        front = np.flatnonzero(new)
        levels += 1
    return float(seen.mean()), levels


def hop_summary(hops: np.ndarray) -> dict:
    return {"mean": float(hops.mean()), "p50": float(np.percentile(hops, 50)),
            "p90": float(np.percentile(hops, 90)),
            "p99": float(np.percentile(hops, 99)), "max": int(hops.max())}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, default=11)
    ap.add_argument("--queries", type=int, default=2048)
    ap.add_argument("--write", action="store_true")
    args = ap.parse_args(argv)
    bench = spec.load_benchmark()
    entry = next(c for c in bench["configs"] if c["name"] == args.config)
    path = spec.ROOT / entry["file"]
    config = json.loads(path.read_text())
    runner.setup_jax(spec.ROOT)
    try:
        devs = runner.devices(1)
    except runner.NoDevice as e:
        print(f"sweep_t: {e}", file=sys.stderr)
        return 2
    import jax

    from repro.core import SearchConfig

    ledger = runner.Ledger()
    index, data_np, queries = runner.make_index(config, args.seed, ledger)
    q = queries[: args.queries]
    k, batch = config["k"], config["max_batch"]
    t0 = time.perf_counter()
    truth, _ = reference.exact_knn(jax.numpy.asarray(data_np), q, k)
    emit(reference_s=time.perf_counter() - t0, queries=len(q))
    ex = runner.program_executor(index, config)

    target = config["check"]["recall_at_10_min"]
    recall, hops = {}, {}
    i = SWEEP.index(config["t"])
    while 0 <= i < len(SWEEP):
        t = SWEEP[i]
        ids, h, iters, walls = batches(ex, q, k, SearchConfig(t=t), batch)
        recall[t] = float(reference.recall_at_k(ids, truth).mean())
        hops[t] = hop_summary(h)
        emit(t=t, recall_at_10=recall[t], hops=hops[t], n_iters=iters,
             batch_wall_s=walls)
        ok = recall[t] >= target
        below = SWEEP[i - 1] if i > 0 else None
        if ok and below is not None and below not in recall:
            i -= 1
        elif not ok and (i + 1 == len(SWEEP) or SWEEP[i + 1] not in recall):
            i += 1
        else:
            break
    reached = [t for t in recall if recall[t] >= target]
    if not reached:
        emit(t=None, error=f"no t in {SWEEP} reaches {target}")
        return 1
    best = min(reached)

    adj = index.graph.adjacency
    deg = np.sum(adj >= 0, 1)
    t0 = time.perf_counter()
    share, levels = reachable(adj)
    graph = {"mean_degree": float(deg.mean()), "min_degree": int(deg.min()),
             "reachable_share": share, "bfs_levels": levels,
             "bfs_s": time.perf_counter() - t0}
    emit(graph=graph)

    cfg = SearchConfig(t=best, max_iters=UNCAPPED * best)
    _, h, iters, _ = batches(ex, q[:batch], k, cfg, batch, rerank=False)
    # Under the default cap no query takes more hops than the capped run's
    # most; the share above it is the share the cap stops early.
    capped = hops[best]["max"]
    uncapped = {"max_iters": cfg.iters(), "n_iters": iters,
                "hops": hop_summary(h), "capped_max_hops": capped,
                "share_over_capped_max": float(np.mean(h > capped))}
    emit(t=best, uncapped=uncapped)

    if args.write:
        config["t"] = best
        config["t_sweep"] = {
            "measured": f"on one TPU v5e: seed {args.seed}, {len(q)} held-out "
                        f"queries in full batches of {batch}, "
                        f"{config['variant']} placement",
            "recall_at_10": {str(t): recall[t] for t in sorted(recall)},
            "hops": {str(t): hops[t] for t in sorted(hops)},
            "graph": graph, "uncapped": uncapped}
        path.write_text(json.dumps(config, indent=2) + "\n")
    peak = (devs[0].memory_stats() or {}).get("peak_bytes_in_use")
    emit(done=True, t=best, setup=ledger, peak_bytes_in_use=peak,
         wall_s=time.perf_counter() - T_PROCESS)
    return 0


if __name__ == "__main__":
    sys.exit(main())
