"""lane_use: share of the search loop's lane-iterations that expanded a
node, in percent: each real query's expansions inside the loop (its n_hops
less the entry point, expanded before the loop) over n_iters x bucket."""


def read(run):
    b = run.window.batches
    lanes = sum(r["iters"] * r["bucket"] for r in b)
    if not lanes:
        return None
    return 100.0 * sum(int(r["hops"].sum()) - len(r["hops"]) for r in b) / lanes
