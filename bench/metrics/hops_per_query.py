"""hops_per_query: mean expansions per query (the handle's n_hops)."""
import numpy as np


def read(run):
    b = run.window.batches
    if not b:
        return None
    return float(np.mean(np.concatenate([r["hops"] for r in b])))
