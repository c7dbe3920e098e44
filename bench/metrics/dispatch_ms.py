"""dispatch_ms: mean host time inside the executor's dispatch() per batch
(padding, upload and the asynchronous launch)."""
import numpy as np


def read(run):
    b = run.window.batches
    if not b:
        return None
    return float(np.mean([r["dispatch_s"] for r in b]) * 1e3)
