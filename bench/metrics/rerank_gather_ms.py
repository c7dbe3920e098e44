"""rerank_gather_ms: host time of the re-rank's full-vector gather per batch:
the summed durations of the telemetry `rerank_gather` spans (one per host
callback of `core/rerank.gather_host_vectors`) that start inside the
window, over the window's batches (traced run only; host-resident vectors
only)."""


def read(run):
    if run.spans is None or not run.window.batches:
        return None
    spans = [e for e in run.spans if e.get("name") == "rerank_gather"
             and e.get("ts", 0) >= run.window_t0_us]
    if not spans:
        return None
    return sum(e["dur"] for e in spans) / 1e3 / len(run.window.batches)
