"""host_gather_ms: host-I/O gather time per batch: the summed durations of
the telemetry `gather` spans that start inside the window, over the
window's batches (traced run only; host-graph placement only)."""


def read(run):
    if run.spans is None or not run.window.batches:
        return None
    spans = [e for e in run.spans
             if e.get("name") == "gather" and e.get("ts", 0) >= run.window_t0_us]
    if not spans:
        return None
    return sum(e["dur"] for e in spans) / 1e3 / len(run.window.batches)
