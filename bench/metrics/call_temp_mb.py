"""call_temp_mb: device temporaries one call of the search executable holds,
in MB: the `temp_bytes` arg of the telemetry `compile` span of the bucket
the window's batches ran in (the compiler's `memory_analysis()`). The
compile falls in the warm-up, before the window, so every traced run has
it; the largest is taken where the window ran several buckets. None where
the program's compile span carries no `temp_bytes`."""


def read(run):
    if run.spans is None or not run.window.batches:
        return None
    buckets = {r["bucket"] for r in run.window.batches}
    temps = [e["args"]["temp_bytes"] for e in run.spans
             if e.get("name") == "compile"
             and e.get("args", {}).get("bucket") in buckets
             and "temp_bytes" in e.get("args", {})]
    if not temps:
        return None
    return max(temps) / 1e6
