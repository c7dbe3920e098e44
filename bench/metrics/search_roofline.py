"""search_roofline: the least time the chip needs for the window's searches
over the device time of the search executable in the trace, in percent.

The work is counted from shapes and expansions (bench/harness/work.py) and
bounded by the peaks of bench/harness/peaks.json.
"""
from bench.harness import work


def read(run):
    t = run.trace
    b = run.window.batches
    if not t or not t.get("module_s") or not b:
        return None
    c = run.config
    need = work.search_work(
        expansions=sum(int(r["hops"].sum()) for r in b),
        queries=sum(r["size"] for r in b), batches=len(b),
        R=c["R"], m=c["m"], d=c["d"])
    return 100.0 * work.least_time(need, run.device_kind)["seconds"] / t[
        "module_s"]
