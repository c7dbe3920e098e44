"""exact_roofline: the least time the chip needs for the window's exact-distance
searches over the device time of the search executable in the trace, in
percent.

The work is counted from shapes and expansions, whatever implements them.
Per expansion (one node taken off a query's worklist): its R adjacency ids
(4 bytes each), its R neighbours' full vectors (R x d x 4 bytes) and their
exact squared L2 (3 operations per coordinate: subtract, multiply, add).
Per query: the query itself (d x 4 bytes). The Exact-distance variant reads
no PQ codes, no distance table and no re-rank, so `search_roofline`'s count
does not hold for it. The bound is `work.least_time` over
bench/harness/peaks.json.
"""
from bench.harness import work


def exact_work(expansions: int, queries: int, *, R: int, d: int) -> dict:
    """Bytes and operations the exact-distance searches need."""
    return {
        "bytes": expansions * (R * 4 + R * d * 4) + queries * d * 4,
        "ops": expansions * 3 * R * d,
    }


def read(run):
    t = run.trace
    b = run.window.batches
    if not t or not t.get("module_s") or not b:
        return None
    c = run.config
    need = exact_work(
        expansions=sum(int(r["hops"].sum()) for r in b),
        queries=sum(r["size"] for r in b), R=c["R"], d=c["d"])
    return 100.0 * work.least_time(need, run.device_kind)["seconds"] / t[
        "module_s"]
