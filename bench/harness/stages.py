"""Device time of the search executable per stage, from a profiler trace.

The program runs each stage of a search under a named scope
(`repro.core.search.STAGES`: bang.table, bang.fetch, bang.bloom, bang.step,
bang.history, bang.rerank), and `SearchExecutor.stage_map()` maps each HLO
instruction of its compiled executables to its stage. Here the leaf ops of
`trace.load_events` (`trace._leaves`) are summed per stage, counting only
the part of each op that lies inside a run of the search module (`XLA
Modules` events named `jit_pipeline...`) inside the window, and averaged
over the devices that ran anything, as `trace.reduce` does. What no stage
claims is the module time less the stages' sum: ops without a scope (loop
control, copies XLA inserts) and the gaps between ops.
"""
from __future__ import annotations

import bisect
import collections

from . import trace


def reduce_stages(events: dict, stage_map: dict[str, str], *,
                  module_prefix: str = "jit_pipeline") -> dict:
    """{"stages": {stage: s}, "module_s", "unclaimed_s", "unclaimed_share"}
    from `trace.load_events` and an executor's `stage_map()`; {} when no
    device ran anything."""
    devs = {k: v for k, v in events["devices"].items() if v[trace.OPS_LINE]}
    if not devs:
        return {}
    windows = [(s, e) for n, s, e in events["host"] if n == trace.WINDOW_SPAN]
    if windows:
        lo, hi = min(s for s, _ in windows), max(e for _, e in windows)
    else:
        lo, hi = -1 << 62, 1 << 62
    stage_ns: collections.Counter = collections.Counter()
    module_ns = 0
    for lines in devs.values():
        runs = trace._merge(trace._clip(
            [(s, e) for n, s, e in lines[trace.MODULES_LINE]
             if n.startswith(module_prefix)], lo, hi))
        module_ns += sum(e - s for s, e in runs)
        starts = [s for s, _ in runs]
        for name, s, e in trace._leaves(lines[trace.OPS_LINE]):
            stage = stage_map.get(trace._parts(name)[0])
            if stage is not None:
                stage_ns[stage] += _inside(s, e, runs, starts)
    n = len(devs)
    stages = {k: v / 1e9 / n for k, v in sorted(stage_ns.items())}
    module_s = module_ns / 1e9 / n
    unclaimed = module_s - sum(stages.values())
    return {"stages": stages, "module_s": module_s, "unclaimed_s": unclaimed,
            "unclaimed_share": unclaimed / module_s if module_s else None}


def _inside(s: int, e: int, runs: list, starts: list) -> int:
    """Nanoseconds of [s, e) inside the sorted, disjoint intervals `runs`."""
    i = max(bisect.bisect_right(starts, s) - 1, 0)
    total = 0
    while i < len(runs) and runs[i][0] < e:
        total += max(0, min(e, runs[i][1]) - max(s, runs[i][0]))
        i += 1
    return total
