"""Reducing a profiler trace (`.xplane.pb`) to device busy time and its gaps.

Device planes are the `/device:TPU:<i>` planes; their `XLA Ops` line holds
one event per operation, and `XLA Modules` one per executable run. Control-flow
ops (`while`, `conditional`, `call`) span their bodies' ops and are left
out; the others are leaves. Busy time is the union of the leaf
intervals inside the window, averaged over the devices that ran anything;
the top ops are leaves too, named by HLO name, kind and result type. Each
idle gap is named by what the host was doing at its midpoint: the innermost `bench.*` annotation (`TimedExecutor` and the
window loop write them), else the host event that overlaps the gap most.
"""
from __future__ import annotations

import collections
import gzip

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench.window"


def _merge(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(intervals, lo: int, hi: int):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def load_events(path: str) -> dict:
    """Planes of interest as plain lists of (name, start_ns, end_ns), from
    an `.xplane.pb` (or a gzipped one, `.xplane.pb.gz`)."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(path)
    devices: dict[str, dict[str, list]] = {}
    host: list[tuple[str, int, int]] = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU"):
            lines = devices.setdefault(plane.name, {OPS_LINE: [],
                                                    MODULES_LINE: []})
            for line in plane.lines:
                if line.name in lines:
                    lines[line.name].extend(
                        (e.name, int(e.start_ns), int(e.end_ns))
                        for e in line.events)
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                host.extend((e.name, int(e.start_ns), int(e.end_ns))
                            for e in line.events)
    return {"devices": devices, "host": host}


def reduce(events: dict, *, top: int = 10,
           module_prefix: str = "jit_pipeline") -> dict:
    """busy_s, window_s, module time and the breakdown, from `load_events`."""
    host = events["host"]
    windows = [(s, e) for n, s, e in host if n == WINDOW_SPAN]
    devs = {k: v for k, v in events["devices"].items() if v[OPS_LINE]}
    if windows:
        lo, hi = min(s for s, _ in windows), max(e for _, e in windows)
    else:
        spans = [(s, e) for v in devs.values() for _, s, e in v[OPS_LINE]]
        if not spans:
            return {}
        lo, hi = min(s for s, _ in spans), max(e for _, e in spans)
    window_ns = hi - lo
    busy, gaps = [], []
    op_time: collections.Counter = collections.Counter()
    module_ns = 0
    for lines in devs.values():
        ops = _leaves([(n, s, e) for n, s, e in lines[OPS_LINE]
                       if e > lo and s < hi])
        for n, s, e in ops:
            op_time[short_name(n)] += min(e, hi) - max(s, lo)
        merged = _merge(_clip([(s, e) for _, s, e in ops], lo, hi))
        busy.append(sum(e - s for s, e in merged))
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        gaps.extend((edges[i], edges[i + 1])
                    for i in range(0, len(edges), 2) if edges[i + 1] > edges[i])
        module_ns += sum(min(e, hi) - max(s, lo)
                         for n, s, e in lines[MODULES_LINE]
                         if n.startswith(module_prefix) and e > lo and s < hi)
    if not devs:
        return {}
    gaps.sort(key=lambda g: g[0] - g[1])
    named = [[_host_activity(host, s, e), (e - s) / 1e9] for s, e in gaps[:top]]
    return {
        "busy_s": sum(busy) / len(busy) / 1e9,
        "window_s": window_ns / 1e9,
        "module_s": module_ns / len(busy) / 1e9,
        "devices": len(busy),
        "breakdown": {
            "device_ops": [[n, t / 1e9 / len(busy)]
                           for n, t in op_time.most_common(top)],
            "idle_gaps": named,
        },
    }


CONTAINERS = ("while", "conditional", "call")


def _parts(hlo: str) -> tuple[str, str, str]:
    """(name, result type, opcode) of `%name = <type> <opcode>(...)...`."""
    name, _, rest = hlo.partition(" = ")
    if not rest:
        return hlo, "", ""
    if rest.startswith("("):                     # a tuple type
        depth = 0
        for i, ch in enumerate(rest):
            depth += {"(": 1, ")": -1}.get(ch, 0)
            if depth == 0:
                break
        typ, rest = rest[:i + 1], rest[i + 1:].lstrip()
    else:
        typ, _, rest = rest.partition(" ")
    return name.lstrip("%"), typ, rest.split("(", 1)[0]


def _leaves(ops: list) -> list:
    """All ops but the control-flow ones that hold others (`while` ...)."""
    return [o for o in ops if _parts(o[0])[2] not in CONTAINERS]


def short_name(hlo: str) -> str:
    """`%fusion.138 = f32[...] fusion(...), kind=kCustom, ...` ->
    `fusion.138 kCustom f32[2097152]`: name, kind or opcode, result."""
    name, typ, op = _parts(hlo)
    if not op:
        return hlo[:80]
    if "kind=" in hlo:
        op = hlo.split("kind=", 1)[1].split(",")[0]
    return f"{name} {op} {typ.split('{')[0]}"[:100]


def _host_activity(host, s: int, e: int) -> str:
    mid = (s + e) // 2
    inner = [(es - bs, n) for n, bs, es in host
             if n.startswith("bench.") and n != WINDOW_SPAN and bs <= mid < es]
    if inner:
        return min(inner)[1]
    best, name = 0, "none"
    for n, bs, es in host:
        if n == WINDOW_SPAN:
            continue
        ov = min(es, e) - max(bs, s)
        if ov > best:
            best, name = ov, n
    return name
