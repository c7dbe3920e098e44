"""The work a batch of searches needs, counted from shapes and expansions.

Counted per expansion (one node taken off a query's worklist): its R
adjacency ids (4 bytes each), its R neighbours' PQ codes (R x m bytes) and
their ADC sums (R x m adds), and, in the re-rank, its full vector (d x 4
bytes) with an exact distance (3d operations). Per query: the stage-1
distance table (256 centroids x d: 3 operations per coordinate, m x 256 x 4
bytes written) and the query itself. Per batch: the codebooks (256 x d x 4
bytes). The count does not depend on how the search is implemented, so a
kernel that replaces another does not make it stale.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).with_name("peaks.json")


def search_work(expansions: int, queries: int, batches: int, *, R: int,
                m: int, d: int) -> dict:
    """Bytes and operations the searches need."""
    per_exp_bytes = R * 4 + R * m + d * 4
    per_exp_ops = R * m + 3 * d
    per_query_bytes = m * 256 * 4 + d * 4
    per_query_ops = 3 * 256 * d
    return {
        "bytes": expansions * per_exp_bytes + queries * per_query_bytes
        + batches * 256 * d * 4,
        "ops": expansions * per_exp_ops + queries * per_query_ops,
    }


def peaks(device_kind: str) -> dict:
    """The published peaks of `device_kind`; an unknown kind is an error."""
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def least_time(work: dict, device_kind: str) -> dict:
    """The least time the chip could take, and which bound sets it.

    Operations are held to the highest operation rate the chip has, so the
    result is a lower bound whatever unit runs them.
    """
    p = peaks(device_kind)
    t_bytes = work["bytes"] / p["hbm_bytes_per_s"]
    t_ops = work["ops"] / max(p["bf16_flops_per_s"], p["int8_ops_per_s"])
    return {"seconds": max(t_bytes, t_ops),
            "bound": "bytes" if t_bytes >= t_ops else "ops",
            "t_bytes": t_bytes, "t_ops": t_ops}
