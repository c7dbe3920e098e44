"""The load generator: one general reader of traffic files.

A traffic file (`bench/traffic/<mix>.json`) sets:

    arrivals     "backlog": a closed backlog that never runs dry; each drain
                 takes `drain_batches` full batches.
    order        "cycle": the query pool in a seeded permutation, repeated.

Seeds change the order of the work, not its amount.
"""
from __future__ import annotations

import numpy as np


def rng(seed: int, salt: int) -> np.random.Generator:
    return np.random.default_rng([seed, salt])


def pool_order(traffic: dict, seed: int, pool_size: int,
               count: int) -> np.ndarray:
    """`count` pool indices in the mix's order."""
    order = traffic.get("order", "cycle")
    if order != "cycle":
        raise ValueError(f"unknown order {order!r}")
    perm = rng(seed, 1).permutation(pool_size)
    return perm[np.arange(count) % pool_size]
