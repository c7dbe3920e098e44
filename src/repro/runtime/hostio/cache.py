"""Device-resident hot-adjacency cache: PilotANN's staging idea for BANG.

The host-resident graph variants pay the host link for *every* hop, but graph
traversals are massively skewed: high-in-degree hub nodes (and the medoid,
which every query expands first) are fetched orders of magnitude more often
than the tail. PilotANN (arXiv:2503.21206) gets its throughput by staging
exactly that hot subgraph in GPU memory. `HotAdjacencyCache` does the same
for the `base`/`sharded-base` neighbour fetch:

  * **Ranking.** Rows are ranked by in-degree over the full adjacency (how
    often a node appears as someone's neighbour -- a static proxy for fetch
    frequency that needs no warm-up traffic), medoid always included; the
    top `n_rows` rows are pinned on device.
  * **Probe.** A dense `slot_of: (n,) int32` map (-1 = not cached) resolves
    frontier ids to cache slots entirely on device. The map costs n*4 bytes
    -- R (the adjacency fan-out) times smaller than the graph it shields, so
    it preserves the variant's memory story.
  * **Bit-exact masked merge.** Cache-hit lanes gather their row from the
    device copy; only miss lanes reach the host service (their lanes are
    masked out of the callback's ownership mask, so host memory is never
    touched for a hit -- the exactly-once-per-miss property). The merged
    result equals the uncached gather bit-for-bit because the cached rows
    ARE the adjacency rows.

Hit counting crosses to the host through the callback's `cache_hit` operand
(`NeighborService._account`), which feeds the measured hit rate into
`exchange_bytes_per_hop` as `host_bytes_saved_per_hop`.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.core.worklist import INVALID_ID

__all__ = ["HotAdjacencyCache"]


class HotAdjacencyCache:
    """Top-in-degree adjacency rows pinned in device memory."""

    def __init__(
        self,
        adjacency: np.ndarray,
        n_rows: int,
        *,
        medoid: int | None = None,
    ) -> None:
        adjacency = np.asarray(adjacency, np.int32)
        n, R = adjacency.shape
        if n_rows < 1:
            raise ValueError(f"n_rows must be >= 1, got {n_rows}")
        n_rows = min(n_rows, n)
        # Frequency ranking: in-degree over the adjacency (pad entries of -1
        # never vote). Stable argsort keeps the ranking deterministic on ties.
        flat = adjacency[adjacency >= 0]
        indeg = np.bincount(np.minimum(flat, n - 1), minlength=n)
        order = np.argsort(-indeg, kind="stable")
        hot = order[:n_rows].astype(np.int32)
        if medoid is not None and medoid not in hot:
            # The medoid is every query's first expansion: always cache it.
            # (int32 array, not a Python list: list concat would promote the
            # whole hot_ids vector to int64 on this path only.)
            hot = np.concatenate(
                [np.array([medoid], np.int32), hot[: n_rows - 1]]
            )
        slot_of = np.full(n, -1, np.int32)
        slot_of[hot] = np.arange(len(hot), dtype=np.int32)
        self.n = n
        self.R = R
        self.n_rows = int(len(hot))
        self.hot_ids = hot
        # Uploaded once here and closed over by every trace: each compiled
        # executable references the same device buffers instead of paying a
        # fresh host->device conversion per trace. Works in plain jit and as
        # replicated constants inside shard_map bodies.
        self._slot_of = jnp.asarray(slot_of)
        self._rows = jnp.asarray(np.ascontiguousarray(adjacency[hot]))
        # Observability: consolidation-driven re-uploads, surfaced through
        # HostIORuntime.set_telemetry as bang_hostio_hot_cache_refreshes.
        self.refreshes = 0
        self._tel = None

    def set_telemetry(self, telemetry) -> None:
        """Attach a telemetry bundle (refresh-count gauge mirroring)."""
        self._tel = telemetry
        self._publish_refreshes()

    def _publish_refreshes(self) -> None:
        tel = self._tel
        if tel is not None:
            tel.registry.gauge(
                "bang_hostio_hot_cache_refreshes",
                "pinned-row re-uploads after consolidations",
            ).set(self.refreshes)

    # ------------------------------------------------------------- inspection
    def device_bytes(self) -> int:
        """Bytes this cache pins on device (rows + id->slot map)."""
        return int(self._rows.nbytes + self._slot_of.nbytes)

    # ------------------------------------------------------------- mutation
    def refresh(self, adjacency: np.ndarray) -> None:
        """Re-upload the pinned rows from a mutated adjacency (same hot set).

        Streaming mutability: consolidation rewrites adjacency rows in place
        (re-linking around deleted nodes), and a stale pinned row would be
        served bit-for-bit to every future hit. Keeping the *same* hot ids
        (in-degree skew doesn't move materially within one consolidation)
        means `slot_of` is unchanged and only the (n_rows, R) row block is
        re-uploaded; executables close over the cache object's buffers via
        this attribute, so new traces see the fresh rows, and
        `MutableBangIndex` drops old executables at the same epoch bump.
        """
        adjacency = np.asarray(adjacency, np.int32)
        if adjacency.shape[0] < self.n or adjacency.shape[1] != self.R:
            raise ValueError(
                f"refresh adjacency must cover ({self.n}, {self.R}), got "
                f"{adjacency.shape}"
            )
        self._rows = jnp.asarray(
            np.ascontiguousarray(adjacency[self.hot_ids])
        )
        self.refreshes += 1
        self._publish_refreshes()

    # ------------------------------------------------------------------ probe
    def probe(self, u):
        """(rows (B, R), hit (B,)) for a traced frontier id vector.

        Hit lanes carry their adjacency row gathered from the device copy;
        non-hit lanes carry -1. Sentinel/negative/out-of-range ids never hit.
        """
        valid = (u >= 0) & (u != INVALID_ID) & (u < self.n)
        slot = self._slot_of[jnp.clip(u, 0, self.n - 1)]
        hit = valid & (slot >= 0)
        rows = self._rows[jnp.clip(slot, 0, self.n_rows - 1)]
        return jnp.where(hit[:, None], rows, -1), hit
