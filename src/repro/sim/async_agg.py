"""Buffered asynchronous aggregation (FedBuff-style) with staleness weights.

Async mode removes the round barrier: clients are dispatched a snapshot of
the global model, train at their own speed, and their *deltas* (update −
snapshot) accumulate in a fixed-capacity buffer.  When the buffer fills, the
server merges it in one shot and bumps the model version.  An update that
trained against version ``v`` but merges at version ``v'`` has staleness
``s = v' − v`` and is down-weighted

    w(s) = (1 + s)^(-alpha)            (FedBuff / Nguyen et al., 2022)

so slow clients still contribute but cannot drag the model backwards.

The merge itself is the repo's one true weighted-mean collective — the
fixed-order tree reduction ``repro.core.aggregation.weighted_delta_mean`` —
which the legacy driver applies per leaf here and the fused engine's
``async_merge`` applies to flat (k, N) rows, elementwise the same tree, so
both give the same bits; it always runs on replicated buffer rows, which
keeps async seeded replay identical across mesh widths.  Chain
integration is the caller's job: the driver gates merge weights with CACC
verification, so tampered updates carry zero weight *and* zero reward.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any

import jax.numpy as jnp
import numpy as np

from repro.core.aggregation import staleness_weight, weighted_delta_mean
from repro.utils.tree import tree_stack

Pytree = Any


@dataclass(frozen=True)
class BufferedUpdate:
    client: int
    delta: Pytree                 # local params − dispatch snapshot
    version: int                  # server model version at dispatch time


@dataclass
class MergeResult:
    delta: Pytree                 # staleness-weighted mean delta
    clients: np.ndarray           # (K,) contributing client ids
    staleness: np.ndarray         # (K,) int staleness per contribution
    weights: np.ndarray           # (K,) effective merge weights


@dataclass
class BufferedAggregator:
    """Fixed-capacity update buffer; :meth:`flush` merges and empties it."""

    capacity: int = 16
    alpha: float = 0.5
    buffer: list[BufferedUpdate] = field(default_factory=list)

    def add(self, update: BufferedUpdate) -> bool:
        """Returns True when the buffer has reached capacity (time to flush)."""
        self.buffer.append(update)
        return len(self.buffer) >= self.capacity

    def __len__(self) -> int:
        return len(self.buffer)

    def flush(self, current_version: int,
              gate: np.ndarray | None = None) -> MergeResult:
        """Merge everything buffered.  ``gate`` (optional, (K,) 0/1) zeroes
        the merge weight of individual contributions — the driver passes the
        chain's verification mask so unverified (tampered) updates are
        excluded from the model as well as from rewards."""
        if not self.buffer:
            raise ValueError("flush of empty buffer")
        clients = np.array([u.client for u in self.buffer], dtype=np.int64)
        staleness = np.array([current_version - u.version for u in self.buffer],
                             dtype=np.int64)
        w = np.asarray(staleness_weight(staleness, self.alpha), np.float32)
        if gate is not None:
            w = w * np.asarray(gate, np.float32)
        stacked = tree_stack([u.delta for u in self.buffer])
        merged = weighted_delta_mean(stacked, jnp.asarray(w))
        self.buffer = []
        return MergeResult(merged, clients, staleness, w)
