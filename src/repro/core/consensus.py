"""CACC — Consensus Algorithm based on Cluster Centroids (paper §IV-C).

From the spectral partition, the client whose Pearson-row is Euclidean-closest
to its cluster's centroid (Eqs. 4–6) becomes that cluster's *representative*.
Representatives join the DPoS-style packing queue: they take turns producing
blocks and act as the aggregation client for their turn.

Centroid selection and queue rotation are host-side NumPy: a round's
Pearson matrix is at most a few hundred rows, so settling the chain needs no
device program and no transfer (the queue is consumed by the blockchain layer,
`repro.blockchain`).
"""
from __future__ import annotations

from typing import Collection, NamedTuple

import numpy as np


class CentroidResult(NamedTuple):
    representatives: np.ndarray  # (n_clusters,) int32 client index per cluster, -1 if empty
    distances: np.ndarray        # (m,) float64 distance of each client to its cluster centroid
    centroids: np.ndarray        # (n_clusters, m) float64 mean Pearson row per cluster


def select_centroid_clients(corr, labels, n_clusters: int) -> CentroidResult:
    """Paper Eqs. 4–6 on the Pearson matrix, in float64 on the host.

    Each client i is represented by its correlation profile Ξ[i, :] (the paper's
    𝔭 — "each point in the cluster").  The cluster centroid is the mean profile
    (Eq. 4); each member's Euclidean distance to it is Eq. 5–6; the argmin
    member becomes the cluster's packing-queue representative (a tie goes to
    the lowest index).  ``corr`` and ``labels`` may be NumPy or device arrays.
    """
    corr = np.asarray(corr, np.float64)
    labels = np.asarray(labels, np.int64)
    member = labels[None, :] == np.arange(n_clusters)[:, None]            # (C, m)
    counts = member.sum(axis=1)                                             # (C,)
    centroids = (member @ corr) / np.maximum(counts, 1)[:, None]            # Eq. 4

    diff = corr - centroids[labels]                                         # Eq. 5
    dist = np.sqrt(np.sum(diff * diff, axis=1))                             # Eq. 6

    # per-cluster argmin over members only
    reps = np.argmin(np.where(member, dist[None, :], np.inf), axis=1)
    reps = np.where(counts > 0, reps, -1).astype(np.int32)
    return CentroidResult(reps, dist, centroids)


def packing_queue(representatives) -> list[int]:
    """Ordered block-producer queue for the next epoch (empty clusters
    dropped).  Order is cluster index — deterministic, so every validator
    derives the same queue (DPoS slot schedule)."""
    return [int(r) for r in np.asarray(representatives) if r >= 0]


def producer_for_round(queue: list[int], round_idx: int,
                       active: Collection[int] | None = None) -> int:
    """Round-robin slot assignment (paper: representatives 'take turns').

    ``active`` (optional) restricts the slot to clients that are actually
    online this round — under partial participation (``repro.sim``) a
    representative may be a straggler or have dropped out, in which case its
    slot deterministically falls through to the next queue member, exactly as
    every validator would compute it from the same arrival set.
    """
    if not queue:
        raise ValueError("empty packing queue")
    if active is None:
        return queue[round_idx % len(queue)]
    start = round_idx % len(queue)
    for off in range(len(queue)):
        cand = queue[(start + off) % len(queue)]
        if cand in active:
            return cand
    raise ValueError("no active producer in packing queue")
