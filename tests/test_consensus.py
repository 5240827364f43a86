"""CACC: centroid-representative selection (Eqs. 4–6) + packing queue."""
import math

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.consensus import packing_queue, producer_for_round, select_centroid_clients


def test_centroid_selection_matches_bruteforce():
    rng = np.random.default_rng(0)
    m, c = 12, 3
    corr = rng.uniform(-1, 1, (m, m)).astype(np.float32)
    corr = (corr + corr.T) / 2
    np.fill_diagonal(corr, 1.0)
    labels = rng.integers(0, c, m)

    res = select_centroid_clients(jnp.asarray(corr), jnp.asarray(labels), c)
    for tau in range(c):
        members = np.flatnonzero(labels == tau)
        centroid = corr[members].mean(axis=0)                 # Eq. 4
        dists = np.linalg.norm(corr[members] - centroid, axis=1)  # Eqs. 5–6
        want = members[np.argmin(dists)]
        assert int(res.representatives[tau]) == int(want)


def test_empty_cluster_marked():
    corr = jnp.eye(4)
    labels = jnp.asarray([0, 0, 1, 1])
    res = select_centroid_clients(corr, labels, 3)
    assert int(res.representatives[2]) == -1
    q = packing_queue(res.representatives)
    assert len(q) == 2 and -1 not in q


def _reference_centroids(corr, labels, c):
    """Eqs. 4–6 member by member in float64; the first member at the least
    distance is the representative, -1 for an empty cluster."""
    m = len(labels)
    corr = [[float(v) for v in row] for row in corr]
    reps, centroids, dists = [], [], [0.0] * m
    for tau in range(c):
        members = [i for i in range(m) if labels[i] == tau]
        centroid = [sum(corr[i][j] for i in members) / max(len(members), 1)
                    for j in range(m)]
        centroids.append(centroid)
        best, best_d = -1, math.inf
        for i in members:
            dists[i] = math.sqrt(sum((corr[i][j] - centroid[j]) ** 2
                                     for j in range(m)))
            if dists[i] < best_d:
                best, best_d = i, dists[i]
        reps.append(best)
    return reps, np.array(dists), np.array(centroids)


def _random_corr(m, seed):
    rng = np.random.default_rng(seed)
    corr = rng.uniform(-1, 1, (m, m)).astype(np.float32)
    corr = (corr + corr.T) / 2
    np.fill_diagonal(corr, 1.0)
    return corr


def _tied_corr():
    """Dyadic entries, so every distance is exact: slots 1 and 4 hold the
    same row and share the least distance of cluster 0 {1, 4, 6}."""
    corr = np.full((8, 8), 0.25, np.float32)
    corr[1] = corr[4] = [1.0, 0.5, 0.0, 0.25, 0.5, 0.0, 0.25, 0.0]
    corr[6] = [0.0, 0.5, 1.0, 0.25, 0.5, 0.75, 0.25, 1.0]
    return corr


_CASES = {
    # name: (corr, labels, n_clusters, representatives or None)
    "random": (_random_corr(12, 0), np.random.default_rng(0).integers(0, 3, 12),
               3, None),
    "empty_clusters": (_random_corr(10, 1),
                       np.array([0, 3, 3, 0, 0, 3, 0, 3, 3, 0]), 5,
                       None),
    "tie_first_slot": (_tied_corr(), np.array([1, 0, 2, 2, 0, 1, 0, 2]), 3,
                       [1, 0, 2]),
    # the FedBuff flush: one cluster over an identity matrix, all tied
    "flush_identity": (np.eye(8, dtype=np.float32), np.zeros(8, np.int32), 1,
                       [0]),
}


@pytest.mark.parametrize("case", list(_CASES))
def test_centroid_selection_matches_float64_reference(case):
    corr, labels, c, want_reps = _CASES[case]
    res = select_centroid_clients(corr, labels, c)
    reps, dists, centroids = _reference_centroids(corr, labels, c)
    for field in res:
        assert isinstance(field, np.ndarray)   # host values, not jax.Arrays
    assert res.representatives.dtype == np.int32
    assert res.representatives.tolist() == reps
    if want_reps is not None:
        assert reps == want_reps
    np.testing.assert_allclose(res.distances, dists, rtol=1e-12, atol=1e-15)
    np.testing.assert_allclose(res.centroids, centroids, rtol=1e-12,
                               atol=1e-15)
    assert packing_queue(res.representatives) == [r for r in reps if r >= 0]
    # device inputs give the same host result
    dev = select_centroid_clients(jnp.asarray(corr), jnp.asarray(labels), c)
    np.testing.assert_array_equal(dev.representatives, res.representatives)


def test_round_robin_rotation():
    q = [4, 7, 1]
    assert [producer_for_round(q, r) for r in range(6)] == [4, 7, 1, 4, 7, 1]
