"""PAA — Prototype-based Aggregation Algorithm (paper §IV-B).

Pipeline per round (all jittable, fixed shapes):

    stacked local params ──embed probe batch──▶ prototypes (m, D)
    prototypes ──Pearson──▶ Ξ (m, m) ──spectral──▶ labels (m,)
    labels + stacked params ──cluster-masked FedAvg──▶ per-client new params

"Cluster-masked FedAvg" is the collective at the heart of the paper: clients in
the same cluster receive the mean of that cluster's parameters.  With stacked
parameters it is a one-hot membership matmul — the pure-jnp form below is the
oracle for the ``repro.kernels.cluster_agg`` Pallas kernel.

Deterministic tree reductions (``tree_sum`` / ``masked_tree_sum`` /
``tree_cluster_mean_params``): every cohort-axis float reduction consumed by
the fused round engine is a fixed-order adjacent-pair binary tree of explicit
elementwise adds.  ``jnp.sum`` / ``tensordot`` leave the reduction order to
the backend — the tree pins it in the math graph itself, so the jitted
program matches the pure-numpy oracle bit for bit, and zero-weight (masked /
padding) slots are where-guarded to contribute exactly +0.0 — appending them
never changes a single output bit.  One discipline applies on a mesh: the
reduced axis must be REPLICATED before the tree runs (the engine's combine
stage does this).  Reducing a still-sharded axis lets GSPMD rewrite tree
levels into cross-device collectives whose CPU codegen rounds differently
than the single-device program — ULP drift that breaks seeded replay
(``tests/test_tree_reduction.py`` pins both facts).  Oracles live in
``repro.kernels.ref`` (``tree_sum_ref`` / ``tree_cluster_mean_ref``).
"""
from __future__ import annotations

from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.core.pearson import pearson_affinity, pearson_matrix
from repro.core.prototypes import client_prototypes
from repro.core.spectral import spectral_cluster

Pytree = Any


class PAAResult(NamedTuple):
    new_stacked_params: Pytree     # per-client aggregated params (personalized)
    labels: jax.Array              # (m,) cluster assignment
    corr: jax.Array                # (m, m) Pearson matrix Ξ
    prototypes: jax.Array          # (m, D)
    cluster_sizes: jax.Array       # (n_clusters,)


# --------------------------------------------------------------------------- #
# deterministic fixed-order tree reductions (replicate-then-reduce bit identity)
# --------------------------------------------------------------------------- #

def _next_pow2(n: int) -> int:
    return 1 if n <= 1 else 1 << (n - 1).bit_length()


def tree_sum(x: jax.Array, axis: int = 0) -> jax.Array:
    """Fixed-order adjacent-pair binary-tree sum along ``axis``.

    The reduction is unrolled into an explicit chain of elementwise adds
    (padding the axis to the next power of two with +0.0), so the float
    rounding sequence is a property of the *graph*: the jitted program and
    the numpy oracle agree bit for bit, and padding within the same
    power-of-two width is a no-op.  Callers on a mesh must replicate the
    reduced axis first — over a sharded axis GSPMD turns tree levels into
    cross-device collectives with different rounding (see module docstring).
    """
    x = jnp.moveaxis(x, axis, 0)
    m = x.shape[0]
    p = _next_pow2(m)
    if p != m:
        x = jnp.concatenate(
            [x, jnp.zeros((p - m,) + x.shape[1:], x.dtype)], axis=0)
    while x.shape[0] > 1:
        h = x.shape[0] // 2
        a = x.reshape((h, 2) + x.shape[1:])
        x = a[:, 0] + a[:, 1]
    return x[0]


def masked_tree_sum(x: jax.Array, w: jax.Array, axis: int = 0) -> jax.Array:
    """Weighted tree sum where zero-weight slots contribute EXACTLY +0.0.

    ``where(w > 0, w·x, +0.0)`` guards against the two ways a dead slot
    could still flip bits: ``-0.0`` contributions (which turn a +0.0 partial
    into -0.0) and ``0·inf = NaN`` from garbage values in padding slots.
    Appending zero-weight slots is therefore a bitwise no-op, which is what
    lets the engine pad the cohort to a shard multiple.
    """
    wb = jnp.moveaxis(
        w.astype(x.dtype).reshape(w.shape + (1,) * (x.ndim - 1)), 0, axis)
    contrib = jnp.where(wb > 0, x * wb, jnp.zeros((), x.dtype))
    return tree_sum(contrib, axis=axis)


# FedBuff merge: staleness weights and the weighted mean of buffered deltas,
# shared by the engine's flat merge entry and the legacy driver

def staleness_weight(staleness, alpha: float = 0.5) -> jax.Array:
    """(1 + s)^(-alpha); alpha=0 disables staleness discounting."""
    s = jnp.asarray(staleness, jnp.float32)
    return (1.0 + s) ** (-alpha)


@jax.jit
def weighted_delta_mean(stacked_deltas: Pytree, weights: jax.Array) -> Pytree:
    """Normalised weighted mean over the leading buffer axis, via the
    deterministic fixed-order tree (zero-weight slots are where-guarded to
    exactly +0.0, denominator clamped like the single-cluster collective it
    replaced)."""
    w = weights.astype(jnp.float32)
    denom = jnp.maximum(tree_sum(w), 1e-9)

    def leaf(x):
        return (masked_tree_sum(x.astype(jnp.float32), w) / denom) \
            .astype(x.dtype)

    return jax.tree.map(leaf, stacked_deltas)


def tree_cluster_mean_params(stacked_params: Pytree, labels: jax.Array,
                             n_clusters: int,
                             weights: jax.Array | None = None) -> Pytree:
    """Cluster-masked FedAvg via fixed-order tree segment sums.

    Same semantics as :func:`cluster_mean_params` (every slot receives its
    cluster's weighted mean, denominator clamped so an all-masked cluster
    degrades to zeros), but each cluster's sum is a where-guarded tree over
    the slot axis instead of a one-hot contraction — run on a replicated
    slot axis (the engine's combine discipline) the bits match the numpy
    oracle exactly and appending zero-weight slots is a no-op.  The
    gather-back is a ``take`` (no second contraction).
    """
    m = labels.shape[0]
    onehot = jax.nn.one_hot(labels, n_clusters, dtype=jnp.float32)      # (m, C)
    w = jnp.ones((m,), jnp.float32) if weights is None \
        else weights.astype(jnp.float32)
    wo = onehot * w[:, None]                                            # (m, C)
    denom = jnp.maximum(tree_sum(wo, axis=0), 1e-9)                     # (C,)

    def leaf(x):
        xf = x.astype(jnp.float32)
        woT = wo.T.reshape((n_clusters, m) + (1,) * (xf.ndim - 1))
        contrib = jnp.where(woT > 0, woT * xf[None],
                            jnp.zeros((), jnp.float32))                 # (C, m, ...)
        sums = tree_sum(contrib, axis=1)                                # (C, ...)
        means = sums / denom.reshape((n_clusters,) + (1,) * (xf.ndim - 1))
        return jnp.take(means, labels, axis=0).astype(x.dtype)          # (m, ...)

    return jax.tree.map(leaf, stacked_params)


def _cluster_weights(labels: jax.Array, n_clusters: int,
                     weights: jax.Array | None) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Shared membership weights: (onehot (m,C), weighted onehot, denom (C,))."""
    m = labels.shape[0]
    onehot = jax.nn.one_hot(labels, n_clusters, dtype=jnp.float32)      # (m, C)
    w = jnp.ones((m,), jnp.float32) if weights is None else weights.astype(jnp.float32)
    wo = onehot * w[:, None]                                            # (m, C)
    denom = jnp.maximum(jnp.sum(wo, axis=0), 1e-9)                      # (C,)
    return onehot, wo, denom


def cluster_mean_rows(rows: jax.Array, labels: jax.Array, n_clusters: int,
                      weights: jax.Array | None = None) -> jax.Array:
    """Cluster-masked FedAvg over **arena rows** — the flat (m, N) form of
    ``cluster_mean_params`` (same two-step math, identical sums).

    The stacked params already live as one ``(m, N_params)`` matrix
    (``repro.runtime.arena``), so the whole FedAvg is two matmuls instead of
    a per-leaf tree map — exactly the input shape ``kernels.cluster_agg``
    streams on TPU.  Note: a single (C,m)×(m,N) contraction may block its
    m-loop differently than the per-leaf dots at large m, so results can
    drift from ``cluster_mean_params`` by float ulps; the fused round engine
    therefore keeps the per-leaf form for bit-identical legacy replay and
    this form is the TPU kernel-path input.
    """
    onehot, wo, denom = _cluster_weights(labels, n_clusters, weights)
    reduce_w = (wo / denom[None, :]).T                                  # (C, m)
    means = jnp.tensordot(reduce_w, rows.astype(jnp.float32), axes=(1, 0))
    return jnp.tensordot(onehot, means, axes=(1, 0)).astype(rows.dtype)


def cluster_mean_params(stacked_params: Pytree, labels: jax.Array, n_clusters: int,
                        weights: jax.Array | None = None,
                        method: str = "two_step") -> Pytree:
    """FedAvg within each cluster, broadcast back to members.

    For every leaf ``x`` of shape (m, ...):
        out[i] = mean_{j : labels[j]==labels[i]} x[j]
    Optionally weighted (paper uses |D_i|/n weights inside FedAvg; with equal
    client data volumes this reduces to the plain mean).

    ``method``:
      * ``"mix"`` — one (m × m) mixing matmul.  On a client-sharded mesh this
        all-reduces the FULL stacked parameter set (the contraction axis is
        the sharded one) — O(m·N_params) collective bytes.
      * ``"two_step"`` (default) — reduce to the C cluster means first, then
        gather back: O(C·N_params) collective bytes, an m/C× win measured in
        EXPERIMENTS.md §Perf.  Mathematically identical (same sums).
    """
    onehot, wo, denom = _cluster_weights(labels, n_clusters, weights)

    if method == "mix":
        # membership[i, j] = w_j * [labels_i == labels_j] / sum_cluster_w
        mix = (onehot / denom[None, :]) @ wo.T                      # (m, m)

        def leaf(x):
            # tensordot over the client axis — no reshape, so sharded layouts
            # survive intact on a pod mesh (launch/fl_target)
            out = jnp.tensordot(mix, x.astype(jnp.float32), axes=(1, 0))
            return out.astype(x.dtype)
    elif method in ("two_step", "two_step_bf16"):
        reduce_w = (wo / denom[None, :]).T                          # (C, m)
        # bf16 variant: cross-shard partial sums travel in bf16 — halves the
        # collective bytes; fine for means of ≤m values (§Perf iteration 2)
        tdt = jnp.bfloat16 if method == "two_step_bf16" else jnp.float32

        def leaf(x):
            means = jnp.tensordot(reduce_w.astype(tdt), x.astype(tdt), axes=(1, 0))
            out = jnp.tensordot(onehot.astype(tdt), means, axes=(1, 0))  # (m, ...)
            return out.astype(x.dtype)
    else:
        raise ValueError(method)

    return jax.tree.map(leaf, stacked_params)


def cluster_sizes(labels: jax.Array, n_clusters: int) -> jax.Array:
    return jnp.sum(jax.nn.one_hot(labels, n_clusters, dtype=jnp.int32), axis=0)


def paa_round(
    embed_fn: Callable,
    stacked_params: Pytree,
    probe_x: jax.Array,
    n_clusters: int,
    weights: jax.Array | None = None,
    kmeans_iters: int = 25,
    agg_method: str = "two_step",
) -> PAAResult:
    """One full PAA aggregation (paper steps 3–5 of Fig. 1).  The clustering
    runs under the ``paa`` named scope and the means under
    ``cluster_means``, so a profile can tell their device time apart."""
    with jax.named_scope("paa"):
        protos = client_prototypes(embed_fn, stacked_params, probe_x)  # (m, D)
        corr = pearson_matrix(protos)                                  # (m, m)
        labels = spectral_cluster(pearson_affinity(corr), n_clusters,
                                  kmeans_iters)
    with jax.named_scope("cluster_means"):
        new_params = cluster_mean_params(stacked_params, labels, n_clusters,
                                         weights, method=agg_method)
    sizes = cluster_sizes(labels, n_clusters)
    return PAAResult(new_params, labels, corr, protos, sizes)
