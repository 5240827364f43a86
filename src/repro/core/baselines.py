"""Federated learning strategies: the paper's four baselines + BFLN itself.

A :class:`Strategy` is a bundle of pure functions consumed by
``repro.core.round`` (the legacy full-participation trainer) and
``repro.core.engine`` (the fused, arena-backed round engine):

    round_extras(stacked_params, cx, cy) -> extras   # what the server ships
    local_loss(params, x, y, extras) -> scalar       # client objective
    aggregate(stacked_params, cx, cy) -> AggOut      # server aggregation
    aggregate_cohort(stacked_params, cx, cy, arrived_w) -> CohortAggOut

``extras`` always carries a leading client axis (it is vmapped alongside the
client during local training).  Every baseline is a real implementation, not a
stub — the paper compares against all four in Table II.

``aggregate_cohort`` is the *engine-facing* aggregation stage: jittable,
fixed-shape, and mask-weighted.  ``arrived_w`` is a (k,) 0/1 float arrival
mask over the cohort slots — slots that missed the round contribute zero
aggregation weight but still occupy their slot (no dynamic shapes, so the
fused round program compiles exactly once per cohort size).  Every strategy
also returns a ``(k,)`` cluster-label vector and a ``(k, k)`` affinity
matrix for the blockchain's CACC consensus: BFLN computes them from its PAA
pipeline; flat strategies report the single-cluster view (zeros / identity),
exactly like the async FedBuff path always has.

Sharded-cohort contract: ``aggregate_cohort`` decomposes into two stages so
the engine can run the cohort axis sharded across a device mesh —

    cohort_partial(stacked_params, cx, cy, arrived_w) -> partial | None
    cohort_combine(stacked_params, partial, arrived_w, k) -> CohortAggOut

``cohort_partial`` is the shard-local half: per-slot values with a leading
cohort axis (BFLN: client prototypes), computable on each device's cohort
slice.  ``cohort_combine`` is the deterministic half: it may receive ``m >=
k`` slots (the engine pads the cohort to a shard multiple; slots ``>= k``
carry zero arrival weight) and must return a :class:`CohortAggOut` over the
first ``k`` slots with bits INVARIANT to the padding and to how the slot
axis was sharded — every cohort-axis float reduction inside it goes through
the fixed-order tree primitives in ``repro.core.aggregation``.
``aggregate_cohort`` is derived by :func:`compose_cohort`, so the
single-device legacy oracle and the sharded engine literally share the same
stage functions — replay parity holds by construction.
"""
from __future__ import annotations

from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.core.aggregation import (
    masked_tree_sum,
    paa_round,
    tree_cluster_mean_params,
    tree_sum,
)
from repro.core.pearson import pearson_affinity, pearson_matrix
from repro.core.prototypes import classwise_prototypes, client_prototypes
from repro.core.spectral import spectral_cluster
from repro.models.layers import TiedHead
from repro.utils.tree import tree_sq_norm, tree_sub

Pytree = Any


class ModelBundle(NamedTuple):
    """The model as the FL layer sees it (architecture-agnostic).

    ``params`` are what a client trains (one arena row).  A model with a
    frozen base every client shares (a LoRA-adapted LM) carries it in
    ``base``, and ``bind(base)`` returns the bundle whose functions close
    over that base: the round engine passes the base into its compiled
    entries as an argument and binds it there, so it is never a constant
    of an executable."""
    apply_fn: Callable[[Pytree, jax.Array], jax.Array]   # params, x -> logits
    embed_fn: Callable[[Pytree, jax.Array], jax.Array]   # params, x -> representations
    num_classes: int
    base: Pytree = None
    bind: Callable[[Pytree], "ModelBundle"] | None = None
    eval_metric: str = "accuracy"     # "accuracy" | "loss" (next-token LM)

    def with_base(self, base: Pytree) -> "ModelBundle":
        return self if self.bind is None else self.bind(base)


class AggOut(NamedTuple):
    stacked_params: Pytree
    labels: jax.Array | None = None          # cluster assignment (BFLN only)
    cluster_sizes: jax.Array | None = None   # (C,) (BFLN only)
    corr: jax.Array | None = None            # Pearson matrix (BFLN only)


class CohortAggOut(NamedTuple):
    """Engine-facing aggregation output (all fixed-shape, jit-friendly)."""
    stacked_params: Pytree       # (k, ...) per-slot aggregated params
    labels: jax.Array            # (k,) cluster assignment (zeros if unclustered)
    corr: jax.Array              # (k, k) affinity for CACC (eye if unclustered)


class Strategy(NamedTuple):
    name: str
    round_extras: Callable[[Pytree, jax.Array, jax.Array], Any]
    local_loss: Callable[[Pytree, jax.Array, jax.Array, Any], jax.Array]
    aggregate: Callable[[Pytree, jax.Array, jax.Array], AggOut]
    # jittable mask-weighted aggregation consumed by the fused round engine;
    # (stacked_params, cx, cy, arrived_w) -> CohortAggOut — derived from the
    # two-stage contract below via compose_cohort()
    aggregate_cohort: Callable[
        [Pytree, jax.Array, jax.Array, jax.Array], "CohortAggOut"] | None = None
    # True: round_extras returns ONE pytree shared by every client (no
    # leading client axis) — local_train broadcasts it via in_axes=None
    # instead of shipping k redundant copies through the vmap
    shared_extras: bool = False
    # sharded-cohort stages (see module docstring): per-slot partial values
    # computable on a cohort shard, and the deterministic combine that
    # tolerates zero-weight padding slots beyond k
    cohort_partial: Callable[
        [Pytree, jax.Array, jax.Array, jax.Array], Any] | None = None
    cohort_combine: Callable[
        [Pytree, Any, jax.Array, int], "CohortAggOut"] | None = None


#: tokens scored per block of a tied-head LM loss
LOSS_BLOCK = 128


def _xent(logits, y: jax.Array) -> jax.Array:
    """Mean cross-entropy of ``(..., C)`` logits against ``(...)`` labels;
    for a language model, ``(B, S, V)`` logits against next-token targets
    ``(B, S)``, or its :class:`~repro.models.layers.TiedHead`, scored a
    block of tokens at a time."""
    if isinstance(logits, TiedHead):
        return _tied_head_xent(logits.hidden, logits.table, y)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, y[..., None], axis=-1))


@jax.named_scope("lm_loss")
def _tied_head_xent(hidden: jax.Array, table: jax.Array, y: jax.Array,
                    block: int = LOSS_BLOCK) -> jax.Array:
    """Next-token loss of hidden states ``(..., D)`` scored against the tied
    head ``table (V, D)``, ``block`` tokens at a time: each block's logits
    are made, reduced to its tokens' losses and dropped, and remade in the
    backward pass, so no more than one block of ``(block, V)`` logits and
    its gradient exist at once."""
    d = hidden.shape[-1]
    h, t = hidden.reshape(-1, d), y.reshape(-1)
    n_tok = h.shape[0]
    blk = min(block, n_tok)
    n = -(-n_tok // blk)
    pad = n * blk - n_tok
    if pad:
        h = jnp.pad(h, ((0, pad), (0, 0)))
        t = jnp.pad(t, (0, pad))

    @jax.checkpoint
    def block_nll(hb, tb):
        z = hb @ table.T
        return jax.nn.logsumexp(z, axis=-1) \
            - jnp.take_along_axis(z, tb[:, None], axis=-1)[:, 0]

    nll = jax.lax.map(lambda a: block_nll(*a),
                      (h.reshape(n, blk, d), t.reshape(n, blk))).reshape(-1)
    return jnp.sum(nll[:n_tok]) / n_tok


def _flatten_batches(cx: jax.Array, cy: jax.Array) -> tuple[jax.Array, jax.Array]:
    """(m, nb, B, ...) -> (m, nb*B, ...)."""
    m = cx.shape[0]
    return (cx.reshape(m, -1, *cx.shape[3:]), cy.reshape(m, -1))


def _global_mean(stacked_params: Pytree) -> Pytree:
    mean = jax.tree.map(lambda x: jnp.mean(x, axis=0), stacked_params)
    m = jax.tree.leaves(stacked_params)[0].shape[0]
    return jax.tree.map(lambda g: jnp.broadcast_to(g[None], (m,) + g.shape), mean)


def _tree_masked_mean(stacked_params: Pytree, arrived_w: jax.Array,
                      k: int) -> Pytree:
    """Mask-weighted global mean, broadcast back to the first ``k`` slots.

    The fixed-shape form of FedAvg under partial participation: slots with
    zero arrival weight contribute nothing, and the denominator is the
    arrived count (clamped, so an empty round degrades to zeros harmlessly —
    the engine's scatter mask drops those rows anyway).  Tree-ordered
    reductions keep the bits invariant to cohort sharding and to
    zero-weight padding slots beyond ``k``.
    """
    w = arrived_w.astype(jnp.float32)
    with jax.named_scope("cluster_means"):
        denom = jnp.maximum(tree_sum(w), 1.0)

        def leaf(x):
            mean = masked_tree_sum(x.astype(jnp.float32), w) / denom
            return jnp.broadcast_to(mean[None],
                                    (k,) + mean.shape).astype(x.dtype)

        return jax.tree.map(leaf, stacked_params)


def compose_cohort(partial_fn: Callable, combine_fn: Callable) -> Callable:
    """Derive the one-shot ``aggregate_cohort`` from the two sharded-cohort
    stages.  The legacy oracle driver calls this composition with ``m == k``
    while the sharded engine calls the stages separately with ``m >= k`` —
    same functions, same bits (the combine is padding/partition-invariant by
    contract), so engine-vs-oracle replay parity needs no extra proof."""

    def aggregate_cohort(stacked_params, cx, cy, arrived_w):
        part = partial_fn(stacked_params, cx, cy, arrived_w)
        stacked_params, part = barrier_combine_inputs(stacked_params, part)
        return combine_fn(stacked_params, part, arrived_w, cx.shape[0])

    return aggregate_cohort


def barrier_combine_inputs(stacked_params: Pytree, partial: Any):
    """Pin the combine stage's inputs with an optimization barrier.

    Without it, XLA is free to clone the producer math (local training, the
    partial stage) into each consumer's fusion, and the clones can vectorise
    differently — ULP-different inputs to the combine, which breaks the
    bit-identical-replay-across-partitionings contract.  The barrier forces
    ONE materialisation that every consumer reads, so the combine's
    fixed-order trees see the same bits in the fused single-device program,
    the sharded program, and the legacy oracle."""
    if partial is None:
        return jax.lax.optimization_barrier(stacked_params), None
    return jax.lax.optimization_barrier((stacked_params, partial))


def _no_partial(stacked_params, cx, cy, arrived_w):
    """Shard-local stage for strategies whose combine needs only the trained
    params themselves (fedavg/fedprox/fedhkd mask-weighted mean, fedproto
    identity)."""
    return None


def _single_cluster_view(m: int) -> tuple[jax.Array, jax.Array]:
    """CACC inputs for unclustered strategies: one cluster, identity affinity
    — the exact view the async FedBuff path has always fed the chain."""
    return jnp.zeros((m,), jnp.int32), jnp.eye(m, dtype=jnp.float32)


# --------------------------------------------------------------------------- #
# FedAvg (McMahan et al., 2017)
# --------------------------------------------------------------------------- #

def make_fedavg(model: ModelBundle) -> Strategy:
    def round_extras(stacked_params, cx, cy):
        m = cx.shape[0]
        return jnp.zeros((m,), jnp.float32)  # no server payload

    def local_loss(params, x, y, extras):
        return _xent(model.apply_fn(params, x), y)

    def aggregate(stacked_params, cx, cy):
        return AggOut(_global_mean(stacked_params))

    def cohort_combine(stacked_params, partial, arrived_w, k):
        return CohortAggOut(_tree_masked_mean(stacked_params, arrived_w, k),
                            *_single_cluster_view(k))

    return Strategy("fedavg", round_extras, local_loss, aggregate,
                    compose_cohort(_no_partial, cohort_combine),
                    cohort_partial=_no_partial, cohort_combine=cohort_combine)


# --------------------------------------------------------------------------- #
# FedProx (Li et al., 2018): CE + (µ/2)‖w − w_global‖²
# --------------------------------------------------------------------------- #

def make_fedprox(model: ModelBundle, mu: float = 0.01) -> Strategy:
    def round_extras(stacked_params, cx, cy):
        # ONE shared anchor (no per-client broadcast): the prox gradient
        # µ·(w − w_global) then reads a single (N,) anchor inside the fused
        # step instead of k identical copies (shared_extras=True below)
        return jax.tree.map(lambda x: jnp.mean(x, axis=0), stacked_params)

    def local_loss(params, x, y, anchor):
        ce = _xent(model.apply_fn(params, x), y)
        prox = 0.5 * mu * tree_sq_norm(tree_sub(params, anchor))
        return ce + prox

    def aggregate(stacked_params, cx, cy):
        return AggOut(_global_mean(stacked_params))

    def cohort_combine(stacked_params, partial, arrived_w, k):
        return CohortAggOut(_tree_masked_mean(stacked_params, arrived_w, k),
                            *_single_cluster_view(k))

    return Strategy("fedprox", round_extras, local_loss, aggregate,
                    compose_cohort(_no_partial, cohort_combine),
                    shared_extras=True,
                    cohort_partial=_no_partial, cohort_combine=cohort_combine)


# --------------------------------------------------------------------------- #
# FedProto (Tan et al., 2022): only class prototypes are shared; models stay
# personal.  Local objective: CE + λ‖proto_c(batch) − global_proto_c‖².
# --------------------------------------------------------------------------- #

def make_fedproto(model: ModelBundle, lam: float = 1.0) -> Strategy:
    K = model.num_classes

    def _client_protos(stacked_params, cx, cy):
        fx, fy = _flatten_batches(cx, cy)

        def one(params, x, y):
            return classwise_prototypes(model.embed_fn, params, x, y, K)

        return jax.vmap(one)(stacked_params, fx, fy)  # (m, K, D), (m, K)

    def round_extras(stacked_params, cx, cy):
        protos, counts = _client_protos(stacked_params, cx, cy)
        w = counts / jnp.maximum(jnp.sum(counts, axis=0, keepdims=True), 1.0)
        global_protos = jnp.sum(protos * w[..., None], axis=0)  # (K, D)
        m = cx.shape[0]
        return jnp.broadcast_to(global_protos[None], (m,) + global_protos.shape)

    def local_loss(params, x, y, global_protos):
        logits = model.apply_fn(params, x)
        ce = _xent(logits, y)
        protos, counts = classwise_prototypes(model.embed_fn, params, x, y, K)
        mask = (counts > 0).astype(jnp.float32)
        d = jnp.sum(jnp.square(protos - global_protos), axis=-1)  # (K,)
        align = jnp.sum(d * mask) / jnp.maximum(jnp.sum(mask), 1.0)
        return ce + lam * align

    def aggregate(stacked_params, cx, cy):
        return AggOut(stacked_params)  # models are never averaged

    def cohort_combine(stacked_params, partial, arrived_w, k):
        # personal models: arrived slots keep their freshly trained params
        # (the engine's scatter mask drops non-arrived rows on its own);
        # slicing to k drops the engine's shard-padding slots
        return CohortAggOut(jax.tree.map(lambda x: x[:k], stacked_params),
                            *_single_cluster_view(k))

    return Strategy("fedproto", round_extras, local_loss, aggregate,
                    compose_cohort(_no_partial, cohort_combine),
                    cohort_partial=_no_partial, cohort_combine=cohort_combine)


# --------------------------------------------------------------------------- #
# FedHKD (Chen & Vikalo, 2023): clients ship "hyper-knowledge" — per-class
# mean representations AND mean soft predictions; the server aggregates both
# and clients distil against them.  Built on FedAvg model averaging.
# --------------------------------------------------------------------------- #

def make_fedhkd(model: ModelBundle, lam_rep: float = 0.05,
                lam_soft: float = 0.05, temp: float = 2.0) -> Strategy:
    K = model.num_classes

    def _hyper_knowledge(stacked_params, cx, cy):
        fx, fy = _flatten_batches(cx, cy)

        def one(params, x, y):
            protos, counts = classwise_prototypes(model.embed_fn, params, x, y, K)
            soft = jax.nn.softmax(model.apply_fn(params, x) / temp, axis=-1)
            onehot = jax.nn.one_hot(y, K, dtype=soft.dtype)
            soft_per_class = (onehot.T @ soft) / jnp.maximum(counts, 1.0)[:, None]
            return protos, soft_per_class, counts

        return jax.vmap(one)(stacked_params, fx, fy)

    def round_extras(stacked_params, cx, cy):
        protos, softs, counts = _hyper_knowledge(stacked_params, cx, cy)
        w = counts / jnp.maximum(jnp.sum(counts, axis=0, keepdims=True), 1.0)
        H = jnp.sum(protos * w[..., None], axis=0)        # (K, D)
        Q = jnp.sum(softs * w[..., None], axis=0)         # (K, K)
        m = cx.shape[0]
        return (jnp.broadcast_to(H[None], (m,) + H.shape),
                jnp.broadcast_to(Q[None], (m,) + Q.shape))

    def local_loss(params, x, y, extras):
        H, Q = extras
        logits = model.apply_fn(params, x)
        ce = _xent(logits, y)
        reps = model.embed_fn(params, x)
        rep_loss = jnp.mean(jnp.sum(jnp.square(reps - H[y]), axis=-1))
        logp = jax.nn.log_softmax(logits / temp, axis=-1)
        q = jnp.maximum(Q[y], 1e-8)
        kd = jnp.mean(jnp.sum(q * (jnp.log(q) - logp), axis=-1))
        return ce + lam_rep * rep_loss + lam_soft * kd

    def aggregate(stacked_params, cx, cy):
        return AggOut(_global_mean(stacked_params))

    def cohort_combine(stacked_params, partial, arrived_w, k):
        return CohortAggOut(_tree_masked_mean(stacked_params, arrived_w, k),
                            *_single_cluster_view(k))

    return Strategy("fedhkd", round_extras, local_loss, aggregate,
                    compose_cohort(_no_partial, cohort_combine),
                    cohort_partial=_no_partial, cohort_combine=cohort_combine)


# --------------------------------------------------------------------------- #
# BFLN (this paper): plain CE locally; PAA clustered aggregation server-side.
# The probe batch (ψ same-category samples, paper §IV-B) is sampled by the
# aggregation client and closed over per round by the caller.
# --------------------------------------------------------------------------- #

def make_bfln(model: ModelBundle, probe_x: jax.Array, n_clusters: int,
              kmeans_iters: int = 25) -> Strategy:
    def round_extras(stacked_params, cx, cy):
        m = cx.shape[0]
        return jnp.zeros((m,), jnp.float32)

    def local_loss(params, x, y, extras):
        return _xent(model.apply_fn(params, x), y)

    def aggregate(stacked_params, cx, cy):
        res = paa_round(model.embed_fn, stacked_params, probe_x, n_clusters,
                        kmeans_iters=kmeans_iters)
        return AggOut(res.new_stacked_params, res.labels, res.cluster_sizes, res.corr)

    def cohort_partial(stacked_params, cx, cy, arrived_w):
        # per-slot prototypes (m, D): the ONLY cross-slot input the combine
        # needs — each device embeds the shared probe batch through its own
        # cohort slice, and only this small matrix gets replicated
        with jax.named_scope("paa"):
            return client_prototypes(model.embed_fn, stacked_params, probe_x)

    def cohort_combine(stacked_params, protos, arrived_w, k):
        # PAA with the arrival mask as aggregation weights.  Pearson +
        # spectral run on the REAL k slots only (slicing the Pearson input
        # is per-entry exact, and the (k, k) spectral problem must match the
        # single-device program op for op); the cluster means run over ALL
        # m >= k slots through the fixed-order tree segment sums — padding
        # slots carry zero weight, so their garbage params and arbitrary
        # labels contribute exactly +0.0
        with jax.named_scope("paa"):
            corr = pearson_matrix(protos[:k])
            labels = spectral_cluster(pearson_affinity(corr), n_clusters,
                                      kmeans_iters)
        m = protos.shape[0]
        with jax.named_scope("cluster_means"):
            labels_m = labels if m == k else jnp.concatenate(
                [labels, jnp.zeros((m - k,), labels.dtype)])
            new_params = tree_cluster_mean_params(stacked_params, labels_m,
                                                  n_clusters,
                                                  weights=arrived_w)
            if m != k:
                new_params = jax.tree.map(lambda x: x[:k], new_params)
        return CohortAggOut(new_params, labels, corr)

    return Strategy("bfln", round_extras, local_loss, aggregate,
                    compose_cohort(cohort_partial, cohort_combine),
                    cohort_partial=cohort_partial,
                    cohort_combine=cohort_combine)


STRATEGY_FACTORIES = {
    "fedavg": make_fedavg,
    "fedprox": make_fedprox,
    "fedproto": make_fedproto,
    "fedhkd": make_fedhkd,
}
