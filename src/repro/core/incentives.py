"""Incentive mechanism based on cluster membership size (paper §IV-C-1).

    Γ(n_i) = κ · n_i^ρ,   κ = ℜ / Σ_i n_i^ρ,   ρ > 1          (Eqs. 7–8)
    per-client reward  r = Γ(n_i) / n_i
    aggregation fee    g = κ / N                               (Eq. 9)

Properties (property-tested in tests/test_incentives.py):
  * ΣΓ(n_i) = ℜ exactly (token conservation),
  * per-capita reward κ·n_i^{ρ-1} strictly increases with cluster size for ρ>1,
  * clients in the same cluster receive equal shares.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


class RewardAllocation(NamedTuple):
    cluster_reward: np.ndarray   # (C,) Γ(n_i)
    client_reward: np.ndarray    # (m,) r_k for every client
    kappa: np.float64            # scalar κ
    fee: np.float64              # scalar g = κ / N


def allocate_rewards(
    labels,
    n_clusters: int,
    total_reward: float,
    rho: float = 2.0,
    participating=None,
) -> RewardAllocation:
    """Distribute the round's reward pool ℜ by cluster size, in float64 on
    the host (the ledger's precision).

    ``labels``: (m,) cluster assignment from PAA, NumPy or a device array.
    Empty clusters get Γ=0 and do not absorb tokens (the denominator only sums
    over realised sizes, which matches Σ n_i = N in the paper since empty
    clusters have n_i = 0).

    ``participating``: optional (m,) boolean/0-1 mask for partial-participation
    rounds (client sampling, stragglers, dropouts — ``repro.sim``).  Cluster
    sizes n_i count only participants, non-participants receive zero reward,
    and the aggregation fee g = κ/N divides by the participant count, so the
    full pool is always allocated over exactly the clients that delivered an
    update.  ``None`` (the paper's full-participation setting) keeps the
    original Eqs. 7–9 semantics unchanged.
    """
    labels = np.asarray(labels, np.int64)
    if participating is None:
        part = np.ones(labels.shape[0])
    else:
        part = np.asarray(participating, np.float64)
    sizes = np.bincount(labels, weights=part, minlength=n_clusters)
    powered = np.where(sizes > 0, sizes ** rho, 0.0)
    denom = powered.sum()
    # zero participants ⇒ zero pool (not total_reward / ε): callers that skip
    # the empty-round check must never see an astronomical κ or fee
    kappa = np.float64(total_reward / denom if denom > 0 else 0.0)
    cluster_reward = kappa * powered                                  # Γ(n_i)
    per_capita = cluster_reward / np.maximum(sizes, 1.0)              # Γ/n_i
    client_reward = per_capita[labels] * part
    fee = kappa / max(part.sum(), 1.0)                                # Eq. 9
    return RewardAllocation(cluster_reward, client_reward, kappa, fee)


def apply_round_settlement(
    balances: jax.Array,
    alloc: RewardAllocation,
    producer: jax.Array | int,
    verified: jax.Array,
) -> jax.Array:
    """Settle one round on the token ledger (jittable mirror of the blockchain
    ledger; `repro.blockchain.ledger` is the authoritative host-side copy).

    * every *verified* client receives its reward and pays the aggregation fee g,
    * the producer (aggregation client) collects the fees only if its OWN
      commitment verified — an unverified producer forfeits them (burned),
    * unverified clients (hash mismatch — paper's anti-freeriding rule) receive
      nothing and pay nothing; their reward is burned rather than re-allocated,
      matching the paper's "only if ... hash values match" wording.
    """
    verified = verified.astype(balances.dtype)
    fees = alloc.fee * verified                       # each verified client pays g
    credit = alloc.client_reward * verified
    balances = balances + credit - fees
    balances = balances.at[producer].add(jnp.sum(fees) * verified[producer])
    return balances
