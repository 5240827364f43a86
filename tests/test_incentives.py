"""Incentive mechanism (Eqs. 7–9): property-based invariants."""
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core.incentives import allocate_rewards, apply_round_settlement


@settings(max_examples=50, deadline=None)
@given(
    m=st.integers(2, 40),
    c=st.integers(1, 8),
    rho=st.floats(1.1, 3.0),
    reward=st.floats(1.0, 100.0),
    seed=st.integers(0, 2**16),
)
def test_total_reward_conserved(m, c, rho, reward, seed):
    rng = np.random.default_rng(seed)
    labels = jnp.asarray(rng.integers(0, c, m))
    alloc = allocate_rewards(labels, c, reward, rho)
    # Σ Γ(n_i) = ℜ exactly (over non-empty clusters)
    np.testing.assert_allclose(float(jnp.sum(alloc.cluster_reward)), reward,
                               rtol=1e-5)
    # per-client payouts also sum to ℜ
    np.testing.assert_allclose(float(jnp.sum(alloc.client_reward)), reward,
                               rtol=1e-5)


@settings(max_examples=30, deadline=None)
@given(rho=st.floats(1.05, 3.0), seed=st.integers(0, 2**16))
def test_percapita_reward_increases_with_cluster_size(rho, seed):
    """ρ>1 ⇒ bigger clusters pay more *per member* (the paper's design goal)."""
    rng = np.random.default_rng(seed)
    sizes = sorted(rng.integers(1, 10, 3).tolist())
    labels = jnp.asarray(np.repeat(np.arange(3), sizes))
    alloc = allocate_rewards(labels, 3, 20.0, rho)
    per_capita = np.asarray(alloc.cluster_reward) / np.maximum(sizes, 1)
    assert all(per_capita[i] <= per_capita[i + 1] + 1e-9 for i in range(2))


def test_equal_shares_within_cluster():
    labels = jnp.asarray([0, 0, 0, 1, 1, 2])
    alloc = allocate_rewards(labels, 3, 20.0, 2.0)
    r = np.asarray(alloc.client_reward)
    np.testing.assert_allclose(r[0], r[1])
    np.testing.assert_allclose(r[1], r[2])
    np.testing.assert_allclose(r[3], r[4])


def test_paper_rho2_example():
    """ρ=2, clusters (3,1): κ = 20/10 = 2; Γ = (18, 2); per-capita (6, 2)."""
    labels = jnp.asarray([0, 0, 0, 1])
    alloc = allocate_rewards(labels, 2, 20.0, 2.0)
    np.testing.assert_allclose(np.asarray(alloc.cluster_reward), [18.0, 2.0],
                               rtol=1e-6)
    np.testing.assert_allclose(float(alloc.kappa), 2.0, rtol=1e-6)
    np.testing.assert_allclose(float(alloc.fee), 0.5, rtol=1e-6)  # κ/N


def _reference_rewards(labels, c, reward, rho, part):
    """Eqs. 7–9 client by client in float64 over the participants."""
    sizes = [sum(p for lab, p in zip(labels, part) if lab == tau)
             for tau in range(c)]
    powered = [n ** rho if n > 0 else 0.0 for n in sizes]
    kappa = reward / sum(powered) if sum(powered) > 0 else 0.0
    gamma = [kappa * pw for pw in powered]
    client = [gamma[lab] / max(sizes[lab], 1.0) * p
              for lab, p in zip(labels, part)]
    return gamma, client, kappa, kappa / max(sum(part), 1.0)


_REWARD_CASES = {
    # name: (labels, n_clusters, rho, participating or None)
    "full": ([0, 1, 2, 1, 0, 0, 2, 1, 1], 3, 2.0, None),
    "empty_clusters": ([4, 0, 4, 4, 0, 4], 6, 1.5, None),
    "non_participants": ([0, 0, 1, 1, 2, 2, 2], 3, 2.0,
                         [1, 0, 1, 1, 0, 1, 1]),
    "no_participants": ([0, 1, 1, 2], 3, 2.0, [0, 0, 0, 0]),
    "flush_one_cluster": ([0] * 10, 1, 2.0, [1] * 10),
}


@pytest.mark.parametrize("case", list(_REWARD_CASES))
def test_allocation_matches_float64_reference(case):
    labels, c, rho, part = _REWARD_CASES[case]
    mask = None if part is None else np.asarray(part, bool)
    alloc = allocate_rewards(np.asarray(labels), c, 20.0, rho,
                             participating=mask)
    gamma, client, kappa, fee = _reference_rewards(
        labels, c, 20.0, rho, [1.0] * len(labels) if part is None else part)
    assert isinstance(alloc.cluster_reward, np.ndarray)
    assert isinstance(alloc.client_reward, np.ndarray)
    assert isinstance(alloc.kappa, np.float64)
    assert isinstance(alloc.fee, np.float64)
    np.testing.assert_allclose(alloc.cluster_reward, gamma, rtol=1e-12)
    np.testing.assert_allclose(alloc.client_reward, client, rtol=1e-12)
    np.testing.assert_allclose(alloc.kappa, kappa, rtol=1e-12)
    np.testing.assert_allclose(alloc.fee, fee, rtol=1e-12)
    if part is not None:
        assert (alloc.client_reward[~mask] == 0.0).all()
    if part is not None and not any(part):
        assert alloc.kappa == 0.0 and alloc.fee == 0.0
        assert not alloc.cluster_reward.any()
    # device inputs give the same host result
    dev = allocate_rewards(jnp.asarray(labels), c, 20.0, rho,
                           participating=None if mask is None
                           else jnp.asarray(mask))
    np.testing.assert_array_equal(dev.client_reward, alloc.client_reward)


def test_settlement_routes_fees_to_producer():
    labels = jnp.asarray([0, 0, 1, 1])
    alloc = allocate_rewards(labels, 2, 20.0, 2.0)
    balances = jnp.full((4,), 5.0)
    verified = jnp.asarray([True, True, True, False])
    new = apply_round_settlement(balances, alloc, producer=0, verified=verified)
    new = np.asarray(new)
    # producer 0 collected 3 fees; client 3 (unverified) got nothing, paid nothing
    fee = float(alloc.fee)
    assert np.isclose(new[3], 5.0)
    expected_total = 20.0 + 4 * 5.0 - float(alloc.client_reward[3])
    np.testing.assert_allclose(new.sum(), expected_total, rtol=1e-6)
    assert new[0] > new[1]  # producer collected fees


def test_unverified_producer_forfeits_fees_jittable_mirror():
    """The jittable settlement burns the fees of an unverified producer
    (regression: it used to credit them unconditionally) and stays in exact
    agreement with the host-side ``TokenLedger.settle_round``."""
    from repro.blockchain import TokenLedger
    labels = jnp.asarray([0, 0, 1, 1])
    alloc = allocate_rewards(labels, 2, 20.0, 2.0)
    balances = jnp.full((4,), 5.0)
    verified = jnp.asarray([False, True, True, True])   # producer 0 unverified
    new = np.asarray(apply_round_settlement(balances, alloc, producer=0,
                                            verified=verified))
    fee = float(alloc.fee)
    # producer: no reward, no fees — balance untouched
    np.testing.assert_allclose(new[0], 5.0, rtol=1e-6)
    # verified clients pay their fee but nobody receives it
    np.testing.assert_allclose(
        new[1:], 5.0 + np.asarray(alloc.client_reward[1:]) - fee, rtol=1e-6)

    # exact agreement with the authoritative host ledger
    ledger = TokenLedger(4, initial_stake=5.0)
    ledger.mint_reward_pool(20.0)
    ledger.settle_round(np.asarray(alloc.client_reward), fee, producer=0,
                        verified=np.asarray(verified))
    np.testing.assert_allclose(ledger.balances, new, rtol=1e-6)
    assert ledger.conserved()

    # and with a verified producer the two mirrors also agree
    verified = jnp.asarray([True, True, False, True])
    new = np.asarray(apply_round_settlement(balances, alloc, producer=0,
                                            verified=verified))
    ledger = TokenLedger(4, initial_stake=5.0)
    ledger.mint_reward_pool(20.0)
    ledger.settle_round(np.asarray(alloc.client_reward), fee, producer=0,
                        verified=np.asarray(verified))
    np.testing.assert_allclose(ledger.balances, new, rtol=1e-6)
    assert ledger.conserved()
