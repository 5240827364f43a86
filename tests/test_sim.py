"""Event-driven federation simulator: determinism, sampled-cohort reward
conservation, straggler/dropout/Byzantine handling, async staleness weights."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.blockchain import TokenLedger
from repro.core import FederatedTrainer, ModelBundle
from repro.models import classifier as clf
from repro.optim import adam
from repro.sim import (
    BufferedAggregator,
    BufferedUpdate,
    ClientPopulation,
    PopulationSpec,
    SamplerState,
    SimConfig,
    SimulatedFederation,
    get_sampler,
    staleness_weight,
    weighted_delta_mean,
)
from repro.utils.tree import tree_stack


def _small_pop(n=60, seed=0, **kw):
    defaults = dict(n_clients=n, dataset="synth10", beta=0.3, n_batches=1,
                    batch_size=16, straggler_frac=0.1, straggler_slowdown=8.0,
                    dropout_rate=0.05, byzantine_frac=0.0, seed=seed)
    defaults.update(kw)
    return ClientPopulation.from_spec(PopulationSpec(**defaults))


def _run(pop, seed=0, **kw):
    defaults = dict(rounds=3, sample_frac=0.25, n_clusters=3, eval_every=0,
                    seed=seed)
    defaults.update(kw)
    sim = SimulatedFederation(pop, SimConfig(**defaults))
    return sim, sim.run()


# --------------------------------------------------------------------------- #
# determinism
# --------------------------------------------------------------------------- #

@pytest.mark.slow
@pytest.mark.parametrize("mode", ["sync", "async"])
def test_fixed_seed_replays_identically(mode):
    kw = dict(mode=mode, buffer_size=6, concurrency=12)
    _, a = _run(_small_pop(seed=3), seed=3, **kw)
    _, b = _run(_small_pop(seed=3), seed=3, **kw)
    _, c = _run(_small_pop(seed=4), seed=4, **kw)
    assert a.event_log == b.event_log
    assert len(a.event_log) > 0
    np.testing.assert_array_equal(a.balances, b.balances)
    assert a.final_accuracy == b.final_accuracy
    assert a.event_log != c.event_log       # seed actually matters


# --------------------------------------------------------------------------- #
# sampled-cohort reward conservation
# --------------------------------------------------------------------------- #

def test_sampled_cohort_reward_conservation():
    pop = _small_pop(byzantine_frac=0.1)
    sim, rep = _run(pop, rounds=4)
    total = sim.cfg.total_reward
    for rec in rep.history:
        if rec.arrived.any():
            # the full pool splits exactly into paid + burned
            np.testing.assert_allclose(rec.reward_paid + rec.reward_burned,
                                       total, rtol=1e-5)
    assert rep.ledger_conserved
    assert rep.chain_valid


def test_non_cohort_balances_untouched():
    pop = _small_pop(dropout_rate=0.0, byzantine_frac=0.0)
    sim, rep = _run(pop, rounds=1)
    rec = rep.history[0]
    touched = set(int(g) for g in rec.cohort) | {rec.producer}
    stake = sim.cfg.initial_stake
    for cid in range(pop.n_clients):
        if cid not in touched:
            assert rep.balances[cid] == stake, cid


# --------------------------------------------------------------------------- #
# stragglers / dropouts / Byzantine clients
# --------------------------------------------------------------------------- #

def test_permanent_straggler_never_settles():
    pop = _small_pop(n=30, straggler_frac=0.0, dropout_rate=0.0)
    pop.availability[:] = 1.0
    pop.latency.speed[7] = 1e9          # never beats any deadline
    sim, rep = _run(pop, rounds=3, sample_frac=1.0)
    for rec in rep.history:
        slot = int(np.flatnonzero(rec.cohort == 7)[0])
        assert not rec.arrived[slot]
        assert rec.n_stragglers >= 1
    assert rep.balances[7] == sim.cfg.initial_stake
    assert rep.ledger_conserved


def test_byzantine_client_rejected_end_to_end():
    pop = _small_pop(n=30, straggler_frac=0.0, dropout_rate=0.0)
    pop.availability[:] = 1.0
    pop.byzantine[5] = True
    sim, rep = _run(pop, rounds=3, sample_frac=1.0, deadline=1e6)
    for rec in rep.history:
        assert rec.n_byzantine == 1
        assert rec.verified_frac < 1.0
        assert rec.reward_burned > 0.0
    # the freerider never earns a training reward — at most the tiny
    # aggregation fees for rounds where CACC elected it producer; honest
    # clients settle their full rewards
    per_round_fee_bound = sim.cfg.total_reward / pop.n_clients
    gain = rep.balances[5] - sim.cfg.initial_stake
    assert gain < len(rep.history) * per_round_fee_bound
    honest = np.delete(rep.balances, 5)
    assert honest.max() > sim.cfg.initial_stake + 1.0
    assert rep.balances[5] < honest.mean()
    assert rep.ledger_conserved and rep.chain_valid


# --------------------------------------------------------------------------- #
# async buffered aggregation: staleness weighting
# --------------------------------------------------------------------------- #

def test_staleness_weight_monotone():
    s = jnp.arange(6)
    w = np.asarray(staleness_weight(s, alpha=0.5))
    assert w[0] == 1.0
    assert np.all(np.diff(w) < 0)
    np.testing.assert_allclose(np.asarray(staleness_weight(s, alpha=0.0)),
                               np.ones(6))


def test_weighted_delta_mean_matches_manual():
    rng = np.random.default_rng(0)
    deltas = [{"w": jnp.asarray(rng.normal(size=(4, 3)), jnp.float32)}
              for _ in range(5)]
    w = jnp.asarray([1.0, 0.5, 0.25, 0.0, 2.0])
    out = weighted_delta_mean(tree_stack(deltas), w)
    manual = sum(float(wi) * np.asarray(d["w"])
                 for wi, d in zip(w, deltas)) / float(w.sum())
    np.testing.assert_allclose(np.asarray(out["w"]), manual, rtol=1e-5)


def test_buffered_aggregator_staleness_and_gate():
    agg = BufferedAggregator(capacity=3, alpha=1.0)
    mk = lambda v: {"w": jnp.ones((2,), jnp.float32) * (v + 1)}
    for client, version in [(0, 0), (1, 1), (2, 2)]:
        agg.add(BufferedUpdate(client, mk(version), version))
    res = agg.flush(current_version=3, gate=np.array([1.0, 1.0, 0.0]))
    np.testing.assert_array_equal(res.staleness, [3, 2, 1])
    # gated update (client 2) contributes nothing despite lowest staleness
    np.testing.assert_allclose(res.weights, [1 / 4, 1 / 3, 0.0], rtol=1e-6)
    manual = (1 / 4 * 1.0 + 1 / 3 * 2.0) / (1 / 4 + 1 / 3)
    np.testing.assert_allclose(np.asarray(res.delta["w"]),
                               np.full(2, manual), rtol=1e-5)
    assert len(agg) == 0


def test_async_sim_staleness_observed_and_conserved():
    pop = _small_pop(byzantine_frac=0.1)
    sim, rep = _run(pop, rounds=4, mode="async", buffer_size=6, concurrency=18)
    assert any(r.staleness_mean > 0 for r in rep.history)
    for rec in rep.history:
        np.testing.assert_allclose(rec.reward_paid + rec.reward_burned,
                                   sim.cfg.total_reward, rtol=1e-5)
    assert rep.ledger_conserved and rep.chain_valid


# --------------------------------------------------------------------------- #
# samplers
# --------------------------------------------------------------------------- #

def test_uniform_sampler_deterministic_and_sorted():
    online = np.arange(50)
    s = get_sampler("uniform")
    a = s(np.random.default_rng(1), online, 10, SamplerState())
    b = s(np.random.default_rng(1), online, 10, SamplerState())
    np.testing.assert_array_equal(a, b)
    assert len(np.unique(a)) == 10
    assert np.all(np.diff(a) > 0)


def test_stake_weighted_sampler_prefers_rich_clients():
    online = np.arange(40)
    balances = np.ones(40)
    balances[:5] = 100.0
    state = SamplerState(balances=balances)
    s = get_sampler("stake_weighted")
    rng = np.random.default_rng(0)
    hits = sum(np.intersect1d(s(rng, online, 8, state), np.arange(5)).size
               for _ in range(50))
    # rich 5 hold ~58% of total stake; uniform would give them 20% of picks
    assert hits > 0.4 * 50 * 8


def test_cluster_stratified_sampler_covers_all_clusters():
    online = np.arange(60)
    labels = np.repeat([0, 1, 2], 20)
    state = SamplerState(last_labels=labels, n_clusters=3)
    s = get_sampler("cluster_stratified")
    cohort = s(np.random.default_rng(0), online, 12, state)
    assert len(cohort) == 12
    picked = labels[cohort]
    for c in range(3):
        assert (picked == c).sum() == 4      # exact proportional allocation


# --------------------------------------------------------------------------- #
# chain_round over an explicit cohort (core integration)
# --------------------------------------------------------------------------- #

def test_chain_round_cohort_scatter():
    pop = _small_pop(n=40, dropout_rate=0.0, straggler_frac=0.0)
    sim, _ = _run(pop, rounds=1, sample_frac=0.3)
    tr = sim.trainer
    cohort = np.array([2, 9, 17, 25, 33])
    arrived = np.array([True, True, False, True, True])
    params = jax.tree.map(lambda x: x[jnp.asarray(cohort)], sim.params)
    labels = jnp.asarray([0, 0, 1, 1, 2])
    corr = jnp.eye(5, dtype=jnp.float32)
    before = tr.ledger.balances.copy()
    res = tr.chain_round(100, params, labels, corr, cohort=cohort,
                         arrived=arrived)
    assert not res.verified[2]               # the no-show is never verified
    assert res.rewards[2] == 0.0
    np.testing.assert_allclose(res.rewards.sum(), sim.cfg.total_reward,
                               rtol=1e-5)
    assert res.producer in set(int(c) for c in cohort[arrived])
    delta = tr.ledger.balances - before
    outside = np.ones(40, bool)
    outside[cohort] = False
    np.testing.assert_array_equal(delta[outside], 0.0)
    assert tr.ledger.conserved()


@pytest.mark.parametrize("k,n_clusters,absent", [
    (10, 1, ()),                       # a FedBuff flush
    (300, 5, (3, 17, 150, 299)),       # a sync round with no-shows
], ids=["flush", "sync_round"])
def test_chain_round_settles_on_host(k, n_clusters, absent):
    """Given NumPy labels, Pearson matrix and digests, the settlement makes
    no transfer in either direction: CACC and the reward split run on the
    host."""
    cfg = clf.MLPConfig(in_dim=8, hidden=(8,), rep_dim=4, num_classes=3)
    bundle = ModelBundle(functools.partial(clf.apply, cfg),
                         functools.partial(clf.embed, cfg), 3)
    tr = FederatedTrainer(bundle, "fedavg", adam(1e-3), n_clusters=n_clusters)
    n_total = 1000
    tr.ledger = TokenLedger(n_total, tr.initial_stake)
    rng = np.random.default_rng(k)
    cohort = rng.choice(n_total, k, replace=False)
    labels = rng.integers(0, n_clusters, k).astype(np.int32)
    corr = rng.uniform(-1, 1, (k, k)).astype(np.float32)
    arrived = np.ones(k, bool)
    arrived[list(absent)] = False
    digests = [f"{i:08x}{i * 7919 % 2**32:08x}{6570:08x}" for i in range(k)]
    with jax.transfer_guard("disallow"):
        res = tr.chain_round(0, None, labels, corr, cohort=cohort,
                             arrived=arrived, digests=digests)
    assert res.verified[arrived].all() and not res.verified[~arrived].any()
    assert res.producer in set(cohort[arrived].tolist())
    np.testing.assert_allclose(res.rewards.sum(), tr.total_reward, rtol=1e-12)
    assert tr.ledger.conserved()
