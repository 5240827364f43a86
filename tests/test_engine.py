"""Arena-backed fused round engine: jit cache stability across varying
arrival counts, in-place (donated) arena updates, and bit-identical seeded
replay against the legacy `_cohort_round` + scatter driver — including empty
rounds and zero-arrival clusters."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.sim import ClientPopulation, PopulationSpec, SimConfig, SimulatedFederation


def _pop(n=60, seed=3, **kw):
    defaults = dict(n_clients=n, dataset="synth10", beta=0.3, n_batches=1,
                    batch_size=16, straggler_frac=0.2, straggler_slowdown=8.0,
                    dropout_rate=0.05, byzantine_frac=0.1, seed=seed)
    defaults.update(kw)
    return ClientPopulation.from_spec(PopulationSpec(**defaults))


def _sim(pop, engine, **kw):
    defaults = dict(rounds=4, sample_frac=0.25, n_clusters=3, eval_every=2,
                    seed=3, engine=engine)
    defaults.update(kw)
    return SimulatedFederation(pop, SimConfig(**defaults))


def _block_hashes(sim):
    return [b.block_hash() for b in sim.trainer.chain.blocks]


# --------------------------------------------------------------------------- #
# jit cache stability (the ROADMAP recompile item)
# --------------------------------------------------------------------------- #

def test_engine_compiles_once_across_varying_arrival_counts():
    """Regression for the ROADMAP open item: eval used to recompile for
    every distinct arrived-client count.  The engine's fixed-shape masked
    entries compile exactly once, no matter how arrivals vary."""
    sim = _sim(_pop(straggler_frac=0.3), engine=True, rounds=5, eval_every=1)
    rep = sim.run()
    counts = {int(r.arrived.sum()) for r in rep.history}
    assert len(counts) > 1, "population should produce varying arrival counts"
    sizes = sim.engine.cache_sizes()
    assert sizes["sync_step"] == 1, sizes
    assert sizes["eval_cohort"] == 1, sizes
    # the final population eval has its own entry and never retraces the
    # round eval
    assert sizes["eval_population"] == 1, sizes


def test_legacy_final_eval_has_dedicated_entry():
    """The final population eval no longer reuses the round-eval jit with a
    different leading dim (which thrashed compile-count accounting)."""
    sim = _sim(_pop(), engine=False, rounds=3, eval_every=1)
    sim.run()
    assert sim._eval_final._cache_size() == 1
    # the legacy round eval still recompiles per arrival count — quarantined
    # to its own entry (and killed entirely by the engine path)
    assert sim._eval._cache_size() >= 1


def test_arena_updated_in_place_no_population_realloc():
    """Donation: after warmup the arena buffer is reused in place — the
    O(n_clients · N_params) per-round reallocation is gone."""
    if jax.default_backend() != "cpu":
        pytest.skip("buffer-pointer check is exercised on CPU CI")
    pop = _pop(straggler_frac=0.0, dropout_rate=0.0)
    pop.availability[:] = 1.0
    sim = _sim(pop, engine=True, rounds=1, eval_every=0)
    sim.history.append(sim._run_sync_round(0))      # warmup (compile)
    ptr = sim.arena.data.unsafe_buffer_pointer()
    for r in range(1, 4):
        sim.history.append(sim._run_sync_round(r))
        assert sim.arena.data.unsafe_buffer_pointer() == ptr


# --------------------------------------------------------------------------- #
# bit-identical replay vs the legacy (pre-arena) driver
# --------------------------------------------------------------------------- #

@pytest.mark.slow
def test_engine_replay_identical_sync():
    # Accuracy comparisons below are exact on purpose: accuracy is a
    # count-based metric (hits/examples), so it tolerates the ulp-level
    # logit differences between the engine's stacked forward and the legacy
    # vmap eval unless an argmax lands exactly on a tie.  Deterministic for
    # a fixed platform/jax version; revisit if a jax upgrade flips one.
    a = _sim(_pop(), engine=True)
    b = _sim(_pop(), engine=False)
    ra, rb = a.run(), b.run()
    assert ra.event_log == rb.event_log
    assert _block_hashes(a) == _block_hashes(b)
    np.testing.assert_array_equal(ra.balances, rb.balances)
    assert ra.final_accuracy == rb.final_accuracy
    assert any(not r.arrived.all() for r in ra.history), \
        "replay should cover rounds with missing arrivals"
    for x, y in zip(ra.history, rb.history):
        assert x.producer == y.producer
        assert x.reward_paid == y.reward_paid
        assert (x.accuracy == y.accuracy) or \
            (np.isnan(x.accuracy) and np.isnan(y.accuracy))


@pytest.mark.slow
def test_engine_replay_identical_async():
    kw = dict(mode="async", buffer_size=6, concurrency=12)
    a = _sim(_pop(), engine=True, **kw)
    b = _sim(_pop(), engine=False, **kw)
    ra, rb = a.run(), b.run()
    assert ra.event_log == rb.event_log
    assert _block_hashes(a) == _block_hashes(b)
    np.testing.assert_array_equal(ra.balances, rb.balances)
    assert ra.final_accuracy == rb.final_accuracy
    assert any(r.staleness_mean > 0 for r in ra.history)


def test_empty_rounds_identical_and_blockless():
    """Nobody beats the deadline: no block is minted, balances untouched,
    and the engine/legacy drivers agree event for event."""
    def make():
        pop = _pop(n=30, straggler_frac=0.0, dropout_rate=0.0)
        pop.latency.speed[:] = 1e9          # everyone misses every deadline
        return pop
    a = _sim(make(), engine=True, rounds=2, eval_every=0)
    b = _sim(make(), engine=False, rounds=2, eval_every=0)
    ra, rb = a.run(), b.run()
    assert ra.event_log == rb.event_log
    assert all(not r.arrived.any() for r in ra.history)
    assert len(a.trainer.chain.blocks) == 1          # genesis only
    assert _block_hashes(a) == _block_hashes(b)
    np.testing.assert_array_equal(ra.balances,
                                  np.full(30, a.cfg.initial_stake))
    # the engine never ran — and never compiled
    assert a.engine.cache_sizes()["sync_step"] == 0


def test_engine_eval_matches_generic_masked_reference():
    """The engine's width-concatenated stacked eval == the generic
    ``masked_global_evaluate`` oracle (same per-client accuracies)."""
    from repro.core.fl import masked_global_evaluate
    pop = _pop(n=30)
    sim = _sim(pop, engine=True, rounds=1)
    k = 8
    cohort_idx = np.arange(k)
    mask = np.asarray([1, 1, 0, 1, 0, 1, 1, 1], np.float32)
    sim.arena.data, out = sim.engine.sync_step(
        sim.arena.data, cohort_idx, *sim.step_data, mask)
    ex, ey = sim._eval_slices()
    acc, cacc = sim.engine.eval_cohort(out.new_rows, mask, out.labels, ex, ey)
    ref_acc, ref_accs = masked_global_evaluate(
        sim.bundle.apply_fn, sim.arena.layout.unflatten(out.new_rows),
        ex, ey, mask)
    assert float(acc) == float(ref_acc)
    assert cacc.shape == (sim.cfg.n_clusters,)


def test_sync_step_zero_arrival_cluster_matches_legacy():
    """A cluster whose members all miss the deadline must aggregate exactly
    like the legacy path (its mean is weight-zero; members keep old rows)."""
    pop = _pop(n=40, straggler_frac=0.0, dropout_rate=0.0, byzantine_frac=0.0)
    ea = _sim(pop, engine=True, rounds=1)
    eb = _sim(pop, engine=False, rounds=1)
    k = 12
    cohort = np.arange(0, 40, 40 // k)[:k]
    cx, cy = pop.cohort_data(cohort)
    cohort_idx = jnp.asarray(cohort)

    # discover the round's labels (mask-independent), then craft an arrival
    # mask that leaves one whole cluster empty
    _, probe_out = ea.engine.sync_step(
        ea.arena.data, cohort_idx, *ea.step_data, np.ones(k, np.float32))
    labels = np.asarray(probe_out.labels)
    dead = labels[0]
    mask = (labels != dead)
    assert mask.any() and not mask.all()

    # fresh sims so both paths start from identical params
    ea = _sim(pop, engine=True, rounds=1)
    eb = _sim(pop, engine=False, rounds=1)
    arrived_w = jnp.asarray(mask, jnp.float32)
    new_data, out = ea.engine.sync_step(
        ea.arena.data, cohort_idx, *ea.step_data, arrived_w)

    local_params, agg, mean_loss = eb._cohort_round(
        jax.tree.map(lambda x: x[cohort_idx], eb.params), cx, cy, arrived_w)
    np.testing.assert_array_equal(np.asarray(out.labels), labels)
    np.testing.assert_array_equal(np.asarray(out.corr), np.asarray(agg.corr))
    assert float(out.mean_loss) == float(mean_loss)
    # scatter-back equivalence, bit for bit, dead cluster rows untouched
    upd = cohort[mask]
    new_rows = jax.tree.map(lambda x: x[jnp.asarray(np.flatnonzero(mask))],
                            agg.stacked_params)
    expect = jax.tree.map(lambda P, rows: P.at[jnp.asarray(upd)].set(rows),
                          eb.params, new_rows)
    np.testing.assert_array_equal(
        np.asarray(new_data).view(np.uint32),
        np.asarray(ea.arena.layout.flatten(expect)).view(np.uint32))


# --------------------------------------------------------------------------- #
# the round loop's device work lives in the engine's entries
# --------------------------------------------------------------------------- #

def _same_bits(a, b):
    a, b = np.asarray(a), np.asarray(b)
    assert a.dtype == b.dtype and a.shape == b.shape
    assert a.tobytes() == b.tobytes()


def test_sync_step_gathers_the_cohort_data_in_step():
    """The step gathers the cohort's data from the whole population's with
    the rows' index: bit-identical to a step given ``pop.cohort_data``
    through an identity index over the cohort's own rows."""
    pop = _pop(n=40)
    sim = _sim(pop, engine=True, rounds=1)
    k = 10
    cohort = np.random.default_rng(0).choice(40, k, replace=False)
    arrived = (np.arange(k) % 4 != 1).astype(np.float32)
    own_rows = jnp.asarray(np.asarray(sim.arena.data)[cohort])
    arena_a, out_a = sim.engine.sync_step(
        jnp.copy(sim.arena.data), cohort, *sim.step_data, arrived)
    arena_b, out_b = sim.engine.sync_step(
        own_rows, np.arange(k), *pop.cohort_data(cohort), arrived)
    for a, b in zip(out_a, out_b):
        _same_bits(a, b)
    _same_bits(np.asarray(arena_a)[cohort], arena_b)


def test_async_step_trains_on_the_flush_data():
    """A flush's host-gathered data and its snapshot rows passed as a
    sequence give the step's outputs bit for bit as ``pop.cohort_data`` and
    one stacked (k, N) array do."""
    pop = _pop(n=40)
    sim = _sim(pop, engine=True, rounds=1, mode="async", buffer_size=5,
               concurrency=10)
    clients = np.array([3, 17, 8, 30, 21])
    rows = [sim.arena.data[i] for i in (0, 0, 1, 0, 2)]
    got = sim.engine.async_step(rows, *sim.flush_data(clients))
    want = sim.engine.async_step(jnp.stack(rows), *pop.cohort_data(clients))
    for a, b in zip(got, want):
        _same_bits(a, b)


@pytest.mark.parametrize("server_lr", [1.0, 0.7])
@pytest.mark.parametrize("verified", [[1, 0, 1, 1, 0, 1], [0] * 6],
                         ids=["some_refused", "all_refused"])
def test_async_merge_equals_the_per_leaf_merge(server_lr, verified):
    """The flat merge entry against the legacy form, bit for bit: the
    eager staleness weights gated by the verdicts, ``weighted_delta_mean``
    over the unflattened deltas, flattened back, and the server update."""
    from repro.sim import staleness_weight, weighted_delta_mean
    sim = _sim(_pop(n=30), engine=True, rounds=1)
    layout = sim.arena.layout
    rng = np.random.default_rng(1)
    k, n = 6, layout.n_params
    local = rng.standard_normal((k, n)).astype(np.float32)
    base = rng.standard_normal((k, n)).astype(np.float32)
    glob = rng.standard_normal(n).astype(np.float32)
    staleness = np.arange(k)                       # 0..5
    verified = np.asarray(verified, np.float32)

    new, w = sim.engine.async_merge(glob, local, list(base), staleness,
                                    verified, 0.5, server_lr)

    w_ref = np.asarray(staleness_weight(staleness, 0.5), np.float32) \
        * verified
    merged = weighted_delta_mean(
        layout.unflatten(jnp.asarray(local) - jnp.asarray(base)),
        jnp.asarray(w_ref))
    row = layout.flatten(jax.tree.map(lambda x: x[None], merged))[0]
    _same_bits(w, w_ref)
    _same_bits(new, jnp.asarray(glob) + server_lr * row)


@pytest.mark.parametrize("mode", ["sync", "async"])
def test_steady_state_round_issues_no_eager_device_op(monkeypatch, mode):
    """After warm-up a sync round (an eval round among them) or a FedBuff
    flush runs no eager JAX primitive: its device work is the engine's
    entries, one ``sync_step`` a round, one ``async_step`` and one
    ``async_merge`` a flush, as the recorder counts them."""
    from jax._src import dispatch

    from repro.obs import FlightRecorder
    if mode == "sync":
        sim = _sim(_pop(n=60), engine=True, rounds=6, eval_every=2)
    else:
        sim = _sim(_pop(n=60), engine=True, rounds=8, eval_every=2,
                   mode="async", buffer_size=5, concurrency=20)
    sim.obs = FlightRecorder()
    eager, watching = [], [False]
    primitive_callable = dispatch.xla_primitive_callable

    def spy(prim, **params):
        if watching[0]:
            eager.append(prim.name)
        return primitive_callable(prim, **params)

    monkeypatch.setattr(dispatch, "xla_primitive_callable", spy)
    counters = sim.obs.metrics.counters
    if mode == "sync":
        for r in range(3):
            sim.history.append(sim._run_sync_round(r))
        before = counters.get("engine.sync_step", 0)
        watching[0] = True
        for r in range(3, 6):                       # r = 3, 5: eval rounds
            sim.history.append(sim._run_sync_round(r))
        watching[0] = False
        stepped = sum(rec.arrived.any() for rec in sim.history[3:])
        assert stepped == 3
        assert counters["engine.sync_step"] - before == stepped
    else:
        flush = sim._async_flush

        def watched(*args):
            watching[0] = len(sim.history) >= 3
            try:
                return flush(*args)
            finally:
                watching[0] = False

        monkeypatch.setattr(sim, "_async_flush", watched)
        sim._run_async()
        assert len(sim.history) == 8
        assert counters["engine.async_step"] == 8
        assert counters["engine.async_merge"] == 8
    assert eager == []
