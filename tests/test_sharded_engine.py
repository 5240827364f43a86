"""Mesh-sharded parameter arena + multi-device round engine.

The contract under test: with ``SimConfig(mesh_shards=8)`` the arena's
(n, N) matrix is row-sharded over a client-axis device mesh — each device
holds n/8 rows and the full matrix never materialises on one device — while
seeded replay (event log, block hashes, ledger balances, final accuracy)
stays BIT-identical to both the single-device engine and the legacy
``engine=False`` oracle, with the 1-compile-per-entry cache guarantee
intact.

Mesh tests need 8 devices: CI's mesh leg forces them with
``XLA_FLAGS=--xla_force_host_platform_device_count=8``; on a single-device
machine the subprocess test below self-forces the flag so the contract is
still exercised by the default (slow) suite.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.runtime.arena import ParamArena, ShardedParamArena
from repro.sim import ClientPopulation, PopulationSpec, SimConfig, SimulatedFederation

N_DEV = len(jax.devices())
mesh8 = pytest.mark.skipif(
    N_DEV < 8,
    reason="needs 8 devices (XLA_FLAGS=--xla_force_host_platform_device_count=8)")


def _pop(n=60, seed=3, **kw):
    defaults = dict(n_clients=n, dataset="synth10", beta=0.3, n_batches=1,
                    batch_size=16, straggler_frac=0.2, straggler_slowdown=8.0,
                    dropout_rate=0.05, byzantine_frac=0.1, seed=seed)
    defaults.update(kw)
    return ClientPopulation.from_spec(PopulationSpec(**defaults))


def _sim(pop, *, engine=True, mesh_shards=1, **kw):
    defaults = dict(rounds=3, sample_frac=0.25, n_clusters=3, eval_every=2,
                    seed=3, engine=engine, mesh_shards=mesh_shards)
    defaults.update(kw)
    return SimulatedFederation(pop, SimConfig(**defaults))


def _block_hashes(sim):
    return [b.block_hash() for b in sim.trainer.chain.blocks]


def _assert_replay_identical(a, ra, b, rb, *, oracle=False):
    """Full replay identity.  ``oracle=True`` compares an engine run against
    the legacy ``engine=False`` driver, whose round DISPLAY metric comes
    from a dynamically-shaped eval — one ULP of slack there, exactly as
    ``test_strategy_parity`` pins it (the protocol state — event log,
    hashes, balances, final accuracy — stays bit-exact either way)."""
    assert ra.event_log == rb.event_log
    assert _block_hashes(a) == _block_hashes(b)
    np.testing.assert_array_equal(ra.balances, rb.balances)
    assert ra.final_accuracy == rb.final_accuracy
    for x, y in zip(ra.history, rb.history):
        assert x.producer == y.producer
        assert x.reward_paid == y.reward_paid
        if oracle:
            assert x.accuracy == pytest.approx(y.accuracy, rel=1e-6,
                                               nan_ok=True)
        else:
            assert (x.accuracy == y.accuracy) or \
                (np.isnan(x.accuracy) and np.isnan(y.accuracy))


# --------------------------------------------------------------------------- #
# sharded arena unit behavior
# --------------------------------------------------------------------------- #

def test_mesh_shards_requires_engine_and_devices():
    pop = _pop(n=16)
    with pytest.raises(ValueError, match="engine"):
        _sim(pop, engine=False, mesh_shards=2)
    if N_DEV < 1000:
        with pytest.raises(ValueError, match="devices"):
            _sim(pop, mesh_shards=1000)


def test_mesh_shards_one_uses_plain_arena():
    """The default knob keeps the exact pre-mesh path: an unsharded arena
    (unsafe_buffer_pointer donation checks depend on it)."""
    sim = _sim(_pop(n=16), mesh_shards=1)
    assert type(sim.arena) is ParamArena


@mesh8
def test_sharded_arena_pads_and_roundtrips():
    """60 clients over 8 shards: rows pad to 64, each device holds 8 rows,
    and the pytree view drops the padding — bit-exact round trip."""
    from repro.launch.mesh import make_client_mesh
    pop = _pop(n=60)
    sim = _sim(pop, mesh_shards=8)
    arena = sim.arena
    assert isinstance(arena, ShardedParamArena)
    assert arena.n_clients == 60 and arena.n_padded == 64
    assert arena.per_device_bytes() * 8 == arena.data.nbytes
    assert {s.data.shape[0] for s in arena.data.addressable_shards} == {8}

    # bit-exact pytree round trip vs an unsharded arena of the same params
    ref = ParamArena.from_stacked(_sim(_pop(n=60), mesh_shards=1).params)
    np.testing.assert_array_equal(
        np.asarray(arena.data[:60]).view(np.uint32),
        np.asarray(ref.data).view(np.uint32))

    # uneven population over the mesh: 61 % 8 != 0 pads to 64 as well
    layout_tree = arena.as_pytree()
    arena61 = ShardedParamArena.from_stacked(
        jax.tree.map(lambda x: jnp.concatenate([x, x[:1]]), layout_tree),
        make_client_mesh(8))
    assert arena61.n_clients == 61 and arena61.n_padded == 64


@mesh8
def test_sharded_arena_never_materialises_on_one_device():
    """The headline memory claim: no single device ever holds the full
    (n, N) arena — shards stay at n_padded/8 rows across a round."""
    sim = _sim(_pop(n=64), mesh_shards=8)
    for r in range(2):
        sim.history.append(sim._run_sync_round(r))
    sim._finalize_history()
    shapes = {s.data.shape for s in sim.arena.data.addressable_shards}
    assert shapes == {(8, sim.arena.n_params)}


@mesh8
def test_sharded_arena_donation_reuses_every_shard():
    """Buffer donation must survive sharding: after warmup each device's
    shard buffer is updated in place, round after round."""
    pop = _pop(straggler_frac=0.0, dropout_rate=0.0)
    pop.availability[:] = 1.0
    sim = _sim(pop, mesh_shards=8, rounds=1, eval_every=0)
    sim.history.append(sim._run_sync_round(0))      # warmup (compile)
    ptrs = [s.data.unsafe_buffer_pointer()
            for s in sim.arena.data.addressable_shards]
    for r in range(1, 4):
        sim.history.append(sim._run_sync_round(r))
        now = [s.data.unsafe_buffer_pointer()
               for s in sim.arena.data.addressable_shards]
        assert now == ptrs


# --------------------------------------------------------------------------- #
# replay identity: forced-8-device mesh vs single-device engine vs oracle
# --------------------------------------------------------------------------- #

@mesh8
def test_sharded_replay_identical_sync_fast():
    """Compact 3-way sync replay (runs in the fast mesh CI leg): sharded
    mesh == single-device engine == legacy oracle, bit for bit."""
    pops = [_pop(n=40), _pop(n=40), _pop(n=40)]
    m = _sim(pops[0], mesh_shards=8)
    e = _sim(pops[1], mesh_shards=1)
    o = _sim(pops[2], engine=False)
    rm, re_, ro = m.run(), e.run(), o.run()
    _assert_replay_identical(m, rm, e, re_)
    _assert_replay_identical(m, rm, o, ro, oracle=True)
    assert any(not r.arrived.all() for r in rm.history), \
        "replay should cover rounds with missing arrivals"


@mesh8
@pytest.mark.slow
def test_sharded_replay_identical_sync_full():
    """Full sync replay with straggler/dropout/Byzantine dynamics and
    per-round eval across 5 rounds."""
    a = _sim(_pop(), mesh_shards=8, rounds=5, eval_every=1)
    b = _sim(_pop(), mesh_shards=1, rounds=5, eval_every=1)
    _assert_replay_identical(a, a.run(), b, b.run())


@mesh8
@pytest.mark.slow
def test_sharded_replay_identical_async():
    kw = dict(mode="async", buffer_size=6, concurrency=12, rounds=4)
    a = _sim(_pop(), mesh_shards=8, **kw)
    b = _sim(_pop(), mesh_shards=1, **kw)
    c = _sim(_pop(), engine=False, **kw)
    ra, rb, rc = a.run(), b.run(), c.run()
    _assert_replay_identical(a, ra, b, rb)
    _assert_replay_identical(a, ra, c, rc)
    assert any(r.staleness_mean > 0 for r in ra.history)


STRATEGIES = ("bfln", "fedavg", "fedprox", "fedproto", "fedhkd")


@mesh8
@pytest.mark.parametrize("strategy", STRATEGIES)
def test_sharded_replay_per_strategy_sync(strategy):
    """Every registered strategy replays bit-identically under cohort
    sharding: its shard-local partial + deterministic combine must compose
    to the exact single-device aggregation (mesh8 == mesh1 == oracle)."""
    kw = dict(rounds=2, strategy=strategy)
    m = _sim(_pop(n=32), mesh_shards=8, **kw)
    e = _sim(_pop(n=32), mesh_shards=1, **kw)
    o = _sim(_pop(n=32), engine=False, **kw)
    rm, re_, ro = m.run(), e.run(), o.run()
    _assert_replay_identical(m, rm, e, re_)
    _assert_replay_identical(m, rm, o, ro, oracle=True)


@mesh8
@pytest.mark.parametrize("strategy", STRATEGIES)
def test_sharded_replay_per_strategy_async(strategy):
    """FedBuff flushes under cohort sharding: the sharded async_step's
    local updates and fingerprints replay bit-identically per strategy."""
    kw = dict(mode="async", buffer_size=4, concurrency=8, rounds=2,
              strategy=strategy)
    a = _sim(_pop(n=32), mesh_shards=8, **kw)
    b = _sim(_pop(n=32), mesh_shards=1, **kw)
    _assert_replay_identical(a, a.run(), b, b.run())


@mesh8
def test_replicated_cohort_mode_still_bit_identical():
    """The ``mesh_cohort='replicated'`` escape hatch (pre-shard behaviour:
    whole cohort gathered to every device) keeps full replay identity."""
    a = _sim(_pop(n=40), mesh_shards=8, mesh_cohort="replicated")
    b = _sim(_pop(n=40), mesh_shards=1)
    assert a.engine.cohort_mode == "replicated"
    _assert_replay_identical(a, a.run(), b, b.run())


@mesh8
def test_sharded_empty_rounds_identical_and_blockless():
    """Nobody beats the deadline on the mesh either: no block minted, arena
    untouched, engine never compiled."""
    def make():
        pop = _pop(n=32, straggler_frac=0.0, dropout_rate=0.0)
        pop.latency.speed[:] = 1e9          # everyone misses every deadline
        return pop
    a = _sim(make(), mesh_shards=8, rounds=2, eval_every=0)
    b = _sim(make(), mesh_shards=1, rounds=2, eval_every=0)
    ra, rb = a.run(), b.run()
    assert ra.event_log == rb.event_log
    assert all(not r.arrived.any() for r in ra.history)
    assert len(a.trainer.chain.blocks) == 1          # genesis only
    assert _block_hashes(a) == _block_hashes(b)
    np.testing.assert_array_equal(ra.balances,
                                  np.full(32, a.cfg.initial_stake))
    assert a.engine.cache_sizes()["sync_step"] == 0


@mesh8
def test_sharded_zero_arrival_cluster_matches_single_device():
    """A cluster whose members all miss the deadline aggregates identically
    on the mesh: weight-zero mean, members keep their old (sharded) rows."""
    pop = _pop(n=40, straggler_frac=0.0, dropout_rate=0.0, byzantine_frac=0.0)
    k = 12
    cohort_idx = np.arange(0, 40, 40 // k)[:k]

    # discover the round's labels (mask-independent), then craft an arrival
    # mask that leaves one whole cluster empty
    probe = _sim(pop, mesh_shards=8, rounds=1)
    _, probe_out = probe.engine.sync_step(
        probe.arena.data, cohort_idx, *probe.step_data, np.ones(k, np.float32))
    labels = np.asarray(probe_out.labels)
    mask = labels != labels[0]
    assert mask.any() and not mask.all()

    a = _sim(pop, mesh_shards=8, rounds=1)
    b = _sim(pop, mesh_shards=1, rounds=1)
    arrived_w = mask.astype(np.float32)
    da, oa = a.engine.sync_step(a.arena.data, cohort_idx, *a.step_data,
                                arrived_w)
    db, ob = b.engine.sync_step(b.arena.data, cohort_idx, *b.step_data,
                                arrived_w)
    np.testing.assert_array_equal(np.asarray(oa.labels), np.asarray(ob.labels))
    np.testing.assert_array_equal(
        np.asarray(oa.new_rows).view(np.uint32),
        np.asarray(ob.new_rows).view(np.uint32))
    np.testing.assert_array_equal(
        np.asarray(oa.residues), np.asarray(ob.residues))
    # full arena parity (padding rows excluded)
    np.testing.assert_array_equal(
        np.asarray(da[: a.arena.n_clients]).view(np.uint32),
        np.asarray(db).view(np.uint32))


@mesh8
def test_sharded_steps_gather_the_cohort_data_in_step():
    """On the mesh the population's data is row-sharded like the arena and
    the step gathers the cohort's slice itself: the outputs equal, bit for
    bit, a single-device step given ``pop.cohort_data`` through an identity
    index over the cohort's own rows.  The mesh's FedBuff step gives the
    same bits on the host-gathered flush data and the snapshot rows as a
    sequence as on ``pop.cohort_data`` and one stacked array."""
    pop = _pop(n=40, straggler_frac=0.0, dropout_rate=0.0, byzantine_frac=0.0)
    a = _sim(pop, mesh_shards=8, rounds=1)
    b = _sim(pop, mesh_shards=1, rounds=1)
    assert len(a.step_data[0].sharding.device_set) == 8
    k = 12
    cohort = np.random.default_rng(0).choice(40, k, replace=False)
    arrived = (np.arange(k) % 5 != 2).astype(np.float32)
    own_rows = jnp.asarray(np.asarray(b.arena.data)[cohort])
    da, oa = a.engine.sync_step(a.arena.data, cohort, *a.step_data, arrived)
    db, ob = b.engine.sync_step(own_rows, np.arange(k),
                                *pop.cohort_data(cohort), arrived)
    # the Pearson matrix (CACC's input only) is not part of the mesh replay
    # contract: its combine rounds differently on the mesh, as before
    for name in ("labels", "residues", "mean_loss", "new_rows"):
        assert np.asarray(getattr(oa, name)).tobytes() \
            == np.asarray(getattr(ob, name)).tobytes(), name
    assert np.asarray(da)[cohort].tobytes() == np.asarray(db).tobytes()

    clients = cohort[:5]
    rows = [b.arena.data[i] for i in (0, 0, 1, 0, 2)]
    got = a.engine.async_step(rows, *a.flush_data(clients))
    want = a.engine.async_step(jnp.stack(rows), *pop.cohort_data(clients))
    for x, y in zip(got, want):
        assert np.asarray(x).tobytes() == np.asarray(y).tobytes()


@mesh8
def test_sharded_cache_sizes_one_compile_per_entry():
    """The 1-compile-per-entry guarantee survives sharding: varying arrival
    counts never retrace any mesh-mode entry."""
    sim = _sim(_pop(straggler_frac=0.3), mesh_shards=8, rounds=5, eval_every=1)
    rep = sim.run()
    counts = {int(r.arrived.sum()) for r in rep.history}
    assert len(counts) > 1, "population should produce varying arrival counts"
    sizes = sim.engine.cache_sizes()
    assert sizes["sync_step"] == 1, sizes
    assert sizes["eval_cohort"] == 1, sizes
    assert sizes["eval_population"] == 1, sizes


# --------------------------------------------------------------------------- #
# single-device environments: self-forcing subprocess replay gate
# --------------------------------------------------------------------------- #

_SUBPROCESS_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                           "--xla_cpu_multi_thread_eigen=false")
import numpy as np
from repro.sim import (ClientPopulation, PopulationSpec, SimConfig,
                       SimulatedFederation)

def pop():
    return ClientPopulation.from_spec(PopulationSpec(
        n_clients=40, dataset="synth10", beta=0.3, n_batches=1, batch_size=16,
        straggler_frac=0.2, straggler_slowdown=8.0, dropout_rate=0.05,
        byzantine_frac=0.1, seed=3))

def run(shards):
    cfg = SimConfig(rounds=3, sample_frac=0.25, n_clusters=3, eval_every=2,
                    seed=3, engine=True, mesh_shards=shards)
    sim = SimulatedFederation(pop(), cfg)
    return sim, sim.run()

a, ra = run(8)
b, rb = run(1)
assert isinstance(a.arena.per_device_bytes(), int)
assert a.arena.per_device_bytes() * 8 == a.arena.data.nbytes
assert ra.event_log == rb.event_log
assert [x.block_hash() for x in a.trainer.chain.blocks] == \
       [x.block_hash() for x in b.trainer.chain.blocks]
assert np.array_equal(ra.balances, rb.balances)
assert ra.final_accuracy == rb.final_accuracy
sizes = a.engine.cache_sizes()
assert sizes["sync_step"] == 1 and sizes["eval_cohort"] == 1, sizes
print("SHARDED_REPLAY_OK")
"""


@pytest.mark.slow
@pytest.mark.skipif(N_DEV >= 8, reason="covered in-process by the mesh tests")
def test_sharded_replay_via_forced_devices_subprocess():
    """On a single-device machine, force an 8-device CPU platform in a
    subprocess (XLA_FLAGS must be set before jax initialises) and assert the
    sharded-vs-single-device replay gate there."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    src = os.path.join(os.path.dirname(__file__), os.pardir, "src")
    env["PYTHONPATH"] = os.path.abspath(src) + os.pathsep + \
        env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", _SUBPROCESS_SCRIPT],
                         capture_output=True, text=True, env=env, timeout=900)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "SHARDED_REPLAY_OK" in out.stdout
