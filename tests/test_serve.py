"""The serving tier (`repro.serve`): snapshot provenance, the refuse-to-serve
gate, mixed-batch single-dispatch bit-identity, and frontend determinism."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.api as api
from repro.blockchain.commit import (
    MerkleProof,
    RoundCommitments,
    verify_membership,
)
from repro.models import classifier as clf
from repro.obs import FlightRecorder, validate_record
from repro.serve import (
    Completion,
    ModelBank,
    ProvenanceError,
    ServeConfig,
    ServeFrontend,
    ServingEngine,
    latest_release,
    load_bank,
    publish_release,
    serve,
    snapshot,
    tampered,
    verify_bank,
)
from repro.sim.clock import VirtualClock


@pytest.fixture(scope="module")
def result():
    spec = api.ExperimentSpec(
        data=api.DataSpec(n_clients=40),
        train=api.TrainSpec(rounds=2, sample_frac=0.3, n_clusters=3),
        eval=api.EvalSpec(every=0, clients=16, examples=64))
    return api.run(spec)


@pytest.fixture(scope="module")
def bank(result):
    return snapshot(result)


@pytest.fixture(scope="module")
def chain(result):
    return result.sim.trainer.chain


# --------------------------------------------------------------------- #
# snapshot
# --------------------------------------------------------------------- #

def test_snapshot_shapes_release_and_chain(result, bank, chain):
    K = result.spec.train.n_clusters
    assert bank.data.shape == (K, bank.layout.n_params)
    assert len(bank.releases) == K
    assert len(set(bank.digests())) >= 1
    # the release block is the chain head and the chain still validates
    head, rc = latest_release(chain)
    assert head is chain.blocks[-1]
    assert head.block_hash() == bank.block_hash
    assert rc.root == bank.root
    assert head.round_idx == bank.round_idx > result.spec.train.rounds - 1
    assert chain.validate()


def test_snapshot_models_are_cluster_means(result, bank):
    sim = result.sim
    rows = np.asarray(jax.device_get(sim.arena.data))[: sim.pop.n_clients]
    labels = np.asarray(sim.last_labels)
    for c in range(bank.n_models):
        members = rows[labels == c]
        if len(members):
            want = members.mean(axis=0)
            np.testing.assert_allclose(np.asarray(bank.data[c]), want,
                                       rtol=1e-6, atol=1e-7)


def test_snapshot_accepts_result_or_sim(result):
    a = snapshot(result, publish=False, verify=False)
    b = snapshot(result.sim, publish=False, verify=False)
    assert np.array_equal(np.asarray(a.data), np.asarray(b.data))
    with pytest.raises(ValueError):
        snapshot(object())


def test_verify_bank_passes_on_fresh_snapshot(bank, chain):
    verify_bank(bank, chain)    # must not raise


# --------------------------------------------------------------------- #
# the refuse-to-serve gate
# --------------------------------------------------------------------- #

def test_tampered_weights_refused_end_to_end(bank, chain):
    bad = tampered(bank, 1)
    with pytest.raises(ProvenanceError, match="fingerprint"):
        ServingEngine(bad, chain)


def test_tampered_digest_refused(bank, chain):
    releases = list(bank.releases)
    releases[0] = dataclasses.replace(releases[0], digest="0" * 40)
    bad = dataclasses.replace(bank, releases=tuple(releases))
    with pytest.raises(ProvenanceError):
        ServingEngine(bad, chain)


def test_wrong_round_refused(bank, chain):
    bad = dataclasses.replace(bank, round_idx=bank.round_idx - 1)
    with pytest.raises(ProvenanceError):
        ServingEngine(bad, chain)


def test_stale_release_refused(result, bank, chain):
    # mint a NEWER release of the same digests: the old bank must refuse
    sim = result.sim
    block, _ = publish_release(chain, sim.trainer.pool, bank.digests())
    try:
        with pytest.raises(ProvenanceError, match="stale"):
            ServingEngine(bank, chain)
        fresh = snapshot(result, publish=False)     # re-anchors on the head
        ServingEngine(fresh, chain)
    finally:
        # restore the fixture bank as the latest release for later tests
        chain.blocks.pop()
        assert chain.validate()


def test_engine_requires_chain_unless_opted_out(bank):
    with pytest.raises(ProvenanceError):
        ServingEngine(bank, None)
    ServingEngine(bank, None, verify=False)     # probe escape hatch


def test_unpublished_chain_refuses(result):
    # a run whose chain carries no release: snapshot(publish=False) refuses
    spec = api.ExperimentSpec(
        data=api.DataSpec(n_clients=20),
        train=api.TrainSpec(rounds=1, sample_frac=0.4, n_clusters=2),
        eval=api.EvalSpec(every=0, clients=8, examples=32))
    res = api.run(spec)
    with pytest.raises(ProvenanceError, match="no model release"):
        snapshot(res, publish=False)


# --------------------------------------------------------------------- #
# verify_membership negative paths, as serving uses them
# --------------------------------------------------------------------- #

def test_membership_negative_paths(bank):
    rc = RoundCommitments(bank.round_idx, tuple(enumerate(bank.digests())))
    digest = bank.releases[1].digest
    proof = rc.proof(1)
    assert verify_membership(rc.root, 1, bank.round_idx, digest, proof)
    # tampered digest
    assert not verify_membership(rc.root, 1, bank.round_idx, "f" * 40, proof)
    # wrong sender (another cluster claiming this model)
    assert not verify_membership(rc.root, 2, bank.round_idx, digest, proof)
    # wrong round (release leaf replayed into another round)
    assert not verify_membership(rc.root, 1, bank.round_idx + 1, digest,
                                 proof)
    # stale root (proof against a superseded release's root)
    rc2 = RoundCommitments(bank.round_idx + 1,
                           tuple(enumerate(bank.digests())))
    assert not verify_membership(rc2.root, 1, bank.round_idx, digest, proof)
    # forged proof path
    forged = MerkleProof(proof.leaf, tuple(("0" * 64, side)
                                           for _, side in proof.path))
    assert not verify_membership(rc.root, 1, bank.round_idx, digest, forged)


# --------------------------------------------------------------------- #
# engine: one dispatch, bit-identical routing
# --------------------------------------------------------------------- #

def test_mixed_batch_one_dispatch_bitwise_per_request(bank, chain):
    eng = ServingEngine(bank, chain)
    rng = np.random.default_rng(1)
    x = rng.standard_normal((8, bank.mcfg.in_dim)).astype(np.float32)
    cids = np.array([0, 1, 2, 0, 2, 1, 0, 2], dtype=np.int32)
    out = eng.forward(x, cids)
    assert out.shape == (8, bank.mcfg.num_classes)
    assert eng.cache_sizes() == {"forward": 1}
    # same shape, different values/routing: the compile count stays pinned
    eng.forward(x + 1.0, cids[::-1].copy())
    assert eng.cache_sizes() == {"forward": 1}
    # a second batch shape compiles exactly once more
    eng.forward(x[:4], cids[:4])
    assert eng.cache_sizes() == {"forward": 2}
    # acceptance: per-request outputs bit-identical to routing each request
    # to its cluster model individually
    oracle = eng.forward_per_request(x, cids)
    assert bool(jnp.all(out.view(jnp.int32) == oracle.view(jnp.int32)))
    # and to the plain single-model forward per cluster
    for c in range(bank.n_models):
        rows = np.flatnonzero(cids == c)
        ref = clf.apply(bank.mcfg, bank.model_pytree(c), jnp.asarray(x))
        assert np.array_equal(np.asarray(out)[rows], np.asarray(ref)[rows])


def test_request_output_independent_of_batch_routing(bank, chain):
    # each row's logits depend only on its own (x, cid) — not on how the
    # rest of the batch routes: uniform-cid batches must reproduce the
    # mixed batch's rows bitwise
    eng = ServingEngine(bank, chain)
    rng = np.random.default_rng(2)
    x = rng.standard_normal((6, bank.mcfg.in_dim)).astype(np.float32)
    cids = np.array([2, 0, 1, 1, 0, 2], dtype=np.int32)
    mixed = np.asarray(eng.forward(x, cids))
    for c in range(bank.n_models):
        uniform = np.asarray(eng.forward(x, np.full(6, c, np.int32)))
        rows = np.flatnonzero(cids == c)
        assert np.array_equal(mixed[rows], uniform[rows])


# --------------------------------------------------------------------- #
# frontend: deterministic bucketing, deadline, rejection, replay
# --------------------------------------------------------------------- #

def _drive(engine, schedule, *, config):
    """Replay a (t_arrival, cluster_id, x) schedule on a fresh virtual
    clock; returns the completions plus the flush count."""
    clock = VirtualClock()
    fe = ServeFrontend(engine, config, clock=clock)
    for t, cid, x in schedule:
        clock.advance_to(t)
        fe.pump()
        fe.submit(cid, x)
    clock.advance_to(schedule[-1][0] + 10 * config.max_wait)
    fe.pump()
    fe.drain()
    return fe.take_completed(), fe.n_flushes, fe


def test_frontend_replay_bit_identical(bank, chain):
    eng = ServingEngine(bank, chain)
    rng = np.random.default_rng(3)
    schedule = [(0.001 * i, int(i % 3),
                 rng.standard_normal(bank.mcfg.in_dim).astype(np.float32))
                for i in range(23)]
    cfg = ServeConfig(buckets=(1, 2, 4, 8), max_wait=0.004)
    a, flushes_a, _ = _drive(eng, schedule, config=cfg)
    b, flushes_b, _ = _drive(eng, schedule, config=cfg)
    assert flushes_a == flushes_b
    assert [c.req_id for c in a] == [c.req_id for c in b]
    assert [c.status for c in a] == [c.status for c in b]
    for ca, cb in zip(a, b):
        assert np.array_equal(ca.logits, cb.logits)
    # every request answered, and answered correctly
    assert sorted(c.req_id for c in a) == list(range(23))
    oracle = eng.forward_per_request(
        np.stack([x for _, _, x in schedule]),
        [cid for _, cid, _ in schedule])
    by_id = {c.req_id: c for c in a}
    for i in range(23):
        assert np.array_equal(by_id[i].logits, np.asarray(oracle[i]))


def test_frontend_full_bucket_flushes_inside_submit(bank, chain):
    eng = ServingEngine(bank, chain)
    fe = ServeFrontend(eng, ServeConfig(buckets=(4,), max_wait=1e9),
                       clock=VirtualClock())
    x = np.zeros(bank.mcfg.in_dim, np.float32)
    for i in range(3):
        fe.submit(i % 3, x)
    assert fe.queue_depth == 3 and fe.n_flushes == 0
    fe.submit(0, x)
    assert fe.queue_depth == 0 and fe.n_flushes == 1
    assert [c.status for c in fe.take_completed()] == ["ok"] * 4


def test_frontend_deadline_pads_to_bucket(bank, chain):
    eng = ServingEngine(bank, chain)
    clock = VirtualClock()
    fe = ServeFrontend(eng, ServeConfig(buckets=(8,), max_wait=0.5),
                       clock=clock)
    rng = np.random.default_rng(4)
    x = rng.standard_normal((3, bank.mcfg.in_dim)).astype(np.float32)
    for i in range(3):
        fe.submit(i, x[i])
    fe.pump()
    assert fe.n_flushes == 0            # deadline not reached
    clock.advance_to(1.0)
    fe.pump()
    assert fe.n_flushes == 1            # padded 3 -> bucket 8
    done = fe.take_completed()
    assert len(done) == 3
    oracle = eng.forward_per_request(x, [0, 1, 2])
    for i, c in enumerate(done):
        assert np.array_equal(c.logits, np.asarray(oracle[i]))
        assert c.t_done >= c.t_arrival


def test_frontend_overload_rejects_gracefully(bank, chain):
    eng = ServingEngine(bank, chain)
    fe = ServeFrontend(eng, ServeConfig(buckets=(8,), max_wait=1e9,
                                        max_pending=4),
                       clock=VirtualClock())
    x = np.zeros(bank.mcfg.in_dim, np.float32)
    for i in range(6):
        fe.submit(0, x)
    done = fe.take_completed()
    assert [c.status for c in done] == ["rejected"] * 2
    assert all(c.logits is None for c in done)
    assert fe.n_rejected == 2 and fe.queue_depth == 4
    fe.drain()
    assert [c.status for c in fe.take_completed()] == ["ok"] * 4


def test_frontend_validates_requests(bank, chain):
    eng = ServingEngine(bank, chain)
    fe = ServeFrontend(eng, clock=VirtualClock())
    with pytest.raises(ValueError, match="features"):
        fe.submit(0, np.zeros(bank.mcfg.in_dim + 1, np.float32))
    with pytest.raises(ValueError, match="cluster_id"):
        fe.submit(bank.n_models, np.zeros(bank.mcfg.in_dim, np.float32))
    with pytest.raises(ValueError, match="clock"):
        ServeFrontend(eng, clock=None)
    with pytest.raises(ValueError):
        ServeConfig(buckets=(4, 2))


# --------------------------------------------------------------------- #
# bank disk round-trip
# --------------------------------------------------------------------- #

def test_bank_save_load_roundtrip_and_tamper(tmp_path, result, bank, chain):
    path = str(tmp_path / "bank.npz")
    bank.save(path)
    loaded = load_bank(path, chain)     # verifies against the chain
    assert np.array_equal(np.asarray(loaded.data), np.asarray(bank.data))
    assert loaded.digests() == bank.digests()
    assert loaded.mcfg == bank.mcfg
    assert loaded.layout.paths == bank.layout.paths
    # the loaded bank serves identically
    eng = ServingEngine(loaded, chain)
    x = np.ones((2, bank.mcfg.in_dim), np.float32)
    ref = ServingEngine(bank, chain).forward(x, [0, 1])
    assert np.array_equal(np.asarray(eng.forward(x, [0, 1])),
                          np.asarray(ref))
    # tamper the saved weights: load refuses
    evil = tampered(loaded, 0)
    evil_path = str(tmp_path / "evil.npz")
    evil.save(evil_path)
    with pytest.raises(ProvenanceError):
        load_bank(evil_path, chain)
    # loading without a chain defers verification — the engine still refuses
    unverified = load_bank(evil_path)
    with pytest.raises(ProvenanceError):
        ServingEngine(unverified, chain)


# --------------------------------------------------------------------- #
# api entry point + observability
# --------------------------------------------------------------------- #

def test_api_serve_entry_point(result):
    fe = serve(result)
    assert isinstance(fe, ServeFrontend)
    x = np.zeros(result.sim.mcfg.in_dim, np.float32)
    rid = fe.submit(1, x)
    fe.drain()
    done = fe.take_completed()
    assert [c.req_id for c in done] == [rid]
    assert done[0].status == "ok" and done[0].cluster_id == 1
    # api.run left the release of test order unchanged: serve() published a
    # new head release, keep the module chain consistent for other tests
    result.sim.trainer.chain.blocks.pop()


def test_serve_records_validate_against_trace_schema(result, bank):
    rec = FlightRecorder(api.ObsSpec(enabled=True))
    sim = result.sim
    b = snapshot(result, obs=rec)
    eng = ServingEngine(b, sim.trainer.chain, obs=rec)
    fe = ServeFrontend(eng, ServeConfig(buckets=(2,), max_wait=0.1),
                       clock=VirtualClock(), obs=rec)
    x = np.zeros(bank.mcfg.in_dim, np.float32)
    fe.submit(0, x)
    fe.submit(1, x)
    fe.drain()
    names = {r["name"] for r in rec.records}
    assert {"serve.snapshot", "serve.verify", "serve.batch",
            "serve.flush"} <= names
    for r in rec.records:
        validate_record(r)
    assert rec.metrics.counters["serve.requests"] == 2
    assert rec.metrics.counters["serve.batches"] >= 1
    assert "serve.latency" in rec.metrics.summaries
    sim.trainer.chain.blocks.pop()      # drop the traced snapshot's release
    assert isinstance(fe.take_completed()[0], Completion)


@pytest.mark.parametrize("arrivals, flush_at", [
    ((0.0, 0.2, 0.3), 1.0),
    ((0.5,), 0.75),
])
def test_frontend_flush_records_queue_waits(bank, chain, arrivals, flush_at):
    """``serve.flush`` carries the batch's queue waits on the frontend's
    clock, from each arrival to the flush's start, and its pack, dispatch
    and readback are its children."""
    rec = FlightRecorder(api.ObsSpec(enabled=True))
    eng = ServingEngine(bank, chain, obs=rec)
    clock = VirtualClock()
    fe = ServeFrontend(eng, ServeConfig(buckets=(8,), max_wait=0.25),
                       clock=clock, obs=rec)
    x = np.zeros(bank.mcfg.in_dim, np.float32)
    for t in arrivals:
        clock.advance_to(t)
        fe.submit(0, x)
    clock.advance_to(flush_at)
    fe.pump()
    (flush,) = [r for r in rec.records if r["name"] == "serve.flush"]
    waits = [(flush_at - t) * 1e6 for t in arrivals]
    assert flush["attrs"]["wait_sum_us"] == pytest.approx(sum(waits))
    assert flush["attrs"]["wait_max_us"] == pytest.approx(max(waits))
    assert flush["attrs"]["n"] == len(arrivals)
    children = [r["name"] for r in rec.records
                if r["kind"] == "span" and r["parent"] == flush["id"]]
    assert children == ["serve.pack", "serve.batch", "serve.readback"]


def test_bank_types(bank):
    assert isinstance(bank, ModelBank)
    assert bank.nbytes == bank.data.size * 4
    tree = bank.model_pytree(0)
    flat = bank.layout.flatten(jax.tree.map(lambda p: p[None], tree))
    assert np.array_equal(np.asarray(flat[0]), np.asarray(bank.data[0]))
