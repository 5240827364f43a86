"""Tracing must be out-of-band: replay with the flight recorder ON is
bit-identical to replay with it OFF.

The invariant pinned here (the tentpole's hard contract): for the same spec
and seed, trace on vs. trace off produces identical event logs, block
hashes, ledger balances and final accuracy — observability times and
counts, it never perturbs.  Verified for sync and async modes, the legacy
``engine=False`` driver, and the mesh-sharded engine (in-process at 8
devices, else via a self-forcing subprocess).  The traced run's artifact is
also checked end to end: every JSONL record validates against the schema,
the manifest's ``trace_digest`` matches the file's sha256, and the manifest
carries the timing readout.
"""
import os
import subprocess
import sys

import jax
import pytest

import repro.api as api
from repro.obs import file_sha256, validate_trace_lines

N_DEV = len(jax.devices())
mesh8 = pytest.mark.skipif(
    N_DEV < 8,
    reason="needs 8 devices (XLA_FLAGS=--xla_force_host_platform_device_count=8)")


def _spec(*, mode="sync", engine=True, mesh_shards=1, obs=None,
          seed=3) -> api.ExperimentSpec:
    return api.ExperimentSpec(
        data=api.DataSpec(n_clients=40, dataset="synth10", beta=0.3,
                          n_batches=1, batch_size=16, straggler_frac=0.2,
                          straggler_slowdown=8.0, dropout_rate=0.05,
                          byzantine_frac=0.1),
        train=api.TrainSpec(rounds=3, sample_frac=0.25, n_clusters=3,
                            local_epochs=1, mode=mode),
        async_=api.AsyncSpec(buffer_size=6, concurrency=12),
        eval=api.EvalSpec(every=2, clients=16, examples=64),
        mesh=api.MeshSpec(shards=mesh_shards),
        obs=obs if obs is not None else api.ObsSpec(),
        engine=engine, seed=seed)


REPLAY_KEYS = ("event_log_digest", "block_hashes_digest", "balances_digest",
               "final_accuracy")


def _assert_traced_replay_identical(tmp_path, *, mode, engine,
                                    mesh_shards=1):
    trace = str(tmp_path / f"{mode}_{engine}_{mesh_shards}.jsonl")
    on = api.run(_spec(mode=mode, engine=engine, mesh_shards=mesh_shards,
                       obs=api.ObsSpec(enabled=True, trace_path=trace)))
    off = api.run(_spec(mode=mode, engine=engine, mesh_shards=mesh_shards))

    # the hard invariant: identical replay with tracing on vs. off
    for key in REPLAY_KEYS:
        assert on.manifest[key] == off.manifest[key], key
    assert on.spec.config_digest() == off.spec.config_digest()

    # the traced artifact is complete and digest-stamped
    assert on.manifest["trace_path"] == trace
    assert on.manifest["trace_digest"] == file_sha256(trace)
    counts = validate_trace_lines(open(trace).read().splitlines())
    assert counts["span"] > 0 and counts["summary"] > 0
    timing = on.manifest["timing"]
    assert timing["rounds"] == len(on.report.history)
    assert "round_ms_p50" in timing
    # the one-line readout surfaces the timing
    assert "timing:" in on.summary() and "compiles=" in on.summary()
    return on


@pytest.mark.parametrize("mode", ["sync", "async"])
def test_traced_replay_identical_engine(tmp_path, mode):
    _assert_traced_replay_identical(tmp_path, mode=mode, engine=True)


@pytest.mark.slow
@pytest.mark.parametrize("mode", ["sync", "async"])
def test_traced_replay_identical_legacy(tmp_path, mode):
    _assert_traced_replay_identical(tmp_path, mode=mode, engine=False)


def test_trace_records_chain_and_phase_spans(tmp_path):
    res = _assert_traced_replay_identical(tmp_path, mode="sync", engine=True)
    import json
    names = set()
    for line in open(res.manifest["trace_path"]):
        rec = json.loads(line)
        if rec["kind"] == "span":
            names.add(rec["name"])
    assert {"round.total", "round.sample", "round.step", "round.chain",
            "chain.pack", "chain.verify", "run.final_eval"} <= names


def _span_records(path) -> list[dict]:
    import json
    return [r for r in map(json.loads, open(path)) if r["kind"] == "span"]


@pytest.mark.parametrize("mode, outer, children", [
    ("sync", "round.total", ("round.schedule", "round.record")),
    ("async", "flush.total", ("flush.prepare", "flush.record")),
])
def test_host_work_spans_nest_once_per_round(tmp_path, mode, outer,
                                             children):
    """The host work between the older spans has spans of its own: once
    per round or flush, each a child of its ``round.total`` or
    ``flush.total``; FedBuff's ``dispatch()`` calls run in the event loop,
    outside any flush."""
    trace = str(tmp_path / f"{mode}.jsonl")
    api.run(_spec(mode=mode, obs=api.ObsSpec(enabled=True,
                                             trace_path=trace)))
    spans = _span_records(trace)
    outers = {r["id"] for r in spans if r["name"] == outer}
    assert outers
    for name in children:
        parents = [r["parent"] for r in spans if r["name"] == name]
        assert sorted(parents) == sorted(outers), name
    if mode == "async":
        dispatch = [r for r in spans if r["name"] == "async.dispatch"]
        assert len(dispatch) > len(outers)
        assert all(r["parent"] is None for r in dispatch)


@pytest.mark.parametrize("strategy", ["bfln", "fedavg"])
def test_named_scopes_change_no_bits(monkeypatch, strategy):
    """The step's named scopes are HLO metadata only: the same run with
    every ``jax.named_scope`` made a no-op replays bit for bit."""
    import contextlib
    import dataclasses

    def spec():
        s = _spec(mode="sync")
        return dataclasses.replace(
            s, train=dataclasses.replace(s.train, strategy=strategy))

    scoped = api.run(spec())
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = api.run(spec())
    for key in REPLAY_KEYS:
        assert scoped.manifest[key] == plain.manifest[key], key


@mesh8
@pytest.mark.parametrize("mode", ["sync", "async"])
def test_traced_replay_identical_mesh8(tmp_path, mode):
    """Tracing stays out of band under cohort sharding too — sync rounds
    AND FedBuff flushes through the sharded step, traced vs untraced,
    replay bit-identical (the `round.step`/`flush.step` spans additionally
    carry `shards`/`cohort_mode` attrs; schema in docs/TRACE_SCHEMA.md)."""
    res = _assert_traced_replay_identical(tmp_path, mode=mode, engine=True,
                                          mesh_shards=8)
    import json
    step = "round.step" if mode == "sync" else "flush.step"
    attrs = [rec.get("attrs", {})
             for rec in map(json.loads, open(res.manifest["trace_path"]))
             if rec["kind"] == "span" and rec["name"] == step]
    assert attrs and all(a.get("shards") == 8 and
                         a.get("cohort_mode") == "sharded" for a in attrs)


# --------------------------------------------------------------------------- #
# single-device environments: self-forcing subprocess mesh gate
# --------------------------------------------------------------------------- #

_SUBPROCESS_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                           "--xla_cpu_multi_thread_eigen=false")
import repro.api as api
from repro.obs import file_sha256, validate_trace_lines

def spec(obs):
    return api.ExperimentSpec(
        data=api.DataSpec(n_clients=40, dataset="synth10", beta=0.3,
                          n_batches=1, batch_size=16, straggler_frac=0.2,
                          straggler_slowdown=8.0, dropout_rate=0.05,
                          byzantine_frac=0.1),
        train=api.TrainSpec(rounds=3, sample_frac=0.25, n_clusters=3,
                            local_epochs=1),
        eval=api.EvalSpec(every=2, clients=16, examples=64),
        mesh=api.MeshSpec(shards=8), obs=obs, engine=True, seed=3)

on = api.run(spec(api.ObsSpec(enabled=True, trace_path="mesh_trace.jsonl")))
off = api.run(spec(api.ObsSpec()))
for key in ("event_log_digest", "block_hashes_digest", "balances_digest",
            "final_accuracy"):
    assert on.manifest[key] == off.manifest[key], key
assert on.manifest["trace_digest"] == file_sha256("mesh_trace.jsonl")
validate_trace_lines(open("mesh_trace.jsonl").read().splitlines())
print("MESH_TRACE_REPLAY_OK")
"""


@pytest.mark.slow
@pytest.mark.skipif(N_DEV >= 8, reason="covered in-process by the mesh8 test")
def test_traced_mesh_replay_via_forced_devices_subprocess(tmp_path):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    src = os.path.join(os.path.dirname(__file__), os.pardir, "src")
    env["PYTHONPATH"] = os.path.abspath(src) + os.pathsep + \
        env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", _SUBPROCESS_SCRIPT],
                         capture_output=True, text=True, env=env,
                         cwd=str(tmp_path), timeout=900)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "MESH_TRACE_REPLAY_OK" in out.stdout
