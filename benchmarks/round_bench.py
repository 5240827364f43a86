"""Sync-round hot path: legacy (pre-arena) driver vs the fused round engine.

Measures steady-state sync-round latency at population scale two ways on the
same seeded population:

  * ``engine=False`` — the retired hot path: eager per-leaf cohort gather,
    jitted train+PAA, a second jitted fingerprint pipeline, per-leaf scatter
    that reallocates the full (n_clients, N_params) stack every round, and a
    ``global_evaluate`` that jit-recompiles for every distinct arrived-client
    count;
  * ``engine=True`` — ONE donated fixed-shape jitted step per round
    (`repro.core.engine`): arena gather → train → PAA → digests → masked
    scatter-back, plus fixed-shape masked eval whose outputs stay on device
    until end of run.

The headline config evaluates every round on a 256-example shared test
slice: the eval recompile pathology this PR kills is *count*-dependent (one
compile per distinct arrival count), not eval-size-dependent, and a larger
metric batch only adds identical GEMM time to both paths, drowning the
round being measured.  A heavy-eval variant (the SimConfig default 1024
examples) is measured and reported alongside.

A third variant — ``mesh_shards=8`` — runs the SAME fused engine with the
parameter arena row-sharded over an 8-device client mesh
(`repro.runtime.arena.ShardedParamArena`): per-device population state drops
to n/8 rows while replay stays bit-identical (asserted).  On the CPU the
sharded run executes in a SUBPROCESS that self-forces
``--xla_force_host_platform_device_count`` — forcing the device count in the
main process would split the CPU thread pool and skew the legacy/engine
timings this file has tracked from its first version.  On an accelerator
it runs in this process over the devices that exist, and fails when there
are too few.  The block-hash / balance comparison therefore doubles as a
replay gate across device topologies.  The cohort axis is sharded end-to-end: each
device trains its slice of the cohort and aggregation combines shard-local
partials with a fixed-order tree, so the sharded latency column measures
real cohort-parallel execution on a forced CPU mesh (8 logical devices on
one physical CPU); ``per_device_arena_bytes`` is the scaling headline.

``--mesh-shards`` also drives a shard-count sweep (1/2/4/8, capped at the
flag) of the steady engine round — every width replaying bit-identically —
recorded as the ``sharded_sweep`` section; ``--sweep-only`` refreshes just
that section, merging into an existing ``BENCH_round.json``.

Also asserts the paths replay identically (block hashes + balances) and
that the engine compiled each used entry exactly once, then emits
``BENCH_round.json`` (steady-state round ms, compile counts, peak host
bytes, per-round population realloc, per-device arena bytes) so the perf
trajectory is tracked PR over PR.

The engine is strategy-generic (PR 5): ``--strategy`` picks the strategy
for the headline legacy-vs-engine case, and a per-strategy sweep records
the steady engine round latency of EVERY registered strategy (bfln,
fedavg, fedprox, fedproto, fedhkd) into ``per_strategy_steady_ms`` —
each asserted at 1 compile per entry.

``--mode async`` (or ``both``) adds the FedBuff lane: legacy vs engine
buffered-flush latency through ``async_step``, steady state past warmup,
with flush timings and staleness / staleness-weight distributions pulled
from the `repro.obs` flight recorder, the same cross-driver replay gate
(block hashes + balances identical), and a 1-compile assert on
``async_step``.  Results land in the ``"async"`` section of
``BENCH_round.json`` (merged into an existing file when run async-only).

``--trace`` re-runs the headline engine case with the flight recorder on
(JSONL trace ``round_bench_trace.jsonl`` + per-phase console table), so
the per-phase round breakdown and the trace-on vs trace-off steady delta
are visible next to the bench numbers.

``--checkpoint-interval N`` (default 10) adds the checkpoint-overhead lane
(`repro.checkpoint`): the steady engine round with a full-state snapshot
every N rounds vs without — amortised overhead (<10% budget at N=10),
snapshot size, and isolated save/restore latency, recorded as the
``"checkpoint"`` section with replay asserted bit-identical.

Prints ``round,<name>,<us_per_round>,<derived>`` CSV like the other benches.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time
import tracemalloc

if __name__ == "__main__":
    # sharded worker mode: needs the multi-device CPU platform, and XLA_FLAGS
    # must be set before jax initialises (the repro.sim import below) —
    # pre-parse the shard count and re-exec once with the forced device count
    _pre = argparse.ArgumentParser(add_help=False)
    _pre.add_argument("--mesh-shards", type=int, default=8)
    _pre.add_argument("--sharded-only", default=None)
    _ns = _pre.parse_known_args()[0]
    if _ns.sharded_only is not None:
        from repro.launch.bootstrap import force_host_device_count
        force_host_device_count(_ns.mesh_shards)

import jax
import numpy as np

from repro.sim import ClientPopulation, PopulationSpec, SimulatedFederation
from repro.utils.tree import tree_bytes

WARMUP = 3            # rounds excluded from the steady-state mean (compiles)


def _build(engine: bool, n_clients: int, sample_frac: float, rounds: int,
           eval_examples: int, mesh_shards: int = 1,
           strategy: str = "bfln", mode: str = "sync",
           trace: bool = False, ckpt_interval: int = 0,
           ckpt_dir: str = "checkpoints") -> SimulatedFederation:
    import repro.api as api

    # fresh population per driver: LatencyModel draws advance an internal rng,
    # so sharing one instance would desynchronise the second run
    pspec = PopulationSpec(n_clients=n_clients, straggler_frac=0.1,
                           dropout_rate=0.03, byzantine_frac=0.05, seed=0)
    pop = ClientPopulation.from_spec(pspec)
    spec = api.ExperimentSpec(
        data=api.DataSpec(n_clients=n_clients, straggler_frac=0.1,
                          dropout_rate=0.03, byzantine_frac=0.05),
        train=api.TrainSpec(strategy=strategy, rounds=rounds,
                            sample_frac=sample_frac, n_clusters=5,
                            mode=mode),
        async_=api.AsyncSpec(
            buffer_size=max(1, int(round(sample_frac * n_clients))),
            concurrency=min(256, max(2, n_clients // 4))),
        eval=api.EvalSpec(every=1, examples=eval_examples),
        mesh=api.MeshSpec(shards=mesh_shards),
        obs=api.ObsSpec(enabled=True, trace_path="round_bench_trace.jsonl")
        if trace else api.ObsSpec(),
        checkpoint=api.CheckpointSpec(interval=ckpt_interval, dir=ckpt_dir),
        engine=engine, seed=0)
    return SimulatedFederation(pop, spec)


def _compile_counts(sim: SimulatedFederation) -> dict[str, int]:
    if sim.engine is not None:
        return sim.engine.cache_sizes()
    return {"_cohort_round": sim._cohort_round._cache_size(),
            "_eval": sim._eval._cache_size(),
            "_eval_final": sim._eval_final._cache_size()}


def _arena_ptrs(sim: SimulatedFederation) -> list[int]:
    """Per-shard device buffer pointers (1 entry when unsharded)."""
    if sim.cfg.mesh_shards > 1:
        return [s.data.unsafe_buffer_pointer()
                for s in sim.arena.data.addressable_shards]
    return [sim.arena.data.unsafe_buffer_pointer()]


def _run(engine: bool, n_clients: int, sample_frac: float, rounds: int,
         eval_examples: int, mesh_shards: int = 1,
         strategy: str = "bfln") -> dict:
    sim = _build(engine, n_clients, sample_frac, rounds, eval_examples,
                 mesh_shards, strategy)
    times_ms = []
    for r in range(rounds):
        t0 = time.perf_counter()
        sim.history.append(sim._run_sync_round(r))
        times_ms.append((time.perf_counter() - t0) * 1e3)
    sim._finalize_history()        # drain deferred (overlapped) eval outputs

    # population-allocation metric: the engine donates the arena (in-place
    # update, 0 bytes); the legacy scatter rebuilds the full stacked pytree
    if engine:
        ptrs = _arena_ptrs(sim)
        realloc = 0
    else:
        ptrs = None
        realloc = tree_bytes(sim.params)
    # separate phase: tracemalloc slows every Python allocation, so host-byte
    # accounting runs over extra (untimed) steady-state rounds
    tracemalloc.start()
    for r in range(rounds, rounds + 5):
        sim.history.append(sim._run_sync_round(r))
    sim._finalize_history()
    _, peak_host = tracemalloc.get_traced_memory()
    tracemalloc.stop()
    if engine:
        assert _arena_ptrs(sim) == ptrs, \
            "arena buffer was reallocated (donation regressed)"

    steady = times_ms[WARMUP:] or times_ms
    counts = sorted({int(rec.arrived.sum()) for rec in sim.history})
    out = {
        "engine": engine,
        "strategy": strategy,
        "rounds": rounds,
        "first_round_ms": round(times_ms[0], 2),
        "steady_ms": round(float(np.mean(steady)), 3),
        "steady_p50_ms": round(float(np.median(steady)), 3),
        "distinct_arrival_counts": len(counts),
        "compile_counts": _compile_counts(sim),
        "peak_host_bytes": int(peak_host),
        "population_realloc_bytes_per_round": int(realloc),
        "block_hashes": [b.block_hash() for b in sim.trainer.chain.blocks],
        "balances": sim.trainer.ledger.balances,
    }
    if mesh_shards > 1:
        out["mesh_shards"] = mesh_shards
        out["per_device_arena_bytes"] = sim.arena.per_device_bytes()
        out["arena_total_bytes"] = int(sim.arena.data.nbytes)
    elif engine:
        out["per_device_arena_bytes"] = int(sim.arena.data.nbytes)
    return out


def _sharded_run(n_clients: int, sample_frac: float, rounds: int,
                 eval_examples: int, mesh_shards: int,
                 strategy: str = "bfln") -> dict:
    """The mesh-sharded engine run — in-process when enough devices already
    exist, otherwise (CPU only) via a ``--sharded-only`` subprocess that
    self-forces the CPU device count (keeping THIS process single-device so
    the legacy and engine timings stay comparable with the pre-mesh
    trajectory).  On an accelerator this process already holds the chips,
    so a child could reach none: too few devices is an error there."""
    n_dev = len(jax.devices())
    if mesh_shards <= n_dev:
        return _run(True, n_clients, sample_frac, rounds, eval_examples,
                    mesh_shards, strategy)
    if jax.default_backend() != "cpu":
        raise RuntimeError(
            f"the sharded case needs {mesh_shards} {jax.default_backend()} "
            f"devices but {n_dev} exist; pass --mesh-shards {n_dev} or less "
            f"(--mesh-shards 1 skips it)")
    payload = json.dumps({"n_clients": n_clients, "sample_frac": sample_frac,
                          "rounds": rounds, "eval_examples": eval_examples,
                          "strategy": strategy})
    out = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--sharded-only", payload,
         "--mesh-shards", str(mesh_shards)],
        capture_output=True, text=True, env=dict(os.environ), timeout=7200)
    if out.returncode != 0:
        raise RuntimeError(f"sharded worker failed:\n{out.stderr[-4000:]}")
    return json.loads(out.stdout.splitlines()[-1])


def _case(n_clients: int, sample_frac: float, rounds: int,
          eval_examples: int, mesh_shards: int = 1,
          strategy: str = "bfln") -> dict:
    legacy = _run(False, n_clients, sample_frac, rounds, eval_examples,
                  strategy=strategy)
    engine = _run(True, n_clients, sample_frac, rounds, eval_examples,
                  strategy=strategy)

    # correctness gates: identical replay, exactly one compile per used entry
    assert legacy["block_hashes"] == engine["block_hashes"], \
        "engine replay diverged from the legacy driver"
    assert np.array_equal(legacy["balances"], engine["balances"])
    used = {k: v for k, v in engine["compile_counts"].items() if v}
    assert all(v == 1 for v in used.values()), \
        f"engine entry recompiled: {engine['compile_counts']}"
    assert engine["distinct_arrival_counts"] > 1, \
        "benchmark population produced constant arrival counts"

    drop = ("block_hashes", "balances", "engine", "rounds")
    case = {
        "strategy": strategy,
        "eval_examples": eval_examples,
        "distinct_arrival_counts": engine["distinct_arrival_counts"],
        "legacy": {k: v for k, v in legacy.items() if k not in drop},
        "engine": {k: v for k, v in engine.items() if k not in drop},
        "steady_speedup": round(legacy["steady_ms"] / engine["steady_ms"], 2),
        "replay_identical": True,
    }
    if mesh_shards > 1:
        sharded = _sharded_run(n_clients, sample_frac, rounds, eval_examples,
                               mesh_shards, strategy)
        # the sharded engine must replay bit-identically to both others
        assert sharded["block_hashes"] == engine["block_hashes"], \
            "sharded replay diverged from the single-device engine"
        assert np.array_equal(np.asarray(sharded["balances"]),
                              np.asarray(engine["balances"]))
        used = {k: v for k, v in sharded["compile_counts"].items() if v}
        assert all(v == 1 for v in used.values()), \
            f"sharded entry recompiled: {sharded['compile_counts']}"
        case["sharded"] = {k: v for k, v in sharded.items() if k not in drop}
        case["sharded_round_overhead"] = round(
            sharded["steady_ms"] / engine["steady_ms"], 2)
        case["arena_bytes_per_device_reduction"] = round(
            engine["per_device_arena_bytes"]
            / sharded["per_device_arena_bytes"], 2)
    return case


def _async_run(engine: bool, n_clients: int, sample_frac: float,
               flushes: int, eval_examples: int,
               strategy: str = "bfln") -> dict:
    """One FedBuff async lane: run ``flushes`` buffer flushes with the flight
    recorder on and report steady flush latency + staleness metrics straight
    from the obs registry (`repro.obs`)."""
    sim = _build(engine, n_clients, sample_frac, flushes, eval_examples,
                 strategy=strategy, mode="async", trace=True)
    t0 = time.perf_counter()
    sim._run_async()
    wall_s = time.perf_counter() - t0
    sim._finalize_history()

    flush_ms = [r["dur_us"] / 1e3 for r in sim.obs.records
                if r["kind"] == "span" and r["name"] == "flush.total"]
    steady = flush_ms[WARMUP:] or flush_ms
    snap = sim.obs.metrics.snapshot()
    out = {
        "engine": engine,
        "strategy": strategy,
        "flushes_run": len(flush_ms),
        "first_flush_ms": round(flush_ms[0], 2) if flush_ms else None,
        "steady_flush_ms": round(float(np.mean(steady)), 3),
        "steady_flush_p50_ms": round(float(np.median(steady)), 3),
        "wall_s": round(wall_s, 2),
        "staleness": snap["summaries"].get("async.staleness"),
        "staleness_weight": snap["summaries"].get("async.staleness_weight"),
        "compile_counts": _compile_counts(sim) if engine else None,
        "block_hashes": [b.block_hash() for b in sim.trainer.chain.blocks],
        "balances": sim.trainer.ledger.balances,
    }
    return out


def _async_case(n_clients: int, sample_frac: float, flushes: int,
                eval_examples: int, strategy: str = "bfln") -> dict:
    """The async lane: engine vs legacy FedBuff flushes on the same seeded
    population — replay gate (block hashes + balances) plus the engine's
    1-compile ``async_step`` contract."""
    legacy = _async_run(False, n_clients, sample_frac, flushes, eval_examples,
                        strategy=strategy)
    engine = _async_run(True, n_clients, sample_frac, flushes, eval_examples,
                        strategy=strategy)
    assert legacy["block_hashes"] == engine["block_hashes"], \
        "async engine replay diverged from the legacy driver"
    assert np.array_equal(legacy["balances"], engine["balances"])
    used = {k: v for k, v in engine["compile_counts"].items() if v}
    assert all(v == 1 for v in used.values()), \
        f"async engine entry recompiled: {engine['compile_counts']}"
    assert used.get("async_step") == 1, \
        f"async_step not exercised/compiled once: {engine['compile_counts']}"
    drop = ("block_hashes", "balances", "engine")
    return {
        "strategy": strategy,
        "buffer_size": max(1, int(round(sample_frac * n_clients))),
        "legacy": {k: v for k, v in legacy.items() if k not in drop},
        "engine": {k: v for k, v in engine.items() if k not in drop},
        "steady_flush_speedup": round(
            legacy["steady_flush_ms"] / engine["steady_flush_ms"], 2),
        "replay_identical": True,
    }


def _sharded_sweep(n_clients: int, sample_frac: float, rounds: int,
                   eval_examples: int, shard_counts: list[int],
                   strategy: str = "bfln") -> dict:
    """Steady engine round latency at each client-mesh width.

    Every width must replay bit-identically to the 1-device engine (block
    hashes + balances) and compile each used entry exactly once; widths
    beyond the available device count run via the self-forcing
    ``--sharded-only`` subprocess so THIS process stays single-device."""
    rows = {}
    base = None
    for s in shard_counts:
        row = (_run(True, n_clients, sample_frac, rounds, eval_examples,
                    strategy=strategy)
               if s == 1 else
               _sharded_run(n_clients, sample_frac, rounds, eval_examples,
                            s, strategy))
        if base is None:
            base = row
        else:
            assert row["block_hashes"] == base["block_hashes"], \
                f"sharded sweep: shards={s} replay diverged"
            assert np.array_equal(np.asarray(row["balances"]),
                                  np.asarray(base["balances"]))
        used = {k: v for k, v in row["compile_counts"].items() if v}
        assert all(v == 1 for v in used.values()), \
            f"sharded sweep: shards={s} recompiled: {row['compile_counts']}"
        rows[str(s)] = {
            "steady_ms": row["steady_ms"],
            "steady_p50_ms": row["steady_p50_ms"],
            "first_round_ms": row["first_round_ms"],
            "per_device_arena_bytes": row["per_device_arena_bytes"],
            "speedup_vs_1": round(base["steady_ms"] / row["steady_ms"], 2),
        }
    return {"shard_counts": shard_counts, "eval_examples": eval_examples,
            "rounds": rounds, "strategy": strategy,
            "replay_identical": True, "per_shards": rows}


def _checkpoint_case(n_clients: int, sample_frac: float, rounds: int,
                     eval_examples: int, interval: int) -> dict:
    """Checkpoint-overhead lane: the steady engine round with snapshots every
    ``interval`` rounds vs without, plus the snapshot's own save/restore
    latency and on-disk size.  The amortised overhead at the default
    interval=10 is the <10% acceptance headline; replay is asserted
    bit-identical (checkpointing is a pure observer)."""
    import shutil
    import tempfile

    # Each timed round blocks on its own device work (arena rows + deferred
    # eval outputs).  The engine normally leaves those async so rounds
    # pipeline — but a snapshot capture is a full sync point, so without
    # per-round blocking the boundary round would be billed every OTHER
    # round's deferred compute and the overhead number would be fiction.
    def _settle(sim):
        rec = sim.history[-1]
        if not isinstance(rec.accuracy, float):
            jax.block_until_ready(rec.accuracy)
        jax.block_until_ready(sim.arena.data if sim.arena is not None
                              else sim._params)

    off = _build(True, n_clients, sample_frac, rounds, eval_examples)
    times_off = []
    for r in range(rounds):
        t0 = time.perf_counter()
        off.history.append(off._run_sync_round(r))
        _settle(off)
        times_off.append((time.perf_counter() - t0) * 1e3)
    off._finalize_history()

    tmp = tempfile.mkdtemp(prefix="round_bench_ckpt_")
    try:
        on = _build(True, n_clients, sample_frac, rounds, eval_examples,
                    ckpt_interval=interval, ckpt_dir=tmp)
        times_on = []
        for r in range(rounds):
            t0 = time.perf_counter()
            on.history.append(on._run_sync_round(r))
            on._maybe_checkpoint(r + 1)
            _settle(on)
            times_on.append((time.perf_counter() - t0) * 1e3)
        # retire the last in-flight background write inside the accounting —
        # the lane must charge every millisecond the writer blocked us for
        t0 = time.perf_counter()
        on._ckpt_wait()
        times_on[-1] += (time.perf_counter() - t0) * 1e3
        on._finalize_history()

        assert ([b.block_hash() for b in on.trainer.chain.blocks]
                == [b.block_hash() for b in off.trainer.chain.blocks]), \
            "checkpointing perturbed the replay"
        assert np.array_equal(on.trainer.ledger.balances,
                              off.trainer.ledger.balances)

        # isolated snapshot save/restore latency (outside the round timing)
        from repro.checkpoint import load_latest, save_checkpoint
        from repro.checkpoint.state import (
            capture_experiment_state,
            restore_experiment_state,
        )
        t0 = time.perf_counter()
        tree = capture_experiment_state(on, rounds)
        _, snap_bytes = save_checkpoint(tmp, rounds, tree)
        save_ms = (time.perf_counter() - t0) * 1e3
        fresh = _build(True, n_clients, sample_frac, rounds, eval_examples,
                       ckpt_interval=interval, ckpt_dir=tmp)
        t0 = time.perf_counter()
        _, tree = load_latest(tmp)
        restore_experiment_state(fresh, tree)
        restore_ms = (time.perf_counter() - t0) * 1e3
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    steady_off = float(np.mean(times_off[WARMUP:] or times_off))
    steady_on = float(np.mean(times_on[WARMUP:] or times_on))
    return {
        "interval": interval,
        "rounds": rounds,
        "steady_ms_off": round(steady_off, 3),
        "steady_ms_on": round(steady_on, 3),
        "overhead_pct": round(100.0 * (steady_on - steady_off) / steady_off,
                              2),
        "snapshot_bytes": int(snap_bytes),
        "save_ms": round(save_ms, 2),
        "restore_ms": round(restore_ms, 2),
        "replay_identical": True,
    }


def _strategy_sweep(n_clients: int, sample_frac: float, rounds: int,
                    eval_examples: int) -> dict:
    """Steady-round engine latency for EVERY registered strategy — the
    strategy-generic fused engine's per-strategy cost (1 compile per entry
    asserted for each)."""
    from repro.api import strategy_names
    sweep = {}
    for name in strategy_names():
        row = _run(True, n_clients, sample_frac, rounds, eval_examples,
                   strategy=name)
        used = {k: v for k, v in row["compile_counts"].items() if v}
        assert all(v == 1 for v in used.values()), \
            f"{name} engine entry recompiled: {row['compile_counts']}"
        sweep[name] = {"steady_ms": row["steady_ms"],
                       "steady_p50_ms": row["steady_p50_ms"],
                       "first_round_ms": row["first_round_ms"]}
    return sweep


def main(n_clients: int = 1000, sample_frac: float = 0.10, rounds: int = 50,
         out: str = "BENCH_round.json", heavy_eval: bool = True,
         mesh_shards: int = 8, strategy: str = "bfln", mode: str = "sync",
         trace: bool = False, sweep_only: bool = False,
         checkpoint_interval: int = 10, checkpoint_only: bool = False) -> dict:
    cases = {}
    per_strategy = None
    ckpt_case = None
    sweep_rounds = max(WARMUP + 2, rounds // 5)
    if mode in ("sync", "both") and not sweep_only:
        if not checkpoint_only:
            cases["headline_eval256"] = _case(n_clients, sample_frac, rounds,
                                              256, mesh_shards, strategy)
            if heavy_eval:
                cases["heavy_eval1024"] = _case(n_clients, sample_frac,
                                                rounds, 1024, mesh_shards,
                                                strategy)
            per_strategy = _strategy_sweep(n_clients, sample_frac,
                                           sweep_rounds, 256)
        if checkpoint_interval > 0:
            ckpt_case = _checkpoint_case(n_clients, sample_frac, rounds, 256,
                                         checkpoint_interval)

    sharded_sweep = None
    if mode in ("sync", "both") and mesh_shards > 1 and not checkpoint_only:
        widths = [s for s in (1, 2, 4, 8) if s <= mesh_shards]
        sharded_sweep = _sharded_sweep(n_clients, sample_frac, sweep_rounds,
                                       256, widths, strategy)

    async_case = None
    if mode in ("async", "both") and not sweep_only and not checkpoint_only:
        flushes = max(WARMUP + 2, rounds // 2)
        async_case = _async_case(n_clients, sample_frac, flushes, 256,
                                 strategy)

    result = {
        "bench": "round",
        "n_clients": n_clients,
        "cohort": max(1, int(round(sample_frac * n_clients))),
        "rounds": rounds,
        "mesh_shards": mesh_shards,
        "strategy": strategy,
        **({"per_strategy_steady_ms": per_strategy} if per_strategy else {}),
        **cases,
        **({"checkpoint": ckpt_case} if ckpt_case else {}),
        **({"sharded_sweep": sharded_sweep} if sharded_sweep else {}),
        **({"async": async_case} if async_case else {}),
    }
    if (mode == "async" or sweep_only or checkpoint_only) \
            and os.path.exists(out):
        # async-only / sweep-only / checkpoint-only runs merge into the
        # existing results instead of clobbering them
        with open(out) as f:
            prev = json.load(f)
        if async_case is not None:
            prev["async"] = async_case
        if sweep_only and sharded_sweep is not None:
            prev["sharded_sweep"] = sharded_sweep
        if checkpoint_only and ckpt_case is not None:
            prev["checkpoint"] = ckpt_case
        result = prev
    with open(out, "w") as f:
        json.dump(result, f, indent=2)

    if trace:
        from repro.obs import console_summary
        sim = _build(True, n_clients, sample_frac,
                     max(WARMUP + 2, rounds // 5), 256,
                     strategy=strategy, trace=True)
        for r in range(max(WARMUP + 2, rounds // 5)):
            sim.history.append(sim._run_sync_round(r))
        sim._finalize_history()
        print(console_summary(sim.obs.metrics,
                              title=f"traced engine rounds ({strategy})"))

    if async_case is not None:
        for side in ("legacy", "engine"):
            row = async_case[side]
            st = row.get("staleness") or {}
            print(f"round,async_{side},{row['steady_flush_ms'] * 1e3:.0f},"
                  f"steady flush ms (buffer={async_case['buffer_size']}) "
                  f"first_ms={row['first_flush_ms']} "
                  f"staleness_p50={st.get('p50', 0):.1f} "
                  f"staleness_p99={st.get('p99', 0):.1f}")
        print(f"round,async_speedup,{async_case['steady_flush_speedup']:.2f},"
              f"replay_identical=True async_step_compiles=1")

    for cname, case in cases.items():
        for side in ("legacy", "engine", "sharded"):
            row = case.get(side)
            if row is None:
                continue
            print(f"round,{cname}_{side},{row['steady_ms'] * 1e3:.0f},"
                  f"n={n_clients} cohort={result['cohort']} rounds={rounds} "
                  f"first_ms={row['first_round_ms']} "
                  f"compiles={sum(row['compile_counts'].values())} "
                  f"realloc_mb_per_round="
                  f"{row['population_realloc_bytes_per_round'] / 1e6:.1f}"
                  + (f" arena_mb_per_device="
                     f"{row['per_device_arena_bytes'] / 1e6:.1f}"
                     if "per_device_arena_bytes" in row else ""))
        print(f"round,{cname}_speedup,{case['steady_speedup']:.2f},"
              f"replay_identical=True "
              f"arrival_counts={case['distinct_arrival_counts']} "
              f"engine_compiles_per_entry=1")
        if "sharded" in case:
            print(f"round,{cname}_sharded,"
                  f"{case['arena_bytes_per_device_reduction']:.2f},"
                  f"arena_bytes_per_device_reduction over {mesh_shards} "
                  f"shards, round_overhead="
                  f"{case['sharded_round_overhead']:.2f}x, replay_identical")
    for name, row in (per_strategy or {}).items():
        print(f"round,strategy_{name},{row['steady_ms'] * 1e3:.0f},"
              f"engine steady round (1 compile per entry) "
              f"first_ms={row['first_round_ms']}")
    if ckpt_case is not None:
        print(f"round,checkpoint,{ckpt_case['overhead_pct']:.2f},"
              f"steady overhead pct at interval={ckpt_case['interval']} "
              f"({ckpt_case['steady_ms_off']:.1f} -> "
              f"{ckpt_case['steady_ms_on']:.1f} ms) "
              f"snapshot_mb={ckpt_case['snapshot_bytes'] / 1e6:.1f} "
              f"save_ms={ckpt_case['save_ms']} "
              f"restore_ms={ckpt_case['restore_ms']} replay_identical")
        if ckpt_case["overhead_pct"] >= 10:
            print(f"round,WARNING,0,checkpoint overhead "
                  f"{ckpt_case['overhead_pct']:.1f}% at interval="
                  f"{ckpt_case['interval']} exceeds the 10% budget")
    if sharded_sweep is not None:
        for s, row in sharded_sweep["per_shards"].items():
            print(f"round,sweep_shards{s},{row['steady_ms'] * 1e3:.0f},"
                  f"steady engine round at {s} shard(s) "
                  f"speedup_vs_1={row['speedup_vs_1']:.2f} "
                  f"arena_mb_per_device="
                  f"{row['per_device_arena_bytes'] / 1e6:.1f}")
    if "headline_eval256" in cases:
        headline = cases["headline_eval256"]["steady_speedup"]
        print(f"round,result,{headline:.2f},-> {out}")
        if headline < 5:
            print(f"round,WARNING,0,headline speedup {headline:.2f}x below "
                  f"the 5x target")
    elif async_case is not None:
        print(f"round,result,{async_case['steady_flush_speedup']:.2f},"
              f"-> {out}")
    elif sharded_sweep is not None:
        widest = max(sharded_sweep["per_shards"], key=int)
        print(f"round,result,"
              f"{sharded_sweep['per_shards'][widest]['speedup_vs_1']:.2f},"
              f"sweep speedup at {widest} shards -> {out}")
    else:
        print(f"round,result,{ckpt_case['overhead_pct']:.2f},"
              f"checkpoint overhead pct -> {out}")
    return result


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--quick", action="store_true",
                   help="CI smoke: small population, few rounds, no heavy case")
    p.add_argument("--strategy", default="bfln",
                   help="strategy for the headline legacy-vs-engine case "
                        "(the per-strategy sweep always covers all of them)")
    p.add_argument("--mode", choices=("sync", "async", "both"),
                   default="sync",
                   help="async: FedBuff flush lane (engine vs legacy, steady "
                        "flush latency + staleness metrics via repro.obs); "
                        "async-only runs merge into an existing out file")
    p.add_argument("--trace", action="store_true",
                   help="after the bench, run a traced engine case and print "
                        "the per-phase console summary (repro.obs)")
    p.add_argument("--n-clients", type=int, default=None)
    p.add_argument("--rounds", type=int, default=None)
    p.add_argument("--mesh-shards", type=int, default=8,
                   help="client-mesh width for the sharded case (1 disables; "
                        "on the CPU the needed devices are forced in a "
                        "subprocess)")
    p.add_argument("--sharded-only", default=None, metavar="JSON",
                   help="internal worker mode: run ONLY the sharded case for "
                        "the given case params and print its metrics as JSON")
    p.add_argument("--sweep-only", action="store_true",
                   help="run ONLY the shard-count sweep (1/2/4/8 up to "
                        "--mesh-shards) and merge its sharded_sweep section "
                        "into an existing --out file")
    p.add_argument("--checkpoint-interval", type=int, default=10,
                   help="checkpoint-overhead lane: steady engine round with "
                        "a full-state snapshot every N rounds vs without "
                        "(<10%% amortised budget at the default 10; 0 skips "
                        "the lane)")
    p.add_argument("--checkpoint-only", action="store_true",
                   help="run ONLY the checkpoint-overhead lane and merge its "
                        "checkpoint section into an existing --out file")
    p.add_argument("--out", default="BENCH_round.json")
    args = p.parse_args()
    if args.sharded_only is not None:
        kw = json.loads(args.sharded_only)
        row = _run(True, kw["n_clients"], kw["sample_frac"], kw["rounds"],
                   kw["eval_examples"], args.mesh_shards,
                   kw.get("strategy", "bfln"))
        row["balances"] = row["balances"].tolist()    # exact: repr round-trip
        print(json.dumps(row))
        sys.exit(0)
    n = args.n_clients or (200 if args.quick else 1000)
    r = args.rounds or (10 if args.quick else 50)
    main(n_clients=n, rounds=r, out=args.out, heavy_eval=not args.quick,
         mesh_shards=args.mesh_shards, strategy=args.strategy,
         mode=args.mode, trace=args.trace, sweep_only=args.sweep_only,
         checkpoint_interval=args.checkpoint_interval,
         checkpoint_only=args.checkpoint_only)
