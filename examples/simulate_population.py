"""Population-scale simulation: any strategy, sampling, stragglers, attacks.

Runs one declarative `repro.api.ExperimentSpec` through `repro.api.run` —
the event-driven simulator over ≥1000 virtual clients with partial
participation, with every strategy (BFLN or a Table II baseline) fused into
the arena-backed round engine:

    PYTHONPATH=src python examples/simulate_population.py \
        --clients 1000 --sample-frac 0.10 --rounds 30 --byzantine-frac 0.05 \
        --strategy bfln

Every run is deterministic and self-describing: the printed manifest stamps
the spec's config digest plus SHA-256 digests of the event log, block
hashes and balances — rerun with the same spec and every digest reproduces
exactly.  ``--spec-json out.json`` dumps the spec; ``--from-spec file``
replays one.

Finishes in well under 2 minutes on CPU.  Scenario knobs:
  --strategy bfln|fedavg|fedprox|fedproto|fedhkd
  --straggler-frac / --straggler-slowdown   heavy-tailed client latency
  --dropout-rate                            mid-round client death
  --byzantine-frac                          freeriding hash commitments
  --sampler uniform|stake_weighted|cluster_stratified
  --mode sync|async  (async = FedBuff buffered aggregation + staleness)
  --mesh-shards N                           row-shard the parameter arena
                                            over an N-device client mesh
                                            (CPU devices self-forced)
  --trace t.jsonl [--chrome-trace t.json]   flight-recorder trace (repro.obs):
                                            per-phase spans + metrics, digest
                                            stamped into the manifest
  --checkpoint-interval N --checkpoint-dir D   snapshot the complete state
                                            every N rounds/flushes (keep-last
                                            --keep-last); --resume continues
                                            from D's newest readable snapshot
                                            with bit-identical final digests
  --crash-round R [--crash-phase P --crash-mode M]   fault injection: die at
                                            boundary R (demo of the
                                            kill-and-resume workflow)
"""
import argparse
import time

if __name__ == "__main__":
    # mesh mode needs its runtime environment (forced CPU device count,
    # platform / x64 / extra XLA flags) resolved BEFORE jax initialises (the
    # repro.api import below) — pre-parse and bootstrap, re-execing once if
    # the environment had to change.  A replayed spec (--from-spec) carries
    # its mesh section inside the JSON, so peek at the file here (plain
    # json, no jax import) or the --mesh-shards flag would silently win
    # with its default of 1 and the mesh run could never replay.
    import json as _json

    from repro.launch.platform import bootstrap
    _pre = argparse.ArgumentParser(add_help=False)
    _pre.add_argument("--mesh-shards", type=int, default=1)
    _pre.add_argument("--from-spec", default=None)
    _ns = _pre.parse_known_args()[0]
    _mesh = {"shards": _ns.mesh_shards}
    if _ns.from_spec:
        with open(_ns.from_spec) as _f:
            _mesh = dict(_json.load(_f).get("mesh", {}))
        _mesh["shards"] = max(_ns.mesh_shards, _mesh.get("shards", 1))
    bootstrap({"mesh": _mesh})

import numpy as np

import repro.api as api


def build_spec(args) -> api.ExperimentSpec:
    return api.ExperimentSpec(
        data=api.DataSpec(
            n_clients=args.clients, dataset=args.dataset, beta=args.bias,
            straggler_frac=args.straggler_frac,
            straggler_slowdown=args.straggler_slowdown,
            dropout_rate=args.dropout_rate,
            byzantine_frac=args.byzantine_frac),
        train=api.TrainSpec(
            strategy=args.strategy, rounds=args.rounds,
            sample_frac=args.sample_frac, n_clusters=args.clusters,
            local_epochs=args.local_epochs, deadline=args.deadline,
            sampler=args.sampler, mode=args.mode),
        async_=api.AsyncSpec(
            buffer_size=args.buffer_size, concurrency=args.concurrency,
            staleness_alpha=args.staleness_alpha),
        eval=api.EvalSpec(every=5),
        mesh=api.MeshSpec(shards=args.mesh_shards),
        obs=api.ObsSpec(enabled=True, trace_path=args.trace,
                        chrome_path=args.chrome_trace, console=True)
        if args.trace else api.ObsSpec(),
        checkpoint=api.CheckpointSpec(interval=args.checkpoint_interval,
                                      dir=args.checkpoint_dir,
                                      keep_last=args.keep_last),
        faults=api.FaultSpec(crash_round=args.crash_round,
                             crash_phase=args.crash_phase,
                             crash_mode=args.crash_mode)
        if args.crash_round >= 0 else api.FaultSpec(),
        seed=args.seed)


def print_history(res: api.ExperimentResult, mode: str) -> None:
    for r in res.report.history:
        acc = f" acc={r.accuracy:.4f}" if np.isfinite(r.accuracy) else ""
        stale = (f" stale={r.staleness_mean:.2f}" if mode == "async" else
                 f" strag={r.n_stragglers} drop={r.n_dropouts}")
        print(f"round {r.round_idx:3d} t={r.t_close:8.1f} "
              f"k={len(r.cohort):3d} arrived={int(r.arrived.sum()):3d}"
              f"{stale} byz={r.n_byzantine} prod={r.producer:4d} "
              f"verified={r.verified_frac:.2f} paid={r.reward_paid:5.1f} "
              f"burned={r.reward_burned:4.1f} loss={r.mean_loss:.4f}{acc}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--clients", type=int, default=1000)
    ap.add_argument("--dataset", default="synth10")
    ap.add_argument("--bias", type=float, default=0.3)
    ap.add_argument("--strategy", default="bfln",
                    choices=api.strategy_names())
    ap.add_argument("--rounds", type=int, default=30)
    ap.add_argument("--sample-frac", type=float, default=0.10)
    ap.add_argument("--clusters", type=int, default=5)
    ap.add_argument("--local-epochs", type=int, default=2)
    ap.add_argument("--deadline", type=float, default=30.0)
    ap.add_argument("--straggler-frac", type=float, default=0.10)
    ap.add_argument("--straggler-slowdown", type=float, default=8.0)
    ap.add_argument("--dropout-rate", type=float, default=0.03)
    ap.add_argument("--byzantine-frac", type=float, default=0.05)
    ap.add_argument("--sampler", default="uniform",
                    choices=["uniform", "stake_weighted", "cluster_stratified"])
    ap.add_argument("--mode", default="sync", choices=["sync", "async"])
    ap.add_argument("--buffer-size", type=int, default=16)
    ap.add_argument("--concurrency", type=int, default=64)
    ap.add_argument("--staleness-alpha", type=float, default=0.5)
    ap.add_argument("--mesh-shards", type=int, default=1)
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="record a flight-recorder trace (repro.obs): JSONL "
                         "to PATH, per-phase console table, trace sha256 "
                         "stamped into the manifest")
    ap.add_argument("--chrome-trace", default=None, metavar="PATH",
                    help="with --trace: also export a Chrome/Perfetto trace")
    ap.add_argument("--checkpoint-interval", type=int, default=0,
                    help="snapshot the complete experiment state every N "
                         "rounds/flushes (0 = off)")
    ap.add_argument("--checkpoint-dir", default="checkpoints",
                    help="snapshot directory (with --checkpoint-interval)")
    ap.add_argument("--keep-last", type=int, default=3,
                    help="keep-last-K snapshot pruning window")
    ap.add_argument("--resume", default=None, metavar="PATH",
                    help="resume from a snapshot file or checkpoint dir "
                         "(newest readable snapshot); the finished run's "
                         "digests are bit-identical to an uninterrupted one")
    ap.add_argument("--crash-round", type=int, default=-1,
                    help="fault injection: crash at this round/flush "
                         "boundary (-1 = never)")
    ap.add_argument("--crash-phase", default="post_checkpoint",
                    choices=["round_start", "pre_chain", "post_checkpoint"])
    ap.add_argument("--crash-mode", default="sigkill",
                    choices=["exception", "sigkill"])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--skip-async-demo", action="store_true")
    ap.add_argument("--spec-json", default=None, metavar="PATH",
                    help="also dump the spec as JSON (reload via --from-spec)")
    ap.add_argument("--from-spec", default=None, metavar="PATH",
                    help="ignore the scenario flags and run this spec JSON")
    args = ap.parse_args()

    t0 = time.time()
    if args.from_spec:
        with open(args.from_spec) as f:
            spec = api.ExperimentSpec.from_json(f.read())
    else:
        spec = build_spec(args)
    if args.spec_json:
        with open(args.spec_json, "w") as f:
            f.write(spec.to_json(indent=1))
        print(f"spec -> {args.spec_json}")

    from repro.sim import ClientPopulation
    pop = ClientPopulation.from_spec(spec.population_spec())
    print(f"population: {pop.n_clients} clients, "
          f"{int(pop.byzantine.sum())} byzantine, "
          f"{int((pop.latency.speed > 1.25).sum())} "   # non-straggler max is 1.25
          f"stragglers, strategy={spec.train.strategy}  "
          f"({time.time()-t0:.1f}s)")

    if args.resume:
        print(f"resuming from {args.resume}")
    res = api.run(spec, population=pop, resume_from=args.resume)
    print_history(res, spec.train.mode)

    print(f"\n{res.report.summary()}")
    print("manifest:")
    print(api.format_manifest(res.manifest))
    balances = res.report.balances
    top = np.argsort(-balances)[:5]
    print("top balances:", [(int(i), round(float(balances[i]), 2))
                            for i in top])
    if pop.byzantine.any():
        stake = spec.chain.initial_stake
        print(f"byzantine mean gain: "
              f"{(balances[pop.byzantine] - stake).mean():+.3f}  "
              f"honest mean gain: "
              f"{(balances[~pop.byzantine] - stake).mean():+.3f}")
    print(f"wall time: {time.time()-t0:.1f}s")

    if spec.train.mode == "sync" and not args.skip_async_demo:
        print("\n--- async (FedBuff) demo: same population spec, buffered "
              "staleness-weighted aggregation ---")
        aspec = api.ExperimentSpec(
            data=spec.data,
            train=api.TrainSpec(
                strategy=spec.train.strategy, rounds=8, mode="async",
                sampler="stake_weighted", n_clusters=spec.train.n_clusters,
                local_epochs=spec.train.local_epochs),
            async_=api.AsyncSpec(buffer_size=args.buffer_size,
                                 concurrency=args.concurrency,
                                 staleness_alpha=args.staleness_alpha),
            eval=api.EvalSpec(every=4), seed=spec.seed)
        ares = api.run(aspec)
        for r in ares.report.history:
            acc = f" acc={r.accuracy:.4f}" if np.isfinite(r.accuracy) else ""
            print(f"flush {r.round_idx:3d} t={r.t_close:8.1f} "
                  f"K={len(r.cohort):3d} stale={r.staleness_mean:.2f} "
                  f"byz={r.n_byzantine} verified={r.verified_frac:.2f} "
                  f"paid={r.reward_paid:5.1f} loss={r.mean_loss:.4f}{acc}")
        print(ares.summary())
        print(f"total wall time: {time.time()-t0:.1f}s")


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main()
