"""Serving-tier benchmark: latency + throughput through `repro.serve`.

Trains a small population (`repro.api.run`), snapshots it into a
chain-verified model bank, then drives the batched serving frontend with a
wall clock at several concurrency levels — open loop: each step submits
``concurrency`` mixed-cluster requests and pumps them through one fused
dispatch.  Reports per-request p50/p99 latency (submit -> completion,
including queue wait) and sustained requests/sec into ``BENCH_serve.json``,
plus snapshot/verify cost and the per-bucket compile counts.

    PYTHONPATH=src python -m benchmarks.serve_bench [--smoke] \\
        [--out BENCH_serve.json] [--levels 1,8,32,64]

``--smoke`` shrinks the trained population and the request count for CI;
the output schema is identical.
"""
from __future__ import annotations

import argparse
import json
import time


def build_serving(n_clients: int, rounds: int, n_clusters: int, seed: int):
    """Train, snapshot, verify; returns (result, bank, engine, timings)."""
    import repro.api as api
    from repro.serve import ServingEngine, snapshot, verify_bank

    spec = api.ExperimentSpec(
        data=api.DataSpec(n_clients=n_clients),
        train=api.TrainSpec(rounds=rounds, sample_frac=0.3,
                            n_clusters=n_clusters),
        eval=api.EvalSpec(every=0, clients=16, examples=64),
        seed=seed)
    t0 = time.perf_counter()
    result = api.run(spec)
    train_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    bank = snapshot(result, verify=False)
    snapshot_ms = (time.perf_counter() - t0) * 1e3
    chain = result.sim.trainer.chain
    t0 = time.perf_counter()
    verify_bank(bank, chain)
    verify_ms = (time.perf_counter() - t0) * 1e3
    engine = ServingEngine(bank, chain)
    return result, bank, engine, {
        "train_s": round(train_s, 2),
        "snapshot_ms": round(snapshot_ms, 2),
        "verify_ms": round(verify_ms, 2),
    }


def bench_level(engine, concurrency: int, n_requests: int, seed: int) -> dict:
    """Open-loop serving at one concurrency level, wall-clocked."""
    import numpy as np

    from repro.serve import ServeConfig, ServeFrontend

    bank = engine.bank
    buckets = tuple(b for b in (1, 2, 4, 8, 16, 32, 64, 128)
                    if b <= max(concurrency, 1)) or (1,)
    fe = ServeFrontend(
        engine, ServeConfig(buckets=buckets, max_wait=0.0,
                            max_pending=max(4 * concurrency, 64)),
        clock=time.perf_counter)
    rng = np.random.default_rng(seed)
    pool = rng.standard_normal((256, bank.mcfg.in_dim)).astype(np.float32)
    cids = rng.integers(0, bank.n_models, size=n_requests).astype(np.int32)

    # warm every bucket shape outside the timed region (compile happens
    # here); the engine is shared across levels so this cache size is
    # cumulative — it must equal the number of DISTINCT batch shapes seen
    # so far (1 compile per shape, never more)
    for b in buckets:
        for i in range(b):
            fe.submit(int(cids[i]), pool[i % 256])
        fe.pump()
    fe.take_completed()
    compiles = dict(engine.cache_sizes())

    latencies, served = [], 0
    t_start = time.perf_counter()
    i = 0
    while served < n_requests:
        burst = min(concurrency, n_requests - served)
        for _ in range(burst):
            fe.submit(int(cids[i]), pool[i % 256])
            i += 1
        fe.pump()
        fe.drain()
        for c in fe.take_completed():
            latencies.append((c.t_done - c.t_arrival) * 1e3)
            served += 1
    wall_s = time.perf_counter() - t_start

    lat = np.asarray(latencies)
    return {
        "concurrency": concurrency,
        "requests": int(served),
        "p50_ms": round(float(np.percentile(lat, 50)), 4),
        "p99_ms": round(float(np.percentile(lat, 99)), 4),
        "mean_ms": round(float(lat.mean()), 4),
        "req_per_s": round(served / wall_s, 1),
        "flushes": fe.n_flushes,
        "engine_cache_sizes": compiles,
    }


def routing_check(engine, seed: int) -> bool:
    """Self-check: one mixed batch bitwise-equal to per-request routing."""
    import numpy as np

    rng = np.random.default_rng(seed)
    x = rng.standard_normal((8, engine.bank.mcfg.in_dim)).astype(np.float32)
    cids = rng.integers(0, engine.bank.n_models, size=8).astype(np.int32)
    fused = np.asarray(engine.forward(x, cids))
    oracle = np.asarray(engine.forward_per_request(x, cids))
    return bool(np.array_equal(fused, oracle))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run (small population, few requests)")
    ap.add_argument("--out", default="BENCH_serve.json")
    ap.add_argument("--levels", default="1,8,32,64",
                    help="comma-separated concurrency levels")
    ap.add_argument("--requests", type=int, default=None,
                    help="requests per level (default 2048; smoke 256)")
    ap.add_argument("--clients", type=int, default=None)
    ap.add_argument("--rounds", type=int, default=None)
    ap.add_argument("--clusters", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    from repro.launch.platform import bootstrap
    bootstrap(None)

    n_clients = args.clients or (60 if args.smoke else 200)
    rounds = args.rounds or (2 if args.smoke else 5)
    n_requests = args.requests or (256 if args.smoke else 2048)
    levels = [int(s) for s in args.levels.split(",") if s]

    result, bank, engine, timings = build_serving(
        n_clients, rounds, args.clusters, args.seed)
    print(f"trained n={n_clients} rounds={rounds} in {timings['train_s']}s; "
          f"bank {bank.n_models}x{bank.n_params} params, snapshot "
          f"{timings['snapshot_ms']}ms, verify {timings['verify_ms']}ms")

    ok = routing_check(engine, args.seed)
    if not ok:
        raise SystemExit("routing check FAILED: fused mixed-batch dispatch "
                         "is not bitwise-identical to per-request routing")

    rows = [bench_level(engine, c, n_requests, args.seed + c)
            for c in levels]
    print(f"{'conc':>5} {'p50 ms':>9} {'p99 ms':>9} {'req/s':>10} "
          f"{'flushes':>8}")
    for r in rows:
        print(f"{r['concurrency']:>5} {r['p50_ms']:>9.3f} "
              f"{r['p99_ms']:>9.3f} {r['req_per_s']:>10.1f} "
              f"{r['flushes']:>8}")

    doc = {
        "bench": "serve",
        "smoke": bool(args.smoke),
        "n_clients": n_clients,
        "rounds": rounds,
        "n_clusters": bank.n_models,
        "n_params": bank.n_params,
        "bank_bytes": bank.nbytes,
        "release_block": bank.block_hash[:16],
        "routing_bitwise_ok": ok,
        **timings,
        "levels": rows,
    }
    with open(args.out, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
        f.write("\n")
    print(f"wrote {args.out}")


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main()
