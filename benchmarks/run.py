# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark harness:

  kernels_bench    — Pallas kernels vs oracles (µs/call)
  commit_bench     — chain commit+verify path: hash_params vs fingerprints
  round_bench      — sync-round hot path: legacy driver vs fused engine
  fig2_rewards     — paper Fig. 2 (reward trends vs cluster size)
  table2_accuracy  — paper Table II (accuracy under label skew)
  sim_bench        — event-driven federation simulator throughput
  roofline         — §Roofline table from the dry-run artifacts

``python -m benchmarks.run [--full] [--rounds N]``
"""
from __future__ import annotations

import argparse
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="all 3 datasets in table2 (slow on CPU)")
    ap.add_argument("--rounds", type=int, default=12)
    ap.add_argument("--skip-table2", action="store_true")
    ap.add_argument("--skip-sim", action="store_true")
    ap.add_argument("--skip-round", action="store_true")
    args = ap.parse_args()

    t0 = time.time()
    from benchmarks import (commit_bench, fig2_rewards, kernels_bench,
                            roofline, round_bench, sim_bench, table2_accuracy)

    print("# kernels")
    kernels_bench.main()
    print("# commit (chain commitment path)")
    commit_bench.main()
    if not args.skip_round:
        print("# round (legacy driver vs fused engine)")
        # only a --full run refreshes the tracked BENCH_round.json artifact
        round_bench.main(n_clients=1000 if args.full else 200,
                         rounds=50 if args.full else 10,
                         out="BENCH_round.json" if args.full
                         else "/tmp/BENCH_round_quick.json",
                         heavy_eval=args.full)
    print("# fig2 (reward trends)")
    fig2_rewards.main(rounds=min(args.rounds, 10))
    if not args.skip_table2:
        print("# table2 (accuracy)")
        table2_accuracy.main(args.full, args.rounds)
    if not args.skip_sim:
        print("# sim (federation simulator throughput)")
        sim_bench.main(quick=not args.full)
    print("# roofline")
    roofline.main()
    print(f"bench,total_wall_s,{time.time()-t0:.0f},done")


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main()
