"""Paper Table II: accuracy under non-IID label skew.

BFLN (cluster counts 2/5/7) vs FedAvg / FedHKD / FedProto / FedProx on the
synthetic stand-in datasets at bias β ∈ {0.1, 0.3, 0.5} (20 clients, the
paper's protocol at reduced round count — CPU container).  The validated
claims are the paper's *relative* ones; see EXPERIMENTS.md §Accuracy.
"""
from __future__ import annotations

import argparse
import json
import os
import time

from benchmarks.common import run_fl

STRATEGIES = ["bfln-2", "bfln-5", "bfln-7", "fedavg", "fedprox", "fedproto",
              "fedhkd"]


def run(datasets, biases, rounds, out_path):
    results = {}
    for ds in datasets:
        for bias in biases:
            for strat in STRATEGIES:
                t0 = time.time()
                if strat.startswith("bfln"):
                    _, acc = run_fl(ds, bias, "bfln", rounds=rounds,
                                    n_clusters=int(strat.split("-")[1]))
                else:
                    _, acc = run_fl(ds, bias, strat, rounds=rounds)
                key = f"{ds}-{bias}-{strat}"
                results[key] = acc
                print(f"table2,{key},{acc:.4f},{time.time()-t0:.0f}s", flush=True)
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(results, f, indent=1)
    return results


def main(full: bool = False, rounds: int = 12,
         out_path: str = "experiments/table2.json"):
    # synth100 is the informative regime (100 classes — the global model
    # can't cover every client's skew, like CIFAR100 in the paper);
    # synth10/synthdigits saturate quickly, mirroring the paper's
    # "SVHN improvements are less pronounced" observation.
    datasets = (["synth10", "synth100", "synthdigits"] if full
                else ["synth10", "synth100"])
    biases = [0.1, 0.3, 0.5]
    return run(datasets, biases, rounds, out_path)


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--rounds", type=int, default=12)
    args = ap.parse_args()
    main(args.full, args.rounds)
