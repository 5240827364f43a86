"""Paper Figure 2: reward trends vs cluster membership.

Runs BFLN with 2 and 7 clusters, dumps per-client cumulative rewards and
per-round cluster sizes, and checks the paper's qualitative claims:
  * clients in larger clusters accumulate more tokens,
  * more clusters -> more dispersed reward distribution.
"""
from __future__ import annotations

import json
import os

import numpy as np

from benchmarks.common import run_fl


def main(rounds: int = 10, out_path: str = "experiments/fig2.json"):
    out = {}
    for n_clusters in (2, 7):
        tr, _ = run_fl("synth10", 0.1, "bfln", rounds=rounds,
                       n_clusters=n_clusters)
        rewards = np.stack([h.rewards for h in tr.history])          # (R, m)
        sizes = np.stack([h.cluster_sizes[h.labels] for h in tr.history])
        cum = rewards.sum(axis=0)
        mean_size = sizes.mean(axis=0)
        corr = float(np.corrcoef(cum, mean_size)[0, 1])
        spread = float(cum.std())
        out[f"clusters-{n_clusters}"] = {
            "cumulative_rewards": cum.tolist(),
            "mean_cluster_size": mean_size.tolist(),
            "reward_size_correlation": corr,
            "reward_spread": spread,
            "balances": tr.ledger.balances.tolist(),
            "chain_valid": tr.chain.validate(),
            "ledger_conserved": tr.ledger.conserved(),
        }
        print(f"fig2,clusters-{n_clusters},corr={corr:.3f},spread={spread:.3f}",
              flush=True)
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(out, f, indent=1)
    return out


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main()
