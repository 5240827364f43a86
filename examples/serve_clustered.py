"""Clustered serving: chain-verified personalized inference via `repro.serve`.

After BFLN training, each spectral cluster owns a personalized model (the
cluster FedAvg).  This example trains a real population with `repro.api.run`,
snapshots the per-cluster models into a fingerprinted model bank anchored to
the blockchain by a release block, then serves a mixed-cluster request batch
in ONE fused dispatch — and demonstrates the refuse-to-serve gate by
tampering with a model and watching verification fail.

    PYTHONPATH=src python examples/serve_clustered.py

Runs on CPU in well under a minute.
"""
import numpy as np

import repro.api as api
from repro.serve import (ProvenanceError, ServeConfig, ServeFrontend,
                         ServingEngine, snapshot, tampered, verify_bank)
from repro.sim.clock import VirtualClock


def main():
    # 1. train a small non-IID population (PAA clustering + chain incentive)
    spec = api.ExperimentSpec(
        data=api.DataSpec(n_clients=60),
        train=api.TrainSpec(rounds=3, sample_frac=0.3, n_clusters=3),
        eval=api.EvalSpec(every=0, clients=16, examples=64),
        seed=0)
    result = api.run(spec)
    chain = result.sim.trainer.chain
    print(f"trained: {len(chain.blocks)} blocks on chain, "
          f"final accuracy {result.report.final_accuracy:.3f}")

    # 2. snapshot -> model bank; publishes a release block whose Merkle root
    #    commits every cluster model's Pallas fingerprint, then verifies it
    bank = snapshot(result)
    print(f"bank: {bank.n_models} cluster models x {bank.n_params} params "
          f"({bank.nbytes} bytes), anchored to block {bank.block_hash[:12]}, "
          f"round {bank.round_idx}")

    # 3. serve a mixed-cluster batch in one fused dispatch
    engine = ServingEngine(bank, chain)   # re-verifies provenance on load
    clock = VirtualClock()
    fe = ServeFrontend(engine, ServeConfig(buckets=(1, 2, 4, 8)), clock=clock)
    rng = np.random.default_rng(0)
    for i in range(8):
        fe.submit(i % bank.n_models,
                  rng.standard_normal(bank.mcfg.in_dim).astype(np.float32))
    fe.drain()
    for c in fe.take_completed():
        pred = int(np.argmax(c.logits))
        print(f"  req {c.req_id}: cluster {c.cluster_id} -> class {pred}")
    print(f"served 8 mixed-cluster requests, "
          f"compiles={engine.cache_sizes()}")

    # 4. tamper-refusal: perturb one model by 0.01% -> the recomputed
    #    fingerprint no longer matches the on-chain release and the gate
    #    refuses to serve
    bad = tampered(bank, cluster_id=1)
    try:
        verify_bank(bad, chain)
        raise AssertionError("tampered bank must not verify")
    except ProvenanceError as e:
        print(f"tampered bank refused: {e}")
    try:
        ServingEngine(bad, chain)
        raise AssertionError("engine must refuse a tampered bank")
    except ProvenanceError:
        print("engine load refused the tampered bank as well")


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main()
