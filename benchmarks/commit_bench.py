"""Commit-path throughput: legacy `hash_params` loop vs batched fingerprints.

Measures one full commit+verify round — client commitments, producer
aggregation record, block packing, consensus verification — over a
100-client cohort of the 1000-client sim population's model, two ways:

  * ``hash_params`` baseline (retired hot path): a Python loop that
    `device_get`s every cohort member's FULL params and SHA-256s them —
    `O(cohort · N_params)` host bytes per round;
  * batched fingerprint pipeline (`repro.kernels.fingerprint` +
    `repro.blockchain.commit`): ONE jitted device pass, `O(cohort)` digest
    bytes to the host, sender-bound Merkle commitments.

Also checks the two pipelines agree on every verification decision under
tamper, and that the new pipeline's block hashes replay identically.

Prints ``commit,<name>,<us_per_round>,<derived>`` CSV like the other benches.
"""
from __future__ import annotations

import json
import time

import jax
import numpy as np

from repro.blockchain import (
    AGG_COMMIT_KIND,
    Blockchain,
    RoundCommitments,
    Transaction,
    TxPool,
    hash_params,
)
from repro.kernels.fingerprint import cohort_digests
from repro.models import classifier as clf
from repro.utils.tree import tree_bytes, tree_index

POPULATION = 1000
COHORT = 100


def _cohort_params():
    cfg = clf.MLPConfig(in_dim=64, hidden=(64,), rep_dim=32, num_classes=10)
    stacked = clf.init_stacked(cfg, jax.random.PRNGKey(0), POPULATION)
    return jax.tree.map(lambda x: x[:COHORT], stacked)


def _tamper_slots():
    return {3: "deadbeef" * 3, 42: "cafef00d" * 3}   # digest substitutions


def round_legacy(params, tamper) -> tuple[Blockchain, np.ndarray]:
    """Retired pipeline: per-client device_get + SHA-256, set-membership."""
    chain, pool = Blockchain(), TxPool()
    honest = []
    for slot in range(COHORT):
        h = hash_params(tree_index(params, slot))
        pool.submit(Transaction("model_hash", slot, tamper.get(slot, h), 0))
        honest.append(h)
    pool.submit(Transaction("agg_hash", 0, json.dumps(sorted(honest)), 0))
    block = chain.pack_block(0, 0, pool)
    return chain, chain.verify_round(block, COHORT)


def round_fingerprint(params, tamper) -> tuple[Blockchain, np.ndarray]:
    """Batched pipeline: one jitted fingerprint pass, sender-bound commit."""
    chain, pool = Blockchain(), TxPool()
    digests = cohort_digests(params)
    for slot in range(COHORT):
        pool.submit(Transaction("model_hash", slot,
                                tamper.get(slot, digests[slot]), 0))
    commits = RoundCommitments(0, tuple(enumerate(digests)))
    pool.submit(Transaction(AGG_COMMIT_KIND, 0, commits.to_payload(), 0))
    block = chain.pack_block(0, 0, pool)
    return chain, chain.verify_round(block, COHORT)


def _time_rounds(fn, params, tamper, iters: int) -> float:
    fn(params, tamper)                               # warm (jit compile)
    t0 = time.perf_counter()
    for _ in range(iters):
        fn(params, tamper)
    return (time.perf_counter() - t0) / iters * 1e6


def main(iters: int = 5) -> None:
    params = _cohort_params()
    tamper = _tamper_slots()

    _, dec_legacy = round_legacy(params, tamper)
    _, dec_fast = round_fingerprint(params, tamper)
    assert (dec_legacy == dec_fast).all(), "verification decisions diverge"
    expected = np.array([s not in tamper for s in range(COHORT)])
    assert (dec_fast == expected).all()

    chain_a, _ = round_fingerprint(params, tamper)
    chain_b, _ = round_fingerprint(params, tamper)
    assert [b.block_hash() for b in chain_a.blocks] == \
        [b.block_hash() for b in chain_b.blocks], "block hashes not replayable"

    us_legacy = _time_rounds(round_legacy, params, tamper, iters)
    us_fast = _time_rounds(round_fingerprint, params, tamper, iters)
    speedup = us_legacy / us_fast

    host_bytes_legacy = tree_bytes(params)           # full cohort params
    host_bytes_fast = COHORT * 8                     # 2 × uint32 per client
    print(f"commit,hash_params_baseline,{us_legacy:.0f},"
          f"cohort={COHORT} host_bytes={host_bytes_legacy}")
    print(f"commit,fingerprint_pipeline,{us_fast:.0f},"
          f"cohort={COHORT} host_bytes={host_bytes_fast} "
          f"speedup={speedup:.1f}x decisions_match=True replay_identical=True")
    if speedup < 10:
        print(f"commit,WARNING,0,speedup {speedup:.1f}x below the 10x target")


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main()
