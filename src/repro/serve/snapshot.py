"""Model bank snapshots: trained population -> K verified cluster models.

BFLN's end product is K cluster-personalized models.  This module extracts
them from a finished run's (possibly sharded) parameter arena into a
fixed-shape ``(K, n_params)`` stacked **model bank**, fingerprints every
bank row with the Pallas digest kernel, and anchors the release on the
run's own blockchain:

  * :func:`snapshot` — one fixed-shape jitted program computes the masked
    per-cluster mean over client rows (cluster-c model = FedAvg of every
    client whose latest chain-recorded assignment is c) AND the bank's
    fingerprint residues; the arena is gathered to host first so the bank
    is bit-identical across mesh widths (replicate-before-reduce, the PR 7
    discipline);
  * :func:`publish_release` — mints a **release block**: one
    ``model_release`` tx per cluster plus the producer's sender-bound
    ``release_commit`` (`repro.blockchain.commit.RoundCommitments` keyed by
    cluster id), so each served model carries an O(log K) Merkle membership
    proof.  Training-round digests commit the *locally trained* params and
    never cover the aggregates — the release block is what puts the served
    artifacts on chain;
  * :func:`verify_bank` — the refuse-to-serve gate: recompute every bank
    row's fingerprint from the weights actually loaded and check it against
    the chain's **latest** release via `commit.verify_membership`.  Tampered
    weights, a tampered digest, a wrong cluster id, a wrong release round,
    and a stale root (bank from an older release than the chain head's) all
    raise :class:`ProvenanceError`.

Banks round-trip through one ``.npz`` file (:meth:`ModelBank.save` /
:func:`load_bank`); loading re-verifies against a chain when one is given.
"""
from __future__ import annotations

import functools
import json
from dataclasses import dataclass, replace
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.blockchain.chain import Block, Blockchain
from repro.blockchain.commit import (
    MODEL_RELEASE_KIND,
    RELEASE_COMMIT_KIND,
    MerkleProof,
    RoundCommitments,
    verify_membership,
)
from repro.blockchain.txpool import Transaction, TxPool
from repro.kernels.fingerprint import fingerprint_rows, format_digest
from repro.models import classifier as clf
from repro.obs import NULL_RECORDER
from repro.runtime.arena import ArenaLayout, bitcast_u32
from repro.utils.tree import tree_index

Pytree = Any


class ProvenanceError(RuntimeError):
    """A served model's chain provenance failed — refuse to load or serve."""


@dataclass(frozen=True)
class ModelRelease:
    """Per-cluster provenance record: the released digest and its Merkle
    membership proof under the release block's commitment root."""
    cluster_id: int
    digest: str
    proof: MerkleProof


@dataclass(frozen=True)
class ModelBank:
    """K cluster-personalized models as one fixed-shape stacked bank, plus
    the chain provenance that makes them servable."""
    mcfg: clf.MLPConfig
    layout: ArenaLayout
    data: jax.Array                       # (K, n_params) float32
    releases: tuple[ModelRelease, ...]    # one per cluster, id order
    root: str                             # release commitments' Merkle root
    round_idx: int                        # release round (past last training round)
    block_hash: str                       # hash of the release block

    @property
    def n_models(self) -> int:
        return int(self.data.shape[0])

    @property
    def n_params(self) -> int:
        return int(self.data.shape[1])

    @property
    def nbytes(self) -> int:
        return int(self.data.size) * 4

    def model_pytree(self, cluster_id: int) -> Pytree:
        """Cluster ``cluster_id``'s model as a plain (unstacked) pytree."""
        return tree_index(self.layout.unflatten(self.data), cluster_id)

    def digests(self) -> list[str]:
        return [r.digest for r in self.releases]

    # ------------------------------------------------------------------ #
    # disk round-trip
    # ------------------------------------------------------------------ #

    def save(self, path: str) -> None:
        """One-file ``.npz``: bank matrix + JSON provenance/arch metadata."""
        meta = {
            "mcfg": {"in_dim": self.mcfg.in_dim,
                     "hidden": list(self.mcfg.hidden),
                     "rep_dim": self.mcfg.rep_dim,
                     "num_classes": self.mcfg.num_classes},
            "releases": [
                {"cluster_id": r.cluster_id, "digest": r.digest,
                 "proof": {"leaf": r.proof.leaf,
                           "path": [[sib, side] for sib, side in r.proof.path]}}
                for r in self.releases],
            "root": self.root,
            "round_idx": self.round_idx,
            "block_hash": self.block_hash,
        }
        with open(path, "wb") as f:
            np.savez(f, data=np.asarray(jax.device_get(self.data)),
                     meta=np.frombuffer(json.dumps(meta, sort_keys=True)
                                        .encode(), dtype=np.uint8))


def load_bank(path: str, chain: Blockchain | None = None, *,
              obs=NULL_RECORDER) -> ModelBank:
    """Load a saved bank; with ``chain`` given, refuse (raise
    :class:`ProvenanceError`) unless every model verifies against the
    chain's latest release."""
    with np.load(path) as z:
        data = jnp.asarray(z["data"])
        meta = json.loads(bytes(z["meta"].tobytes()).decode())
    mcfg = clf.MLPConfig(in_dim=int(meta["mcfg"]["in_dim"]),
                         hidden=tuple(meta["mcfg"]["hidden"]),
                         rep_dim=int(meta["mcfg"]["rep_dim"]),
                         num_classes=int(meta["mcfg"]["num_classes"]))
    releases = tuple(
        ModelRelease(int(r["cluster_id"]), str(r["digest"]),
                     MerkleProof(str(r["proof"]["leaf"]),
                                 tuple((str(s), str(side))
                                       for s, side in r["proof"]["path"])))
        for r in meta["releases"])
    # layout from an architecture template: ArenaLayout records only paths /
    # shapes / dtypes, so a 1-row init reproduces the training layout exactly
    template = clf.init_stacked(mcfg, jax.random.PRNGKey(0), 1)
    bank = ModelBank(mcfg=mcfg, layout=ArenaLayout.from_stacked(template),
                     data=data, releases=releases, root=str(meta["root"]),
                     round_idx=int(meta["round_idx"]),
                     block_hash=str(meta["block_hash"]))
    if chain is not None:
        verify_bank(bank, chain, obs=obs)
    return bank


# ---------------------------------------------------------------------- #
# extraction
# ---------------------------------------------------------------------- #

@functools.partial(jax.jit, static_argnames=("n_clusters",))
def _extract_bank(rows: jax.Array, labels: jax.Array, valid: jax.Array, *,
                  n_clusters: int):
    """Fixed-shape bank extraction + fingerprinting in ONE program.

    ``rows`` (n, N) client params, ``labels`` (n,) last cluster assignment
    (-1 = never assigned), ``valid`` (n,) 1.0 for real client rows.  A
    cluster with no assigned clients falls back to the mean over all
    labeled clients, and — when nobody was ever labeled (e.g. async mode,
    where every client tracks the one global model) — to the mean over all
    valid rows.  Out-of-range labels vanish from ``one_hot``, so -1 rows
    never contribute to any cluster.
    """
    onehot = jax.nn.one_hot(labels, n_clusters, dtype=rows.dtype)
    onehot = onehot * valid[:, None]
    counts = onehot.sum(axis=0)                             # (K,)
    sums = onehot.T @ rows                                  # (K, N)
    labeled = counts.sum()
    labeled_mean = sums.sum(axis=0) / jnp.maximum(labeled, 1.0)
    global_mean = ((rows * valid[:, None]).sum(axis=0)
                   / jnp.maximum(valid.sum(), 1.0))
    fallback = jnp.where(labeled > 0, labeled_mean, global_mean)
    bank = jnp.where((counts > 0)[:, None],
                     sums / jnp.maximum(counts, 1.0)[:, None],
                     fallback[None, :])
    return bank, fingerprint_rows(bitcast_u32(bank))


@jax.jit
def _fingerprint_bank(bank_rows: jax.Array) -> jax.Array:
    """Residues of the bank rows as loaded — the verification-side digest."""
    return fingerprint_rows(bitcast_u32(bank_rows))


def bank_digests(bank_rows: jax.Array, n_params: int) -> list[str]:
    """Recompute per-model digests from the actual bank weights."""
    residues = np.asarray(jax.device_get(_fingerprint_bank(bank_rows)))
    return [format_digest(residues[c], n_params)
            for c in range(bank_rows.shape[0])]


# ---------------------------------------------------------------------- #
# release block
# ---------------------------------------------------------------------- #

def publish_release(chain: Blockchain, pool: TxPool, digests: list[str], *,
                    producer: int | None = None,
                    obs=NULL_RECORDER) -> tuple[Block, RoundCommitments]:
    """Mint the release block: per-cluster ``model_release`` txs plus the
    producer's sender-bound ``release_commit`` (senders = cluster ids).

    The release round is ``head.round_idx + 1`` — strictly past every
    training round, so release leaves can never collide with (or replay
    into) a training round's commitments.  The producer defaults to the
    head block's packing producer.
    """
    round_idx = chain.head.round_idx + 1
    if producer is None:
        producer = chain.head.producer
    for cluster_id, digest in enumerate(digests):
        pool.submit(Transaction(MODEL_RELEASE_KIND, cluster_id, digest,
                                round_idx))
    rc = RoundCommitments(round_idx, tuple(enumerate(digests)))
    pool.submit(Transaction(RELEASE_COMMIT_KIND, producer, rc.to_payload(),
                            round_idx))
    block = chain.pack_block(round_idx, producer, pool)
    obs.inc("serve.releases")
    return block, rc


def latest_release(chain: Blockchain) -> tuple[Block, RoundCommitments] | None:
    """The newest block carrying a release commitment (first ``release_commit``
    from the block's own producer wins, mirroring ``verify_round``)."""
    for block in reversed(chain.blocks):
        for tx in block.transactions:
            if tx.kind == RELEASE_COMMIT_KIND and tx.sender == block.producer:
                return block, RoundCommitments.from_payload(block.round_idx,
                                                            tx.payload)
    return None


# ---------------------------------------------------------------------- #
# the refuse-to-serve gate
# ---------------------------------------------------------------------- #

def verify_bank(bank: ModelBank, chain: Blockchain, *,
                obs=NULL_RECORDER) -> None:
    """Every served model must prove provenance against the chain's LATEST
    release block; anything less raises :class:`ProvenanceError`.

    Checks, in order: a release exists; the bank points at the head release
    (stale banks refuse — a newer release supersedes them); roots and
    release rounds agree; and per model, the fingerprint recomputed from the
    weights *actually in the bank* matches the recorded digest AND its
    Merkle proof places (cluster, round, digest) under the on-chain root.
    """
    with obs.span("serve.verify", cat="serve") as sp:
        rel = latest_release(chain)
        if rel is None:
            raise ProvenanceError(
                "refusing to serve: the chain carries no model release — "
                "publish one with repro.serve.publish_release / snapshot()")
        block, rc = rel
        if block.block_hash() != bank.block_hash:
            raise ProvenanceError(
                f"refusing to serve: stale release — bank was released in "
                f"block {bank.block_hash[:12]} (round {bank.round_idx}) but "
                f"the chain's latest release is block "
                f"{block.block_hash()[:12]} (round {block.round_idx})")
        if rc.root != bank.root:
            raise ProvenanceError(
                "refusing to serve: bank's commitment root does not match "
                "the release block's agg record")
        if block.round_idx != bank.round_idx:
            raise ProvenanceError(
                "refusing to serve: bank's release round does not match the "
                "release block")
        digests = bank_digests(bank.data, bank.n_params)
        for c, digest in enumerate(digests):
            r = bank.releases[c]
            if r.cluster_id != c or r.digest != digest:
                raise ProvenanceError(
                    f"refusing to serve model {c}: loaded weights fingerprint "
                    f"to {digest[:12]} but the release records "
                    f"{r.digest[:12]} for cluster {r.cluster_id}")
            if not verify_membership(rc.root, c, bank.round_idx, digest,
                                     r.proof):
                raise ProvenanceError(
                    f"refusing to serve model {c}: Merkle membership proof "
                    f"does not place (cluster={c}, round={bank.round_idx}, "
                    f"digest={digest[:12]}) under the release root")
        sp.set(n_models=bank.n_models, block=block.index)
    obs.inc("serve.verifications")


# ---------------------------------------------------------------------- #
# snapshot: finished run -> verified bank
# ---------------------------------------------------------------------- #

def snapshot(source, *, publish: bool = True, verify: bool = True,
             obs=NULL_RECORDER) -> ModelBank:
    """Extract the K cluster-personalized models from a finished run.

    ``source`` is an ``ExperimentResult`` (from ``repro.api.run``) or the
    underlying ``SimulatedFederation``.  The arena — sharded or not — is
    gathered to host and the extraction runs replicated on the default
    device, so the bank bytes are identical across mesh widths.  With
    ``publish`` the bank's digests are minted into a release block on the
    run's own chain; with ``verify`` the freshly built bank must pass
    :func:`verify_bank` before it is returned.
    """
    sim = getattr(source, "sim", source)
    if sim is None or not hasattr(sim, "trainer"):
        raise ValueError(
            "snapshot() needs a finished run: pass the ExperimentResult "
            "returned by repro.api.run(spec) (or the SimulatedFederation)")
    if sim.mcfg is None:
        raise ValueError(
            f"snapshot() serves the classifier MLP; this run trained "
            f"{sim.model.kind} adapters over a frozen base, and serving a "
            "generative LM bank (prefill, decode, cache) is not built yet")
    with obs.span("serve.snapshot", cat="serve") as sp:
        n = sim.pop.n_clients
        n_clusters = sim.cfg.n_clusters
        if sim.arena is not None:
            layout = sim.arena.layout
            rows = np.asarray(jax.device_get(sim.arena.data))[:n]
        else:
            layout = ArenaLayout.from_stacked(sim.params)
            rows = np.asarray(jax.device_get(layout.flatten(sim.params)))
        labels = np.asarray(sim.last_labels, dtype=np.int64)
        data, residues = _extract_bank(
            jnp.asarray(rows), jnp.asarray(labels),
            jnp.ones((n,), jnp.float32), n_clusters=n_clusters)
        residues = np.asarray(jax.device_get(residues))
        digests = [format_digest(residues[c], layout.n_params)
                   for c in range(n_clusters)]
        sp.set(n_models=n_clusters, n_params=layout.n_params)

    chain = sim.trainer.chain
    if publish:
        block, rc = publish_release(chain, sim.trainer.pool, digests, obs=obs)
    else:
        rel = latest_release(chain)
        if rel is None:
            # no release on chain: return an unanchored bank — verify_bank /
            # ServingEngine will refuse it, which is the point of the gate
            rc = RoundCommitments(chain.head.round_idx + 1,
                                  tuple(enumerate(digests)))
            bank = ModelBank(
                mcfg=sim.mcfg, layout=layout, data=data,
                releases=tuple(ModelRelease(c, d, rc.proof(c))
                               for c, d in enumerate(digests)),
                root=rc.root, round_idx=rc.round_idx, block_hash="")
            if verify:
                verify_bank(bank, chain, obs=obs)
            return bank
        block, rc = rel
    bank = ModelBank(
        mcfg=sim.mcfg, layout=layout, data=data,
        releases=tuple(ModelRelease(c, d, rc.proof(c))
                       for c, d in enumerate(digests)),
        root=rc.root, round_idx=block.round_idx,
        block_hash=block.block_hash())
    if verify:
        verify_bank(bank, chain, obs=obs)
    return bank


def tampered(bank: ModelBank, cluster_id: int, scale: float = 1.0001
             ) -> ModelBank:
    """A copy of ``bank`` with one model's weights perturbed — the
    adversarial fixture for refuse-to-serve tests and demos."""
    data = bank.data.at[cluster_id].multiply(scale)
    return replace(bank, data=data)
