"""The BFLN federated round driver (paper Fig. 1, steps 1–6).

The jittable inner program (local training + aggregation) is wrapped by the
host-side blockchain protocol (hash commitments, block packing, consensus
verification, token settlement).  The same driver runs every baseline strategy
— baselines simply skip the chain (no clustering → no CACC queue).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.blockchain import (
    AGG_COMMIT_KIND,
    Blockchain,
    RoundCommitments,
    TokenLedger,
    Transaction,
    TxPool,
)
from repro.core import consensus as cacc
from repro.core.baselines import AggOut, ModelBundle, Strategy
from repro.core.fl import LocalTrainResult, global_evaluate, local_train
from repro.core.incentives import allocate_rewards
from repro.faults import NULL_INJECTOR
from repro.kernels.fingerprint import cohort_digests
from repro.obs import NULL_RECORDER
from repro.optim import Optimizer

Pytree = Any


def digest_of(params: Pytree) -> str:
    """Fingerprint digest of ONE client's (unstacked) param pytree — the
    commitment a client would make for these params.  Convenience wrapper
    for tests/tamper payloads; the round hot path digests the whole cohort
    in one batched call instead."""
    stacked = jax.tree.map(lambda x: x[None], params)
    return cohort_digests(stacked)[0]


@dataclass
class RoundRecord:
    round_idx: int
    mean_loss: float
    accuracy: float
    labels: np.ndarray | None = None
    cluster_sizes: np.ndarray | None = None
    rewards: np.ndarray | None = None
    balances: np.ndarray | None = None
    producer: int = -1
    verified_frac: float = 1.0


@dataclass
class ChainRoundResult:
    """Outcome of the host-side blockchain protocol for one round's cohort."""
    producer: int               # global client id of the packing client
    verified: np.ndarray        # (n_cohort,) consensus verification mask
    rewards: np.ndarray         # (n_cohort,) settled rewards (0 if unverified)
    block: Any = None


@dataclass
class FederatedTrainer:
    """Runs strategy rounds over stacked clients; BFLN adds the chain.

    ``strategy`` may be a built :class:`Strategy` or a registry name
    (`repro.api.registry`) — a string is resolved at construction against
    ``model``/``probe``/``n_clusters``, so
    ``FederatedTrainer(bundle, "fedprox", opt)`` just works.
    """

    model: ModelBundle
    strategy: Strategy | str
    opt: Optimizer
    local_epochs: int = 5
    n_clusters: int = 0              # >0 enables CACC/chain (BFLN)
    total_reward: float = 20.0       # paper: "Local training total stake reward"
    rho: float = 2.0                 # paper Table I
    initial_stake: float = 5.0       # paper Table I
    use_chain: bool = True
    probe: Any = None                # PAA probe batch (name-resolved bfln)
    history: list[RoundRecord] = field(default_factory=list)

    def __post_init__(self):
        if isinstance(self.strategy, str):
            from repro.api.registry import build_strategy
            self.strategy = build_strategy(
                self.strategy, self.model, probe=self.probe,
                n_clusters=self.n_clusters)
        self.chain = Blockchain()
        self.pool = TxPool()
        self.ledger: TokenLedger | None = None
        self._queue: list[int] = []
        self.obs = NULL_RECORDER
        self.faults = NULL_INJECTOR

        strategy = self.strategy

        @jax.jit
        def _train_round(stacked_params, stacked_opt, cx, cy):
            extras = strategy.round_extras(stacked_params, cx, cy)
            res: LocalTrainResult = local_train(
                strategy.local_loss, self.opt, stacked_params, stacked_opt,
                cx, cy, extras, self.local_epochs,
                shared_extras=strategy.shared_extras)
            agg: AggOut = strategy.aggregate(res.params, cx, cy)
            return res.params, agg, res.opt_state, jnp.mean(res.mean_loss)

        self._train_round = _train_round
        self._eval = jax.jit(partial(global_evaluate, self.model.apply_fn))

    # ------------------------------------------------------------------ #

    def attach_obs(self, obs) -> None:
        """Bind a flight recorder (`repro.obs`) to the trainer and its chain
        components.  Called after construction so it also covers a ledger
        the simulator swapped in."""
        self.obs = obs
        self.chain.obs = obs
        if self.ledger is not None:
            self.ledger.obs = obs

    def attach_faults(self, faults) -> None:
        """Bind a fault injector (`repro.faults`) so the chain protocol can
        absorb injected producer failures, bad blocks, and commit-delivery
        faults.  Default: the shared no-op injector."""
        self.faults = faults

    def init(self, stacked_params: Pytree) -> tuple[Pytree, Pytree]:
        n = jax.tree.leaves(stacked_params)[0].shape[0]
        if self.use_chain:
            self.ledger = TokenLedger(n, self.initial_stake)
            self.ledger.obs = self.obs
        opt_state = jax.vmap(self.opt.init)(stacked_params)
        return stacked_params, opt_state

    def run_round(
        self,
        round_idx: int,
        stacked_params: Pytree,
        stacked_opt: Pytree,
        cx: jax.Array,
        cy: jax.Array,
        test_x: jax.Array,
        test_y: jax.Array,
        tamper: dict[int, Pytree] | None = None,
    ) -> tuple[Pytree, Pytree, RoundRecord]:
        """One full BFLN round.  ``tamper`` (tests only) swaps the params a
        client *claims* (hash-commits) for something else, exercising the
        consensus rejection path."""
        n = cx.shape[0]

        local_params, agg, stacked_opt, mean_loss = self._train_round(
            stacked_params, stacked_opt, cx, cy)

        record = RoundRecord(round_idx, float(mean_loss), 0.0)

        if self.use_chain and agg.labels is not None:
            cres = self.chain_round(round_idx, local_params, agg.labels,
                                    agg.corr, tamper=tamper)
            record.labels = np.asarray(agg.labels)
            record.cluster_sizes = np.asarray(agg.cluster_sizes)
            record.rewards = cres.rewards
            record.balances = self.ledger.balances.copy()
            record.producer = cres.producer
            record.verified_frac = float(cres.verified.mean())

        record.accuracy = float(self._eval(agg.stacked_params, test_x, test_y))
        self.history.append(record)
        return agg.stacked_params, stacked_opt, record

    def chain_round(
        self,
        round_idx: int,
        local_params: Pytree | None,
        labels: np.ndarray | jax.Array,
        corr: np.ndarray | jax.Array,
        cohort: np.ndarray | None = None,
        arrived: np.ndarray | None = None,
        tamper: dict[int, str | Pytree] | None = None,
        digests: list[str] | None = None,
    ) -> ChainRoundResult:
        """Host-side blockchain protocol (Fig. 1 steps 2/5/6) over one round's
        *cohort* — the clients that actually trained this round.

        ``local_params`` is cohort-stacked (slot axis); ``cohort`` maps slot →
        global client id (default: identity over the full population — the
        paper's 20-always-on-clients setting).  ``arrived`` masks slots whose
        update reached the producer before the block slot: stragglers and
        dropouts (``repro.sim``) never commit a digest and are never
        aggregated — they simply miss the round.  ``tamper`` (keyed by global
        client id) substitutes the digest a client *commits* — either a digest
        string directly or a param pytree to digest — exercising the consensus
        rejection path.

        Commitments are batched and device-side: ONE jitted fingerprint call
        digests the whole cohort, and the host pulls `O(cohort)` digest bytes
        — never per-client full params (`repro.kernels.fingerprint`).

        ``digests`` (per-slot digest strings) may be precomputed — the fused
        round engine (`repro.core.engine`) fingerprints the cohort inside its
        single jitted step, so the protocol here never touches params at all
        (``local_params`` may then be ``None``).

        CACC and the reward split run in NumPy on the host: with NumPy
        ``labels``, ``corr`` and ``digests`` the settlement dispatches no
        device program and makes no transfer.  Device arrays are read back.
        """
        assert self.ledger is not None
        labels = np.asarray(labels)
        k = labels.shape[0]
        cohort = np.arange(k) if cohort is None else np.asarray(cohort)
        arrived = np.ones(k, bool) if arrived is None else np.asarray(arrived, bool)
        n_total = self.ledger.n_clients
        tamper = tamper or {}

        if not arrived.any():
            # nobody delivered an update: no block, the round's pool stays unminted
            return ChainRoundResult(-1, np.zeros(k, bool), np.zeros(k))

        obs = self.obs
        if digests is None:
            # one fingerprint pass over the cohort-stacked params (slot-indexed)
            with obs.span("chain.digests", cat="chain", round=round_idx):
                digests = cohort_digests(local_params)

        # -- Fig.1 step 2: arrived clients commit model digests ------------ #
        faults = self.faults
        with obs.span("chain.commit", cat="chain", round=round_idx) as sp:
            # commits a fault delayed in an earlier round arrive only now —
            # they land in THIS block, where verification ignores them
            # (model_hash txs from another round carry no weight)
            for late in faults.release_commits():
                self.pool.submit(late)
                obs.event("fault.commit_delivered_late", round=round_idx,
                          client=late.sender, from_round=late.round_idx)
            entries: list[tuple[int, str]] = []  # what the producer aggregated
            arrived_slots = [s for s in range(k) if arrived[s]]
            drop_i = faults.commit_drop_slot(round_idx, len(arrived_slots))
            delay_i = faults.commit_delay_slot(round_idx, len(arrived_slots))
            for j, slot in enumerate(arrived_slots):
                gid = int(cohort[slot])
                claimed = tamper.get(gid, digests[slot])
                if not isinstance(claimed, str):
                    claimed = digest_of(claimed)
                tx = Transaction("model_hash", gid, claimed, round_idx)
                if j == drop_i:
                    # lost in transit: the producer aggregated this client's
                    # update, but its commit never reaches the pool — the
                    # client fails verification and forfeits its reward
                    obs.event("fault.commit_dropped", round=round_idx,
                              client=gid)
                    obs.inc("fault.commit_dropped")
                elif j == delay_i:
                    faults.hold_commit(tx)
                    obs.event("fault.commit_delayed", round=round_idx,
                              client=gid)
                    obs.inc("fault.commit_delayed")
                else:
                    self.pool.submit(tx)
                entries.append((gid, digests[slot]))
            sp.set(n_commits=len(entries))

        # -- CACC: centroid representatives -> packing queue --------------- #
        with obs.span("chain.consensus", cat="chain", round=round_idx):
            sel = cacc.select_centroid_clients(corr, labels, self.n_clusters)
            queue = [int(cohort[slot])
                     for slot in cacc.packing_queue(sel.representatives)]
            self._queue = queue or self._queue or [int(cohort[0])]
            active = {int(g) for g in cohort[arrived]}
            try:
                producer = cacc.producer_for_round(self._queue, round_idx,
                                                   active)
            except ValueError:
                producer = min(active)  # no representative arrived this round
            if faults.producer_fails(round_idx):
                # producer death mid-pack: fail over to the next consensus
                # candidate, exactly as every validator would recompute the
                # slot from the same queue and the reduced active set
                remaining = active - {producer}
                if remaining:
                    failed = producer
                    try:
                        producer = cacc.producer_for_round(
                            self._queue, round_idx, remaining)
                    except ValueError:
                        producer = min(remaining)
                    obs.event("fault.producer_failover", round=round_idx,
                              failed=failed, successor=producer)
                    obs.inc("fault.producer_failover")
                # a sole active client has no successor: it keeps the slot

        # -- Fig.1 step 5: producer records sender-bound commitments ------- #
        commits = RoundCommitments(round_idx, tuple(entries))
        self.pool.submit(Transaction(
            AGG_COMMIT_KIND, producer, commits.to_payload(), round_idx))
        block = self.chain.pack_block(round_idx, producer, self.pool,
                                      faults=faults)

        # -- Fig.1 step 6: consensus verification + incentives ------------- #
        verified_total = self.chain.verify_round(block, n_total)
        with obs.span("chain.rewards", cat="chain", round=round_idx):
            alloc = allocate_rewards(labels, self.n_clusters,
                                     self.total_reward, self.rho,
                                     participating=arrived)
            rewards_total = np.zeros(n_total)
            rewards_total[cohort] = alloc.client_reward
            self.ledger.mint_reward_pool(self.total_reward)
            self.ledger.settle_round(rewards_total, float(alloc.fee),
                                     producer, verified_total)

        verified = verified_total[cohort]
        rewards = np.where(verified, rewards_total[cohort], 0.0)
        return ChainRoundResult(producer, verified, rewards, block)

    def fit(self, stacked_params: Pytree, cx, cy, test_x, test_y,
            rounds: int, log_every: int = 0,
            log_fn: Callable[[str], None] = print) -> Pytree:
        stacked_params, stacked_opt = self.init(stacked_params)
        for r in range(rounds):
            stacked_params, stacked_opt, rec = self.run_round(
                r, stacked_params, stacked_opt, cx, cy, test_x, test_y)
            if log_every and (r % log_every == 0 or r == rounds - 1):
                log_fn(f"[{self.strategy.name}] round {r:3d} "
                       f"loss={rec.mean_loss:.4f} acc={rec.accuracy:.4f}"
                       + (f" clusters={rec.cluster_sizes.tolist()}"
                          if rec.cluster_sizes is not None else ""))
        return stacked_params
