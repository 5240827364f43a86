"""`SimulatedFederation` — event-driven federation over a virtual population.

Layers realistic client dynamics (sampling, stragglers, dropouts, Byzantine
freeriders) on top of the existing BFLN machinery.  The driver is
strategy-generic: the experiment's strategy (BFLN or any registered
baseline, `repro.api.registry`) supplies both the local objective and the
jittable mask-weighted ``aggregate_cohort`` stage the fused round engine
traces.  Configuration arrives as a nested `repro.api.ExperimentSpec` (the
canonical form, see `repro.api.run`) or the flat legacy :class:`SimConfig`
(deprecated shim).  Per synchronous round:

    1. availability draw → online pool → sampler picks the cohort,
    2. cohort events scheduled on the virtual clock (arrival, update-ready
       after per-client latency, dropout), block slot closes the round,
    3. ONE fused, buffer-donated jitted step (`repro.core.engine`): arena
       and data gather → local training → PAA (arrival mask = aggregation
       weights) → cohort fingerprint digests → masked scatter-back into the
       donated parameter arena (`repro.runtime.arena`),
    4. `FederatedTrainer.chain_round` runs the full blockchain protocol over
       the cohort — hash commits, CACC packing queue, block, verification,
       participation-aware reward settlement on the population-wide ledger.

Async mode (``mode="async"``) replaces 2–3 with FedBuff buffered
aggregation: clients train against dispatched snapshots, finished deltas
buffer up, and each buffer flush = one block + one staleness-weighted merge
(merge weights are *gated by chain verification*, so tampered updates carry
zero weight and zero reward).

``SimConfig.engine=False`` preserves the pre-arena driver — eager per-leaf
gathers/scatters and shape-polymorphic eval — as the bit-identical oracle
for the engine (`tests/test_engine.py`) and the baseline for
``benchmarks/round_bench.py``.

``SimConfig.mesh_shards > 1`` row-shards the parameter arena over a
client-axis device mesh (`repro.runtime.arena.ShardedParamArena`): each
device holds only ``n_clients/shards`` rows of population state while the
cohort working set replicates, so seeded replay stays bit-identical to the
single-device engine (`tests/test_sharded_engine.py`).

Everything is driven by seeded numpy generators and a deterministic event
queue: two runs with the same config produce identical event logs, block
hashes, ledger balances and final parameters — with the engine on or off.

Modeling notes: cohort members that miss the deadline still burn local
compute (their training is simulated) but their params never reach the
producer — they keep their previous personalized model and earn nothing.
Byzantine clients train honestly but *commit a hash for params they did not
train* (the paper's freeriding attack); CACC verification catches the
mismatch.
"""
from __future__ import annotations

import functools
import os
import warnings
from dataclasses import dataclass, field
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.blockchain import TokenLedger
from repro.core import FederatedTrainer, digest_of
from repro.core.engine import RoundEngine
from repro.core.fl import global_evaluate, local_train
from repro.faults import NULL_INJECTOR, FaultInjector
from repro.obs import NULL_RECORDER, FlightRecorder
from repro.optim import adam
from repro.runtime.arena import ParamArena, ShardedParamArena
from repro.sim import events as ev
from repro.sim.async_agg import (
    BufferedAggregator,
    BufferedUpdate,
    staleness_weight,
)
from repro.sim.clock import VirtualClock
from repro.sim.events import EventQueue
from repro.sim.population import ClientPopulation
from repro.sim.sampler import SamplerState, get_sampler
from repro.utils.tree import tree_index, tree_stack

Pytree = Any


_SIMCONFIG_INTERNAL = False    # True while repro.api builds the flat view


@functools.partial(jax.jit, static_argnums=(1,))
def _broadcast_rows(row: jax.Array, n: int) -> jax.Array:
    """(N,) -> a fresh (n, N) arena with ``row`` in every slot."""
    return jnp.broadcast_to(row[None], (n, row.shape[0])) + 0.0


@dataclass(frozen=True)
class SimConfig:
    """Flat legacy experiment config.

    .. deprecated::
        Build a nested :class:`repro.api.ExperimentSpec` instead (and run it
        with :func:`repro.api.run`).  ``SimConfig(...)`` keeps working as a
        shim: it validates, maps onto the nested spec via :meth:`to_spec`,
        and ``SimulatedFederation`` accepts either form.
    """
    rounds: int = 20                  # sync rounds, or async buffer flushes
    sample_frac: float = 0.10
    n_clusters: int = 5
    local_epochs: int = 1
    lr: float = 1e-3
    deadline: float = 30.0            # virtual seconds per block slot (sync)
    sampler: str = "uniform"
    mode: str = "sync"                # "sync" | "async"
    strategy: str = "bfln"            # repro.api.registry name
    strategy_params: dict = field(default_factory=dict)
    buffer_size: int = 16             # async: flush threshold K
    staleness_alpha: float = 0.5      # async: w(s) = (1+s)^-alpha
    server_lr: float = 1.0            # async: global += lr · merged delta
    concurrency: int = 64             # async: target in-flight clients
    total_reward: float = 20.0
    rho: float = 2.0
    initial_stake: float = 5.0
    eval_every: int = 5               # 0 = only final eval
    eval_clients: int = 128           # population sub-sample for evaluation
    eval_examples: int = 1024         # shared-test sub-sample for evaluation
    hidden: tuple[int, ...] = (64,)
    rep_dim: int = 32
    model: str = "mlp"                # or a LoRA-trained architecture
    model_overrides: dict = field(default_factory=dict)
    engine: bool = True               # arena-backed fused round engine
    mesh_shards: int = 1              # >1: shard the arena's client axis over
                                      # a device mesh (engine mode only); on
                                      # CPU force devices with XLA_FLAGS=
                                      # --xla_force_host_platform_device_count=N
    mesh_cohort: str = "sharded"      # cohort axis on that mesh: "sharded"
                                      # slices + tree-combines, "replicated"
                                      # gathers the cohort to every device
    seed: int = 0

    def __post_init__(self):
        # ONE source of validation truth: building the nested spec runs every
        # sub-spec's __post_init__ (mode/sampler/strategy membership,
        # fractions, positivity, the mesh-requires-engine cross check) — a
        # bad value raises ValueError here, at construction, never deep
        # inside the round loop
        self.to_spec()
        if not _SIMCONFIG_INTERNAL:
            warnings.warn(
                "SimConfig is deprecated; build a nested "
                "repro.api.ExperimentSpec and run it with repro.api.run() "
                "(SimConfig(...) keeps working as a shim via .to_spec())",
                DeprecationWarning, stacklevel=3)

    @classmethod
    def _internal(cls, **kw) -> "SimConfig":
        """Construct the flat view without the deprecation warning (used by
        ``ExperimentSpec.sim_config()``); validation still runs."""
        global _SIMCONFIG_INTERNAL
        prev, _SIMCONFIG_INTERNAL = _SIMCONFIG_INTERNAL, True
        try:
            return cls(**kw)
        finally:
            _SIMCONFIG_INTERNAL = prev

    def to_spec(self, data=None):
        """The equivalent nested :class:`repro.api.ExperimentSpec` (the
        old-kwargs → new-spec mapping the compat test pins).  ``data`` may
        supply a :class:`repro.api.DataSpec`; population-less callers (the
        common case — they pass a materialised population) get defaults."""
        from repro.api.spec import (
            AsyncSpec,
            ChainSpec,
            DataSpec,
            EvalSpec,
            ExperimentSpec,
            MeshSpec,
            TrainSpec,
        )
        return ExperimentSpec(
            data=data if data is not None else DataSpec(),
            train=TrainSpec(
                strategy=self.strategy,
                strategy_params=dict(self.strategy_params),
                rounds=self.rounds, sample_frac=self.sample_frac,
                n_clusters=self.n_clusters, local_epochs=self.local_epochs,
                lr=self.lr, deadline=self.deadline, sampler=self.sampler,
                mode=self.mode, hidden=tuple(self.hidden),
                rep_dim=self.rep_dim, model=self.model,
                model_overrides=dict(self.model_overrides)),
            async_=AsyncSpec(
                buffer_size=self.buffer_size,
                staleness_alpha=self.staleness_alpha,
                server_lr=self.server_lr, concurrency=self.concurrency),
            eval=EvalSpec(every=self.eval_every, clients=self.eval_clients,
                          examples=self.eval_examples),
            chain=ChainSpec(total_reward=self.total_reward, rho=self.rho,
                            initial_stake=self.initial_stake),
            mesh=MeshSpec(shards=self.mesh_shards, cohort=self.mesh_cohort),
            engine=self.engine, seed=self.seed)


@dataclass
class SimRoundRecord:
    round_idx: int
    t_open: float
    t_close: float
    cohort: np.ndarray
    arrived: np.ndarray               # (k,) bool
    n_stragglers: int
    n_dropouts: int
    n_byzantine: int
    producer: int
    verified_frac: float
    reward_paid: float
    reward_burned: float
    mean_loss: float
    accuracy: float = float("nan")    # cohort accuracy (sync) / global (async);
                                      # a token model's held-out next-token loss
    staleness_mean: float = 0.0       # async only
    cluster_accuracy: np.ndarray | None = None   # (C,) engine-mode sync eval


@dataclass
class SimReport:
    config: SimConfig
    history: list[SimRoundRecord]
    event_log: list[tuple]
    final_accuracy: float
    balances: np.ndarray
    chain_valid: bool
    n_blocks: int
    ledger_conserved: bool

    def summary(self) -> str:
        h = self.history
        paid = sum(r.reward_paid for r in h)
        burned = sum(r.reward_burned for r in h)
        return (f"{len(h)} rounds, {len(self.event_log)} events, "
                f"final_acc={self.final_accuracy:.4f}, paid={paid:.1f}, "
                f"burned={burned:.1f}, blocks={self.n_blocks}, "
                f"chain_valid={self.chain_valid}, "
                f"conserved={self.ledger_conserved}")


class SimulatedFederation:
    """Drives `FederatedTrainer` round logic over sampled cohorts of a
    virtual client population, on a deterministic virtual clock.

    ``config`` may be a nested :class:`repro.api.ExperimentSpec` (the
    canonical form) or a flat legacy :class:`SimConfig`; both normalise to
    the same pair (``self.spec``, ``self.cfg``).  The strategy is resolved
    by name through :mod:`repro.api.registry`, so any registered strategy —
    BFLN or a Table II baseline — runs through the fused round engine, the
    simulator, and the sharded mesh.
    """

    def __init__(self, population: ClientPopulation, config, weights=None):
        """``weights``: for a LoRA-trained model, ``(base, row)`` made
        elsewhere (see :func:`repro.api.setup.build_model`)."""
        from repro.api.registry import build_strategy
        from repro.api.setup import build_model
        from repro.api.spec import ExperimentSpec
        if isinstance(config, ExperimentSpec):
            self.spec = config
            config = config.sim_config()
        else:
            self.spec = config.to_spec()
        self.pop = population
        self.cfg = config
        n = population.n_clients

        if config.model != "mlp":
            if not config.engine:
                raise ValueError(
                    f"{config.model} trains through the round engine only "
                    "(engine=True); the legacy driver keeps whole models")
            if config.mode != "sync":
                raise ValueError(
                    f"{config.model} federates in sync rounds only: the async "
                    "(FedBuff) path does not take a frozen base yet")
        model = build_model(self.spec.train, population, config.seed,
                            weights=weights)
        self.model = model
        self.mcfg = model.mcfg   # the serving tier rebuilds forwards from this
        self.bundle = model.bundle
        self.opt = adam(config.lr)

        def make_strategy(bundle):
            return build_strategy(config.strategy, bundle,
                                  probe=population.probe,
                                  n_clusters=config.n_clusters,
                                  **config.strategy_params)

        strat = make_strategy(self.bundle)
        self.trainer = FederatedTrainer(
            self.bundle, strat, self.opt, local_epochs=config.local_epochs,
            n_clusters=config.n_clusters, total_reward=config.total_reward,
            rho=config.rho, initial_stake=config.initial_stake)
        # population-wide ledger (the trainer's chain_round settles against it)
        self.trainer.ledger = TokenLedger(n, config.initial_stake)

        self.arena: ParamArena | None = None
        self.engine: RoundEngine | None = None
        self.step_data: tuple | None = None   # (cx, cy) where the step reads
        self._host_data: tuple | None = None  # (cx, cy) on the host (async)
        self._eval_data: tuple | None = None
        self._params = model.params
        # shared tamper digest for Byzantine commits (built once; chain_round
        # substitutes the digest each freerider *claims*, which never varies)
        lay = model.layout
        zeros = [None] * len(lay.order)
        for pos, i in enumerate(lay.order):
            zeros[i] = np.zeros(lay.shapes[pos], lay.dtypes[pos])
        self._fake_digest = digest_of(
            jax.tree_util.tree_unflatten(lay.treedef, zeros))
        self.last_labels = np.full(n, -1, dtype=np.int64)
        self.sampler = get_sampler(config.sampler)

        self.rng = np.random.default_rng(config.seed)
        self.clock = VirtualClock()
        self.queue = EventQueue()
        self.event_log: list[tuple] = []
        self.history: list[SimRoundRecord] = []

        # flight recorder (repro.obs): spans/metrics ride along out of band.
        # Disabled runs bind the shared no-op recorder — the hot path then
        # pays only no-op method calls (the < 2% trace-off budget).
        obs_spec = getattr(self.spec, "obs", None)
        if obs_spec is not None and obs_spec.enabled:
            self.obs = FlightRecorder(obs_spec, clock=lambda: self.clock.now)
        else:
            self.obs = NULL_RECORDER

        # checkpoint/resume + fault injection (repro.checkpoint/repro.faults):
        # both default off and follow the recorder's no-op-object pattern, so
        # the default hot path is bit-identical to a build without them
        ckpt_spec = getattr(self.spec, "checkpoint", None)
        self.ckpt = ckpt_spec if (ckpt_spec is not None
                                  and ckpt_spec.enabled) else None
        fault_spec = getattr(self.spec, "faults", None)
        if fault_spec is not None and fault_spec.enabled:
            self.faults = FaultInjector(fault_spec, obs=self.obs)
        else:
            self.faults = NULL_INJECTOR
        self._resume_async: dict | None = None
        self._resumed_from: tuple[str, int] | None = None
        self._ckpt_written = 0
        self._ckpt_bytes = 0
        self._ckpt_executor = None     # lazy single-worker snapshot writer
        self._ckpt_future = None       # at most one write in flight

        strategy = strat
        opt = self.opt
        n_clusters = config.n_clusters
        epochs = config.local_epochs

        if config.engine:
            # flatten the population ONCE into the (n, N) arena; all round
            # state now lives as donated rows of this matrix.  mesh_shards>1
            # row-shards the arena over a client-axis device mesh — each
            # device then holds only n/shards rows of population state
            if model.row is not None:
                if config.mesh_shards > 1:
                    raise ValueError(f"{model.kind} runs on one device: its "
                                     "arena is not row-sharded yet")
                # every client starts from the same adapter row
                self.arena = ParamArena(model.layout, _broadcast_rows(
                    model.row, n))
            elif config.mesh_shards > 1:
                from repro.launch.mesh import make_client_mesh
                self.arena = ShardedParamArena.from_stacked(
                    self._params, make_client_mesh(config.mesh_shards))
            else:
                self.arena = ParamArena.from_stacked(self._params)
            self._params = None
            bind = None
            if model.bundle.bind is not None:
                def bind(base):
                    b = model.bundle.with_base(base)
                    return make_strategy(b), b.apply_fn
            self.engine = RoundEngine(
                self.arena.layout, apply_fn=self.bundle.apply_fn,
                strategy=strategy, opt=opt,
                n_clusters=n_clusters, local_epochs=epochs,
                stacked_apply_fn=model.stacked_apply_fn,
                sharding=getattr(self.arena, "sharding", None),
                cohort_mode=config.mesh_cohort, bind=bind,
                eval_metric=model.bundle.eval_metric)
            # the population's train data, placed once like the arena rows:
            # sync_step gathers each cohort's slice of it on the device
            self.step_data = (self.arena.place_rows(population.cx),
                              self.arena.place_rows(population.cy))
            if self.obs.enabled:
                self.obs.set_gauge("arena.bytes", int(self.arena.data.nbytes))
                per_dev = getattr(self.arena, "per_device_bytes", None)
                self.obs.set_gauge(
                    "arena.per_device_bytes",
                    int(per_dev()) if per_dev else int(self.arena.data.nbytes))
                # per-round cohort collective traffic (see repro.core.engine):
                # sharded cohort moves each device's slice in/out plus the
                # replicated combine block; replicated mode gathers the full
                # (k, N) block in and scatters the row updates out
                k = max(1, int(round(config.sample_frac * n)))
                n_params = self.arena.layout.n_params
                if self.engine.cohort_mode == "sharded":
                    s = self.engine.cohort_shards
                    k_pad = -(-k // s) * s
                    per_dev_slice = (k_pad // s) * n_params * 4
                    traffic = 2 * per_dev_slice + k_pad * n_params * 4
                else:
                    traffic = 2 * k * n_params * 4
                self.obs.set_gauge("engine.cohort_bytes", traffic)
        self.trainer.attach_obs(self.obs)
        self.trainer.attach_faults(self.faults)
        # tokens one client trains on in a round (token data only)
        self._tokens_per_client = int(np.prod(population.cx.shape[1:])) \
            * config.local_epochs

        # ------- legacy (pre-arena) jitted programs, kept as the oracle ---- #

        @jax.jit
        def _cohort_round(cohort_params, cx, cy, arrived_w):
            """Local training (fresh per-round optimizer, standard for sampled
            cohorts) + the strategy's cohort aggregation weighted by the
            arrival mask (BFLN: the PAA pipeline)."""
            opt_state = jax.vmap(opt.init)(cohort_params)
            extras = strategy.round_extras(cohort_params, cx, cy)
            res = local_train(strategy.local_loss, opt, cohort_params,
                              opt_state, cx, cy, extras, epochs,
                              shared_extras=strategy.shared_extras)
            agg = strategy.aggregate_cohort(res.params, cx, cy, arrived_w)
            return res.params, agg, jnp.mean(res.mean_loss)

        self._cohort_round = _cohort_round

        @jax.jit
        def _local_only(cohort_params, cx, cy):
            """Async path: just the local updates (aggregation happens at
            flush time in ``async_agg.weighted_delta_mean``)."""
            opt_state = jax.vmap(opt.init)(cohort_params)
            extras = strategy.round_extras(cohort_params, cx, cy)
            res = local_train(strategy.local_loss, opt, cohort_params,
                              opt_state, cx, cy, extras, epochs,
                              shared_extras=strategy.shared_extras)
            return res.params, jnp.mean(res.mean_loss)

        self._local_only = _local_only
        self._eval = jax.jit(functools.partial(global_evaluate,
                                               self.bundle.apply_fn))
        # the final population eval has its own jitted entry: its leading dim
        # (eval_clients) differs from the round cohort's, and sharing one
        # cache entry per distinct shape made compile counts unauditable
        self._eval_final = jax.jit(functools.partial(global_evaluate,
                                                     self.bundle.apply_fn))

    # ------------------------------------------------------------------ #
    # stacked-params view (legacy attribute; engine mode stores the arena)
    # ------------------------------------------------------------------ #

    @property
    def params(self) -> Pytree:
        if self.arena is not None:
            return self.arena.as_pytree()
        return self._params

    @params.setter
    def params(self, value: Pytree) -> None:
        if self.arena is not None:
            self.arena.rebind(self.arena.layout.flatten(value))
        else:
            self._params = value

    # ------------------------------------------------------------------ #
    # shared helpers
    # ------------------------------------------------------------------ #

    def _log(self, event: ev.Event) -> None:
        self.event_log.append(event.log_entry())

    def _sampler_state(self) -> SamplerState:
        return SamplerState(balances=self.trainer.ledger.balances,
                            last_labels=self.last_labels,
                            n_clusters=self.cfg.n_clusters)

    def _tampers(self, cohort: np.ndarray, arrived: np.ndarray) -> dict:
        """Byzantine freeriders commit digests of params they did not train."""
        return {int(gid): self._fake_digest
                for slot, gid in enumerate(cohort)
                if arrived[slot] and self.pop.byzantine[gid]}

    def _schedule_retries(self, r: int, gid: int, t_fail: float,
                          lat: float) -> None:
        """Bounded retry-with-backoff for a dropped cohort slot
        (``FaultSpec.retry``).  Every redraw comes from the injector's own
        seeded generator — the simulator's streams are untouched, so the
        retry knob perturbs nothing else and replays/resumes exactly.  A
        recovered client may still miss the deadline: retry is bounded, not
        a delivery guarantee."""
        faults, obs = self.faults, self.obs
        t_retry = t_fail
        for attempt in range(1, faults.spec.retry_max + 1):
            with obs.span("round.retry", round=r, client=gid,
                          attempt=attempt) as sp:
                t_retry += faults.retry_latency(lat, attempt)
                ok = faults.retry_succeeds(self.pop.dropout[gid])
                sp.set(t_retry=t_retry, recovered=ok)
            obs.inc("fault.retry")
            if ok:
                self.queue.push(t_retry, ev.UPDATE_READY, gid, r)
                obs.inc("fault.retry_recovered")
                return
            self.queue.push(t_retry, ev.DROPOUT, gid, r)

    def _with_base(self, *args) -> tuple:
        """An engine entry's arguments, with the model's frozen base last
        where it has one."""
        base = self.bundle.base
        return args if base is None else args + (base,)

    def _eval_slices(self) -> tuple[jnp.ndarray, jnp.ndarray]:
        if self._eval_data is None:       # sliced once, not every eval round
            self._eval_data = (self.pop.test_x[: self.cfg.eval_examples],
                               self.pop.test_y[: self.cfg.eval_examples])
        return self._eval_data

    def flush_data(self, clients: np.ndarray) -> tuple:
        """A flush's train data, gathered on the host from one host copy of
        the population's: the jit transfers it, so no eager device op runs
        (a flush holds ``buffer_size`` clients, a few tens of KB)."""
        if self._host_data is None:
            self._host_data = (np.asarray(self.pop.cx),
                               np.asarray(self.pop.cy))
        cx, cy = self._host_data
        return cx[clients], cy[clients]

    def _evaluate_clients(self, ids: np.ndarray) -> float:
        ex, ey = self._eval_slices()
        if self.engine is not None:
            return float(self.engine.eval_population(
                *self._with_base(self.arena.data, jnp.asarray(ids), ex, ey)))
        stacked = jax.tree.map(lambda x: x[jnp.asarray(ids)], self._params)
        return float(self._eval_final(stacked, ex, ey))

    # ------------------------------------------------------------------ #
    # synchronous mode
    # ------------------------------------------------------------------ #

    def _run_sync_round(self, r: int) -> SimRoundRecord:
        with self.obs.span("round.total", round=r) as rt:
            return self._sync_round_body(r, rt)

    def _sync_round_body(self, r: int, rt) -> SimRoundRecord:
        cfg, pop, rng, obs = self.cfg, self.pop, self.rng, self.obs
        self.faults.maybe_crash(r, "round_start")
        t0 = self.clock.now
        k = max(1, int(round(cfg.sample_frac * pop.n_clients)))

        with obs.span("round.sample", round=r) as sp:
            online = pop.online_clients(rng)
            cohort = self.sampler(rng, online, k, self._sampler_state())
            sp.set(online=len(online), k=len(cohort))

        dropouts: set[int] = set()        # classified at schedule time — a
        with obs.span("round.schedule", round=r):   # dropout past the
            self.queue.push(t0 + cfg.deadline, ev.BLOCK_SLOT, round_idx=r)
            for gid in cohort:            # deadline is still a death, not a
                gid = int(gid)            # straggler
                self.queue.push(t0, ev.CLIENT_ARRIVAL, gid, r)
                lat = pop.latency.draw(gid)
                if rng.random() < pop.dropout[gid]:
                    dropouts.add(gid)
                    t_fail = t0 + lat * rng.uniform(0.1, 0.9)
                    self.queue.push(t_fail, ev.DROPOUT, gid, r)
                    if self.faults.retry:
                        self._schedule_retries(r, gid, t_fail, lat)
                else:
                    self.queue.push(t0 + lat, ev.UPDATE_READY, gid, r)

        arrived_set: set[int] = set()
        with obs.span("round.wait", round=r) as sp:
            # the block slot on the VIRTUAL clock: wall time here is event
            # bookkeeping, the span's vt_dur attr is the simulated wait
            n_events = 0
            while True:
                e = self.queue.pop()
                self.clock.advance_to(e.time)
                self._log(e)
                n_events += 1
                if e.kind == ev.BLOCK_SLOT and e.round_idx == r:
                    break
                if e.round_idx != r:
                    continue                  # late event from an old round
                if e.kind == ev.UPDATE_READY:
                    arrived_set.add(e.client)
            sp.set(n_events=n_events)

        arrived = np.array([int(g) in arrived_set for g in cohort], dtype=bool)
        # with FaultSpec.retry a dropout may recover and still arrive;
        # count only the deaths that stuck (faults off: identical to before)
        n_drop = sum(1 for g in dropouts if g not in arrived_set)
        n_strag = int(len(cohort) - arrived.sum() - n_drop)
        rt.set(arrived=int(arrived.sum()))

        record = SimRoundRecord(
            round_idx=r, t_open=t0, t_close=self.clock.now, cohort=cohort,
            arrived=arrived, n_stragglers=n_strag, n_dropouts=n_drop,
            n_byzantine=int(pop.byzantine[cohort][arrived].sum()),
            producer=-1, verified_frac=0.0, reward_paid=0.0,
            reward_burned=0.0, mean_loss=float("nan"))

        if not arrived.any():
            obs.inc("rounds.empty")
            return record                     # empty round: no block minted

        arrived_w = arrived.astype(np.float32)

        if self.engine is not None:
            # ONE donated device program: row and data gather → train → PAA
            # → digests → masked scatter-back.  The cohort ids and arrival
            # mask go in as NumPy; the host reads back O(cohort) bytes
            with obs.span("round.step", round=r,
                          shards=self.engine.cohort_shards,
                          cohort_mode=self.engine.cohort_mode):
                self.arena.data, out = self.engine.sync_step(
                    *self._with_base(self.arena.data, cohort,
                                     *self.step_data, arrived_w))
                obs.inc("engine.sync_step")
                if self.bundle.eval_metric == "loss":
                    # every cohort slot trains, arrived or not
                    obs.inc("engine.tokens", self._tokens_per_client
                            * len(cohort))
                obs.ready(out)
            if obs.enabled:
                obs.compile_delta(self.engine.cache_sizes(), r)
            labels_dev, mean_loss = out.labels, out.mean_loss
            with obs.span("round.digests", round=r):
                digests = self.engine.format_digests(out.residues)
            self.faults.maybe_crash(r, "pre_chain")
            with obs.span("round.chain", round=r):
                cres = self.trainer.chain_round(
                    r, None, labels_dev, out.corr, cohort=cohort,
                    arrived=arrived, tamper=self._tampers(cohort, arrived),
                    digests=digests)
        else:
            with obs.span("round.gather", round=r):
                cx, cy = pop.cohort_data(cohort)
            with obs.span("round.step", round=r):
                cohort_params = jax.tree.map(lambda x: x[jnp.asarray(cohort)],
                                             self._params)
                local_params, agg, mean_loss = self._cohort_round(
                    cohort_params, cx, cy, arrived_w)
                obs.ready(mean_loss)
            labels_dev = agg.labels
            self.faults.maybe_crash(r, "pre_chain")
            with obs.span("round.chain", round=r):
                cres = self.trainer.chain_round(
                    r, local_params, agg.labels, agg.corr, cohort=cohort,
                    arrived=arrived, tamper=self._tampers(cohort, arrived))

            # arrived clients adopt their aggregated model; stragglers and
            # dropouts keep their previous personalized params
            with obs.span("round.scatter", round=r):
                new_rows = jax.tree.map(
                    lambda x: x[jnp.asarray(np.flatnonzero(arrived))],
                    agg.stacked_params)
                upd_ids = jnp.asarray(np.asarray(cohort)[arrived])
                self._params = jax.tree.map(
                    lambda P, rows: P.at[upd_ids].set(rows),
                    self._params, new_rows)

        with obs.span("round.record", round=r):
            upd = np.asarray(cohort)[arrived]
            labels = np.asarray(labels_dev)
            self.last_labels[upd] = labels[arrived]

            record.producer = cres.producer
            record.verified_frac = float(cres.verified[arrived].mean())
            record.reward_paid = float(cres.rewards.sum())
            record.reward_burned = float(cfg.total_reward - cres.rewards.sum())
            record.mean_loss = float(mean_loss)
        if cfg.eval_every and ((r + 1) % cfg.eval_every == 0):
            ex, ey = self._eval_slices()
            if self.engine is not None:
                # fixed-shape mask-weighted eval: the cohort shape never
                # changes, so this entry compiles exactly once.  The outputs
                # stay on device — metrics never gate the round, so the eval
                # overlaps the next round's host work (`_finalize_history`
                # materialises them at end of run).  Tracing blocks on them
                # (timing attribution only — the values are unchanged).
                with obs.span("round.eval", round=r):
                    acc, cacc = self.engine.eval_cohort(*self._with_base(
                        out.new_rows, arrived_w, labels_dev, ex, ey))
                    obs.ready(acc)
                if obs.enabled:
                    obs.compile_delta(self.engine.cache_sizes(), r)
                record.accuracy = acc
                record.cluster_accuracy = cacc
            else:
                # evaluate only the adopted (arrived) rows: stragglers keep
                # their old params, and a cluster with zero arrivals yields a
                # garbage row.  new_rows' leading dim varies with the arrival
                # count → one jit recompile per distinct count (the engine
                # path exists to kill exactly this).
                with obs.span("round.eval", round=r):
                    record.accuracy = float(self._eval(new_rows, ex, ey))
        return record

    # ------------------------------------------------------------------ #
    # asynchronous mode (FedBuff)
    # ------------------------------------------------------------------ #

    def _run_async(self) -> None:
        cfg, pop, rng, obs = self.cfg, self.pop, self.rng, self.obs
        if cfg.buffer_size + cfg.concurrency > pop.n_clients:
            # buffered clients stay "busy" until their flush: a buffer that
            # cannot fill from the remaining population stalls forever
            raise ValueError(
                f"buffer_size ({cfg.buffer_size}) + concurrency "
                f"({cfg.concurrency}) exceeds the population "
                f"({pop.n_clients}); the buffer could never fill")
        resume = self._resume_async
        self._resume_async = None
        if resume is not None:
            # loop state restored from a flush-boundary snapshot
            # (`repro.checkpoint.state`): the post-flush dispatch already
            # happened before the snapshot, so the loop re-enters directly
            version = resume["version"]
            global_state = resume["global_state"]
            snapshots: dict[int, Any] = resume["snapshots"]
            inflight: dict[int, int] = resume["inflight"]
            agg = resume["agg"]
        else:
            version = 0
            if self.arena is not None:
                global_state = self.arena.data[0]      # (N,) flat row
            else:
                global_state = tree_index(self._params, 0)
            snapshots = {0: global_state}
            inflight = {}                  # client -> dispatch version
            agg = BufferedAggregator(cfg.buffer_size, cfg.staleness_alpha)

        def dispatch() -> None:
            with obs.span("async.dispatch", cat="flush", round=version):
                _dispatch()

        def _dispatch() -> None:
            want = cfg.concurrency - len(inflight)
            if want <= 0:
                return
            # a client already in flight OR sitting in the buffer must not be
            # re-dispatched: a duplicate in one flush cohort would collapse
            # its two rewards into one ledger scatter slot
            busy = set(inflight) | {u.client for u in agg.buffer}
            online = pop.online_clients(rng)
            online = np.setdiff1d(online, np.fromiter(busy, np.int64,
                                                      len(busy)))
            picked = self.sampler(rng, online, want, self._sampler_state())
            t = self.clock.now
            for gid in picked:
                gid = int(gid)
                inflight[gid] = version
                self.queue.push(t, ev.CLIENT_ARRIVAL, gid,
                                round_idx=version, tag=version)
                lat = pop.latency.draw(gid)
                if rng.random() < pop.dropout[gid]:
                    self.queue.push(t + lat * rng.uniform(0.1, 0.9),
                                    ev.DROPOUT, gid, version, tag=version)
                else:
                    self.queue.push(t + lat, ev.UPDATE_READY, gid, version,
                                    tag=version)

        if resume is None:
            dispatch()
        while version < cfg.rounds and self.queue:
            e = self.queue.pop()
            self.clock.advance_to(e.time)
            self._log(e)
            if e.kind == ev.DROPOUT:
                inflight.pop(e.client, None)
                dispatch()
                continue
            if e.kind != ev.UPDATE_READY:
                continue
            dispatched_v = inflight.pop(e.client, None)
            if dispatched_v is None:
                continue
            agg.add(BufferedUpdate(e.client, None, dispatched_v))
            flushed = len(agg) >= cfg.buffer_size
            if flushed:
                version, global_state = self._async_flush(
                    agg, version, global_state, snapshots)
                snapshots[version] = global_state
                live = set(inflight.values()) | {version}
                for v in [v for v in snapshots if v not in live]:
                    del snapshots[v]
            dispatch()
            if flushed:
                # flush boundary: snapshot AFTER the post-flush dispatch so
                # a resume re-enters the loop with nothing left to re-issue
                self._maybe_checkpoint(version, async_view={
                    "version": version, "global_state": global_state,
                    "snapshots": snapshots, "inflight": inflight,
                    "agg": agg})
                if self.faults.will_crash(version, "post_checkpoint"):
                    self._ckpt_wait()      # snapshot durable before dying
                self.faults.maybe_crash(version, "post_checkpoint")

        if version < cfg.rounds:
            # event queue drained early (e.g. availability collapse) — the
            # report simply carries fewer flushes than requested
            self.event_log.append((self.clock.now, "queue_drained", -1,
                                   version, 0))
        if self.arena is not None:
            self.arena.rebind(jnp.broadcast_to(
                global_state[None],
                (self.arena.n_clients,) + global_state.shape))
        else:
            self._params = jax.tree.map(
                lambda g: jnp.broadcast_to(g[None], (pop.n_clients,) + g.shape),
                global_state)

    def _async_flush(self, agg: BufferedAggregator, version: int,
                     global_state, snapshots: dict) -> tuple:
        """One buffer flush = one training batch + one block + one merge."""
        with self.obs.span("flush.total", cat="flush", round=version):
            return self._async_flush_body(agg, version, global_state,
                                          snapshots)

    def _async_flush_body(self, agg: BufferedAggregator, version: int,
                          global_state, snapshots: dict) -> tuple:
        cfg, pop, obs = self.cfg, self.pop, self.obs
        self.faults.maybe_crash(version, "round_start")
        clients = np.array([u.client for u in agg.buffer], dtype=np.int64)
        versions = [u.version for u in agg.buffer]
        k = len(clients)

        with obs.span("flush.prepare", cat="flush", round=version):
            # chain: single-cluster CACC over the flush group (host
            # constants; the chain's jitted consumers take NumPy)
            labels = np.zeros(k, np.int32)
            corr = np.eye(k, dtype=np.float32)
            arrived = np.ones(k, dtype=bool)
            tamper = self._tampers(clients, arrived)

        if self.engine is not None:
            with obs.span("flush.step", cat="flush", round=version,
                          shards=self.engine.cohort_shards,
                          cohort_mode=self.engine.cohort_mode):
                # each client's dispatch snapshot, stacked inside the step
                base_rows = [snapshots[v] for v in versions]
                local_rows, residues, mean_loss = self.engine.async_step(
                    base_rows, *self.flush_data(clients))
                obs.inc("engine.async_step")
                obs.ready(local_rows)
            if obs.enabled:
                obs.compile_delta(self.engine.cache_sizes(), version)
            self.faults.maybe_crash(version, "pre_chain")
            with obs.span("flush.chain", cat="flush", round=version):
                cres = self.trainer.chain_round(
                    version, None, labels, corr, cohort=clients,
                    arrived=arrived, tamper=tamper,
                    digests=self.engine.format_digests(residues))
            with obs.span("flush.merge", cat="flush", round=version):
                staleness = np.array([version - v for v in versions],
                                     np.int64)
                # one program over the flat rows, bit-identical to the
                # legacy driver's per-leaf weighted_delta_mean merge
                global_state, staleness_w = self.engine.async_merge(
                    global_state, local_rows, base_rows, staleness,
                    cres.verified.astype(np.float32), cfg.staleness_alpha,
                    cfg.server_lr)
                obs.inc("engine.async_merge")
                obs.ready(global_state)
            agg.buffer = []
            staleness_mean = float(staleness.mean())
        else:
            with obs.span("flush.gather", cat="flush", round=version):
                cx, cy = pop.cohort_data(clients)
            with obs.span("flush.step", cat="flush", round=version):
                base = tree_stack([snapshots[v] for v in versions])
                local_params, mean_loss = self._local_only(base, cx, cy)
                deltas = jax.tree.map(lambda a, b: a - b, local_params, base)
                obs.ready(mean_loss)
            # re-materialise the buffer with the actual deltas (kept lazy
            # until now so every flush trains its K clients in one vmapped
            # call)
            agg.buffer = [BufferedUpdate(int(c), tree_index(deltas, i), v)
                          for i, (c, v) in enumerate(zip(clients, versions))]
            self.faults.maybe_crash(version, "pre_chain")
            with obs.span("flush.chain", cat="flush", round=version):
                cres = self.trainer.chain_round(
                    version, local_params, labels, corr, cohort=clients,
                    arrived=arrived, tamper=tamper)
            with obs.span("flush.merge", cat="flush", round=version):
                merge = agg.flush(version,
                                  gate=cres.verified.astype(np.float32))
                global_state = jax.tree.map(
                    lambda g, d: g + cfg.server_lr * d.astype(g.dtype),
                    global_state, merge.delta)
                obs.ready(global_state)
            staleness = np.asarray(merge.staleness)
            staleness_mean = float(staleness.mean())
            staleness_w = np.asarray(
                staleness_weight(staleness, cfg.staleness_alpha),
                np.float32) * cres.verified.astype(np.float32)

        with obs.span("flush.record", cat="flush", round=version):
            if obs.enabled:
                # staleness-weight distribution: how much each flush
                # discounts its stale contributors (and zeroes its
                # unverified ones)
                for s in staleness:
                    obs.observe("async.staleness", float(s))
                for wv in np.asarray(staleness_w):
                    obs.observe("async.staleness_weight", float(wv))
                obs.point("async.staleness_mean", staleness_mean,
                          round=version)

            new_version = version + 1
            self.last_labels[clients] = 0
            record = SimRoundRecord(
                round_idx=version, t_open=self.clock.now,
                t_close=self.clock.now, cohort=clients, arrived=arrived,
                n_stragglers=0, n_dropouts=0,
                n_byzantine=int(pop.byzantine[clients].sum()),
                producer=cres.producer,
                verified_frac=float(cres.verified.mean()),
                reward_paid=float(cres.rewards.sum()),
                reward_burned=float(cfg.total_reward - cres.rewards.sum()),
                mean_loss=float(mean_loss),
                staleness_mean=staleness_mean)
        if cfg.eval_every and (new_version % cfg.eval_every == 0):
            ex, ey = self._eval_slices()
            if self.engine is not None:
                # deferred like the sync eval: materialised at end of run
                with obs.span("flush.eval", cat="flush", round=version):
                    record.accuracy = self.engine.eval_global(
                        global_state, ex, ey)
                    obs.ready(record.accuracy)
                if obs.enabled:
                    obs.compile_delta(self.engine.cache_sizes(), version)
            else:
                with obs.span("flush.eval", cat="flush", round=version):
                    stacked = jax.tree.map(lambda g: g[None], global_state)
                    record.accuracy = float(self._eval(stacked, ex, ey))
        self.history.append(record)
        return new_version, global_state

    # ------------------------------------------------------------------ #

    def _finalize_history(self) -> None:
        """Materialise deferred (still-on-device) eval metrics.  The engine
        path leaves accuracy outputs as device arrays so metric extraction
        never blocks the round hot path."""
        for rec in self.history:
            if not isinstance(rec.accuracy, float):
                rec.accuracy = float(rec.accuracy)
            if rec.cluster_accuracy is not None:
                rec.cluster_accuracy = np.asarray(rec.cluster_accuracy)

    def _maybe_checkpoint(self, boundary: int,
                          async_view: dict | None = None) -> None:
        """Snapshot the complete experiment state when ``boundary``
        (completed rounds/flushes) hits the checkpoint interval.

        Only the *capture* (a consistent host copy of all state) runs on the
        round hot path; the expensive half — npz encode, sha256, write,
        fsync — is handed to a single background writer thread so the next
        round overlaps the disk work (the <10% steady-overhead budget,
        `benchmarks/round_bench.py --checkpoint-interval`).  At most one
        write is in flight: a new boundary first retires the previous one.
        Crash consistency is unaffected — the writer stages to a temp file
        and atomically renames, so a death mid-write leaves the previous
        snapshot intact — and a scheduled ``post_checkpoint`` crash flushes
        the writer first (see :meth:`run`), keeping the kill-and-resume
        contract exact.  The fault injector corrupts the file (if scheduled)
        only after its write completes."""
        ck = self.ckpt
        if ck is None or boundary == 0 or boundary % ck.interval:
            return
        from repro.checkpoint import save_checkpoint
        from repro.checkpoint.state import capture_experiment_state
        with self.obs.span("ckpt.save", cat="ckpt", round=boundary) as sp:
            tree = capture_experiment_state(self, boundary, async_view)
            self._ckpt_wait()          # retire the previous in-flight write
            if self._ckpt_executor is None:
                from concurrent.futures import ThreadPoolExecutor
                self._ckpt_executor = ThreadPoolExecutor(
                    max_workers=1, thread_name_prefix="ckpt-writer")
            faults = self.faults

            def _write() -> int:
                path, n_bytes = save_checkpoint(ck.dir, boundary, tree,
                                                keep_last=ck.keep_last)
                faults.corrupt_checkpoint(path, boundary)
                return n_bytes
            self._ckpt_future = self._ckpt_executor.submit(_write)
            sp.set(boundary=boundary)

    def _ckpt_wait(self) -> None:
        """Block until the in-flight snapshot write (if any) is durable,
        then account for it (``ckpt.saved`` counter, ``ckpt.bytes`` gauge).
        Re-raises a failed write's exception on the main thread."""
        fut, self._ckpt_future = self._ckpt_future, None
        if fut is None:
            return
        n_bytes = fut.result()
        self.obs.inc("ckpt.saved")
        self.obs.set_gauge("ckpt.bytes", n_bytes)
        self._ckpt_written += 1
        self._ckpt_bytes = n_bytes

    def _restore(self, resume_from: str) -> int:
        """Restore from ``resume_from`` (a snapshot file, or a checkpoint
        directory whose newest *readable* snapshot is used).  Returns the
        next round/flush index to execute."""
        from repro.checkpoint import load_latest, load_pytree
        from repro.checkpoint.state import restore_experiment_state
        with self.obs.span("ckpt.restore", cat="ckpt") as sp:
            if os.path.isdir(resume_from):
                _, tree = load_latest(resume_from)
            else:
                tree = load_pytree(resume_from)
            next_round, async_view = restore_experiment_state(self, tree)
            sp.set(step=next_round)
        self.obs.inc("ckpt.restored")
        self._resume_async = async_view
        self._resumed_from = (resume_from, next_round)
        return next_round

    def run(self, resume_from: str | None = None) -> SimReport:
        cfg = self.cfg
        start = self._restore(resume_from) if resume_from is not None else 0
        if cfg.mode == "sync":
            for r in range(start, cfg.rounds):
                self.history.append(self._run_sync_round(r))
                self._maybe_checkpoint(r + 1)
                if self.faults.will_crash(r + 1, "post_checkpoint"):
                    self._ckpt_wait()      # snapshot durable before dying
                self.faults.maybe_crash(r + 1, "post_checkpoint")
        elif cfg.mode == "async":
            self._run_async()
        else:
            raise ValueError(f"unknown mode {cfg.mode!r}")
        self._ckpt_wait()                  # retire any in-flight snapshot
        if self._ckpt_executor is not None:
            self._ckpt_executor.shutdown(wait=True)
            self._ckpt_executor = None
        self._finalize_history()

        n_eval = min(cfg.eval_clients, self.pop.n_clients)
        eval_ids = np.linspace(0, self.pop.n_clients - 1, n_eval).astype(int)
        with self.obs.span("run.final_eval", cat="run") as sp:
            final_acc = self._evaluate_clients(eval_ids)
            sp.set(n_eval=n_eval)
        if self.obs.enabled and self.engine is not None:
            self.obs.compile_delta(self.engine.cache_sizes())
        ledger = self.trainer.ledger
        report = SimReport(
            config=cfg, history=self.history, event_log=self.event_log,
            final_accuracy=final_acc, balances=ledger.balances.copy(),
            chain_valid=self.trainer.chain.validate(),
            n_blocks=len(self.trainer.chain.blocks),
            ledger_conserved=ledger.conserved())
        if self.obs.enabled:
            self.obs.set_gauge("run.final_accuracy", report.final_accuracy)
            self.obs.set_gauge("run.n_blocks", report.n_blocks)
        return report
