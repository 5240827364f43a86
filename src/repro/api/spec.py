"""`ExperimentSpec` — ONE declarative description of a BFLN experiment.

The public experiment surface used to be three disjoint entry points: the
legacy ``FederatedTrainer`` (hand-wired bundle/optimizer/data), the flat
22-field ``SimConfig`` (BFLN hardcoded), and per-example wiring.  The spec
nests the flat knobs into nine sub-configs —

    data        population: shards, behaviour profiles, latency (→ PopulationSpec)
    train       the round loop: strategy, rounds, sampling, model width, lr
    async_      FedBuff buffered aggregation (mode="async" only)
    eval        metric cadence and sub-sampling
    chain       blockchain incentives: reward pool, rho, initial stake
    mesh        client-axis device mesh for the sharded arena
    obs         flight recorder: span tracing + metrics sinks (→ repro.obs)
    checkpoint  crash-consistent snapshot/resume (→ repro.checkpoint)
    faults      seeded fault-injection schedule (→ repro.faults)

— and is the input to :func:`repro.api.run`.  Every spec round-trips through
JSON (``from_json(to_json(spec)) == spec``) and hashes to a stable
``config_digest`` that is stamped into every run manifest, so a result can
always be traced back to the exact configuration that produced it.

Validation happens at construction: invalid ``mode`` / ``sampler`` /
``strategy`` / ``mesh_shards`` / fraction values raise ``ValueError``
immediately instead of failing deep inside the round loop.  The legacy
``SimConfig`` delegates to the same validators (and still works, with a
``DeprecationWarning``) — see ``repro.sim.driver``.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
from dataclasses import dataclass, field
from typing import Any, Mapping

from repro.checkpoint.spec import CheckpointSpec
from repro.faults.spec import FaultSpec
from repro.obs.spec import ObsSpec


def _check(cond: bool, msg: str) -> None:
    if not cond:
        raise ValueError(msg)


def _check_frac(name: str, value: float, *, lo: float = 0.0, hi: float = 1.0,
                lo_open: bool = False) -> None:
    ok = (value > lo if lo_open else value >= lo) and value <= hi
    _check(ok, f"{name} must be in {'(' if lo_open else '['}{lo}, {hi}], "
               f"got {value!r}")


@dataclass(frozen=True)
class DataSpec:
    """The virtual client population (mirrors ``repro.sim.PopulationSpec``)."""
    n_clients: int = 1000
    dataset: str = "synth10"
    beta: float = 0.3                 # Dirichlet label-skew concentration
    n_batches: int = 1
    batch_size: int = 16
    availability: float = 0.85
    dropout_rate: float = 0.03
    straggler_frac: float = 0.10
    straggler_slowdown: float = 8.0
    byzantine_frac: float = 0.0
    base_latency: float = 10.0
    latency_sigma: float = 0.25
    psi: int = 32                     # probe-batch size for PAA
    # token data (dataset "markov-topics", repro.data.lm): rows of seq_len
    # inputs with their next-token targets, over a vocab_size vocabulary;
    # beta mixes n_topics topics per client; PAA probes psi rows of
    # probe_len tokens
    seq_len: int = 0
    vocab_size: int = 0
    n_topics: int = 8
    probe_len: int = 256

    def __post_init__(self):
        _check(self.n_clients >= 1, f"n_clients must be >= 1, got {self.n_clients}")
        if self.dataset == "markov-topics":
            for f in ("seq_len", "vocab_size", "n_topics", "probe_len"):
                _check(getattr(self, f) >= 1,
                       f"{f} must be >= 1 for token data, got {getattr(self, f)}")
        for f in ("n_batches", "batch_size", "psi"):
            _check(getattr(self, f) >= 1, f"{f} must be >= 1, got {getattr(self, f)}")
        _check(self.beta > 0, f"beta must be > 0, got {self.beta}")
        _check_frac("availability", self.availability, lo_open=True)
        for f in ("dropout_rate", "straggler_frac", "byzantine_frac"):
            _check_frac(f, getattr(self, f))
        _check(self.straggler_slowdown >= 1.0,
               f"straggler_slowdown must be >= 1, got {self.straggler_slowdown}")
        _check(self.base_latency > 0, f"base_latency must be > 0, got {self.base_latency}")


@dataclass(frozen=True)
class TrainSpec:
    """The round loop: which strategy runs, over whom, for how long."""
    strategy: str = "bfln"            # repro.api.registry name
    strategy_params: Mapping[str, Any] = field(default_factory=dict)
    rounds: int = 20                  # sync rounds, or async buffer flushes
    sample_frac: float = 0.10
    n_clusters: int = 5
    local_epochs: int = 1
    lr: float = 1e-3
    deadline: float = 30.0            # virtual seconds per block slot (sync)
    sampler: str = "uniform"
    mode: str = "sync"                # "sync" | "async"
    hidden: tuple[int, ...] = (64,)   # MLP widths of the trained model
    rep_dim: int = 32
    # the trained model: "mlp" (the classifier above), or an architecture
    # of repro.configs.FEDERATED_ARCHS trained as LoRA adapters over a
    # frozen base, its config cut or changed by model_overrides
    model: str = "mlp"
    model_overrides: Mapping[str, Any] = field(default_factory=dict)

    def __post_init__(self):
        # strategy membership is checked lazily against the registry so the
        # spec module stays importable without the strategy factories
        from repro.api.registry import strategy_names
        _check(self.strategy in strategy_names(),
               f"unknown strategy {self.strategy!r}; "
               f"registered: {strategy_names()}")
        _check(self.mode in ("sync", "async"),
               f"mode must be 'sync' or 'async', got {self.mode!r}")
        from repro.sim.sampler import SAMPLERS
        _check(self.sampler in SAMPLERS,
               f"unknown sampler {self.sampler!r}; options: {sorted(SAMPLERS)}")
        _check_frac("sample_frac", self.sample_frac, lo_open=True)
        for f in ("rounds", "n_clusters", "local_epochs"):
            _check(getattr(self, f) >= 1, f"{f} must be >= 1, got {getattr(self, f)}")
        _check(self.lr > 0, f"lr must be > 0, got {self.lr}")
        _check(self.deadline > 0, f"deadline must be > 0, got {self.deadline}")
        _check(self.rep_dim >= 1, f"rep_dim must be >= 1, got {self.rep_dim}")
        _check(len(self.hidden) >= 1 and all(h >= 1 for h in self.hidden),
               f"hidden must be a non-empty tuple of widths, got {self.hidden!r}")
        from repro.configs import FEDERATED_ARCHS
        _check(self.model == "mlp" or self.model in FEDERATED_ARCHS,
               f"unknown model {self.model!r}; options: "
               f"{['mlp', *sorted(FEDERATED_ARCHS)]}")


@dataclass(frozen=True)
class AsyncSpec:
    """FedBuff buffered aggregation knobs (``mode='async'`` only)."""
    buffer_size: int = 16             # flush threshold K
    staleness_alpha: float = 0.5      # w(s) = (1+s)^-alpha
    server_lr: float = 1.0            # global += lr · merged delta
    concurrency: int = 64             # target in-flight clients

    def __post_init__(self):
        _check(self.buffer_size >= 1, f"buffer_size must be >= 1, got {self.buffer_size}")
        _check(self.concurrency >= 1, f"concurrency must be >= 1, got {self.concurrency}")
        _check(self.staleness_alpha >= 0,
               f"staleness_alpha must be >= 0, got {self.staleness_alpha}")
        _check(self.server_lr > 0, f"server_lr must be > 0, got {self.server_lr}")


@dataclass(frozen=True)
class EvalSpec:
    every: int = 5                    # 0 = only final eval
    clients: int = 128                # population sub-sample for evaluation
    examples: int = 1024              # shared-test sub-sample for evaluation

    def __post_init__(self):
        _check(self.every >= 0, f"every must be >= 0, got {self.every}")
        _check(self.clients >= 1, f"clients must be >= 1, got {self.clients}")
        _check(self.examples >= 1, f"examples must be >= 1, got {self.examples}")


@dataclass(frozen=True)
class ChainSpec:
    """Blockchain incentives (paper Table I)."""
    total_reward: float = 20.0
    rho: float = 2.0
    initial_stake: float = 5.0

    def __post_init__(self):
        _check(self.total_reward >= 0, f"total_reward must be >= 0, got {self.total_reward}")
        _check(self.rho >= 0, f"rho must be >= 0, got {self.rho}")
        _check(self.initial_stake >= 0, f"initial_stake must be >= 0, got {self.initial_stake}")


#: Cohort-axis execution modes for the mesh round engine.
COHORT_MODES = ("sharded", "replicated")


@dataclass(frozen=True)
class MeshSpec:
    """Client-axis device mesh for the row-sharded parameter arena.

    ``cohort`` picks how the per-round cohort executes on that mesh:
    ``"sharded"`` (default) trains each device's cohort slice locally and
    combines shard-local aggregation partials with a fixed-order tree;
    ``"replicated"`` gathers the whole cohort to every device (the pre-shard
    behaviour — still bit-identical, kept as an escape hatch for strategies
    without partial/combine stages).

    ``platform`` / ``x64`` / ``xla_flags`` are process-level runtime knobs
    resolved by :func:`repro.launch.platform.bootstrap` BEFORE jax
    initialises — they cannot take effect once a backend exists, which is
    why they live on the spec rather than in ad-hoc shell exports.
    """
    shards: int = 1
    cohort: str = "sharded"           # "sharded" | "replicated"
    platform: str = ""                # "" = let jax pick ("cpu"/"gpu"/"tpu")
    x64: bool = False                 # enable float64 (JAX_ENABLE_X64)
    xla_flags: tuple[str, ...] = ()   # extra XLA_FLAGS, appended in order

    def __post_init__(self):
        _check(isinstance(self.shards, int) and self.shards >= 1,
               f"mesh shards must be an int >= 1, got {self.shards!r}")
        _check(self.cohort in COHORT_MODES,
               f"mesh cohort must be one of {COHORT_MODES}, "
               f"got {self.cohort!r}")
        _check(isinstance(self.platform, str),
               f"mesh platform must be a string, got {self.platform!r}")
        _check(isinstance(self.x64, bool),
               f"mesh x64 must be a bool, got {self.x64!r}")
        _check(isinstance(self.xla_flags, tuple)
               and all(isinstance(f, str) and f for f in self.xla_flags),
               f"mesh xla_flags must be a tuple of non-empty strings, "
               f"got {self.xla_flags!r}")


_SUB_SPECS = {"data": DataSpec, "train": TrainSpec, "async_": AsyncSpec,
              "eval": EvalSpec, "chain": ChainSpec, "mesh": MeshSpec,
              "obs": ObsSpec, "checkpoint": CheckpointSpec,
              "faults": FaultSpec}

#: FaultSpec round-list fields normalised list -> tuple on JSON load.
_FAULT_TUPLE_FIELDS = ("producer_fail_rounds", "bad_block_rounds",
                       "drop_commit_rounds", "delay_commit_rounds")


@dataclass(frozen=True)
class ExperimentSpec:
    """One experiment, declaratively: ``run(spec) -> ExperimentResult``."""
    data: DataSpec = field(default_factory=DataSpec)
    train: TrainSpec = field(default_factory=TrainSpec)
    async_: AsyncSpec = field(default_factory=AsyncSpec)
    eval: EvalSpec = field(default_factory=EvalSpec)
    chain: ChainSpec = field(default_factory=ChainSpec)
    mesh: MeshSpec = field(default_factory=MeshSpec)
    obs: ObsSpec = field(default_factory=ObsSpec)   # flight recorder (off)
    checkpoint: CheckpointSpec = field(         # snapshot/resume (off)
        default_factory=CheckpointSpec)
    faults: FaultSpec = field(default_factory=FaultSpec)  # injection (off)
    engine: bool = True               # arena-backed fused round engine
    seed: int = 0

    def __post_init__(self):
        # cross-field constraint (was a deep-in-the-driver failure before)
        _check(self.mesh.shards == 1 or self.engine,
               "mesh shards > 1 requires engine=True (the legacy oracle "
               "driver is single-device only)")

    # ------------------------------------------------------------------ #
    # conversions
    # ------------------------------------------------------------------ #

    def population_spec(self):
        """The ``repro.sim.PopulationSpec`` this experiment's population uses
        (seeded with the experiment seed)."""
        from repro.sim.population import PopulationSpec
        keys = PopulationSpec.__dataclass_fields__.keys() - {"seed"}
        d = dataclasses.asdict(self.data)
        return PopulationSpec(**{k: d[k] for k in keys}, seed=self.seed)

    def token_spec(self):
        """The ``repro.sim.population.TokenSpec`` of token data, or None."""
        if self.data.dataset != "markov-topics":
            return None
        from repro.sim.population import TokenSpec
        d = self.data
        return TokenSpec(d.seq_len, d.vocab_size, d.n_topics, d.probe_len)

    def sim_config(self):
        """Flat legacy view (``repro.sim.SimConfig``) consumed by the round
        loop; constructed without the deprecation warning."""
        from repro.sim.driver import SimConfig
        t, a, e, c = self.train, self.async_, self.eval, self.chain
        return SimConfig._internal(
            rounds=t.rounds, sample_frac=t.sample_frac,
            n_clusters=t.n_clusters, local_epochs=t.local_epochs, lr=t.lr,
            deadline=t.deadline, sampler=t.sampler, mode=t.mode,
            strategy=t.strategy, strategy_params=dict(t.strategy_params),
            buffer_size=a.buffer_size, staleness_alpha=a.staleness_alpha,
            server_lr=a.server_lr, concurrency=a.concurrency,
            total_reward=c.total_reward, rho=c.rho,
            initial_stake=c.initial_stake, eval_every=e.every,
            eval_clients=e.clients, eval_examples=e.examples,
            hidden=tuple(t.hidden), rep_dim=t.rep_dim, model=t.model,
            model_overrides=dict(t.model_overrides), engine=self.engine,
            mesh_shards=self.mesh.shards, mesh_cohort=self.mesh.cohort,
            seed=self.seed)

    @classmethod
    def from_flat(cls, data: DataSpec | None = None, **flat) -> "ExperimentSpec":
        """Build a nested spec from flat ``SimConfig``-style kwargs — the
        migration path for CLIs and benchmarks that accumulate flat knobs."""
        from repro.sim.driver import SimConfig
        return SimConfig._internal(**flat).to_spec(data=data)

    # ------------------------------------------------------------------ #
    # JSON round trip + digest
    # ------------------------------------------------------------------ #

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["train"]["hidden"] = list(self.train.hidden)
        d["train"]["strategy_params"] = dict(self.train.strategy_params)
        d["train"]["model_overrides"] = dict(self.train.model_overrides)
        d["mesh"]["xla_flags"] = list(self.mesh.xla_flags)
        for f in _FAULT_TUPLE_FIELDS:
            d["faults"][f] = list(getattr(self.faults, f))
        return d

    def to_json(self, indent: int | None = None) -> str:
        return json.dumps(self.to_dict(), indent=indent, sort_keys=True)

    @classmethod
    def from_dict(cls, d: Mapping[str, Any]) -> "ExperimentSpec":
        d = dict(d)
        if "async" in d:                      # friendly alias for the
            d["async_"] = d.pop("async")      # keyword-escaped field name
        unknown = set(d) - set(_SUB_SPECS) - {"engine", "seed"}
        if unknown:
            # silently dropping a misspelt section would run defaults under a
            # digest the author never configured — reject loudly instead
            raise ValueError(
                f"unknown spec section(s) {sorted(unknown)}; expected "
                f"{sorted(_SUB_SPECS)} + ['engine', 'seed']")
        kw: dict[str, Any] = {}
        for name, sub_cls in _SUB_SPECS.items():
            sub = dict(d.get(name, {}))
            if name == "train" and "hidden" in sub:
                sub["hidden"] = tuple(sub["hidden"])
            if name == "mesh" and "xla_flags" in sub:
                sub["xla_flags"] = tuple(sub["xla_flags"])
            if name == "faults":
                for f in _FAULT_TUPLE_FIELDS:
                    if f in sub:
                        sub[f] = tuple(sub[f])
            kw[name] = sub_cls(**sub)
        for name in ("engine", "seed"):
            if name in d:
                kw[name] = d[name]
        return cls(**kw)

    @classmethod
    def from_json(cls, text: str) -> "ExperimentSpec":
        return cls.from_dict(json.loads(text))

    def config_digest(self) -> str:
        """Stable SHA-256 over the canonical JSON form — the reproducibility
        stamp every run manifest carries.

        The ``obs`` and ``checkpoint`` sections are excluded: both are
        out-of-band by contract — observability times and counts but never
        perturbs, and checkpointing snapshots state without changing the
        trajectory (the resume tests pin bit-identical manifests with
        checkpointing on, off, and resumed-from) — so such runs all share
        the same replay recipe.  ``faults`` IS included: an injected fault
        schedule perturbs the run it describes.
        """
        d = self.to_dict()
        d.pop("obs", None)
        d.pop("checkpoint", None)
        return hashlib.sha256(
            json.dumps(d, sort_keys=True).encode()).hexdigest()

    def resume_digest(self) -> str:
        """The experiment identity a checkpoint binds to: like
        ``config_digest`` but ALSO excluding ``faults``, so a crashed run can
        be resumed with its fault schedule cleared (a ``round_start`` crash
        fault would otherwise re-fire on every resume, forever) while any
        change to the underlying experiment is still rejected at restore."""
        d = self.to_dict()
        for section in ("obs", "checkpoint", "faults"):
            d.pop(section, None)
        return hashlib.sha256(
            json.dumps(d, sort_keys=True).encode()).hexdigest()
