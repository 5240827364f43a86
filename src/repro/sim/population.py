"""Virtual client populations over the non-IID partitions.

Scales the paper's 20 always-on clients to thousands of *virtual* clients:
each client owns a Dirichlet label-skew shard (``repro.data.partition``) plus
a behavioural profile —

  * ``speed``        — latency multiplier (stragglers live in the slow tail),
  * ``availability`` — probability the client is online when a round starts,
  * ``dropout``      — probability an accepted client dies mid-round,
  * ``byzantine``    — commits a hash for params it did not train (the
                       paper's freeriding attack, caught by CACC verification).

Data stays rectangular (every client: ``n_batches × batch_size`` train
samples + a small local test split) so any sampled cohort stacks into the
vmapped trainer without reshaping.
"""
from __future__ import annotations

from dataclasses import dataclass, field

import jax.numpy as jnp
import numpy as np

from repro.data import dirichlet_partition, make_classification_dataset, pack_clients
from repro.data.partition import sample_probe_batch
from repro.sim.clock import LatencyModel, make_speed_profile


@dataclass(frozen=True)
class PopulationSpec:
    n_clients: int = 1000
    dataset: str = "synth10"
    beta: float = 0.3                 # Dirichlet label-skew concentration
    n_batches: int = 1
    batch_size: int = 16
    availability: float = 0.85        # mean per-round online probability
    dropout_rate: float = 0.03        # mean mid-round death probability
    straggler_frac: float = 0.10
    straggler_slowdown: float = 8.0
    byzantine_frac: float = 0.0
    base_latency: float = 10.0        # virtual seconds, 1×-speed local round
    latency_sigma: float = 0.25
    psi: int = 32                     # probe-batch size for PAA
    seed: int = 0


@dataclass(frozen=True)
class TokenSpec:
    """Token data of a federated LM (dataset ``"markov-topics"``,
    ``repro.data.lm``): rows of ``seq_len`` inputs with their next-token
    targets over ``vocab_size`` tokens, ``n_topics`` topics mixed per client
    by Dirichlet(``beta``), and PAA probe rows of ``probe_len`` tokens."""
    seq_len: int
    vocab_size: int
    n_topics: int = 8
    probe_len: int = 256


@dataclass
class ClientPopulation:
    """Materialised population: data shards + behaviour profiles + latency."""

    spec: PopulationSpec
    cx: jnp.ndarray                   # (n, n_batches, B, ...) train
    cy: jnp.ndarray                   # (n, n_batches, B)
    tx: np.ndarray                    # (n, n_test, ...) per-client local test
    ty: np.ndarray                    # (n, n_test)
    test_x: jnp.ndarray               # shared global test split
    test_y: jnp.ndarray
    probe: jnp.ndarray                # (psi, ...) PAA probe batch
    num_classes: int
    in_dim: int
    availability: np.ndarray          # (n,) per-client online probability
    dropout: np.ndarray               # (n,) per-client mid-round death prob
    byzantine: np.ndarray             # (n,) bool
    latency: LatencyModel = field(repr=False)
    tokens: TokenSpec | None = None   # token data: its shape (LM only)

    @property
    def n_clients(self) -> int:
        return self.spec.n_clients

    @classmethod
    def from_spec(cls, spec: PopulationSpec,
                  tokens: TokenSpec | None = None) -> "ClientPopulation":
        """``tokens``: the token data's shape, for dataset
        ``"markov-topics"``."""
        rng = np.random.default_rng(spec.seed)
        if spec.dataset == "markov-topics":
            if tokens is None:
                raise ValueError("token data needs a TokenSpec (seq_len, "
                                 "vocab_size, ...)")
            return cls._tokens(spec, tokens, rng)
        (xt, yt), (xe, ye) = make_classification_dataset(spec.dataset,
                                                         seed=spec.seed)
        parts = dirichlet_partition(yt, spec.n_clients, spec.beta,
                                    seed=spec.seed)
        cx, cy, tx, ty = pack_clients(xt, yt, parts, n_batches=spec.n_batches,
                                      batch_size=spec.batch_size,
                                      seed=spec.seed)
        probe = sample_probe_batch(xt, yt, category=0, psi=spec.psi,
                                   seed=spec.seed)

        return cls(
            spec=spec,
            cx=jnp.asarray(cx), cy=jnp.asarray(cy), tx=tx, ty=ty,
            test_x=jnp.asarray(xe), test_y=jnp.asarray(ye),
            probe=jnp.asarray(probe),
            num_classes=int(yt.max()) + 1, in_dim=int(xt.shape[1]),
            **_behaviour(spec, rng))

    @classmethod
    def _tokens(cls, spec: PopulationSpec, tokens: TokenSpec,
                rng) -> "ClientPopulation":
        """Token rows of a federated LM (``repro.data.lm``): inputs and
        next-token targets, one row per sample; ``num_classes`` is the
        vocabulary, ``in_dim`` the row length."""
        from repro.data.lm import token_population
        per = spec.n_batches * spec.batch_size
        t = tokens
        x, y, tx, ty, test_x, test_y, probe = token_population(
            spec.n_clients, per, t.seq_len, t.vocab_size, t.n_topics,
            spec.beta, spec.psi, t.probe_len, seed=spec.seed + 2)
        shape = (spec.n_clients, spec.n_batches, spec.batch_size, t.seq_len)
        return cls(
            spec=spec, cx=jnp.asarray(x.reshape(shape)),
            cy=jnp.asarray(y.reshape(shape)), tx=tx, ty=ty,
            test_x=jnp.asarray(test_x), test_y=jnp.asarray(test_y),
            probe=jnp.asarray(probe), num_classes=t.vocab_size,
            in_dim=t.seq_len, tokens=t, **_behaviour(spec, rng))

    # ------------------------------------------------------------------ #

    def online_clients(self, rng: np.random.Generator) -> np.ndarray:
        """Ids of clients online at a round boundary (availability draw)."""
        return np.flatnonzero(rng.random(self.n_clients) < self.availability)

    def cohort_data(self, cohort: np.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
        """Stacked (k, n_batches, B, ...) train data for a sampled cohort."""
        idx = jnp.asarray(np.asarray(cohort))
        return self.cx[idx], self.cy[idx]


def _behaviour(spec: PopulationSpec, rng: np.random.Generator) -> dict:
    """Per-client behaviour, jittered around the spec means."""
    n = spec.n_clients
    avail = np.clip(rng.normal(spec.availability, 0.08, size=n), 0.05, 1.0)
    drop = np.clip(rng.normal(spec.dropout_rate, spec.dropout_rate / 2,
                              size=n), 0.0, 0.9)
    byz = np.zeros(n, dtype=bool)
    n_byz = int(round(spec.byzantine_frac * n))
    if n_byz:
        byz[rng.choice(n, size=n_byz, replace=False)] = True

    speed = make_speed_profile(n, spec.straggler_frac,
                               spec.straggler_slowdown, rng)
    latency = LatencyModel(speed, spec.base_latency, spec.latency_sigma,
                           np.random.default_rng(spec.seed + 1))
    return dict(availability=avail, dropout=drop, byzantine=byz,
                latency=latency)
