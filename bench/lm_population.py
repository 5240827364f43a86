"""The token population a language-model cell federates, made from ``--seed``.

A copy of the recipe of ``repro.data.lm`` (topic successor tables, Dirichlet
topic mixes, documents packed end to end) and of the behaviour draws of
``bench.population``, kept here so that the tokens the program trains on and
the tokens the reference reads come from the benchmark, not from the
program.  The arrays are handed to the program's ``ClientPopulation``
unchanged.

The topic tables, the shared held-out split and PAA's probe batch are fixed
(``DATASET_SEED``), as a published corpus is: the program closes over the
probe as a constant of its compiled round step, so a probe that changed with
the seed would make every seed compile anew.  The seed draws each client's
topic mix, its rows and its behaviour.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

DATASET_SEED = 0
N_HELDOUT = 16
TOPIC_SALT = 2246822519
DOC_LEN = (64, 512)


@dataclass
class TokenPopulation:
    cx: np.ndarray            # (n, n_batches, B, S) int32 inputs
    cy: np.ndarray            # (n, n_batches, B, S) next-token targets
    tx: np.ndarray            # (n, 1, S) one local held-out row each
    ty: np.ndarray
    test_x: np.ndarray        # (N_HELDOUT, S) shared held-out split
    test_y: np.ndarray
    probe: np.ndarray         # (psi, probe_len) one topic-0 document a row
    availability: np.ndarray
    dropout: np.ndarray
    byzantine: np.ndarray
    speed: np.ndarray


def successors(vocab: int, n_topics: int, branching: int = 8) -> np.ndarray:
    base = (np.arange(vocab, dtype=np.int64)[:, None] * 2654435761
            + np.arange(branching, dtype=np.int64)[None, :] * 40503)
    salt = np.arange(n_topics, dtype=np.int64)[:, None, None] * TOPIC_SALT
    return (np.abs(base[None] + salt) % vocab).astype(np.int32)


def streams(succ, mix, n_tokens, rng, doc_len=DOC_LEN) -> np.ndarray:
    """(m, n_tokens): documents end to end, each of one topic drawn from its
    stream's mix, of a uniform length in ``doc_len``, from a uniform first
    token through Zipf-weighted successors."""
    n_topics, vocab, branching = succ.shape
    m = mix.shape[0]
    w = 1.0 / np.arange(1, branching + 1)
    choices = rng.choice(branching, size=(n_tokens, m), p=w / w.sum())
    cum = np.cumsum(mix, axis=1)
    out = np.empty((m, n_tokens), np.int32)
    topic, tok, left = (np.zeros(m, np.int64) for _ in range(3))
    for i in range(n_tokens):
        new = left == 0
        cont = ~new
        tok[cont] = succ[topic[cont], tok[cont], choices[i, cont]]
        if new.any():
            idx = np.flatnonzero(new)
            topic[idx] = np.minimum(
                (rng.random(idx.size)[:, None] > cum[idx]).sum(axis=1),
                n_topics - 1)
            left[idx] = rng.integers(doc_len[0], doc_len[1] + 1,
                                     size=idx.size)
            tok[idx] = rng.integers(vocab, size=idx.size)
        out[:, i] = tok
        left -= 1
    return out


def make_population(data: dict, vocab: int, seed: int) -> TokenPopulation:
    """``data`` is the configuration's ``data`` section."""
    n, S = int(data["n_clients"]), int(data["seq_len"])
    per = int(data["n_batches"]) * int(data["batch_size"])
    n_topics = int(data["n_topics"])
    succ = successors(vocab, n_topics)
    rng = np.random.default_rng([seed, 1])
    mix = rng.dirichlet(np.full(n_topics, float(data["beta"])), size=n)
    rows = streams(succ, mix, (per + 1) * (S + 1), rng).reshape(
        n, per + 1, S + 1)
    drng = np.random.default_rng(DATASET_SEED)
    even = np.full((N_HELDOUT, n_topics), 1.0 / n_topics)
    held = streams(succ, even, S + 1, drng)
    psi, plen = int(data["psi"]), int(data["probe_len"])
    one = np.zeros((psi, n_topics))
    one[:, 0] = 1.0
    probe = streams(succ, one, plen, drng, doc_len=(plen, plen))
    shape = (n, int(data["n_batches"]), int(data["batch_size"]), S)

    brng = np.random.default_rng(seed)
    avail = np.clip(brng.normal(data["availability"], 0.08, size=n), 0.05, 1.0)
    rate = float(data["dropout_rate"])
    drop = np.clip(brng.normal(rate, rate / 2, size=n), 0.0, 0.9)
    byz = np.zeros(n, dtype=bool)
    n_byz = int(round(float(data["byzantine_frac"]) * n))
    if n_byz:
        byz[brng.choice(n, size=n_byz, replace=False)] = True
    speed = brng.uniform(0.8, 1.25, size=n)
    n_strag = int(round(float(data["straggler_frac"]) * n))
    if n_strag:
        speed[brng.choice(n, size=n_strag, replace=False)] *= float(
            data["straggler_slowdown"])
    return TokenPopulation(
        cx=rows[:, :per, :-1].reshape(shape), cy=rows[:, :per, 1:].reshape(shape),
        tx=rows[:, per:, :-1], ty=rows[:, per:, 1:],
        test_x=held[:, :-1], test_y=held[:, 1:], probe=probe,
        availability=avail, dropout=drop, byzantine=byz, speed=speed)


def program_population(pd: TokenPopulation, data: dict, vocab: int,
                       seed: int):
    """The program's ``ClientPopulation`` over these arrays, with its own
    latency model seeded as ``from_spec`` seeds it."""
    import jax.numpy as jnp

    from repro.sim.clock import LatencyModel
    from repro.sim.population import ClientPopulation, PopulationSpec, TokenSpec

    keys = PopulationSpec.__dataclass_fields__.keys() - {"seed"}
    spec = PopulationSpec(**{k: data[k] for k in keys}, seed=seed)
    latency = LatencyModel(pd.speed, float(data["base_latency"]),
                           float(data["latency_sigma"]),
                           np.random.default_rng(seed + 1))
    return ClientPopulation(
        spec=spec, cx=jnp.asarray(pd.cx), cy=jnp.asarray(pd.cy), tx=pd.tx,
        ty=pd.ty, test_x=jnp.asarray(pd.test_x),
        test_y=jnp.asarray(pd.test_y), probe=jnp.asarray(pd.probe),
        num_classes=vocab, in_dim=pd.cx.shape[-1],
        availability=pd.availability, dropout=pd.dropout,
        byzantine=pd.byzantine, latency=latency,
        tokens=TokenSpec(int(data["seq_len"]), vocab, int(data["n_topics"]),
                         int(data["probe_len"])))
