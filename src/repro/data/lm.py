"""Synthetic token streams for language-model training examples.

A first-order Markov chain with Zipf-distributed stationary mass gives a
non-trivial next-token structure (learnable; loss drops measurably within a
few hundred steps) without any external corpus.
"""
from __future__ import annotations

import numpy as np


def make_token_stream(vocab_size: int, n_tokens: int, seed: int = 0,
                      branching: int = 8) -> np.ndarray:
    """Each token deterministically restricts its successors to ``branching``
    candidates (hash-derived), sampled Zipf-weighted -> learnable bigram task."""
    rng = np.random.default_rng(seed)
    ranks = np.arange(1, branching + 1, dtype=np.float64)
    probs = (1.0 / ranks) / np.sum(1.0 / ranks)

    # successor table: vocab_size x branching, derived from a hashed congruence
    base = (np.arange(vocab_size, dtype=np.int64)[:, None] * 2654435761
            + np.arange(branching, dtype=np.int64)[None, :] * 40503)
    succ = np.abs(base) % vocab_size

    out = np.empty(n_tokens, dtype=np.int32)
    tok = int(rng.integers(vocab_size))
    choices = rng.choice(branching, size=n_tokens, p=probs)
    for i in range(n_tokens):
        out[i] = tok
        tok = int(succ[tok, choices[i]])
    return out


def batch_stream(tokens: np.ndarray, batch: int, seq_len: int, n_steps: int,
                 seed: int = 0):
    """Yield (tokens, labels) batches of shape (batch, seq_len)."""
    rng = np.random.default_rng(seed)
    max_start = len(tokens) - seq_len - 1
    for _ in range(n_steps):
        starts = rng.integers(0, max_start, size=batch)
        x = np.stack([tokens[s:s + seq_len] for s in starts])
        y = np.stack([tokens[s + 1:s + seq_len + 1] for s in starts])
        yield x, y


# --------------------------------------------------------------------------- #
# non-IID token data for a federated language model
# --------------------------------------------------------------------------- #

TOPIC_SALT = 2246822519          # per-topic offset of the successor hash


def topic_successors(vocab_size: int, n_topics: int, branching: int = 8
                     ) -> np.ndarray:
    """(n_topics, vocab, branching) successor tables: topic ``t`` hashes the
    same congruence as :func:`make_token_stream`, offset by ``t``, so each
    topic is its own bigram language (topic 0 is that stream's)."""
    base = (np.arange(vocab_size, dtype=np.int64)[:, None] * 2654435761
            + np.arange(branching, dtype=np.int64)[None, :] * 40503)
    salt = np.arange(n_topics, dtype=np.int64)[:, None, None] * TOPIC_SALT
    return (np.abs(base[None] + salt) % vocab_size).astype(np.int32)


def markov_rows(succ: np.ndarray, mix: np.ndarray, n_tokens: int,
                rng: np.random.Generator, doc_len: tuple[int, int] = (64, 512)
                ) -> np.ndarray:
    """(m, n_tokens) streams, one per row of ``mix (m, n_topics)``: documents
    packed end to end, with no boundary marker.  A document draws its topic
    from its stream's mix, its length uniformly from ``doc_len``, its first
    token uniformly; each next token is a Zipf-weighted pick among its
    topic's successors of the token before."""
    n_topics, vocab, branching = succ.shape
    m = mix.shape[0]
    ranks = np.arange(1, branching + 1, dtype=np.float64)
    probs = (1.0 / ranks) / np.sum(1.0 / ranks)
    choices = rng.choice(branching, size=(n_tokens, m), p=probs)
    cum = np.cumsum(mix, axis=1)
    out = np.empty((m, n_tokens), np.int32)
    topic = np.zeros(m, np.int64)
    tok = np.zeros(m, np.int64)
    left = np.zeros(m, np.int64)
    for i in range(n_tokens):
        new = left == 0
        if new.any():
            idx = np.flatnonzero(new)
            u = rng.random(idx.size)
            topic[idx] = np.minimum((u[:, None] > cum[idx]).sum(axis=1),
                                    n_topics - 1)
            left[idx] = rng.integers(doc_len[0], doc_len[1] + 1, size=idx.size)
        cont = ~new
        tok[cont] = succ[topic[cont], tok[cont], choices[i, cont]]
        if new.any():
            tok[idx] = rng.integers(vocab, size=idx.size)
        out[:, i] = tok
        left -= 1
    return out


def topic_mixes(n_clients: int, n_topics: int, beta: float,
                rng: np.random.Generator) -> np.ndarray:
    """(n, n_topics) per-client topic mix, each row ~ Dirichlet(beta)."""
    return rng.dirichlet(np.full(n_topics, beta), size=n_clients)


def split_rows(rows: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """(..., S + 1) packed rows -> (inputs, next-token targets), (..., S)."""
    return rows[..., :-1], rows[..., 1:]


def token_population(n_clients: int, n_rows: int, seq_len: int,
                     vocab_size: int, n_topics: int, beta: float, psi: int,
                     probe_len: int, seed: int, n_heldout: int = 16):
    """Per-client token rows with a non-IID topic mix, a shared held-out
    split and PAA's probe batch.

    Returns ``(x, y, tx, ty, test_x, test_y, probe)``: ``x``/``y`` the
    clients' ``(n, n_rows, seq_len)`` inputs and targets, ``tx``/``ty`` one
    local held-out row each, ``test_x``/``test_y`` ``n_heldout`` shared rows
    over an even topic mix, and ``probe`` ``(psi, probe_len)``: one document
    of topic 0 per row."""
    rng = np.random.default_rng(seed)
    succ = topic_successors(vocab_size, n_topics)
    mix = topic_mixes(n_clients, n_topics, beta, rng)
    rows = markov_rows(succ, mix, (n_rows + 1) * (seq_len + 1), rng)
    rows = rows.reshape(n_clients, n_rows + 1, seq_len + 1)
    x, y = split_rows(rows[:, :n_rows])
    tx, ty = split_rows(rows[:, n_rows:])
    even = np.full((n_heldout, n_topics), 1.0 / n_topics)
    test_x, test_y = split_rows(markov_rows(succ, even, seq_len + 1, rng))
    one = np.zeros((psi, n_topics))
    one[:, 0] = 1.0
    probe = markov_rows(succ, one, probe_len, rng,
                        doc_len=(probe_len, probe_len))
    return x, y, tx, ty, test_x, test_y, probe
