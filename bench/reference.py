"""Plain reference of what a cell's timed path computes.

Written from the model's and the protocol's equations, in straightforward
``jax.numpy`` at float32 with ``precision="highest"`` on every product, and
importing nothing of the program.  The control computes the same one step
lower, as the configuration names it: a ``dtype`` for storage and
arithmetic, and a ``precision`` for products, where ``"high"`` takes every
product (forward and backward) in three bfloat16 passes with float32
accumulation, written out here so that it means the same on every backend.

The model is the classifier MLP of the configuration (``in -> hidden... ->
rep (tanh) -> classes``).  A client's parameters are one float32 row in the
arena's column order: leaves sorted by name (``b0, b1, ..., b_head, w0,
w1, ..., w_head``), each raveled row-major.
"""
from __future__ import annotations

import hashlib
import json
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
REFERENCE = ("float32", "highest")      # (dtype, precision) of the reference


# --------------------------------------------------------------------------- #
# layout and weights
# --------------------------------------------------------------------------- #

def layout(model: dict) -> list[tuple[str, tuple[int, ...]]]:
    """(name, shape) of every leaf in arena column order."""
    dims = [model["in_dim"], *model["hidden"], model["rep_dim"]]
    leaves = {}
    for i, (a, b) in enumerate(zip(dims[:-1], dims[1:])):
        leaves[f"w{i}"] = (a, b)
        leaves[f"b{i}"] = (b,)
    leaves["w_head"] = (model["rep_dim"], model["num_classes"])
    leaves["b_head"] = (model["num_classes"],)
    return sorted(leaves.items())


def unflatten(model: dict, rows: jax.Array) -> dict:
    """(..., N) rows -> {leaf: (..., *shape)}."""
    out, off = {}, 0
    for name, shape in layout(model):
        size = int(np.prod(shape))
        out[name] = rows[..., off:off + size].reshape(rows.shape[:-1] + shape)
        off += size
    return out


def flatten(model: dict, params: dict) -> jax.Array:
    lead = params["w_head"].shape[:-2]
    return jnp.concatenate([params[name].reshape(lead + (-1,))
                            for name, _ in layout(model)], axis=-1)


def init_row_from_key(model: dict, key: jax.Array) -> jax.Array:
    """The initial weights every client starts from: He-normal matrices
    (the head at 1/fan_in), zero biases, drawn from ``key``."""
    dims = [model["in_dim"], *model["hidden"], model["rep_dim"]]
    keys = jax.random.split(key, len(dims))
    params = {}
    for i, (a, b) in enumerate(zip(dims[:-1], dims[1:])):
        params[f"w{i}"] = jax.random.normal(keys[i], (a, b)) * (2.0 / a) ** 0.5
        params[f"b{i}"] = jnp.zeros((b,))
    rep, classes = model["rep_dim"], model["num_classes"]
    params["w_head"] = jax.random.normal(keys[-1], (rep, classes)) \
        * (1.0 / rep) ** 0.5
    params["b_head"] = jnp.zeros((classes,))
    return flatten(model, params).astype(jnp.float32)


# --------------------------------------------------------------------------- #
# model, loss, local training
# --------------------------------------------------------------------------- #

def _split(a):
    hi = a.astype(jnp.bfloat16)
    return hi, (a - hi.astype(a.dtype)).astype(jnp.bfloat16)


def _three_pass(a, b):
    (ah, al), (bh, bl) = _split(a), _split(b)
    f32 = jnp.float32
    return (jnp.matmul(ah, bh, preferred_element_type=f32)
            + jnp.matmul(ah, bl, preferred_element_type=f32)
            + jnp.matmul(al, bh, preferred_element_type=f32)).astype(a.dtype)


@jax.custom_vjp
def _mm_high(a, b):
    return _three_pass(a, b)


def _mm_high_fwd(a, b):
    return _three_pass(a, b), (a, b)


def _mm_high_bwd(res, g):
    a, b = res
    return (_three_pass(g, jnp.swapaxes(b, -1, -2)),
            _three_pass(jnp.swapaxes(a, -1, -2), g))


_mm_high.defvjp(_mm_high_fwd, _mm_high_bwd)


def _mm(a, b, precision: str = "highest"):
    """A product at ``precision``: ``highest``, or ``high`` (three bfloat16
    passes)."""
    if precision == "high" and a.dtype == jnp.float32:
        return _mm_high(a, b)
    return jnp.matmul(a, b, precision=HI)


def embed(model: dict, p: dict, x: jax.Array, precision: str = "highest"
          ) -> jax.Array:
    n_layers = len(model["hidden"]) + 1
    h = x
    for i in range(n_layers):
        h = _mm(h, p[f"w{i}"], precision) + p[f"b{i}"]
        if i < n_layers - 1:
            h = jnp.maximum(h, 0)
    return jnp.tanh(h)


def logits(model: dict, p: dict, x: jax.Array, precision: str = "highest"
           ) -> jax.Array:
    return _mm(embed(model, p, x, precision), p["w_head"], precision) \
        + p["b_head"]


def xent(model: dict, p: dict, x: jax.Array, y: jax.Array,
         precision: str = "highest") -> jax.Array:
    z = logits(model, p, x, precision)
    z = z - jnp.max(z, axis=-1, keepdims=True)
    logp = z - jnp.log(jnp.sum(jnp.exp(z), axis=-1, keepdims=True))
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=-1))


def adam_train(model: dict, row: jax.Array, cx: jax.Array, cy: jax.Array,
               lr: float, steps: int, precision: str = "highest",
               b1: float = 0.9, b2: float = 0.999, eps: float = 1e-8):
    """One client's local training from a fresh Adam state: ``steps``
    minibatch steps cycling over its batches.  Returns the trained row and
    the mean loss of the steps."""
    dt = row.dtype
    p = unflatten(model, row)
    m = jax.tree.map(jnp.zeros_like, p)
    v = jax.tree.map(jnp.zeros_like, p)
    nb = cx.shape[0]
    total = jnp.zeros((), dt)
    for t in range(1, steps + 1):
        bx, by = cx[(t - 1) % nb], cy[(t - 1) % nb]
        loss, g = jax.value_and_grad(partial(xent, model, precision=precision))(
            p, bx, by)
        total = total + loss
        m = jax.tree.map(lambda a, b: (b1 * a + (1 - b1) * b).astype(dt), m, g)
        v = jax.tree.map(lambda a, b: (b2 * a + (1 - b2) * b * b).astype(dt),
                         v, g)
        c1, c2 = 1 - b1 ** t, 1 - b2 ** t
        p = jax.tree.map(
            lambda w, a, b: (w - lr * (a / c1) / (jnp.sqrt(b / c2) + eps)
                             ).astype(dt), p, m, v)
    return flatten(model, p), total / steps


@partial(jax.jit, static_argnums=(0, 5, 6))
def train_cohort(model_key, rows, cx, cy, lr, steps, arith=REFERENCE):
    """All cohort clients' local training: (k, N) rows -> trained rows and
    per-client mean loss, in ``arith`` (dtype, precision)."""
    model = dict(model_key)
    model["hidden"] = list(model["hidden"])
    dt = jnp.dtype(arith[0])
    f = lambda r, x, y: adam_train(model, r, x, y, lr, steps,  # noqa: E731
                                   arith[1])
    return jax.vmap(f)(rows.astype(dt), cx.astype(dt), cy)


def model_key(model: dict):
    """Hashable form of a model section (a static jit argument)."""
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in model.items()
                        if k in ("in_dim", "hidden", "rep_dim",
                                 "num_classes")))


# --------------------------------------------------------------------------- #
# aggregation
# --------------------------------------------------------------------------- #

def cluster_means(rows: np.ndarray, labels: np.ndarray, weights: np.ndarray,
                  n_clusters: int) -> np.ndarray:
    """Every slot receives the weighted mean of the rows of its cluster
    (float64 sums on the host)."""
    out = np.zeros(rows.shape, np.float64)
    r64 = rows.astype(np.float64)
    for c in range(n_clusters):
        sel = labels == c
        w = weights * sel
        if w.sum() > 0:
            out[sel] = (w[:, None] * r64).sum(0) / w.sum()
    return out


def masked_mean(rows: np.ndarray, weights: np.ndarray) -> np.ndarray:
    mean = (weights[:, None] * rows.astype(np.float64)).sum(0) \
        / max(weights.sum(), 1.0)
    return np.broadcast_to(mean, rows.shape)


def fedbuff_weights(staleness: np.ndarray, verified: np.ndarray,
                    alpha: float) -> np.ndarray:
    """FedBuff's staleness discount (1 + s)^-alpha, zero where the chain
    refused the update."""
    return (1.0 + staleness.astype(np.float64)) ** (-alpha) * verified


# --------------------------------------------------------------------------- #
# the chain, read back
# --------------------------------------------------------------------------- #

FINGERPRINT_BASE = 0x85EBCA77


def fingerprint(rows: np.ndarray) -> np.ndarray:
    """(k, N) float32 rows -> (k, 2) uint32 residues of the chain's model
    fingerprint: with v the raw bits of each parameter, mixed as
    v ^ (v >> 16), A = sum_j v_j r^(j+1) and B = sum_j v_j r^(2(j+1)),
    mod 2^32, r = 0x85EBCA77."""
    v = np.ascontiguousarray(rows, np.float32).view(np.uint32) \
        .astype(np.uint64)
    v ^= v >> np.uint64(16)
    n = v.shape[1]
    mod = 1 << 32
    w1 = np.empty(n, np.uint64)
    acc = 1
    for j in range(n):
        acc = acc * FINGERPRINT_BASE % mod
        w1[j] = acc
    w2 = w1 * w1 % np.uint64(mod)
    a = (v * w1 % np.uint64(mod)).sum(axis=1, dtype=np.uint64) % np.uint64(mod)
    b = (v * w2 % np.uint64(mod)).sum(axis=1, dtype=np.uint64) % np.uint64(mod)
    return np.stack([a, b], axis=1).astype(np.uint32)


def digest(residues, n_params: int) -> str:
    """The digest string a client commits: both residues and the length,
    as eight hex digits each."""
    a, b = (int(x) & 0xFFFFFFFF for x in residues)
    return f"{a:08x}{b:08x}{n_params:08x}"


def block_link_breaks(blocks) -> int:
    """Blocks whose ``prev`` field is not the SHA-256 of the canonical JSON
    header of the block before them."""
    breaks = 0
    for prev, cur in zip(blocks, blocks[1:]):
        header = {"index": prev.index, "round": prev.round_idx,
                  "producer": prev.producer, "prev": prev.prev_hash,
                  "merkle": prev.merkle_root}
        digest = hashlib.sha256(
            json.dumps(header, sort_keys=True).encode()).hexdigest()
        breaks += int(cur.prev_hash != digest)
    return breaks


def block_commitments(block) -> tuple[dict[int, str], dict[int, str]]:
    """(first ``model_hash`` digest per sender, first digest per sender in
    the producer's ``agg_commit`` record) of one block."""
    committed: dict[int, str] = {}
    recorded: dict[int, str] | None = None
    for tx in block.transactions:
        if tx.kind == "model_hash" and tx.round_idx == block.round_idx:
            committed.setdefault(int(tx.sender), tx.payload)
        elif (tx.kind == "agg_commit" and tx.sender == block.producer
              and recorded is None):
            body = json.loads(tx.payload)
            entries = body["entries"] if isinstance(body, dict) else body
            recorded = {}
            for sender, digest in entries:
                recorded.setdefault(int(sender), digest)
    return committed, recorded or {}


# --------------------------------------------------------------------------- #
# PAA: prototypes, Pearson, spectral embedding, k-means (BFLN section IV-B)
# --------------------------------------------------------------------------- #

@partial(jax.jit, static_argnums=(0, 3))
def prototypes(model_key, rows, probe, arith=REFERENCE):
    """(k, N) rows -> (k, rep) mean representation of the probe batch
    through every row's model (paper Eq. 1)."""
    model = dict(model_key)
    model["hidden"] = list(model["hidden"])
    dt = jnp.dtype(arith[0])
    p = unflatten(model, rows.astype(dt))
    x = probe.astype(dt)
    return jax.vmap(lambda q: jnp.mean(embed(model, q, x, arith[1]),
                                       axis=0))(p)


@partial(jax.jit, static_argnums=(1,))
def _pearson_device(protos, arith):
    c = protos.astype(arith[0])
    c = c - jnp.mean(c, axis=1, keepdims=True)
    c = c / jnp.linalg.norm(c, axis=1, keepdims=True)
    return _mm(c, c.T, arith[1])


def pearson(protos, arith=None) -> np.ndarray:
    """(k, D) -> (k, k) Pearson correlation over the feature axis (paper
    Eq. 2-3): in float64 on the host, or in ``arith`` (the control's
    dtype and precision) on the device."""
    if arith is None:
        c = np.asarray(protos, np.float64)
        c = c - c.mean(axis=1, keepdims=True)
        c = c / np.maximum(np.linalg.norm(c, axis=1, keepdims=True), 1e-8)
        return np.clip(c @ c.T, -1.0, 1.0)
    return np.clip(np.asarray(_pearson_device(jnp.asarray(protos), arith),
                              np.float64), -1.0, 1.0)


def spectral_embedding(corr: np.ndarray, n_clusters: int) -> np.ndarray:
    """Rows of the ``n_clusters`` eigenvectors of the normalised Laplacian
    of the affinity (1 + corr) / 2 with the smallest eigenvalues, each row
    scaled to unit length (Ng, Jordan and Weiss)."""
    m = corr.shape[0]
    a = (corr + 1.0) * 0.5 * (1.0 - np.eye(m))
    d = 1.0 / np.sqrt(np.maximum(a.sum(axis=1), 1e-8))
    lap = np.eye(m) - a * d[:, None] * d[None, :]
    _, vecs = np.linalg.eigh(lap)
    emb = vecs[:, :n_clusters]
    return emb / np.maximum(np.linalg.norm(emb, axis=1, keepdims=True), 1e-8)


def kmeans(points: np.ndarray, n_clusters: int, n_iters: int = 25
           ) -> np.ndarray:
    """Lloyd's algorithm from a farthest-first start at point 0, a fixed
    number of iterations; an empty cluster keeps its centre."""
    centers = [points[0]]
    mind = np.full(points.shape[0], np.inf)
    for _ in range(1, n_clusters):
        mind = np.minimum(mind, ((points - centers[-1]) ** 2).sum(axis=1))
        centers.append(points[int(np.argmax(mind))])
    centers = np.stack(centers)
    for _ in range(n_iters + 1):
        d = ((points[:, None, :] - centers[None]) ** 2).sum(axis=-1)
        labels = np.argmin(d, axis=1)
        for c in range(n_clusters):
            if np.any(labels == c):
                centers[c] = points[labels == c].mean(axis=0)
    return labels


def paa(model_key, rows, probe, n_clusters: int, arith=REFERENCE
        ) -> tuple[np.ndarray, np.ndarray]:
    """(spectral embedding, labels) of PAA over the (k, N) trained rows:
    prototypes in float32 and Pearson in float64, or both in the control's
    ``arith``; the eigendecomposition and k-means in float64."""
    protos = prototypes(model_key, rows, probe, arith)
    corr = pearson(protos, None if arith == REFERENCE else arith)
    emb = spectral_embedding(corr, n_clusters)
    return emb, kmeans(emb, n_clusters)


def partition_cost(points: np.ndarray, labels: np.ndarray) -> float:
    """Sum of squared distances of the points to their cluster's mean: a
    partition's k-means cost, whatever the clusters are numbered."""
    cost = 0.0
    for c in np.unique(labels):
        sel = points[labels == c]
        cost += float(((sel - sel.mean(axis=0)) ** 2).sum())
    return cost
