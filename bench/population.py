"""The client population a cell federates, made from ``--seed``.

A copy of the recipe of ``repro.data`` (``make_classification_dataset``,
``dirichlet_partition``, ``pack_clients``, ``sample_probe_batch``) and of the
behaviour draws of ``repro.sim.population.ClientPopulation.from_spec`` and
``repro.sim.clock.make_speed_profile``, kept here so that the data the
program trains on and the data the reference reads come from the
benchmark, not from the program.  The arrays are handed to the program's
``ClientPopulation`` unchanged.

The synthetic dataset itself and the PAA probe batch drawn from it are
fixed (``DATASET_SEED``), as a published dataset is; the seed draws the
partition over clients, their batches and their behaviour.  The program
closes over the probe batch as a constant of its compiled round step, so a
probe that changed with the seed would make every seed compile anew.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

# name -> (classes, dim, margin, noise, n_train, n_test), as in repro.data
DATASETS = {
    "synth10": (10, 64, 1.0, 1.0, 20000, 4000),
    "synth100": (100, 64, 0.8, 1.0, 30000, 6000),
}
DATASET_SEED = 0


@dataclass
class PopulationData:
    """Everything the population holds, as host arrays."""
    cx: np.ndarray            # (n, n_batches, B, dim) float32
    cy: np.ndarray            # (n, n_batches, B) int32
    tx: np.ndarray            # (n, n_test, dim) per-client local test
    ty: np.ndarray
    test_x: np.ndarray        # shared test split
    test_y: np.ndarray
    probe: np.ndarray         # (psi, dim) PAA probe batch
    num_classes: int
    in_dim: int
    availability: np.ndarray  # (n,)
    dropout: np.ndarray       # (n,)
    byzantine: np.ndarray     # (n,) bool
    speed: np.ndarray         # (n,) latency multiplier


def make_dataset(name: str, seed: int):
    classes, dim, margin, noise, n_train, n_test = DATASETS[name]
    rng = np.random.default_rng(seed)
    means = rng.normal(size=(classes, dim)).astype(np.float32)
    means *= margin / np.linalg.norm(means, axis=1, keepdims=True)
    means *= np.sqrt(dim)
    w = rng.normal(size=(dim, dim)).astype(np.float32) / np.sqrt(dim)

    def sample(n):
        y = rng.integers(0, classes, size=n).astype(np.int32)
        x = means[y] + noise * rng.normal(size=(n, dim)).astype(np.float32)
        x = 0.5 * (x + np.tanh(x @ w))
        return x.astype(np.float32), y

    return sample(n_train), sample(n_test)


def dirichlet_partition(labels: np.ndarray, n_clients: int, beta: float,
                        seed: int, min_per_client: int = 2
                        ) -> list[np.ndarray]:
    """Label-skew split: each class's share per client from Dir(beta); every
    client then takes samples from the largest until it holds two."""
    rng = np.random.default_rng(seed)
    n_classes = int(labels.max()) + 1
    client_idx: list[list[int]] = [[] for _ in range(n_clients)]
    for k in range(n_classes):
        idx = np.flatnonzero(labels == k)
        rng.shuffle(idx)
        props = rng.dirichlet(np.full(n_clients, beta))
        cuts = (np.cumsum(props) * len(idx)).astype(int)[:-1]
        for cid, part in enumerate(np.split(idx, cuts)):
            client_idx[cid].extend(part.tolist())
    sizes = np.array([len(c) for c in client_idx])
    for cid in range(n_clients):
        while len(client_idx[cid]) < min_per_client:
            donor = int(np.argmax(sizes))
            client_idx[cid].append(client_idx[donor].pop())
            sizes[donor] -= 1
            sizes[cid] += 1
    return [np.asarray(sorted(c), dtype=np.int64) for c in client_idx]


def pack_clients(x, y, parts, n_batches: int, batch_size: int, seed: int,
                 test_frac: float = 0.2):
    rng = np.random.default_rng(seed)
    m = len(parts)
    need = n_batches * batch_size
    n_test = max(int(need * test_frac), 8)
    cx = np.zeros((m, need) + x.shape[1:], x.dtype)
    cy = np.zeros((m, need), y.dtype)
    tx = np.zeros((m, n_test) + x.shape[1:], x.dtype)
    ty = np.zeros((m, n_test), y.dtype)
    for cid, idx in enumerate(parts):
        idx = idx.copy()
        rng.shuffle(idx)
        split = max(int(len(idx) * (1 - test_frac)), 1)
        tr, te = idx[:split], idx[split:] if len(idx) > split else idx[:1]
        tr_sel = rng.choice(tr, size=need, replace=len(tr) < need)
        te_sel = rng.choice(te, size=n_test, replace=len(te) < n_test)
        cx[cid], cy[cid] = x[tr_sel], y[tr_sel]
        tx[cid], ty[cid] = x[te_sel], y[te_sel]
    return (cx.reshape(m, n_batches, batch_size, *x.shape[1:]),
            cy.reshape(m, n_batches, batch_size), tx, ty)


def make_population(data: dict, seed: int) -> PopulationData:
    """``data`` is the configuration's ``data`` section."""
    n = int(data["n_clients"])
    rng = np.random.default_rng(seed)
    (xt, yt), (xe, ye) = make_dataset(data["dataset"], DATASET_SEED)
    parts = dirichlet_partition(yt, n, float(data["beta"]), seed)
    cx, cy, tx, ty = pack_clients(xt, yt, parts, int(data["n_batches"]),
                                  int(data["batch_size"]), seed)
    prng = np.random.default_rng(DATASET_SEED)
    cat0 = np.flatnonzero(yt == 0)
    psi = int(data["psi"])
    probe = xt[prng.choice(cat0, size=psi, replace=len(cat0) < psi)]

    avail = np.clip(rng.normal(data["availability"], 0.08, size=n), 0.05, 1.0)
    rate = float(data["dropout_rate"])
    drop = np.clip(rng.normal(rate, rate / 2, size=n), 0.0, 0.9)
    byz = np.zeros(n, dtype=bool)
    n_byz = int(round(float(data["byzantine_frac"]) * n))
    if n_byz:
        byz[rng.choice(n, size=n_byz, replace=False)] = True
    speed = rng.uniform(0.8, 1.25, size=n)
    n_strag = int(round(float(data["straggler_frac"]) * n))
    if n_strag:
        speed[rng.choice(n, size=n_strag, replace=False)] *= float(
            data["straggler_slowdown"])
    return PopulationData(
        cx=cx, cy=cy, tx=tx, ty=ty, test_x=xe, test_y=ye, probe=probe,
        num_classes=int(yt.max()) + 1, in_dim=int(xt.shape[1]),
        availability=avail, dropout=drop, byzantine=byz,
        speed=speed.astype(np.float64))


def program_population(pd: PopulationData, data: dict, seed: int):
    """The program's ``ClientPopulation`` over these arrays, with its own
    latency model seeded as ``from_spec`` seeds it."""
    import jax.numpy as jnp

    from repro.sim.clock import LatencyModel
    from repro.sim.population import ClientPopulation, PopulationSpec

    keys = PopulationSpec.__dataclass_fields__.keys() - {"seed"}
    spec = PopulationSpec(**{k: data[k] for k in keys}, seed=seed)
    latency = LatencyModel(pd.speed, float(data["base_latency"]),
                           float(data["latency_sigma"]),
                           np.random.default_rng(seed + 1))
    return ClientPopulation(
        spec=spec, cx=jnp.asarray(pd.cx), cy=jnp.asarray(pd.cy), tx=pd.tx,
        ty=pd.ty, test_x=jnp.asarray(pd.test_x), test_y=jnp.asarray(pd.test_y),
        probe=jnp.asarray(pd.probe), num_classes=pd.num_classes,
        in_dim=pd.in_dim, availability=pd.availability, dropout=pd.dropout,
        byzantine=pd.byzantine, latency=latency)
