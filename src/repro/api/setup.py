"""Shared experiment wiring: dataset packing + model-bundle construction.

The three FL examples (`quickstart`, `train_federated`,
`simulate_population`) and the benchmark harness used to each carry their
own copy of the same setup dance — partition a dataset, pack rectangular
client shards, sample a probe batch, build the MLP ``ModelBundle``.  These
helpers are that dance, once.  :func:`build_model` is the one place the
simulator gets its model from, whatever ``TrainSpec.model`` names.
"""
from __future__ import annotations

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.baselines import ModelBundle
from repro.data import dirichlet_partition, make_classification_dataset, pack_clients
from repro.data.partition import sample_probe_batch
from repro.models import classifier as clf
from repro.runtime.arena import ArenaLayout


class PackedClients(NamedTuple):
    """A partitioned classification dataset, stacked for the vmapped trainer."""
    cx: jnp.ndarray          # (n, n_batches, B, D) train
    cy: jnp.ndarray          # (n, n_batches, B)
    tx: np.ndarray           # (n, n_test, D) per-client local test
    ty: np.ndarray           # (n, n_test)
    test_x: jnp.ndarray      # shared global test split
    test_y: jnp.ndarray
    probe: jnp.ndarray       # (psi, D) PAA probe batch
    num_classes: int
    in_dim: int


def load_packed_clients(dataset: str, n_clients: int, bias: float, *,
                        n_batches: int = 4, batch_size: int = 64,
                        psi: int = 32, probe_category: int = 0,
                        seed: int = 0) -> PackedClients:
    """Dirichlet-partition ``dataset`` into ``n_clients`` rectangular shards
    plus the shared test split and the PAA probe batch."""
    (xt, yt), (xe, ye) = make_classification_dataset(dataset, seed=seed)
    parts = dirichlet_partition(yt, n_clients, bias, seed=seed)
    cx, cy, tx, ty = pack_clients(xt, yt, parts, n_batches=n_batches,
                                  batch_size=batch_size, seed=seed)
    probe = sample_probe_batch(xt, yt, category=probe_category, psi=psi,
                               seed=seed)
    return PackedClients(
        cx=jnp.asarray(cx), cy=jnp.asarray(cy), tx=tx, ty=ty,
        test_x=jnp.asarray(xe), test_y=jnp.asarray(ye),
        probe=jnp.asarray(probe),
        num_classes=int(yt.max()) + 1, in_dim=int(xt.shape[1]))


def make_mlp_bundle(in_dim: int, num_classes: int, *,
                    hidden: tuple[int, ...] = (128,), rep_dim: int = 64,
                    ) -> tuple[clf.MLPConfig, ModelBundle]:
    """The FL classifier as (architecture config, architecture-agnostic
    bundle) — the pair every entry point needs."""
    cfg = clf.MLPConfig(in_dim=in_dim, hidden=tuple(hidden), rep_dim=rep_dim,
                        num_classes=num_classes)
    bundle = ModelBundle(functools.partial(clf.apply, cfg),
                         functools.partial(clf.embed, cfg), num_classes)
    return cfg, bundle


class ModelSetup(NamedTuple):
    """What a federation needs of its model: the bundle (with the frozen
    base, if any), the arena row layout, the population's initial weights
    (``params`` stacked per client, or for an adapted LM one ``row`` every
    client starts from) and the architecture config."""
    kind: str                            # "mlp" or the architecture's name
    bundle: ModelBundle
    layout: ArenaLayout
    params: Any = None                   # (n, ...) stacked pytree (MLP)
    row: jax.Array | None = None         # (N,) initial adapter row (LM)
    mcfg: clf.MLPConfig | None = None
    arch: Any = None                     # repro.models.transformer.ArchConfig
    stacked_apply_fn: Any = None


def build_model(train, population, seed: int, weights=None) -> ModelSetup:
    """The model ``train.model`` names (``repro.api.TrainSpec``) over
    ``population``.  ``weights``, for an adapted LM, is ``(base, row)``:
    the frozen base pytree and the adapter row every client starts from,
    made elsewhere (a benchmark's own); by default both come from ``seed``.
    """
    if train.model == "mlp":
        mcfg, bundle = make_mlp_bundle(
            population.in_dim, population.num_classes,
            hidden=tuple(train.hidden), rep_dim=train.rep_dim)
        params = clf.init_stacked(mcfg, jax.random.PRNGKey(seed),
                                  population.n_clients)
        return ModelSetup("mlp", bundle, ArenaLayout.from_stacked(params),
                          params=params, mcfg=mcfg,
                          stacked_apply_fn=functools.partial(
                              clf.apply_stacked, mcfg))
    from repro.configs import FEDERATED_ARCHS
    from repro.models import lora
    from repro.models.transformer import init_params
    cfg = FEDERATED_ARCHS[train.model](**dict(train.model_overrides))
    if population.num_classes != cfg.vocab_size:
        raise ValueError(
            f"{train.model} reads a {cfg.vocab_size}-token vocabulary; the "
            f"population's data has {population.num_classes}")
    shapes = jax.eval_shape(lambda: lora.init_adapters(
        cfg, jax.random.PRNGKey(0)))
    layout = ArenaLayout.from_stacked(jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((1,) + s.shape, s.dtype), shapes))
    if weights is None:
        k_base, k_ad = jax.random.split(jax.random.PRNGKey(seed))
        base = jax.jit(functools.partial(init_params, cfg))(k_base)
        row = jax.jit(lambda k: layout.flatten(jax.tree.map(
            lambda x: x[None], lora.init_adapters(cfg, k)))[0])(k_ad)
    else:
        base, row = weights
    scale = lora.ALPHA / lora.RANK

    def bind(b):
        return ModelBundle(functools.partial(lora.apply, cfg, scale, b),
                           functools.partial(lora.embed, cfg, scale, b),
                           cfg.vocab_size, base=b, bind=bind,
                           eval_metric="loss")

    return ModelSetup(train.model, bind(base), layout, row=jnp.asarray(row),
                      arch=cfg)
