"""LoRA adapters over a frozen language-model base (Hu et al. 2021).

A federated client of a large model trains only low-rank adapters: each
targeted projection ``W`` (``(in, out)``, frozen) gains ``A (in, r)`` and
``B (r, out)`` and computes ``x W + (alpha / r) (x A) B``.  ``A`` is drawn
from the seed, ``B`` starts at zero, so the adapted model starts as the base.
The targets are the mixers' projections: Mamba's ``in_proj`` and
``out_proj``, attention's ``q``, ``k``, ``v`` and ``o``.

The adapters are a pytree that mirrors the base's layer lists (leaves under
``layers`` keep the base's leading period axis), so one client's adapters
flatten to one arena row and :func:`merge` lays them over the base for the
forward.  The base and the adapters keep their own dtypes: a bfloat16 base
times float32 activations is a product at the configured matmul precision
with float32 accumulation; the adapters are float32.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp

from repro.models.layers import TiedHead

Pytree = Any

#: mixer -> the projections that carry adapters
TARGETS = {"mamba": ("in_proj", "out_proj"), "attn": ("q", "k", "v", "o")}
#: adapter rank and scale numerator (the update is scaled by ALPHA / RANK)
RANK = 16
ALPHA = 32.0


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=["w", "a", "b"], meta_fields=["scale"])
@dataclass(frozen=True)
class LoraWeight:
    """A frozen projection with its adapter pair."""
    w: jax.Array          # (in, out) frozen base weight
    a: jax.Array          # (in, r)
    b: jax.Array          # (r, out)
    scale: float          # alpha / r


def lora_dense(x: jax.Array, lw: LoraWeight) -> jax.Array:
    y = x @ lw.w
    with jax.named_scope("lora"):
        return y + ((x @ lw.a) @ lw.b) * lw.scale


def target_shapes(cfg) -> dict[str, dict[str, tuple[int, int]]]:
    """mixer -> {projection: (in, out)} of an ``ArchConfig``."""
    d, di = cfg.d_model, cfg.mamba_d_inner
    q, kv = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
    return {"mamba": {"in_proj": (d, 2 * di), "out_proj": (di, d)},
            "attn": {"q": (d, q), "k": (d, kv), "v": (d, kv), "o": (q, d)}}


def _check_arch(cfg) -> None:
    bad = sorted({s.mixer for s in cfg.pattern} - set(TARGETS))
    if bad:
        raise ValueError(f"LoRA targets cover the mixers {sorted(TARGETS)}; "
                         f"{cfg.name} has {bad}")


def init_adapters(cfg, key: jax.Array) -> Pytree:
    """Adapters of every targeted projection, rank :data:`RANK`:
    ``A ~ N(0, 1/in)``, ``B = 0``, float32; ``layers`` leaves carry the
    leading period axis."""
    _check_arch(cfg)
    shapes = target_shapes(cfg)

    def layer(spec, k, lead):
        out = {}
        names = TARGETS[spec.mixer]
        for name, kk in zip(names, jax.random.split(k, len(names))):
            fan_in, fan_out = shapes[spec.mixer][name]
            out[name] = {
                "a": jax.random.normal(kk, lead + (fan_in, RANK), jnp.float32)
                * (1.0 / fan_in) ** 0.5,
                "b": jnp.zeros(lead + (RANK, fan_out), jnp.float32)}
        return out

    k_per, k_rem = jax.random.split(key)
    pk = jax.random.split(k_per, len(cfg.pattern))
    rk = jax.random.split(k_rem, max(len(cfg.remainder), 1))
    out: dict = {"rem_layers": [layer(s, rk[i], ())
                                for i, s in enumerate(cfg.remainder)]}
    if cfg.n_periods > 0:
        out["layers"] = [layer(s, pk[i], (cfg.n_periods,))
                         for i, s in enumerate(cfg.pattern)]
    return out


def merge(base: Pytree, adapters: Pytree, scale: float) -> Pytree:
    """The base with every targeted projection replaced by a
    :class:`LoraWeight` over it (no arithmetic: the forward applies them)."""

    def layer(bp: dict, ad: dict) -> dict:
        bp = dict(bp)
        holder = bp
        if "mamba" in bp:
            holder = bp["mamba"] = dict(bp["mamba"])
        for name, ab in ad.items():
            holder[name] = LoraWeight(holder[name], ab["a"], ab["b"], scale)
        return bp

    out = dict(base)
    for group in ("layers", "rem_layers"):
        if group in adapters:
            out[group] = [layer(b, a) for b, a in zip(base[group],
                                                      adapters[group])]
    return out


# --------------------------------------------------------------------------- #
# the adapted language model, as the federated layer sees it
# --------------------------------------------------------------------------- #

def apply(cfg, scale: float, base: Pytree, adapters: Pytree,
          tokens: jax.Array) -> TiedHead:
    """tokens ``(..., S)`` -> final-norm hidden states and the tied head."""
    from repro.models.transformer import hidden_states
    lead = tokens.shape[:-1]
    h = hidden_states(cfg, merge(base, adapters, scale),
                      tokens.reshape((-1, tokens.shape[-1])))
    return TiedHead(h.reshape(lead + h.shape[1:]), base["embed"])


def embed(cfg, scale: float, base: Pytree, adapters: Pytree,
          tokens: jax.Array) -> jax.Array:
    """tokens ``(n, S)`` -> ``(n, D)``: the final-norm hidden state averaged
    over positions (PAA's representation of a sequence)."""
    return jnp.mean(apply(cfg, scale, base, adapters, tokens).hidden, axis=-2)
