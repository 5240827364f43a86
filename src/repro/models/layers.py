"""Shared layer primitives: norms, activations, RoPE, projections.

Weight layouts are chosen for mesh sharding (see launch/sharding.py):
matmul weights are (in_features, out_features); fused-head projections keep
heads flattened into the feature dim so GQA head counts that do not divide the
model axis still shard cleanly on the fused dim.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp


def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)


def layer_norm(x: jax.Array, scale: jax.Array, bias: jax.Array,
               eps: float = 1e-5) -> jax.Array:
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(x.dtype)


def norm(kind: str, x: jax.Array, p: dict) -> jax.Array:
    if kind == "rmsnorm":
        return rms_norm(x, p["scale"])
    return layer_norm(x, p["scale"], p["bias"])


def init_norm(kind: str, dim: int, dtype) -> dict:
    if kind == "rmsnorm":
        return {"scale": jnp.zeros((dim,), dtype)}
    return {"scale": jnp.ones((dim,), dtype), "bias": jnp.zeros((dim,), dtype)}


class TiedHead(NamedTuple):
    """A language model's output before its tied head: final hidden states
    ``(..., S, D)`` and the embedding table ``(V, D)`` they are scored
    against.  A loss that takes this instead of ``(..., S, V)`` logits can
    score the tokens a block at a time (``repro.core.baselines._xent``)."""
    hidden: jax.Array
    table: jax.Array


def dense(x: jax.Array, w) -> jax.Array:
    """``x @ w`` for a plain weight or a LoRA-adapted one
    (:class:`repro.models.lora.LoraWeight`)."""
    from repro.models.lora import LoraWeight, lora_dense
    if isinstance(w, LoraWeight):
        return lora_dense(x, w)
    return x @ w


def dense_init(key: jax.Array, in_dim: int, out_dim: int, dtype) -> jax.Array:
    scale = (1.0 / in_dim) ** 0.5
    return (jax.random.normal(key, (in_dim, out_dim), jnp.float32) * scale).astype(dtype)


# --------------------------------------------------------------------------- #
# Activations / gated FFN
# --------------------------------------------------------------------------- #

def activation(kind: str, gate: jax.Array, up: jax.Array | None) -> jax.Array:
    """Gated activations take (gate, up); plain ones ignore ``up``."""
    if kind == "swiglu":
        return jax.nn.silu(gate) * up
    if kind == "geglu":
        return jax.nn.gelu(gate, approximate=True) * up
    if kind == "gelu":
        return jax.nn.gelu(gate, approximate=True)
    if kind == "relu2":
        r = jax.nn.relu(gate)
        return r * r
    raise ValueError(f"unknown activation {kind}")


def is_gated(kind: str) -> bool:
    return kind in ("swiglu", "geglu")


def ffn_apply(act: str, p: dict, x: jax.Array) -> jax.Array:
    """Dense FFN. Params: w_gate (D,F) [+ w_up (D,F) if gated], w_down (F,D)."""
    gate = x @ p["w_gate"]
    up = x @ p["w_up"] if is_gated(act) else None
    h = activation(act, gate, up)
    return h @ p["w_down"]


def ffn_init(key: jax.Array, act: str, d_model: int, d_ff: int, dtype) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    p = {"w_gate": dense_init(k1, d_model, d_ff, dtype),
         "w_down": dense_init(k3, d_ff, d_model, dtype)}
    if is_gated(act):
        p["w_up"] = dense_init(k2, d_model, d_ff, dtype)
    return p


# --------------------------------------------------------------------------- #
# Rotary position embeddings
# --------------------------------------------------------------------------- #

def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, hd); positions: broadcastable to (..., S)."""
    hd = x.shape[-1]
    freqs = rope_frequencies(hd, theta)                       # (hd/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs    # (..., S, hd/2)
    cos = jnp.cos(ang)[..., None, :]                          # (..., S, 1, hd/2)
    sin = jnp.sin(ang)[..., None, :]
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., : hd // 2], x32[..., hd // 2:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(n_pos: int, dim: int) -> jax.Array:
    """Whisper-style sinusoidal embeddings (n_pos, dim)."""
    return sinusoidal_at(jnp.arange(n_pos, dtype=jnp.float32), dim)


def sinusoidal_at(positions: jax.Array, dim: int) -> jax.Array:
    """Sinusoidal embedding rows for arbitrary (possibly traced) positions."""
    pos = positions.astype(jnp.float32)[..., None]
    inv = jnp.exp(-jnp.log(10000.0) * jnp.arange(dim // 2, dtype=jnp.float32)
                  / max(dim // 2 - 1, 1))
    ang = pos * inv
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)
