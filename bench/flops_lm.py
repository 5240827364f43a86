"""Operations and bytes of a LoRA-adapted hybrid LM's round step, from its
shapes (the reference's ``model`` description, ``bench/reference_lm.py``).

Model FLOPs count the multiply-adds of the matrix products (2 each): the
base's projections and tied head, attention's two score products over the
causal half, and the adapters'.  A training token runs the forward and the
products that carry the activations' gradient back (the base is frozen, so
no weight gradient of it); the adapters add their weight gradients.
Recompute is not counted.  The scan's elementwise work is no matrix
product and is left out.
"""
from __future__ import annotations

from bench import reference_lm as rlm


def _layers(model: dict):
    plen = model["attn_layer_period"]
    return [rlm.is_attn(model, l % plen) for l in range(model["n_layers"])]


def base_matmul_params(model: dict, head: bool = True) -> int:
    """Base weights that take part in a product: one multiply-add a token."""
    k = rlm.dims(model)
    d, di, f = k["d"], k["di"], model["d_ff"]
    mamba = d * 2 * di + di * (k["r"] + 2 * k["n"]) + k["r"] * di + di * d
    attn = d * k["hq"] + 2 * d * k["hkv"] + k["hq"] * d
    total = sum((attn if a else mamba) + 3 * d * f for a in _layers(model))
    return total + (model["vocab_size"] * d if head else 0)


def lora_params(model: dict) -> int:
    r = model["lora_rank"]
    plen = model["attn_layer_period"]
    return sum(r * (fi + fo)
               for l in range(model["n_layers"])
               for fi, fo in rlm.targets(model, l % plen).values())


def attention_flops(model: dict, tokens: int, seq_len: int) -> float:
    """Forward score and value products of the attention layers over the
    causal half, for ``tokens`` tokens in rows of ``seq_len``."""
    per_row = 2 * 2 * seq_len * (seq_len + 1) / 2 * model["n_heads"] \
        * model["head_dim"]
    return sum(_layers(model)) * per_row * tokens / seq_len


def forward_flops(model: dict, tokens: int, seq_len: int,
                  head: bool = True) -> float:
    return (2 * tokens * (base_matmul_params(model, head) + lora_params(model))
            + attention_flops(model, tokens, seq_len))


def round_flops(model: dict, train_tokens: float, seq_len: int,
                probe_tokens: float, probe_len: int) -> float:
    """One sync round: local training of ``train_tokens`` (forward,
    activation gradients, adapter weight gradients) and PAA's probe forward
    without the head."""
    base = 2 * train_tokens * base_matmul_params(model) \
        + attention_flops(model, train_tokens, seq_len)
    train = 2 * base + 3 * 2 * train_tokens * lora_params(model)
    return train + forward_flops(model, probe_tokens, probe_len, head=False)


def scan_bytes(model: dict, train_tokens: float, probe_tokens: float) -> float:
    """Least HBM bytes of the selective scans of a round: per Mamba layer
    and pass, its float32 inputs and outputs once.  The forward reads
    u, delta (d_inner each) and B, C (d_state each) and writes y; the
    backward, with its recompute, moves the forward's bytes again plus dy
    in and du, d_delta, dB, dC out.  The ``(T, d_inner, d_state)``
    intermediates are never counted, so the count is the same whatever
    implements the scan.  Training tokens run the forward and the
    backward, probe tokens the forward."""
    k = rlm.dims(model)
    fwd = 4 * (3 * k["di"] + 2 * k["n"])
    bwd = fwd + 4 * (3 * k["di"] + 2 * k["n"])
    mamba = len(_layers(model)) - sum(_layers(model))
    return mamba * (train_tokens * (fwd + bwd) + probe_tokens * fwd)
