"""Operations and bytes of the counted pieces of work, from their shapes.

Only the multiply-adds of the matrix products count as model operations
(2 per multiply-add); bias adds and activations are left out, so a share of
the peak computed from these never counts more than the work needs.
"""
from __future__ import annotations

import json
import os

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; a device not in the table is
    an error, not a default."""
    with open(PEAKS_FILE) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE}; add its published numbers")
    return table[device_kind]


def mlp_matmul_params(model: dict) -> int:
    """Weights that take part in a product: one multiply-add per sample."""
    dims = [model["in_dim"], *model["hidden"], model["rep_dim"],
            model["num_classes"]]
    return sum(a * b for a, b in zip(dims[:-1], dims[1:]))


def forward_flops(model: dict, samples: int) -> int:
    return 2 * mlp_matmul_params(model) * samples


def train_flops(model: dict, samples: int) -> int:
    """Forward and backward of one local-training pass over ``samples``:
    the backward pass takes twice the forward's products."""
    return 3 * forward_flops(model, samples)


def serve_flops(model: dict, n_models: int, bucket: int) -> int:
    """One fused serving dispatch: every one of the ``n_models`` runs over
    the whole padded bucket (each request then keeps its model's row)."""
    return n_models * forward_flops(model, bucket)


def fingerprint_bytes(rows: int, n_params: int, block_m: int = 8,
                      block_n: int = 2048) -> int:
    """Least bytes the fingerprint kernel moves for ``rows`` rows of
    ``n_params`` uint32 words: the padded input once, the two weight rows
    once, and the (rows, 256) lane accumulators written once."""
    mp = -(-rows // block_m) * block_m
    bn = min(block_n, -(-n_params // 128) * 128)
    np_ = -(-n_params // bn) * bn
    return 4 * (mp * np_ + 2 * np_ + mp * 256)
