"""Model FLOPs of a round (``bench/flops_lm.py``: the base's forward and
activation-gradient products, attention, LoRA, PAA's probe forward; the
trained tokens read from the ``engine.tokens`` counter) over the traced
round time times the chip's bf16 peak (%)."""
from bench import flops, flops_lm


def read(layer):
    if not layer.get("units") or not layer.get("tokens") \
            or not layer["trace"].n_devices:
        return None
    work = flops_lm.round_flops(layer["model"],
                                layer["tokens"] / layer["units"],
                                layer["seq_len"], layer["probe_tokens"],
                                layer["probe_len"])
    unit_s = layer["window_s"] / layer["units"]
    peak = flops.peaks(layer["device_kind"])["bf16_flops_per_s"]
    return 100.0 * work / (unit_s * peak)
