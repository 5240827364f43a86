"""Selective scan: its least HBM bytes a round (``bench/flops_lm.py``) over
its device time a round times the chip's HBM bandwidth (%)."""
from bench import flops, flops_lm
from bench.lm_scopes import scope_ms_per_unit


def read(layer):
    ms = scope_ms_per_unit(layer, "jit__sync_step", "mamba_scan")
    if not ms or not layer.get("tokens"):
        return None
    moved = flops_lm.scan_bytes(layer["model"],
                                layer["tokens"] / layer["units"],
                                layer["probe_tokens"])
    bw = flops.peaks(layer["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * moved / (ms / 1e3 * bw)
