"""Arithmetic the per-layer metric readers share.  Each reader in
``bench/metrics/`` takes the layer context a driver built from its traced
window and returns one number, or ``None`` where it finds nothing to read.
"""
from __future__ import annotations

from bench import flops


def span_ms(layer: dict, name: str) -> list[float]:
    return [s["dur_us"] / 1e3 for s in layer["spans"] if s["name"] == name]


def mean_span_ms(layer: dict, name: str) -> float | None:
    d = span_ms(layer, name)
    return sum(d) / len(d) if d else None


def per_unit_ms(layer: dict, total_ms: float) -> float | None:
    return total_ms / layer["units"] if layer.get("units") else None


def loop_self_ms(layer: dict, outer: str, children: tuple[str, ...]
                 ) -> float | None:
    """Self time of the ``outer`` spans not covered by ``children``, per
    unit of the window."""
    if not span_ms(layer, outer):
        return None
    total = sum(span_ms(layer, outer))
    covered = sum(sum(span_ms(layer, c)) for c in children)
    return per_unit_ms(layer, total - covered)


def window_minus_ms(layer: dict, children: tuple[str, ...]) -> float | None:
    """(window - the ``children`` spans) per unit."""
    if not layer.get("units"):
        return None
    covered = sum(sum(span_ms(layer, c)) for c in children)
    return per_unit_ms(layer, layer["window_s"] * 1e3 - covered)


def module_ms_per_unit(layer: dict, module: str) -> float | None:
    s, n = layer["trace"].module_time(module)
    if n == 0:
        return None
    return per_unit_ms(layer, s * 1e3)


def idle_share(layer: dict) -> float | None:
    red = layer.get("trace")
    if red is None or red.window_s <= 0 or red.busy_s <= 0:
        return None
    return 100.0 * (1.0 - red.busy_s / red.window_s)


def train_mfu(layer: dict, clients: int) -> float | None:
    """Local-training FLOPs of ``clients`` clients per unit over (the traced
    window's time per unit x the chip's bf16 peak), in %."""
    if not layer.get("units") or not layer["trace"].n_devices:
        return None
    work = flops.train_flops(layer["model"],
                             layer["samples_per_client"] * clients)
    unit_s = layer["window_s"] / layer["units"]
    peak = flops.peaks(layer["device_kind"])["bf16_flops_per_s"]
    return 100.0 * work / (unit_s * peak)


# the fingerprint kernel's operation in the trace: the Mosaic call whose
# output is the (rows, 256) uint32 lane accumulator
FINGERPRINT_OP = r"= u32\[\d+,256\].* custom-call\(.*tpu_custom_call"


def fingerprint_us(layer: dict) -> float | None:
    """The fingerprint kernel's device time per call (us)."""
    s, n = layer["trace"].ops_matching(FINGERPRINT_OP)
    if n == 0 or s <= 0:
        return None
    return s / n * 1e6
