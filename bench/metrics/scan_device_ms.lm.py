"""Selective scan: device time per round of the ops under the named scope
``mamba_scan`` inside ``jit__sync_step`` (training's forward, recompute and
backward, and PAA's probe forward), the union of their intervals in the
profiler trace (ms)."""
from bench.lm_scopes import scope_ms_per_unit


def read(layer):
    return scope_ms_per_unit(layer, "jit__sync_step", "mamba_scan")
