"""Fused flush step: device time of ``jit__async_step`` per flush, from
the profiler trace (ms)."""
from bench.readers import module_ms_per_unit


def read(layer):
    return module_ms_per_unit(layer, "jit__async_step")
