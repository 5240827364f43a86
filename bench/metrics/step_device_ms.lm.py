"""Fused round step of the LM cell: device time of ``jit__sync_step`` per
round, from the profiler trace (ms)."""
from bench.readers import module_ms_per_unit


def read(layer):
    return module_ms_per_unit(layer, "jit__sync_step")
