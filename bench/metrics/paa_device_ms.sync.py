"""Fused round step: device time per round under the ``paa`` named scope
inside ``jit__sync_step`` (BFLN's prototypes, Pearson affinity, spectral
embedding and k-means), the union of its ops' intervals in the profiler
trace (ms)."""
from bench.scopes import scope_ms_per_unit


def read(layer):
    return scope_ms_per_unit(layer, "jit__sync_step", "paa")
