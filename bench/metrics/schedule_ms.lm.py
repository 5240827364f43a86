"""Host round loop of the LM cell: the ``round.schedule`` spans of the
profiler trace, clipped to the window, per round (ms)."""
from bench.scopes import span_ms_per_unit


def read(layer):
    return span_ms_per_unit(layer, "round.schedule")
