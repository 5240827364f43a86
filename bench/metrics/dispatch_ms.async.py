"""Host event loop: the ``async.dispatch`` spans (FedBuff's availability
redraw, sampling and event pushes) of the profiler trace, clipped to the
window, per flush (ms)."""
from bench.scopes import span_ms_per_unit


def read(layer):
    return span_ms_per_unit(layer, "async.dispatch")
