"""Host event loop: (window - flush.step - flush.chain spans) per
flush (ms)."""
from bench.readers import window_minus_ms


def read(layer):
    return window_minus_ms(layer, ("flush.step", "flush.chain"))
