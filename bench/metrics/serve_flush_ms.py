"""Serving engine: mean ``serve.flush`` span, one fused dispatch and its
readback (ms)."""
from bench.readers import mean_span_ms


def read(layer):
    return mean_span_ms(layer, "serve.flush")
