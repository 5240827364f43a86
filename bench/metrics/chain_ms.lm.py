"""Chain settlement in the LM cell: mean ``round.chain`` span (ms)."""
from bench.readers import mean_span_ms


def read(layer):
    return mean_span_ms(layer, "round.chain")
