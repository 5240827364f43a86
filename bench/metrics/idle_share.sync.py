"""Device idle share of the traced window, 1 - busy / window (%)."""
from bench.readers import idle_share


def read(layer):
    return idle_share(layer)
