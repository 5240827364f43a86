"""Local training of the flushed clients, as a share of the bf16 peak
over the traced flush time (%)."""
from bench.readers import train_mfu


def read(layer):
    if not layer.get("units"):
        return None
    return train_mfu(layer, layer["arrived"] / layer["units"])
