"""Local training of the arrived clients, as a share of the bf16 peak
over the traced round time (%)."""
from bench.readers import train_mfu


def read(layer):
    if not layer.get("units"):
        return None
    return train_mfu(layer, layer["arrived"] / layer["units"])
