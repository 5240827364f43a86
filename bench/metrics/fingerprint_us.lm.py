"""Fingerprint kernel (Mosaic) in the LM cell, over the 4,509,696-value
adapter rows: device time per call, from the profiler trace (us)."""
from bench.readers import fingerprint_us


def read(layer):
    return fingerprint_us(layer)
