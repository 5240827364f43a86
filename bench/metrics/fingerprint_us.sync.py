"""Fingerprint kernel (Mosaic): device time per call, from the profiler
trace (us).  Its input rows are placed in VMEM by the step, so the HBM
bandwidth does not bound it, and the chip publishes no peak for the integer
vector work that does: no roofline share is formed."""
from bench.readers import fingerprint_us


def read(layer):
    return fingerprint_us(layer)
