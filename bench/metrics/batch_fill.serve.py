"""Serving frontend: requests served over the padded bucket slots
dispatched (%)."""


def read(layer):
    flushes = [s for s in layer["spans"] if s["name"] == "serve.flush"]
    slots = sum(s.get("attrs", {}).get("bucket", 0) for s in flushes)
    if not slots:
        return None
    served = sum(s.get("attrs", {}).get("n", 0) for s in flushes)
    return 100.0 * served / slots
