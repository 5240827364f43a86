"""Serving frontend: mean queue wait of a served request, from its arrival
to the start of the flush that serves it: the ``serve.flush`` spans'
``wait_sum_us`` over their ``n`` (ms)."""


def read(layer):
    flushes = [s.get("attrs", {}) for s in layer["spans"]
               if s["name"] == "serve.flush"]
    waits = [a for a in flushes if "wait_sum_us" in a]
    served = sum(a.get("n", 0) for a in waits)
    if not served:
        return None
    return sum(a["wait_sum_us"] for a in waits) / served / 1e3
