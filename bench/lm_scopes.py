"""Device time of a named scope of the model inside the round step.

``bench.scopes`` reads the step's own scopes (``paa``, ``gather``, ...) as
the innermost of a fixed list on an op's path.  A model's scope
(``mamba_scan``) sits inside those (``local_train``, ``paa``) and inside
the transformations' parts of the path (``transpose(jvp(...))``,
``rematted_computation``), and a transformation may wrap the scope's own
name (``jvp(mamba_scan)``); so this reads the trace's ``tf_op`` stats for
any part of the path that names the scope, bare or so wrapped.  It returns
``None`` where the trace has no such op, as a program without the scope
gives.
"""
from __future__ import annotations

import re

from bench import scopes as sc


def _named(op_name: str, scope: str) -> bool:
    pat = re.compile(r"(^|\()" + re.escape(scope) + r"(\)|$)")
    return any(pat.search(p) for p in op_name.split("/")[:-1])


def trace_scope_ops(path: str, scope: str) -> dict[str, str]:
    """{device op's event name: ``scope``} for every op of the trace whose
    ``tf_op`` path names ``scope``."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out: dict[str, str] = {}
    for field, plane in sc._fields(space):
        if field != 1:
            continue
        name, events, stat_names = "", [], {}
        for f, v in sc._fields(plane):
            if f == 2:
                name = bytes(v).decode()
            elif f == 4:
                events.append(v)
            elif f == 5:
                meta = dict(sc._fields(sc._map_value(v)))
                stat_names[meta.get(1, 0)] = bytes(meta.get(2, b"")).decode()
        if not re.match(r"/device:[A-Z]+:\d+$", name):
            continue
        tf_op = [k for k, v in stat_names.items() if v == "tf_op"]
        if not tf_op:
            continue
        for entry in events:
            ev_name, op_name = None, None
            for f, v in sc._fields(sc._map_value(entry)):
                if f == 2:
                    ev_name = bytes(v).decode()
                elif f == 5:
                    stat = dict(sc._fields(v))
                    if stat.get(1) != tf_op[0]:
                        continue
                    if 5 in stat:
                        op_name = bytes(stat[5]).decode()
                    elif 7 in stat:
                        op_name = stat_names.get(stat[7])
            if ev_name and op_name and _named(op_name, scope):
                out[ev_name] = scope
    return out


def scope_ms_per_unit(layer: dict, module: str, scope: str) -> float | None:
    """Device time of ``scope`` inside ``module`` per round (ms), the union
    of its ops' intervals; kept in ``layer``, so that the readers that share
    it parse the trace once."""
    key = f"scope_ms:{module}:{scope}"
    if key not in layer:
        layer[key] = _scope_ms_per_unit(layer, module, scope)
    return layer[key]


def _scope_ms_per_unit(layer: dict, module: str, scope: str) -> float | None:
    found = sc.layer_trace(layer)
    if found is None or not layer.get("units"):
        return None
    planes, window, path = found
    ops = trace_scope_ops(path, scope)
    if not ops:
        return None
    s, _ = sc.scope_time(planes, window, ops, module, scope)
    return s * 1e3 / layer["units"] if s > 0 else None
