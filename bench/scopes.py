"""Read the program's own names out of a profiler trace: the flight
recorder's spans, which are host annotations named as the spans, and the
named scopes of the fused round step, which each device op's ``op_name``
metadata carries (``jit(_sync_step)/cohort_combine/paa/while:``).

A TPU trace keeps that path as the ``tf_op`` stat of each device op's event
metadata.  ``jax.profiler.ProfileData`` does not expose metadata stats, so
``trace_op_scopes`` reads them from the ``.xplane.pb`` file itself with a
small protobuf reader (``XSpace`` / ``XPlane`` / ``XEventMetadata`` /
``XStat``, field numbers of ``tsl/profiler/protobuf/xplane.proto``).  The
compiled program's text carries the same path (``op_scopes``).

A per-layer reader gets only the layer context its driver built, which
holds the trace's reduction but not its file; ``layer_trace`` finds the
file under ``.bench_traces/`` by the window's start, which the reduction
keeps.  Everything here returns ``None`` or nothing where the trace has no
such span or scope, as a program without them gives.
"""
from __future__ import annotations

import bisect
import glob
import os
import re

from bench import trace_reduce as tr
from bench.harness import ROOT

# the named scopes of RoundEngine's steps, innermost wins
STEP_SCOPES = ("gather", "local_train", "paa", "cluster_means",
               "fingerprint", "scatter_back")

TRACES_DIR = os.path.join(ROOT, ".bench_traces")


def innermost_scope(op_name: str) -> str | None:
    """The innermost of ``STEP_SCOPES`` on an ``op_name`` path.  The path's
    last part is the op's own primitive (``.../local_train/.../gather``;
    ``add:`` in a ``tf_op``), never a scope, even where a primitive shares
    a scope's name."""
    for part in reversed(op_name.split("/")[:-1]):
        if part in STEP_SCOPES:
            return part
    return None


_INSTR = re.compile(r"^\s*(?:ROOT\s+)?(%[\w.\-]+)\s*=.*?"
                    r'metadata=\{[^}]*?op_name="([^"]*)"')


def op_scopes(hlo_text: str) -> dict[str, str]:
    """Compiled HLO text -> {instruction name (``%while.143``): innermost
    scope} for every instruction whose ``op_name`` lies in a scope."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m:
            scope = innermost_scope(m.group(2))
            if scope is not None:
                out[m.group(1)] = scope
    return out


# ---------------------------------------------------------------------- #
# the trace's own op metadata
# ---------------------------------------------------------------------- #

def _varint(b: bytes, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        c = b[i]
        i += 1
        out |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return out, i


def _fields(b: bytes):
    """(field number, value) of one protobuf message: an int for a varint,
    a memoryview for a length-delimited field, fixed widths skipped."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            size, i = _varint(b, i)
            v, i = b[i:i + size], i + size
        elif wire in (1, 5):
            v, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield field, v


def _map_value(entry) -> memoryview | None:
    """The value of one ``map<int64, Message>`` entry."""
    for f, v in _fields(entry):
        if f == 2:
            return v
    return None


def trace_op_scopes(path: str) -> dict[str, str]:
    """{device op's event name: innermost scope} from the ``tf_op`` stats of
    the device planes' event metadata in an ``.xplane.pb`` file.  The event
    name is the op's HLO text, as ``ProfileData`` names its events."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out: dict[str, str] = {}
    for field, plane in _fields(space):
        if field != 1:                              # XSpace.planes
            continue
        name, events, stat_names = "", [], {}
        for f, v in _fields(plane):
            if f == 2:                              # XPlane.name
                name = bytes(v).decode()
            elif f == 4:                            # XPlane.event_metadata
                events.append(v)
            elif f == 5:                            # XPlane.stat_metadata
                meta = dict(_fields(_map_value(v)))
                stat_names[meta.get(1, 0)] = bytes(meta.get(2, b"")).decode()
        if not re.match(r"/device:[A-Z]+:\d+$", name):
            continue
        tf_op = [k for k, v in stat_names.items() if v == "tf_op"]
        if not tf_op:
            continue
        for entry in events:
            ev_name, op_name = None, None
            for f, v in _fields(_map_value(entry)):
                if f == 2:                          # XEventMetadata.name
                    ev_name = bytes(v).decode()
                elif f == 5:                        # XEventMetadata.stats
                    stat = dict(_fields(v))
                    if stat.get(1) != tf_op[0]:
                        continue
                    if 5 in stat:                   # XStat.str_value
                        op_name = bytes(stat[5]).decode()
                    elif 7 in stat:                 # XStat.ref_value
                        op_name = stat_names.get(stat[7])
            scope = innermost_scope(op_name) if op_name else None
            if ev_name and scope:
                out[ev_name] = scope
    return out


# ---------------------------------------------------------------------- #
# spans and scope time on the trace's clock
# ---------------------------------------------------------------------- #

def host_spans(planes, window: tuple[float, float]
               ) -> list[tuple[str, float, float]]:
    """(name, start_ns, end_ns) of every host annotation named as a
    flight-recorder span, clipped to the window; a span that straddles an
    edge keeps its part inside."""
    from repro.obs.names import SPAN_NAMES
    lo, hi = window
    out = []
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name not in SPAN_NAMES:
                    continue
                s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                if e > lo and s < hi:
                    out.append((ev.name, max(s, lo), min(e, hi)))
    return sorted(out, key=lambda sp: sp[1])


def _scoped_ops(events, scopes: dict[str, str]) -> list[list]:
    """[start, end, scope] of each op event, sorted by start (an enclosing op
    first).  ``scopes`` maps an op's event name, or its instruction name
    (``%while.143``), to its scope.  A control-flow op (``while``,
    ``conditional``) missing from it takes the one scope of the ops nested
    in it: it runs only its own computations, traced inside its scope, and a
    TPU trace gives such ops no ``tf_op`` of their own."""
    ops = sorted(((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                  for ev in events), key=lambda o: (o[0], -o[1]))
    out = [[s, e, scopes.get(n) or scopes.get(n.split(" = ", 1)[0])]
           for s, e, n in ops]
    starts = [o[0] for o in out]
    for i, (s, e, n) in enumerate(ops):
        if out[i][2] is None and re.search(r" (while|conditional)\(", n):
            j = bisect.bisect_right(starts, e)
            inner = {o[2] for o in out[i + 1:j] if o[1] <= e and o[2]}
            if len(inner) == 1:
                out[i][2] = inner.pop()
    return out


def scope_time(planes, window: tuple[float, float], scopes: dict[str, str],
               module: str, scope: str) -> tuple[float, int]:
    """(seconds, runs): the device time of ``scope`` inside the runs of the
    program ``module`` (``jit__sync_step``) in the window, as the union of
    its ops' intervals, so that an op nested in another (a loop's body in
    its ``while``) counts once; and how many runs of the program the window
    holds.  ``scopes`` as for ``_scoped_ops``.  Mean over devices."""
    lo, hi = window
    dev = [p for p in planes if re.match(r"/device:[A-Z]+:\d+$", p.name)]
    total, runs = 0.0, 0
    for plane in dev:
        mods, ops = [], []
        for line in plane.lines:
            if line.name == "XLA Modules":
                mods = sorted((ev.start_ns, ev.start_ns + ev.duration_ns)
                              for ev in line.events
                              if tr._base(ev.name) == module)
            elif line.name == "XLA Ops":
                ops = _scoped_ops(line.events, scopes)
        mods = tr._clip(mods, lo, hi)
        runs = max(runs, len(mods))
        mstarts = [m[0] for m in mods]
        inside = []
        for s, e, sc in ops:
            k = bisect.bisect_right(mstarts, s) - 1
            if sc == scope and k >= 0 and s < mods[k][1]:
                inside.append((s, min(e, mods[k][1])))
        total += sum(e - s for s, e in tr._union(inside)) / 1e9
    return total / max(len(dev), 1), runs


def _window(planes) -> tuple[float, float] | None:
    for plane in planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == tr.WINDOW_ANNOTATION:
                        return ev.start_ns, ev.start_ns + ev.duration_ns
    return None


def layer_trace(layer: dict):
    """(planes, window, path) of the profiler trace the layer context was
    reduced from, found under ``.bench_traces/`` by its window's start; or
    ``None``."""
    red = layer.get("trace")
    if red is None:
        return None
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(TRACES_DIR, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime,
                   reverse=True)
    for path in paths:
        planes = list(ProfileData.from_file(path).planes)
        window = _window(planes)
        if window is not None and window[0] == red.window_start_ns:
            return planes, window, path
    return None


def span_ms_per_unit(layer: dict, name: str) -> float | None:
    """The trace's ``name`` spans in the window, clipped to it, summed and
    divided by the window's rounds or flushes (ms)."""
    found = layer_trace(layer)
    if found is None or not layer.get("units"):
        return None
    planes, window, _ = found
    d = [e - s for n, s, e in host_spans(planes, window) if n == name]
    if not d:
        return None
    return sum(d) / 1e6 / layer["units"]


def scope_ms_per_unit(layer: dict, module: str, scope: str) -> float | None:
    """Device time of ``scope`` in ``module`` per round or flush (ms)."""
    found = layer_trace(layer)
    if found is None or not layer.get("units"):
        return None
    planes, window, path = found
    seconds, runs = scope_time(planes, window, trace_op_scopes(path),
                               module, scope)
    if runs == 0 or seconds <= 0:
        return None
    return seconds * 1e3 / layer["units"]
