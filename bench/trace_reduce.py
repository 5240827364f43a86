"""Reduce a profiler trace (``.xplane.pb``) to the numbers the benchmark
reports: device busy time in the window, device time per compiled program
and per operation, and the idle gaps put down to what the host was doing.

The window is the host event of the ``bench.window`` annotation that the
run places around its measured loop.  Device planes are those named
``/device:<platform>:<n>``; their ``XLA Ops`` line holds one event per
executed operation and their ``XLA Modules`` line one per program run.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

WINDOW_ANNOTATION = "bench.window"


@dataclass
class Reduction:
    window_s: float                         # length of the traced window
    window_start_ns: float                  # its start on the profiler clock
    busy_s: float                           # device busy, mean over chips
    n_devices: int
    module_s: dict[str, float] = field(default_factory=dict)
    module_calls: dict[str, int] = field(default_factory=dict)
    op_s: dict[str, float] = field(default_factory=dict)
    op_calls: dict[str, int] = field(default_factory=dict)
    gaps: list[tuple[float, float]] = field(default_factory=list)

    def module_time(self, name: str) -> tuple[float, int]:
        """(seconds, runs) of every program whose name starts with ``name``
        (``jit__sync_step`` matches ``jit__sync_step(123)``)."""
        s = sum(v for k, v in self.module_s.items() if _base(k) == name)
        n = sum(v for k, v in self.module_calls.items() if _base(k) == name)
        return s, n

    def ops_matching(self, pattern: str) -> tuple[float, int]:
        rx = re.compile(pattern)
        s = sum(v for k, v in self.op_s.items() if rx.search(k))
        n = sum(v for k, v in self.op_calls.items() if rx.search(k))
        return s, n


def _base(module_name: str) -> str:
    return module_name.split("(", 1)[0]


def short_op(name: str) -> str:
    """An operation's HLO name without its signature (``%fusion.14``), with
    a custom call's target (``%custom-call.26 [EighTpu]``)."""
    head = name.split(" = ", 1)[0]
    target = re.search(r'custom_call_target="([^"]+)"', name)
    return f"{head} [{target.group(1)}]" if target else head


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def reduce_planes(planes, window: tuple[float, float] | None = None
                  ) -> Reduction:
    """``planes``: ``jax.profiler.ProfileData.planes`` (or objects with the
    same ``name``/``lines``/``events`` shape; times in ns)."""
    planes = list(planes)
    if window is None:
        for plane in planes:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW_ANNOTATION:
                        window = (ev.start_ns, ev.start_ns + ev.duration_ns)
        if window is None:
            raise ValueError(f"no {WINDOW_ANNOTATION!r} event in the trace")
    lo, hi = window
    dev_planes = [p for p in planes if re.match(r"/device:[A-Z]+:\d+$",
                                                p.name)]
    red = Reduction(window_s=(hi - lo) / 1e9, window_start_ns=lo,
                    busy_s=0.0, n_devices=len(dev_planes))
    busy_total = 0.0
    first_gaps = None
    for plane in dev_planes:
        ops: list[tuple[float, float]] = []
        for line in plane.lines:
            if line.name == "XLA Modules":
                for ev in line.events:
                    s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                    if e <= lo or s >= hi:
                        continue
                    red.module_s[ev.name] = red.module_s.get(ev.name, 0.0) \
                        + (min(e, hi) - max(s, lo)) / 1e9
                    red.module_calls[ev.name] = \
                        red.module_calls.get(ev.name, 0) + 1
            elif line.name == "XLA Ops":
                for ev in line.events:
                    s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                    if e <= lo or s >= hi:
                        continue
                    ops.append((s, e))
                    red.op_s[ev.name] = red.op_s.get(ev.name, 0.0) \
                        + (min(e, hi) - max(s, lo)) / 1e9
                    red.op_calls[ev.name] = red.op_calls.get(ev.name, 0) + 1
        busy = _clip(_union(ops), lo, hi)
        busy_total += sum(e - s for s, e in busy) / 1e9
        if first_gaps is None:
            edges = [lo] + [t for iv in busy for t in iv] + [hi]
            first_gaps = [(edges[i], edges[i + 1])
                          for i in range(0, len(edges), 2)
                          if edges[i + 1] > edges[i]]
    red.busy_s = busy_total / max(len(dev_planes), 1)
    red.gaps = first_gaps or []
    return red


def reduce_file(path: str) -> Reduction:
    from jax.profiler import ProfileData
    return reduce_planes(ProfileData.from_file(path).planes)


def attribute_gaps(gaps: list[tuple[float, float]],
                   spans: list[tuple[str, float, float]]
                   ) -> list[tuple[str, float]]:
    """Total idle seconds per host activity: each gap goes to the innermost
    host span (shortest one) that covers its midpoint, or to ``host.other``.
    ``spans`` are (name, start_ns, end_ns) on the profiler clock."""
    by_start = sorted(spans, key=lambda s: s[1])
    totals: dict[str, float] = {}
    j = 0
    active: list[tuple[str, float, float]] = []
    for s, e in sorted(gaps):
        mid = (s + e) / 2
        while j < len(by_start) and by_start[j][1] <= mid:
            active.append(by_start[j])
            j += 1
        active = [a for a in active if a[2] >= mid]
        name = min(active, key=lambda a: a[2] - a[1])[0] if active \
            else "host.other"
        totals[name] = totals.get(name, 0.0) + (e - s) / 1e9
    return sorted(totals.items(), key=lambda kv: -kv[1])
