"""What every driver shares: the run's context, the measured window, the
compile watch, the result, and the layer context the metric readers read."""
from __future__ import annotations

import gc
import os
import time
from dataclasses import dataclass, field
from typing import Any

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


class BenchError(RuntimeError):
    """The run cannot produce a result (no chip, a compile in the window)."""


@dataclass
class RunContext:
    workload: str
    seed: int
    seconds: float
    trace: bool
    cell: dict
    config: dict
    traffic: dict
    t_process: float               # perf_counter at process start
    trace_dir: str = ""


@dataclass
class Check:
    """One number compared with the reference, beside its limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


@dataclass
class RunResult:
    e2e: dict[str, float]
    checks: list[Check]
    attempted: int
    failed: int
    layer: dict[str, Any] = field(default_factory=dict)
    memory_peak_bytes: int = 0
    info: dict[str, float] = field(default_factory=dict)


class CompileWatch:
    """Counts JAX tracing and compilation events while armed.  A window in
    which it counts anything has compiled, and the run fails."""

    _installed = False
    _armed: "CompileWatch | None" = None

    def __init__(self):
        self.events: list[str] = []
        if not CompileWatch._installed:
            import jax.monitoring as mon
            mon.register_event_duration_secs_listener(CompileWatch._on_dur)
            mon.register_event_listener(CompileWatch._on_event)
            CompileWatch._installed = True

    @staticmethod
    def _note(event: str) -> None:
        w = CompileWatch._armed
        if w is not None and ("/jax/core/compile" in event
                              or "compilation_cache" in event):
            w.events.append(event)

    @staticmethod
    def _on_dur(event: str, duration: float, **kw) -> None:
        CompileWatch._note(event)

    @staticmethod
    def _on_event(event: str, **kw) -> None:
        CompileWatch._note(event)

    def __enter__(self) -> "CompileWatch":
        CompileWatch._armed = self
        return self

    def __exit__(self, *exc) -> bool:
        CompileWatch._armed = None
        return False


class Window:
    """The measured window: host clock around the loop, the compile watch,
    and with tracing on the profiler and its ``bench.window`` annotation."""

    def __init__(self, ctx: RunContext):
        self.ctx = ctx
        self.t0 = self.t1 = 0.0
        self.t0_ns = 0
        self._annot = None
        self.watch = CompileWatch()

    def __enter__(self) -> "Window":
        import jax
        # the set-up's objects leave the collector's young generations, so
        # the window's collections scan only what the window allocates
        gc.collect()
        gc.freeze()
        if self.ctx.trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(self.ctx.trace_dir,
                                     profiler_options=opts)
            self._annot = jax.profiler.TraceAnnotation("bench.window")
            self._annot.__enter__()
        self.watch.__enter__()
        self.t0_ns = time.perf_counter_ns()
        self.t0 = self.t0_ns / 1e9
        return self

    def _close(self) -> None:
        self.watch.__exit__(None, None, None)
        if self._annot is not None:
            import jax
            self._annot.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self._annot = None

    def end(self) -> None:
        """Close the window: call once the device work has finished."""
        self.t1 = time.perf_counter()
        self._close()
        if self.watch.events:
            raise BenchError(f"the window compiled: {self.watch.events[:5]}")

    def __exit__(self, exc_type, *exc) -> bool:
        if exc_type is not None:
            self._close()
        return False

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


def spans_in_window(recorder, window: Window) -> list[dict]:
    """The recorder's span records that lie inside the window, with
    ``t0_ns``/``t1_ns`` on ``perf_counter_ns``."""
    base = recorder._t0
    out = []
    for r in recorder.records:
        if r.get("kind") != "span":
            continue
        t0 = base + r["ts_us"] * 1e3
        t1 = t0 + r["dur_us"] * 1e3
        if t0 >= window.t0_ns and t1 <= window.t1 * 1e9:
            out.append(dict(r, t0_ns=t0, t1_ns=t1))
    return out


def program_precision(config: dict):
    """The product precision the configuration states
    (``matmul_precision``), as a context for everything the run does."""
    import jax
    return jax.default_matmul_precision(config["matmul_precision"])


def memory_peak_bytes() -> int:
    import jax
    peaks = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0
