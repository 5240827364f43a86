"""Pre-jax process bootstrap helpers.

This module must never import jax (directly or transitively): its whole
point is to adjust ``XLA_FLAGS`` *before* jax initialises the platform —
scripts call :func:`force_host_device_count` ahead of their first repro /
jax import (see ``benchmarks/round_bench.py`` and
``examples/simulate_population.py``).
"""
from __future__ import annotations

import os
import sys

_FLAG = "--xla_force_host_platform_device_count"


def refuse_reexec_after_backend() -> None:
    """Raise if this process already created a JAX backend — read from
    ``sys.modules``, so asking never imports jax.  Environment changes for
    jax must re-exec before that point: a live backend may hold the
    process's accelerator, which the re-exec'd image could not reach."""
    xb = sys.modules.get("jax._src.xla_bridge")
    if xb is not None and xb.backends_are_initialized():
        raise RuntimeError(
            "the jax environment (XLA_FLAGS / JAX_PLATFORMS) must be set "
            "before the first jax computation; this process already created "
            "a backend, so it cannot re-exec to apply it")


def force_host_device_count(n: int) -> None:
    """Ensure ``XLA_FLAGS`` forces ``n`` CPU host devices, re-execing the
    current script once if the flag had to be added or changed.

    No-op when ``n <= 1`` or the flag already requests exactly ``n`` (the
    re-exec'd process lands here again and falls through).  An existing
    forced count with a different value is replaced, not shadowed.
    """
    if n <= 1:
        return
    flags = os.environ.get("XLA_FLAGS", "").split()
    want = f"{_FLAG}={n}"
    if want in flags:
        return
    refuse_reexec_after_backend()
    flags = [f for f in flags if not f.startswith(_FLAG + "=")]
    os.environ["XLA_FLAGS"] = " ".join(flags + [want])
    os.execv(sys.executable, [sys.executable, sys.argv[0], *sys.argv[1:]])
