"""Spec-driven pre-jax runtime bootstrap.

``ExperimentSpec.mesh`` carries process-level runtime knobs — ``platform``,
``x64``, extra ``xla_flags``, and the forced host-device count implied by
``shards`` — that only take effect when the environment is set BEFORE jax
initialises its backend.  This module turns that spec section into
environment state:

    from repro.launch.platform import bootstrap
    bootstrap({"mesh": {"shards": 8, "platform": "cpu"}})
    import repro.api as api          # jax now initialises under the right env

``bootstrap`` accepts the raw JSON dict of a spec (or just its ``mesh``
section, or a ``MeshSpec``-shaped object) precisely so callers can peek at a
spec file without importing anything jax-adjacent first.  Like
``repro.launch.bootstrap`` it must never import jax: when the environment
had to change after jax was already imported, the interpreter re-execs once
(``os.execv`` preserves ``os.environ``), and the re-exec'd process falls
through because the environment already matches.
"""
from __future__ import annotations

import os
import sys
from typing import Any, Mapping

from repro.launch.bootstrap import refuse_reexec_after_backend

_FORCE = "--xla_force_host_platform_device_count"


def _get(section: Any, name: str, default: Any) -> Any:
    """Field access across raw dicts and MeshSpec-shaped objects."""
    if isinstance(section, Mapping):
        return section.get(name, default)
    return getattr(section, name, default)


def _mesh_section(spec: Any) -> Any:
    """The mesh section of ``spec`` — itself, if already a mesh section."""
    if isinstance(spec, Mapping) and "mesh" in spec:
        return spec["mesh"]
    inner = getattr(spec, "mesh", None)
    return inner if inner is not None else spec


def resolve_env(spec: Any, environ: Mapping[str, str] | None = None
                ) -> dict[str, str]:
    """The environment updates ``spec``'s mesh section implies — pure.

    Returns only the variables whose value must CHANGE relative to
    ``environ`` (default ``os.environ``), so an empty dict means the process
    is already correctly configured (the re-exec termination condition).

      * ``platform`` (non-empty)  → ``JAX_PLATFORMS``
      * ``x64`` (true)            → ``JAX_ENABLE_X64=1``
      * ``xla_flags``             → appended to ``XLA_FLAGS`` in spec order,
                                    skipping flags already present verbatim
      * ``shards > 1``            → ``--xla_force_host_platform_device_count``
                                    (cpu / unset platform only; replaces a
                                    smaller forced count, never shrinks one)
    """
    env = os.environ if environ is None else environ
    mesh = _mesh_section(spec)
    shards = int(_get(mesh, "shards", 1))
    platform = str(_get(mesh, "platform", "") or "")
    x64 = bool(_get(mesh, "x64", False))
    extra = tuple(_get(mesh, "xla_flags", ()) or ())

    updates: dict[str, str] = {}
    if platform and env.get("JAX_PLATFORMS", "") != platform:
        updates["JAX_PLATFORMS"] = platform
    if x64 and env.get("JAX_ENABLE_X64", "") not in ("1", "true", "True"):
        updates["JAX_ENABLE_X64"] = "1"

    flags = env.get("XLA_FLAGS", "").split()
    for f in extra:
        if f not in flags:
            flags.append(f)
    if shards > 1 and platform in ("", "cpu"):
        current = 0
        for f in flags:
            if f.startswith(_FORCE + "="):
                current = int(f.split("=", 1)[1])
        if current < shards:
            flags = [f for f in flags if not f.startswith(_FORCE + "=")]
            flags.append(f"{_FORCE}={shards}")
    joined = " ".join(flags)
    if joined != env.get("XLA_FLAGS", ""):
        updates["XLA_FLAGS"] = joined
    return updates


def bootstrap(spec: Any, *, reexec: bool | None = None) -> bool:
    """Apply :func:`resolve_env` to ``os.environ``; returns True if anything
    changed.

    When jax is already imported the new environment cannot take effect in
    this process, so the script re-execs once (``reexec=None`` means "only
    if jax is in ``sys.modules``"; pass False to force in-process mutation
    for tests).  Idempotent: a second call — including the re-exec'd
    process's — finds nothing to change and falls straight through.  Once a
    backend exists it raises instead of re-exec'ing (see
    :func:`repro.launch.bootstrap.refuse_reexec_after_backend`).
    """
    updates = resolve_env(spec)
    if not updates:
        return False
    if reexec is None:
        reexec = "jax" in sys.modules
    if reexec:
        refuse_reexec_after_backend()
    os.environ.update(updates)
    if reexec:
        os.execv(sys.executable, [sys.executable, sys.argv[0], *sys.argv[1:]])
    return True
