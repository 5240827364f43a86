"""Production mesh definitions (TPU v5e pods).

Functions, not module-level constants — importing this module never touches
jax device state (critical: the dry-run sets XLA_FLAGS before first jax use,
while tests/benches must keep seeing 1 CPU device).  Every mesh here has
Auto axis types: sharding is propagated by GSPMD from the constraints the
engine places.  Activate one for jit/shard_map bodies with
``jax.set_mesh(mesh)``; a device-free mesh for rule checks is
``jax.sharding.AbstractMesh(shape, axes)``.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False,
                         dm_shape: tuple[int, int] | None = None):
    """16×16 = 256 chips single pod; 2×16×16 = 512 chips across two pods.

    ``dm_shape`` overrides the (data, model) factorisation (same chip count)
    — e.g. (32, 8) keeps attention-head sharding divisible for archs with few
    (GQA) heads; see EXPERIMENTS.md §Perf.
    """
    dm = dm_shape or (16, 16)
    assert dm[0] * dm[1] == 256, dm
    shape = (2, *dm) if multi_pod else dm
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many (host) devices exist — for tests."""
    return _auto_mesh((data, model), ("data", "model"))


CLIENT_AXIS = "clients"


def make_client_mesh(shards: int):
    """1-D mesh sharding the *population* (client) axis of the parameter
    arena (`repro.runtime.arena.ShardedParamArena`) over ``shards`` devices.

    This is the federation scaling axis: population state is
    O(n_clients · N_params) and spreads across devices as arena rows, while
    the per-round cohort axis shards over the SAME mesh (each device trains
    its slice of the cohort; `repro.launch.sharding.cohort_shardings` builds
    the constraint pair).  On CPU, force multiple host devices with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` *before* the
    first jax call (CI's mesh leg and the sharded tests do exactly this).
    """
    avail = len(jax.devices())
    if shards > avail:
        raise ValueError(
            f"make_client_mesh({shards}) needs {shards} devices but only "
            f"{avail} exist; on CPU set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={shards} before jax "
            f"initialises")
    return _auto_mesh((shards,), (CLIENT_AXIS,))


def batch_axes(mesh) -> tuple[str, ...]:
    """Mesh axes the global batch is sharded over."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def axis_size(mesh, name: str) -> int:
    return mesh.shape[name] if name in mesh.axis_names else 1
