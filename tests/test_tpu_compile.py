"""Compile the main path's Mosaic kernel and round step for a TPU v5e.

Nothing runs here: the TPU compiler, given a described ``v5e:2x2``
topology, compiles for chips that are not attached and raises what the
chip would raise — a kernel Mosaic refuses, a kernel that cannot be
partitioned over a mesh.  Interpret-mode tests cannot see either.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every test worker imports this
file.  Code that asks ``jax.default_backend()`` still sees the CPU and
would take the jnp fingerprint path, so each test forces the kernel by
monkeypatching ``fingerprint_rows``.  The persistent compilation cache is
off around the compiles (a program compiled for a described chip is
written but cannot be read back without one).
"""
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (
    Mesh,
    NamedSharding,
    PartitionSpec as P,
    SingleDeviceSharding,
)

import repro.api as api
import repro.core.engine as engine_mod
import repro.kernels.fingerprint as fp
from repro.sim import ClientPopulation, SimulatedFederation


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _force_kernel(monkeypatch, module):
    monkeypatch.setattr(module, "fingerprint_rows", functools.partial(
        fp.fingerprint_rows, use_pallas=True))


@pytest.mark.parametrize("shape", [(100, 6570), (8, 2048), (13, 1000)])
def test_fingerprint_kernel_compiles_for_one_chip(shape, one_chip,
                                                  no_compile_cache):
    x = jax.ShapeDtypeStruct(shape, jnp.uint32, sharding=one_chip)
    hlo = jax.jit(fp.fingerprint_pallas).lower(x).compile().as_text()
    assert "tpu_custom_call" in hlo


def test_selective_scan_kernel_compiles_for_one_chip(one_chip,
                                                    no_compile_cache):
    """Jamba2-3B's scan at its widths (d_inner 5,120, d_state 16) over a
    1,024-token row of an 8-client cohort, forward and backward."""
    from repro.kernels.selective_scan import scan_pallas
    B, S, di, n = 8, 1024, 5120, 16

    def sd(shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    def loss(u, dl, a, b, c):
        return jnp.sum(scan_pallas(u, dl, a, b, c) ** 2)

    hlo = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        sd((B, S, di)), sd((B, S, di)), sd((n, di)), sd((B, S, n)),
        sd((B, S, n))).compile().as_text()
    assert hlo.count("tpu_custom_call") == 2


def test_sync_step_compiles_for_one_chip_with_kernel(one_chip, monkeypatch,
                                                     no_compile_cache):
    """The engine's donated sync step at the default experiment shapes
    (1000 clients, k=100, N=6,570) with the Mosaic kernel inside."""
    _force_kernel(monkeypatch, engine_mod)
    spec = api.ExperimentSpec()
    pop = ClientPopulation.from_spec(spec.population_spec())
    sim = SimulatedFederation(pop, spec)
    k = 100
    data_x, data_y = sim.step_data

    def on_chip(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = (on_chip(sim.arena.data.shape, jnp.float32),
            on_chip((k,), jnp.int32), on_chip(data_x.shape, data_x.dtype),
            on_chip(data_y.shape, data_y.dtype), on_chip((k,), jnp.float32))
    hlo = sim.engine.lower_entry("sync_step", *args).compile().as_text()
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("rows", ["sharded", "replicated"])
def test_sharded_fingerprint_compiles_on_four_chips(rows, topo, monkeypatch,
                                                    no_compile_cache):
    """A Mosaic kernel cannot be partitioned automatically: under
    shard_map each of the four devices fingerprints its own rows, whether
    the input arrives row-sharded or replicated."""
    _force_kernel(monkeypatch, fp)
    mesh = Mesh(np.array(topo.devices), ("clients",))
    spec = P("clients") if rows == "sharded" else P()
    x = jax.ShapeDtypeStruct((100, 6570), jnp.uint32,
                             sharding=NamedSharding(mesh, spec))
    hlo = jax.jit(lambda v: fp.fingerprint_rows_sharded(v, mesh, "clients")
                  ).lower(x).compile().as_text()
    assert "tpu_custom_call" in hlo
