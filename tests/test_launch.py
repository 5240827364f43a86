"""Launcher machinery: fl-round target build, dryrun lower on a small mesh
(subprocess — device count must be set before jax initialises), flops model
consistency with the registry."""
import subprocess
import sys

import jax
import pytest

from repro.configs import ARCHS, SHAPES, shape_applicable
from repro.launch.flops import step_cost


def test_fl_target_builds_abstract():
    from repro.launch.fl_target import FLTargetConfig, stacked_param_specs
    cfg = FLTargetConfig(n_clients=8, in_dim=32, hidden=64, rep_dim=16)
    shapes = stacked_param_specs(cfg)
    assert shapes["w0"].shape == (8, 32, 64)


def test_make_client_mesh_shapes_and_device_guard():
    from repro.launch.mesh import CLIENT_AXIS, make_client_mesh
    n = len(jax.devices())
    mesh = make_client_mesh(n)
    assert mesh.axis_names == (CLIENT_AXIS,)
    assert mesh.shape[CLIENT_AXIS] == n
    with pytest.raises(ValueError, match="host_platform_device_count"):
        make_client_mesh(n + 1)


def test_platform_resolve_env_pure():
    """repro.launch.platform.resolve_env: returns only the vars that must
    change, from a raw spec dict / mesh section / MeshSpec-shaped object,
    without ever importing jax."""
    from repro.launch.platform import resolve_env

    # full spec dict, empty environment: shards force the CPU device count
    up = resolve_env({"mesh": {"shards": 8}}, environ={})
    assert up == {"XLA_FLAGS": "--xla_force_host_platform_device_count=8"}

    # bare mesh section + platform/x64/extra flags
    up = resolve_env({"shards": 2, "platform": "cpu", "x64": True,
                      "xla_flags": ["--xla_cpu_multi_thread_eigen=false"]},
                     environ={})
    assert up["JAX_PLATFORMS"] == "cpu"
    assert up["JAX_ENABLE_X64"] == "1"
    assert up["XLA_FLAGS"].split() == [
        "--xla_cpu_multi_thread_eigen=false",
        "--xla_force_host_platform_device_count=2"]

    # idempotence: an environment that already matches needs no updates
    env = dict(up, JAX_PLATFORMS="cpu", JAX_ENABLE_X64="1")
    assert resolve_env({"shards": 2, "platform": "cpu", "x64": True,
                        "xla_flags": ["--xla_cpu_multi_thread_eigen=false"]},
                       environ=env) == {}

    # a larger already-forced count is never shrunk; a smaller one grows
    big = {"XLA_FLAGS": "--xla_force_host_platform_device_count=16"}
    assert resolve_env({"mesh": {"shards": 8}}, environ=big) == {}
    small = {"XLA_FLAGS": "--xla_force_host_platform_device_count=2"}
    up = resolve_env({"mesh": {"shards": 8}}, environ=small)
    assert up["XLA_FLAGS"] == "--xla_force_host_platform_device_count=8"

    # non-cpu platform: no forced host device count
    assert resolve_env({"mesh": {"shards": 8, "platform": "gpu"}},
                       environ={}) == {"JAX_PLATFORMS": "gpu"}

    # MeshSpec itself works as the section (attr access path)
    from repro.api import MeshSpec
    assert resolve_env(MeshSpec(shards=4), environ={}) == {
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}


def test_platform_bootstrap_subprocess_reexec(tmp_path):
    """End-to-end: bootstrap() after jax import re-execs once (the re-exec
    replays ``sys.argv``, so this must be a real script file), and the
    re-exec'd process sees the forced device count without looping."""
    import os
    script = tmp_path / "boot.py"
    script.write_text(
        "import jax\n"                           # jax initialised too early…
        "from repro.launch.platform import bootstrap\n"
        "bootstrap({'mesh': {'shards': 4}})\n"   # …so this re-execs
        "print('DEVS', len(jax.devices()))\n")
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    out = subprocess.run([sys.executable, str(script)], text=True,
                         capture_output=True, env=env, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "DEVS 4" in out.stdout


def test_platform_bootstrap_refuses_reexec_after_backend(tmp_path):
    """Once a backend exists (it may hold the chip), bootstrap() raises
    instead of re-exec'ing, and leaves the environment untouched."""
    import os
    script = tmp_path / "late.py"
    script.write_text(
        "import os, jax\n"
        "jax.devices()\n"                       # backend created
        "from repro.launch.platform import bootstrap\n"
        "try:\n"
        "    bootstrap({'mesh': {'shards': 4}})\n"
        "except RuntimeError:\n"
        "    print('REFUSED', os.environ.get('XLA_FLAGS', ''))\n")
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    out = subprocess.run([sys.executable, str(script)], text=True,
                         capture_output=True, env=env, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip() == "REFUSED"


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_step_cost_defined_for_all_runnable_combos(arch):
    for shape_name, shape in SHAPES.items():
        ok, _ = shape_applicable(arch, shape_name)
        if not ok:
            continue
        c = step_cost(ARCHS[arch], shape)
        assert c.flops_total > 0 and c.hbm_bytes > 0
        assert 0 < c.model_flops / c.flops_total < 1.2, (arch, shape_name)


_DRYRUN_SMOKE = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
import jax, jax.numpy as jnp
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
from repro.configs import get_config, SHAPES
from repro.launch import sharding as shd
from repro.launch.specs import batch_pspecs, train_batch_specs
from repro.models import lm
from repro.models.transformer import param_specs
from repro.optim import adamw
import dataclasses

# reduced arch on a 4x2 mini-mesh: the same machinery as production
cfg = dataclasses.replace(get_config("internvl2-2b").reduced(),
                          param_dtype="bfloat16")
mesh = jax.make_mesh((4, 2), ("data", "model"),
                     axis_types=(AxisType.Auto,) * 2)
pshape = param_specs(cfg)
pspec = shd.param_pspecs(cfg, pshape, mesh)
ns = lambda t: jax.tree.map(lambda s: NamedSharding(mesh, s), t,
                            is_leaf=lambda x: isinstance(x, P))
opt = adamw(1e-4)
oshape = jax.eval_shape(opt.init, pshape)
osh = ns(shd.opt_state_pspecs(oshape, pspec))
shape = dataclasses.replace(SHAPES["train_4k"], seq_len=32, global_batch=8)
batch = train_batch_specs(cfg, shape)
bsh = ns(batch_pspecs(cfg, batch, mesh))
with jax.set_mesh(mesh):
    step = lm.make_train_step(cfg, opt)
    compiled = jax.jit(step, in_shardings=(ns(pspec), osh, bsh),
                       out_shardings=(NamedSharding(mesh, P()), ns(pspec), osh)
                       ).lower(pshape, oshape, batch).compile()
assert compiled.cost_analysis() is not None
print("OK")
"""


@pytest.mark.slow   # compiles a full reduced arch on an 8-device host mesh
def test_dryrun_machinery_on_mini_mesh():
    res = subprocess.run([sys.executable, "-c", _DRYRUN_SMOKE],
                         capture_output=True, text=True,
                         env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin"})
    assert "OK" in res.stdout, res.stdout + res.stderr
