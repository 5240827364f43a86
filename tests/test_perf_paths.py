"""The §Perf optimisation paths are mathematically identical to baselines.

* two_step / two_step_bf16 cluster aggregation ≡ the mix matmul (exact / bf16
  tolerance) — property over random labels;
* shard_map expert-parallel MoE ≡ the dense-dispatch MoE, verified on a real
  4-device mesh in a subprocess (device count must be set before jax init).
"""
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core.aggregation import cluster_mean_params
from repro.utils.tree import tree_stack


@settings(max_examples=25, deadline=None)
@given(m=st.integers(2, 24), c=st.integers(1, 6), seed=st.integers(0, 2**16))
def test_two_step_equals_mix(m, c, seed):
    ks = jax.random.split(jax.random.PRNGKey(seed), m + 1)
    sp = tree_stack([{"w": jax.random.normal(k, (6, 5)),
                      "b": jax.random.normal(k, (3,))} for k in ks[:m]])
    labels = jax.random.randint(ks[-1], (m,), 0, c)
    a = cluster_mean_params(sp, labels, c, method="mix")
    b = cluster_mean_params(sp, labels, c, method="two_step")
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=1e-5)


def test_two_step_bf16_close():
    ks = jax.random.split(jax.random.PRNGKey(0), 9)
    sp = tree_stack([{"w": jax.random.normal(k, (16, 8))} for k in ks[:8]])
    labels = jnp.asarray([0, 0, 1, 1, 2, 2, 2, 0])
    a = cluster_mean_params(sp, labels, 3, method="mix")
    b = cluster_mean_params(sp, labels, 3, method="two_step_bf16")
    np.testing.assert_allclose(np.asarray(a["w"]), np.asarray(b["w"]), atol=3e-2)


_EP_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
from repro.models.moe import moe_apply, moe_init, moe_capacity
from repro.models.moe_sharded import moe_apply_shard_map

mesh = jax.make_mesh((2, 2), ("data", "model"),
                     axis_types=(AxisType.Auto,) * 2)
E, D, F, T, k = 4, 16, 32, 64, 2
p = moe_init(jax.random.PRNGKey(0), "swiglu", D, F, E, jnp.float32)
x = jax.random.normal(jax.random.PRNGKey(1), (8, 8, D))
cap = moe_capacity(T, k, E, multiple=8)

with jax.set_mesh(mesh):
    y_ref, aux_ref = jax.jit(
        lambda p, x: moe_apply("swiglu", p, x, top_k=k, capacity=cap))(p, x)
    # EP path: per-shard capacity = cap // 2 per local dispatch -> give the
    # same TOTAL capacity so no extra drops vs the reference
    y_ep, aux_ep = jax.jit(
        lambda p, x: moe_apply_shard_map(
            "swiglu", p, x, top_k=k, capacity=cap * 2))(p, x)

# EP computes capacity per shard; with generous capacity no token drops on
# either path, so outputs must match exactly up to float error.
err = float(jnp.max(jnp.abs(y_ref - y_ep)))
print("MAXERR", err)
assert err < 1e-4, err
print("OK")
"""


def test_shard_map_ep_matches_dense_moe():
    res = subprocess.run([sys.executable, "-c", _EP_SCRIPT],
                         capture_output=True, text=True,
                         env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin"})
    assert "OK" in res.stdout, res.stdout + res.stderr
