"""Selective-scan kernel (interpret mode) against its oracle,
``repro.models.mamba.chunked_scan``: the forward and the gradients with
respect to every input, through ``selective_scan`` as the model calls it.

Both sides compute the same float32 recurrence; only the order of the
state-index sums differs (the kernel adds them one index at a time), so
1e-6 of the largest output, and of each gradient's norm, leaves room for
rounding alone.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.selective_scan import scan_pallas
from repro.models import mamba


def _inputs(B, S, di, n, seed):
    ks = jax.random.split(jax.random.key(seed), 8)
    u = jax.random.normal(ks[0], (B, S, di))
    delta = jax.nn.softplus(jax.random.normal(ks[1], (B, S, di)) - 1.0)
    a_log = jnp.log(jnp.broadcast_to(jnp.arange(1.0, n + 1), (di, n))) \
        + 0.1 * jax.random.normal(ks[2], (di, n))
    b = jax.random.normal(ks[3], (B, S, n))
    c = jax.random.normal(ks[4], (B, S, n))
    d = jax.random.normal(ks[5], (di,))
    z = jax.random.normal(ks[6], (B, S, di))
    w = jax.random.normal(ks[7], (B, S, di))
    return (u, delta, a_log, b, c, d, z), w


@pytest.fixture
def interpreted(monkeypatch):
    """``selective_scan``'s kernel in interpret mode, on any backend."""
    import repro.kernels.selective_scan as ks

    def scan(u, delta, a, b, c):
        return scan_pallas(u, delta, a, b, c, 8, True)
    monkeypatch.setattr(ks, "scan_pallas", scan)


@pytest.mark.parametrize("B,S,di,n", [(2, 24, 16, 4), (1, 21, 64, 3),
                                      (2, 16, 2048, 2)],
                         ids=["divides", "ragged", "two-channel-blocks"])
def test_kernel_matches_chunked_scan(B, S, di, n, interpreted):
    args, w = _inputs(B, S, di, n, S + di)

    def loss(use_pallas):
        return jax.value_and_grad(
            lambda *a: jnp.sum(mamba.selective_scan(
                *a, chunk=5, use_pallas=use_pallas) * w),
            argnums=tuple(range(7)))

    y_k = mamba.selective_scan(*args, use_pallas=True)
    y_o = mamba.selective_scan(*args, chunk=5, use_pallas=False)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_o),
                               atol=1e-6 * float(jnp.max(jnp.abs(y_o))))
    (_, gk), (_, go) = loss(True)(*args), loss(False)(*args)
    for name, p, r in zip(("u", "delta", "a_log", "b", "c", "d", "z"),
                          gk, go):
        assert np.linalg.norm(np.asarray(p - r)) <= 1e-6 * np.linalg.norm(
            np.asarray(r)), name


def test_kernel_under_vmap_matches_unbatched(interpreted):
    """The round step maps the scan over the cohort's clients."""
    args, _ = _inputs(3, 16, 16, 4, 1)
    u, delta, a_log, b, c, d, z = args
    one = jax.vmap(lambda u, dl, b, c, z: mamba.selective_scan(
        u[None], dl[None], a_log, b[None], c[None], d, z[None],
        use_pallas=True)[0])(u, delta, b, c, z)
    whole = mamba.selective_scan(*args, use_pallas=True)
    np.testing.assert_allclose(np.asarray(one), np.asarray(whole),
                               rtol=0, atol=1e-6)
