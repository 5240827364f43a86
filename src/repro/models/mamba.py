"""Mamba (selective SSM) mixer for the Jamba hybrid architecture.

Faithful Mamba-1 block: in_proj → (x, z); causal depthwise conv; selective
(input-dependent) Δ, B, C — each RMSNorm'd first where the block has Jamba's
inner norms (``dt_norm``, ``b_norm``, ``c_norm``); diagonal state-space
scan; gated output.

The train/prefill scan (:func:`selective_scan`) keeps only the state at
the edges of blocks of time, and the backward pass recomputes a block's
states instead of storing a ``(B, S, d_inner, d_state)`` array per layer
(Mamba's own kernel recomputes the same way).  On a TPU it is the Pallas
kernel ``repro.kernels.selective_scan``, which keeps the state on chip; on
other backends :func:`chunked_scan`, an outer ``lax.scan`` over chunks of
time, each chunk a rematerialised inner ``lax.scan`` over its steps (the
kernel's oracle).  The state is laid out ``(B, d_state, d_inner)``,
with d_inner on the minor axis.  The recurrence is elementwise over
d_inner, so sharding d_inner over the `model` mesh axis keeps the scan free
of collectives.  Decode is the single-step recurrence against carried
(conv_state, ssm_state).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import dense, dense_init, rms_norm

#: time steps per rematerialised chunk of the train/prefill scan
SCAN_CHUNK = 64


def mamba_init(key: jax.Array, d_model: int, d_inner: int, d_state: int,
               d_conv: int, dt_rank: int, dtype, inner_norms: bool = False
               ) -> dict:
    ks = jax.random.split(key, 7)
    p = {
        "in_proj": dense_init(ks[0], d_model, 2 * d_inner, dtype),
        "conv_w": (jax.random.normal(ks[1], (d_conv, d_inner), jnp.float32)
                   * (1.0 / d_conv) ** 0.5).astype(dtype),
        "conv_b": jnp.zeros((d_inner,), dtype),
        "x_proj": dense_init(ks[2], d_inner, dt_rank + 2 * d_state, dtype),
        "dt_proj": dense_init(ks[3], dt_rank, d_inner, dtype),
        "dt_bias": jnp.full((d_inner,), -4.6, jnp.float32),  # softplus^-1(0.01)
        "A_log": jnp.log(jnp.broadcast_to(
            jnp.arange(1, d_state + 1, dtype=jnp.float32)[None, :],
            (d_inner, d_state)).copy()),
        "D": jnp.ones((d_inner,), jnp.float32),
        "out_proj": dense_init(ks[4], d_inner, d_model, dtype),
    }
    if inner_norms:
        for name, dim in (("dt_norm", dt_rank), ("b_norm", d_state),
                          ("c_norm", d_state)):
            p[name] = {"scale": jnp.zeros((dim,), dtype)}
    return p


def _selective_terms(p: dict, xc: jax.Array, d_state: int, dt_rank: int):
    """xc (B, S, d_inner) -> dt (B,S,d_inner), Bmat/Cmat (B,S,d_state)."""
    proj = xc @ p["x_proj"]
    dt_in, Bm, Cm = jnp.split(proj.astype(jnp.float32),
                              [dt_rank, dt_rank + d_state], axis=-1)
    if "dt_norm" in p:                       # Jamba's inner RMSNorms
        dt_in = rms_norm(dt_in, p["dt_norm"]["scale"])
        Bm = rms_norm(Bm, p["b_norm"]["scale"])
        Cm = rms_norm(Cm, p["c_norm"]["scale"])
    dt = jax.nn.softplus(dt_in @ p["dt_proj"].astype(jnp.float32) + p["dt_bias"])
    return dt, Bm, Cm


def _scan_step(a_t: jax.Array):
    """One step of the recurrence, with ``a_t = A.T`` (N, d_inner)."""
    def step(h, x):
        u_t, dt_t, b_t, c_t = x                 # (B, di), (B, di), (B, N) x2
        h = jnp.exp(dt_t[:, None, :] * a_t) * h \
            + (dt_t * u_t)[:, None, :] * b_t[:, :, None]
        return h, jnp.sum(h * c_t[:, :, None], axis=1)
    return step


@jax.named_scope("mamba_scan")
def selective_scan(u: jax.Array, delta: jax.Array, a_log: jax.Array,
                   b: jax.Array, c: jax.Array, d: jax.Array, z: jax.Array,
                   *, chunk: int = SCAN_CHUNK,
                   use_pallas: bool | None = None) -> jax.Array:
    """``y_t = (C_t·h_t + D ⊙ u_t) ⊙ SiLU(z_t)`` with ``h_0 = 0`` and
    ``h_t = exp(Δ_t A) ⊙ h_{t-1} + (Δ_t u_t) B_t``, ``A = -exp(A_log)``.

    u, delta, z: (B, S, d_inner) float32; b, c: (B, S, N); a_log:
    (d_inner, N); d: (d_inner,).  The state stays float32.
    ``use_pallas=None`` takes the Pallas kernel on a TPU and
    :func:`chunked_scan` elsewhere.
    """
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    f32 = jnp.float32
    a = -jnp.exp(a_log.astype(f32))
    u32, dt32, b32, c32 = (x.astype(f32) for x in (u, delta, b, c))
    if use_pallas:
        from repro.kernels.selective_scan import scan_pallas
        y = scan_pallas(u32, dt32, a.T, b32, c32)
    else:
        y = chunked_scan(u32, dt32, a, b32, c32, chunk)
    return (y + u * d) * jax.nn.silu(z)


def chunked_scan(u: jax.Array, delta: jax.Array, a: jax.Array, b: jax.Array,
                 c: jax.Array, chunk: int = SCAN_CHUNK) -> jax.Array:
    """``y_t = C_t·h_t`` of the recurrence, ``a = A`` (d_inner, N), as an
    outer ``lax.scan`` over chunks of ``chunk`` steps, each chunk a
    rematerialised inner ``lax.scan``.  ``chunk`` need not divide S: the
    tail is padded with ``Δ = 0``, which carries the state through
    unchanged, and its outputs are dropped."""
    bsz, S, di = u.shape
    L = min(chunk, S)
    n = -(-S // L)
    pad = n * L - S
    a_t = a.T

    def chunks(x):                            # (B, S, F) -> (n, L, B, F)
        if pad:
            x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        return x.reshape(bsz, n, L, -1).transpose(1, 2, 0, 3)

    @jax.checkpoint
    def run_chunk(h, xs, a_t):
        return jax.lax.scan(_scan_step(a_t), h, xs)

    h0 = jnp.zeros((bsz, b.shape[-1], di), jnp.float32)
    _, ys = jax.lax.scan(lambda h, xs: run_chunk(h, xs, a_t), h0,
                         tuple(chunks(x) for x in (u, delta, b, c)))
    return ys.transpose(2, 0, 1, 3).reshape(bsz, n * L, di)[:, :S]


def mamba_apply(p: dict, x: jax.Array, *, d_state: int, d_conv: int,
                dt_rank: int, chunk: int = SCAN_CHUNK) -> jax.Array:
    """Train/prefill path. x (B, S, D) -> (B, S, D)."""
    S = x.shape[1]
    xz = dense(x, p["in_proj"])
    xr, z = jnp.split(xz, 2, axis=-1)                          # (B, S, d_inner)

    # causal depthwise conv over time
    pad = jnp.pad(xr, ((0, 0), (d_conv - 1, 0), (0, 0)))
    xc = sum(pad[:, i:i + S, :] * p["conv_w"][i][None, None, :]
             for i in range(d_conv)) + p["conv_b"]
    xc = jax.nn.silu(xc)

    dt, Bm, Cm = _selective_terms(p, xc, d_state, dt_rank)
    y = selective_scan(xc.astype(jnp.float32), dt, p["A_log"], Bm, Cm,
                       p["D"], z.astype(jnp.float32), chunk=chunk)
    return dense(y.astype(x.dtype), p["out_proj"])


def mamba_init_state(batch: int, d_inner: int, d_state: int, d_conv: int,
                     dtype) -> dict:
    return {"conv": jnp.zeros((batch, d_conv - 1, d_inner), dtype),
            "ssm": jnp.zeros((batch, d_inner, d_state), jnp.float32)}


def mamba_decode(p: dict, x: jax.Array, state: dict, *, d_state: int,
                 d_conv: int, dt_rank: int) -> tuple[jax.Array, dict]:
    """Single-token step. x (B, 1, D) -> (B, 1, D), new state."""
    B = x.shape[0]
    xz = dense(x[:, 0], p["in_proj"])
    xr, z = jnp.split(xz, 2, axis=-1)                          # (B, d_inner)

    conv_buf = jnp.concatenate([state["conv"], xr[:, None]], axis=1)  # (B,d_conv,di)
    xc = jnp.einsum("bcd,cd->bd", conv_buf, p["conv_w"]) + p["conv_b"]
    xc = jax.nn.silu(xc)
    new_conv = conv_buf[:, 1:]

    dt, Bm, Cm = _selective_terms(p, xc[:, None], d_state, dt_rank)
    dt, Bm, Cm = dt[:, 0], Bm[:, 0], Cm[:, 0]
    A = -jnp.exp(p["A_log"])
    dA = jnp.exp(dt[..., None] * A[None])                      # (B, d_inner, N)
    h = state["ssm"] * dA + (dt * xc.astype(jnp.float32))[..., None] * Bm[:, None, :]
    y = jnp.einsum("bdn,bn->bd", h, Cm) + xc.astype(jnp.float32) * p["D"][None]
    y = y.astype(x.dtype) * jax.nn.silu(z)
    out = dense(y, p["out_proj"])[:, None]
    return out, {"conv": new_conv, "ssm": h}
