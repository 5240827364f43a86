"""Pallas TPU kernels: Mamba-1's selective scan, forward and backward.

    h_t = exp(Δ_t A) ⊙ h_{t-1} + (Δ_t u_t) B_t,   y_t = C_t · h_t

per channel ``d`` of ``d_inner`` and state index ``n`` of ``d_state``, with
``h_0 = 0``.  The XLA form of this recurrence (``lax.scan`` over time) runs
a handful of device ops per time step and moves the whole ``(B, N,
d_inner)`` state through HBM at each; here the state stays on chip.

Layout: each channel block of ``8 × TW`` channels is one (8, TW) float32
tile (``d_inner`` reshaped ``(8, d_inner / 8)``, a free row-major view), so
every vector op on a state row fills whole vregs; the state is ``N`` such
tiles.  ``B_t`` and ``C_t`` are read as SMEM scalars, one per state index.
Grid ``(batch, channel blocks, time blocks)``: the last axis walks time in
blocks of ``TB`` steps (forward in the forward kernel, backward in the
backward one) and carries the state, or its gradient, in VMEM scratch.

The forward kernel also writes the state entering each time block.  The
backward kernel starts from it, recomputes the block's states into VMEM
and walks the block back, so nothing of size ``(S, N, d_inner)`` is ever
written to HBM (Mamba's own CUDA kernel recomputes the same way).  The
gradients of ``B`` and ``C`` are sums over channels, taken per channel
block and added outside.

A sequence whose length is not a multiple of ``TB`` is padded with
``Δ = 0``, which carries the state through unchanged and adds nothing to
any gradient.

Oracle: ``repro.models.mamba.chunked_scan`` (and the plain ``lax.scan`` of
``bench/reference_lm.py``); the gradients are those of the recurrence,
checked against autodiff of the oracle.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: time steps per grid step
TIME_BLOCK = 64


def _tiles(di: int) -> tuple[int, int]:
    """(W, TW): ``d_inner`` as (8, W) tiles, TW lanes a channel block."""
    if di % 8:
        raise ValueError(f"d_inner must be a multiple of 8, got {di}")
    w = di // 8
    return w, 128 if w % 128 == 0 else w


def _fwd_kernel(u_ref, dl_ref, a_ref, b_ref, c_ref, y_ref, hs_ref, h_ref):
    n_state, tb = a_ref.shape[0], u_ref.shape[0]

    @pl.when(pl.program_id(2) == 0)
    def _():
        h_ref[...] = jnp.zeros_like(h_ref)

    hs_ref[...] = h_ref[...]

    def step(j, h):
        dt = dl_ref[j]
        x = dt * u_ref[j]
        y = jnp.zeros_like(dt)
        new = []
        for n in range(n_state):
            hn = jnp.exp(dt * a_ref[n]) * h[n] + x * b_ref[j, n]
            y = y + hn * c_ref[j, n]
            new.append(hn)
        y_ref[j] = y
        return tuple(new)

    h = jax.lax.fori_loop(0, tb, step,
                          tuple(h_ref[n] for n in range(n_state)))
    for n in range(n_state):
        h_ref[n] = h[n]


def _bwd_kernel(u_ref, dl_ref, a_ref, b_ref, c_ref, hs_ref, dy_ref,
                du_ref, ddl_ref, da_ref, db_ref, dc_ref, dh_ref, st_ref):
    n_state, tb = a_ref.shape[0], u_ref.shape[0]

    @pl.when(pl.program_id(2) == 0)
    def _():
        dh_ref[...] = jnp.zeros_like(dh_ref)
        da_ref[...] = jnp.zeros_like(da_ref)

    # the block's states, h_j after step j, from the state entering it
    def forward(j, h):
        dt = dl_ref[j]
        x = dt * u_ref[j]
        new = tuple(jnp.exp(dt * a_ref[n]) * h[n] + x * b_ref[j, n]
                    for n in range(n_state))
        for n in range(n_state):
            st_ref[j, n] = new[n]
        return new

    jax.lax.fori_loop(0, tb, forward,
                      tuple(hs_ref[n] for n in range(n_state)))

    def backward(i, dh):
        j = tb - 1 - i
        dt, u, dy = dl_ref[j], u_ref[j], dy_ref[j]
        x = dt * u
        du = jnp.zeros_like(dt)
        ddl = jnp.zeros_like(dt)
        new = []
        for n in range(n_state):
            prev = jnp.where(j > 0, st_ref[jnp.maximum(j - 1, 0), n],
                             hs_ref[n])
            a = a_ref[n]
            e = jnp.exp(dt * a)
            bn = b_ref[j, n]
            dc_ref[j, n] = jnp.sum(dy * st_ref[j, n])
            g = dh[n] + dy * c_ref[j, n]          # dL/dh_j
            db_ref[j, n] = jnp.sum(g * x)
            du = du + g * bn
            ddl = ddl + g * (a * e * prev)
            da_ref[n] += g * dt * e * prev
            new.append(g * e)
        du_ref[j] = du * dt
        ddl_ref[j] = ddl + du * u
        return tuple(new)

    dh = jax.lax.fori_loop(0, tb, backward,
                           tuple(dh_ref[n] for n in range(n_state)))
    for n in range(n_state):
        dh_ref[n] = dh[n]


def _layout(u, delta, a, b, c, tb):
    """Pad time to whole blocks and view channels as (8, W) tiles."""
    bsz, s, di = u.shape
    w, _ = _tiles(di)
    sp = -(-s // tb) * tb

    def pad(x):
        return jnp.pad(x, ((0, 0), (0, sp - s), (0, 0))) if sp != s else x

    return (pad(u).reshape(bsz, sp, 8, w), pad(delta).reshape(bsz, sp, 8, w),
            a.reshape(a.shape[0], 8, w), pad(b), pad(c))


def _specs(n_state, tw, tb, n_time, reverse):
    t_of = (lambda t: n_time - 1 - t) if reverse else (lambda t: t)
    seq = pl.BlockSpec((None, tb, 8, tw), lambda i, d, t: (i, t_of(t), 0, d))
    state = pl.BlockSpec((n_state, 8, tw), lambda i, d, t: (0, 0, d))
    scal = pl.BlockSpec((None, tb, n_state), lambda i, d, t: (i, t_of(t), 0),
                        memory_space=pltpu.SMEM)
    edge = pl.BlockSpec((None, None, n_state, 8, tw),
                        lambda i, d, t: (i, t_of(t), 0, 0, d))
    return seq, state, scal, edge


def _forward(u, delta, a, b, c, tb, interpret):
    bsz, s, di = u.shape
    n_state = a.shape[0]
    w, tw = _tiles(di)
    ut, dt, at, bt, ct = _layout(u, delta, a, b, c, tb)
    sp = ut.shape[1]
    n_time = sp // tb
    seq, state, scal, edge = _specs(n_state, tw, tb, n_time, False)
    y, hs = pl.pallas_call(
        _fwd_kernel,
        grid=(bsz, w // tw, n_time),
        in_specs=[seq, seq, state, scal, scal],
        out_specs=[seq, edge],
        out_shape=[jax.ShapeDtypeStruct((bsz, sp, 8, w), jnp.float32),
                   jax.ShapeDtypeStruct((bsz, n_time, n_state, 8, w),
                                        jnp.float32)],
        scratch_shapes=[pltpu.VMEM((n_state, 8, tw), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="selective_scan_fwd",
    )(ut, dt, at, bt, ct)
    return y.reshape(bsz, sp, di)[:, :s], hs


def _backward(u, delta, a, b, c, hs, dy, tb, interpret):
    bsz, s, di = u.shape
    n_state = a.shape[0]
    w, tw = _tiles(di)
    ut, dt, at, bt, ct = _layout(u, delta, a, b, c, tb)
    sp = ut.shape[1]
    n_time = sp // tb
    n_blk = w // tw
    dyt = _layout(dy, dy, a, b, c, tb)[0]
    seq, state, scal, edge = _specs(n_state, tw, tb, n_time, True)
    part = pl.BlockSpec((None, None, tb, n_state),
                        lambda i, d, t: (i, d, n_time - 1 - t, 0),
                        memory_space=pltpu.SMEM)
    acc = pl.BlockSpec((None, n_state, 8, tw), lambda i, d, t: (i, 0, 0, d))
    du, ddl, da, db, dc = pl.pallas_call(
        _bwd_kernel,
        grid=(bsz, n_blk, n_time),
        in_specs=[seq, seq, state, scal, scal, edge, seq],
        out_specs=[seq, seq, acc, part, part],
        out_shape=[jax.ShapeDtypeStruct((bsz, sp, 8, w), jnp.float32),
                   jax.ShapeDtypeStruct((bsz, sp, 8, w), jnp.float32),
                   jax.ShapeDtypeStruct((bsz, n_state, 8, w), jnp.float32),
                   jax.ShapeDtypeStruct((bsz, n_blk, sp, n_state),
                                        jnp.float32),
                   jax.ShapeDtypeStruct((bsz, n_blk, sp, n_state),
                                        jnp.float32)],
        scratch_shapes=[pltpu.VMEM((n_state, 8, tw), jnp.float32),
                        pltpu.VMEM((tb, n_state, 8, tw), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="selective_scan_bwd",
    )(ut, dt, at, bt, ct, hs, dyt)
    return (du.reshape(bsz, sp, di)[:, :s], ddl.reshape(bsz, sp, di)[:, :s],
            jnp.sum(da, axis=0).reshape(n_state, di),
            jnp.sum(db, axis=1)[:, :s], jnp.sum(dc, axis=1)[:, :s])


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def scan_pallas(u, delta, a, b, c, time_block: int = TIME_BLOCK,
                interpret: bool = False):
    """``y_t = C_t · h_t`` of the recurrence above.  u, delta (B, S, di)
    and b, c (B, S, N) float32; ``a`` (N, di) is ``A`` transposed (its
    entries negative).  Returns y (B, S, di) float32."""
    return _forward(u, delta, a, b, c, time_block, interpret)[0]


def _scan_fwd(u, delta, a, b, c, time_block, interpret):
    y, hs = _forward(u, delta, a, b, c, time_block, interpret)
    return y, (u, delta, a, b, c, hs)


def _scan_bwd(time_block, interpret, res, dy):
    u, delta, a, b, c, hs = res
    return _backward(u, delta, a, b, c, hs, dy, time_block, interpret)


scan_pallas.defvjp(_scan_fwd, _scan_bwd)
