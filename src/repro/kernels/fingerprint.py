"""Pallas TPU kernel: batched per-client model fingerprints.

The BFLN commitment layer (Fig. 1 steps 2/5/6) needs one digest per cohort
member per round.  The original ``hash_params`` path pulled every full model
to the host (`O(cohort · N_params)` bytes, a Python loop of `device_get` +
SHA-256) — the dominant host cost of ``repro.sim`` at 1000-client
populations.  This kernel computes all digests on device in one streamed
pass and ships `O(cohort)` digest bytes instead.

Scheme — a blocked Rabin-style polynomial fingerprint over the raw bit
pattern of the stacked-flattened cohort params ``V`` (shape (m, N) uint32,
one row per client):

    A_i = Σ_j mix(V[i, j]) · r^(j+1)      (mod 2^32)
    B_i = Σ_j mix(V[i, j]) · r^(2(j+1))   (mod 2^32)

with ``r`` a fixed odd base and ``mix(v) = v ^ (v >> 16)`` (a bijection
folding high bits into low ones — float32 bit patterns of smooth params
share long trailing-zero runs that a bare weighted sum would propagate
into the residues); the per-client digest is the pair ``(A_i, B_i)`` plus
the length ``N`` (so zero-extension cannot collide).  ``B`` is
the same polynomial at base ``r²`` — two independent 32-bit residues from a
single streamed weight row.  Weights are precomputed once per ``N`` (natural
uint32 wraparound) and streamed through VMEM alongside the data, so the
kernel is a pure VPU multiply-accumulate:

    grid (m_tiles, n_tiles); each program owns a (BM, 128) lane accumulator
    per base, multiplies its whole (BM, BN) data tile by each (1, BN) weight
    row, and folds the products into the accumulator with BN//128 elementwise
    uint32 adds of 128-lane slices.  No reduction op appears in the kernel
    body (Mosaic refuses reductions over unsigned integers), and the weight
    rows are broadcast whole, at lane offset 0, never as lane slices.  The
    final 128-lane fold is exact because r^j already encodes the lane offset
    (j = 128·t + l), so cross-lane combination is plain modular addition —
    done in jnp on the tiny (m, 128) output.

On a device mesh (:func:`fingerprint_rows_sharded`) the kernel runs under
``jax.shard_map`` over the client axis: each device fingerprints the rows
it holds.  Mosaic kernels cannot be partitioned automatically, and each
row's residues depend only on that row.

Zero padding of the N axis is neutral by construction (0 · w = 0), so
non-aligned N needs no masking.  This is a *fingerprint* (tamper-evidence
for the simulated chain, linear over GF-style residues), not a
cryptographic hash; sender binding and Merkle commitment live in
``repro.blockchain.commit``.

Oracle: ``repro.kernels.ref.fingerprint_ref`` (bit-identical — integer
arithmetic is exact, so kernel, interpret mode and oracle all agree).
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.sharding import PartitionSpec as P

from repro.runtime.arena import ArenaLayout

Pytree = Any

# Odd base (from MurmurHash3's c1); order mod 2^32 divides 2^30 — weights
# cycle only past N ≈ 10^9, far beyond any stacked model here.
FINGERPRINT_BASE = np.uint32(0x85EBCA77)


@functools.lru_cache(maxsize=8)
def poly_weights(n: int, base: int = int(FINGERPRINT_BASE)) -> np.ndarray:
    """(2, n) uint32: rows ``r^(j+1)`` and ``r^(2(j+1))`` mod 2^32."""
    with np.errstate(over="ignore"):
        w1 = np.cumprod(np.full((n,), np.uint32(base), dtype=np.uint32))
        w2 = w1 * w1
    return np.stack([w1, w2])


def stack_flatten_u32(stacked_params: Pytree) -> jax.Array:
    """Stacked pytree (leading client axis) -> (m, N) uint32 bit matrix.

    Leaves are raveled per client in canonical (path-sorted) order and
    bitcast so the fingerprint sees exact bit patterns.  Delegates to the
    shared :class:`repro.runtime.arena.ArenaLayout` so fingerprinting,
    cluster aggregation and the round engine all use ONE leaf layout.
    """
    return ArenaLayout.from_stacked(stacked_params).flatten_u32(stacked_params)


def _fingerprint_kernel(x_ref, w_ref, out_ref, *, bn: int):
    """x (BM, BN) uint32; w (2, BN); out (BM, 256) lane accumulators
    (lanes 0:128 base r, lanes 128:256 base r²), revisited across the
    n-tile grid axis."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    x = x_ref[...]
    x = x ^ (x >> 16)                  # bit mix; mix(0) == 0 keeps padding neutral
    p1 = x * w_ref[0:1, :]             # (BM, BN), wraps mod 2^32
    p2 = x * w_ref[1:2, :]
    acc1, acc2 = p1[:, :128], p2[:, :128]
    for t in range(1, bn // 128):
        lanes = slice(128 * t, 128 * (t + 1))
        acc1 = acc1 + p1[:, lanes]
        acc2 = acc2 + p2[:, lanes]
    out_ref[:, :128] += acc1
    out_ref[:, 128:] += acc2


def fingerprint_pallas(flat_u32: jax.Array, *, block_m: int = 8,
                       block_n: int = 2048,
                       interpret: bool = False) -> jax.Array:
    """(m, N) uint32 -> (m, 2) uint32 per-client polynomial residues."""
    m, n = flat_u32.shape
    mp = -(-m // block_m) * block_m
    bn = min(block_n, -(-n // 128) * 128)
    np_ = -(-n // bn) * bn
    x = flat_u32
    if np_ != n:
        x = jnp.pad(x, ((0, 0), (0, np_ - n)))      # zero pad: weight-neutral
    if mp != m:
        x = jnp.pad(x, ((0, mp - m), (0, 0)))
    w = jnp.asarray(poly_weights(np_))

    lanes = pl.pallas_call(
        functools.partial(_fingerprint_kernel, bn=bn),
        grid=(mp // block_m, np_ // bn),
        in_specs=[
            pl.BlockSpec((block_m, bn), lambda i, j: (i, j)),
            pl.BlockSpec((2, bn), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_m, 256), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((mp, 256), jnp.uint32),
        interpret=interpret,
    )(x, w)
    # exact cross-lane fold (modular addition commutes)
    return jnp.stack([jnp.sum(lanes[:m, :128], axis=1, dtype=jnp.uint32),
                      jnp.sum(lanes[:m, 128:], axis=1, dtype=jnp.uint32)],
                     axis=1)


def fingerprint_rows(flat_u32: jax.Array, *, use_pallas: bool | None = None,
                     interpret: bool = False) -> jax.Array:
    """(m, N) uint32 bit matrix -> (m, 2) residues, jit-safe.

    The arena fast path: the fused round engine bitcasts its (already flat)
    parameter rows and calls this inside ONE jitted program — no re-stacking,
    no extra flatten.  ``use_pallas=None`` auto-selects the Mosaic kernel on
    accelerators and the bit-identical jnp oracle on CPU.
    """
    if use_pallas is None:
        use_pallas = jax.default_backend() != "cpu"
    if use_pallas:
        return fingerprint_pallas(flat_u32, interpret=interpret)
    from repro.kernels.ref import fingerprint_ref
    return fingerprint_ref(flat_u32,
                           jnp.asarray(poly_weights(flat_u32.shape[1])))


def fingerprint_rows_sharded(flat_u32: jax.Array, mesh, axis: str
                             ) -> jax.Array:
    """:func:`fingerprint_rows` on a device mesh, jit-safe: under
    ``jax.shard_map`` over ``axis`` each device fingerprints the rows it
    holds (Mosaic kernels cannot be partitioned automatically).

    Rows are zero-padded to a multiple of the axis size — a zero row's
    residues are sliced away — so a replicated input works as well as a
    row-sharded one."""
    m = flat_u32.shape[0]
    pad = -m % mesh.shape[axis]
    if pad:
        flat_u32 = jnp.pad(flat_u32, ((0, pad), (0, 0)))
    # check_vma=False: pallas_call's output carries no varying-axes type,
    # and a P(axis) output declares no replication for the check to prove
    per_device = jax.shard_map(fingerprint_rows, mesh=mesh, in_specs=P(axis),
                               out_specs=P(axis), check_vma=False)
    return per_device(flat_u32)[:m]


@jax.jit
def _digest_pipeline(stacked_params: Pytree) -> jax.Array:
    flat = stack_flatten_u32(stacked_params)
    return fingerprint_rows(flat, use_pallas=False)


def format_digest(residues, n_params: int) -> str:
    """(2,) uint32 residues + length -> canonical digest string."""
    a, b = (int(v) & 0xFFFFFFFF for v in residues)
    return f"{a:08x}{b:08x}{n_params:08x}"


def cohort_digests(stacked_params: Pytree, *, use_pallas: bool | None = None,
                   interpret: bool = False) -> list[str]:
    """Per-client digest strings for a cohort-stacked pytree — ONE jitted
    device program + an `O(cohort)` host transfer (2 uint32 per client).

    ``use_pallas=None`` auto-selects: the Mosaic kernel on accelerators, the
    bit-identical jnp oracle on CPU (integer math is exact, so digests never
    depend on the path taken).  Tests force ``use_pallas=True`` with
    ``interpret=True`` to validate the kernel body on CPU.
    """
    n_params = int(sum(int(np.prod(x.shape[1:]))
                       for x in jax.tree.leaves(stacked_params)))
    if use_pallas is None:
        use_pallas = jax.default_backend() != "cpu"
    if use_pallas:
        flat = jax.jit(stack_flatten_u32)(stacked_params)
        res = fingerprint_pallas(flat, interpret=interpret)
    else:
        res = _digest_pipeline(stacked_params)
    res = np.asarray(jax.device_get(res))
    return [format_digest(row, n_params) for row in res]
