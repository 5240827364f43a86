"""Kernel microbenchmarks: Pallas (interpret on CPU — correctness-path
timing, NOT TPU perf) vs the pure-jnp oracle (XLA:CPU compiled).

On TPU the Pallas kernels compile via Mosaic; here the numbers only show the
harness works end-to-end and give the oracle a CPU reference point.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.common import time_us
from repro.kernels import ops, ref
from repro.kernels.cluster_agg import mixing_matrix


def main():
    rows = []
    key = jax.random.PRNGKey(0)

    protos = jax.random.normal(key, (20, 512))
    rows.append(("pearson_pallas_20x512", time_us(ops.pearson, protos),
                 "m=20 D=512 interpret"))
    rows.append(("pearson_ref_20x512",
                 time_us(jax.jit(ref.pearson_ref), protos), "oracle xla:cpu"))

    flat = jax.random.normal(key, (20, 65536))
    labels = jax.random.randint(key, (20,), 0, 5)
    mix = mixing_matrix(labels, 5)
    rows.append(("cluster_agg_pallas_20x64k",
                 time_us(lambda: ops.cluster_aggregate(flat, labels, 5)),
                 "interpret"))
    rows.append(("cluster_agg_ref_20x64k",
                 time_us(jax.jit(ref.cluster_agg_ref), flat, mix),
                 "oracle xla:cpu"))

    bits = jax.random.bits(key, (100, 8192), dtype=jnp.uint32)
    from repro.kernels.fingerprint import poly_weights
    fw = jnp.asarray(poly_weights(8192))
    rows.append(("fingerprint_pallas_100x8k",
                 time_us(ops.fingerprint, bits, iters=2),
                 "interpret (slow: python kernel body)"))
    rows.append(("fingerprint_ref_100x8k",
                 time_us(jax.jit(ref.fingerprint_ref), bits, fw),
                 "oracle xla:cpu"))

    q = jax.random.normal(key, (1, 512, 4, 64), jnp.float32)
    k = jax.random.normal(key, (1, 512, 2, 64), jnp.float32)
    rows.append(("flash_attn_pallas_512", time_us(
        lambda: ops.attention(q, k, k, causal=True)), "interpret"))
    rows.append(("flash_attn_ref_512", time_us(
        jax.jit(lambda a, b, c: ref.attention_ref(a, b, c, causal=True)),
        q, k, k), "oracle xla:cpu"))

    r = jax.random.normal(key, (1, 2, 128, 32))
    w = jax.nn.sigmoid(jax.random.normal(key, (1, 2, 128, 32))) * 0.4 + 0.55
    u = jax.random.normal(key, (2, 32)) * 0.1
    s0 = jnp.zeros((1, 2, 32, 32))
    rows.append(("rwkv6_scan_pallas_T128", time_us(
        lambda: ops.rwkv6_wkv(r, r, r, w, u, s0)), "interpret"))
    rows.append(("rwkv6_scan_ref_T128", time_us(
        jax.jit(ref.rwkv6_scan_ref), r, r, r, w, u, s0), "oracle xla:cpu"))

    for name, us, derived in rows:
        print(f"kernel,{name},{us:.1f},{derived}", flush=True)
    return rows


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main()
