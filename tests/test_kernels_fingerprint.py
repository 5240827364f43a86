"""Batched model-fingerprint kernel: interpret-mode vs jnp oracle parity,
padding neutrality, digest sensitivity, and decision-parity of the
fingerprint-based commitment pipeline against the legacy `hash_params`
verification on tampered cohorts."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType

import repro.kernels.fingerprint as fp
from repro.blockchain import Blockchain, Transaction, TxPool, hash_params
from repro.kernels.fingerprint import (
    cohort_digests,
    fingerprint_pallas,
    fingerprint_rows_sharded,
    format_digest,
    poly_weights,
    stack_flatten_u32,
)
from repro.kernels.ref import fingerprint_ref


@pytest.mark.parametrize("m,n", [(4, 256), (8, 1024), (5, 131), (3, 2049),
                                 (17, 6500), (1, 128)])
def test_interpret_matches_ref_oracle(m, n, rng):
    """Pallas interpret mode == jnp oracle, bit-exact, aligned and not."""
    x = jnp.asarray(rng.integers(0, 2**32, size=(m, n), dtype=np.uint32))
    ref = np.asarray(fingerprint_ref(x, jnp.asarray(poly_weights(n))))
    pal = np.asarray(fingerprint_pallas(x, interpret=True))
    np.testing.assert_array_equal(ref, pal)


@pytest.mark.parametrize("fill", ["all_ones", "high_bit"])
@pytest.mark.parametrize("m,n", [(13, 1000), (3, 6570)])
def test_interpret_matches_ref_when_accumulators_wrap(fill, m, n, rng):
    """Every product and lane sum wraps mod 2^32: rows of 0xFFFFFFFF, and
    random values with the high bit set, over row counts the 8-row block
    must pad."""
    if fill == "all_ones":
        x = np.full((m, n), 0xFFFFFFFF, dtype=np.uint32)
    else:
        x = rng.integers(0, 2**31, size=(m, n), dtype=np.uint32) \
            | np.uint32(0x80000000)
    x = jnp.asarray(x)
    ref = np.asarray(fingerprint_ref(x, jnp.asarray(poly_weights(n))))
    pal = np.asarray(fingerprint_pallas(x, interpret=True))
    np.testing.assert_array_equal(ref, pal)


def test_sharded_rows_match_ref_on_device_mesh(rng, monkeypatch):
    """The shard_map wrapper (kernel forced, interpret mode) over every
    local device == the oracle; a row count no device count divides."""
    monkeypatch.setattr(fp, "fingerprint_rows", functools.partial(
        fp.fingerprint_rows, use_pallas=True, interpret=True))
    mesh = jax.make_mesh((len(jax.devices()),), ("clients",),
                         axis_types=(AxisType.Auto,))
    x = jnp.asarray(rng.integers(0, 2**32, size=(13, 300), dtype=np.uint32))
    got = np.asarray(jax.jit(lambda v: fingerprint_rows_sharded(
        v, mesh, "clients"))(x))
    ref = np.asarray(fingerprint_ref(x, jnp.asarray(poly_weights(300))))
    np.testing.assert_array_equal(got, ref)


def test_non_aligned_padding_is_neutral(rng):
    """Zero-padding N to the block size must not change any digest: the
    padded columns multiply weights by mix(0) = 0."""
    x = rng.integers(0, 2**32, size=(4, 300), dtype=np.uint32)
    out = np.asarray(fingerprint_pallas(jnp.asarray(x), interpret=True,
                                        block_n=256))
    # manually pad to the next 256 multiple and compare the overlapping rows
    xp = np.pad(x, ((0, 0), (0, 512 - 300)))
    padded = np.asarray(fingerprint_ref(jnp.asarray(xp),
                                        jnp.asarray(poly_weights(512))))
    np.testing.assert_array_equal(out, padded)


def test_digest_sensitivity_and_length_binding():
    p = {"a": jnp.arange(12.0).reshape(3, 2, 2), "b": {"c": jnp.ones((3, 5))}}
    d = cohort_digests(p)
    assert len(set(d)) == 3                      # distinct rows -> distinct digests
    assert d == cohort_digests(p)                # deterministic
    p2 = {"a": jnp.asarray(p["a"]).at[1, 0, 0].add(1e-5), "b": p["b"]}
    d2 = cohort_digests(p2)
    assert d2[1] != d[1] and d2[0] == d[0] and d2[2] == d[2]
    # same values, zero-extended: the digest binds N, so no collision
    assert cohort_digests({"a": jnp.zeros((2, 4))}) != \
        cohort_digests({"a": jnp.zeros((2, 8))})


def test_pallas_pipeline_matches_default():
    """cohort_digests(use_pallas=True, interpret=True) == jnp default."""
    k = jax.random.PRNGKey(0)
    p = {"w": jax.random.normal(k, (6, 33, 7)),
         "b": jax.random.normal(k, (6, 19))}
    assert cohort_digests(p, use_pallas=True, interpret=True) == cohort_digests(p)


def test_stack_flatten_path_sorted_and_exact():
    """Leaf order is canonical (path-sorted) and the bit pattern is exact."""
    a = jnp.asarray([[1.5, -2.25]])
    b = jnp.asarray([[3.0]])
    f1 = np.asarray(stack_flatten_u32({"x": a, "y": b}))
    f2 = np.asarray(stack_flatten_u32({"y": b, "x": a}))
    np.testing.assert_array_equal(f1, f2)
    np.testing.assert_array_equal(
        f1[0], np.array([1.5, -2.25, 3.0], np.float32).view(np.uint32))


def _verify_decisions_legacy(local_params, tamper):
    """The retired host-side pipeline: per-client hash_params + set-membership
    agg_hash (identity binding aside, tamper decisions should coincide)."""
    m = jax.tree.leaves(local_params)[0].shape[0]
    chain, pool = Blockchain(), TxPool()
    honest = []
    for slot in range(m):
        own = jax.tree.map(lambda x: x[slot], local_params)
        claimed = tamper.get(slot, own)
        pool.submit(Transaction("model_hash", slot, hash_params(claimed), 0))
        honest.append(hash_params(own))
    import json
    pool.submit(Transaction("agg_hash", 0, json.dumps(sorted(honest)), 0))
    return chain.verify_round(chain.pack_block(0, 0, pool), m)


def _verify_decisions_fingerprint(local_params, tamper):
    from repro.blockchain import AGG_COMMIT_KIND, RoundCommitments
    from repro.core.round import digest_of
    m = jax.tree.leaves(local_params)[0].shape[0]
    digests = cohort_digests(local_params)
    chain, pool = Blockchain(), TxPool()
    for slot in range(m):
        claimed = digest_of(tamper[slot]) if slot in tamper else digests[slot]
        pool.submit(Transaction("model_hash", slot, claimed, 0))
    commits = RoundCommitments(0, tuple(enumerate(digests)))
    pool.submit(Transaction(AGG_COMMIT_KIND, 0, commits.to_payload(), 0))
    return chain.verify_round(chain.pack_block(0, 0, pool), m)


def test_tamper_decisions_match_hash_params_pipeline():
    """Fingerprint commitments reproduce the hash_params-based verification
    decisions exactly — tampered clients rejected, honest accepted."""
    ks = jax.random.split(jax.random.PRNGKey(7), 8)
    local = {"w": jnp.stack([jax.random.normal(k, (5, 3)) for k in ks]),
             "b": jnp.stack([jax.random.normal(k, (4,)) for k in ks])}
    fake = {"w": jnp.zeros((5, 3)), "b": jnp.ones((4,))}
    tamper = {2: fake, 5: jax.tree.map(lambda x: x + 1.0, fake)}
    legacy = _verify_decisions_legacy(local, tamper)
    bound = _verify_decisions_fingerprint(local, tamper)
    expected = np.array([i not in tamper for i in range(8)])
    np.testing.assert_array_equal(legacy, expected)
    np.testing.assert_array_equal(bound, expected)


def test_format_digest_stable():
    assert format_digest(np.array([1, 2], np.uint32), 9) == \
        "000000010000000200000009"
