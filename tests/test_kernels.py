"""Pallas kernel validation: shape/dtype sweeps, interpret=True vs the
pure-jnp oracle in each kernel's ref.py, plus hypothesis property tests on
the paged/compaction invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import HealthCheck, given, settings, strategies as st
except ImportError:  # container lacks hypothesis: seeded fallback
    from repro.testing.minihyp import (HealthCheck, given, settings,
                                       strategies as st)

from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.kv_compaction.ops import compact_kv_pool
from repro.kernels.kv_compaction.ref import compact_kv_pool_ref
from repro.kernels.paged_attention.ops import paged_decode_attention

KEY = jax.random.PRNGKey(0)


def tol(dt):
    return dict(rtol=2e-2, atol=2e-2) if dt == jnp.bfloat16 else \
        dict(rtol=2e-5, atol=2e-5)


FLASH_SWEEP = [
    # (B, nh, nkv, S, hd, dtype, bq, bk)
    (2, 4, 2, 256, 64, jnp.float32, 128, 128),
    (1, 8, 8, 512, 128, jnp.bfloat16, 256, 128),
    (2, 6, 2, 128, 64, jnp.bfloat16, 128, 128),
    (1, 2, 1, 384, 64, jnp.float32, 128, 128),
    (3, 4, 4, 128, 256, jnp.float32, 64, 64),
    (1, 9, 3, 256, 64, jnp.bfloat16, 128, 64),   # smollm-style 9/3 heads
]


@pytest.mark.parametrize("B,nh,nkv,S,hd,dt,bq,bk", FLASH_SWEEP)
def test_flash_attention_sweep(B, nh, nkv, S, hd, dt, bq, bk):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, nh, S, hd), dt)
    k = jax.random.normal(ks[1], (B, nkv, S, hd), dt)
    v = jax.random.normal(ks[2], (B, nkv, S, hd), dt)
    ref = flash_attention(q, k, v, backend="reference")
    out = flash_attention(q, k, v, backend="pallas_interpret",
                          block_q=bq, block_k=bk)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **tol(dt))


def test_flash_attention_non_causal():
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (1, 4, 256, 64), jnp.float32)
    k = jax.random.normal(ks[1], (1, 4, 256, 64), jnp.float32)
    v = jax.random.normal(ks[2], (1, 4, 256, 64), jnp.float32)
    ref = flash_attention(q, k, v, causal=False, backend="reference")
    out = flash_attention(q, k, v, causal=False,
                          backend="pallas_interpret", block_q=128,
                          block_k=128)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


PAGED_SWEEP = [
    # (B, nh, nkv, nblk, bs, hd, dtype)
    (2, 8, 2, 8, 16, 64, jnp.float32),
    (3, 4, 4, 4, 32, 128, jnp.bfloat16),
    (1, 16, 8, 16, 8, 64, jnp.bfloat16),
    (4, 2, 2, 2, 64, 128, jnp.float32),
]


@pytest.mark.parametrize("B,nh,nkv,nblk,bs,hd,dt", PAGED_SWEEP)
def test_paged_attention_sweep(B, nh, nkv, nblk, bs, hd, dt):
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (B, nh, hd), dt)
    pk = jax.random.normal(ks[1], (B, nblk, bs, nkv, hd), dt)
    pv = jax.random.normal(ks[2], (B, nblk, bs, nkv, hd), dt)
    table = jnp.stack([
        jax.random.permutation(jax.random.fold_in(ks[3], b), nblk)
        for b in range(B)]).astype(jnp.int32)
    length = jnp.array([max(1, nblk * bs - 5)] + [nblk * bs] * (B - 1),
                       jnp.int32)
    ref = paged_decode_attention(q, pk, pv, table, length,
                                 backend="reference")
    out = paged_decode_attention(q, pk, pv, table, length,
                                 backend="pallas_interpret")
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **tol(dt))


@settings(max_examples=20, deadline=None,
          suppress_health_check=list(HealthCheck))
@given(B=st.integers(1, 3), nblk=st.integers(1, 8),
       bs=st.sampled_from([8, 16]), C=st.sampled_from([32, 64]),
       seed=st.integers(0, 1000))
def test_compaction_is_permutation_inverse(B, nblk, bs, C, seed):
    """Property: compaction output at logical block i == input at table[i];
    compacting an identity table is a no-op."""
    k = jax.random.PRNGKey(seed)
    pool = jax.random.normal(k, (B, nblk, bs, C), jnp.float32)
    table = jnp.stack([
        jax.random.permutation(jax.random.fold_in(k, b), nblk)
        for b in range(B)]).astype(jnp.int32)
    out, ident = compact_kv_pool(pool, table, backend="pallas_interpret")
    ref = compact_kv_pool_ref(pool, table)
    assert np.array_equal(np.asarray(out), np.asarray(ref))
    out2, _ = compact_kv_pool(out, ident, backend="pallas_interpret")
    assert np.array_equal(np.asarray(out2), np.asarray(out))


def test_paged_attention_invariant_under_compaction():
    """Attention(q, pool, table) == Attention(q, compact(pool), identity) —
    the kernel-level statement of the paper's GC correctness."""
    ks = jax.random.split(KEY, 4)
    B, nh, nkv, nblk, bs, hd = 2, 4, 2, 8, 16, 64
    q = jax.random.normal(ks[0], (B, nh, hd), jnp.float32)
    pk = jax.random.normal(ks[1], (B, nblk, bs, nkv, hd), jnp.float32)
    pv = jax.random.normal(ks[2], (B, nblk, bs, nkv, hd), jnp.float32)
    table = jnp.stack([
        jax.random.permutation(jax.random.fold_in(ks[3], b), nblk)
        for b in range(B)]).astype(jnp.int32)
    length = jnp.full((B,), nblk * bs, jnp.int32)
    before = paged_decode_attention(q, pk, pv, table, length,
                                    backend="pallas_interpret")
    ck, ident = compact_kv_pool(pk.reshape(B, nblk, bs, -1), table,
                                backend="pallas_interpret")
    cv, _ = compact_kv_pool(pv.reshape(B, nblk, bs, -1), table,
                            backend="pallas_interpret")
    after = paged_decode_attention(
        q, ck.reshape(pk.shape), cv.reshape(pv.shape), ident, length,
        backend="pallas_interpret")
    np.testing.assert_allclose(np.asarray(before), np.asarray(after),
                               rtol=1e-6, atol=1e-6)


def test_compaction_rejects_an_unknown_backend():
    pool = jnp.zeros((1, 2, 8, 32), jnp.float32)
    table = jnp.zeros((1, 2), jnp.int32)
    with pytest.raises(ValueError, match="not one of"):
        compact_kv_pool(pool, table, backend="pallas_compiled")
