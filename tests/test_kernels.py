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
from repro.kernels.paged_attention.ops import (paged_decode_attention,
                                               write_token)

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
LAYERS = 2


def _paged_inputs(B, nh, nkv, nblk, bs, hd, dt, table="scrambled"):
    """q, the stacked K/V pools (LAYERS, B, nblk, bs, nkv, hd) and a block
    table, scrambled or the identity."""
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (B, nh, hd), dt)
    pk = jax.random.normal(ks[1], (LAYERS, B, nblk, bs, nkv, hd), dt)
    pv = jax.random.normal(ks[2], (LAYERS, B, nblk, bs, nkv, hd), dt)
    if table == "identity":
        tbl = jnp.tile(jnp.arange(nblk, dtype=jnp.int32)[None], (B, 1))
    else:
        tbl = jnp.stack([
            jax.random.permutation(jax.random.fold_in(ks[3], b), nblk)
            for b in range(B)]).astype(jnp.int32)
    return q, pk, pv, tbl


def _assert_kernel_matches_oracle(q, pk, pv, table, lengths, layer, dt):
    ref = paged_decode_attention(q, pk, pv, table, lengths, layer,
                                 backend="reference")
    out = paged_decode_attention(q, pk, pv, table, lengths, layer,
                                 backend="pallas_interpret")
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **tol(dt))
    return out


@pytest.mark.parametrize("B,nh,nkv,nblk,bs,hd,dt", PAGED_SWEEP)
def test_paged_attention_sweep(B, nh, nkv, nblk, bs, hd, dt):
    q, pk, pv, table = _paged_inputs(B, nh, nkv, nblk, bs, hd, dt)
    length = jnp.array([max(1, nblk * bs - 5)] + [nblk * bs] * (B - 1),
                       jnp.int32)
    _assert_kernel_matches_oracle(q, pk, pv, table, length, 1, dt)


# the serving cell's heads (Qwen3-8B): 32 query heads over 8 KV heads of
# 128, blocks of 64 tokens, bf16; lengths ragged: empty, one token, a block
# boundary either side, and full
CELL_HEADS = dict(nh=32, nkv=8, hd=128, bs=64, dt=jnp.bfloat16)
CELL_LENGTHS = [0, 1, 64, 65, 128, 4 * 64]


@pytest.mark.parametrize("layer", range(LAYERS))
@pytest.mark.parametrize("table", ["scrambled", "identity"])
def test_paged_attention_at_the_cells_heads(layer, table):
    B, nblk = len(CELL_LENGTHS), 4
    q, pk, pv, tbl = _paged_inputs(B, nblk=nblk, table=table, **CELL_HEADS)
    out = _assert_kernel_matches_oracle(
        q, pk, pv, tbl, jnp.array(CELL_LENGTHS, jnp.int32), layer,
        CELL_HEADS["dt"])
    assert not np.any(np.asarray(out[0], np.float32))   # length 0: zeros


@pytest.mark.parametrize("backend", ["pallas_interpret", "reference"])
def test_token_write_touches_only_its_rows(backend):
    """Each live sequence's K/V row lands at (layer, b, blk, row) and
    nothing else of the pool changes; a block out of range writes
    nothing."""
    B, nkv, nblk, bs, hd = 4, 2, 4, 8, 128
    _, pk, pv, table = _paged_inputs(B, 4, nkv, nblk, bs, hd, jnp.float32)
    k_new = jnp.full((B, nkv, hd), 7.0)
    v_new = -k_new
    blk = table[:, 2].at[1].set(nblk)
    row = jnp.array([0, 5, bs - 1, 3], jnp.int32)
    nk, nv = write_token(pk, pv, k_new, v_new, blk, row, 1, backend=backend)
    want_k, want_v = np.array(pk), np.array(pv)
    for b, (k, r) in enumerate(zip(np.asarray(blk), np.asarray(row))):
        if k < nblk:
            want_k[1, b, k, r] = 7.0
            want_v[1, b, k, r] = -7.0
    assert np.array_equal(np.asarray(nk), want_k)
    assert np.array_equal(np.asarray(nv), want_v)


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


@pytest.mark.parametrize("table", ["scrambled", "identity"])
def test_paged_attention_with_a_configured_scale(table):
    """granite-4.0-h's softmax scale, 1/128 at head size 128 (not
    128 ** -0.5): the kernel matches its oracle at that scale, and the
    oracle is plain softmax attention at it (f32: both within rounding)."""
    B, nblk, scale = len(CELL_LENGTHS), 4, 1 / 128
    heads = dict(CELL_HEADS, dt=jnp.float32)
    q, pk, pv, tbl = _paged_inputs(B, nblk=nblk, table=table, **heads)
    lengths = jnp.array(CELL_LENGTHS, jnp.int32)
    ref = paged_decode_attention(q, pk, pv, tbl, lengths, 1,
                                 backend="reference", scale=scale)
    out = paged_decode_attention(q, pk, pv, tbl, lengths, 1,
                                 backend="pallas_interpret", scale=scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               **tol(jnp.float32))
    rep = heads["nh"] // heads["nkv"]
    for b, n in enumerate(CELL_LENGTHS[1:], 1):
        k = np.asarray(pk[1, b])[np.asarray(tbl[b])].reshape(-1, 8, 128)[:n]
        v = np.asarray(pv[1, b])[np.asarray(tbl[b])].reshape(-1, 8, 128)[:n]
        k, v = np.repeat(k, rep, 1), np.repeat(v, rep, 1)
        s = np.einsum("hd,khd->hk", np.asarray(q[b]), k) * scale
        p = np.exp(s - s.max(-1, keepdims=True))
        want = np.einsum("hk,khd->hd", p / p.sum(-1, keepdims=True), v)
        np.testing.assert_allclose(np.asarray(ref[b]), want, rtol=1e-4,
                                   atol=1e-4)


def test_paged_attention_invariant_under_compaction():
    """Attention(q, pool, table) == Attention(q, compact(pool), identity) —
    the kernel-level statement of the paper's GC correctness."""
    B, nh, nkv, nblk, bs, hd = 2, 4, 2, 8, 16, 64
    q, pk, pv, table = _paged_inputs(B, nh, nkv, nblk, bs, hd, jnp.float32)
    length = jnp.full((B,), nblk * bs, jnp.int32)
    before = paged_decode_attention(q, pk, pv, table, length, 1,
                                    backend="pallas_interpret")
    flat = (LAYERS * B, nblk, bs, nkv * hd)
    flat_t = jnp.tile(table, (LAYERS, 1))
    ck, ident = compact_kv_pool(pk.reshape(flat), flat_t,
                                backend="pallas_interpret")
    cv, _ = compact_kv_pool(pv.reshape(flat), flat_t,
                            backend="pallas_interpret")
    after = paged_decode_attention(
        q, ck.reshape(pk.shape), cv.reshape(pv.shape), ident[:B], length, 1,
        backend="pallas_interpret")
    np.testing.assert_allclose(np.asarray(before), np.asarray(after),
                               rtol=1e-6, atol=1e-6)


def test_paged_attention_rejects_an_unknown_backend():
    q, pk, pv, table = _paged_inputs(1, 2, 2, 2, 8, 128, jnp.float32)
    with pytest.raises(ValueError, match="not one of"):
        paged_decode_attention(q, pk, pv, table, jnp.ones((1,), jnp.int32),
                               0, backend="pallas_compiled")


def test_compaction_rejects_an_unknown_backend():
    pool = jnp.zeros((1, 2, 8, 32), jnp.float32)
    table = jnp.zeros((1, 2), jnp.int32)
    with pytest.raises(ValueError, match="not one of"):
        compact_kv_pool(pool, table, backend="pallas_compiled")
