"""Pure-jnp oracles for paged decode attention (block-table gather) and the
token write, on the stacked pool of every layer."""
from __future__ import annotations

import jax.numpy as jnp

NEG_INF = -1e30


def paged_decode_attention_ref(q, pool_k, pool_v, table, lengths, layer,
                               scale=None):
    """q: (B, nh, hd); pool_k/v: (layers, B, nblk, bs, nkv, hd); table:
    (B, nblk) int32 (logical block -> physical block of `layer`); lengths:
    scalar or (B,) number of valid tokens; scale: the softmax's, hd ** -0.5
    where None.  Returns (B, nh, hd); a sequence of length 0 gives
    zeros."""
    B, nh, hd = q.shape
    nblk, bs, nkv = pool_k.shape[2], pool_k.shape[3], pool_k.shape[4]
    rep = nh // nkv
    tbl = table[..., None, None, None]
    k = jnp.take_along_axis(pool_k[layer], tbl, axis=1).reshape(
        B, nblk * bs, nkv, hd)
    v = jnp.take_along_axis(pool_v[layer], tbl, axis=1).reshape(
        B, nblk * bs, nkv, hd)
    qf = q.astype(jnp.float32).reshape(B, nkv, rep, hd)
    s = jnp.einsum("bgrd,bkgd->bgrk", qf, k.astype(jnp.float32)) * (
        hd ** -0.5 if scale is None else scale)
    lengths = jnp.broadcast_to(jnp.asarray(lengths), (B,))
    mask = jnp.arange(nblk * bs)[None] < lengths[:, None]      # (B, S)
    s = jnp.where(mask[:, None, None], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.where(mask[:, None, None], jnp.exp(s - m), 0.0)
    o = jnp.einsum("bgrk,bkgd->bgrd", p, v.astype(jnp.float32))
    o = o / jnp.maximum(p.sum(-1, keepdims=True), 1e-30)
    return o.reshape(B, nh, hd).astype(q.dtype)


def write_token_ref(pool_k, pool_v, k_new, v_new, blk, row, layer):
    """The token write as a scatter; a block out of range (nblk) writes
    nothing."""
    bi = jnp.arange(k_new.shape[0])
    pool_k = pool_k.at[layer, bi, blk, row].set(
        k_new.astype(pool_k.dtype), mode="drop")
    pool_v = pool_v.at[layer, bi, blk, row].set(
        v_new.astype(pool_v.dtype), mode="drop")
    return pool_k, pool_v
