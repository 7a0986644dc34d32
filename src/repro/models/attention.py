"""GQA attention: chunked-causal (train/prefill) + cached decode.

Two decode cache layouts, mirroring the paper's storage states (DESIGN.md §2):
  * ``paged``  — block pool + per-sequence block table (scattered ValueLog):
                 (B, n_blocks, block, n_kv, hd) with a logical->physical table.
                 Decode reads and writes it in place through
                 kernels/paged_attention; this is the serving engine's layout.
  * ``dense``  — contiguous cache (sorted ValueLog, i.e. post-GC/compaction):
                 (B, S, n_kv, hd).  Decode writes the token's row and attends
                 in jnp; it is the reference the paged decode is tested
                 against.

Train and prefill run the pure-jnp query-chunked causal attention (f32
logsumexp) on every platform; no Pallas kernel is substituted on a TPU.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.paged_attention import ops as paged_attention_ops
from repro.models.layers import apply_rope, dense_init, rmsnorm

NEG_INF = -1e30


def attn_init(key, cfg):
    d, nh, nkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    dt = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (d, nh * hd), dt),
        "wk": dense_init(ks[1], (d, nkv * hd), dt),
        "wv": dense_init(ks[2], (d, nkv * hd), dt),
        "wo_attn": dense_init(ks[3], (nh * hd, d), dt, fan_in=nh * hd),
    }
    if cfg.qkv_bias:
        p["wq_bias"] = jnp.zeros((nh * hd,), dt)
        p["wk_bias"] = jnp.zeros((nkv * hd,), dt)
        p["wv_bias"] = jnp.zeros((nkv * hd,), dt)
    if cfg.qk_norm:
        p["q_norm_scale"] = jnp.ones((hd,), dt)
        p["k_norm_scale"] = jnp.ones((hd,), dt)
    return p


def softmax_scale(cfg) -> float:
    return cfg.attn_scale if cfg.attn_scale is not None else cfg.hd ** -0.5


def _project_qkv(params, x, cfg, positions):
    B, S, _ = x.shape
    nh, nkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    q = x @ params["wq"]
    k = x @ params["wk"]
    v = x @ params["wv"]
    if cfg.qkv_bias:
        q = q + params["wq_bias"]
        k = k + params["wk_bias"]
        v = v + params["wv_bias"]
    q = q.reshape(B, S, nh, hd)
    k = k.reshape(B, S, nkv, hd)
    v = v.reshape(B, S, nkv, hd)
    if cfg.qk_norm:
        q = rmsnorm({"scale": params["q_norm_scale"]}, q, cfg.norm_eps)
        k = rmsnorm({"scale": params["k_norm_scale"]}, k, cfg.norm_eps)
    if not cfg.nope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _repeat_kv(k, rep, rules=None):
    """(B,S,nkv,hd) -> (B,S,nkv*rep,hd).  GQA heads are expanded BEFORE the
    score einsum so the full head axis (divisible by the model axis) carries
    the tensor-parallel sharding; a (nkv, rep) split reshape would break
    GSPMD propagation and silently replicate attention (observed: 16x compute
    + 245GiB temps on qwen2-72b before this fix)."""
    if rep == 1:
        return k
    k = jnp.repeat(k, rep, axis=2)
    if rules is not None:
        k = rules.constrain(k, "batch", None, "heads")
    return k


def _sdpa_chunk(qc, k, v, q_pos, kv_pos, scale):
    """One query chunk against full K/V. qc:(B,C,nh,hd) k/v:(B,S,nh,hd)."""
    s = jnp.einsum("bqhd,bkhd->bhqk", qc.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    mask = (kv_pos[None, :] <= q_pos[:, None])          # (C, S) causal
    s = jnp.where(mask[None, None], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - jax.lax.stop_gradient(jnp.maximum(m, NEG_INF)))
    denom = jnp.sum(p, axis=-1, keepdims=True)
    p = p / jnp.maximum(denom, 1e-30)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return o


def chunked_causal_attention(q, k, v, *, q_offset=0, chunk=512, rules=None,
                             scale=None):
    """q: (B,Sq,nh,hd); k,v: (B,Skv,nkv,hd). Returns (B,Sq,nh,hd); the
    softmax scale defaults to hd ** -0.5."""
    B, Sq, nh, hd = q.shape
    Skv, nkv = k.shape[1], k.shape[2]
    rep = nh // nkv
    scale = hd ** -0.5 if scale is None else scale
    k = _repeat_kv(k, rep, rules)
    v = _repeat_kv(v, rep, rules)
    kv_pos = jnp.arange(Skv)
    chunk = min(chunk, Sq)
    n_chunks = Sq // chunk
    if n_chunks <= 1:
        o = _sdpa_chunk(q, k, v, jnp.arange(Sq) + q_offset, kv_pos, scale)
        return o.astype(q.dtype)

    qg = q.reshape(B, n_chunks, chunk, nh, hd)

    @jax.checkpoint
    def body(carry, xs):
        qc, start = xs
        q_pos = start + jnp.arange(chunk) + q_offset
        o = _sdpa_chunk(qc, k, v, q_pos, kv_pos, scale)
        return carry, o

    starts = jnp.arange(n_chunks) * chunk
    _, o = jax.lax.scan(body, (), (jnp.moveaxis(qg, 1, 0), starts))
    o = jnp.moveaxis(o, 0, 1).reshape(B, Sq, nh, hd)
    return o.astype(q.dtype)


# ------------------------------------------------------------ cache layouts
def init_attn_cache(cfg, batch: int, max_seq: int, layout: str, dtype=None):
    nkv, hd, bs = cfg.n_kv_heads, cfg.hd, cfg.kv_block_size
    dt = dtype or jnp.dtype(cfg.param_dtype)
    if layout == "dense":
        return {
            "k": jnp.zeros((batch, max_seq, nkv, hd), dt),
            "v": jnp.zeros((batch, max_seq, nkv, hd), dt),
        }
    n_blk = max_seq // bs
    return {
        "pool_k": jnp.zeros((batch, n_blk, bs, nkv, hd), dt),
        "pool_v": jnp.zeros((batch, n_blk, bs, nkv, hd), dt),
        # logical block -> physical block (identity = fully compacted)
        "table": jnp.tile(jnp.arange(n_blk, dtype=jnp.int32)[None], (batch, 1)),
    }


def _decode_attend(q, k_all, v_all, pos, nh, scale):
    """q:(B,1,nh,hd) vs full cache (B,S,nkv,hd) masked to <=pos.

    Grouped (no KV repeat): the cache is read once.  The (nkv, rep) head
    split only touches q, which is tiny at decode."""
    B, S, nkv, hd = k_all.shape
    rep = nh // nkv
    qg = q.reshape(B, 1, nkv, rep, hd)
    s = jnp.einsum("bqgrd,bkgd->bgrqk", qg.astype(jnp.float32),
                   k_all.astype(jnp.float32)) * scale
    pos_b = jnp.broadcast_to(jnp.asarray(pos), (B,))    # per-seq positions
    mask = jnp.arange(S)[None, :] <= pos_b[:, None]     # (B, S)
    s = jnp.where(mask[:, None, None, None, :], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    o = jnp.einsum("bgrqk,bkgd->bqgrd", p, v_all.astype(jnp.float32))
    den = jnp.sum(p, axis=-1)[..., None].transpose(0, 3, 1, 2, 4)
    o = o / jnp.maximum(den, 1e-30)
    return o.reshape(B, 1, nh * hd)


def attn_decode(params, cache, x, pos, cfg):
    """One-token decode on the dense cache (k/v (B, S, nkv, hd)). x:(B,1,d);
    pos: scalar OR per-sequence (B,) index (continuous batching serves
    ragged sequences in one lockstep batch); a negative pos marks a free
    sequence, whose token is written nowhere."""
    B, S = cache["k"].shape[:2]
    pos_b = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    q, k_new, v_new = _project_qkv(params, x, cfg,
                                   jnp.maximum(pos_b, 0)[:, None])
    bi = jnp.arange(B)
    row = jnp.where(pos_b >= 0, pos_b, S)          # S: out of range, dropped
    k_all = cache["k"].at[bi, row].set(k_new[:, 0], mode="drop")
    v_all = cache["v"].at[bi, row].set(v_new[:, 0], mode="drop")
    o = _decode_attend(q, k_all, v_all, pos, cfg.n_heads, softmax_scale(cfg))
    out = o.astype(x.dtype) @ params["wo_attn"]
    return out, {"k": k_all, "v": v_all}


def _token_block(table, pos_b, bs):
    """Physical block and row of each sequence's token at pos_b through its
    table (B, nblk); a free (pos < 0) or full sequence gets block nblk,
    out of range, which a write leaves out."""
    nblk = table.shape[-1]
    logical = jnp.clip(pos_b // bs, 0, nblk - 1)
    blk = jnp.take_along_axis(table, logical[:, None], axis=1)[:, 0]
    live = (pos_b >= 0) & (pos_b < nblk * bs)
    return jnp.where(live, blk, nblk), pos_b % bs


def attn_decode_in_place(params, cache, x, pos, cfg, layer, kv_backend):
    """One-token paged decode on every layer's stacked cache (pool_k/pool_v
    (layers, B, nblk, bs, nkv, hd), table (layers, B, nblk)), at `layer`:
    the token's K/V are written into their block of the pool in place and
    attention reads each sequence's live blocks through its table
    (kernels/paged_attention, backend `kv_backend`; None: its default).
    Nothing of the pool is sliced out or copied.  pos as in attn_decode: a
    free sequence (pos < 0) writes nothing and reads nothing."""
    B = x.shape[0]
    bs = cfg.kv_block_size
    pos_b = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    q, k_new, v_new = _project_qkv(params, x, cfg,
                                   jnp.maximum(pos_b, 0)[:, None])
    table = cache["table"][layer]                               # (B, nblk)
    blk, row = _token_block(table, pos_b, bs)
    pool_k, pool_v = paged_attention_ops.write_token(
        cache["pool_k"], cache["pool_v"], k_new[:, 0], v_new[:, 0], blk, row,
        layer, backend=kv_backend)
    lengths = jnp.where(pos_b >= 0, pos_b + 1, 0)
    o = paged_attention_ops.paged_decode_attention(
        q[:, 0], pool_k, pool_v, table, lengths, layer, backend=kv_backend,
        scale=cfg.attn_scale)
    out = o.reshape(B, 1, -1).astype(x.dtype) @ params["wo_attn"]
    return out, dict(cache, pool_k=pool_k, pool_v=pool_v)


def attn_apply(params, x, cfg, rules, *, mode="train", cache=None, pos=None,
               chunk=512, layer=None, kv_backend=None):
    """Unified entry. Returns (out, new_cache).  Decode follows the cache's
    layout: a paged cache is every layer's stacked cache, read and written
    in place at `layer` (attn_decode_in_place, backend `kv_backend`); a
    dense one is this layer's (attn_decode).  Train and prefill run the
    chunked causal attention, under `rules` where given."""
    if mode == "decode":
        if "pool_k" in cache:
            return attn_decode_in_place(params, cache, x, pos, cfg, layer,
                                        kv_backend)
        return attn_decode(params, cache, x, pos, cfg)
    B, S, _ = x.shape
    positions = jnp.arange(S)[None]
    q, k, v = _project_qkv(params, x, cfg, positions)
    if rules is not None and cfg.attn_seq_parallel:
        # context parallelism: q rows sharded over `model`, K/V replicated;
        # each rank computes its strip of the score matrix (no head-count
        # divisibility requirement — see DESIGN.md §6 / EXPERIMENTS §Perf)
        q = rules.constrain(q, "batch", "act_seq", None, None)
        k = rules.constrain(k, "batch", None, None, None)
        v = rules.constrain(v, "batch", None, None, None)
        rep = cfg.n_heads // cfg.n_kv_heads
        o = _sdpa_chunk(q, _repeat_kv(k, rep), _repeat_kv(v, rep),
                        jnp.arange(S), jnp.arange(S), softmax_scale(cfg))
        o = o.astype(q.dtype)
    else:
        if rules is not None:
            q = rules.constrain(q, "batch", None, "heads")
            k = rules.constrain(k, "batch", None, "kv_heads")
            v = rules.constrain(v, "batch", None, "kv_heads")
        o = chunked_causal_attention(q, k, v, chunk=chunk, rules=rules,
                                     scale=softmax_scale(cfg))
    out = o.reshape(B, S, -1) @ params["wo_attn"]
    new_cache = None
    if mode == "prefill" and cache is not None:
        if "k" in cache:
            k_pad = jax.lax.dynamic_update_slice(cache["k"], k, (0, 0, 0, 0))
            v_pad = jax.lax.dynamic_update_slice(cache["v"], v, (0, 0, 0, 0))
            new_cache = {"k": k_pad, "v": v_pad}
        else:
            bs = cfg.kv_block_size
            kb = k.reshape(B, S // bs, bs, *k.shape[2:])
            vb = v.reshape(B, S // bs, bs, *v.shape[2:])
            # write THROUGH the block table (physical placement may be
            # scattered — the serving allocator owns the table)
            dest = cache["table"][:, :S // bs]               # (B, nwb)
            bi = jnp.arange(B)[:, None]
            pool_k = cache["pool_k"].at[bi, dest].set(kb)
            pool_v = cache["pool_v"].at[bi, dest].set(vb)
            new_cache = dict(cache, pool_k=pool_k, pool_v=pool_v)
    return out, new_cache
