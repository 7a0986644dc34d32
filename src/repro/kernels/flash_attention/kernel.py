"""Causal GQA flash attention — Pallas TPU kernel.

Tiling: grid (B*nh, Sq/Bq, Skv/Bk); the kv dimension is innermost and
"arbitrary" (sequential) so the online-softmax state (m, l, acc) lives in
VMEM scratch across kv steps.  GQA is handled in the K/V BlockSpec index
maps (q-head -> kv-head division) — no materialized head repeat, KV is read
once per q tile.  MXU-aligned tiles: Bq, Bk multiples of 128 where the
sequence allows; head_dim padded to the lane width by the caller if needed.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_sc, l_sc, acc_sc, *,
                  block_q: int, block_k: int, causal: bool, scale: float,
                  n_kv_blocks: int):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    q_start = qi * block_q
    k_start = kj * block_k
    # causal: skip blocks strictly above the diagonal
    run = (not causal) or (k_start <= q_start + block_q - 1)

    @pl.when(run)
    def _step():
        q = q_ref[0].astype(jnp.float32)                 # (Bq, hd)
        k = k_ref[0].astype(jnp.float32)                 # (Bk, hd)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                                      (block_q, block_k), 0)
            kpos = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                      (block_q, block_k), 1)
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        m_prev = m_sc[...]
        l_prev = l_sc[...]
        m_cur = jnp.max(s, axis=1)[:, None]              # (Bq, 1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                           # (Bq, Bk)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=1)[:, None]
        acc_sc[...] = acc_sc[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_sc[...] = m_new
        l_sc[...] = l_new

    @pl.when(kj == n_kv_blocks - 1)
    def _finalize():
        o_ref[0] = (acc_sc[...] /
                    jnp.maximum(l_sc[...], 1e-30)).astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, *, causal: bool = True,
                           block_q: int = 512, block_k: int = 512,
                           interpret: bool = False):
    """q: (B, nh, S, hd); k, v: (B, nkv, S, hd)."""
    B, nh, Sq, hd = q.shape
    nkv, Skv = k.shape[1], k.shape[2]
    rep = nh // nkv
    block_q = min(block_q, Sq)
    block_k = min(block_k, Skv)
    assert Sq % block_q == 0 and Skv % block_k == 0
    nq, nk = Sq // block_q, Skv // block_k
    qf = q.reshape(B * nh, Sq, hd)
    kf = k.reshape(B * nkv, Skv, hd)
    vf = v.reshape(B * nkv, Skv, hd)

    def kv_index(bh, i, j):
        return (bh // nh) * nkv + (bh % nh) // rep, j, 0

    kernel = functools.partial(
        _flash_kernel, block_q=block_q, block_k=block_k, causal=causal,
        scale=hd ** -0.5, n_kv_blocks=nk)
    out = pl.pallas_call(
        kernel,
        grid=(B * nh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, block_k, hd), kv_index),
            pl.BlockSpec((1, block_k, hd), kv_index),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), lambda bh, i, j: (bh, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * nh, Sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(B, nh, Sq, hd)
