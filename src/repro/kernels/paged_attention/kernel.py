"""Paged decode attention — Pallas TPU kernel with block-table indirection.

This is the paper's KV-separation read path on TPU (DESIGN.md §2): the block
table (the lightweight key->offset index) rides in scalar-prefetch SMEM and
*drives the BlockSpec index maps*, so each KV block is DMA'd from wherever it
physically lives in the HBM pool ("scattered ValueLog") straight into VMEM.
After compaction (kv_compaction kernel) the table is the identity and the
same kernel streams contiguously — the TPU analogue of Nezha's sorted
ValueLog restoring sequential reads.

Grid: (B, nkv, nblk); online softmax per (batch, kv-head) with rep q-heads
processed together (rows of an MXU tile).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _paged_kernel(lengths_ref, table_ref, q_ref, k_ref, v_ref, o_ref,
                  m_sc, l_sc, acc_sc, *, block_size: int, n_blocks: int,
                  scale: float):
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    start = j * block_size
    length = lengths_ref[b]

    @pl.when(start < length)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32)              # (rep, hd)
        k = k_ref[0, 0, :, 0].astype(jnp.float32)        # (bs, hd)
        v = v_ref[0, 0, :, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        pos = start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(pos < length, s, NEG_INF)
        m_prev, l_prev = m_sc[...], l_sc[...]
        m_cur = jnp.max(s, axis=1)[:, None]
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_sc[...] = alpha * l_prev + jnp.sum(p, axis=1)[:, None]
        acc_sc[...] = acc_sc[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_sc[...] = m_new

    @pl.when(j == n_blocks - 1)
    def _finalize():
        o_ref[0, 0] = (acc_sc[...] /
                       jnp.maximum(l_sc[...], 1e-30)).astype(o_ref.dtype)


def paged_decode_attention_pallas(q, pool_k, pool_v, table, length, *,
                                  interpret: bool = False):
    """q: (B, nh, hd); pool_k/v: (B, nblk, bs, nkv, hd); table: (B, nblk);
    length: (B,) int32 valid tokens per sequence."""
    B, nh, hd = q.shape
    nblk, bs, nkv = pool_k.shape[1], pool_k.shape[2], pool_k.shape[3]
    rep = nh // nkv
    qg = q.reshape(B, nkv, rep, hd)
    lengths = jnp.broadcast_to(jnp.asarray(length, jnp.int32), (B,))

    def q_index(b, h, j, lengths_ref, table_ref):
        return b, h, 0, 0

    def kv_index(b, h, j, lengths_ref, table_ref):
        return b, table_ref[b, j], 0, h, 0     # the indirection

    def o_index(b, h, j, lengths_ref, table_ref):
        return b, h, 0, 0

    kernel = functools.partial(_paged_kernel, block_size=bs, n_blocks=nblk,
                               scale=hd ** -0.5)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, nkv, nblk),
        in_specs=[
            pl.BlockSpec((1, 1, rep, hd), q_index),
            pl.BlockSpec((1, 1, bs, 1, hd), kv_index),
            pl.BlockSpec((1, 1, bs, 1, hd), kv_index),
        ],
        out_specs=pl.BlockSpec((1, 1, rep, hd), o_index),
        scratch_shapes=[
            pltpu.VMEM((rep, 1), jnp.float32),
            pltpu.VMEM((rep, 1), jnp.float32),
            pltpu.VMEM((rep, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, nkv, rep, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(lengths, table, qg, pool_k, pool_v)
    return out.reshape(B, nh, hd)
