"""Paged decode attention and the token write — Pallas TPU kernels that work
on the KV pool in place.

This is the paper's KV-separation read path on TPU (DESIGN.md §2): the block
table (the lightweight key->offset index) rides in scalar-prefetch SMEM and
drives the DMAs, so each live KV block is copied from wherever it physically
lives in the HBM pool ("scattered ValueLog") straight into VMEM.  After
compaction (kv_compaction kernel) the table is the identity and the same
kernel streams contiguously — the TPU analogue of Nezha's sorted ValueLog
restoring sequential reads.

Both kernels take the stacked pool of every layer, (layers, B, nblk, bs, nkv,
hd), and the layer as a scalar, so the model's layer loop carries the pool
as state and never slices a layer out of it or stacks one back:

  paged_decode_attention_pallas  grid (B,), one sequence a step; the live
      blocks of a sequence (ceil(length / bs) of them, none for length 0)
      are read whole, (bs, nkv, hd), through the table, GROUP blocks to a
      loop step with double-buffered DMAs (a last group short of GROUP
      reads its last live block again), the first group of the next live
      sequence fetched while the last of this one is used.  The rep query
      heads of each KV head are grouped inside the kernel: all nh heads
      meet all GROUP * bs * nkv rows in one matmul, and a row's scores
      outside its KV head's group are masked.  Softmax and accumulation in
      f32.
  write_token_pallas  the new token's K and V rows DMA'd into their block
      of the aliased pool; a sequence whose block is out of range (nblk)
      writes nothing.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
GROUP = 4           # blocks a loop step: 1.9 ms against 2.9 ms for one at a
                    # time, 9 layers of 555 live blocks on a TPU v5e


def _attn_kernel(layer_ref, lengths_ref, table_ref, next_ref, q_ref, k_hbm,
                 v_hbm, o_ref, k_buf, v_buf, sems, step_ref, *, bs: int,
                 nblk: int, nkv: int, scale: float):
    b = pl.program_id(0)
    n_seq = pl.num_programs(0)
    layer = layer_ref[0]

    def live_blocks(seq):
        return jnp.minimum((lengths_ref[seq] + bs - 1) // bs, nblk)

    def copies(seq, j, buf):
        """The DMAs of group j of sequence `seq` into buffer `buf`."""
        last = live_blocks(seq) - 1
        out = []
        for g in range(GROUP):
            blk = table_ref[seq * nblk + jnp.minimum(j * GROUP + g, last)]
            out += [pltpu.make_async_copy(k_hbm.at[layer, seq, blk],
                                          k_buf.at[buf, g], sems.at[0, buf]),
                    pltpu.make_async_copy(v_hbm.at[layer, seq, blk],
                                          v_buf.at[buf, g], sems.at[1, buf])]
        return out

    def start(seq, j, buf):
        for c in copies(seq, j, buf):
            c.start()

    @pl.when(b == 0)
    def _first():
        step_ref[0] = 0
        first = next_ref[0]

        @pl.when(first < n_seq)
        def _():
            start(first, 0, 0)

    length = lengths_ref[b]
    n = (live_blocks(b) + GROUP - 1) // GROUP            # loop steps
    step0 = step_ref[0]
    nxt = next_ref[b + 1]
    q = q_ref[0]                                         # (nh, hd)
    nh = q.shape[0]
    rows = GROUP * bs * nkv
    rep = nh // nkv
    col = jax.lax.broadcasted_iota(jnp.int32, (nh, rows), 1)
    head = jax.lax.broadcasted_iota(jnp.int32, (nh, rows), 0)
    in_group = (col % nkv) == (head // rep)
    token = col // nkv

    def body(j, carry):
        m, l, acc = carry
        buf = (step0 + j) % 2

        # prefetch: this sequence's next group, else the next live one's
        # first
        more = j + 1 < n

        @pl.when(jnp.logical_or(more, nxt < n_seq))
        def _():
            start(jnp.where(more, b, nxt), jnp.where(more, j + 1, 0), 1 - buf)

        for c in copies(b, j, buf):
            c.wait()
        k = k_buf[buf].astype(jnp.float32).reshape(rows, -1)
        v = v_buf[buf].astype(jnp.float32).reshape(rows, -1)
        s = jax.lax.dot_general(q, k.astype(q.dtype), (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = jnp.where(in_group & (token < length - j * GROUP * bs), s,
                      NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l = alpha * l + jnp.sum(p, axis=1, keepdims=True)
        acc = acc * alpha + jax.lax.dot_general(
            p.astype(v_buf.dtype), v.astype(v_buf.dtype),
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        return m_new, l, acc

    init = (jnp.full((nh, 1), NEG_INF, jnp.float32),
            jnp.zeros((nh, 1), jnp.float32),
            jnp.zeros(q.shape, jnp.float32))
    _, l, acc = jax.lax.fori_loop(0, n, body, init)
    step_ref[0] = step0 + n
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def _next_live(lengths):
    """(B + 1,) int32: entry i is the first sequence >= i with a nonzero
    length, or B where there is none."""
    B = lengths.shape[0]
    cand = jnp.where(lengths > 0, jnp.arange(B, dtype=jnp.int32), B)
    nxt = jax.lax.cummin(cand, reverse=True)
    return jnp.concatenate([nxt, jnp.full((1,), B, jnp.int32)])


def paged_decode_attention_pallas(q, pool_k, pool_v, table, lengths, layer,
                                  *, scale=None, interpret: bool = False):
    """q: (B, nh, hd); pool_k/v: (layers, B, nblk, bs, nkv, hd); table:
    (B, nblk) int32, logical -> physical block of `layer`; lengths: (B,)
    int32 valid tokens per sequence; layer: int32 scalar; scale: the
    softmax's (a compile-time constant), hd ** -0.5 where None.  Returns
    (B, nh, hd); a sequence of length 0 gives zeros."""
    B, nh, hd = q.shape
    nblk, bs, nkv = pool_k.shape[2], pool_k.shape[3], pool_k.shape[4]
    lengths = jnp.asarray(lengths, jnp.int32)
    scalars = (jnp.reshape(jnp.asarray(layer, jnp.int32), (1,)), lengths,
               table.reshape(-1).astype(jnp.int32), _next_live(lengths))
    kernel = functools.partial(_attn_kernel, bs=bs, nblk=nblk, nkv=nkv,
                               scale=hd ** -0.5 if scale is None else scale)
    seq = lambda b, *_: (b, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(B,),
        in_specs=[pl.BlockSpec((1, nh, hd), seq),
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, nh, hd), seq),
        scratch_shapes=[
            pltpu.VMEM((2, GROUP, bs, nkv, hd), pool_k.dtype),
            pltpu.VMEM((2, GROUP, bs, nkv, hd), pool_v.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((1,), jnp.int32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, nh, hd), q.dtype),
        # sequential: a step prefetches the next live sequence's first group
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_decode_attention",
    )(*scalars, q, pool_k, pool_v)


def _write_kernel(layer_ref, blk_ref, row_ref, k_new, v_new, pk_in, pv_in,
                  pk_out, pv_out, sems):
    del pk_in, pv_in                     # aliased to pk_out, pv_out
    layer = layer_ref[0]
    B, nblk = blk_ref.shape[0], pk_out.shape[2]

    def copies(b):
        dst = (layer, b, blk_ref[b], row_ref[b])
        return (pltpu.make_async_copy(k_new.at[b], pk_out.at[dst], sems.at[0]),
                pltpu.make_async_copy(v_new.at[b], pv_out.at[dst], sems.at[1]))

    def each_written(act):
        def body(b, carry):
            @pl.when(blk_ref[b] < nblk)
            def _():
                for c in copies(b):
                    act(c)
            return carry
        jax.lax.fori_loop(0, B, body, 0)

    each_written(lambda c: c.start())
    each_written(lambda c: c.wait())


def write_token_pallas(pool_k, pool_v, k_new, v_new, blk, row, layer, *,
                       interpret: bool = False):
    """pool_k/v: (layers, B, nblk, bs, nkv, hd), updated in place; k_new,
    v_new: (B, nkv, hd); blk, row: (B,) int32 physical block and row of each
    sequence's token, blk nblk (out of range) for one that writes nothing."""
    scalars = (jnp.reshape(jnp.asarray(layer, jnp.int32), (1,)),
               jnp.asarray(blk, jnp.int32), jnp.asarray(row, jnp.int32))
    anyspec = pl.BlockSpec(memory_space=pl.ANY)
    n = len(scalars)
    return pl.pallas_call(
        _write_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=n, grid=(1,), in_specs=[anyspec] * 4,
            out_specs=[anyspec] * 2,
            scratch_shapes=[pltpu.SemaphoreType.DMA((2,))]),
        out_shape=(jax.ShapeDtypeStruct(pool_k.shape, pool_k.dtype),
                   jax.ShapeDtypeStruct(pool_v.shape, pool_v.dtype)),
        input_output_aliases={n + 2: 0, n + 3: 1},
        interpret=interpret,
        name="paged_kv_write",
    )(*scalars, k_new.astype(pool_k.dtype), v_new.astype(pool_v.dtype),
      pool_k, pool_v)
