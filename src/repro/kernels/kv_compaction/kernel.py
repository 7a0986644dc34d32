"""KV-pool compaction — the paper's GC on TPU.

Re-packs scattered KV-cache blocks into logical (sequential) order: the
Pallas analogue of Nezha's sorted-ValueLog rebuild.  The block table rides in
scalar-prefetch SMEM and drives the INPUT BlockSpec index map; the output is
written with an identity map, so after one pass the pool is contiguous and
decode attention streams at full HBM bandwidth instead of block-granular
gathers.  Pure data movement — zero FLOPs, one read + one write per byte.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _compact_kernel(table_ref, pool_ref, out_ref):
    out_ref[0, 0] = pool_ref[0, 0]


def compact_kv_pool_pallas(pool, table, *, interpret: bool = False):
    """pool: (B, nblk, bs, C); table: (B, nblk). Returns logical-order pool."""
    B, nblk, bs, C = pool.shape

    def in_index(b, i, table_ref):
        return b, table_ref[b, i], 0, 0

    def out_index(b, i, table_ref):
        return b, i, 0, 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, nblk),
        in_specs=[pl.BlockSpec((1, 1, bs, C), in_index)],
        out_specs=pl.BlockSpec((1, 1, bs, C), out_index),
    )
    return pl.pallas_call(
        _compact_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(table, pool)
