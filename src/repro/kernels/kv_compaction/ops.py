"""jit'd dispatch for KV-pool compaction."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.kv_compaction.kernel import compact_kv_pool_pallas
from repro.kernels.kv_compaction.ref import compact_kv_pool_ref

# the caller's choice, never inferred: the compiled TPU kernel, the same
# kernel interpreted (CPU validation), or the pure-jnp oracle
BACKENDS = ("pallas", "pallas_interpret", "reference")


@functools.partial(jax.jit, static_argnames=("backend",))
def compact_kv_pool(pool, table, *, backend: str):
    """Returns (compacted_pool, identity_table)."""
    if backend not in BACKENDS:
        raise ValueError(f"backend {backend!r} is not one of {BACKENDS}")
    if backend == "reference":
        out = compact_kv_pool_ref(pool, table)
    else:
        out = compact_kv_pool_pallas(pool, table,
                                     interpret=(backend == "pallas_interpret"))
    B, nblk = table.shape
    ident = jnp.tile(jnp.arange(nblk, dtype=table.dtype)[None], (B, 1))
    return out, ident
