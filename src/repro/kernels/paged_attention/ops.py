"""jit'd dispatch for paged decode attention."""
from __future__ import annotations

import functools

import jax

from repro.kernels.paged_attention.kernel import paged_decode_attention_pallas
from repro.kernels.paged_attention.ref import paged_decode_attention_ref


@functools.partial(jax.jit, static_argnames=("backend",))
def paged_decode_attention(q, pool_k, pool_v, table, length, *,
                           backend: str):
    if backend == "reference":
        return paged_decode_attention_ref(q, pool_k, pool_v, table, length)
    return paged_decode_attention_pallas(
        q, pool_k, pool_v, table, length,
        interpret=(backend == "pallas_interpret"))
