"""jit'd dispatch for paged decode attention and the token write."""
from __future__ import annotations

import functools
from typing import Optional

import jax

from repro.kernels.paged_attention.kernel import (
    paged_decode_attention_pallas, write_token_pallas)
from repro.kernels.paged_attention.ref import (paged_decode_attention_ref,
                                               write_token_ref)

# the compiled TPU kernels, the same kernels interpreted (CPU validation),
# or the pure-jnp oracles
BACKENDS = ("pallas", "pallas_interpret", "reference")


def resolve_backend(backend: Optional[str]) -> str:
    """`backend`, or where None the default for the platform JAX runs on:
    the compiled kernels on a TPU, the jnp oracles elsewhere."""
    if backend is None:
        return "pallas" if jax.default_backend() == "tpu" else "reference"
    if backend not in BACKENDS:
        raise ValueError(f"backend {backend!r} is not one of {BACKENDS}")
    return backend


@functools.partial(jax.jit, static_argnames=("backend", "scale"))
def paged_decode_attention(q, pool_k, pool_v, table, lengths, layer, *,
                           backend: Optional[str] = None,
                           scale: Optional[float] = None):
    """Softmax scale `scale`, hd ** -0.5 where None; backend as in
    resolve_backend."""
    backend = resolve_backend(backend)
    if backend == "reference":
        return paged_decode_attention_ref(q, pool_k, pool_v, table, lengths,
                                          layer, scale)
    return paged_decode_attention_pallas(
        q, pool_k, pool_v, table, lengths, layer, scale=scale,
        interpret=(backend == "pallas_interpret"))


@functools.partial(jax.jit, static_argnames=("backend",))
def write_token(pool_k, pool_v, k_new, v_new, blk, row, layer, *,
                backend: Optional[str] = None):
    """Returns (pool_k, pool_v) with each sequence's token written at
    (layer, b, blk[b], row[b]); blk[b] out of range (nblk) writes nothing.
    Backend as in resolve_backend."""
    backend = resolve_backend(backend)
    if backend == "reference":
        return write_token_ref(pool_k, pool_v, k_new, v_new, blk, row, layer)
    return write_token_pallas(pool_k, pool_v, k_new, v_new, blk, row, layer,
                              interpret=(backend == "pallas_interpret"))
