"""jit'd dispatch wrapper: pallas (TPU), pallas-interpret (CPU validation),
or the pure-jnp reference."""
from __future__ import annotations

import functools

import jax

from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.flash_attention.ref import flash_attention_ref


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "backend"))
def flash_attention(q, k, v, *, causal: bool = True, block_q: int = 512,
                    block_k: int = 512, backend: str):
    if backend == "reference":
        return flash_attention_ref(q, k, v, causal=causal)
    return flash_attention_pallas(
        q, k, v, causal=causal, block_q=block_q, block_k=block_k,
        interpret=(backend == "pallas_interpret"))
