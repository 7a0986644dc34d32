"""Core layer primitives: norms, RoPE, MLPs, embeddings, initializers.

Pure-functional JAX: parameters are pytrees of jnp arrays, every layer is
``apply(params, x, ...) -> y``.  All matmul-bearing ops keep activations in the
config dtype (bf16 by default) with reductions in f32 where it matters.
"""
from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

PyTree = Any


def _dtype(cfg):
    return jnp.dtype(cfg.param_dtype)


def dense_init(key, shape, dtype, fan_in=None):
    fan_in = fan_in if fan_in is not None else shape[0]
    std = 1.0 / math.sqrt(fan_in)
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


# ----------------------------------------------------------------- RMSNorm
def rmsnorm_init(d, dtype):
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm(params, x, eps):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * params["scale"].astype(jnp.float32)).astype(x.dtype)


# -------------------------------------------------------------------- RoPE
def rope_freqs(head_dim: int, theta: float) -> jnp.ndarray:
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: (..., S, H, hd); positions: broadcastable to (..., S)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                       # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    cos = jnp.cos(angles)[..., None, :]                 # (..., S, 1, hd/2)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    y1 = x1 * cos - x2 * sin
    y2 = x2 * cos + x1 * sin
    return jnp.concatenate([y1, y2], axis=-1).astype(x.dtype)


# --------------------------------------------------------------------- MLP
def mlp_init(key, cfg):
    d, ff, dt = cfg.d_model, cfg.d_ff, _dtype(cfg)
    ks = jax.random.split(key, 3)
    if cfg.mlp_kind == "swiglu":
        return {"wi": dense_init(ks[0], (d, ff), dt),
                "wg": dense_init(ks[1], (d, ff), dt),
                "wo": dense_init(ks[2], (ff, d), dt, fan_in=ff)}
    return {"wi": dense_init(ks[0], (d, ff), dt),
            "wo": dense_init(ks[2], (ff, d), dt, fan_in=ff)}


def mlp(params, x, cfg):
    if cfg.mlp_kind == "swiglu":
        h = jax.nn.silu(x @ params["wg"]) * (x @ params["wi"])
    else:
        h = jax.nn.gelu(x @ params["wi"])
    return h @ params["wo"]


# -------------------------------------------------------------- Embeddings
def embed_init(key, cfg):
    dt = _dtype(cfg)
    p: Dict[str, Any] = {}
    if cfg.input_kind == "tokens":
        # drawn at 1/embedding_multiplier of the usual scale, so that the
        # scaled embedding starts at it (a tied head then does not favour
        # the input token by the multiplier)
        p["embedding"] = dense_init(key, (cfg.vocab_size, cfg.d_model), dt,
                                    fan_in=cfg.d_model
                                    * cfg.embedding_multiplier ** 2)
    if not cfg.tie_embeddings:
        p["unembed"] = dense_init(jax.random.fold_in(key, 1),
                                  (cfg.d_model, cfg.vocab_size), dt)
    return p


def embed(params, tokens_or_embeds, cfg):
    if cfg.input_kind == "tokens":
        return jnp.take(params["embedding"], tokens_or_embeds, axis=0)
    return tokens_or_embeds.astype(_dtype(cfg))


def unembed(params, x, cfg):
    if cfg.tie_embeddings and cfg.input_kind == "tokens":
        w = params["embedding"].T
    else:
        w = params["unembed"]
    return x @ w
