"""Mamba-2 (SSD) block — chunked parallel scan for train/prefill, recurrent
state update for decode.  Follows the SSD formulation of arXiv:2405.21060
(single B/C group), adapted to TPU: all intra-chunk work is batched einsum
(MXU-friendly), the only sequential dependency is a length-``n_chunks``
``lax.scan`` over 128-token chunks.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.models.layers import dense_init, rmsnorm


def _dims(cfg):
    di = cfg.ssm_expand * cfg.d_model
    nh = di // cfg.ssm_head_dim
    return di, nh, cfg.ssm_state, cfg.ssm_conv


def mamba2_init(key, cfg):
    d = cfg.d_model
    di, nh, N, K = _dims(cfg)
    dt = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 4)
    conv_dim = di + 2 * N
    return {
        "in_proj": dense_init(ks[0], (d, 2 * di + 2 * N + nh), dt),
        "conv_w": dense_init(ks[1], (K, conv_dim), dt, fan_in=K),
        "conv_b": jnp.zeros((conv_dim,), dt),
        "A_log": jnp.zeros((nh,), jnp.float32),
        "D": jnp.ones((nh,), jnp.float32),
        "dt_bias": jnp.zeros((nh,), jnp.float32),
        "norm_scale": jnp.ones((di,), dt),
        "out_proj": dense_init(ks[3], (di, d), dt, fan_in=di),
    }


def _split_proj(params, x, cfg):
    di, nh, N, _ = _dims(cfg)
    zxbcdt = x @ params["in_proj"]
    z = zxbcdt[..., :di]
    xBC = zxbcdt[..., di:di + di + 2 * N]
    dt_raw = zxbcdt[..., -nh:]
    return z, xBC, dt_raw


def _causal_conv(xBC, params, K):
    """Depthwise causal conv along S. xBC: (B,S,C)."""
    pads = jnp.pad(xBC, ((0, 0), (K - 1, 0), (0, 0)))
    out = sum(pads[:, i:i + xBC.shape[1]] * params["conv_w"][i]
              for i in range(K))
    return jax.nn.silu(out + params["conv_b"])


def ssd_chunked(x, dt, A, Bm, Cm, chunk: int, init_state=None):
    """x:(b,s,h,p) dt:(b,s,h) A:(h,) Bm,Cm:(b,s,n). Returns y, final_state."""
    b, s, h, p = x.shape
    n = Bm.shape[-1]
    Q = min(chunk, s)
    nc = s // Q
    xf = x.astype(jnp.float32).reshape(b, nc, Q, h, p)
    dtc = dt.reshape(b, nc, Q, h)
    Bc = Bm.astype(jnp.float32).reshape(b, nc, Q, n)
    Cc = Cm.astype(jnp.float32).reshape(b, nc, Q, n)
    dA = dtc * A                                    # (b,nc,Q,h), A<0
    cum = jnp.cumsum(dA, axis=2)
    # intra-chunk
    diff = cum[:, :, :, None, :] - cum[:, :, None, :, :]   # (b,nc,Q,Q,h)
    tril = jnp.tril(jnp.ones((Q, Q), bool))
    L = jnp.where(tril[None, None, :, :, None], jnp.exp(diff), 0.0)
    scores = jnp.einsum("bcqn,bckn->bcqk", Cc, Bc)
    Y = jnp.einsum("bcqk,bcqkh,bckh,bckhp->bcqhp", scores, L, dtc, xf)
    # per-chunk input states
    decay_end = jnp.exp(cum[:, :, -1:, :] - cum)           # (b,nc,Q,h)
    states = jnp.einsum("bckn,bckh,bckhp->bchpn", Bc, decay_end * dtc, xf)
    chunk_decay = jnp.exp(cum[:, :, -1, :])                # (b,nc,h)

    def scanf(S_prev, inp):
        st, dec = inp                                      # (b,h,p,n), (b,h)
        S_new = S_prev * dec[..., None, None] + st
        return S_new, S_prev

    S0 = (init_state if init_state is not None
          else jnp.zeros((b, h, p, n), jnp.float32))
    S_final, S_in = jax.lax.scan(
        scanf, S0, (jnp.moveaxis(states, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)))
    Yoff = jnp.einsum("bcqn,cbhpn->bcqhp", Cc, S_in) * \
        jnp.exp(cum)[..., None]
    y = (Y + Yoff).reshape(b, s, h, p)
    return y.astype(x.dtype), S_final


def _conv_tail(xBC, K, lengths):
    """(B, K-1, C): the K-1 conv inputs before each sequence's length (the
    last K-1 where lengths is None), zeros before the sequence's start."""
    pads = jnp.pad(xBC, ((0, 0), (K - 1, 0), (0, 0)))
    if lengths is None:
        return pads[:, -(K - 1):]
    return jax.vmap(lambda p, n: jax.lax.dynamic_slice_in_dim(
        p, n, K - 1, axis=0))(pads, lengths)


def init_mamba_cache(cfg, batch: int, dtype=None):
    di, nh, N, K = _dims(cfg)
    dt = dtype or jnp.dtype(cfg.param_dtype)
    return {
        "ssm": jnp.zeros((batch, nh, cfg.ssm_head_dim, N), jnp.float32),
        "conv": jnp.zeros((batch, K - 1, di + 2 * N), dt),
    }


def mamba2_apply(params, x, cfg, rules, *, mode="train", cache=None, pos=None,
                 lengths=None):
    """x: (B,S,d) (train/prefill) or (B,1,d) (decode).  A prefill padded
    past each sequence's `lengths` (B,) leaves the state and conv tail at
    the length: dt is 0 past it, and the tail is the K-1 inputs before
    it."""
    di, nh, N, K = _dims(cfg)
    hp = cfg.ssm_head_dim
    B = x.shape[0]
    A = -jnp.exp(params["A_log"])
    if mode == "decode":
        z, xBC, dt_raw = _split_proj(params, x[:, 0], cfg)   # (B, ·)
        conv_buf = jnp.concatenate([cache["conv"], xBC[:, None]], axis=1)
        xBC_c = jax.nn.silu(
            jnp.einsum("bkc,kc->bc", conv_buf, params["conv_w"])
            + params["conv_b"])
        new_conv = conv_buf[:, 1:]
        xs = xBC_c[..., :di].reshape(B, nh, hp).astype(jnp.float32)
        Bm = xBC_c[..., di:di + N].astype(jnp.float32)
        Cm = xBC_c[..., di + N:].astype(jnp.float32)
        dtv = jax.nn.softplus(dt_raw.astype(jnp.float32) + params["dt_bias"])
        dA = jnp.exp(dtv * A)                                # (B,nh)
        S = cache["ssm"] * dA[..., None, None] + \
            jnp.einsum("bh,bn,bhp->bhpn", dtv, Bm, xs)
        y = jnp.einsum("bn,bhpn->bhp", Cm, S) + xs * params["D"][:, None]
        y = y.reshape(B, 1, di).astype(x.dtype)
        new_cache = {"ssm": S, "conv": new_conv}
        z = z[:, None]
    else:
        z, xBC, dt_raw = _split_proj(params, x, cfg)
        xBC_c = _causal_conv(xBC, params, K)
        xs = xBC_c[..., :di].reshape(B, x.shape[1], nh, hp)
        Bm = xBC_c[..., di:di + N]
        Cm = xBC_c[..., di + N:]
        dtv = jax.nn.softplus(dt_raw.astype(jnp.float32) + params["dt_bias"])
        if lengths is not None:
            live = jnp.arange(x.shape[1])[None, :] < lengths[:, None]
            dtv = jnp.where(live[..., None], dtv, 0.0)
        if rules is not None:
            xs = rules.constrain(xs, "batch", None, "heads")
        y, S_final = ssd_chunked(xs, dtv, A, Bm, Cm, chunk=128)
        y = y + xs.astype(jnp.float32) * params["D"][:, None]
        y = y.reshape(B, x.shape[1], di).astype(x.dtype)
        new_cache = None
        if mode == "prefill" and cache is not None:
            new_cache = {"ssm": S_final, "conv": _conv_tail(xBC, K, lengths)}
    y = rmsnorm({"scale": params["norm_scale"]}, y * jax.nn.silu(z), cfg.norm_eps)
    return y @ params["out_proj"], new_cache
