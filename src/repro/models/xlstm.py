"""xLSTM blocks (arXiv:2405.04517): mLSTM (matrix memory, chunkwise-parallel)
and sLSTM (scalar memory, strictly sequential scan with exponential-gating
stabilizer).  Recurrent decode paths carry O(1) state per sequence — these are
the sub-quadratic archs.

mLSTM is implemented chunkwise (same segsum machinery as SSD): per-head scalar
forget decay (log-sigmoid, hence stable cumulative sums) + exp input gate
(clamped), matrix state C:(p,p) and normalizer n:(p,) carried across chunks.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import dense_init, rmsnorm

ICLAMP = 8.0


# ================================================================== mLSTM
def _mdims(cfg):
    di = 2 * cfg.d_model
    nh = cfg.n_heads
    return di, nh, di // nh


def mlstm_init(key, cfg):
    d = cfg.d_model
    di, nh, hd = _mdims(cfg)
    dt = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 7)
    return {
        "in_proj": dense_init(ks[0], (d, 2 * di), dt),
        "wq": dense_init(ks[1], (di, di), dt),
        "wk": dense_init(ks[2], (di, di), dt),
        "wv": dense_init(ks[3], (di, di), dt),
        "w_if": dense_init(ks[4], (di, 2 * nh), jnp.float32),
        "b_if": jnp.zeros((2 * nh,), jnp.float32),
        "head_norm_scale": jnp.ones((di,), dt),
        "out_proj": dense_init(ks[5], (di, d), dt, fan_in=di),
    }


def init_mlstm_cache(cfg, batch: int, dtype=None):
    di, nh, hd = _mdims(cfg)
    return {
        "C": jnp.zeros((batch, nh, hd, hd), jnp.float32),
        "n": jnp.zeros((batch, nh, hd), jnp.float32),
    }


def _mlstm_chunked(q, k, v, logf, logi, chunk, C0=None, n0=None):
    """q,k,v: (b,s,h,p) f32; logf<=0, logi: (b,s,h). Returns y, (C,n)."""
    b, s, h, p = q.shape
    Q = min(chunk, s)
    nc = s // Q
    qc = q.reshape(b, nc, Q, h, p)
    kc = k.reshape(b, nc, Q, h, p)
    vc = v.reshape(b, nc, Q, h, p)
    lf = logf.reshape(b, nc, Q, h)
    li = logi.reshape(b, nc, Q, h)
    cf = jnp.cumsum(lf, axis=2)
    # intra-chunk: D[t,j] = exp(cf[t]-cf[j]+li[j]) causal
    diff = cf[:, :, :, None, :] - cf[:, :, None, :, :] + li[:, :, None, :, :]
    tril = jnp.tril(jnp.ones((Q, Q), bool))
    D = jnp.where(tril[None, None, :, :, None], jnp.exp(diff), 0.0)
    qk = jnp.einsum("bcqhp,bckhp->bcqkh", qc, kc)
    w = qk * D                                          # (b,nc,Q,Q,h)
    y_in = jnp.einsum("bcqkh,bckhp->bcqhp", w, vc)
    den_in = jnp.sum(w, axis=3)                         # (b,nc,Q,h)
    # chunk states
    decay_end = jnp.exp(cf[:, :, -1:, :] - cf + li)     # (b,nc,Q,h)
    C_chunk = jnp.einsum("bckh,bckhp,bckhr->bchpr", decay_end, kc, vc)
    n_chunk = jnp.einsum("bckh,bckhp->bchp", decay_end, kc)
    cdecay = jnp.exp(cf[:, :, -1, :])                   # (b,nc,h)

    def scanf(carry, inp):
        C, n = carry
        Cc, nc_, dec = inp
        C2 = C * dec[..., None, None] + Cc
        n2 = n * dec[..., None] + nc_
        return (C2, n2), (C, n)

    C0 = C0 if C0 is not None else jnp.zeros((b, h, p, p), jnp.float32)
    n0 = n0 if n0 is not None else jnp.zeros((b, h, p), jnp.float32)
    (Cf, nf), (C_in, n_in) = jax.lax.scan(
        scanf, (C0, n0),
        (jnp.moveaxis(C_chunk, 1, 0), jnp.moveaxis(n_chunk, 1, 0),
         jnp.moveaxis(cdecay, 1, 0)))
    g = jnp.exp(cf)                                     # (b,nc,Q,h)
    y_off = jnp.einsum("bcqhp,cbhpr->bcqhr", qc, C_in) * g[..., None]
    den_off = jnp.einsum("bcqhp,cbhp->bcqh", qc, n_in) * g
    den = jnp.maximum(jnp.abs(den_in + den_off), 1.0)[..., None]
    y = (y_in + y_off) / den
    return y.reshape(b, s, h, p), (Cf, nf)


def mlstm_apply(params, x, cfg, rules, *, mode="train", cache=None, pos=None):
    B, S, d = x.shape
    di, nh, hd = _mdims(cfg)
    xz = x @ params["in_proj"]
    xm, z = xz[..., :di], xz[..., di:]
    q = (xm @ params["wq"]).reshape(B, S, nh, hd).astype(jnp.float32)
    k = (xm @ params["wk"]).reshape(B, S, nh, hd).astype(jnp.float32) * hd ** -0.5
    v = (xm @ params["wv"]).reshape(B, S, nh, hd).astype(jnp.float32)
    gates = xm.astype(jnp.float32) @ params["w_if"] + params["b_if"]
    logi = jnp.clip(gates[..., :nh], -ICLAMP, ICLAMP)
    logf = jax.nn.log_sigmoid(gates[..., nh:])
    if mode == "decode":
        f = jnp.exp(logf[:, 0])                         # (B,nh)
        i = jnp.exp(logi[:, 0])
        C = cache["C"] * f[..., None, None] + \
            i[..., None, None] * jnp.einsum("bhp,bhr->bhpr", k[:, 0], v[:, 0])
        n = cache["n"] * f[..., None] + i[..., None] * k[:, 0]
        num = jnp.einsum("bhp,bhpr->bhr", q[:, 0], C)
        den = jnp.maximum(jnp.abs(jnp.einsum("bhp,bhp->bh", q[:, 0], n)), 1.0)
        y = (num / den[..., None])[:, None]             # (B,1,nh,hd)
        new_cache = {"C": C, "n": n}
    else:
        C0 = n0 = None
        if mode == "prefill" and cache is not None:
            C0, n0 = cache["C"], cache["n"]
        y, (Cf, nf) = _mlstm_chunked(q, k, v, logf, logi, chunk=128,
                                     C0=C0, n0=n0)
        new_cache = {"C": Cf, "n": nf} if mode == "prefill" else None
    y = y.reshape(B, -1, di).astype(x.dtype)
    y = rmsnorm({"scale": params["head_norm_scale"]}, y, cfg.norm_eps)
    y = y * jax.nn.silu(z)
    return y @ params["out_proj"], new_cache


# ================================================================== sLSTM
def _sdims(cfg):
    nh = cfg.n_heads
    return nh, cfg.d_model // nh


def slstm_init(key, cfg):
    d = cfg.d_model
    nh, hd = _sdims(cfg)
    dt = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 4)
    ffd = int(4 * d / 3)
    return {
        "w_gates": dense_init(ks[0], (d, 4 * d), jnp.float32),
        "r_gates": dense_init(ks[1], (nh, hd, 4 * hd), jnp.float32, fan_in=hd),
        "b_gates": jnp.zeros((4 * d,), jnp.float32),
        "gn_scale": jnp.ones((d,), dt),
        "ff_wi": dense_init(ks[2], (d, ffd), dt),
        "ff_wo": dense_init(ks[3], (ffd, d), dt, fan_in=ffd),
    }


def init_slstm_cache(cfg, batch: int, dtype=None):
    nh, hd = _sdims(cfg)
    z = jnp.zeros((batch, nh, hd), jnp.float32)
    return {"c": z, "n": z, "h": z, "m": jnp.zeros((batch, nh), jnp.float32)}


def _slstm_cell(state, wx, r_gates, nh, hd):
    """One timestep. wx: (B, 4d) input preactivations."""
    c, n, h, m = state["c"], state["n"], state["h"], state["m"]
    rx = jnp.einsum("bhp,hpq->bhq", h, r_gates)          # (B,nh,4hd)
    pre = wx.reshape(wx.shape[0], nh, 4 * hd) + rx
    zt = jnp.tanh(pre[..., :hd])
    it = pre[..., hd:2 * hd]
    ft = pre[..., 2 * hd:3 * hd]
    ot = jax.nn.sigmoid(pre[..., 3 * hd:])
    # exponential gating with stabilizer (per head: use max over head dim)
    logf = jax.nn.log_sigmoid(ft)
    m_new = jnp.maximum(logf.max(-1) + m, it.max(-1))    # (B,nh)
    i_p = jnp.exp(jnp.clip(it - m_new[..., None], -ICLAMP, ICLAMP))
    f_p = jnp.exp(jnp.clip(logf + (m - m_new)[..., None], -ICLAMP, ICLAMP))
    c2 = f_p * c + i_p * zt
    n2 = f_p * n + i_p
    h2 = ot * c2 / jnp.maximum(jnp.abs(n2), 1.0)
    return {"c": c2, "n": n2, "h": h2, "m": m_new}


def slstm_apply(params, x, cfg, rules, *, mode="train", cache=None, pos=None):
    B, S, d = x.shape
    nh, hd = _sdims(cfg)
    wx = x.astype(jnp.float32) @ params["w_gates"] + params["b_gates"]
    state = cache if cache is not None else init_slstm_cache(cfg, B)
    if mode == "decode":
        state = _slstm_cell(state, wx[:, 0], params["r_gates"], nh, hd)
        y = state["h"][:, None].reshape(B, 1, d)
        new_cache = state
    else:
        def body(st, wxt):
            st2 = _slstm_cell(st, wxt, params["r_gates"], nh, hd)
            return st2, st2["h"]
        state_f, hs = jax.lax.scan(body, state, jnp.moveaxis(wx, 1, 0))
        y = jnp.moveaxis(hs, 0, 1).reshape(B, S, d)
        new_cache = state_f if mode == "prefill" else None
    y = rmsnorm({"scale": params["gn_scale"]}, y.astype(x.dtype), cfg.norm_eps)
    y = y + jax.nn.gelu(y @ params["ff_wi"]) @ params["ff_wo"]
    return y, new_cache
