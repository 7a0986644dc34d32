"""Token-choice top-k Mixture-of-Experts FFN.

Expert parallelism over the ``model`` mesh axis via ``jax.shard_map``:
tokens stay sharded over (pod, data) and *replicated* over ``model``; each
model-rank owns E/model_size experts, dispatches locally (capacity-bounded
scatter), runs its expert GEMMs, scatters back, and the per-rank partial
outputs are psum-combined over ``model`` — the same collective volume as a
tensor-parallel MLP (one all-reduce of the token activations), with zero
cross-rank dispatch traffic.

For tiny token counts (decode) a dense no-drop path computes every expert and
masks, avoiding capacity drops on the serving path.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.models.layers import dense_init


def moe_init(key, cfg):
    d, ff, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    dt = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 4)
    return {
        "router": dense_init(ks[0], (d, E), jnp.float32),
        "experts_wi": dense_init(ks[1], (E, d, ff), dt),
        "experts_wg": dense_init(ks[2], (E, d, ff), dt),
        "experts_wo": dense_init(ks[3], (E, ff, d), dt, fan_in=ff),
    }


def _route(xt, router_w, top_k):
    logits = xt.astype(jnp.float32) @ router_w          # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    topv, topi = jax.lax.top_k(probs, top_k)            # (T, k)
    topv = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)
    return topv, topi, probs


def _expert_ffn(buf, wi, wg, wo):
    """buf: (E, C, d) -> (E, C, d) via per-expert SwiGLU."""
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, wg)) * \
        jnp.einsum("ecd,edf->ecf", buf, wi)
    return jnp.einsum("ecf,efd->ecd", h, wo)


def _moe_dense_nodrop(xt, p, cfg):
    """All-experts dense path (small T): no capacity drops."""
    topv, topi, _ = _route(xt, p["router"], cfg.top_k)
    h = jax.nn.silu(jnp.einsum("td,edf->tef", xt, p["experts_wg"])) * \
        jnp.einsum("td,edf->tef", xt, p["experts_wi"])
    y_all = jnp.einsum("tef,efd->ted", h, p["experts_wo"])  # (T, E, d)
    w = jnp.zeros(y_all.shape[:2], jnp.float32)
    w = w.at[jnp.arange(xt.shape[0])[:, None], topi].add(topv)
    return jnp.einsum("ted,te->td", y_all.astype(jnp.float32), w).astype(xt.dtype)


def _moe_local(xt, router_w, wi, wg, wo, *, cfg, E_local, model_axis):
    """Body run per model-rank under shard_map. xt: (T_local, d)."""
    T, d = xt.shape
    k, E = cfg.top_k, cfg.n_experts
    topv, topi, _ = _route(xt, router_w, k)
    rank = jax.lax.axis_index(model_axis) if model_axis else 0
    lo = rank * E_local
    e_flat = topi.reshape(-1)                           # (T*k,)
    w_flat = topv.reshape(-1)
    is_local = (e_flat >= lo) & (e_flat < lo + E_local)
    e_loc = jnp.where(is_local, e_flat - lo, E_local)   # E_local = drop bucket
    onehot = jax.nn.one_hot(e_loc, E_local + 1, dtype=jnp.int32)
    pos = jnp.cumsum(onehot, axis=0) - onehot
    pos = jnp.take_along_axis(pos, e_loc[:, None], axis=1)[:, 0]
    C = max(int(cfg.capacity_factor * k * T / E), 1)
    keep = is_local & (pos < C)
    e_sc = jnp.where(keep, e_loc, E_local)              # scatter drop row
    p_sc = jnp.where(keep, pos, 0)
    x_rep = jnp.repeat(xt, k, axis=0)                   # (T*k, d)
    buf = jnp.zeros((E_local + 1, C, d), xt.dtype)
    buf = buf.at[e_sc, p_sc].add(x_rep * keep[:, None].astype(xt.dtype))
    y = _expert_ffn(buf[:E_local], wi, wg, wo)          # (E_local, C, d)
    y = jnp.concatenate([y, jnp.zeros((1, C, d), y.dtype)], axis=0)
    gathered = y[e_sc, p_sc] * (w_flat * keep)[:, None].astype(y.dtype)
    out = gathered.reshape(T, k, d).sum(axis=1)
    if model_axis:
        out = jax.lax.psum(out, model_axis)
    return out.astype(xt.dtype)


def moe_apply(params, x, cfg, rules):
    """x: (B, S, d) -> (B, S, d)."""
    B, S, d = x.shape
    xt = x.reshape(B * S, d)
    mesh = rules.mesh if rules is not None else None
    if mesh is None or "model" not in mesh.axis_names:
        if B * S <= 4096:
            out = _moe_dense_nodrop(xt, params, cfg)
        else:
            out = _moe_local(xt, params["router"], params["experts_wi"],
                             params["experts_wg"], params["experts_wo"],
                             cfg=cfg, E_local=cfg.n_experts, model_axis=None)
        return out.reshape(B, S, d)

    n_model = mesh.shape["model"]
    E_local = cfg.n_experts // n_model
    dp = ("pod", "data") if "pod" in mesh.axis_names else ("data",)
    T_local = (B * S) // functools.reduce(
        lambda a, b: a * mesh.shape[b], dp, 1)
    P = jax.sharding.PartitionSpec
    if T_local * cfg.top_k <= 2 * cfg.n_experts:
        # decode-scale: dense no-drop path, experts sharded by the einsum
        out = _moe_dense_nodrop(xt, params, cfg)
        return out.reshape(B, S, d)
    fn = functools.partial(_moe_local, cfg=cfg, E_local=E_local,
                           model_axis="model")
    out = jax.shard_map(
        fn, mesh=mesh,
        in_specs=(P(dp, None), P(None, None), P("model", None, None),
                  P("model", None, None), P("model", None, None)),
        out_specs=P(dp, None),
    )(xt, params["router"], params["experts_wi"], params["experts_wg"],
      params["experts_wo"])
    return out.reshape(B, S, d)
