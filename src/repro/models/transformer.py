"""Model assembly: repeating-unit block stacks scanned over layers.

The per-layer block pattern (cfg.block_pattern) is a repeating unit; params
for each unit position are stacked over repeats and the stack is traversed
with ``lax.scan`` so the HLO contains each distinct block exactly once
(fast multi-pod compiles, MaxText-style).  Zamba2's shared attention block is
closure-captured (weights shared) and applied every ``shared_attn_every``
layers through ``lax.cond``; its per-application KV caches ride in the scan
carry with dynamic indexing.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import (ATTN, ATTN_MOE, MAMBA2, MAMBA2_MOE, MLSTM,
                                SLSTM, ModelConfig)
from repro.models import attention, mamba2, moe as moe_mod, xlstm
from repro.models.layers import embed, embed_init, mlp, mlp_init, rmsnorm, \
    rmsnorm_init, unembed

PyTree = Any


# ------------------------------------------------------------------- init
def _block_init(key, cfg, kind):
    ks = jax.random.split(key, 4)
    p = {"ln1": rmsnorm_init(cfg.d_model, jnp.dtype(cfg.param_dtype))}
    if kind in (ATTN, ATTN_MOE):
        p["attn"] = attention.attn_init(ks[0], cfg)
        p["ln2"] = rmsnorm_init(cfg.d_model, jnp.dtype(cfg.param_dtype))
        if kind == ATTN:
            p["mlp"] = mlp_init(ks[1], cfg)
        else:
            p["moe"] = moe_mod.moe_init(ks[1], cfg)
    elif kind in (MAMBA2, MAMBA2_MOE):
        p["mamba"] = mamba2.mamba2_init(ks[0], cfg)
        if kind == MAMBA2_MOE:
            p["ln2"] = rmsnorm_init(cfg.d_model, jnp.dtype(cfg.param_dtype))
            p["moe"] = moe_mod.moe_init(ks[1], cfg)
    elif kind == MLSTM:
        p["mlstm"] = xlstm.mlstm_init(ks[0], cfg)
    elif kind == SLSTM:
        p["slstm"] = xlstm.slstm_init(ks[0], cfg)
    else:
        raise ValueError(kind)
    return p


def n_repeats(cfg) -> int:
    assert cfg.n_layers % len(cfg.block_pattern) == 0, \
        f"{cfg.name}: n_layers must divide by unit length"
    return cfg.n_layers // len(cfg.block_pattern)


def init_params(key, cfg: ModelConfig) -> PyTree:
    reps = n_repeats(cfg)
    keys = jax.random.split(key, 3 + len(cfg.block_pattern))
    layers = []
    for pi, kind in enumerate(cfg.block_pattern):
        stacked = jax.vmap(
            lambda k, kind=kind: _block_init(k, cfg, kind))(
                jax.random.split(keys[pi], reps))
        layers.append(stacked)
    params = {
        "embed": embed_init(keys[-3], cfg),
        "layers": tuple(layers),
        "final_norm": rmsnorm_init(cfg.d_model, jnp.dtype(cfg.param_dtype)),
    }
    if cfg.shared_attn_every:
        params["shared_attn"] = {
            "ln": rmsnorm_init(cfg.d_model, jnp.dtype(cfg.param_dtype)),
            "attn": attention.attn_init(keys[-2], cfg),
            "ln2": rmsnorm_init(cfg.d_model, jnp.dtype(cfg.param_dtype)),
            "mlp": mlp_init(keys[-1], cfg),
        }
    return params


# ------------------------------------------------------------------ cache
def _block_cache(cfg, kind, batch, max_seq, layout):
    if kind in (ATTN, ATTN_MOE):
        return attention.init_attn_cache(cfg, batch, max_seq, layout)
    if kind in (MAMBA2, MAMBA2_MOE):
        return mamba2.init_mamba_cache(cfg, batch)
    if kind == MLSTM:
        return xlstm.init_mlstm_cache(cfg, batch)
    if kind == SLSTM:
        return xlstm.init_slstm_cache(cfg, batch)
    raise ValueError(kind)


def init_cache(cfg, batch: int, max_seq: int, layout: str = "dense") -> PyTree:
    reps = n_repeats(cfg)
    caches = {"layers": tuple(
        jax.tree.map(lambda x: jnp.broadcast_to(x, (reps,) + x.shape),
                     _block_cache(cfg, kind, batch, max_seq, layout))
        for kind in cfg.block_pattern)}
    if cfg.shared_attn_every:
        n_apps = cfg.n_layers // cfg.shared_attn_every
        sc = attention.init_attn_cache(cfg, batch, max_seq, layout)
        caches["shared"] = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (n_apps,) + x.shape), sc)
    return caches


# ------------------------------------------------------------------ apply
def _residual(x, y, cfg):
    """x + the branch y, scaled by the residual multiplier where it has
    one."""
    if cfg.residual_multiplier != 1.0:
        y = y * jnp.asarray(cfg.residual_multiplier, y.dtype)
    return x + y


def _block_apply(kind, p, x, cfg, rules, mode, cache, pos, layer=None,
                 kv_backend=None, lengths=None, count_mask=None):
    """One block; returns (x, new_cache, counts).  With `layer` given
    (decode in place), `cache` is the stacked cache of every layer and
    comes back updated at `layer`.  `lengths` (B,): a padded prefill's
    sequence lengths, for the recurrent state.  With `count_mask` given,
    an MoE block's counts are moe.route_counts of the tokens under it;
    otherwise counts is None."""
    if layer is not None and kind not in (ATTN, ATTN_MOE):
        x, nc, counts = _block_apply(
            kind, p, x, cfg, rules, mode,
            jax.tree.map(lambda a: a[layer], cache), pos,
            count_mask=count_mask)
        return x, jax.tree.map(
            lambda a, u: jax.lax.dynamic_update_index_in_dim(
                a, u.astype(a.dtype), layer, 0), cache, nc), counts
    eps = cfg.norm_eps
    h = rmsnorm(p["ln1"], x, eps)
    new_cache = counts = None
    if kind in (ATTN, ATTN_MOE):
        a, new_cache = attention.attn_apply(p["attn"], h, cfg, rules,
                                            mode=mode, cache=cache, pos=pos,
                                            layer=layer,
                                            kv_backend=kv_backend)
        x = _residual(x, a, cfg)
    elif kind in (MAMBA2, MAMBA2_MOE):
        y, new_cache = mamba2.mamba2_apply(p["mamba"], h, cfg, rules,
                                           mode=mode, cache=cache, pos=pos,
                                           lengths=lengths)
        x = _residual(x, y, cfg)
    elif kind == MLSTM:
        y, new_cache = xlstm.mlstm_apply(p["mlstm"], h, cfg, rules,
                                         mode=mode, cache=cache, pos=pos)
        x = x + y
    elif kind == SLSTM:
        y, new_cache = xlstm.slstm_apply(p["slstm"], h, cfg, rules,
                                         mode=mode, cache=cache, pos=pos)
        x = x + y
    if kind == ATTN:
        x = x + mlp(p["mlp"], rmsnorm(p["ln2"], x, eps), cfg)
    elif kind in (ATTN_MOE, MAMBA2_MOE):
        m = moe_mod.moe_apply(p["moe"], rmsnorm(p["ln2"], x, eps), cfg,
                              rules, count_mask)
        if count_mask is not None:
            m, counts = m
        x = _residual(x, m, cfg)
    if rules is not None:
        seq = "act_seq" if cfg.seq_shard else None
        x = rules.constrain(x, "batch", seq, None)
    return x, new_cache, counts


def _add_counts(acc, counts):
    return acc if counts is None else acc + counts


def _shared_attn_apply(p, x, cfg, rules, mode, cache, pos, layer=None,
                       kv_backend=None):
    h = rmsnorm(p["ln"], x, cfg.norm_eps)
    a, new_cache = attention.attn_apply(p["attn"], h, cfg, rules,
                                        mode=mode, cache=cache, pos=pos,
                                        layer=layer, kv_backend=kv_backend)
    x = x + a
    h2 = rmsnorm(p["ln2"], x, cfg.norm_eps)
    x = x + mlp(p["mlp"], h2, cfg)
    return x, new_cache


def _decode_in_place(params, x, cfg, caches, pos, kv_backend, count_mask):
    """The decode layer loop with every layer's caches as loop state: each
    block reads and updates its layer of the stacked caches in place (the
    paged KV pool through kernels/paged_attention), so no layer's cache is
    sliced out as a scan input or stacked back as a scan output.  Returns
    (x, caches, MoE counts summed over layers or None)."""
    unit = cfg.block_pattern

    def run(x, p_layers, c_layers, first):
        def body(carry, lp):
            x, cs, i, acc = carry
            out = []
            for pi, kind in enumerate(unit):
                x, c, counts = _block_apply(kind, lp[pi], x, cfg, None,
                                            "decode", cs[pi], pos, layer=i,
                                            kv_backend=kv_backend,
                                            count_mask=count_mask)
                acc = _add_counts(acc, counts)
                out.append(c)
            return (x, tuple(out), i + 1, acc), None

        acc = None if count_mask is None else jnp.zeros(2, jnp.int32)
        (x, c_layers, _, acc), _ = jax.lax.scan(
            body, (x, c_layers, jnp.int32(first), acc), p_layers)
        return x, c_layers, acc

    every = cfg.shared_attn_every
    if not every:
        x, layers, acc = run(x, params["layers"], caches["layers"], 0)
        return x, {"layers": layers}, acc
    # zamba2-style: the groups unrolled as in forward; the shared block's
    # caches are one stack indexed by the application
    p_all = params["layers"][0]
    layers, shared = caches["layers"], caches["shared"]
    for g, start in enumerate(range(0, cfg.n_layers, every)):
        sl = slice(start, min(start + every, cfg.n_layers))
        x, layers, _ = run(x, (jax.tree.map(lambda a: a[sl], p_all),),
                           layers, start)
        if sl.stop - sl.start == every:
            x, shared = _shared_attn_apply(params["shared_attn"], x, cfg,
                                           None, "decode", shared, pos,
                                           layer=g, kv_backend=kv_backend)
    return x, {"layers": layers, "shared": shared}, None


def _count_mask(x, mode, pos, lengths):
    """(B, S) bool: the tokens whose routings are counted, those of active
    sequences (decode: pos >= 0) or before each sequence's length."""
    B, S = x.shape[:2]
    if mode == "decode":
        live = jnp.broadcast_to(jnp.asarray(pos), (B,)) >= 0
        return jnp.broadcast_to(live[:, None], (B, S))
    if lengths is None:
        return jnp.ones((B, S), bool)
    return jnp.arange(S)[None, :] < lengths[:, None]


def forward(params, inputs, cfg: ModelConfig, rules=None, *, mode="train",
            caches=None, pos=None, return_hidden=False, kv_backend=None,
            lengths=None, count_routes=False):
    """inputs: (B,S) int tokens or (B,S,d) embeds.  Returns (logits, caches),
    and with `count_routes` (MoE) also the int32 (2,) routings of counted
    tokens to held experts and held experts hit, summed over layers
    (moe.route_counts).  `lengths` (B,): a padded prefill's sequence
    lengths (recurrent state, counted tokens).

    Decode follows the cache's layout: on a paged cache (init_cache(...,
    "paged")) it updates every layer's caches in place (_decode_in_place)
    through kernels/paged_attention, whose backend `kv_backend` picks (None:
    the ops module's default for this platform); on a dense cache it runs
    the generic layer loop.  Decode is unsharded: with `rules` given it
    raises."""
    if mode == "decode" and rules is not None:
        raise NotImplementedError(
            "decode under a mesh (rules given) is not supported: the "
            "in-place decode is unsharded (ROADMAP R8)")
    x = embed(params["embed"], inputs, cfg)
    if cfg.embedding_multiplier != 1.0:
        x = x * jnp.asarray(cfg.embedding_multiplier, x.dtype)
    if rules is not None:
        x = rules.constrain(x, "batch",
                            "act_seq" if cfg.seq_shard else None, None)
    count_mask = _count_mask(x, mode, pos, lengths) if count_routes else None

    def done(x, new_caches, counts):
        out = _head(params, x, cfg, rules, new_caches, return_hidden)
        return out + (counts,) if count_routes else out

    if mode == "decode" and any("pool_k" in c for c in (
            *caches["layers"], caches.get("shared", {}))):
        return done(*_decode_in_place(params, x, cfg, caches, pos,
                                      kv_backend, count_mask))
    unit = cfg.block_pattern
    use_cache = caches is not None
    every = cfg.shared_attn_every

    def body(carry, xs):
        x, acc = carry
        layer_params, layer_caches = xs
        new_caches = []
        for pi, kind in enumerate(unit):
            c_i = layer_caches[pi] if use_cache else None
            x, nc, counts = _block_apply(kind, layer_params[pi], x, cfg,
                                         rules, mode, c_i, pos,
                                         lengths=lengths,
                                         count_mask=count_mask)
            acc = _add_counts(acc, counts)
            new_caches.append(nc if nc is not None else 0)
        return (x, acc), tuple(new_caches)

    if mode == "train" and cfg.remat != "none":
        policy = {"full": jax.checkpoint_policies.nothing_saveable,
                  "dots": jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims,
                  }[cfg.remat]
        body = jax.checkpoint(body, policy=policy)

    acc = None if count_mask is None else jnp.zeros(2, jnp.int32)

    def seg_scan(x, acc, p_seg, c_seg):
        xs = (p_seg, c_seg if use_cache else jnp.zeros(
            (jax.tree.leaves(p_seg)[0].shape[0],)))
        (x, acc), out = jax.lax.scan(body, (x, acc), xs)
        return x, acc, out

    if every:
        # zamba2-style: shared attention block applied (with its own cache
        # slot) after every `every` backbone layers — statically unrolled
        # into groups so the HLO and its cost analysis reflect the true
        # per-layer mix (no lax.cond over-/under-counting).
        assert len(unit) == 1, "shared_attn requires a unit-1 block pattern"
        shared_p = params["shared_attn"]
        groups = cfg.n_layers // every
        rem = cfg.n_layers - groups * every
        p_all = params["layers"][0]
        c_all = caches["layers"][0] if use_cache else None
        seg_caches, shared_caches = [], []
        for g in range(groups):
            sl = slice(g * every, (g + 1) * every)
            p_seg = jax.tree.map(lambda a: a[sl], p_all)
            c_seg = jax.tree.map(lambda a: a[sl], c_all) if use_cache else None
            x, acc, c_out = seg_scan(x, acc, (p_seg,), (c_seg,))
            if use_cache:
                seg_caches.append(c_out[0])
            sc = (jax.tree.map(lambda a: a[g], caches["shared"])
                  if use_cache else None)
            x, sc_out = _shared_attn_apply(shared_p, x, cfg, rules, mode,
                                           sc, pos)
            if use_cache:
                shared_caches.append(sc_out)
        if rem:
            sl = slice(groups * every, cfg.n_layers)
            p_seg = jax.tree.map(lambda a: a[sl], p_all)
            c_seg = jax.tree.map(lambda a: a[sl], c_all) if use_cache else None
            x, acc, c_out = seg_scan(x, acc, (p_seg,), (c_seg,))
            if use_cache:
                seg_caches.append(c_out[0])
        new_caches = None
        if use_cache:
            new_caches = {
                "layers": (jax.tree.map(
                    lambda *xs: jnp.concatenate(xs, axis=0), *seg_caches),),
                "shared": jax.tree.map(
                    lambda *xs: jnp.stack([u.astype(xs[0].dtype) for u in xs],
                                          axis=0), *shared_caches),
            }
    else:
        x, acc, layer_caches_out = seg_scan(
            x, acc, params["layers"], caches["layers"] if use_cache else None)
        new_caches = {"layers": layer_caches_out} if use_cache else None
    return done(x, new_caches, acc)


def _head(params, x, cfg, rules, new_caches, return_hidden):
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    if return_hidden:
        return x, new_caches
    logits = unembed(params["embed"], x, cfg)
    if cfg.logits_scaling != 1.0:
        logits = logits / jnp.asarray(cfg.logits_scaling, logits.dtype)
    if rules is not None:
        logits = rules.constrain(logits, "batch", None, "vocab")
    return logits, new_caches


def lm_loss(logits, labels, mask=None):
    """Mean next-token cross-entropy. logits:(B,S,V) labels:(B,S)."""
    lf = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lf, axis=-1)
    ll = jnp.take_along_axis(lf, labels[..., None], axis=-1)[..., 0]
    nll = lse - ll
    if mask is not None:
        return (nll * mask).sum() / jnp.maximum(mask.sum(), 1)
    return nll.mean()


def lm_loss_chunked(params, hidden, labels, cfg, rules=None):
    """Cross-entropy computed in sequence chunks: the (B, S, V) logits
    tensor never materializes (per-chunk unembed + CE under jax.checkpoint).
    Memory: O(B * loss_chunk * V) instead of O(B * S * V)."""
    B, S, d = hidden.shape
    c = min(cfg.loss_chunk, S)
    n = S // c
    hc = jnp.moveaxis(hidden.reshape(B, n, c, d), 1, 0)
    lc = jnp.moveaxis(labels.reshape(B, n, c), 1, 0)

    @jax.checkpoint
    def body(acc, xs):
        h, l = xs
        logits = unembed(params["embed"], h, cfg)
        if rules is not None:
            logits = rules.constrain(logits, "batch", None, "vocab")
        lf = logits.astype(jnp.float32)
        lse = jax.nn.logsumexp(lf, axis=-1)
        ll = jnp.take_along_axis(lf, l[..., None], axis=-1)[..., 0]
        return acc + (lse - ll).sum(), ()

    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (hc, lc))
    return total / (B * S)
