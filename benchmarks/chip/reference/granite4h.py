"""Plain reference of one pipeline stage of Granite-4.0-H, for the checks.

Written from the published architecture (hf:ibm-granite/granite-4.0-h-small,
model_type granitemoehybrid).  The embedding times embedding_multiplier;
then, for each layer of the stage,

  h = x + r * mixer(rms(x));   y = h + r * (moe(rms(h)) + shared(rms(h)))

with r the residual_multiplier, and a final RMSNorm and the tied head,
divided by logits_scaling.  The mixer is

  attention  grouped-query attention with no positional encoding (NoPE),
             softmax scale attention_multiplier, causal;
  mamba      Mamba-2 with one B/C group: in_proj to (z, xBC, dt), a causal
             depthwise conv of width d_conv (with bias) and SiLU over xBC,
             dt = softplus(dt + dt_bias), A = -exp(A_log), and the
             recurrence S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T,
             y_t = S_t C_t + D x_t, run as a sequential scan over the
             positions (no chunking); then RMSNorm(y * SiLU(z)) and
             out_proj.

The MoE routes each token over all router_experts with the router's
logits, keeps the top num_experts_per_tok and weighs them by a softmax
over those logits; of them only the experts held on this chip (a share of
an expert-parallel deployment, experts first_held .. first_held + held - 1)
add their SwiGLU output.  The shared expert is a SwiGLU every token takes.
Straight jax.numpy in float32 at HIGHEST matmul precision, one sequence at
a time; no cache, no kernels, no batching.  It imports nothing of the
system under test.

Weights come from the seed by the recipe the configuration file states
("init"): bfloat16 matrices drawn as normal(0, 1) / sqrt(fan_in) in float32
(the router kept in float32; the embedding at 1/embedding_multiplier of
that, so that the scaled embedding starts at it), from a fixed tree of
jax.random key splits (`_stage`); norm scales and D of one, A_log,
dt_bias and conv bias of zero.

`matmul` is the one place precision enters: `f32` is the reference, and
`fp8` (per-tensor scaled float8_e4m3fn operands, float32 accumulation) is
the control, one step below the bfloat16 that the configuration states.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
BF16 = jnp.bfloat16
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0


class Dims(NamedTuple):
    d: int
    nh: int
    nkv: int
    hd: int
    ff: int                     # one expert's width
    shared_ff: int
    experts: int                # the router's width
    held: int
    first_held: int
    top_k: int
    vocab: int
    kinds: Tuple[str, ...]      # "mamba" / "attention", each layer's
    ssm_heads: int
    ssm_head_dim: int
    ssm_state: int
    conv: int
    eps: float
    scale: float                # attention softmax scale
    emb_mult: float
    resid_mult: float
    logits_div: float


def dims(config: dict) -> Dims:
    ep = config["expert_parallel"]
    kinds = tuple(config["layer_types"][:config["num_hidden_layers"]])
    return Dims(config["hidden_size"], config["num_attention_heads"],
                config["num_key_value_heads"],
                config["hidden_size"] // config["num_attention_heads"],
                config["intermediate_size"],
                config["shared_intermediate_size"], ep["router_experts"],
                config["num_local_experts"], ep["first_held_expert"],
                config["num_experts_per_tok"], config["vocab_size"], kinds,
                config["mamba_n_heads"], config["mamba_d_head"],
                config["mamba_d_state"], config["mamba_d_conv"],
                float(config["rms_norm_eps"]),
                float(config["attention_multiplier"]),
                float(config["embedding_multiplier"]),
                float(config["residual_multiplier"]),
                float(config["logits_scaling"]))


# ------------------------------------------------------------- precision
def matmul_f32(a, b):
    return jnp.matmul(a.astype(jnp.float32), b.astype(jnp.float32),
                      precision=HIGHEST)


def _fp8(x):
    """x rounded to float8_e4m3fn under a per-tensor scale."""
    x = x.astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    return (x / s).astype(FP8).astype(jnp.float32) * s


def matmul_fp8(a, b):
    return jnp.matmul(_fp8(a), _fp8(b), precision=HIGHEST)


MATMULS = {"f32": matmul_f32, "fp8": matmul_fp8}


# ------------------------------------------------------------------ init
def _normal(key, shape, fan_in, dtype=BF16):
    std = 1.0 / math.sqrt(fan_in)
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def _mamba(key, m: Dims):
    di = m.ssm_heads * m.ssm_head_dim
    conv_dim = di + 2 * m.ssm_state
    ks = jax.random.split(key, 4)
    return {
        "in_proj": _normal(ks[0], (m.d, 2 * di + 2 * m.ssm_state
                                   + m.ssm_heads), m.d),
        "conv_w": _normal(ks[1], (m.conv, conv_dim), m.conv),
        "conv_b": jnp.zeros((conv_dim,), BF16),
        "A_log": jnp.zeros((m.ssm_heads,), jnp.float32),
        "D": jnp.ones((m.ssm_heads,), jnp.float32),
        "dt_bias": jnp.zeros((m.ssm_heads,), jnp.float32),
        "norm_scale": jnp.ones((di,), BF16),
        "out_proj": _normal(ks[3], (di, m.d), di),
    }


def _attention(key, m: Dims):
    ks = jax.random.split(key, 4)
    return {"wq": _normal(ks[0], (m.d, m.nh * m.hd), m.d),
            "wk": _normal(ks[1], (m.d, m.nkv * m.hd), m.d),
            "wv": _normal(ks[2], (m.d, m.nkv * m.hd), m.d),
            "wo_attn": _normal(ks[3], (m.nh * m.hd, m.d), m.nh * m.hd)}


def _moe(key, m: Dims):
    ks = jax.random.split(key, 4)
    kw = jax.random.split(jax.random.fold_in(key, 1), 3)
    return {
        "router": _normal(ks[0], (m.d, m.experts), m.d, jnp.float32),
        "experts_wi": _normal(ks[1], (m.held, m.d, m.ff), m.d),
        "experts_wg": _normal(ks[2], (m.held, m.d, m.ff), m.d),
        "experts_wo": _normal(ks[3], (m.held, m.ff, m.d), m.ff),
        "shared_wi": _normal(kw[0], (m.d, m.shared_ff), m.d),
        "shared_wg": _normal(kw[1], (m.d, m.shared_ff), m.d),
        "shared_wo": _normal(kw[2], (m.shared_ff, m.d), m.shared_ff),
    }


def _layer(key, kind: str, m: Dims):
    ks = jax.random.split(key, 4)
    one = jnp.ones((m.d,), BF16)
    mixer = ({"mamba": _mamba(ks[0], m)} if kind == "mamba"
             else {"attn": _attention(ks[0], m)})
    return dict(mixer, ln1={"scale": one}, ln2={"scale": one},
                moe=_moe(ks[1], m))


def init_weights(seed: int, m: Dims):
    """bfloat16 weights, made on the device in one call: each layer's as a
    stack of one (the program's layer stacks for a stage of one period)."""
    return _stage(jax.random.PRNGKey(seed), m)


@functools.partial(jax.jit, static_argnums=(1,))
def _stage(key, m: Dims):
    keys = jax.random.split(key, 3 + len(m.kinds))
    layers = tuple(
        jax.vmap(lambda k, kind=kind: _layer(k, kind, m))(
            jax.random.split(keys[i], 1))
        for i, kind in enumerate(m.kinds))
    emb = _normal(keys[-3], (m.vocab, m.d), m.d * m.emb_mult ** 2)
    return {"embed": {"embedding": emb},
            "layers": layers,
            "final_norm": {"scale": jnp.ones((m.d,), BF16)}}


# --------------------------------------------------------------- forward
def _rms(x, scale, eps):
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def _silu(x):
    return x * jax.nn.sigmoid(x)


def _attend(w, h, m: Dims, mm):
    S = h.shape[0]
    q = mm(h, w["wq"]).reshape(S, m.nh, m.hd)
    k = mm(h, w["wk"]).reshape(S, m.nkv, m.hd)
    v = mm(h, w["wv"]).reshape(S, m.nkv, m.hd)
    rep = m.nh // m.nkv                      # query head h reads kv head h//rep
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) * m.scale
    pos = jnp.arange(S)
    s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v,
                   precision=HIGHEST)
    return mm(o.reshape(S, m.nh * m.hd), w["wo_attn"])


def _mamba_mix(w, h, m: Dims, mm):
    """Mamba-2 over h (S, d), the recurrence a sequential scan."""
    S = h.shape[0]
    di, N, H, P = (m.ssm_heads * m.ssm_head_dim, m.ssm_state, m.ssm_heads,
                   m.ssm_head_dim)
    proj = mm(h, w["in_proj"])
    z, xbc, dt = proj[:, :di], proj[:, di:2 * di + 2 * N], proj[:, -H:]
    padded = jnp.concatenate([jnp.zeros((m.conv - 1, xbc.shape[1])), xbc])
    conv = sum(padded[i:i + S] * w["conv_w"][i].astype(jnp.float32)
               for i in range(m.conv)) + w["conv_b"].astype(jnp.float32)
    conv = _silu(conv)
    xs = conv[:, :di].reshape(S, H, P)
    Bm, Cm = conv[:, di:di + N], conv[:, di + N:]
    dt = jax.nn.softplus(dt + w["dt_bias"])
    A = -jnp.exp(w["A_log"])

    def step(state, inp):                    # state: (H, P, N)
        x_t, b_t, c_t, dt_t = inp
        state = (state * jnp.exp(dt_t * A)[:, None, None]
                 + jnp.einsum("h,hp,n->hpn", dt_t, x_t, b_t,
                              precision=HIGHEST))
        y = jnp.einsum("hpn,n->hp", state, c_t, precision=HIGHEST)
        return state, y + w["D"][:, None] * x_t

    _, y = jax.lax.scan(step, jnp.zeros((H, P, N), jnp.float32),
                        (xs, Bm, Cm, dt))
    y = _rms(y.reshape(S, di) * _silu(z), w["norm_scale"], m.eps)
    return mm(y, w["out_proj"])


def _swiglu(h, wg, wi, wo, mm):
    return mm(_silu(mm(h, wg)) * mm(h, wi), wo)


def _moe_ffn(w, h, m: Dims, mm):
    """The held experts' share of the routed result, plus the shared
    expert."""
    logits = mm(h, w["router"])                           # (S, experts)
    top, idx = jax.lax.top_k(logits, m.top_k)
    gate = jax.nn.softmax(top, axis=-1)
    out = _swiglu(h, w["shared_wg"], w["shared_wi"], w["shared_wo"], mm)
    for e in range(m.held):
        weight = jnp.sum(jnp.where(idx == m.first_held + e, gate, 0.0), -1)
        out = out + weight[:, None] * _swiglu(
            h, w["experts_wg"][e], w["experts_wi"][e], w["experts_wo"][e],
            mm)
    return out


def _layer_fwd(w, kind: str, x, m: Dims, mm):
    h = _rms(x, w["ln1"]["scale"], m.eps)
    mix = (_mamba_mix(w["mamba"], h, m, mm) if kind == "mamba"
           else _attend(w["attn"], h, m, mm))
    x = x + m.resid_mult * mix
    h = _rms(x, w["ln2"]["scale"], m.eps)
    return x + m.resid_mult * _moe_ffn(w["moe"], h, m, mm)


def logits(weights, tokens, m: Dims, precision: str = "f32"):
    """tokens: (S,) int -> (S, vocab) float32 logits of the next token."""
    mm = MATMULS[precision]
    x = weights["embed"]["embedding"][tokens].astype(jnp.float32) * m.emb_mult
    for kind, stack in zip(m.kinds, weights["layers"]):
        x = _layer_fwd(jax.tree.map(lambda a: a[0], stack), kind, x, m, mm)
    x = _rms(x, weights["final_norm"]["scale"], m.eps)
    return mm(x, weights["embed"]["embedding"].T) / m.logits_div


logits_jit = jax.jit(logits, static_argnums=(2, 3))
