"""Step factories: the jitted, sharded train step and its state.

Each factory closes over (cfg, mesh) and returns the jitted step plus the
shardings of its inputs; ``input_specs`` gives ShapeDtypeStruct stand-ins
for a train batch (smoke tests, examples, the checkpoint runner).
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig
from repro.models import forward, init_params, lm_loss
from repro.sharding.rules import Rules, make_rules, param_spec, tree_specs
from repro.train.optimizer import OptHyper, adamw_update, init_opt_state

PyTree = Any


# ------------------------------------------------------------- input specs
def input_specs(cfg: ModelConfig, shape: ShapeConfig) -> Dict[str, Any]:
    """ShapeDtypeStruct stand-ins for a train batch of this shape."""
    B, S = shape.global_batch, shape.seq_len
    if cfg.input_kind == "embeds":
        tokens = jax.ShapeDtypeStruct((B, S, cfg.d_model), jnp.bfloat16)
    else:
        tokens = jax.ShapeDtypeStruct((B, S), jnp.int32)
    return {"tokens": tokens, "labels": jax.ShapeDtypeStruct((B, S),
                                                             jnp.int32)}


def batch_shardings(cfg, shape, rules: Rules):
    return {k: rules.sharding(v.shape, "batch")
            for k, v in input_specs(cfg, shape).items()}


def abstract_state(cfg, key=None):
    """Abstract (ShapeDtypeStruct) train state, never materialized."""
    def mk():
        params = init_params(jax.random.PRNGKey(0) if key is None else key,
                             cfg)
        m, v = init_opt_state(params)
        return {"params": params, "m": m, "v": v,
                "step": jnp.zeros((), jnp.int32)}
    return jax.eval_shape(mk)


def state_shardings(cfg, rules: Rules):
    st = abstract_state(cfg)
    return {
        "params": tree_specs(st["params"], param_spec, rules),
        "m": tree_specs(st["m"], param_spec, rules),
        "v": tree_specs(st["v"], param_spec, rules),
        "step": NamedSharding(rules.mesh, P()),
    }


# -------------------------------------------------------------- train step
def make_train_step(cfg: ModelConfig, mesh, shape: ShapeConfig,
                    hyper: OptHyper = OptHyper()):
    rules = make_rules(mesh)

    def loss_of(params, tokens, labels):
        if cfg.loss_chunk:
            from repro.models.transformer import lm_loss_chunked
            hidden, _ = forward(params, tokens, cfg, rules, mode="train",
                                return_hidden=True)
            return lm_loss_chunked(params, hidden, labels, cfg, rules)
        logits, _ = forward(params, tokens, cfg, rules, mode="train")
        return lm_loss(logits, labels)

    p_specs = tree_specs(abstract_state(cfg)["params"], param_spec, rules)

    def shard_grads(grads):
        """Pin gradients to the parameter sharding.  Without this GSPMD
        emits per-layer f32 ALL-REDUCES of full weight gradients inside the
        backward scan (measured 4.6e12 B/dev on qwen2-72b); with it the sums
        lower to reduce-scatters into the (fsdp, model) layout."""
        if not cfg.grad_shard:
            return grads
        return jax.tree.map(
            lambda g, s: jax.lax.with_sharding_constraint(g, s), grads,
            p_specs)

    def train_step(state, batch):
        tokens, labels = batch["tokens"], batch["labels"]
        accum = cfg.grad_accum
        if accum > 1:
            B = tokens.shape[0]
            tk = tokens.reshape((accum, B // accum) + tokens.shape[1:])
            lb = labels.reshape((accum, B // accum) + labels.shape[1:])

            def micro(carry, xs):
                t, l = xs
                loss, g = jax.value_and_grad(loss_of)(state["params"], t, l)
                g = shard_grads(g)
                carry = jax.tree.map(jnp.add, carry, (g, loss))
                return carry, ()

            g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                              state["params"])
            (grads, loss), _ = jax.lax.scan(micro, (g0, 0.0), (tk, lb))
            grads = jax.tree.map(lambda g: g / accum, grads)
            loss = loss / accum
        else:
            loss, grads = jax.value_and_grad(loss_of)(
                state["params"], tokens, labels)
            grads = shard_grads(grads)
        new_p, new_m, new_v, gnorm = adamw_update(
            state["params"], grads, state["m"], state["v"], state["step"],
            hyper)
        new_state = {"params": new_p, "m": new_m, "v": new_v,
                     "step": state["step"] + 1}
        metrics = {"loss": loss.astype(jnp.float32), "grad_norm": gnorm}
        return new_state, metrics

    st_sh = state_shardings(cfg, rules)
    b_sh = batch_shardings(cfg, shape, rules)
    rep = NamedSharding(mesh, P())
    step = jax.jit(train_step,
                   in_shardings=(st_sh, b_sh),
                   out_shardings=(st_sh, {"loss": rep, "grad_norm": rep}),
                   donate_argnums=(0,))
    return step, rules, st_sh, b_sh


def make_init_fn(cfg, mesh):
    rules = make_rules(mesh)
    st_sh = state_shardings(cfg, rules)

    def init_fn(key):
        params = init_params(key, cfg)
        m, v = init_opt_state(params)
        return {"params": params, "m": m, "v": v,
                "step": jnp.zeros((), jnp.int32)}

    return jax.jit(init_fn, out_shardings=st_sh), st_sh
