"""Per-architecture smoke tests (deliverable f): reduced config of the same
family, one forward + one train step on CPU, assert output shapes + no NaNs;
plus prefill/decode == full-forward consistency for every cache layout."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get
from repro.models import forward, init_cache, init_params, lm_loss
from repro.train.optimizer import adamw_update, init_opt_state

KEY = jax.random.PRNGKey(0)


def _inputs(cfg, B=2, S=16, extra=0):
    if cfg.input_kind == "embeds":
        x = jax.random.normal(KEY, (B, S + extra, cfg.d_model),
                              jnp.dtype(cfg.param_dtype))
    else:
        x = jax.random.randint(KEY, (B, S + extra), 0, cfg.vocab_size)
    labels = jax.random.randint(KEY, (B, S + extra), 0, cfg.vocab_size)
    return x, labels


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_forward_shapes_no_nans(arch):
    cfg = get(arch, smoke=True)
    params = init_params(KEY, cfg)
    B, S = 2, 16
    x, labels = _inputs(cfg, B, S)
    logits, _ = forward(params, x, cfg, mode="train")
    assert logits.shape == (B, S, cfg.vocab_size)
    assert not bool(jnp.isnan(logits.astype(jnp.float32)).any())
    loss = lm_loss(logits, labels)
    assert np.isfinite(float(loss))


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_train_step_updates(arch):
    cfg = get(arch, smoke=True)
    params = init_params(KEY, cfg)
    m, v = init_opt_state(params)
    x, labels = _inputs(cfg)

    def loss_fn(p):
        logits, _ = forward(p, x, cfg, mode="train")
        return lm_loss(logits, labels)

    loss0, grads = jax.value_and_grad(loss_fn)(params)
    new_p, m, v, gnorm = adamw_update(params, grads, m, v,
                                      jnp.zeros((), jnp.int32))
    assert np.isfinite(float(loss0)) and np.isfinite(float(gnorm))
    loss1 = loss_fn(new_p)
    assert float(loss1) < float(loss0)  # one step of AdamW must descend


@pytest.mark.parametrize("arch", ARCH_IDS)
@pytest.mark.parametrize("layout", ["dense", "paged"])
def test_prefill_decode_matches_forward(arch, layout):
    cfg = get(arch, smoke=True).replace(param_dtype="float32",
                                        kv_block_size=8)
    params = init_params(KEY, cfg)
    B, S = 2, 16
    x, _ = _inputs(cfg, B, S, extra=1)
    ref, _ = forward(params, x, cfg, mode="train")
    cache = init_cache(cfg, B, 32, layout)
    pre, cache = forward(params, x[:, :S], cfg, mode="prefill", caches=cache)
    np.testing.assert_allclose(np.asarray(pre, np.float32),
                               np.asarray(ref[:, :S], np.float32),
                               rtol=3e-4, atol=3e-4)
    dec, _ = forward(params, x[:, S:S + 1], cfg, mode="decode", caches=cache,
                     pos=jnp.int32(S))
    np.testing.assert_allclose(np.asarray(dec[:, 0], np.float32),
                               np.asarray(ref[:, S], np.float32),
                               rtol=3e-4, atol=3e-4)


@pytest.mark.parametrize("arch", ARCH_IDS)
@pytest.mark.parametrize("kv_backend", ["reference", "pallas_interpret"])
def test_decode_in_place_matches_the_gathering_decode(arch, kv_backend):
    """Decode on every layer's caches in place (kv_backend given) gives the
    logits and caches of the decode that slices each layer out, for every
    block kind that rides the loop (attention, MoE, Mamba2, xLSTM, Zamba2's
    shared attention).  The pools are compared whole, a free sequence's
    (pos -1) rows too; its recurrent state, fed by its unused hidden
    state, is left out."""
    cfg = get(arch, smoke=True).replace(param_dtype="float32",
                                        kv_block_size=8)
    params = init_params(KEY, cfg)
    B, S = 3, 16
    x, _ = _inputs(cfg, B, S, extra=1)
    cache = init_cache(cfg, B, 32, "paged")
    _, cache = forward(params, x[:, :S], cfg, mode="prefill", caches=cache)
    pos = jnp.array([S, S - 3, -1], jnp.int32)
    ref, ref_cache = forward(params, x[:, S:S + 1], cfg, mode="decode",
                             caches=cache, pos=pos)
    got, got_cache = forward(params, x[:, S:S + 1], cfg, mode="decode",
                             caches=cache, pos=pos, kv_backend=kv_backend)
    np.testing.assert_allclose(np.asarray(got[:2], np.float32),
                               np.asarray(ref[:2], np.float32),
                               rtol=1e-4, atol=1e-4)
    flat = jax.tree_util.tree_flatten_with_path(ref_cache)[0]
    for (path, b), a in zip(flat, jax.tree.leaves(got_cache)):
        rows = slice(None) if "pool" in jax.tree_util.keystr(path) \
            else slice(0, 2)                   # every leaf: (layers, B, ...)
        np.testing.assert_allclose(np.asarray(a, np.float32)[:, rows],
                                   np.asarray(b, np.float32)[:, rows],
                                   rtol=1e-4, atol=1e-4)


def test_decode_per_sequence_positions():
    """Ragged decode (continuous batching): per-seq pos vector must match
    per-seq scalar decode."""
    cfg = get("smollm_135m", smoke=True).replace(param_dtype="float32",
                                                 kv_block_size=8)
    params = init_params(KEY, cfg)
    B, S = 3, 16  # prefill length must be a multiple of kv_block_size
    x, _ = _inputs(cfg, B, S + 4)
    cache = init_cache(cfg, B, 32, "paged")
    _, cache = forward(params, x[:, :S], cfg, mode="prefill", caches=cache)
    pos = jnp.array([S, S, S], jnp.int32)
    step_tok = x[:, S:S + 1]
    ragged, _ = forward(params, step_tok, cfg, mode="decode", caches=cache,
                        pos=pos)
    scalar, _ = forward(params, step_tok, cfg, mode="decode", caches=cache,
                        pos=jnp.int32(S))
    np.testing.assert_allclose(np.asarray(ragged), np.asarray(scalar),
                               rtol=1e-5, atol=1e-5)


def test_param_counts_match_family_scale():
    full = get("smollm_135m")
    n = full.param_count()
    assert 120e6 < n < 150e6, n  # "135M" within tolerance
    q3 = get("qwen3_8b").param_count()
    assert 7e9 < q3 < 9e9, q3
    moe = get("olmoe_1b_7b")
    assert moe.active_param_count() < 0.4 * moe.param_count()
