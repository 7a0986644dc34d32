"""Per-architecture smoke tests (deliverable f): reduced config of the same
family, one forward + one train step on CPU, assert output shapes + no NaNs;
plus prefill/decode == full-forward consistency for every cache layout."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get
from repro.models import forward, init_cache, init_params, lm_loss
from repro.serve.engine import ServingEngine
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


def _groups(caches):
    """The cache groups (dicts of leaves stacked over layers or
    applications), in tree order."""
    return list(caches["layers"]) + ([caches["shared"]] if "shared" in caches
                                     else [])


def _through_table(group, b, n):
    """(stack, n, nkv, hd) K and V of sequence b's first n tokens, read
    from the paged pool through its table."""
    table = np.asarray(group["table"])[:, b]                 # (stack, nblk)
    t = np.arange(n)
    bs = group["pool_k"].shape[3]
    st = np.arange(table.shape[0])[:, None]
    return tuple(np.asarray(group[k])[:, b][st, table[:, t // bs], t % bs]
                 for k in ("pool_k", "pool_v"))


@pytest.mark.parametrize("arch", ARCH_IDS)
@pytest.mark.parametrize("kv_backend", ["reference", "pallas_interpret"])
def test_decode_in_place_matches_the_dense_decode(arch, kv_backend):
    """Decode on every layer's paged caches in place gives the logits and
    caches of the dense-cache decode of the same tokens, for every block
    kind that rides the loop (attention, MoE, Mamba2, xLSTM, Zamba2's
    shared attention).  The pools are read through their tables up to each
    live sequence's position; a free sequence's (pos -1) pool rows and
    every table are those before the step.  A free sequence's recurrent
    state, fed by its unused hidden state, is left out."""
    cfg = get(arch, smoke=True).replace(param_dtype="float32",
                                        kv_block_size=8)
    params = init_params(KEY, cfg)
    B, S = 3, 16
    x, _ = _inputs(cfg, B, S, extra=1)
    pos = jnp.array([S, S - 3, -1], jnp.int32)
    dense = init_cache(cfg, B, 32, "dense")
    _, dense = forward(params, x[:, :S], cfg, mode="prefill", caches=dense)
    ref, ref_cache = forward(params, x[:, S:S + 1], cfg, mode="decode",
                             caches=dense, pos=pos)
    paged = init_cache(cfg, B, 32, "paged")
    _, paged = forward(params, x[:, :S], cfg, mode="prefill", caches=paged)
    got, got_cache = forward(params, x[:, S:S + 1], cfg, mode="decode",
                             caches=paged, pos=pos, kv_backend=kv_backend)
    np.testing.assert_allclose(np.asarray(got[:2], np.float32),
                               np.asarray(ref[:2], np.float32),
                               rtol=1e-4, atol=1e-4)
    close = functools.partial(np.testing.assert_allclose, rtol=1e-4,
                              atol=1e-4)
    for g, r, before in zip(*map(_groups, (got_cache, ref_cache, paged))):
        if "pool_k" not in g:            # recurrent state: (stack, B, ...)
            for k in g:
                close(np.asarray(g[k], np.float32)[:, :2],
                      np.asarray(r[k], np.float32)[:, :2])
            continue
        np.testing.assert_array_equal(np.asarray(g["table"]),
                                      np.asarray(before["table"]))
        for b in range(2):
            n = int(pos[b]) + 1
            k, v = _through_table(g, b, n)
            close(k, np.asarray(r["k"])[:, b, :n])
            close(v, np.asarray(r["v"])[:, b, :n])
        for k in ("pool_k", "pool_v"):
            np.testing.assert_array_equal(np.asarray(g[k])[:, 2],
                                          np.asarray(before[k])[:, 2])


def test_dense_decode_writes_nothing_for_a_free_sequence():
    """A free sequence (pos -1) leaves its dense cache as it was, its last
    row too: the write index is out of range and dropped, as the paged
    pool's is."""
    cfg = get("smollm_135m", smoke=True).replace(param_dtype="float32")
    params = init_params(KEY, cfg)
    B, S = 2, 16
    x, _ = _inputs(cfg, B, S, extra=1)
    cache = init_cache(cfg, B, 32, "dense")
    _, cache = forward(params, x[:, :S], cfg, mode="prefill", caches=cache)
    _, new = forward(params, x[:, S:S + 1], cfg, mode="decode", caches=cache,
                     pos=jnp.array([S, -1], jnp.int32))
    for a, b in zip(jax.tree.leaves(new), jax.tree.leaves(cache)):
        a, b = np.asarray(a), np.asarray(b)
        np.testing.assert_array_equal(a[:, 1], b[:, 1])
        assert np.any(a[:, 0, S] != 0)             # the live one's token
        np.testing.assert_array_equal(a[:, 0, S + 1:], b[:, 0, S + 1:])


def test_decode_without_a_backend_runs_in_place():
    """With no kv_backend, a paged decode runs in place on the ops module's
    default backend, the jnp oracle off a TPU: bit-equal logits and caches
    to kv_backend="reference", and a ServingEngine built without one
    serves what one built with "reference" serves."""
    assert jax.default_backend() != "tpu"
    cfg = get("granite_4_0_h_small", smoke=True).replace(
        param_dtype="float32", kv_block_size=8)
    params = init_params(KEY, cfg)
    B, S = 3, 16
    x, _ = _inputs(cfg, B, S, extra=1)
    cache = init_cache(cfg, B, 32, "paged")
    _, cache = forward(params, x[:, :S], cfg, mode="prefill", caches=cache)
    pos = jnp.array([S, S - 3, -1], jnp.int32)
    outs = [forward(params, x[:, S:S + 1], cfg, mode="decode", caches=cache,
                    pos=pos, count_routes=True, **kw)
            for kw in ({}, {"kv_backend": "reference"})]
    for a, b in zip(*map(jax.tree.leaves, outs)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def serve(**kw):
        eng = ServingEngine(cfg, params, max_slots=2, max_seq=128, seed=4,
                            **kw)
        reqs = [eng.submit(p, max_new=5) for p in ([5, 9, 2], [1, 2, 3, 4],
                                                   [7, 7])]
        eng.run_until_drained()
        return eng, [r.out for r in reqs]

    (default, served), (ref, ref_served) = serve(), serve(
        kv_backend="reference")
    assert default.kv_backend == "reference"
    assert served == ref_served
    for a, b in zip(jax.tree.leaves(default.caches),
                    jax.tree.leaves(ref.caches)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


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
