"""The serving path's kernels compiled for a described TPU v5e (nothing
runs): the kv_compaction kernel at SmolLM-135M serving widths (the chip's
compiler accepts `compact_kv_pool(backend="pallas")`, the program holds the
Pallas custom call, and it fits one chip's HBM), and the paged decode step
at the serving cells' attention widths, in place: Qwen3-8B's, and
granite-4.0-h's, whose Mamba-2 state is updated in place too.

The topology is described only inside fixtures: describing it loads the TPU
compiler library, which one process at a time may hold."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get
from repro.kernels.kv_compaction.ops import compact_kv_pool
from repro.models import forward, init_cache, init_params

CFG = get("smollm_135m")
SLOTS, MAX_SEQ = 32, 2048          # chip_smoke.py's ServingEngine widths
V5E_HBM_BYTES = 16 * 10 ** 9       # "16 GB of HBM", Google Cloud TPU v5e


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    # a compile for an absent chip can be written to the persistent cache
    # but never read back: keep the cache out of these compiles
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler library to describe it
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", was_on)
        cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def pool(one_chip):
    """ServingEngine's flattened K (or V) pool: (layers x slots, blocks,
    block size, kv heads x head dim) in bf16."""
    bs = CFG.kv_block_size
    return jax.ShapeDtypeStruct(
        (CFG.n_layers * SLOTS, MAX_SEQ // bs, bs, CFG.n_kv_heads * CFG.hd),
        jnp.bfloat16, sharding=one_chip)


def _table(kind, n, nblk):
    if kind == "identity":
        return np.tile(np.arange(nblk, dtype=np.int32), (n, 1))
    rng = np.random.default_rng(0)
    return np.stack([rng.permutation(nblk) for _ in range(n)]).astype(
        np.int32)


def _assert_fits_and_uses_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()
    m = compiled.memory_analysis()
    used = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert 0 < used < V5E_HBM_BYTES, used


@pytest.mark.parametrize("kind", ["scrambled", "identity"])
def test_compaction_compiles_for_v5e_with_table(pool, kind):
    """The table's values are compiled in as a constant."""
    table = _table(kind, pool.shape[0], pool.shape[1])

    def compact(p):
        return compact_kv_pool(p, jnp.asarray(table), backend="pallas")

    _assert_fits_and_uses_kernel(jax.jit(compact).lower(pool).compile())


def test_compaction_compiles_for_v5e_with_runtime_table(pool, one_chip):
    """The serving engine's call: the table arrives at run time."""
    table = jax.ShapeDtypeStruct(pool.shape[:2], jnp.int32,
                                 sharding=one_chip)
    compiled = compact_kv_pool.lower(pool, table, backend="pallas").compile()
    _assert_fits_and_uses_kernel(compiled)


# the serving cell's attention widths (Qwen3-8B: 32 query heads over 8 KV
# heads of 128, blocks of 64) in 2 layers, 32 slots of 2,048 tokens
SERVE_CFG = get("qwen3_8b").replace(n_layers=2, vocab_size=37984,
                                    kv_block_size=64)
LAYER_POOL_BYTES = (SLOTS * MAX_SEQ * SERVE_CFG.n_kv_heads * SERVE_CFG.hd
                    * 2)                # one layer's K pool in bf16: 134 MB


def _decode_step(one_chip):
    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), SERVE_CFG)))
    caches = on_chip(jax.eval_shape(
        lambda: init_cache(SERVE_CFG, SLOTS, MAX_SEQ, "paged")))

    def step(params, caches, last, first, pos):     # ServingEngine's
        tokens = jnp.where(first >= 0, first, last)[:, None]   # decode_fn
        logits, caches = forward(params, tokens, SERVE_CFG, None,
                                 mode="decode", caches=caches, pos=pos,
                                 kv_backend="pallas")
        return jnp.argmax(logits[:, -1], axis=-1), caches

    i32 = jax.ShapeDtypeStruct((SLOTS,), jnp.int32, sharding=one_chip)
    return jax.jit(step, donate_argnums=(1,)).lower(params, caches, i32, i32,
                                                   i32).compile()


def test_paged_decode_step_compiles_in_place_for_v5e(one_chip):
    """The engine's decode step on the chip: the paged-attention kernel is
    in it, and its temporaries stay under one layer's K pool (a step that
    gathered the whole pool every layer needed 1,646,139,904 B of them)."""
    compiled = _decode_step(one_chip)
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "paged_decode_attention" in text
    assert compiled.memory_analysis().temp_size_in_bytes < LAYER_POOL_BYTES


# granite-4.0-h at its published widths, one Mamba-2 + MoE layer and the
# attention + MoE layer, 64 slots of 3,584 tokens, 9 of 72 experts held
GRANITE = get("granite_4_0_h_small").replace(
    n_layers=2, block_pattern=("mamba2_moe", "attn_moe"), vocab_size=25088,
    experts_held=9)
GRANITE_SLOTS, GRANITE_SEQ = 64, 3584
LAYER_STATE_BYTES = GRANITE_SLOTS * 128 * 64 * 128 * 4    # 268 MB of fp32


def test_granite_decode_step_updates_its_state_in_place_for_v5e(one_chip):
    """The engine's decode step of the hybrid, with its route counts: the
    paged-attention kernel is in it, and its temporaries stay under one
    layer's SSM state (the caches are donated and aliased)."""
    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), GRANITE)))
    caches = on_chip(jax.eval_shape(
        lambda: init_cache(GRANITE, GRANITE_SLOTS, GRANITE_SEQ, "paged")))

    def step(params, caches, last, first, pos):     # ServingEngine's
        tokens = jnp.where(first >= 0, first, last)[:, None]   # decode_fn
        out = forward(params, tokens, GRANITE, None, mode="decode",
                      caches=caches, pos=pos, kv_backend="pallas",
                      count_routes=True)
        return (jnp.argmax(out[0][:, -1], axis=-1),) + out[1:]

    i32 = jax.ShapeDtypeStruct((GRANITE_SLOTS,), jnp.int32, sharding=one_chip)
    compiled = jax.jit(step, donate_argnums=(1,)).lower(
        params, caches, i32, i32, i32).compile()
    assert "paged_decode_attention" in compiled.as_text()
    m = compiled.memory_analysis()
    assert m.temp_size_in_bytes < LAYER_STATE_BYTES
    assert m.alias_size_in_bytes >= sum(              # tiles may pad
        a.size * a.dtype.itemsize for a in jax.tree.leaves(caches))
