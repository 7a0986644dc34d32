"""The kv_compaction kernel at SmolLM-135M serving widths, compiled for a
described TPU v5e (nothing runs): the chip's compiler accepts the compiled
path `compact_kv_pool(backend="pallas")`, the program holds the Pallas
custom call, and it fits one chip's HBM.

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
