"""Serving engine: continuous batching correctness + Nezha cache GC."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get
from repro.models import forward, init_params
from repro.serve.engine import ServingEngine

CFG = get("smollm_135m", smoke=True).replace(param_dtype="float32",
                                             kv_block_size=8)
PARAMS = init_params(jax.random.PRNGKey(0), CFG)


def ref_generate(prompt, n):
    toks = list(prompt)
    for _ in range(n + 1):
        logits, _ = forward(PARAMS, jnp.asarray([toks]), CFG, mode="train")
        toks.append(int(jnp.argmax(logits[0, -1])))
    return toks[len(prompt):]


def test_continuous_batching_matches_reference():
    eng = ServingEngine(CFG, PARAMS, max_slots=3, max_seq=64, seed=0,
                        scramble_blocks=True)
    prompts = [[5, 9, 2, 7], [1, 2, 3], [11, 4, 6, 8, 10], [3, 3, 3], [9, 1]]
    reqs = [eng.submit(p, max_new=6) for p in prompts]
    total = eng.run_until_drained()
    assert len(eng.finished) == len(prompts)
    assert total >= sum(r.max_new for r in reqs)
    for r in eng.finished:
        exp = ref_generate(r.prompt, r.max_new)
        assert r.out[:r.max_new] == exp[:r.max_new], (r.rid, r.out, exp)


def test_fragmentation_and_compaction():
    eng = ServingEngine(CFG, PARAMS, max_slots=2, max_seq=64, seed=1,
                        scramble_blocks=True)
    for i in range(4):
        eng.submit([1 + i, 2, 3], max_new=4)
    eng.run_until_drained()
    assert eng.fragmentation() > 0.3          # scrambled tables
    eng.compact(backend="reference")
    assert eng.fragmentation() == 0.0         # identity layout restored
    # correctness preserved after compaction
    r = eng.submit([5, 9, 2, 7], max_new=5)
    eng.run_until_drained()
    assert r.out[:5] == ref_generate([5, 9, 2, 7], 5)[:5]


def test_compaction_with_pallas_interpret_kernel():
    eng = ServingEngine(CFG, PARAMS, max_slots=2, max_seq=32, seed=2,
                        scramble_blocks=True)
    eng.submit([4, 2], max_new=3)
    eng.run_until_drained()
    eng.compact(backend="pallas_interpret")   # the actual GC kernel
    assert eng.fragmentation() == 0.0
    r = eng.submit([4, 2], max_new=3)
    eng.run_until_drained()
    assert r.out[:3] == ref_generate([4, 2], 3)[:3]


def test_mid_stream_admission():
    """A request admitted while another is mid-decode must not corrupt it."""
    eng = ServingEngine(CFG, PARAMS, max_slots=2, max_seq=64, seed=3,
                        scramble_blocks=True)
    r1 = eng.submit([7, 7, 7], max_new=8)
    for _ in range(3):
        eng.step()
    r2 = eng.submit([1, 2, 3, 4], max_new=4)
    eng.run_until_drained()
    assert r1.out[:8] == ref_generate([7, 7, 7], 8)[:8]
    assert r2.out[:4] == ref_generate([1, 2, 3, 4], 4)[:4]


def test_the_engine_picks_the_kernel_from_where_its_pool_lives():
    eng = ServingEngine(CFG, PARAMS, max_slots=2, max_seq=32, seed=0)
    assert eng.kv_backend == "reference"           # the pool is on the CPU


def test_pallas_interpret_engine_matches_the_reference_engine():
    """The decode step's kernels, interpreted, serve the same tokens as the
    jnp oracles, across admissions mid-stream and a compaction."""
    def serve(backend):
        eng = ServingEngine(CFG, PARAMS, max_slots=3, max_seq=64, seed=5,
                            scramble_blocks=True, kv_backend=backend)
        reqs = [eng.submit(p, max_new=8) for p in ([5, 9, 2, 7], [1, 2, 3])]
        for _ in range(3):
            eng.step()
        reqs += [eng.submit(p, max_new=6)
                 for p in ([11, 4, 6, 8, 10], [3, 3, 3])]
        for _ in range(2):
            eng.step()
        eng.compact(backend="reference")
        eng.run_until_drained()
        return [r.out for r in reqs]

    assert serve("pallas_interpret") == serve("reference")


@pytest.mark.parametrize("backend", ["reference", "pallas_interpret"])
def test_a_decode_step_writes_only_the_active_rows(backend):
    """One decode step changes each layer's pool at the active sequence's
    new-token row and nowhere else: a free slot writes nothing, also one
    whose stale pos is max_seq."""
    eng = ServingEngine(CFG, PARAMS, max_slots=3, max_seq=32, seed=4,
                        kv_backend=backend)
    eng.submit(list(range(1, 31)), max_new=2)      # ends with pos == max_seq
    live = eng.submit([3, 1, 4], max_new=10)
    while not eng.finished:
        eng.step()
    assert eng.max_seq in [eng.pos[s] for s in eng.free_slots]
    # a constant pool: any write shows
    eng.caches = jax.tree.map(
        lambda a: jnp.full_like(a, 1e4) if a.dtype != jnp.int32 else a,
        eng.caches)
    before = jax.tree.map(np.asarray, eng.caches)
    pos, bs = int(eng.pos[live.slot]), CFG.kv_block_size
    eng.step()
    for old, new in zip(before["layers"], eng.caches["layers"]):
        blk = old["table"][:, live.slot, pos // bs]
        want = {(l, live.slot, int(blk[l]), pos % bs)
                for l in range(len(blk))}
        for name in ("pool_k", "pool_v"):
            changed = np.argwhere(np.any(old[name] != np.asarray(new[name]),
                                         axis=(-2, -1)))
            assert {tuple(int(i) for i in c) for c in changed} == want
