"""Serving engine: continuous batching correctness + Nezha cache GC."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get
from repro.core import trace
from repro.models import forward, init_params
from repro.serve.engine import ServingEngine, prefill_buckets

CFG = get("smollm_135m", smoke=True).replace(param_dtype="float32",
                                             kv_block_size=8)
PARAMS = init_params(jax.random.PRNGKey(0), CFG)


def ref_generate(prompt, n):
    toks = list(prompt)
    for _ in range(n + 1):
        logits, _ = forward(PARAMS, jnp.asarray([toks]), CFG, mode="train")
        toks.append(int(jnp.argmax(logits[0, -1])))
    return toks[len(prompt):]


def forced_greedy(cfg, params, req):
    """The full forward's greedy token after each prefix of the request's
    prompt and served tokens, in one teacher-forced call: equal to
    `req.out` when every served token is the greedy one."""
    seq = req.prompt + req.out[:-1]
    logits, _ = forward(params, jnp.asarray([seq]), cfg, mode="train")
    return np.asarray(jnp.argmax(logits[0, len(req.prompt) - 1:],
                                 axis=-1)).tolist()


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
    assert eng.kv_backend == "reference"           # JAX runs on the CPU


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


# the ladders of the benchmark's configurations (max_seq 2,048 and 3,584,
# blocks of 64), of smoke engines and of blocks longer than 128 tokens; each
# with the bucket a prompt of each length is padded to
@pytest.mark.parametrize("max_seq, block, ladder, padded", [
    (2048, 64, [128, 256, 512, 1024, 2048],
     {1: 128, 127: 128, 128: 128, 129: 256, 512: 512, 513: 1024,
      2048: 2048}),
    (3584, 64, [128, 256, 512, 1024, 2048, 3584],
     {1: 128, 127: 128, 128: 128, 129: 256, 512: 512, 513: 1024,
      2049: 3584, 3584: 3584}),
    (256, 8, [128, 256], {1: 128, 127: 128, 128: 128, 129: 256, 256: 256}),
    (32, 8, [32], {1: 32, 32: 32}),
    (1024, 256, [256, 512, 1024], {1: 256, 257: 512, 1024: 1024}),
])
def test_prefill_buckets(max_seq, block, ladder, padded):
    """Each bucket is whole KV blocks and whole SSD chunks (128, or the
    whole prefill where shorter), and above 512 whole attention query
    chunks; max_seq is the top one."""
    assert prefill_buckets(max_seq, block) == ladder
    for b in ladder:
        assert b % block == 0 and b % min(128, b) == 0
        assert b <= 512 or b % 512 == 0
    for n, want in padded.items():
        assert min(b for b in ladder if b >= n) == want


def test_admission_pads_to_the_bucket_and_compiles_once_a_bucket(
        monkeypatch):
    """serve.admit's padded_tokens is the prompt's bucket, and a second
    prompt in a bucket already used compiles no prefill."""
    t = trace.Tracer()
    monkeypatch.setattr(trace, "_ACTIVE", t)
    eng = ServingEngine(CFG, PARAMS, max_slots=2, max_seq=512, seed=7)
    rng = np.random.default_rng(7)
    compiled = []
    for n in (1, 127, 128, 129, 256, 257, 500):
        eng.submit(rng.integers(0, CFG.vocab_size, n).tolist(), max_new=2)
        eng.run_until_drained()
        compiled.append(eng._prefill._cache_size())
    admits = [s for s in t.spans if s.name == "serve.admit"]
    assert [(s.tags["prompt_tokens"], s.tags["padded_tokens"])
            for s in admits] == [(1, 128), (127, 128), (128, 128),
                                 (129, 256), (256, 256), (257, 512),
                                 (500, 512)]
    assert compiled == [1, 1, 1, 2, 2, 3, 3]


def admit_both_ways(eng, prompt):
    """Admit `prompt` through the engine, at its bucket, and prefill the
    same slot from the same fresh state and block table padded to max_seq,
    by a plain call of the engine's prefill.  Returns the request, the
    slot's caches after admission, and the max_seq prefill's first token,
    slot caches and route counts (an MoE model's, else None)."""
    req = eng.submit(prompt, max_new=2)
    eng._admit()
    slot = eng._slot_cache(req.slot)
    fresh = jax.tree.map(
        lambda a: a if a.dtype == jnp.int32 else jnp.zeros_like(a), slot)
    n = len(prompt)
    toks = np.zeros((1, eng.max_seq), np.int32)
    toks[0, :n] = prompt
    kw = {"lengths": jnp.asarray([n], jnp.int32)} if eng._lengths else {}
    logits, want, *counts = eng._prefill(eng.params, fresh,
                                         jnp.asarray(toks), **kw)
    return (req, jax.tree.map(np.asarray, slot),
            int(np.argmax(np.asarray(logits)[0, n - 1])),
            jax.tree.map(np.asarray, want),
            int(np.asarray(counts[0])[0]) if counts else None)


def prompt_kv(group, n, bs):
    """Each layer's K and V of a one-slot attention cache group's first
    n positions, read through its block table."""
    blocks = group["table"][:, 0, :-(-n // bs)]             # (layers, nb)
    layers = np.arange(blocks.shape[0])[:, None]
    return [group[name][layers, 0, blocks].reshape(
        blocks.shape[0], -1, *group[name].shape[-2:])[:, :n]
        for name in ("pool_k", "pool_v")]


QWEN3 = get("qwen3_8b", smoke=True).replace(param_dtype="float32")


def test_a_bucketed_admission_equals_one_padded_to_max_seq():
    """A Qwen3-shaped model: prompts in two buckets below max_seq (1,024:
    two attention query chunks) leave their slot's first token and K/V up
    to the prompt's length as a prefill padded to max_seq does."""
    eng = ServingEngine(QWEN3, init_params(jax.random.PRNGKey(1), QWEN3),
                        max_slots=2, max_seq=1024, seed=8)
    rng = np.random.default_rng(8)
    for n in (100, 300):                            # buckets 128 and 512
        req, got, first, want, _ = admit_both_ways(
            eng, rng.integers(0, QWEN3.vocab_size, n).tolist())
        assert req.out == [first]
        for g, w in zip(got["layers"], want["layers"]):
            for a, b in zip(prompt_kv(g, n, QWEN3.kv_block_size),
                            prompt_kv(w, n, QWEN3.kv_block_size)):
                np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


def serve_staggered(eng, compact_after=None):
    """Five requests on the engine's slots: three at once, two more after
    two steps (admissions mid-stream and, on few slots, slot reuse), a
    compaction after `compact_after` steps; after every step, each
    finished request holds all its tokens, an active one not more, and
    an engine with none active has nothing in flight.  Returns the
    requests once drained."""
    prompts = ([5, 9, 2, 7], [1, 2, 3], [11, 4, 6, 8, 10], [3, 3, 3],
               [9, 1])
    lengths = (6, 3, 8, 5, 4)
    reqs = [eng.submit(p, max_new=m)
            for p, m in zip(prompts[:3], lengths[:3])]
    steps = 0
    while eng.active or eng.queue or steps < 2:
        eng.step()
        steps += 1
        if steps == 2:
            reqs += [eng.submit(p, max_new=m)
                     for p, m in zip(prompts[3:], lengths[3:])]
        if steps == compact_after:
            eng.compact(backend="reference")
        assert all(r.done and len(r.out) == r.max_new + 1
                   for r in eng.finished)
        assert all(len(r.out) <= r.max_new for r in eng.active.values())
        assert eng.active or eng._inflight is None
    assert len(eng.finished) == len(reqs)
    return reqs


def test_the_next_decode_is_dispatched_before_the_last_ones_tokens_are_fetched():
    """Every decode but the last is followed by the next one's dispatch
    before its own tokens are fetched, across admissions mid-stream and
    slot reuse (three slots: some slot always has a token left to decode
    until the end); each decode's tokens are fetched once, in order."""
    eng = ServingEngine(CFG, PARAMS, max_slots=3, max_seq=64, seed=6)
    dispatched, events = [], []
    decode_next, land = eng._decode_next, eng._land

    def dispatch(*a):
        out = decode_next(*a)
        dispatched.append(out[0])
        events.append(("dispatch", len(dispatched) - 1))
        return out

    def fetch(nxt, *a):
        k = next(i for i, x in enumerate(dispatched) if x is nxt)
        events.append(("fetch", k))
        return land(nxt, *a)

    eng._decode_next, eng._land = dispatch, fetch
    serve_staggered(eng)
    n = len(dispatched)
    assert [k for e, k in events if e == "fetch"] == list(range(n))
    for k in range(n - 1):
        assert events.index(("dispatch", k + 1)) < \
            events.index(("fetch", k)), events
    assert events[-1] == ("fetch", n - 1)


def test_finished_requests_hold_all_their_tokens_after_every_step():
    """On two slots (three of five requests wait for a reused slot), with
    a compaction midway: see serve_staggered's checks after each step."""
    eng = ServingEngine(CFG, PARAMS, max_slots=2, max_seq=64, seed=7,
                        scramble_blocks=True)
    reqs = serve_staggered(eng, compact_after=4)
    assert eng.compactions == 1
    for r in reqs:
        assert r.out == forced_greedy(CFG, PARAMS, r), r.rid


@pytest.mark.parametrize("backend", ["reference", "pallas_interpret"])
def test_served_tokens_are_the_greedy_tokens_qwen3(backend):
    """A Qwen3-shaped model, one step in flight: every served token,
    the last included, is the full forward's greedy token."""
    params = init_params(jax.random.PRNGKey(2), QWEN3)
    eng = ServingEngine(QWEN3, params, max_slots=3, max_seq=64, seed=9,
                        kv_backend=backend)
    for r in serve_staggered(eng, compact_after=3):
        assert r.out == forced_greedy(QWEN3, params, r), r.rid
